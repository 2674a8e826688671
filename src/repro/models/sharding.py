"""Logical-axis sharding rules (GSPMD / pjit).

Every parameter is created with a tuple of *logical* axis names; the rules
below map them to mesh axes.  One rule table serves both the single-pod
(data, model) mesh and the multi-pod (pod, data, model) mesh: the data-
parallel group is ("pod", "data") when a pod axis exists.

TP axes ("heads", "kv_heads", "ff", "experts", "vocab") map to "model" only
when the dimension is divisible by the mesh extent — otherwise the axis is
replicated (MaxText-style fallback; attention-head counts like 15/24/28/40
do not divide 16).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axes that map onto the tensor-parallel ("model") mesh axis
_MODEL_AXES = {"heads", "kv_heads", "ff", "experts", "vocab", "items"}
# logical axes that map onto the (pod x) data axis
_DATA_AXES = {"batch", "fsdp"}


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_extent(mesh: Mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    e = 1
    for n in names:
        e *= mesh.shape[n]
    return e


def logical_to_spec(
    mesh: Mesh, axes: Tuple[Optional[str], ...], dims: Tuple[int, ...]
) -> P:
    """Map logical axes -> PartitionSpec, dropping non-divisible shardings."""
    assert len(axes) == len(dims), (axes, dims)
    out = []
    used = set()
    for ax, dim in zip(axes, dims):
        if ax is None:
            out.append(None)
            continue
        if ax in _MODEL_AXES:
            tgt: Tuple[str, ...] = ("model",)
        elif ax in _DATA_AXES:
            tgt = data_axes(mesh)
        elif ax == "seq_model":
            tgt = ("model",)
        else:
            out.append(None)
            continue
        tgt = tuple(t for t in tgt if t not in used)
        if not tgt or dim % mesh_extent(mesh, tgt) != 0:
            out.append(None)
            continue
        used.update(tgt)
        # data-parallel groups stay tuples (("pod", "data") or ("data",)):
        # the group is one sharding unit even when the pod axis is absent
        out.append(tgt if ax in _DATA_AXES else (tgt[0] if len(tgt) == 1 else tgt))
    return P(*out)


def named(mesh: Mesh, axes, dims) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(mesh, axes, dims))


def constrain(x: jax.Array, mesh, axes: Tuple[Optional[str], ...]):
    """with_sharding_constraint by logical axes (no-op off-mesh)."""
    if mesh is None or getattr(mesh, "empty", True):
        return x
    spec = logical_to_spec(mesh, axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# Cross-shard row gathers (sampler item-axis sharding).
#
# The NDPP samplers shard the catalog ("items") axis of (M, R) matrices over
# the mesh "model" axis.  Subsets are tiny (<= 2K items), so gathering their
# feature rows is a masked local lookup + psum: exactly one shard owns each
# row, every other shard contributes exact floating-point zeros, and x + 0.0
# is exact — the gathered rows are bit-identical to an unsharded gather.
# --------------------------------------------------------------------------


def model_extent(mesh: Mesh) -> int:
    """Size of the mesh "model" axis; raises a clear error when the mesh
    has no such axis (the sampler sharding entry points require one —
    see ``repro.launch.mesh.make_sampler_mesh``)."""
    if "model" not in mesh.axis_names:
        raise ValueError(
            f"mesh {mesh} has no 'model' axis; build sampler meshes with "
            f"make_sampler_mesh (1-D ('model',) axis)")
    return mesh_extent(mesh, ("model",))


def shard_offset(n_local: int, axis_name: str) -> jax.Array:
    """First global row index owned by this shard of an evenly-split axis."""
    return jax.lax.axis_index(axis_name) * n_local


def gather_row(Z: jax.Array, j: jax.Array, axis_name: Optional[str] = None) -> jax.Array:
    """Row ``Z[j]`` of a (possibly row-sharded) (M, R) matrix.

    ``j``: scalar (or batched (N,)) global row index.  With ``axis_name``
    set, ``Z`` is the *local* (M/S, R) block inside a ``shard_map`` and the
    row is fetched from its owner by masked-psum; otherwise a plain gather.
    """
    if axis_name is None:
        return Z[j]
    rps = Z.shape[0]
    off = shard_offset(rps, axis_name)
    own = (j >= off) & (j < off + rps)
    loc = jnp.clip(j - off, 0, rps - 1)
    return jax.lax.psum(
        jnp.where(own[..., None], Z[loc], 0.0).astype(Z.dtype), axis_name)


def gather_rows(
    Z: jax.Array, items: jax.Array, mask: jax.Array,
    axis_name: Optional[str] = None,
) -> jax.Array:
    """Masked subset rows ``Z[items] * mask`` with padding rows zeroed.

    ``items``: (..., k_pad) global indices (-1 on padding slots), ``mask``:
    (..., k_pad) validity.  Returns (..., k_pad, R).  Bit-identical between
    the plain gather and the sharded masked-psum path (see module comment).
    """
    if axis_name is None:
        return Z[jnp.maximum(items, 0)] * mask[..., None].astype(Z.dtype)
    rps = Z.shape[0]
    off = shard_offset(rps, axis_name)
    own = (items >= off) & (items < off + rps) & mask
    loc = jnp.clip(items - off, 0, rps - 1)
    return jax.lax.psum(Z[loc] * own[..., None].astype(Z.dtype), axis_name)


def scatter_rows(
    Z: jax.Array, idx: jax.Array, rows: jax.Array,
    axis_name: Optional[str] = None,
) -> jax.Array:
    """Write ``rows`` into ``Z[idx]`` on the shard owning each row.

    The dual of ``gather_row``: with ``axis_name`` set, ``Z`` is the local
    (M/S, R) block inside a ``shard_map`` and each update is routed to its
    owner — non-owned updates are mapped to a positive out-of-bounds index
    and dropped, so no cross-shard traffic and no masked read-modify-write
    is needed.  ``idx`` must be unique.  Used by the dynamic catalog to
    keep streaming row updates device-local (``serve.catalog``).
    """
    if axis_name is None:
        return Z.at[idx].set(rows)
    rps = Z.shape[0]
    off = shard_offset(rps, axis_name)
    own = (idx >= off) & (idx < off + rps)
    return Z.at[jnp.where(own, idx - off, rps)].set(rows, mode="drop")


def scatter_rows_sharded(
    Z: jax.Array, idx: jax.Array, rows: jax.Array, mesh: Mesh
) -> jax.Array:
    """``scatter_rows`` over a mesh: keeps the (M, R) rows device-local
    while every shard applies only the updates it owns.  Falls back to a
    plain functional scatter when Z does not divide the mesh."""
    spec = logical_to_spec(mesh, ("items", None), Z.shape)
    if model_extent(mesh) == 1 or spec == P(None, None) or spec[0] is None:
        return Z.at[idx].set(rows)

    def inner(z_loc, idx, rows):
        return scatter_rows(z_loc, idx, rows, axis_name="model")

    f = jax.shard_map(inner, mesh=mesh,
                      in_specs=(spec, P(None), P(None, None)),
                      out_specs=spec, check_vma=False)
    return f(Z, idx, rows)


def specs_for_params(mesh: Mesh, logical_tree, shape_tree):
    """Map a pytree of logical-axis tuples + shapes -> PartitionSpecs."""
    return jax.tree.map(
        lambda axes, shp: logical_to_spec(mesh, axes, shp),
        logical_tree,
        shape_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(a, (str, type(None))) for a in x),
    )
