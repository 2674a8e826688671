"""jit'd public wrapper for the bilinear Pallas kernel.

``bilinear`` pads to TPU-aligned shapes (rows to block_m, feature dim to
a multiple of 128 lanes); ``bilinear_batched`` needs no padding (its
blocks span whole trailing dims).  Where each op runs is ``kernels.backend``'s
rule: the kernel on TPU, the jnp oracle elsewhere, the interpreter under
``force_interpret`` / ``REPRO_PALLAS_INTERPRET=1``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..backend import interpret_requested, on_tpu
from .bilinear import bilinear_batched_pallas, bilinear_pallas
from .ref import bilinear_batched_ref, bilinear_ref


def bilinear(
    Z: jax.Array, W: jax.Array, *, block_m: int = 512, force_interpret: bool = False
) -> jax.Array:
    """p_i = z_i^T W z_i for all rows of Z, fused single-pass over Z."""
    interpret = interpret_requested(force_interpret)
    if not (on_tpu() or interpret):
        return bilinear_ref(Z, W)
    m, r = Z.shape
    r_pad = (-r) % 128
    m_blk = min(block_m, max(8, 1 << (m - 1).bit_length()))
    m_pad = (-m) % m_blk
    zp = jnp.pad(Z, ((0, m_pad), (0, r_pad)))
    wp = jnp.pad(W, ((0, r_pad), (0, r_pad)))
    out = bilinear_pallas(zp, wp, block_m=m_blk, interpret=interpret)
    return out[:m]


def bilinear_sharded(
    Z: jax.Array, W: jax.Array, mesh: Mesh, *, block_m: int = 512,
    force_interpret: bool = False,
) -> jax.Array:
    """``bilinear`` over a device mesh: every shard scores only its local
    (M/S, R) rows against the replicated (R, R) inner matrix — bit-identical
    values to the unsharded op, with the (M, R) rows kept device-local.
    Returns the (M,) scores sharded over the mesh "model" axis.  Requires M
    divisible by the mesh "model" extent."""
    s = int(mesh.shape["model"])
    if Z.shape[0] % s != 0:
        raise ValueError(f"the mesh 'model' extent {s} must divide "
                         f"M={Z.shape[0]}")

    def inner(zl, w):
        return bilinear(zl, w, block_m=block_m,
                        force_interpret=force_interpret)

    f = jax.shard_map(inner, mesh=mesh, in_specs=(P("model", None), P(None)),
                      out_specs=P("model"), check_vma=False)
    return f(Z, W)


def bilinear_batched(
    Z: jax.Array, W: jax.Array, *, force_interpret: bool = False
) -> jax.Array:
    """p_{n,b} = z_{n,b}^T W_n z_{n,b}: one (B, R) row block and one (R, R)
    inner matrix per batch element, fused in a single kernel over the batch.
    No padding: each block spans its array's last two dims whole."""
    interpret = interpret_requested(force_interpret)
    if not (on_tpu() or interpret):
        return bilinear_batched_ref(Z, W)
    return bilinear_batched_pallas(Z, W, interpret=interpret)
