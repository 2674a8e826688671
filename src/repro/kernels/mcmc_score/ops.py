"""jit'd public wrapper for the MCMC all-candidate scorer.

Pads to TPU-aligned shapes (rows to block_m, feature dim to a multiple of
128 lanes).  Where it runs is ``kernels.backend``'s rule: the kernel on
TPU, the einsum oracle elsewhere, the interpreter under
``force_interpret`` / ``REPRO_PALLAS_INTERPRET=1``.  Per-chain candidate
*rows* (instead of the shared ground set) are the
``kernels.bilinear.ops.bilinear_batched`` layout — use that op directly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..backend import interpret_requested, on_tpu
from .mcmc_score import score_all_pallas
from .ref import score_all_ref


def score_all(
    Z: jax.Array, A: jax.Array, *, block_m: int = 512,
    force_interpret: bool = False,
) -> jax.Array:
    """s_{c,m} = z_m^T A_c z_m for every item m and chain c.

    Z: (M, R) ground-set features, A: (C, R, R) per-chain score matrices
    -> (C, M) float32 move scores (add ratios, or swap ratios when A is a
    swap score matrix)."""
    interpret = interpret_requested(force_interpret)
    if not (on_tpu() or interpret):
        return score_all_ref(Z, A)
    m, r = Z.shape
    r_pad = (-r) % 128
    m_blk = min(block_m, max(8, 1 << (m - 1).bit_length()))
    m_pad = (-m) % m_blk
    zp = jnp.pad(Z, ((0, m_pad), (0, r_pad)))
    ap = jnp.pad(A, ((0, 0), (0, r_pad), (0, r_pad)))
    out = score_all_pallas(zp, ap, block_m=m_blk, interpret=interpret)
    return out[:, :m]


def score_all_sharded(
    Z: jax.Array, A: jax.Array, mesh: Mesh, *, block_m: int = 512,
    force_interpret: bool = False,
) -> jax.Array:
    """``score_all`` over a device mesh: each shard scores only its local
    (M/S, R) row block of the catalog (Pallas kernel on TPU, einsum ref
    elsewhere — per-row arithmetic is M-independent, so the values are
    bit-identical to the unsharded scorer).  Returns the (C, M) scores
    sharded along M over the mesh "model" axis; rows never leave their
    device.  Requires M divisible by the mesh "model" extent."""
    s = int(mesh.shape["model"])
    if Z.shape[0] % s != 0:
        raise ValueError(f"the mesh 'model' extent {s} must divide "
                         f"M={Z.shape[0]}")

    def inner(zl, a):
        return score_all(zl, a, block_m=block_m,
                         force_interpret=force_interpret)

    f = jax.shard_map(inner, mesh=mesh, in_specs=(P("model", None), P(None)),
                      out_specs=P(None, "model"), check_vma=False)
    return f(Z, A)


def score_argmax_sharded(
    Z: jax.Array, A: jax.Array, mesh: Mesh, *, block_m: int = 512,
    force_interpret: bool = False,
):
    """Best candidate per chain without materializing (C, M) anywhere
    replicated: each shard scores its local rows and reduces them to one
    (C,) winner; only the (S, C) per-shard winning scores/indices are
    all-gathered and argmax'd.  Returns (scores (C,), items (C,)) with
    global item indices — the greedy/MAP pick at O(C) cross-shard traffic.
    """
    s = int(mesh.shape["model"])
    if Z.shape[0] % s != 0:
        raise ValueError(f"the mesh 'model' extent {s} must divide "
                         f"M={Z.shape[0]}")

    def inner(zl, a):
        sc = score_all(zl, a, block_m=block_m,
                       force_interpret=force_interpret)    # (C, M_loc)
        base = jax.lax.axis_index("model") * zl.shape[0]
        loc_max = sc.max(axis=1)
        loc_arg = sc.argmax(axis=1).astype(jnp.int32) + base
        all_max = jax.lax.all_gather(loc_max, "model")     # (S, C)
        all_arg = jax.lax.all_gather(loc_arg, "model")
        win = all_max.argmax(axis=0)                       # (C,)
        c = jnp.arange(all_max.shape[1], dtype=jnp.int32)
        return all_max[win, c], all_arg[win, c]

    f = jax.shard_map(inner, mesh=mesh, in_specs=(P("model", None), P(None)),
                      out_specs=(P(None), P(None)), check_vma=False)
    return f(Z, A)
