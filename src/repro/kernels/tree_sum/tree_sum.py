"""Pallas TPU kernel: per-block Gram matrices for tree construction (Alg. 3).

The leaf level of the flat sample tree stores, for every block of ``block``
consecutive items, the matrix  Σ_n = Z_n^T Z_n  (R x R).  On TPU this is one
(R, block) x (block, R) MXU matmul per grid step with the Z tile read from
HBM exactly once.  Upper tree levels are pairwise sums of these outputs
(done by the caller; they touch (M/block) * R^2 bytes, negligible).

Grid: (n_blocks,).  W is viewed as (n_blocks, block, R) so each step's
(block, R) tile spans the array's last two dims whole — legal for the TPU
tiling rule at any ``block`` (the leaf block of a tiny tree may be 2).
R is lane-padded to 128 by the ops.py wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _tree_sum_kernel(z_ref, out_ref):
    z = z_ref[0]  # (block, R) VMEM
    zf = z.astype(jnp.float32)
    out_ref[...] = jnp.dot(zf.T, zf, preferred_element_type=jnp.float32)[None]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def block_outer_sums_pallas(
    W: jax.Array, *, block: int, interpret: bool = False
) -> jax.Array:
    m, r = W.shape
    assert m % block == 0
    n = m // block
    return pl.pallas_call(
        _tree_sum_kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, block, r), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, r, r), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, r, r), jnp.float32),
        interpret=interpret,
        name="ndpp_block_outer_sums",
    )(W.reshape(n, block, r))


def _gathered_gram_kernel(blk_ref, w_ref, out_ref):
    # blk_ref is the scalar-prefetch block-id vector; the index_map already
    # used it to DMA exactly the touched (block, R) tile of W into VMEM, so
    # the body is the same single MXU Gram as the full construction kernel —
    # recomputed blocks are bit-equal to a from-scratch build.
    z = w_ref[0]
    zf = z.astype(jnp.float32)
    out_ref[...] = jnp.dot(zf.T, zf, preferred_element_type=jnp.float32)[None]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gathered_block_grams_pallas(
    W: jax.Array, blks: jax.Array, *, block: int, interpret: bool = False
) -> jax.Array:
    """Grams of the leaf blocks named by ``blks`` (nb,) only: grid (nb,),
    each step gathers its block of W by scalar-prefetched index and runs one
    (R, block) x (block, R) MXU matmul — the batched-row-update hot path of
    ``core.tree.update_rows`` (one launch per update batch)."""
    from jax.experimental.pallas import tpu as pltpu

    m, r = W.shape
    assert m % block == 0
    nb = blks.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, block, r),
                               lambda i, blk_ref: (blk_ref[i], 0, 0))],
        out_specs=pl.BlockSpec((1, r, r), lambda i, blk_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _gathered_gram_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, r, r), jnp.float32),
        interpret=interpret,
        name="ndpp_gathered_block_grams",
    )(blks.astype(jnp.int32), W.reshape(m // block, block, r))
