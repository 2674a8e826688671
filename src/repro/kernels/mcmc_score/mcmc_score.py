"""Pallas TPU kernel: all-candidate MCMC move scores.

Shape: one ground set Z (M, R) shared by every chain, one (R, R) score
matrix per chain — s_{c,m} = z_m^T A_c z_m.  This differs from
``kernels/bilinear`` in both directions: ``bilinear`` shares one W across
all rows, ``bilinear_batched`` gives every batch element its own rows AND
its own matrix.  Here the (M, R) row block is reused C times, so the fused
kernel streams each Z tile into VMEM once per chain column-block and keeps
the chain's A resident — the proposal scorer for C chains is C tiled
matmuls in one launch instead of a per-item (or per-chain) host loop.

Grid: (C, M / BLK_M).  The Z tile index map ignores the chain axis, so
revisits of the same tile hit the pipeline's VMEM copy when C > 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _score_all_kernel(z_ref, a_ref, out_ref):
    z = z_ref[...]            # (BLK_M, R) VMEM
    a = a_ref[0]              # (R, R)     VMEM, resident per chain
    za = jnp.dot(z, a, preferred_element_type=jnp.float32)  # MXU
    out_ref[0] = jnp.sum(za * z.astype(jnp.float32), axis=1)[None, :]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def score_all_pallas(
    Z: jax.Array, A: jax.Array, *, block_m: int = 512, interpret: bool = False
) -> jax.Array:
    """Z: (M, R), A: (C, R, R) -> (C, M) float32.  M % block_m == 0,
    block_m % 128 == 0 or block_m == M, and R % 128 == 0 (ops.py pads).
    Each program writes a (1, 1, block_m) block of a (C, 1, M) output, so
    the block's last two dims satisfy the TPU tiling rule for any C."""
    m, r = Z.shape
    c = A.shape[0]
    assert m % block_m == 0, (m, block_m)
    out = pl.pallas_call(
        _score_all_kernel,
        grid=(c, m // block_m),
        in_specs=[
            pl.BlockSpec((block_m, r), lambda ci, mi: (mi, 0)),
            pl.BlockSpec((1, r, r), lambda ci, mi: (ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_m), lambda ci, mi: (ci, 0, mi)),
        out_shape=jax.ShapeDtypeStruct((c, 1, m), jnp.float32),
        interpret=interpret,
        name="ndpp_score_all",
    )(Z, A)
    return out.reshape(c, m)
