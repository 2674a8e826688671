"""Pallas TPU kernel: batched quadratic forms  p_i = z_i^T W z_i.

This is the paper's hot primitive (marginals for the Cholesky sampler,
leaf-block scores for tree sampling, conditional gains for greedy MAP).

Naive composition materializes the (M, R) intermediate ``Z @ W`` in HBM —
2x the HBM traffic of Z itself.  The fused kernel streams one (BLK_M, R)
tile of Z into VMEM, multiplies against the resident (R, R) inner matrix on
the MXU, multiplies elementwise with the same tile (still in VMEM) and
row-reduces — a single HBM pass over Z.

Arithmetic intensity:  2*R^2 flops per R-element row read
=> R/HBM-byte ~ 2K/2 = K flops/byte: memory-bound for K = 100 but ~4x above
the naive two-pass composition.

Grid: (M / BLK_M,).  BLK_M rows per program; R padded to a multiple of 128
(lane dim) by the wrapper in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bilinear_kernel(z_ref, w_ref, out_ref):
    z = z_ref[...]            # (BLK_M, R)  VMEM
    w = w_ref[...]            # (R, R)      VMEM (resident across grid)
    zw = jnp.dot(z, w, preferred_element_type=jnp.float32)  # MXU
    out_ref[...] = jnp.sum(zw * z.astype(jnp.float32), axis=1)


def _bilinear_batched_kernel(z_ref, w_ref, out_ref):
    z = z_ref[0]              # (B, R)   VMEM, one batch element per program
    w = w_ref[0]              # (R, R)   VMEM, per-element inner matrix
    zw = jnp.dot(z, w, preferred_element_type=jnp.float32)  # MXU
    out_ref[0] = jnp.sum(zw * z.astype(jnp.float32), axis=1)[None, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bilinear_batched_pallas(
    Z: jax.Array, W: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """Z: (N, B, R), W: (N, R, R) -> (N, B) float32, any B and R.  Grid
    over N: each program fuses one proposal's (B, R) x (R, R) x (B, R)
    quadratic form in a single VMEM pass — the speculative leaf-scoring
    layout (n_spec proposals, per-proposal Q).  Every block spans its
    array's last two dims whole — (B, R), (R, R) and the (1, B) rows of an
    (N, 1, B) output — which the TPU tiling rule accepts unpadded."""
    n, b, r = Z.shape
    out = pl.pallas_call(
        _bilinear_batched_kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, b, r), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, r, r), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, b), jnp.float32),
        interpret=interpret,
        name="ndpp_bilinear_batched",
    )(Z, W)
    return out.reshape(n, b)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def bilinear_pallas(
    Z: jax.Array, W: jax.Array, *, block_m: int = 512, interpret: bool = False
) -> jax.Array:
    """Z: (M, R), W: (R, R) -> (M,) float32.  M % block_m == 0, R % 128 == 0
    (ops.py pads); W is broadcast to every grid step (stays in VMEM)."""
    m, r = Z.shape
    assert m % block_m == 0, (m, block_m)
    grid = (m // block_m,)
    return pl.pallas_call(
        _bilinear_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, r), lambda i: (i, 0)),
            pl.BlockSpec((r, r), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((m,), jnp.float32),
        interpret=interpret,
    )(Z, W)
