"""Pallas TPU kernel: sign and log |det| of many small matrices at once.

The acceptance test of the rejection round factors two k x k matrices
per proposal (L_Y and Lhat_Y, k = 2K).  XLA factors such a batch one
matrix at a time, a serial column loop on a matrix far smaller than a
vector register file.  Here the matrices sit across the 128 vector
lanes instead: a block holds 128 matrices as (k rows, T column tiles,
8, 128), rows on the leading dim, columns on sublanes, one matrix per
lane, so every step of the elimination is a plain elementwise vector op
over 128 matrices.

Arithmetic: Gaussian elimination with partial pivoting in each lane,
the rule of ``core.learning._slogdet_width_invariant`` in float32.  At
column j the pivot is the first row at or below j of largest |a[i, j]|;
it trades places with row j (the permutation's sign flips when they
differ) and every row below j loses ``a[i, j] / pivot`` times the pivot
row.  The pivot search, the row choice and the row exchange are
compares and selects across rows: the pivot row differs from lane to
lane, so nothing is gathered.  The search for column j + 1 runs inside
the pass that eliminates column j, so each column costs one pass over
the rows below it.  A zero pivot gives sign 0 and log |det| -inf and
eliminates nothing (a deleted item's zeroed row must be rejected).

Trip count.  Outside its leading ``n_live`` rows and columns each matrix
is the identity (the subset mask is a prefix), so a block's loop runs to
the largest ``n_live`` of its 128 lanes, a scalar-prefetch operand, and
touches no row or column beyond it.  A lane with fewer live rows meets
pivots of exactly 1 and eliminations by exactly 0 past its own size, so
its result does not depend on its block's neighbours.

Grid: (N / 128,), one block of 128 matrices a step; sign and log |det|
leave as (N / 128, 2, 128) rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: matrices a grid step factors: one per vector lane
LANES = 128
#: columns a sublane tile holds
_SUB = 8


def block_bytes(k: int) -> int:
    """VMEM bytes of one block of 128 (k, k) float32 matrices."""
    return k * -(-k // _SUB) * _SUB * LANES * 4


def _slogdet_kernel(n_live_ref, a_ref, out_ref, w_ref, col_ref):
    # a_ref / w_ref: (k, T, 8, 128) matrices, w_ref the working copy;
    # col_ref: (k, 1, 128) entry of each row in the column being pivoted
    n = n_live_ref[pl.program_id(0)]
    w_ref[...] = a_ref[...]
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, LANES), 0)
    t = a_ref.shape[1]

    def entry(i, c):
        """Entry (i, c) of every lane's matrix, a (1, 128) row."""
        tile = w_ref[i, c // _SUB]
        return jnp.sum(jnp.where(sub == c % _SUB, tile, 0.0), axis=0,
                       keepdims=True)

    def pick(i, v, row, best):
        """Keep row i as the pivot candidate where |v| beats the best."""
        b_abs, b_val, b_idx, b_row = best
        better = jnp.abs(v) > b_abs
        return (jnp.where(better, jnp.abs(v), b_abs),
                jnp.where(better, v, b_val),
                jnp.where(better, i, b_idx),
                jnp.where(better[None], row, b_row))

    def no_best():
        # below any |v|, so the first row searched is always taken
        return (jnp.full((1, LANES), -1.0, jnp.float32),
                jnp.zeros((1, LANES), jnp.float32),
                jnp.zeros((1, LANES), jnp.int32),
                jnp.zeros((t, _SUB, LANES), jnp.float32))

    def search0(i, best):
        v = entry(i, 0)
        col_ref[i] = v
        return pick(i, v, w_ref[i], best)

    def step(j, carry):
        sign, logdet, p, piv, row_p = carry
        sign = sign * jnp.sign(piv) * jnp.where(p == j, 1.0, -1.0)
        logdet = logdet + jnp.log(jnp.abs(piv))
        zero = piv == 0.0
        piv = jnp.where(zero, 1.0, piv)
        c_j = col_ref[j]

        def eliminate(i, best):
            # row j moves to where the pivot row was
            at_p = p == i
            row = jnp.where(at_p[None], w_ref[j], w_ref[i])
            c = jnp.where(at_p, c_j, col_ref[i])
            f = jnp.where(zero, 0.0, c / piv)
            row = row - f[None] * row_p
            w_ref[i] = row
            v = entry(i, j + 1)
            col_ref[i] = v
            return pick(i, v, row, best)

        _, piv, p, row_p = jax.lax.fori_loop(j + 1, n, eliminate, no_best())
        return sign, logdet, p, piv, row_p

    _, piv, p, row_p = jax.lax.fori_loop(0, n, search0, no_best())
    ones = jnp.ones((1, LANES), jnp.float32)
    sign, logdet, _, _, _ = jax.lax.fori_loop(
        0, n, step, (ones, jnp.zeros((1, LANES), jnp.float32), p, piv, row_p))
    out_ref[0, 0:1] = jnp.where(sign == 0.0, 0.0, sign)
    out_ref[0, 1:2] = logdet


@functools.partial(jax.jit, static_argnames=("interpret",))
def slogdet_pallas(a: jax.Array, n_live: jax.Array, *,
                   interpret: bool = False):
    """a: (k, T, 8, N) float32, entry (i, c) of matrix m at
    ``a[i, c // 8, c % 8, m]``, N a multiple of 128; n_live: (N / 128,)
    int32, the largest leading block of each 128 matrices outside which
    they are the identity.  Returns (sign, logdet), each (N,) float32."""
    k, t, _, n = a.shape
    assert n % LANES == 0 and t * _SUB >= k, (a.shape,)
    blocks = n // LANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((k, t, _SUB, LANES),
                               lambda b, n_live: (0, 0, 0, b))],
        out_specs=pl.BlockSpec((1, 2, LANES), lambda b, n_live: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((k, t, _SUB, LANES), jnp.float32),
            pltpu.VMEM((k, 1, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        _slogdet_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((blocks, 2, LANES), jnp.float32),
        # the double-buffered block, the working copy, the column
        # entries (a sublane tile a row) and room for the small blocks
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=3 * block_bytes(k) + k * _SUB * LANES * 4
            + (4 << 20)),
        interpret=interpret,
        name="ndpp_slogdet",
    )(n_live.astype(jnp.int32), a)
    return out[:, 0].reshape(n), out[:, 1].reshape(n)
