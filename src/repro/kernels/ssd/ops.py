"""Public SSD op: chunked Pallas scan on TPU, jnp chunked scan elsewhere.

Also provides ``ssd_chunked_ref`` — the chunked algorithm in pure jnp
(used in training on any backend: it is a scan over S/chunk steps of MXU
matmuls rather than S steps of rank-1 updates, which is what makes the
mamba2/jamba train steps compile to dense compute).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..backend import interpret_requested, on_tpu
from .ref import ssd_ref
from .ssd import ssd_pallas


def ssd_chunked_ref(
    x: jax.Array,  # (B, S, H, P)
    a: jax.Array,  # (B, S, H)
    b: jax.Array,  # (B, S, H, N)
    c: jax.Array,  # (B, S, H, N)
    h0: Optional[jax.Array] = None,
    chunk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD in pure jnp (same math as the Pallas kernel)."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nq = s // chunk
    xf = x.astype(jnp.float32).reshape(bsz, nq, chunk, h, p)
    af = a.astype(jnp.float32).reshape(bsz, nq, chunk, h)
    bf = b.astype(jnp.float32).reshape(bsz, nq, chunk, h, n)
    cf = c.astype(jnp.float32).reshape(bsz, nq, chunk, h, n)
    if h0 is None:
        h0 = jnp.zeros((bsz, h, n, p), jnp.float32)

    loga = jnp.log(jnp.maximum(af, 1e-37))
    cum = jnp.cumsum(loga, axis=2)                   # (B, nq, Q, H)
    total = cum[:, :, -1]                            # (B, nq, H)
    rows = jnp.arange(chunk, dtype=jnp.int32)[:, None]
    cols = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    lmask = rows >= cols

    @jax.checkpoint
    def step(hprev, t):
        xq, aq, bq, cq = xf[:, t], af[:, t], bf[:, t], cf[:, t]
        cumq, totq = cum[:, t], total[:, t]
        # mask BEFORE exp: for i < j the exponent is positive and can
        # overflow; where-after-exp turns the cotangent into inf * 0 = NaN
        lexp = jnp.where(
            lmask[None, :, :, None],
            cumq[:, :, None] - cumq[:, None, :],
            -jnp.inf,
        )
        lmat = jnp.exp(lexp)                         # (B, Q, Q, H)
        y_inter = jnp.einsum(
            "bqhn,bhnp->bqhp", cq * jnp.exp(cumq)[..., None], hprev
        )
        s_mat = jnp.einsum("bqhn,bkhn->bqkh", cq, bq) * lmat
        y_intra = jnp.einsum("bqkh,bkhp->bqhp", s_mat, xq)
        w = jnp.exp(totq[:, None] - cumq)            # (B, Q, H)
        h_new = jnp.exp(totq)[:, :, None, None] * hprev + jnp.einsum(
            "bqhn,bqhp->bhnp", bq * w[..., None], xq
        )
        return h_new, y_inter + y_intra

    h_last, ys = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(nq, dtype=jnp.int32))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p)
    return y.astype(x.dtype), h_last


def ssd(
    x: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    h0: Optional[jax.Array] = None,
    *,
    chunk: int = 128,
    force_interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Mamba2 SSD scan.  x: (B,S,H,P), a: (B,S,H), b/c: (B,S,H,N)."""
    interpret = interpret_requested(force_interpret)
    bsz, s, h, p = x.shape
    usable = (
        (on_tpu() or interpret)
        and h0 is None
        and s % chunk == 0
        and p % 8 == 0
    )
    if not usable:
        return ssd_chunked_ref(x, a, b, c, h0, chunk=min(chunk, s))
    n = b.shape[-1]
    xr = jnp.moveaxis(x, 2, 1).reshape(bsz * h, s, p)
    ar = jnp.moveaxis(a, 2, 1).reshape(bsz * h, s)
    br = jnp.moveaxis(b, 2, 1).reshape(bsz * h, s, n)
    cr = jnp.moveaxis(c, 2, 1).reshape(bsz * h, s, n)
    y, hl = ssd_pallas(xr, ar, br, cr, chunk=chunk, interpret=interpret)
    y = jnp.moveaxis(y.reshape(bsz, h, s, p), 1, 2)
    return y, hl.reshape(bsz, h, n, p)


def ssd_decode_step(
    x: jax.Array,   # (B, H, P) one token
    a: jax.Array,   # (B, H)
    b: jax.Array,   # (B, H, N)
    c: jax.Array,   # (B, H, N)
    h: jax.Array,   # (B, H, N, P) state
) -> Tuple[jax.Array, jax.Array]:
    """O(1)-in-S decode: one recurrence step (pure jnp; it is tiny)."""
    hf = h.astype(jnp.float32)
    h_new = hf * a[..., None, None].astype(jnp.float32) + jnp.einsum(
        "bhn,bhp->bhnp", b.astype(jnp.float32), x.astype(jnp.float32)
    )
    y = jnp.einsum("bhn,bhnp->bhp", c.astype(jnp.float32), h_new)
    return y.astype(x.dtype), h_new
