"""ONDPP learning with orthogonality constraints (Section 5, Eq. 14).

Loss = - (1/n) sum_i log( det(L_{Y_i}) / det(L + I) )
       + alpha * sum_i ||v_i||^2 / mu_i + beta * sum_i ||b_i||^2 / mu_i
       + gamma * sum_j log(1 + 2 sigma_j / (sigma_j^2 + 1))

The gamma term is exactly the log of the expected number of rejections
(Theorem 2), so it trades predictive fit against sampling speed.

Constraints (footnote ¶): after each optimizer step we project
    B <- qr(B).Q            (B^T B = I)
    V <- V - B (B^T V)      (V^T B = 0; B is orthonormal at that point)
    sigma <- max(sigma, 0)

Also provides the unconstrained NDPP baseline (Gartrell et al. 2021) and
the symmetric low-rank DPP baseline (Gartrell et al. 2017) that the paper
compares against in Table 2.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .types import NDPPParams, ONDPPParams, d_from_sigma

_DET_EPS = 1e-5  # Appendix C: epsilon*I added to each L_{Y_i}


class Baskets(NamedTuple):
    """Padded training baskets: items (n, k_max) int32, mask (n, k_max)."""

    items: jax.Array
    mask: jax.Array


def _basket_logdets(
    V: jax.Array, B: jax.Array, D: jax.Array, baskets: Baskets
) -> jax.Array:
    """log det(L_{Y_i} + eps I) for each padded basket (unit padding diag)."""
    vy = V[baskets.items] * baskets.mask[..., None]      # (n, k, K)
    by = B[baskets.items] * baskets.mask[..., None]
    # products summed over K elementwise, not as dots: the backend's dot
    # picks its summation order from the padded width k, so the same basket
    # re-padded wider drifted by an ulp per entry (~1e-5 in its log det)
    bs = jnp.sum(by[..., :, None] * (D - D.T), axis=-2)  # (n, k, K)
    ly = jnp.sum(vy[:, :, None, :] * vy[:, None, :, :]
                 + bs[:, :, None, :] * by[:, None, :, :], axis=-1)
    k_pad = ly.shape[-1]
    eye = jnp.eye(k_pad, dtype=ly.dtype)
    # padding rows get diag exactly 1 (factor 1 in the det); the eps jitter
    # goes on REAL rows only — adding it to padding too would bias each
    # basket's log-likelihood by (k_max - |Y|) log(1 + eps), a size-dependent
    # offset that the variable-basket-size exactness tests catch
    diag_fill = (1.0 - baskets.mask)[..., None] * eye[None]
    ly = ly + diag_fill + _DET_EPS * baskets.mask[..., None] * eye[None]
    sign, logdet = _slogdet_width_invariant(ly)
    # det should be positive for PSD-style kernels; clamp invalid to -inf-ish
    return jnp.where(sign > 0, logdet, -1e9)


@jax.jit
def _slogdet_width_invariant(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """slogdet of a batch (n, k, k) by Gaussian elimination with partial
    pivoting. Every update is elementwise and log|pivot| is summed column by
    column, so a basket padded with identity rows/columns gets the same
    result at any k (LAPACK's LU splits its recursion by k, which moved a
    re-padded log det by up to ~1e-5)."""
    k = a.shape[-1]
    idx = jnp.arange(k, dtype=jnp.int32)

    def step(j, carry):
        a, sign, logdet = carry
        col = jnp.abs(a[:, :, j])
        p = jnp.argmax(jnp.where(idx >= j, col, -1.0), axis=-1)   # (n,)
        row_j = a[:, j, :]
        row_p = jnp.take_along_axis(a, p[:, None, None], axis=1)[:, 0]
        is_j = (idx == j)[None, :, None]
        is_p = (idx[None, :] == p[:, None])[..., None]
        a = jnp.where(is_j, row_p[:, None], jnp.where(is_p, row_j[:, None], a))
        piv = a[:, j, j]
        below = (idx > j)[None, :, None] & (idx > j)[None, None, :]
        lower = a[:, :, j] / piv[:, None]
        a = jnp.where(below, a - lower[:, :, None] * a[:, j, None, :], a)
        sign = sign * jnp.sign(piv) * jnp.where(p == j, 1.0, -1.0)
        return a, sign, logdet + jnp.log(jnp.abs(piv))

    n = a.shape[0]
    init = (a, jnp.ones((n,), a.dtype), jnp.zeros((n,), a.dtype))
    _, sign, logdet = jax.lax.fori_loop(0, k, step, init)
    return sign, logdet


def log_normalizer(V: jax.Array, B: jax.Array, D: jax.Array) -> jax.Array:
    """log det(L + I) = log det(I_{2K} + X Z^T Z)  — O(M K^2)."""
    z = jnp.concatenate([V, B], axis=1)
    k = V.shape[1]
    g = z.T @ z
    x = jnp.zeros((2 * k, 2 * k), z.dtype)
    x = x.at[:k, :k].set(jnp.eye(k, dtype=z.dtype))
    x = x.at[k:, k:].set(D - D.T)
    sign, logdet = jnp.linalg.slogdet(jnp.eye(2 * k, dtype=z.dtype) + x @ g)
    return logdet


def ondpp_loss(
    params: ONDPPParams,
    baskets: Baskets,
    item_freq: jax.Array,
    alpha: float = 0.01,
    beta: float = 0.01,
    gamma: float = 0.1,
) -> jax.Array:
    """Eq. 14 (mean NLL + regularizers)."""
    d = d_from_sigma(params.sigma)
    ll = _basket_logdets(params.V, params.B, d, baskets)
    logz = log_normalizer(params.V, params.B, d)
    nll = -(jnp.mean(ll) - logz)
    inv_freq = 1.0 / jnp.maximum(item_freq, 1.0)
    reg_v = alpha * jnp.sum(jnp.sum(params.V ** 2, axis=1) * inv_freq)
    reg_b = beta * jnp.sum(jnp.sum(params.B ** 2, axis=1) * inv_freq)
    s = params.sigma
    reg_s = gamma * jnp.sum(jnp.log1p(2.0 * s / (s ** 2 + 1.0)))
    return nll + reg_v + reg_b + reg_s


def ndpp_loss(
    params: NDPPParams,
    baskets: Baskets,
    item_freq: jax.Array,
    alpha: float = 0.01,
    beta: float = 0.01,
) -> jax.Array:
    """Unconstrained NDPP baseline objective (Gartrell et al. 2021)."""
    ll = _basket_logdets(params.V, params.B, params.D, baskets)
    logz = log_normalizer(params.V, params.B, params.D)
    nll = -(jnp.mean(ll) - logz)
    inv_freq = 1.0 / jnp.maximum(item_freq, 1.0)
    reg_v = alpha * jnp.sum(jnp.sum(params.V ** 2, axis=1) * inv_freq)
    reg_b = beta * jnp.sum(jnp.sum(params.B ** 2, axis=1) * inv_freq)
    return nll + reg_v + reg_b


def symmetric_dpp_loss(
    V: jax.Array, baskets: Baskets, item_freq: jax.Array, alpha: float = 0.01
) -> jax.Array:
    """Symmetric low-rank DPP baseline (Gartrell et al. 2017): L = V V^T."""
    vy = V[baskets.items] * baskets.mask[..., None]
    ly = jnp.einsum("nik,njk->nij", vy, vy)
    k_pad = ly.shape[-1]
    eye = jnp.eye(k_pad, dtype=ly.dtype)
    # same padding convention as _basket_logdets: unit diag on padding, eps
    # jitter on real rows only
    ly = ly + (1.0 - baskets.mask)[..., None] * eye[None] \
        + _DET_EPS * baskets.mask[..., None] * eye[None]
    sign, logdet = jnp.linalg.slogdet(ly)
    ll = jnp.where(sign > 0, logdet, -1e9)
    g = V.T @ V
    k = V.shape[1]
    _, logz = jnp.linalg.slogdet(jnp.eye(k, dtype=V.dtype) + g)
    inv_freq = 1.0 / jnp.maximum(item_freq, 1.0)
    return -(jnp.mean(ll) - logz) + alpha * jnp.sum(
        jnp.sum(V ** 2, axis=1) * inv_freq
    )


def project_constraints(params: ONDPPParams) -> ONDPPParams:
    """Enforce B^T B = I, V^T B = 0, sigma >= 0 (footnote ¶ of Section 5)."""
    q, r = jnp.linalg.qr(params.B)
    # keep orientation deterministic: positive diagonal of R
    signs = jnp.sign(jnp.diagonal(r))
    signs = jnp.where(signs == 0, 1.0, signs)
    b = q * signs[None, :]
    v = params.V - b @ (b.T @ params.V)
    # |sigma| rather than relu: clipping at 0 kills the gradient and the
    # skew part collapses permanently (sigma >= 0 is required by Eq. 13;
    # reflection is an equally valid projection without the dead zone)
    return ONDPPParams(V=v, B=b, sigma=jnp.abs(params.sigma))


def init_ondpp(
    key: jax.Array, m: int, k: int, dtype=jnp.float32
) -> ONDPPParams:
    """Paper init: V, B ~ uniform(0, 1); sigma from |N(0,1)|; then project."""
    kv, kb, ks = jax.random.split(key, 3)
    v = jax.random.uniform(kv, (m, k), dtype=dtype)
    b = jax.random.uniform(kb, (m, k), dtype=dtype)
    sigma = jnp.abs(jax.random.normal(ks, (k // 2,), dtype=dtype))
    return project_constraints(ONDPPParams(V=v, B=b, sigma=sigma))


def init_ndpp(key: jax.Array, m: int, k: int, dtype=jnp.float32) -> NDPPParams:
    kv, kb, kd = jax.random.split(key, 3)
    return NDPPParams(
        V=jax.random.uniform(kv, (m, k), dtype=dtype),
        B=jax.random.uniform(kb, (m, k), dtype=dtype),
        D=jax.random.normal(kd, (k, k), dtype=dtype),
    )


def item_frequencies(baskets: Baskets, m: int) -> jax.Array:
    """mu_i — number of training baskets containing item i."""
    flat = jnp.where(baskets.mask.astype(bool), baskets.items, m)
    counts = jnp.zeros((m + 1,), jnp.float32).at[flat.reshape(-1)].add(1.0)
    return counts[:m]
