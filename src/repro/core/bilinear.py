"""Shared primitive: batched bilinear forms ``p_i = z_i^T W z_i`` over items.

Every hot path of the paper reduces to this primitive with a different
2K x 2K inner matrix ``W``:

* Cholesky sampler marginals (Eqs. 4-5),
* tree-based sampling leaf-block scores (Eq. 11),
* greedy MAP / next-item conditioning (Gartrell et al. 2021, Sec. 4.2),
* rejection-sampler acceptance diagnostics.

``bilinear_scores`` is the pure-jnp implementation (also the oracle for the
Pallas kernel in ``repro.kernels.bilinear``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bilinear_scores(Z: jax.Array, W: jax.Array) -> jax.Array:
    """p_i = z_i^T W z_i for all rows z_i of Z.  Z: (M, R), W: (R, R)."""
    return jnp.einsum("mi,ij,mj->m", Z, W, Z, optimize=True)


def conditional_inner_matrix(
    Z_obs: jax.Array, mask: jax.Array, X: jax.Array, eps: float = 1e-6
) -> jax.Array:
    """Inner matrix of the Schur complement of L given observed rows.

    For an observed set J with (padded) rows ``Z_obs`` (k_pad, R) and row
    mask ``mask`` (k_pad,), the conditional score of item i is

        det(L_{J u i}) / det(L_J) = z_i^T W_J z_i,
        W_J = X - X Z_J^T (Z_J X Z_J^T)^{-1} Z_J X.

    Padding rows are neutralized by masking and unit diagonal fill.
    """
    zj = Z_obs * mask[:, None]
    right = zj @ X                 # Z_J X            (k_pad, R)
    left = X @ zj.T                # X Z_J^T          (R, k_pad)
    g = right @ zj.T               # Z_J X Z_J^T
    k_pad = g.shape[0]
    g = g + jnp.diag(1.0 - mask) + eps * jnp.eye(k_pad, dtype=g.dtype)
    sol = jnp.linalg.solve(g, right)  # (k_pad, R)
    # X is NOT symmetric (skew blocks): the left factor must be X Z_J^T,
    # not (Z_J X)^T = X^T Z_J^T — caught by the hypothesis det-ratio test
    return X - left @ sol


def conditional_scores(
    Z: jax.Array, Z_obs: jax.Array, mask: jax.Array, X: jax.Array
) -> jax.Array:
    """det(L_{J u i})/det(L_J) for every item i (rows of Z)."""
    w = conditional_inner_matrix(Z_obs, mask, X)
    return bilinear_scores(Z, w)
