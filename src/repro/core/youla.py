"""Youla decomposition of the low-rank skew-symmetric kernel part.

Implements Algorithm 4 of the paper: the nonzero eigenvalues of
``S = B (D - D^T) B^T`` (M x M, rank K) equal those of the K x K matrix
``(D - D^T) B^T B`` (Nakatsukasa 2019, Proposition 1 / paper Proposition 2),
so the decomposition costs O(M K^2 + K^3) instead of O(M^3).

Returns sigma (K/2 nonnegative reals, descending) and Y (M x K) with
``S = sum_j sigma_j (y_{2j-1} y_{2j}^T - y_{2j} y_{2j-1}^T)``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def youla_decompose_np(B: np.ndarray, D: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host (numpy, f64) Youla decomposition — K x K eig + one M x K matmul.

    The complex eigendecomposition is not jittable on all backends and is a
    K x K one-time preprocessing cost, so we keep it on host in float64 (the
    paper runs it once per kernel, Table 3 'spectral decomposition' row).
    """
    B = np.asarray(B, dtype=np.float64)
    D = np.asarray(D, dtype=np.float64)
    K = B.shape[1]
    C = (D - D.T) @ (B.T @ B)  # (K, K); eigenvalues purely imaginary pairs
    eigvals, eigvecs = np.linalg.eig(C)
    # Keep one of each conjugate pair: eigenvalues i*sigma with sigma > 0.
    order = np.argsort(-np.imag(eigvals), kind="stable")
    eigvals, eigvecs = eigvals[order], eigvecs[:, order]
    half = K // 2
    sig = np.imag(eigvals[:half]).copy()
    vecs = eigvecs[:, :half]  # (K, K/2) complex
    # Map back up: eigenvector of S is B v (Prop. 2), normalized.
    y = np.zeros((B.shape[0], K), dtype=np.float64)
    for j in range(half):
        if sig[j] <= 1e-12:  # numerically rank-deficient pair
            sig[j] = 0.0
            # pick arbitrary orthonormal filler in the column space of B
            bv = B @ np.real(vecs[:, j])
            if np.linalg.norm(bv) < 1e-12:
                bv = B[:, j % B.shape[1]]
            a = bv / max(np.linalg.norm(bv), 1e-30)
            y[:, 2 * j] = a
            y[:, 2 * j + 1] = 0.0
            continue
        bv = B @ vecs[:, j]
        bv = bv / np.linalg.norm(bv)  # unit complex eigenvector a + i b
        a, b = np.real(bv), np.imag(bv)
        y1 = a - b
        y2 = a + b
        # a ⟂ b and |a| = |b| = 1/sqrt(2) for a normal (skew) matrix, so
        # y1, y2 are unit in exact arithmetic; normalize to be safe.
        y[:, 2 * j] = y1 / np.linalg.norm(y1)
        y[:, 2 * j + 1] = y2 / np.linalg.norm(y2)
    return sig, y


def youla_decompose(B: jax.Array, D: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Youla decomposition returning jnp arrays in B's dtype."""
    sig, y = youla_decompose_np(np.asarray(B), np.asarray(D))
    return jnp.asarray(sig, B.dtype), jnp.asarray(y, B.dtype)


def spectral_from_params(V: jax.Array, B: jax.Array, D: jax.Array):
    """Build the spectral form Z = [V, Y], sigma (Section 4.1)."""
    from .types import SpectralNDPP

    sig, y = youla_decompose(B, D)
    z = jnp.concatenate([V, y], axis=1)
    return SpectralNDPP(Z=z, sigma=sig)


def spectral_and_gram(V: jax.Array, B: jax.Array, D: jax.Array):
    """``spectral_from_params``, plus the 2K x 2K Gram ``A^T A`` of the
    proposal factor ``A = Z Xhat^{1/2}`` (``Lhat = A A^T``) in float64 on
    the host, formed from the float64 Youla factors before Z is rounded
    to V's dtype (``tree.proposal_eigens`` takes it)."""
    from .types import SpectralNDPP

    sig, y = youla_decompose_np(np.asarray(B), np.asarray(D))
    sp = SpectralNDPP(Z=jnp.concatenate([V, jnp.asarray(y, B.dtype)], axis=1),
                      sigma=jnp.asarray(sig, B.dtype))
    v = np.asarray(V, np.float64)
    a = np.concatenate([v, y], axis=1) * np.sqrt(np.concatenate(
        [np.ones(v.shape[1]), np.repeat(sig, 2)]))
    return sp, a.T @ a


def youla_transform_np(B: np.ndarray, D: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """(sigma, T): the Youla change of basis as a K x K *right transform*,
    ``Y = B @ T``.

    Why a transform instead of the eigenbasis itself: Youla gives
    ``B (D - Dᵀ) Bᵀ = (B T) Σ_skew (B T)ᵀ``, and when B has full column
    rank that forces the K x K identity ``T Σ_skew Tᵀ = D - Dᵀ`` — which
    holds for *any* later B.  So a dynamic catalog can freeze (sigma, T)
    once and embed a new/updated item as ``z_j = [v_j, b_j @ T]``: the
    spectral form ``Z X Zᵀ`` stays an exact factorization of the live
    kernel under arbitrary row inserts/updates/deletes, as long as D is
    unchanged (a D change is a real re-decomposition).  This is the
    rank-structured dual-state update behind ``serve.catalog``.
    """
    B = np.asarray(B, dtype=np.float64)
    D = np.asarray(D, dtype=np.float64)
    K = B.shape[1]
    C = (D - D.T) @ (B.T @ B)
    eigvals, eigvecs = np.linalg.eig(C)
    order = np.argsort(-np.imag(eigvals), kind="stable")
    eigvals, eigvecs = eigvals[order], eigvecs[:, order]
    half = K // 2
    sig = np.imag(eigvals[:half]).copy()
    t = np.zeros((K, K))
    for j in range(half):
        if sig[j] <= 1e-12:  # numerically rank-deficient pair
            sig[j] = 0.0
            u = np.real(eigvecs[:, j])
            if np.linalg.norm(B @ u) < 1e-12:
                u = np.zeros(K)
                u[j % K] = 1.0
            t[:, 2 * j] = u / max(np.linalg.norm(B @ u), 1e-30)
            continue
        v = eigvecs[:, j]
        u1 = np.real(v) - np.imag(v)
        u2 = np.real(v) + np.imag(v)
        t[:, 2 * j] = u1 / max(np.linalg.norm(B @ u1), 1e-30)
        t[:, 2 * j + 1] = u2 / max(np.linalg.norm(B @ u2), 1e-30)
    return sig, t


def spectral_from_transform(V: jax.Array, B: jax.Array, T: jax.Array,
                            sigma: jax.Array):
    """Spectral form via a frozen Youla transform: Z = [V, B T]."""
    from .types import SpectralNDPP

    z = jnp.concatenate([V, B @ jnp.asarray(T, B.dtype)], axis=1)
    return SpectralNDPP(Z=z, sigma=jnp.asarray(sigma, B.dtype))
