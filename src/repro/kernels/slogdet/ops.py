"""jit'd public wrapper for the batched slogdet kernel.

``slogdet_batched`` lays a (N, k, k) batch out for the kernel: matrices
across the 128 vector lanes, rows on the leading dim, columns on
sublanes, N padded to a multiple of 128 with identity matrices.  Where
it runs is ``kernels.backend``'s rule: the kernel on TPU, the jnp oracle
(``jnp.linalg.slogdet``) elsewhere, the interpreter under
``force_interpret`` / ``REPRO_PALLAS_INTERPRET=1``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..backend import interpret_requested, on_tpu
from .ref import slogdet_ref
from .slogdet import LANES, slogdet_pallas


def slogdet_batched(a: jax.Array, n_live: jax.Array, *,
                    force_interpret: bool = False
                    ) -> Tuple[jax.Array, jax.Array]:
    """(sign, log |det|) of each matrix of a (N, k, k) batch.

    ``n_live`` (N,) int: each matrix is the identity outside its leading
    ``n_live`` rows and columns (identity-padded subsets); the kernel
    factors no row or column beyond the largest ``n_live`` of each 128
    matrices.  A singular matrix gives sign 0 and log |det| -inf.
    """
    interpret = interpret_requested(force_interpret)
    if not (on_tpu() or interpret):
        return slogdet_ref(a)
    n, k, _ = a.shape
    n_pad = (-n) % LANES
    t = -(-k // 8)
    eye = jnp.broadcast_to(jnp.eye(k, dtype=jnp.float32), (n_pad, k, k))
    a = jnp.concatenate([a.astype(jnp.float32), eye])
    a = jnp.pad(a, ((0, 0), (0, 0), (0, 8 * t - k)))
    a = a.reshape(n + n_pad, k, t, 8).transpose(1, 2, 3, 0)
    live = jnp.pad(jnp.clip(n_live, 0, k), (0, n_pad))
    live = live.reshape(-1, LANES).max(axis=1)
    sign, logdet = slogdet_pallas(a, live, interpret=interpret)
    return sign[:n], logdet[:n]
