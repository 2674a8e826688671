"""The catalog a cell serves, made on the device in one jitted call.

It follows the ``synthetic_features`` family of Han & Gillenwater (2020)
that the repository's benchmarks use: the rows of ``[V | B]`` scatter
around ``n_clusters`` Gaussian centres, with cluster sizes proportional to
Poisson(5) draws, and both factors are scaled by ``1/sqrt(M)``; ``D`` is
standard normal.  The kernel is the unconstrained NDPP
``L = V V^T + B (D - D^T) B^T``.

The catalog itself comes from the configuration's ``catalog_seed``, so
every run serves the same items (and the same E[trials] and E|Y|); the
run's ``--seed`` only puts its rows in another order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int) -> jax.Array:
    """A raw threefry key for (seed, stream), for any non-negative seed,
    wider than 32 bits too."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, dtype=np.uint32)
    return jnp.asarray(words, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("m", "k", "n_clusters"))
def make_catalog(key: jax.Array, order_key: jax.Array, *, m: int, k: int,
                 n_clusters: int = 100):
    """(V, B, D) float32: V, B (m, k), D (k, k); ``key`` makes the items,
    ``order_key`` permutes their rows."""
    kc, kt, kz, kd = jax.random.split(key, 4)
    n_c = min(n_clusters, m)
    centers = jax.random.normal(kc, (n_c, 2 * k)) / jnp.sqrt(2.0 * k)
    t = jax.random.poisson(kt, 5.0, (n_c,)).astype(jnp.float32) + 1e-9
    counts = jnp.round(t * m / t.sum()).astype(jnp.int32)
    counts = counts.at[0].add(m - counts.sum())
    cluster = jnp.searchsorted(jnp.cumsum(counts), jnp.arange(m), side="right")
    z = (centers[cluster] + jax.random.normal(kz, (m, 2 * k))) / jnp.sqrt(m)
    z = z[jax.random.permutation(order_key, m)]
    d = jax.random.normal(kd, (k, k))
    return z[:, :k], z[:, k:], d
