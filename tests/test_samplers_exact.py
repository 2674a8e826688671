"""Exactness of the NDPP samplers against brute-force enumeration.

For tiny ground sets the subset distribution Pr(Y) = det(L_Y)/det(L+I) is
enumerable; both samplers must match it in total-variation distance up to
Monte-Carlo noise.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    NDPPParams,
    det_ratio_exact,
    preprocess,
    sample_batch,
    sample_cholesky,
    sample_cholesky_blocked,
    sample_cholesky_params,
    spectral_from_params,
)
from repro.core.types import dense_l, x_from_sigma

pytestmark = pytest.mark.exactness

M, K = 8, 4
N_SAMPLES = 20000


@pytest.fixture(scope="module")
def params(rng):
    v = jnp.asarray(rng.normal(size=(M, K)) * 0.6, jnp.float32)
    b = jnp.asarray(rng.normal(size=(M, K)) * 0.6, jnp.float32)
    d = jnp.asarray(rng.normal(size=(K, K)), jnp.float32)
    return NDPPParams(v, b, d)


@pytest.fixture(scope="module")
def exact_probs(params):
    l = np.asarray(dense_l(params), np.float64)
    norm = np.linalg.det(l + np.eye(M))
    probs = {}
    for r in range(M + 1):
        for y in itertools.combinations(range(M), r):
            sub = l[np.ix_(list(y), list(y))]
            probs[y] = (np.linalg.det(sub) if y else 1.0) / norm
    assert abs(sum(probs.values()) - 1.0) < 1e-8
    return probs


def _tv(emp_counts, probs, n):
    return 0.5 * sum(abs(emp_counts.get(y, 0) / n - p) for y, p in probs.items())


def test_cholesky_sampler_exact(params, exact_probs):
    samp = jax.jit(jax.vmap(lambda k: sample_cholesky_params(params, k)))
    keys = jax.random.split(jax.random.PRNGKey(1), N_SAMPLES)
    masks = np.asarray(samp(keys))
    emp = {}
    for row in masks:
        y = tuple(np.nonzero(row)[0])
        emp[y] = emp.get(y, 0) + 1
    assert _tv(emp, exact_probs, N_SAMPLES) < 0.05


def test_blocked_cholesky_matches(params, exact_probs):
    z = jnp.concatenate([params.V, params.B], axis=1)
    x = jnp.zeros((2 * K, 2 * K), jnp.float32)
    x = x.at[:K, :K].set(jnp.eye(K))
    x = x.at[K:, K:].set(params.D - params.D.T)
    samp = jax.jit(jax.vmap(lambda k: sample_cholesky_blocked(z, x, k, block=4)))
    keys = jax.random.split(jax.random.PRNGKey(2), N_SAMPLES)
    masks = np.asarray(samp(keys))
    emp = {}
    for row in masks:
        y = tuple(np.nonzero(row)[0])
        emp[y] = emp.get(y, 0) + 1
    assert _tv(emp, exact_probs, N_SAMPLES) < 0.05


def test_rejection_sampler_exact(params, exact_probs):
    sampler = preprocess(params.V, params.B, params.D, block=2)
    res = jax.jit(lambda k: sample_batch(sampler, k, N_SAMPLES))(
        jax.random.PRNGKey(3)
    )
    items = np.asarray(res.items)
    mask = np.asarray(res.mask)
    assert bool(np.asarray(res.accepted).all())
    emp = {}
    for i in range(N_SAMPLES):
        y = tuple(sorted(items[i][mask[i]]))
        emp[y] = emp.get(y, 0) + 1
    # no impossible subsets
    assert set(emp) <= set(exact_probs)
    assert _tv(emp, exact_probs, N_SAMPLES) < 0.05
    # mean trials matches det(Lhat+I)/det(L+I)
    expected = float(det_ratio_exact(sampler.sp))
    assert np.mean(np.asarray(res.trials)) == pytest.approx(expected, rel=0.1)


def test_tree_vs_dense_proposal(params, rng):
    """The flat-tree elementary sampler must match the dense O(MK) oracle."""
    from repro.core import proposal_eigens, sample_elementary, sample_elementary_dense
    from repro.core.tree import construct_tree

    sp = spectral_from_params(params.V, params.B, params.D)
    lam, w = proposal_eigens(sp)
    tree = construct_tree(lam, w, block=2)
    e_mask = jnp.asarray([True, False, True, True, False, False, True, False])
    n = 4000
    keys = jax.random.split(jax.random.PRNGKey(4), n)
    t_items, _ = jax.jit(jax.vmap(lambda k: sample_elementary(tree, e_mask, k)))(keys)
    d_items, _ = jax.jit(
        jax.vmap(lambda k: sample_elementary_dense(w, e_mask, k))
    )(jax.random.split(jax.random.PRNGKey(5), n))

    def incl(items):
        out = np.zeros(M)
        arr = np.asarray(items)
        for row in arr:
            out[row[row >= 0]] += 1
        return out / len(arr)

    assert np.abs(incl(t_items) - incl(d_items)).max() < 0.05


def test_preprocess_eigenbasis_in_near_degenerate_pairs():
    """Lhat's eigenvalues come in Youla pairs that V V^T splits only
    slightly; here V is small, so pairs sit a few float32 ulps apart.
    ``preprocess`` takes the eigendecomposition from a float64 Gram, so
    each eigenvector column matches Lhat's own, from a float64 dense
    eigendecomposition, up to sign and float32 rounding."""
    rng = np.random.default_rng(15)
    m, k = 256, 8
    v = (rng.normal(size=(m, k)) * 1e-3).astype(np.float32)
    b = rng.normal(size=(m, k)).astype(np.float32)
    d = rng.normal(size=(k, k)).astype(np.float32)
    sampler = preprocess(jnp.asarray(v), jnp.asarray(b), jnp.asarray(d),
                         block=8)
    w = np.asarray(sampler.tree.W, np.float64)[:m]
    lam = np.asarray(sampler.tree.lam, np.float64)

    v64, b64 = v.astype(np.float64), b.astype(np.float64)
    qb, rb = np.linalg.qr(b64)
    c = rb @ (d - d.T).astype(np.float64) @ rb.T
    ev, evec = np.linalg.eigh(c.T @ c)
    abs_c = (evec * np.sqrt(np.maximum(ev, 0.0))) @ evec.T      # |C|
    lam_exact, w_exact = np.linalg.eigh(v64 @ v64.T + qb @ abs_c @ qb.T)
    lam_exact, w_exact = lam_exact[-2 * k:], w_exact[:, -2 * k:]
    assert np.diff(lam_exact).min() < 1e-5 * lam_exact.max()   # near pairs
    np.testing.assert_allclose(lam, lam_exact, rtol=1e-5, atol=1e-6)
    cos = np.abs(np.sum(w * w_exact, axis=0))
    assert cos.min() > 1 - 1e-5, cos
