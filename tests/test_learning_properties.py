"""Property tests for the learning layer (Eq. 14 and its scaffolding).

``project_constraints`` must land exactly on the ONDPP constraint set for
arbitrary parameters; the losses and their gradients must stay finite on
arbitrary (variable-size, even empty) padded baskets; ``_basket_logdets``
must agree with dense brute-force determinants — including the padding
convention (a padding slot contributes a factor of exactly 1, so basket
log-likelihoods are independent of ``k_max``); and the log-space ESP
table must match the f64 host recurrence.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import assume, given, settings, strategies as st
except ImportError:  # pragma: no cover - CI installs the real hypothesis
    from _hypothesis_fallback import assume, given, settings, strategies as st

from repro.core import (
    Baskets,
    elementary_symmetric,
    elementary_symmetric_log,
    init_ndpp,
    init_ondpp,
    item_frequencies,
    ndpp_loss,
    ondpp_loss,
    project_constraints,
    symmetric_dpp_loss,
)
from repro.core.learning import (
    _DET_EPS,
    _basket_logdets,
    _slogdet_width_invariant,
)
from repro.core.types import NDPPParams, ONDPPParams, dense_l

SETTINGS = dict(max_examples=15, deadline=None)


def _random_baskets(rng, m, n=12, k_max=5):
    """Variable-size padded baskets, including empty and full rows."""
    items = np.zeros((n, k_max), np.int32)
    mask = np.zeros((n, k_max), np.float32)
    for i in range(n):
        size = int(rng.integers(0, k_max + 1))  # 0 = empty basket
        chosen = rng.choice(m, size=size, replace=False)
        items[i, :size] = chosen
        mask[i, :size] = 1.0
    return Baskets(jnp.asarray(items), jnp.asarray(mask))


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), m=st.integers(6, 40),
       k_half=st.integers(1, 4))
def test_project_constraints_invariants(seed, m, k_half):
    """B^T B = I, V^T B = 0, sigma >= 0 for arbitrary input params."""
    k = 2 * k_half
    assume(m >= k)
    rng = np.random.default_rng(seed)
    p = ONDPPParams(
        V=jnp.asarray(rng.normal(size=(m, k)) * 3.0, jnp.float32),
        B=jnp.asarray(rng.normal(size=(m, k)) * 3.0, jnp.float32),
        sigma=jnp.asarray(rng.normal(size=(k_half,)), jnp.float32),
    )
    q = project_constraints(p)
    np.testing.assert_allclose(
        np.asarray(q.B.T @ q.B), np.eye(k), atol=2e-5)
    assert float(jnp.abs(q.V.T @ q.B).max()) < 2e-4
    assert bool((q.sigma >= 0).all())
    # projection is idempotent up to float noise
    q2 = project_constraints(q)
    np.testing.assert_allclose(np.asarray(q2.B), np.asarray(q.B), atol=2e-5)
    np.testing.assert_allclose(np.asarray(q2.V), np.asarray(q.V), atol=2e-4)


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000))
def test_losses_and_grads_finite(seed):
    """Losses and their grads are finite on random variable-size baskets
    (empty baskets included) for both parameterizations + the symmetric
    baseline."""
    m, k = 20, 4
    rng = np.random.default_rng(seed)
    baskets = _random_baskets(rng, m)
    freq = item_frequencies(baskets, m)
    po = init_ondpp(jax.random.PRNGKey(seed), m, k)
    pn = init_ndpp(jax.random.PRNGKey(seed + 1), m, k)

    lo, go = jax.value_and_grad(
        lambda p: ondpp_loss(p, baskets, freq))(po)
    ln, gn = jax.value_and_grad(
        lambda p: ndpp_loss(p, baskets, freq))(pn)
    v = jax.random.uniform(jax.random.PRNGKey(seed + 2), (m, k))
    ls, gs = jax.value_and_grad(
        lambda w: symmetric_dpp_loss(w, baskets, freq))(v)
    for val in (lo, ln, ls):
        assert np.isfinite(float(val))
    for g in (go, gn, gs):
        assert all(bool(jnp.isfinite(leaf).all())
                   for leaf in jax.tree.leaves(g))


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000))
def test_basket_logdets_match_dense(seed):
    """_basket_logdets == slogdet(L_Y + eps I) from the dense kernel, for
    variable-size baskets; padding must contribute a factor of exactly 1
    (the k_max-dependent bias was a real seed bug)."""
    m, k = 12, 4
    rng = np.random.default_rng(seed)
    V = jnp.asarray(rng.normal(size=(m, k)) * 0.7, jnp.float32)
    B = jnp.asarray(rng.normal(size=(m, k)) * 0.7, jnp.float32)
    D = jnp.asarray(rng.normal(size=(k, k)), jnp.float32)
    baskets = _random_baskets(rng, m, n=8, k_max=5)
    L = np.asarray(dense_l(NDPPParams(V, B, D)), np.float64)
    got = np.asarray(_basket_logdets(V, B, D, baskets), np.float64)
    for i in range(baskets.items.shape[0]):
        y = np.asarray(baskets.items[i])[np.asarray(baskets.mask[i], bool)]
        sub = L[np.ix_(y, y)] + _DET_EPS * np.eye(len(y))
        ref = np.linalg.slogdet(sub)[1] if len(y) else 0.0
        np.testing.assert_allclose(got[i], ref, rtol=2e-4, atol=2e-5)


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), k_max_a=st.integers(5, 9))
def test_basket_logdets_padding_invariant(seed, k_max_a):
    """Re-padding the same baskets to a wider k_max must not change any
    basket's log det (regression for the eps-on-padding bias)."""
    m, k = 12, 4
    rng = np.random.default_rng(seed)
    V = jnp.asarray(rng.normal(size=(m, k)) * 0.7, jnp.float32)
    B = jnp.asarray(rng.normal(size=(m, k)) * 0.7, jnp.float32)
    D = jnp.asarray(rng.normal(size=(k, k)), jnp.float32)
    b1 = _random_baskets(rng, m, n=8, k_max=5)
    pad = k_max_a - 5
    b2 = Baskets(
        jnp.pad(b1.items, ((0, 0), (0, pad))),
        jnp.pad(b1.mask, ((0, 0), (0, pad))),
    )
    a = np.asarray(_basket_logdets(V, B, D, b1))
    b = np.asarray(_basket_logdets(V, B, D, b2))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), k=st.integers(1, 9))
def test_slogdet_width_invariant_matches_lapack(seed, k):
    """The elimination behind _basket_logdets gives the f64 sign and log
    |det| of dense square matrices, negative determinants included
    (Q1 diag(s) Q2 with |s| in [0.5, 2] and random signs: condition
    number at most 4, so float32 cannot flip a sign)."""
    rng = np.random.default_rng(seed)
    q1 = np.linalg.qr(rng.normal(size=(6, k, k)))[0]
    q2 = np.linalg.qr(rng.normal(size=(6, k, k)))[0]
    s = (rng.uniform(0.5, 2.0, size=(6, 1, k))
         * rng.choice([-1.0, 1.0], size=(6, 1, k)))
    a = (q1 * s) @ q2
    sign, logdet = _slogdet_width_invariant(jnp.asarray(a, jnp.float32))
    ref_sign, ref_logdet = np.linalg.slogdet(a)
    np.testing.assert_array_equal(np.asarray(sign), ref_sign)
    np.testing.assert_allclose(np.asarray(logdet), ref_logdet,
                               rtol=1e-4, atol=1e-4)


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), n=st.integers(3, 24),
       k=st.integers(1, 8))
def test_elementary_symmetric_log_consistency(seed, n, k):
    """exp(elementary_symmetric_log) == elementary_symmetric == f64 host
    recurrence on small spectra (the log table is the overflow-safe path
    used by the fixed-size samplers)."""
    assume(k <= n)
    rng = np.random.default_rng(seed)
    lam = rng.uniform(0.0, 2.0, size=n)
    lam[rng.random(n) < 0.2] = 0.0  # exercise the -inf (zero eigen) path
    lam_j = jnp.asarray(lam, jnp.float32)
    log_tab = np.asarray(elementary_symmetric_log(lam_j, k), np.float64)
    lin_tab = np.asarray(elementary_symmetric(lam_j, k), np.float64)
    # host recurrence in f64
    ref = np.zeros((n + 1, k + 1))
    ref[:, 0] = 1.0
    for i in range(1, n + 1):
        for j in range(1, k + 1):
            ref[i, j] = ref[i - 1, j] + lam[i - 1] * ref[i - 1, j - 1]
    np.testing.assert_allclose(np.exp(log_tab), ref, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(lin_tab, ref, rtol=2e-4, atol=1e-5)
