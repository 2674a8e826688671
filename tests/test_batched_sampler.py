"""Exactness of the speculative batched rejection sampler.

The batched engine must be distribution-identical to the sequential
sampler: same subset-frequency histogram (chi-square tolerance against the
enumerated distribution, TV agreement with the sequential empirical
histogram), and trial counts that match the Theorem-2 rate for an ONDPP
kernel.  Also covers the slot-pool SamplerEngine: every retired request is
returned, and a request's draw is independent of pool scheduling.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _exactness import (
    assert_chi_square_close,
    enumerate_subset_probs,
    histogram,
    tv_hist,
)
from repro.core import (
    NDPPParams,
    NDPPSampler,
    construct_tree,
    d_from_sigma,
    det_ratio_exact,
    expected_trials,
    init_ondpp,
    preprocess,
    proposal_eigens,
    sample_batch,
    sample_batched,
    sample_batched_many,
    spectral_from_params,
)
from repro.core.types import dense_l
from repro.serve.sampler_engine import SampleRequest, SamplerEngine

M, K = 8, 4
N_SAMPLES = 8000


@pytest.fixture(scope="module")
def params():
    # module-local RNG: the kernel must not depend on which test files ran
    # earlier in the same worker (the truncation test needs a request that
    # outlives its first round)
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.normal(size=(M, K)) * 0.6, jnp.float32)
    b = jnp.asarray(rng.normal(size=(M, K)) * 0.6, jnp.float32)
    d = jnp.asarray(rng.normal(size=(K, K)), jnp.float32)
    return NDPPParams(v, b, d)


@pytest.fixture(scope="module")
def sampler(params):
    return preprocess(params.V, params.B, params.D, block=2)


@pytest.fixture(scope="module")
def exact_probs(params):
    return enumerate_subset_probs(dense_l(params))


def test_batched_matches_sequential_histogram(sampler, exact_probs):
    """sample_batched_many and the sequential sampler draw from the same
    subset distribution."""
    bat = sample_batched_many(sampler, jax.random.PRNGKey(3), N_SAMPLES,
                              n_spec=4)
    assert bool(np.asarray(bat.accepted).all())
    emp_b = histogram(bat.items, bat.mask)
    # no impossible subsets
    assert set(emp_b) <= set(exact_probs)

    # chi-square against the enumerated distribution over well-populated
    # bins (expected count >= 5, rare subsets pooled into one bin)
    assert_chi_square_close(emp_b, exact_probs, N_SAMPLES)

    # and the two empirical histograms agree with each other
    seq = jax.jit(lambda k: sample_batch(sampler, k, N_SAMPLES))(
        jax.random.PRNGKey(4)
    )
    emp_s = histogram(seq.items, seq.mask)
    assert tv_hist(emp_b, emp_s, N_SAMPLES) < 0.08


def test_batched_trials_match_expected_ondpp():
    """For an ONDPP kernel (V ⟂ B) the mean trial count of the batched
    sampler matches Theorem 2's det(Lhat+I)/det(L+I) rate."""
    p = init_ondpp(jax.random.PRNGKey(7), 64, 4)
    sp = spectral_from_params(p.V, p.B, d_from_sigma(p.sigma))
    lam, w = proposal_eigens(sp)
    sampler = NDPPSampler(sp=sp, tree=construct_tree(lam, w, block=8))
    res = sample_batched_many(sampler, jax.random.PRNGKey(8), 2000, n_spec=4)
    assert bool(np.asarray(res.accepted).all())
    expect = float(expected_trials(sp))
    assert expect == pytest.approx(float(det_ratio_exact(sp)), rel=1e-3)
    assert float(np.mean(np.asarray(res.trials))) == pytest.approx(
        expect, rel=0.1
    )


class _NullObserver:
    """Minimal duck-typed telemetry sink: forces sample_batched_many onto
    the host ``drive_rounds`` driver without recording anything."""

    def on_round(self, **kw):
        pass

    def on_retire(self, **kw):
        pass


def test_fused_driver_matches_python_driver(sampler):
    """The device-resident lax.while_loop driver and the host drive_rounds
    loop are bit-identical — items, masks, trial counts, and accept flags —
    including exhausted requests (the last-in-budget payout) and a
    max_trials that is not a multiple of any round width."""
    key = jax.random.PRNGKey(42)
    fused = sample_batched_many(sampler, key, 64, n_spec=4, max_trials=10)
    host = sample_batched_many(sampler, key, 64, n_spec=4, max_trials=10,
                               observer=_NullObserver())
    assert np.array_equal(np.asarray(fused.items), np.asarray(host.items))
    assert np.array_equal(np.asarray(fused.mask), np.asarray(host.mask))
    assert np.array_equal(np.asarray(fused.trials), np.asarray(host.trials))
    assert np.array_equal(np.asarray(fused.accepted),
                          np.asarray(host.accepted))


def test_drive_rounds_truncation_keeps_pow2_shapes(sampler):
    """Budget truncation masks lanes instead of reshaping: every round
    dispatch keeps its power-of-two width even when the remaining budget
    is smaller than the doubled round (no fresh jit cache entry near
    exhaustion), and the draws still match the fused driver."""
    from repro.core.rejection import _spec_round, drive_rounds

    widths = []

    def round_fn(keys):
        widths.append(int(keys.shape[0]))
        return _spec_round(sampler, keys)

    req = jax.random.split(jax.random.PRNGKey(5), 6)
    # max_trials=10: after rounds of 4 the doubled round of 8 has only 6
    # in-budget lanes — the dispatch must still be 8 wide
    res = drive_rounds(round_fn, req, sampler.tree.R, n_spec=4,
                       max_trials=10)
    assert widths, "no rounds dispatched"
    assert len(widths) >= 2, widths   # the truncated round must occur
    for w in widths:
        assert w & (w - 1) == 0, (w, widths)
    base = sample_batched_many(sampler, req, n_spec=4, max_trials=10,
                               split_keys=False)
    assert np.array_equal(np.asarray(res.items), np.asarray(base.items))
    assert np.array_equal(np.asarray(res.trials), np.asarray(base.trials))
    assert np.array_equal(np.asarray(res.accepted),
                          np.asarray(base.accepted))


def test_single_request_speculative(sampler):
    """sample_batched (one request, doubling rounds) returns a valid draw
    with trials counted in proposal order."""
    res = sample_batched(sampler, jax.random.PRNGKey(11), n_spec=2,
                         max_spec=8)
    assert bool(res.accepted)
    assert int(res.trials) >= 1
    items = np.asarray(res.items)
    mask = np.asarray(res.mask)
    assert (items[mask] >= 0).all() and (items[mask] < M).all()


def test_sampler_engine_returns_all_requests(sampler):
    """Every retired request appears in run()'s output, outputs recorded at
    retire time; draws are schedule-independent (engine == standalone)."""
    eng = SamplerEngine(sampler, n_slots=3, n_spec=4)
    n_req = 10
    for i in range(n_req):
        eng.submit(SampleRequest(rid=i, seed=1000 + i))
    out = eng.run()
    assert sorted(out) == list(range(n_req))
    assert all(out[i].accepted for i in range(n_req))
    # schedule independence: the engine's draw for a seed equals the
    # standalone speculative sampler's draw for the same key
    solo = sample_batched(sampler, jax.random.PRNGKey(1004), n_spec=4)
    assert np.array_equal(out[4].items, np.asarray(solo.items))
    assert out[4].trials == int(solo.trials)


def test_sampler_engine_respects_max_trials(sampler):
    """A request's budget caps which proposals can be accepted mid-tick:
    with max_trials=3 and n_spec=4 the engine must agree with the
    standalone sampler on items, trials, and the accepted flag."""
    eng = SamplerEngine(sampler, n_slots=2, n_spec=4)
    seeds = list(range(20, 28))
    for i, s in enumerate(seeds):
        eng.submit(SampleRequest(rid=i, seed=s, max_trials=3))
    out = eng.run()
    for i, s in enumerate(seeds):
        solo = sample_batched_many(
            sampler, jax.random.PRNGKey(s)[None], n_spec=4, max_trials=3,
            split_keys=False,
        )
        assert out[i].accepted == bool(solo.accepted[0]), (i, s)
        assert out[i].trials == int(solo.trials[0]) <= 3, (i, s)
        assert np.array_equal(out[i].items, np.asarray(solo.items[0])), (i, s)


def test_sampler_engine_continuous_admission(sampler):
    """Requests submitted mid-run are admitted into freed slots."""
    eng = SamplerEngine(sampler, n_slots=2, n_spec=4)
    eng.submit(SampleRequest(rid=0, seed=1))
    eng.submit(SampleRequest(rid=1, seed=2))
    eng.step()
    eng.submit(SampleRequest(rid=2, seed=3))
    out = eng.run()
    assert sorted(out) == [0, 1, 2]
