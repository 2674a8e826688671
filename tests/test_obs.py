"""Telemetry subsystem tests (PR 7).

Three layers: (1) metric primitives — exact log-bucket edges, merges,
percentiles checked against numpy; (2) the flight recorder ring; (3) the
acceptance property that instrumentation is *free* — a fully
instrumented engine produces bit-identical draws to a bare one on both
backends (compile-freeness is asserted in tests/test_compile_cache.py),
plus the ``run()`` tick-budget bugfix and catalog/swap event coverage.

The whole module is in the strict marker set: under ``NDPP_STRICT=1``
every telemetry path must survive the transfer guard — recording metrics
may never trigger an implicit device→host sync.
"""
import json
import math
import warnings

import numpy as np
import pytest

from repro.core import preprocess
from repro.obs import (
    FlightRecorder,
    LogHistogram,
    MetricRegistry,
    RegistryObserver,
    Span,
    Telemetry,
)
from repro.serve.sampler_engine import (
    SampleRequest,
    SamplerEngine,
    TickBudgetExhausted,
)

pytestmark = pytest.mark.strict

M, K = 32, 4


@pytest.fixture(scope="module")
def sampler():
    import jax.numpy as jnp

    r = np.random.default_rng(7)
    v = jnp.asarray(r.normal(size=(M, K)) * 0.6, jnp.float32)
    b = jnp.asarray(r.normal(size=(M, K)) * 0.6, jnp.float32)
    d = jnp.asarray(r.normal(size=(K, K)), jnp.float32)
    return preprocess(v, b, d, block=4)


# ---------------------------------------------------------------- histograms
def test_histogram_exact_bucket_edges():
    h = LogHistogram(start=1.0, factor=2.0)
    # exact powers of two land in the bucket they open, never below
    assert h.bucket_index(1.0) == 0
    assert h.bucket_index(2.0) == 1
    assert h.bucket_index(4.0) == 2
    assert h.bucket_index(3.999999) == 1
    assert h.bucket_index(0.5) == -1
    lo, hi = h.bucket_edges(3)
    assert (lo, hi) == (8.0, 16.0)


@pytest.mark.parametrize("factor", [2.0, 2 ** 0.5, 2 ** 0.25, 10.0])
def test_histogram_index_consistent_with_edges(factor):
    """bucket_index must agree with bucket_edges on the edge lattice
    itself — the float log/floor estimate is snapped, so an exact edge
    value always opens its own bucket."""
    h = LogHistogram(start=1e-5, factor=factor)
    for i in range(-40, 41):
        lo, hi = h.bucket_edges(i)
        assert h.bucket_index(lo) == i
        got = h.bucket_index(math.nextafter(hi, 0.0))
        assert got == i, f"just-below-hi landed in {got}, want {i}"


def test_histogram_merge_exact():
    r = np.random.default_rng(0)
    a_vals = r.lognormal(0.0, 2.0, size=200)
    b_vals = r.lognormal(1.0, 1.0, size=300)
    a = LogHistogram(1e-6, 2.0)
    b = LogHistogram(1e-6, 2.0)
    both = LogHistogram(1e-6, 2.0)
    for v in a_vals:
        a.observe(v)
        both.observe(v)
    for v in b_vals:
        b.observe(v)
        both.observe(v)
    m = a.merge(b)
    assert m.counts == both.counts
    assert m.count == both.count == 500
    assert m.total == pytest.approx(both.total)
    assert (m.vmin, m.vmax) == (both.vmin, both.vmax)
    with pytest.raises(ValueError):
        a.merge(LogHistogram(1e-6, 4.0))


@pytest.mark.parametrize("q", [0.0, 10.0, 50.0, 90.0, 99.0, 100.0])
def test_histogram_percentile_vs_numpy(q):
    """Bucket-resolution percentile: the estimate brackets the exact
    nearest-rank value within one bucket factor, and p100 is exact."""
    r = np.random.default_rng(1)
    vals = r.lognormal(-3.0, 2.5, size=2000)
    factor = 2 ** 0.25
    h = LogHistogram(start=1e-6, factor=factor)
    for v in vals:
        h.observe(v)
    exact = np.sort(vals)[max(1, math.ceil(q / 100.0 * vals.size)) - 1]
    got = h.percentile(q)
    assert exact <= got <= exact * factor + 1e-12
    assert h.percentile(100.0) == vals.max()
    assert h.mean() == pytest.approx(vals.mean())


def test_histogram_underflow_and_empty():
    h = LogHistogram(start=1.0, factor=2.0)
    assert math.isnan(h.percentile(50))
    tiny = 2.0 ** -80            # below start * factor**-64
    h.observe(tiny)
    h.observe(8.0)
    assert h.underflow == 1
    assert h.count == 2
    assert h.percentile(0) == tiny      # underflow resolves to vmin
    assert h.percentile(100) == 8.0
    with pytest.raises(ValueError):
        h.percentile(101)
    with pytest.raises(ValueError):
        LogHistogram(start=0.0)
    with pytest.raises(ValueError):
        LogHistogram(factor=1.0)


# ------------------------------------------------------ registry + exporters
def test_registry_labels_and_expose():
    reg = MetricRegistry()
    c = reg.counter("req_total", "requests", labels=("backend",))
    c.inc(backend="rejection")
    c.inc(2, backend="mcmc")
    assert c.value(backend="rejection") == 1
    assert c.total() == 3
    with pytest.raises(ValueError):
        c.inc(backend="rejection", extra="nope")
    with pytest.raises(ValueError):
        c.inc(-1, backend="mcmc")
    g = reg.gauge("depth")
    g.set(4)
    h = reg.histogram("lat", "latency", labels=("backend",),
                      start=1e-3, factor=2.0)
    h.observe(0.25, backend="rejection")
    h.observe(0.5, backend="rejection")
    text = reg.expose()
    assert '# TYPE req_total counter' in text
    assert 'req_total{backend="mcmc"} 2' in text
    assert "depth 4" in text
    assert '# TYPE lat histogram' in text
    assert 'lat_bucket{backend="rejection",le="0.256"} 1' in text
    assert 'lat_bucket{backend="rejection",le="0.512"} 2' in text
    assert 'lat_bucket{backend="rejection",le="+Inf"} 2' in text
    assert 'lat_count{backend="rejection"} 2' in text
    # get-or-create is idempotent; schema conflicts are errors
    assert reg.counter("req_total", labels=("backend",)) is c
    with pytest.raises(ValueError):
        reg.gauge("req_total")
    with pytest.raises(ValueError):
        reg.counter("req_total", labels=("other",))
    with pytest.raises(ValueError):
        reg.histogram("lat", labels=("backend",), start=1.0, factor=2.0)
    snap = reg.snapshot()
    assert snap["req_total"]["values"]["backend=mcmc"] == 2
    assert snap["lat"]["values"]["backend=rejection"]["count"] == 2
    json.dumps(snap)  # snapshot must be JSON-safe as-is


def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(7):
        fr.record("tick", n=i)
    assert len(fr) == 4
    assert fr.total == 7
    assert fr.dropped == 3
    assert [e["n"] for e in fr.events()] == [3, 4, 5, 6]
    assert [e["seq"] for e in fr.events()] == [3, 4, 5, 6]
    fr.record("retire", rid=1, trials=np.int64(9))  # numpy must serialize
    path = tmp_path / "flight.jsonl"
    assert fr.dump(str(path)) == 4
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines[-1]["event"] == "retire" and lines[-1]["trials"] == 9
    assert [e["event"] for e in fr.events("retire")] == ["retire"]
    # monotone within the buffer
    ts = [e["t"] for e in fr.events()]
    assert ts == sorted(ts)


def test_span_lifecycle():
    s = Span(rid=3, seed=7, backend="rejection")
    assert s.state == "queued" and s.queue_wait is None and s.wall is None
    s.admit(slot=2, version=5)
    assert s.state == "active" and s.queue_wait >= 0
    s.ticks_held += 2
    s.retire(trials=9, accepted=True)
    assert s.state == "retired"
    assert s.wall >= s.queue_wait
    snap = s.snapshot()
    assert snap["pinned_version"] == 5 and snap["trials"] == 9
    json.dumps(snap)


def test_span_abandon_terminal_states():
    """A queued request abandoned before admission terminates as
    ``shed``/``cancelled`` — and only a queued one can be abandoned."""
    for outcome in Span.TERMINAL_ABANDONED:
        s = Span(rid=1, seed=0, backend="rejection")
        s.abandon(outcome)
        assert s.state == outcome and s.t_retire is not None
        # never admitted: queue_wait stays None so histograms that observe
        # at admit/retire can't see this request
        assert s.queue_wait is None and s.service_time is None
        json.dumps(s.snapshot())
        with pytest.raises(ValueError, match="only queued"):
            s.abandon(outcome)     # already terminal
    s = Span(rid=2, seed=0, backend="rejection")
    with pytest.raises(ValueError, match="outcome must be one of"):
        s.abandon("lost")
    s.admit(slot=0)
    with pytest.raises(ValueError, match="only queued"):
        s.abandon("cancelled")     # admitted requests always retire


def test_cancel_keeps_wait_histograms_clean(sampler):
    """Engine-level cancel: the span ends ``cancelled``, the abandoned
    counter and flight recorder see it, and the queue-wait / latency
    histograms count only the requests that were actually served."""
    tel = Telemetry()
    eng = SamplerEngine(sampler, n_slots=2, telemetry=tel)
    for i in range(6):
        eng.submit(SampleRequest(rid=i, seed=i, max_trials=200))
    # 4, 5 still queued (pool is 2-wide and no tick has run)
    assert eng.cancel(4) and eng.cancel(5, outcome="shed")
    assert not eng.cancel(4)       # already gone
    assert not eng.cancel(99)      # never submitted
    done = eng.run()
    assert sorted(done) == [0, 1, 2, 3]
    reg = tel.registry
    ab = reg.get("ndpp_requests_abandoned_total")
    assert ab.total() == 2
    assert ab.value(backend="rejection", outcome="cancelled") == 1
    assert ab.value(backend="rejection", outcome="shed") == 1
    # unpolluted: exactly one observation per *served* request, none for
    # the abandoned pair (their spans never reached admit/retire)
    assert reg.get("ndpp_queue_wait_seconds").data(
        backend="rejection").count == 4
    assert reg.get("ndpp_request_latency_seconds").data(
        backend="rejection").count == 4
    evs = tel.flight.events("abandon")
    assert [(e["rid"], e["outcome"]) for e in evs] == [
        (4, "cancelled"), (5, "shed")]


# ------------------------------------------------- instrumentation is free
def _drain(sampler, telemetry, n=12, **kw):
    eng = SamplerEngine(sampler, n_slots=4, telemetry=telemetry, **kw)
    for i in range(n):
        eng.submit(SampleRequest(rid=i, seed=i, max_trials=200))
    return eng, eng.run()


def test_rejection_draws_bit_identical_with_metrics(sampler):
    tel = Telemetry()
    _, bare = _drain(sampler, None)
    eng, inst = _drain(sampler, tel)
    assert sorted(bare) == sorted(inst)
    for rid in bare:
        assert np.array_equal(bare[rid].items, inst[rid].items)
        assert np.array_equal(bare[rid].mask, inst[rid].mask)
        assert bare[rid].trials == inst[rid].trials
        assert bare[rid].accepted == inst[rid].accepted
    # the registry really filled, and agrees with ground truth
    reg = tel.registry
    assert reg.get("ndpp_requests_retired_total").total() == len(bare)
    lat = reg.get("ndpp_request_latency_seconds").data(backend="rejection")
    assert lat.count == len(bare) and lat.vmin > 0
    n_acc = sum(r.accepted for r in bare.values())
    tri = reg.get("ndpp_request_trials").data(backend="rejection")
    assert tri.count == n_acc
    assert tri.total == sum(r.trials for r in bare.values() if r.accepted)
    ev = [e["event"] for e in tel.flight.events()]
    assert ev.count("submit") == len(bare) == ev.count("retire")
    st = eng.stats()
    assert st["finished"] == len(bare) and "metrics" in st


def test_mcmc_draws_bit_identical_with_metrics(sampler):
    tel = Telemetry()
    kw = dict(backend="mcmc", mcmc_burn_in=32, mcmc_thin=8, n=6)
    _, bare = _drain(sampler, None, **kw)
    _, inst = _drain(sampler, tel, **kw)
    for rid in bare:
        assert np.array_equal(bare[rid].items, inst[rid].items)
        assert np.array_equal(bare[rid].mask, inst[rid].mask)
    frac = tel.registry.get("ndpp_mcmc_accept_fraction").data()
    assert frac.count > 0 and 0.0 <= frac.vmax <= 1.0
    assert tel.registry.get("ndpp_mcmc_steps_total").total() > 0


def test_observer_matches_returned_trials(sampler):
    """RegistryObserver through sample_batched_many: the histogram must
    reproduce the exact trial counts the sampler returns."""
    import jax

    reg = MetricRegistry()
    res = jax.device_get(
        __import__("repro.core.rejection", fromlist=["x"]).sample_batched_many(
            sampler, jax.random.PRNGKey(3), 16, max_trials=400,
            observer=RegistryObserver(reg)))
    tri = reg.get("ndpp_request_trials").data(backend="rejection")
    acc = res.accepted
    assert tri.count == int(acc.sum())
    assert tri.total == float(res.trials[acc].sum())
    assert reg.get("ndpp_trials_total").total() == float(res.trials.sum())
    # per-round accounting is self-consistent
    assert (reg.get("ndpp_proposals_total").total()
            >= reg.get("ndpp_accepts_total").total())


# ------------------------------------------------- run() tick-budget bugfix
def test_run_exhausted_raises_with_span_state(sampler, tmp_path):
    dump = tmp_path / "flight.jsonl"
    tel = Telemetry(dump_on_error=str(dump))
    # MCMC needs burn_in+thin steps per request, so one 16-step tick
    # deterministically leaves every admitted chain in flight
    eng = SamplerEngine(sampler, n_slots=2, telemetry=tel, backend="mcmc",
                        mcmc_burn_in=64, mcmc_thin=8,
                        mcmc_steps_per_tick=16)
    for i in range(8):
        eng.submit(SampleRequest(rid=i, seed=i))
    with pytest.raises(TickBudgetExhausted) as ei:
        eng.run(max_ticks=1)
    err = ei.value
    assert err.unfinished and err.queued
    for state in err.unfinished.values():
        assert state["state"] == "active" and state["ticks_held"] >= 1
    assert set(err.unfinished).isdisjoint(err.queued)
    # flight event emitted and recorder dumped to the error path
    ev = tel.flight.events("tick_budget_exhausted")
    assert len(ev) == 1 and ev[0]["queued"] == err.queued
    assert dump.exists()
    assert any(json.loads(l)["event"] == "tick_budget_exhausted"
               for l in dump.read_text().splitlines())


def test_run_exhausted_warn_and_ignore(sampler):
    eng = SamplerEngine(sampler, n_slots=2, on_exhausted="warn")
    for i in range(8):
        eng.submit(SampleRequest(rid=i, seed=i))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        partial = eng.run(max_ticks=1)
    assert len(w) == 1 and issubclass(w[0].category, RuntimeWarning)
    assert "still queued" in str(w[0].message)
    assert len(partial) < 8          # the old silent behavior, now opt-in

    eng = SamplerEngine(sampler, n_slots=2, on_exhausted="ignore")
    for i in range(8):
        eng.submit(SampleRequest(rid=i, seed=i))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng.run(max_ticks=1)         # must not warn or raise

    with pytest.raises(ValueError):
        SamplerEngine(sampler, on_exhausted="explode")


def test_run_completes_cleanly_never_raises(sampler):
    eng = SamplerEngine(sampler, n_slots=4)
    for i in range(6):
        eng.submit(SampleRequest(rid=i, seed=i, max_trials=200))
    out = eng.run()                  # default on_exhausted="raise"
    assert len(out) == 6


# --------------------------------------------------------- catalog events
def test_catalog_mutations_and_swap_events():
    from repro.serve.catalog import Catalog

    r = np.random.default_rng(11)
    v = (r.normal(size=(24, K)) * 0.5).astype(np.float32)
    b = (r.normal(size=(24, K)) * 0.5).astype(np.float32)
    d = r.normal(size=(K, K)).astype(np.float32)
    tel = Telemetry()
    cat = Catalog(v, b, d, block=4, staleness=2, telemetry=tel)
    ids = cat.insert_items(v[:3] * 0.9, b[:3] * 0.9)
    cat.update_items(ids[:2], v[:2] * 0.8, b[:2] * 0.8)
    cat.delete_items(ids[:1])
    cat.refresh()
    ops = [e["event"] for e in tel.flight.events()]
    for want in ("catalog_build", "catalog_insert", "catalog_update",
                 "catalog_delete", "catalog_refresh"):
        assert want in ops, f"missing {want} in {ops}"
    mut = tel.registry.get("ndpp_catalog_mutations_total")
    assert mut.value(op="insert") == 1
    assert tel.registry.get("ndpp_catalog_items").value() == cat.m

    # engine swap event carries version provenance and in-flight rids
    eng = SamplerEngine(cat, n_slots=2, telemetry=tel)
    for i in range(4):
        eng.submit(SampleRequest(rid=i, seed=i, max_trials=400))
    cat.insert_items(v[:1] * 0.7, b[:1] * 0.7)
    old_v = eng._cat.version
    eng.swap_catalog(cat)
    swaps = tel.flight.events("catalog_swap")
    assert len(swaps) == 1
    assert swaps[0]["from_version"] == old_v
    assert swaps[0]["version"] == cat.version > old_v
    assert tel.registry.get("ndpp_catalog_version").value() == cat.version
    eng.run()
    assert len(eng.finished) == 4


# ----------------------------------------------------------- profiler gate
def test_profile_gate_defaults_off(monkeypatch):
    from repro.obs import trace

    monkeypatch.delenv(trace.PROFILE_ENV, raising=False)
    assert Telemetry().profile is False
    monkeypatch.setenv(trace.PROFILE_ENV, "1")
    assert Telemetry().profile is True
    # a disabled span builds no annotation and never reaches the
    # profiler: it wraps the shared no-op object, and still records
    def no_profiler(name, enabled):
        raise AssertionError("a disabled span built an annotation")

    monkeypatch.setattr(trace, "annotation", no_profiler)
    got = []
    sp = trace.span("ndpp_engine_tick/rejection", got.append, False)
    with sp:
        pass
    assert sp._ann is trace._NULL
    assert len(got) == 1 and got[0] >= 0.0


# ------------------------------------------------------- spans and set-up
def _setup(stage):
    """(host seconds, compile seconds) a set-up stage has recorded."""
    from repro.obs import startup_registry

    reg = startup_registry()
    return (reg.counter("ndpp_setup_seconds_total", labels=("stage",))
            .value(stage=stage),
            reg.counter("ndpp_setup_compile_seconds_total",
                        labels=("stage",)).value(stage=stage))


def test_span_records_host_seconds_even_on_error():
    from repro.obs import now, span

    got = []
    t0 = now()
    with span("ndpp_phase/harvest", got.append, False):
        inner = now()
    assert 0.0 <= got[0] <= now() - t0 and inner >= t0
    with pytest.raises(RuntimeError):
        with span("ndpp_phase/harvest", got.append, False):
            raise RuntimeError("boom")
    assert len(got) == 2 and got[1] >= 0.0


def test_compile_counter_sums_compile_seconds():
    import jax
    import jax.numpy as jnp

    from repro.analysis.runtime import CompileCounter

    cc = CompileCounter.install()
    n0, s0 = cc.count, cc.seconds
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.125)
    jax.block_until_ready(f(np.ones(7, np.float32)))
    assert cc.count == n0 + 1 and cc.seconds > s0
    n1, s1 = cc.count, cc.seconds
    jax.block_until_ready(f(np.ones(7, np.float32)))
    assert (cc.count, cc.seconds) == (n1, s1)


def test_preprocess_records_setup_stages():
    import jax

    r = np.random.default_rng(5)
    m, k = 40, 4
    v = (r.normal(size=(m, k)) * 0.6).astype(np.float32)
    b = (r.normal(size=(m, k)) * 0.6).astype(np.float32)
    d = r.normal(size=(k, k)).astype(np.float32)
    stages = ("youla", "proposal_eigens", "tree_build")
    before = {st: _setup(st) for st in stages}
    # leak checking (the strict leg) recompiles every eager op
    with jax.check_tracer_leaks(False):
        preprocess(v, b, d, block=4)
        mid = {st: _setup(st) for st in stages}
        preprocess(v, b, d, block=4)  # same shapes: every program cached
    for st in stages:
        assert mid[st][0] > before[st][0], st
    for st in stages:
        secs, comp = _setup(st)
        assert secs > mid[st][0], st
        assert comp == mid[st][1], st


@pytest.mark.parametrize("backend", ["rejection", "mcmc"])
def test_engine_phase_spans_within_tick(sampler, backend):
    """Per engine tick: each phase is entered once, and the phases'
    host seconds sum to at most the tick's seconds."""
    from repro.obs import engine_instruments
    from repro.obs.prof import phases as ph

    tel = Telemetry()
    m = engine_instruments(tel.registry)
    entries = []
    inc = m.phase_seconds.inc

    def counting_inc(v=1.0, **labels):
        entries.append(labels["phase"])
        inc(v, **labels)

    m.phase_seconds.inc = counting_inc
    kw = (dict(backend="mcmc", mcmc_burn_in=16, mcmc_thin=8,
               mcmc_steps_per_tick=8) if backend == "mcmc" else {})
    eng = SamplerEngine(sampler, n_slots=3, telemetry=tel, **kw)
    first = _setup(f"first_tick/{backend}")[0]
    for i in range(5):
        eng.submit(SampleRequest(rid=i, seed=i, max_trials=200))
    tick_h = tel.registry.get("ndpp_tick_seconds")
    ticks = 0
    while True:
        n0 = len(entries)
        t0 = tick_h.data(backend=backend).total
        p0 = m.phase_seconds.total()
        if not eng.step():
            break
        ticks += 1
        assert sorted(entries[n0:]) == sorted(ph.ENGINE_PHASES)
        tick_s = tick_h.data(backend=backend).total - t0
        assert 0.0 < m.phase_seconds.total() - p0 <= tick_s
    assert ticks > 1 and eng.ticks == ticks
    assert m.ticks.value(backend=backend) == ticks
    assert tick_h.data(backend=backend).count == ticks
    for p in ph.ENGINE_PHASES:
        assert m.phase_seconds.value(backend=backend, phase=p) > 0.0
    # the engine's first tick that progressed is its own set-up stage
    assert _setup(f"first_tick/{backend}")[0] > first


def test_frontdoor_feeds_sched_phase_seconds(sampler):
    import asyncio

    from repro.obs.prof import phases as ph
    from repro.serve.frontdoor import FrontDoor
    from repro.serve.scheduler import Scheduler

    tel = Telemetry()

    async def main():
        pools = {"rej": SamplerEngine(sampler, n_slots=2, n_spec=4,
                                      telemetry=tel)}
        async with FrontDoor(Scheduler(pools, telemetry=tel),
                             idle_interval=0.001) as door:
            await asyncio.gather(*(door.sample(s, rid=s, max_trials=400)
                                   for s in range(5)))

    asyncio.run(main())
    c = tel.registry.get("ndpp_sched_phase_seconds_total")
    assert sorted(k[0] for k, _ in c.labelsets()) == sorted(ph.SCHED_PHASES)
    for p in ph.SCHED_PHASES:
        assert c.value(phase=p) > 0.0, p


def test_profile_capture_holds_setup_phase_and_tick_events(
        monkeypatch, tmp_path):
    """Under NDPP_PROFILE=1 every span is also a host annotation in a
    real (CPU) profiler capture."""
    import asyncio

    from repro.obs import trace
    from repro.obs.prof import capture as cap
    from repro.obs.prof import complete_events, load_trace
    from repro.obs.prof import phases as ph
    from repro.serve.frontdoor import FrontDoor
    from repro.serve.scheduler import Scheduler

    monkeypatch.setenv(trace.PROFILE_ENV, "1")
    r = np.random.default_rng(9)
    v = (r.normal(size=(M, K)) * 0.6).astype(np.float32)
    b = (r.normal(size=(M, K)) * 0.6).astype(np.float32)
    d = r.normal(size=(K, K)).astype(np.float32)

    async def serve(smp):
        tel = Telemetry()
        pools = {"rej": SamplerEngine(smp, n_slots=2, n_spec=4,
                                      telemetry=tel)}
        async with FrontDoor(Scheduler(pools, telemetry=tel),
                             idle_interval=0.001) as door:
            await asyncio.gather(*(door.sample(s, rid=s, max_trials=400)
                                   for s in range(3)))

    log_dir = str(tmp_path / "prof")
    try:
        with cap.capture(log_dir):
            asyncio.run(serve(preprocess(v, b, d, block=4)))
    except cap.ProfilerUnavailable as e:
        pytest.skip(f"profiler not available here: {e}")
    names = {e["name"] for e in complete_events(
        load_trace(cap.trace_path(log_dir)))}
    want = {trace.SETUP_PREFIX + st for st in (
        "youla", "proposal_eigens", "tree_build", "engine_init/rejection",
        "first_tick/rejection")}
    want |= {trace.PHASE_PREFIX + p for p in ph.HOST_PHASES}
    want.add("ndpp_engine_tick/rejection")
    assert want <= names, sorted(want - names)


def test_lowered_round_carries_descent_operands_scope(monkeypatch):
    """The per-round flattening of the levels into the descent kernel's
    layout is its own device scope (kernel path, interpreted here)."""
    import jax.numpy as jnp

    from repro.core.rejection import _spec_round_fused
    from repro.obs.prof import phases as ph

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    r = np.random.default_rng(13)
    m, k = 56, 4                       # shapes no other test lowers
    smp = preprocess(jnp.asarray(r.normal(size=(m, k)) * 0.5, jnp.float32),
                     jnp.asarray(r.normal(size=(m, k)) * 0.5, jnp.float32),
                     jnp.asarray(r.normal(size=(k, k)), jnp.float32),
                     block=4)
    keys = np.zeros((3, 2), np.uint32)
    trials = np.zeros(3, np.uint32)
    text = _spec_round_fused.lower(smp, keys, trials, n_spec=5).as_text(
        debug_info=True)
    assert ph.DESCENT_OPERANDS in ph.DEVICE_SCOPES
    assert ph.DESCENT_OPERANDS + "/" in text
    assert ph.TREE_DESCENT in text


def test_v1_metrics_exposes_setup_counters(sampler):
    import asyncio
    import threading
    import urllib.request

    from repro.serve.frontdoor import FrontDoor, serve_http
    from repro.serve.scheduler import Scheduler

    async def main():
        tel = Telemetry()
        pools = {"rej": SamplerEngine(sampler, n_slots=2, n_spec=4,
                                      telemetry=tel)}
        async with FrontDoor(Scheduler(pools, telemetry=tel),
                             idle_interval=0.001) as door:
            await door.sample(3, rid=1, max_trials=400)
            srv = serve_http(door, asyncio.get_running_loop())
            thread = threading.Thread(target=srv.serve_forever, daemon=True)
            thread.start()
            host, port = srv.server_address

            def get():
                with urllib.request.urlopen(
                        f"http://{host}:{port}/v1/metrics", timeout=30) as r:
                    return r.status, r.read().decode()

            try:
                return await asyncio.get_running_loop().run_in_executor(
                    None, get)
            finally:
                srv.shutdown()

    code, text = asyncio.run(main())
    assert code == 200
    assert "ndpp_sched_phase_seconds_total" in text
    assert 'ndpp_setup_seconds_total{stage="engine_init/rejection"}' in text
    assert "# TYPE ndpp_setup_compile_seconds_total counter" in text
