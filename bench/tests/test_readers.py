"""The per-layer readers of the program's spans, on a tiny catalog on the
CPU: each reads a value from the program's counters, and reads None (no
error) where the program lacks the span or counter.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import asyncio
import types

import numpy as np
import pytest

from tiny import BENCH

import harness

READERS = ("setup_youla_s", "setup_eigens_s", "setup_tree_s",
           "setup_warmup_s.rej", "setup_compile_s", "setup_rest_s",
           "dispatch_ms.rej", "harvest_ms.rej", "pump_ms.rej",
           "operands_ms.rej")


def _read(name, run):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(run)


@pytest.fixture(scope="module")
def served():
    """A run's view of 6 requests through FrontDoor → Scheduler → one
    rejection pool, set-up included.  The set-up counters start empty,
    as in the fresh process of a benchmark run."""
    import repro.obs

    kept = repro.obs._STARTUP
    repro.obs._STARTUP = None
    try:
        yield _serve()
    finally:
        repro.obs._STARTUP = kept


def _serve():
    from repro.core import preprocess
    from repro.obs import Telemetry
    from repro.serve.frontdoor import FrontDoor
    from repro.serve.sampler_engine import SamplerEngine
    from repro.serve.scheduler import Scheduler

    t0 = harness.now()
    r = np.random.default_rng(3)
    v, b = (r.normal(size=(2, 64, 4)) * 0.5).astype(np.float32)
    smp = preprocess(v, b, r.normal(size=(4, 4)).astype(np.float32), block=4)
    tel = Telemetry()
    pools = {"rej": SamplerEngine(smp, n_slots=2, n_spec=4, telemetry=tel)}
    snaps = []

    async def main():
        async with FrontDoor(Scheduler(pools, telemetry=tel),
                             idle_interval=0.001) as door:
            await door.sample(99, rid=99, max_trials=400)   # warm-up
            snaps.append((harness.now(), harness.snapshot(tel.registry)))
            await asyncio.gather(*(door.sample(s, rid=s, max_trials=400)
                                   for s in range(6)))
            snaps.append((harness.now(), harness.snapshot(tel.registry)))

    asyncio.run(main())
    (t_open, s0), (t_close, s1) = snaps
    delta = {k: s1.get(k, 0.0) - s0.get(k, 0.0) for k in set(s0) | set(s1)}
    return harness.Run(
        cell=None, seed=0, setup_s=t_open - t0, t_open=t_open,
        t_close=t_close, requests=[], delta=delta,
        pools={"rej": {"backend": "rejection", "n_slots": 2, "n_spec": 4}},
        facts={}, trace=types.SimpleNamespace(
            scopes={"ndpp.descent_operands": 0.002}))


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_program(served, name):
    val = _read(name, served)
    assert val is not None and val >= 0.0, name
    if name.endswith("_s") and name != "setup_compile_s":
        assert val <= served.setup_s or name == "setup_rest_s"


def test_phases_fit_in_the_tick(served):
    tick = 1e3 * (served.counter("ndpp_tick_seconds:sum", "rejection")
                  / served.counter("ndpp_tick_seconds:count", "rejection"))
    assert _read("dispatch_ms.rej", served) + _read(
        "harvest_ms.rej", served) <= tick


@pytest.mark.parametrize("name", READERS)
def test_reader_without_the_spans_reads_none(served, name, monkeypatch):
    """As on a program that has none of these spans and counters."""
    import repro.obs

    monkeypatch.delattr(repro.obs, "startup_registry")
    bare = harness.Run(
        cell=None, seed=0, setup_s=served.setup_s, t_open=0.0, t_close=1.0,
        requests=[], delta={("ndpp_ticks_total", ("rejection",)): 5.0},
        pools=served.pools, facts={},
        trace=types.SimpleNamespace(scopes={"ndpp.proposal": 0.1}))
    assert _read(name, bare) is None
