"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

Everything that belongs to a configuration, a traffic mix, a check or a
metric is read from files found by name:

- ``BENCHMARK.json`` names the cell's configuration, traffic and metrics;
- ``bench/configs/<config>.json``: catalog sizes and serving pools;
- ``bench/traffic/<mix>.json``: read by ``traffic.Traffic``;
- ``bench/checks/<backend>.py`` (+ ``.json`` limits): the comparison of a
  pool backend's answers with the reference;
- ``bench/metrics/<metric>.py``: ``read(run) -> float | None``.

The window drives ``FrontDoor.sample()`` → ``Scheduler`` →
``SamplerEngine`` pools, as a deployment does.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: seconds past the window's close that answers due in it may take
DRAIN_S = 60.0
#: where a traced run writes its trace (removed once it is read)
TRACE_DIR = ROOT / ".bench" / "trace"
#: request ids of the warm-up waves, far above the traffic's
WARMUP_RID = 1 << 40


class BenchError(RuntimeError):
    """A run that cannot be made: no result is printed."""


def now() -> float:
    return time.perf_counter()


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """A benchmark module by file path (metric and check names may hold
    dots, so they are not importable by name)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bm = load_json(root / "BENCHMARK.json")
    cell = next((w for w in bm["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bm["configs"] if c["name"] == cell["config"])

    def here(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bm["end_to_end"] if here(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"]
             if workload in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in names)]
    return Cell(name=workload, chips=int(cell["chips"]),
                config=load_json(root / conf["file"]),
                traffic=load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
                end_to_end=e2e, per_layer=layer)


# ------------------------------------------------------------- compile stats


class CompileStats:
    """Backend compiles and persistent-cache hits, counted from JAX's
    monitoring events."""

    def __init__(self, jax):
        self.n = 0
        self.s = 0.0
        self.cache_hits = 0

        def on_duration(name, secs, **kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.s += secs

        def on_event(name, **kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def setup_jax(require_chip: bool, chips: int):
    """Compile cache at a fixed path in the checkout, every program kept;
    the devices the cell asks for."""
    if require_chip and "REPRO_PALLAS_INTERPRET" in os.environ:
        raise BenchError("REPRO_PALLAS_INTERPRET is set: the benchmark runs "
                         "compiled kernels only")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX reports {devs[0].platform}")
        if len(devs) < chips:
            raise BenchError(f"the cell needs {chips} chips, JAX finds "
                             f"{len(devs)}")
    return jax, devs[:chips]


# ----------------------------------------------------------------- the run


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: Cell
    seed: int
    setup_s: float
    t_open: float
    t_close: float
    requests: list                 # traffic.Request sent inside the window
    delta: Dict[tuple, float]      # registry changes over the window
    pools: Dict[str, dict]         # name -> backend, n_slots, n_spec
    facts: dict                    # catalog facts (depth, r, block, ...)
    peak: Optional[dict] = None
    trace: object = None           # devtrace.TraceSummary in a traced run

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def counter(self, name: str, *labels: str) -> float:
        return self.delta.get((name, tuple(labels)), 0.0)

    def backend_pools(self, backend: str) -> List[str]:
        return [n for n, p in self.pools.items() if p["backend"] == backend]

    def consumed_trials(self, backend: str) -> float:
        """Trials that requests of a backend's pools consumed inside the
        window: each answered request's trials spread evenly over the time
        from its send to its answer, and the part inside the window kept."""
        names = set(self.backend_pools(backend))
        total = 0.0
        for r in self.requests:
            if r.pool not in names or r.done is None:
                continue
            span = r.done - r.submit
            inside = min(r.done, self.t_close) - max(r.submit, self.t_open)
            if span > 0 and inside > 0:
                total += r.trials * inside / span
        return total


def snapshot(registry) -> Dict[tuple, float]:
    """Counters, and histogram counts and sums, by label values."""
    out = {}
    for name in registry.names():
        m = registry.get(name)
        for key, child in m.labelsets():
            if m.kind == "counter":
                out[(name, key)] = float(child)
            elif m.kind == "histogram":
                out[(name + ":count", key)] = float(child.count)
                out[(name + ":sum", key)] = float(child.total)
    return out


def build_pools(cell: Cell, sampler, telemetry) -> dict:
    """The pools the cell's traffic uses, as its configuration states."""
    from repro.serve.sampler_engine import SamplerEngine

    pools = {}
    for name in dict.fromkeys(e["pool"] for e in cell.traffic["mix"]):
        p = dict(cell.config["pools"][name])
        backend = p.pop("backend")
        pools[name] = SamplerEngine(sampler, backend=backend,
                                    telemetry=telemetry, **p)
    return pools


def tick_scope_map(pools) -> Dict[str, str]:
    """Scope paths of the ops of the rejection pools' tick program: the
    tick is lowered again (a compile-cache hit) and its HLO text read."""
    import devtrace
    from repro.core.rejection import _spec_round_fused

    out: Dict[str, str] = {}
    for eng in pools.values():
        if eng.backend == "rejection" and eng.sampler is not None \
                and eng.mesh is None:
            trials = np.zeros(eng.n_slots, np.uint32)
            text = _spec_round_fused.lower(
                eng.sampler, eng.slot_key, trials,
                n_spec=eng.n_spec).compile().as_text()
            out.update(devtrace.hlo_scope_map(text))
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float, require_chip: bool = True, root: Path = ROOT,
             precision: Optional[str] = None, log=None) -> dict:
    """One run; returns the result line's object.  ``require_chip=False``
    skips the look for a TPU (the benchmark's own tests run on the CPU);
    ``precision`` runs the program at another matmul precision than its
    configuration states (the control, ``control.py``)."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = load_cell(workload, root)
    jax, devs = setup_jax(require_chip, cell.chips)

    from catalog import make_catalog, seed_key
    from traffic import Traffic
    import work

    from repro.core import det_ratio_exact, preprocess
    from repro.obs import Telemetry
    from repro.serve.frontdoor import FrontDoor, ShedError
    from repro.serve.scheduler import Scheduler

    stats = CompileStats(jax)
    conf = cell.config
    # the program runs at the precision its configuration states
    jax.config.update("jax_default_matmul_precision",
                      precision or conf["matmul_precision"])
    m, rank, block = int(conf["items"]), int(conf["rank"]), int(conf["leaf_block"])
    v, b, d = make_catalog(seed_key(int(conf["catalog_seed"]), 0),
                           seed_key(seed, 1), m=m, k=rank // 2,
                           n_clusters=int(conf["clusters"]))
    jax.block_until_ready((v, b, d))
    t_pre = now()
    sampler = preprocess(v, b, d, block=block)
    jax.block_until_ready(sampler)
    pre_s = now() - t_pre
    e_prog = float(det_ratio_exact(sampler.sp))
    log(f"catalog: M={m} K={rank} block={block} depth={sampler.tree.depth} "
        f"preprocess {pre_s:.3f} s; E[trials] (program) {e_prog:.3f}")

    tel = Telemetry(profile=trace)
    pools = build_pools(cell, sampler, tel)
    door = FrontDoor(Scheduler(pools, telemetry=tel))
    gen = Traffic(cell.traffic, seed)
    budget = {name: int(gen.pool_params(name).get("max_trials_per_expected", 16)
                        * math.ceil(e_prog)) for name in pools}
    facts = {"items": m, "r": rank, "block": block,
             "depth": sampler.tree.depth}
    pool_info = {n: {"backend": e.backend, "n_slots": e.n_slots,
                     "n_spec": getattr(e, "n_spec", None)}
                 for n, e in pools.items()}
    reqs: list = []
    marks: dict = {}

    async def send(r, max_trials):
        r.submit = now()
        try:
            res = await door.sample(r.seed, rid=r.rid, pool=r.pool,
                                    max_trials=max_trials)
        except ShedError:
            r.error = "shed"
            return
        r.done = now()
        r.trials, r.accepted = int(res.trials), bool(res.accepted)
        r.items = np.asarray(res.items)

    async def session():
        door.start()
        # warm-up: one full wave per pool, each request one tick long
        # (a rejection budget of one round), so every shape compiles here
        rid = WARMUP_RID
        for name, eng in pools.items():
            short = eng.n_spec if eng.backend == "rejection" else 1
            seeds = gen.warmup_seeds(eng.n_slots)
            await asyncio.gather(*(
                door.sample(s, rid=rid + i, pool=name, max_trials=short)
                for i, s in enumerate(seeds)))
            rid += len(seeds)
        marks["compiles_setup"] = (stats.n, stats.s, stats.cache_hits)
        prof = None
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            prof = jax.profiler.TraceAnnotation("bench.window")
            prof.__enter__()
        t_open = now()
        marks["t_open"] = t_open
        marks["snap0"] = snapshot(tel.registry)
        marks["compiles0"] = stats.n
        t_close = t_open + seconds
        tasks = []
        if gen.loop == "closed":
            async def client():
                while now() < t_close:
                    r = gen.next(now())
                    reqs.append(r)
                    await send(r, budget[r.pool])

            tasks = [asyncio.ensure_future(client())
                     for _ in range(int(cell.traffic["clients"]))]
        else:
            async def opener():
                sent = []
                for due in gen.due_times(t_open, t_close):
                    if due > now():
                        await asyncio.sleep(due - now())
                    r = gen.next(due)
                    reqs.append(r)
                    sent.append(asyncio.ensure_future(send(r, budget[r.pool])))
                await asyncio.gather(*sent)

            tasks = [asyncio.ensure_future(opener())]
        await asyncio.sleep(max(0.0, t_close - now()))
        marks["t_close"] = now()
        marks["snap1"] = snapshot(tel.registry)
        marks["compiles1"] = stats.n
        if prof is not None:
            prof.__exit__(None, None, None)
        _, pending = await asyncio.wait(tasks, timeout=DRAIN_S)
        for t in pending:
            t.cancel()
        if not pending:
            await door.drain()
        if prof is not None:
            # stopped once every answer is in: writing the trace stalls
            # the event loop, which would stretch the answers' intervals
            jax.profiler.stop_trace()

    asyncio.run(session())
    t_open, t_close = marks["t_open"], marks["t_close"]
    setup_s = t_open - t0
    window_compiles = marks["compiles1"] - marks["compiles0"]
    n_c, s_c, hits = marks["compiles_setup"]
    log(f"set-up {setup_s:.3f} s: {n_c} programs compiled or loaded "
        f"({s_c:.3f} s), {hits} of them from the persistent cache; "
        f"{window_compiles} inside the window")
    if window_compiles:
        raise BenchError(f"{window_compiles} compiles inside the window")
    s0, s1 = marks["snap0"], marks["snap1"]
    delta = {k: s1.get(k, 0.0) - s0.get(k, 0.0) for k in set(s0) | set(s1)}

    peak_bytes = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for dv in devs)
    run = Run(cell=cell, seed=seed, setup_s=setup_s, t_open=t_open,
              t_close=t_close, requests=reqs, delta=delta, pools=pool_info,
              facts=facts)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak_bytes)}
    if require_chip:
        run.peak = work.peaks(devs[0].device_kind)
    if trace:
        from jax.profiler import ProfileData
        import devtrace

        files = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        if not files:
            raise BenchError("the traced run wrote no .xplane.pb")
        profile = ProfileData.from_file(str(files[-1]))
        run.trace = devtrace.reduce(profile, chips=len(devs),
                                    scope_map=tick_scope_map(pools))
        log("device seconds per scope: " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(run.trace.scopes.items())))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)

    # the program's state goes before the reference runs
    del door, pools, sampler, tel
    gc.collect()

    checks = {}
    backends = {p["backend"] for p in pool_info.values()}
    for backend in sorted(backends):
        mod = load_module(BENCH / "checks" / f"{backend}.py")
        mine = [r for r in reqs if pool_info[r.pool]["backend"] == backend]
        numbers, found = mod.check(mine, catalog=(v, b, d), facts=facts,
                                   seed=seed, log=log)
        run.facts.update(found)
        checks.update(numbers)

    metrics = {}
    for spec in (cell.per_layer if trace else cell.end_to_end):
        val = load_module(BENCH / "metrics" / f"{spec['name']}.py").read(run)
        if val is not None:
            metrics[spec["name"]] = {"value": float(val), "unit": spec["unit"]}

    failed = sum(1 for r in reqs if r.done is None or not r.accepted)
    never = sum(1 for r in reqs if r.done is None)
    correct = never == 0 and all(val <= lim for val, lim in checks.values())
    retired = sum(1 for r in reqs if r.accepted and r.done <= t_close)
    for name, p in pool_info.items():
        if p["backend"] == "rejection":
            log(f"pool {name}: {run.counter('ndpp_ticks_total', 'rejection'):.0f} "
                f"ticks, {run.counter('ndpp_proposals_total', 'rejection'):.0f} "
                f"proposals scored, {run.consumed_trials('rejection'):.1f} "
                f"trials consumed inside the window")
    log(f"window {run.window_s:.3f} s: {len(reqs)} requests sent, "
        f"{len(reqs) - failed} accepted, {failed} failed ({never} never "
        f"answered); {retired} draws retired inside the window "
        f"({retired / run.window_s:.3f}/s)")
    for name, (val, lim) in checks.items():
        log(f"check {name} = {val!r} limit {lim!r} "
            f"{'ok' if val <= lim else 'FAIL'}")
    out = {"correct": bool(correct), "attempted": len(reqs), "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = run.trace.breakdown()
    out["check"] = {name: {"value": val, "limit": lim}
                    for name, (val, lim) in checks.items()}
    return out
