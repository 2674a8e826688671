"""Smoke run of the NDPP serving path on a TPU, at catalog scale.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py                # one chip: serving + exactness
    python chip_smoke.py --four-chips   # item-sharded engines on 4 chips

One chip, one process:

1. catalog — seeded ``synthetic_features`` at M = 2^20, K = 100 (rank
   R = 100, rows scaled by 1/sqrt(M) as ``benchmarks/sampling_time.py``
   does), then ``preprocess(..., block=64)`` on the device;
2. serve — a ``Scheduler`` with a rejection pool "rej" and a fixed-size
   MCMC pool "mcmc" behind a ``FrontDoor``; one warm-up wave per pool,
   then 16 rejection and 8 MCMC requests through ``FrontDoor.sample()``
   with no compile allowed inside that window;
3. exactness — 8000 draws at M = 8, K = 4, block = 2 (tree depth 2, so
   the descent kernel runs) against the enumerated distribution, to the
   chi-square / TV bar of ``tests/_exactness.py``.

Every draw is checked on the host: unique items, in range, det(L_Y) > 0.
The rejection pool's mean trial count over n requests must sit within
1 -/+ 4/sqrt(n) of the exact E[trials] = det(Lhat + I) / det(L + I).  ``--four-chips`` runs only the
sharded phase: the same catalog on ``make_sampler_mesh(4)``, sharded
rejection and MCMC engines against this process's one-device engines on
the same seeds.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any failed
phase raises, so the exit code is non-zero and that line is not printed;
so is a run without a TPU, or with ``REPRO_PALLAS_INTERPRET`` set.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "tests"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from _exactness import (  # noqa: E402
    assert_chi_square_close,
    enumerate_subset_probs,
    histogram,
    tv_to_probs,
)
from repro.analysis.runtime import CompileCounter  # noqa: E402
from repro.core import (  # noqa: E402
    NDPPParams,
    det_ratio_exact,
    preprocess,
    sample_batched_many,
)
from repro.core import mcmc as mcmc_core  # noqa: E402
from repro.core.rejection import _spec_round_fused  # noqa: E402
from repro.core.types import dense_l  # noqa: E402
from repro.data.baskets import synthetic_features  # noqa: E402
from repro.kernels.spec_round import ops as spec_round_ops  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.obs import Telemetry  # noqa: E402
from repro.serve.frontdoor import FrontDoor  # noqa: E402
from repro.serve.sampler_engine import SampleRequest, SamplerEngine  # noqa: E402
from repro.serve.scheduler import Scheduler  # noqa: E402

#: the catalog: the largest point of the paper's sweep
#: (``benchmarks/sampling_time.py``), rank K = 100, leaf block 64
M_ITEMS, RANK, BLOCK = 1 << 20, 100, 64
#: band of mean rejection trials over E[trials] that the run must hit,
#: given the number of requests it averages: trials per request are
#: geometric, so the mean of n has a relative standard deviation of about
#: 1/sqrt(n), and the band is 1 -/+ 4 of those (0.18..1.82 at n = 24)
TRIALS_SIGMAS = 4.0


def trials_band(n: int) -> tuple:
    half = TRIALS_SIGMAS / float(np.sqrt(n))
    return (1.0 - half, 1.0 + half)

#: trial budget per rejection request, in multiples of E[trials]
BUDGET_FACTOR = 16
#: fixed-size MCMC chains: greedy size-k starts run the score_all kernel
MCMC_K = 8
MCMC_BURN_IN, MCMC_THIN = 128, 16
REJ_SLOTS, MCMC_SLOTS = 8, 4


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


@contextlib.contextmanager
def compile_stats():
    """Backend compiles inside the block: yields a dict that holds, once
    the block exits, their seconds (``s``), their count (``n``) and the
    programs the persistent compile cache served instead (``cache_hits``)."""
    stats = {"s": 0.0, "n": 0, "cache_hits": 0}
    active = [True]

    def on_duration(name, secs, **kw):
        if active[0] and name == "/jax/core/compile/backend_compile_duration":
            stats["s"] += secs
            stats["n"] += 1

    def on_event(name, **kw):
        if active[0] and name == "/jax/compilation_cache/cache_hits":
            stats["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield stats
    finally:
        active[0] = False


# ------------------------------------------------------------------ phases


def build_catalog(m: int, k: int, *, seed: int = 0, block: int = 64):
    """Seeded synthetic catalog of M items at rank K, preprocessed on the
    device.  Returns (sampler, preprocess seconds)."""
    v, b, d = synthetic_features(m, k // 2, seed=seed)
    scale = 1.0 / np.sqrt(m)
    v, b = v * scale, b * scale
    jax.block_until_ready((v, b, d))
    t0 = time.perf_counter()
    sampler = preprocess(v, b, d, block=block)
    jax.block_until_ready(sampler)
    return sampler, time.perf_counter() - t0


def make_engines(sampler, *, mesh=None, telemetry=None):
    """The two pools of the serving path: speculative rejection and
    fixed-size MCMC."""
    return {
        "rej": SamplerEngine(sampler, n_slots=REJ_SLOTS, mesh=mesh,
                             telemetry=telemetry),
        "mcmc": SamplerEngine(sampler, backend="mcmc", n_slots=MCMC_SLOTS,
                              mcmc_k=MCMC_K, mcmc_burn_in=MCMC_BURN_IN,
                              mcmc_thin=MCMC_THIN, mesh=mesh,
                              telemetry=telemetry),
    }


def serve(sampler, *, n_rej: int, n_mcmc: int, max_trials: int) -> dict:
    """Warm each pool with one full wave, then serve the window through
    ``FrontDoor.sample()``.  Returns draws, timings and compile counts."""
    tel = Telemetry()
    pools = make_engines(sampler, telemetry=tel)
    door = FrontDoor(Scheduler(pools, telemetry=tel))
    counter = CompileCounter.install()

    def wave(reqs):
        async def go():
            async with door:
                return await asyncio.gather(*(
                    door.sample(seed, pool=pool, max_trials=max_trials)
                    for pool, seed in reqs))
        return asyncio.run(go())

    out = {"compile": {}, "warm": {}}
    for name, base in (("rej", 10_000), ("mcmc", 20_000)):
        with compile_stats() as stats:
            t0 = time.perf_counter()
            res = wave([(name, base + i) for i in range(pools[name].n_slots)])
            out["warm"][name] = (res, time.perf_counter() - t0)
        out["compile"][name] = stats
    reqs = ([("rej", i) for i in range(n_rej)]
            + [("mcmc", 1_000 + i) for i in range(n_mcmc)])
    with counter.measure() as m:
        t0 = time.perf_counter()
        res = wave(reqs)
        out["window_s"] = time.perf_counter() - t0
    out["window_compiles"] = m.compiles
    out["window"] = {"rej": res[:n_rej], "mcmc": res[n_rej:]}
    out["pools"] = pools
    return out


def check_draws(sampler, results, *, size=None) -> None:
    """Host checks of served draws: unique items in range, det(L_Y) > 0
    (float64), and a fixed size where the chain is fixed-size."""
    sp = sampler.sp
    z = np.asarray(jax.device_get(sp.Z), np.float64)
    x = np.asarray(jax.device_get(sp.x_matrix()), np.float64)
    for r in results:
        y = np.asarray(r.items)[np.asarray(r.mask)]
        _require(len(np.unique(y)) == len(y), f"repeated items {y}")
        _require(bool(np.all((y >= 0) & (y < sp.M))), f"out of range {y}")
        if size is not None:
            _require(len(y) == size, f"size {len(y)} != {size}")
        if len(y):
            sign, _ = np.linalg.slogdet(z[y] @ x @ z[y].T)
            _require(sign > 0, f"det(L_Y) <= 0 for Y={y.tolist()}")


def pallas_kernels(text: str):
    """Sorted names of the Pallas kernels (``tpu_custom_call``) in a
    compiled HLO module's text."""
    names = re.findall(r'%([\w.\-]+) = [^\n]*custom_call_target='
                       r'"tpu_custom_call"', text)
    return sorted({re.sub(r"\.\d+$", "", n) for n in names})


def tick_kernels(pools) -> dict:
    """Pallas kernels in each pool's compiled programs: the rejection tick,
    the MCMC tick and the MCMC pool's greedy chain init."""
    rej, mc = pools["rej"], pools["mcmc"]
    trials = np.zeros(rej.n_slots, np.uint32)
    rej_tick = _spec_round_fused.lower(
        rej.sampler, rej.slot_key, trials, n_spec=rej.n_spec)
    mc_tick = mcmc_core.run_chains.lower(
        mc.sp, mc.slot_key, mc._states, n_steps=mc.mcmc_steps_per_tick,
        fixed=True, p_swap=mc.mcmc_p_swap, refresh_every=mc.mcmc_refresh_every)
    st = jax.vmap(lambda _: mcmc_core.init_empty(mc.sp))(
        jnp.arange(1, dtype=jnp.int32))
    mc_init = mcmc_core._greedy_round.lower(
        mc.sp, st, jax.random.split(jax.random.PRNGKey(0), 1),
        jnp.asarray(0, jnp.int32))
    return {name: pallas_kernels(low.compile().as_text())
            for name, low in (("rej_tick", rej_tick), ("mcmc_tick", mc_tick),
                              ("mcmc_init", mc_init))}


def exactness(n_draws: int = 8000, seed: int = 0, chunk: int = 500) -> dict:
    """Rejection draws at M = 8, K = 4, block = 2 against the enumerated
    subset distribution (chi-square within 5 sigma, TV < 0.08), drawn
    ``chunk`` requests per call (one compiled shape)."""
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.normal(size=(8, 4)) * 0.6, jnp.float32)
    b = jnp.asarray(rng.normal(size=(8, 4)) * 0.6, jnp.float32)
    d = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    sampler = preprocess(v, b, d, block=2)
    _require(sampler.tree.depth > 0, "exactness tree has no levels")
    _require(n_draws % chunk == 0, f"{n_draws} draws in chunks of {chunk}")
    keys = jax.random.split(jax.random.PRNGKey(3), n_draws)
    emp = {}
    for i in range(0, n_draws, chunk):
        res = sample_batched_many(sampler, keys[i:i + chunk], n_spec=4,
                                  split_keys=False)
        _require(bool(np.asarray(res.accepted).all()), "unaccepted draws")
        for y, c in histogram(res.items, res.mask).items():
            emp[y] = emp.get(y, 0) + c
    probs = enumerate_subset_probs(dense_l(NDPPParams(v, b, d)))
    _require(set(emp) <= set(probs), "impossible subsets drawn")
    assert_chi_square_close(emp, probs, n_draws)
    tv = float(tv_to_probs(emp, probs, n_draws))
    _require(tv < 0.08, f"TV {tv} >= 0.08")
    return {"n": n_draws, "tv": tv, "depth": sampler.tree.depth,
            "kernel_path": spec_round_ops.descent_operands(
                sampler.tree.levels) is not None}


def serve_engines(engines, seeds, max_trials: int) -> dict:
    """Drive each engine directly on the given seeds: {pool: [result]}."""
    out = {}
    for name, eng in engines.items():
        for i, s in enumerate(seeds[name]):
            eng.submit(SampleRequest(rid=i, seed=s, max_trials=max_trials))
        res = eng.run()
        out[name] = [res[i] for i in range(len(seeds[name]))]
    return out


def same_draws(a, b) -> bool:
    return all(np.array_equal(x.items, y.items) and x.trials == y.trials
               for x, y in zip(a, b, strict=True))


@contextlib.contextmanager
def xla_descent():
    """Route the unsharded descent through the jnp oracle (plain XLA) for
    the block, so a one-device engine walks the tree the way the sharded
    engine does.  Clears the jit caches on entry and exit."""
    orig = spec_round_ops.descent_operands
    spec_round_ops.descent_operands = lambda levels, **kw: None
    jax.clear_caches()
    try:
        yield
    finally:
        spec_round_ops.descent_operands = orig
        jax.clear_caches()


def four_chips(sampler, *, n_rej: int, n_mcmc: int, max_trials: int) -> dict:
    """Sharded engines on a 4-device mesh against one-device engines on
    the same seeds: which pairs draw bit-identically."""
    from repro.launch.mesh import make_sampler_mesh

    mesh = make_sampler_mesh(4)
    seeds = {"rej": list(range(n_rej)),
             "mcmc": [1_000 + i for i in range(n_mcmc)]}
    t0 = time.perf_counter()
    one = serve_engines(make_engines(sampler), seeds, max_trials)
    t_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    four = serve_engines(make_engines(sampler, mesh=mesh), seeds, max_trials)
    t_four = time.perf_counter() - t0
    with xla_descent():
        one_xla = serve_engines({"rej": make_engines(sampler)["rej"]},
                                {"rej": seeds["rej"]}, max_trials)
    for res in (one, four, one_xla):
        _require(all(r.accepted for r in res["rej"]), "unaccepted request")
    check_draws(sampler, four["rej"])
    check_draws(sampler, four["mcmc"], size=MCMC_K)
    out = {
        "one_s": t_one, "four_s": t_four,
        "rej_four_eq_one_kernel": same_draws(four["rej"], one["rej"]),
        "rej_four_eq_one_xla": same_draws(four["rej"], one_xla["rej"]),
        "rej_one_kernel_eq_one_xla": same_draws(one["rej"], one_xla["rej"]),
        "mcmc_four_eq_one": same_draws(four["mcmc"], one["mcmc"]),
    }
    _require(out["rej_four_eq_one_kernel"] or out["rej_four_eq_one_xla"],
             f"sharded rejection draws match no one-device engine: {out}")
    _require(out["mcmc_four_eq_one"], "sharded MCMC draws differ")
    return out


# -------------------------------------------------------------------- main


def _device_line(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded comparison")
    ap.add_argument("--out", default=None,
                    help="also write the run's numbers to this JSON file")
    args = ap.parse_args(argv)
    _require("REPRO_PALLAS_INTERPRET" not in os.environ,
             "REPRO_PALLAS_INTERPRET is set; the chip path runs compiled "
             "kernels only")
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    devs = jax.devices()
    _require(devs[0].platform == "tpu",
             f"no TPU: JAX reports {devs[0].platform}")
    if args.four_chips:
        _require(len(devs) >= 4, f"--four-chips needs 4 devices, "
                                 f"found {len(devs)}")
        devs = devs[:4]
    print(f"device: {devs[0].device_kind} x{len(devs)}", flush=True)

    m = M_ITEMS
    sampler, pre_s = build_catalog(m, RANK, block=BLOCK)
    expect = float(det_ratio_exact(sampler.sp))
    max_trials = int(BUDGET_FACTOR * np.ceil(expect))
    rec = {"device": _device_line(devs), "M": m, "K": RANK,
           "preprocess_s": pre_s, "expected_trials": expect,
           "max_trials": max_trials}
    print(f"catalog: M={m} K={RANK} block={BLOCK} depth="
          f"{sampler.tree.depth} preprocess {pre_s:.3f} s", flush=True)
    print(f"det_ratio_exact (E[trials]): {expect:.3f}; max_trials "
          f"{max_trials}", flush=True)

    if args.four_chips:
        rec["four_chips"] = four_chips(sampler, n_rej=16, n_mcmc=8,
                                       max_trials=max_trials)
        print(f"four chips: {rec['four_chips']}", flush=True)
    else:
        srv = serve(sampler, n_rej=16, n_mcmc=8, max_trials=max_trials)
        rej = list(srv["warm"]["rej"][0]) + srv["window"]["rej"]
        mcmc = list(srv["warm"]["mcmc"][0]) + srv["window"]["mcmc"]
        check_draws(sampler, rej)
        check_draws(sampler, mcmc, size=MCMC_K)
        n_acc = sum(bool(r.accepted) for r in rej)
        mean_trials = float(np.mean([r.trials for r in rej]))
        kernels = tick_kernels(srv["pools"])
        band = trials_band(len(rej))
        rec.update(
            compile=srv["compile"],
            warm_s={k: v[1] for k, v in srv["warm"].items()},
            window_s=srv["window_s"], window_compiles=srv["window_compiles"],
            served={"rej": len(rej), "mcmc": len(mcmc)},
            accepted_rej=n_acc, mean_trials=mean_trials,
            trials_over_expected=mean_trials / expect, kernels=kernels)
        print(f"compiles per pool in the warm-up wave (seconds, programs, "
              f"persistent-cache hits): {srv['compile']}", flush=True)
        print(f"served: rej {len(rej)} (accepted {n_acc}), mcmc "
              f"{len(mcmc)}; window {srv['window_s']:.3f} s, compiles in "
              f"window {srv['window_compiles']}", flush=True)
        print(f"mean trials {mean_trials:.2f} vs expected {expect:.2f} "
              f"(ratio {mean_trials / expect:.3f}, band {band})", flush=True)
        print(f"pallas kernels: {kernels}", flush=True)
        _require(n_acc == len(rej), f"{len(rej) - n_acc} rejection "
                                    f"requests unaccepted")
        _require(srv["window_compiles"] == 0,
                 f"{srv['window_compiles']} compiles inside the window")
        _require(band[0] <= mean_trials / expect <= band[1],
                 f"mean trials {mean_trials} outside {band} x {expect}")
        _require("ndpp_tree_descent" in kernels["rej_tick"],
                 f"descent kernel missing from the rejection tick: {kernels}")
        _require("ndpp_slogdet" in kernels["rej_tick"],
                 f"slogdet kernel missing from the rejection tick: {kernels}")
        _require("ndpp_score_all" in kernels["mcmc_init"],
                 f"score_all missing from the MCMC init: {kernels}")
        rec["exactness"] = exactness()
        print(f"exactness (M=8, K=4, block=2): {rec['exactness']}",
              flush=True)
        _require(rec["exactness"]["kernel_path"],
                 "exactness phase did not take the kernel path")
    stats = devs[0].memory_stats() or {}
    rec["hbm_peak_bytes"] = stats.get("peak_bytes_in_use")
    print(f"HBM peak (device 0): {rec['hbm_peak_bytes']} bytes", flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rec, indent=1, default=str))
    print(json.dumps({"ok": True, "device": _device_line(devs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
