"""Performance-observatory unit tests (``repro.obs.prof``).

The trace parser is pinned against a hand-written synthetic Chrome
trace (``tests/prof_fixtures/synthetic_trace.json``) whose every number
is computed in the comments below — attribution must reproduce them
exactly, so a parser drift (lost dedupe, broken clipping, scope-join
regression) fails loudly.  The dispatch/transfer accounting is
cross-validated against a *real* profiler capture: the call-boundary
counts of ``Accountant`` must agree with the ``PjitFunction`` events
the C++ pjit fastpath emits into the trace — the test that proves the
accounting identities rather than asserting them.
"""
import gzip
import json
import math
from pathlib import Path

import numpy as np
import pytest

from repro.obs.prof import (
    Accountant,
    NULL_ACCOUNTANT,
    attribute,
    benchdiff,
    capture as cap,
    complete_events,
    cost,
    hlo_scope_map,
    host_nbytes,
    load_trace,
    phases as ph,
    schema,
)

FIXTURES = Path(__file__).parent / "prof_fixtures"
TRACE = FIXTURES / "synthetic_trace.json"
HLO = FIXTURES / "spec_round_hlo.txt"


# ------------------------------------------------------------------ parsing
def test_load_trace_wrapper_and_bare(tmp_path):
    events = load_trace(str(TRACE))
    assert isinstance(events, list) and len(events) > 10
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(events))
    assert load_trace(str(bare)) == events


def test_load_trace_gz(tmp_path):
    gz = tmp_path / "trace.json.gz"
    with gzip.open(gz, "wt") as f:
        f.write(TRACE.read_text())
    assert load_trace(str(gz)) == load_trace(str(TRACE))


def test_complete_events_drops_nested_duplicates():
    evs = complete_events(load_trace(str(TRACE)))
    # the fixture plants a duplicate PjitFunction(_spec_round) at
    # ts=1095 dur=10, contained in the kept [1090, 1120] span on the
    # same thread — exactly one survives per tick
    spec = [e for e in evs if e["name"] == "PjitFunction(_spec_round)"]
    assert len(spec) == 2
    assert sorted(e["ts"] for e in spec) == [1090, 2090]
    # non-complete events (ph M/i metadata and markers) are gone
    assert all(e["ph"] == "X" for e in evs)


def test_hlo_scope_map_innermost_scope_wins():
    maps = hlo_scope_map(HLO.read_text())
    assert maps == {"jit__spec_round": {
        # op_name ".../ndpp.proposal/ndpp.tree_descent/dot_general":
        # the innermost ndpp.* component is the one attributed
        "dot.1": "ndpp.tree_descent",
        "fusion.2": "ndpp.leaf_scoring",
        "lu.7": "ndpp.logdet_ratio",
    }}


def _scope_maps():
    return hlo_scope_map(HLO.read_text())


def test_attribute_exact_fixture_numbers():
    """Every field of the report, from hand-computed fixture arithmetic.

    ticks: [1000,1400] + [2000,2400]           -> wall 800us
    exec spans: [500,600] outside ticks (dropped by clipping),
      [1100,1300] (200), [1350,1450] clipped to [1350,1400] (50),
      [2100,2300] (200)                        -> busy 450us
    gap: 800 - 450 = 350us -> frac 0.4375
    """
    rep = attribute(load_trace(str(TRACE)), scope_maps=_scope_maps())
    assert rep.n_ticks == 2
    assert rep.rounds == 2
    assert rep.wall_us == 800.0
    assert rep.device_busy_us == 450.0
    assert rep.host_gap_us == 350.0
    assert rep.host_gap_frac == pytest.approx(0.4375)
    assert rep.phases == {
        "admission": {"count": 2, "wall_us": 100.0},
        "round_dispatch": {"count": 2, "wall_us": 240.0},
        "harvest": {"count": 2, "wall_us": 200.0},
    }
    # dup dropped -> 2+2 dispatches over 2 ticks / 2 rounds
    assert rep.dispatches == {"_fanout_keys": 2, "_spec_round": 2}
    assert rep.dispatches_total == 4
    assert rep.dispatches_per_tick == 2.0
    assert rep.dispatches_per_round == 2.0
    assert rep.device == {
        "ndpp.tree_descent": {"ops": 1, "busy_us": 40.0},   # dot.1 exact
        "ndpp.leaf_scoring": {"ops": 1, "busy_us": 30.0},   # fusion.2
        # trace says lu.5, compiled text says lu.7: the unambiguous
        # base-name ("lu") fallback attributes it anyway
        "ndpp.logdet_ratio": {"ops": 1, "busy_us": 60.0},
        # iota.9 appears in no compiled module -> unattributed bucket
        "unattributed": {"ops": 1, "busy_us": 10.0},
    }
    # report round-trips to JSON and renders
    json.dumps(rep.to_dict())
    table = rep.format_table()
    assert "dispatches/tick=2.00" in table and "harvest" in table


def test_hlo_ops_outside_exec_spans_join_busy_union():
    """Async-runtime busy accounting (the fused-tick misattribution).

    On TFRT CPU the fused one-dispatch tick's ``Execute`` returns while
    the ops still run on pool threads, so per-HLO-op spans must count
    toward device busy even when no launch marker covers them — else
    real compute is charged to the host gap.
    """
    events = [
        {"ph": "X", "name": "ndpp_engine_tick/rejection",
         "ts": 0, "dur": 1000, "tid": 1},
        # the launch marker covers only the dispatch itself...
        {"ph": "X", "name": "TfrtCpuExecutable::Execute",
         "ts": 100, "dur": 50, "tid": 2},
        # ...the ops run after it returned, on pool threads; the two
        # overlap so the union must dedupe them
        {"ph": "X", "name": "fusion.1", "ts": 200, "dur": 300, "tid": 3,
         "args": {"hlo_module": "m", "hlo_op": "fusion.1"}},
        {"ph": "X", "name": "dot.2", "ts": 400, "dur": 200, "tid": 4,
         "args": {"hlo_module": "m", "hlo_op": "dot.2"}},
    ]
    rep = attribute(events)
    # busy = |[100,150] U [200,500] U [400,600]| = 50 + 400 = 450
    assert rep.device_busy_us == 450.0
    assert rep.host_gap_us == 550.0
    assert rep.host_gap_frac == pytest.approx(0.55)
    assert rep.device == {"unattributed": {"ops": 2, "busy_us": 500.0}}


def test_attribute_degrades_without_scope_maps():
    rep = attribute(load_trace(str(TRACE)))
    assert rep.device == {"unattributed": {"ops": 4, "busy_us": 140.0}}
    assert rep.dispatches_total == 4          # everything else unchanged


def test_attribute_empty_trace_is_all_zero():
    rep = attribute([])
    assert rep.n_ticks == 0 and rep.wall_us == 0.0
    assert rep.host_gap_frac == 0.0 and rep.dispatches_per_tick == 0.0


# --------------------------------------------------------------- accounting
def _double(x):
    return x * 2.0


def test_accountant_exact_counts():
    import jax

    f = jax.jit(_double)
    acct = Accountant("rejection")
    x = np.ones((4, 4), np.float32)                       # 64 bytes
    with acct.measure() as m:
        y = acct.call("double", f, x)
        k = acct.put("key", np.zeros(8, np.uint32))       # 32 bytes, no disp
        out = acct.device_get((y, k))
    assert m.dispatches == {"double": 1}
    assert m.dispatches_total == 1
    assert m.h2d_bytes == 64 + 32
    assert m.d2h_bytes == 64 + 32
    assert host_nbytes(out) == 96
    t = acct.totals()
    assert t["dispatches_total"] == 1 and t["backend"] == "rejection"
    # device-resident args transfer nothing
    before = acct.h2d_bytes
    acct.call("double", f, y)
    assert acct.h2d_bytes == before
    assert acct.dispatches == {"double": 2}


def test_accountant_streams_into_registry():
    from repro.obs import MetricRegistry, engine_instruments

    reg = MetricRegistry()
    ins = engine_instruments(reg)
    import jax

    f = jax.jit(_double)
    acct = Accountant("rejection", instruments=ins)
    acct.call("double", f, np.ones(4, np.float32))
    acct.device_get(acct.put("k", np.zeros(2, np.uint32)))
    assert reg.get("ndpp_dispatches_total").value(
        backend="rejection", fn="double") == 1
    assert reg.get("ndpp_transfer_bytes_total").value(
        backend="rejection", direction="h2d") == 16 + 8
    assert reg.get("ndpp_transfer_bytes_total").value(
        backend="rejection", direction="d2h") == 8


def test_null_accountant_is_a_straight_pipe():
    import jax

    f = jax.jit(_double)
    y = NULL_ACCOUNTANT.call("x", f, np.ones(2, np.float32))
    got = NULL_ACCOUNTANT.device_get(y)
    np.testing.assert_array_equal(got, [2.0, 2.0])
    assert not hasattr(NULL_ACCOUNTANT, "h2d_bytes")


def test_accounting_cross_validates_against_real_trace(tmp_path):
    """The identity behind the whole accounting design: one warm call to
    a jitted function == one PjitFunction event in a real capture (the
    C++ fastpath emits these even though it bypasses Python seams)."""
    import jax

    f = jax.jit(_double)
    x = np.ones((8, 8), np.float32)
    jax.device_get(f(x))                       # compile outside capture
    acct = Accountant("xval")
    log_dir = str(tmp_path / "prof")
    try:
        with cap.capture(log_dir):
            for _ in range(3):
                y = acct.call("_double", f, x)
            acct.device_get(y)                 # flush before capture ends
    except cap.ProfilerUnavailable as e:
        pytest.skip(f"profiler not available here: {e}")
    evs = complete_events(load_trace(cap.trace_path(log_dir)))
    pjit = [e for e in evs if e["name"] == "PjitFunction(_double)"]
    assert len(pjit) == acct.dispatches["_double"] == 3
    assert acct.h2d_bytes == 3 * x.nbytes
    assert acct.d2h_bytes == x.nbytes


# --------------------------------------------------------------- cost model
def test_cost_join_math():
    costs = cost.phase_costs_mcmc(K=4, steps=100)
    assert costs[ph.MCMC_STEP] == {"flops": 3200.0, "bytes": 6400.0}
    joined = cost.join({ph.MCMC_STEP: {"ops": 5, "busy_us": 1000.0}},
                       costs, peak_flops=1e9, mem_bw=1e9)
    row = joined[ph.MCMC_STEP]
    assert row["roofline_s"] == pytest.approx(6.4e-6)
    assert row["dominant"] == "memory"
    assert row["measured_s"] == pytest.approx(1e-3)
    assert row["achieved_frac"] == pytest.approx(6.4e-3)


def test_cost_join_handles_one_sided_scopes():
    joined = cost.join({"unattributed": {"ops": 2, "busy_us": 10.0}},
                       cost.phase_costs_rejection(M=64, K=4, n_trials=16,
                                                  block=2))
    assert joined["unattributed"]["roofline_s"] is None   # measured only
    assert joined[ph.TREE_DESCENT]["measured_s"] is None  # modelled only
    assert joined[ph.TREE_DESCENT]["flops"] > 0


def test_phase_catalog_matches_lint_contract():
    # NDPP701's sanctioned-phase set is a literal copy of this frozenset;
    # if the catalog grows a second sanctioned phase, both must move
    assert ph.BLOCKING_ALLOWED == frozenset({"harvest"})
    assert set(ph.HOST_PHASES) == {"admission", "round_dispatch", "harvest",
                                   "sched_admission", "resolve", "yield"}
    assert set(ph.ENGINE_PHASES) | set(ph.SCHED_PHASES) == set(ph.HOST_PHASES)


# ------------------------------------------------------------------- schema
def _bench_payload():
    return {
        "meta": {"bench": "sampling_time", "backend": "cpu",
                 "jax": "0.4.37", "unix_time": 1.0,
                 "git_commit": "abc1234", "git_dirty": False},
        "modes": {"profile": [
            {"backend": "rejection", "M": 4096, "K": 8, "wall_s": 1.0,
             "dispatches_per_tick": 2.0, "host_gap_frac": 0.5},
            {"backend": "mcmc", "M": 4096, "K": 8, "wall_s": 2.0,
             "dispatches_per_tick": 1.0, "host_gap_frac": 0.4},
        ]},
    }


def test_schema_accepts_valid_payload():
    errors, warnings = schema.validate(_bench_payload())
    assert errors == [] and warnings == []


def test_schema_rejects_nonfinite_and_bad_shape():
    bad = _bench_payload()
    bad["modes"]["profile"][0]["wall_s"] = math.nan
    errors, _ = schema.validate(bad)
    assert any("non-finite" in e for e in errors)
    errors, _ = schema.validate({"meta": {}, "modes": "nope"})
    assert any("missing required key" in e for e in errors)
    assert any("modes" in e for e in errors)


def test_schema_warns_on_missing_provenance():
    legacy = _bench_payload()
    del legacy["meta"]["git_commit"], legacy["meta"]["git_dirty"]
    errors, warnings = schema.validate(legacy)
    assert errors == []
    assert any("provenance" in w for w in warnings)


def test_committed_bench_files_validate():
    repo = Path(__file__).parent.parent
    for name in ("BENCH_sampling.json", "BENCH_profile.json"):
        path = repo / name
        if not path.exists():
            continue
        errors, _ = schema.validate_file(str(path))
        assert errors == [], f"{name}: {errors}"
        # the full --validate gate also hard-fails dirty provenance: a
        # committed artifact must come from a clean checkout
        assert benchdiff.main(["--validate", str(path)]) == 0, (
            f"{name} failed benchdiff --validate (dirty git stamp?)")


# ---------------------------------------------------------------- benchdiff
def test_benchdiff_detects_perturbed_row():
    """The acceptance self-test: a deliberately perturbed bench row must
    trip the gate — exact-field mismatch AND out-of-band wall clock."""
    base = _bench_payload()
    perturbed = json.loads(json.dumps(base))
    perturbed["modes"]["profile"][0]["dispatches_per_tick"] = 7.0  # exact
    perturbed["modes"]["profile"][1]["wall_s"] = 9.0   # 350% slower
    diff = benchdiff.compare(base, perturbed)
    assert diff.exit_code == 1
    assert len(diff.failures) == 2
    assert any("dispatches_per_tick" in f and "exact" in f
               for f in diff.failures)
    assert any("wall_s" in f and "worse" in f for f in diff.failures)


def test_benchdiff_wall_noise_and_improvements_pass():
    base = _bench_payload()
    new = json.loads(json.dumps(base))
    new["modes"]["profile"][0]["wall_s"] = 1.3    # +30% < 50% tol band
    new["modes"]["profile"][1]["wall_s"] = 0.2    # improvement: never fails
    assert benchdiff.compare(base, new).exit_code == 0


def test_benchdiff_warn_only_wall_downgrade():
    base = _bench_payload()
    slow = json.loads(json.dumps(base))
    slow["modes"]["profile"][1]["wall_s"] = 9.0
    diff = benchdiff.compare(base, slow, warn_only_wall=True)
    assert diff.exit_code == 0 and len(diff.warnings) == 1
    # exact fields still fail even under --warn-only-wall
    slow["modes"]["profile"][0]["dispatches_per_tick"] = 9.0
    assert benchdiff.compare(base, slow, warn_only_wall=True).exit_code == 1


def test_benchdiff_neutral_drift_warns_and_subset_notes():
    base = _bench_payload()
    new = json.loads(json.dumps(base))
    new["modes"]["profile"][0]["host_gap_frac"] = 0.95   # 90% drift
    del new["modes"]["profile"][1]                        # smoke subset
    diff = benchdiff.compare(base, new)
    assert diff.exit_code == 0
    assert any("host_gap_frac" in w for w in diff.warnings)
    assert any("only in baseline" in n for n in diff.notes)


def test_benchdiff_absent_measurement_is_a_note():
    """Attribution fields degrade to None when the profiler can't
    capture — that's a coverage loss to surface, not a regression."""
    base = _bench_payload()
    new = json.loads(json.dumps(base))
    new["modes"]["profile"][0]["host_gap_frac"] = None
    diff = benchdiff.compare(base, new)
    assert diff.exit_code == 0
    assert any("absent" in n for n in diff.notes)


def test_benchdiff_cli_end_to_end(tmp_path, capsys):
    base_p = tmp_path / "base.json"
    new_p = tmp_path / "new.json"
    base = _bench_payload()
    base_p.write_text(json.dumps(base))
    base["modes"]["profile"][0]["dispatches_per_tick"] = 3.0
    new_p.write_text(json.dumps(base))
    assert benchdiff.main([str(base_p), str(base_p)]) == 0
    assert benchdiff.main([str(base_p), str(new_p)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "exact mismatch" in out
    assert benchdiff.main(["--validate", str(base_p), str(new_p)]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"meta": {}, "modes": {}}))
    assert benchdiff.main(["--validate", str(bad)]) == 1
    with pytest.raises(SystemExit):
        benchdiff.main([str(base_p)])          # diff needs exactly 2 files


def test_benchdiff_validate_fails_dirty_stamp(tmp_path, capsys):
    """An artifact stamped ``git_dirty: true`` was measured from an
    uncommitted tree — ``--validate`` must hard-fail it.  (Regression
    for the bug where the bench stamped provenance at dump time, so the
    first artifact write dirtied the tree for the second and every
    committed file carried a dirty stamp.)"""
    clean = _bench_payload()
    dirty = json.loads(json.dumps(clean))
    dirty["meta"]["git_dirty"] = True
    clean_p, dirty_p = tmp_path / "clean.json", tmp_path / "dirty.json"
    clean_p.write_text(json.dumps(clean))
    dirty_p.write_text(json.dumps(dirty))
    assert benchdiff.main(["--validate", str(clean_p)]) == 0
    assert benchdiff.main(["--validate", str(clean_p), str(dirty_p)]) == 1
    out = capsys.readouterr().out
    assert "git_dirty" in out and "uncommitted" in out
    # diff mode is unaffected: provenance is a validation property
    assert benchdiff.main([str(clean_p), str(dirty_p)]) == 0
