"""The trace reduction: its interval arithmetic, and a small trace
recorded on one TPU v5e (``bench/fixtures/``) reduced to the numbers
recorded beside it."""
import json
from pathlib import Path

import pytest

from tiny import BENCH  # noqa: F401  (puts bench/ on the path)

import devtrace

FIXTURES = Path(__file__).resolve().parents[1] / "fixtures"


def test_union_and_gaps():
    spans = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.7)]
    assert devtrace.union(spans) == 4.0
    assert devtrace.gaps(spans, -1.0, 8.0) == [(-1.0, 0.0), (3.0, 5.0),
                                               (6.0, 8.0)]
    assert devtrace.gaps(spans, 0.5, 2.5) == []
    assert devtrace.base_name("ndpp_tree_descent.12") == "ndpp_tree_descent"


@pytest.mark.parametrize("trace", sorted(FIXTURES.glob("*.xplane.pb")),
                         ids=lambda p: p.name)
def test_recorded_trace(trace):
    from jax.profiler import ProfileData

    want = json.loads(trace.with_name(
        trace.name.replace(".xplane.pb", ".json")).read_text())
    got = devtrace.reduce(ProfileData.from_file(str(trace)),
                          chips=want["chips"])
    assert got.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert got.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    for name, sec in want["kernels"].items():
        assert got.kernel_seconds(name) == pytest.approx(sec, rel=1e-9)
    assert 0.0 < got.busy_s <= got.window_s
