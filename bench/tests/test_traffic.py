"""The traffic generator: the same seed gives the same requests, and an
open loop's due times keep the mix's mean rate."""
import numpy as np
import pytest

from tiny import BENCH  # noqa: F401  (puts bench/ on the path)

from traffic import Traffic

MIX = [{"pool": "rej", "share": 4.0}, {"pool": "mcmc", "share": 1.0}]


def test_same_seed_same_requests():
    spec = {"loop": "closed", "clients": 2, "mix": MIX}
    a, b, c = Traffic(spec, 7), Traffic(spec, 7), Traffic(spec, 8)
    ra = [(r.pool, r.seed) for r in (a.next() for _ in range(50))]
    assert ra == [(r.pool, r.seed) for r in (b.next() for _ in range(50))]
    assert ra != [(r.pool, r.seed) for r in (c.next() for _ in range(50))]
    assert a.warmup_seeds(4) == b.warmup_seeds(4)


def test_open_loop_rate():
    spec = {"loop": "open", "rate_per_s": 200.0, "mix": MIX}
    due = np.array(list(Traffic(spec, 3).due_times(10.0, 70.0)))
    assert np.all(np.diff(due) > 0) and due[0] >= 10.0 and due[-1] < 70.0
    assert len(due) / 60.0 == pytest.approx(200.0, rel=0.05)


def test_mix_shares():
    gen = Traffic({"loop": "closed", "clients": 1, "mix": MIX}, 5)
    pools = [gen.next().pool for _ in range(4000)]
    assert pools.count("rej") / len(pools) == pytest.approx(0.8, abs=0.03)
