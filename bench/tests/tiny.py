"""A tiny cell for the benchmark's own tests on the CPU: the book-1m
configuration and the rej-backlog traffic at M = 3,000, K = 16."""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

CELL = "tiny.rej-backlog"


def tiny_root(tmp: Path) -> Path:
    """A directory holding a BENCHMARK.json with the one tiny cell."""
    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    conf = json.loads((BENCH / "configs" / "book-1m.json").read_text())
    conf.update(name="tiny", items=3000, rank=16, leaf_block=16)
    conf["pools"]["rej"]["n_spec"] = 16
    (tmp / "tiny.json").write_text(json.dumps(conf))
    bm["configs"] = [{"name": "tiny", "source": "test", "file": "tiny.json",
                      "reduced": [], "why": "test"}]
    bm["workloads"] = [{"name": CELL, "config": "tiny",
                        "traffic": "rej-backlog", "chips": 1, "why": "test"}]
    for m in bm["per_layer"]:
        m["workloads"] = [CELL]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp
