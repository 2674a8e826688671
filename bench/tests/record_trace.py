"""Record the trace fixture of ``test_devtrace.py`` on a TPU: a traced run
of the tiny cell with a short window, its ``.xplane.pb`` copied into
``<out>`` with the reduction's numbers beside it (commit both under
``bench/fixtures/``).

    python3 bench/tests/record_trace.py <out>
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

from tiny import BENCH, CELL, tiny_root


def main() -> int:
    import devtrace
    import harness
    from jax.profiler import ProfileData

    fixtures = Path(sys.argv[1]) if len(sys.argv) > 1 else BENCH / "fixtures"
    fixtures.mkdir(parents=True, exist_ok=True)
    dst = fixtures / "tiny-v5e.xplane.pb"
    reduce = devtrace.reduce

    def keep(profile, **kw):
        """The run's trace, copied before the run removes it."""
        shutil.copy(sorted(harness.TRACE_DIR.rglob("*.xplane.pb"))[-1], dst)
        return reduce(profile, **kw)

    devtrace.reduce = keep
    with tempfile.TemporaryDirectory() as tmp:
        harness.run_cell(CELL, 5, 0.15, True, t0=time.perf_counter(),
                         root=tiny_root(Path(tmp)))
    devtrace.reduce = reduce
    got = devtrace.reduce(ProfileData.from_file(str(dst)), chips=1)
    want = {"chips": 1, "window_s": got.window_s, "busy_s": got.busy_s,
            "kernels": got.kernels}
    dst.with_name("tiny-v5e.json").write_text(json.dumps(want, indent=1))
    print(json.dumps({"bytes": dst.stat().st_size, **want}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
