"""The control of a cell: the program itself with its lower-precision path
switched on, run and judged exactly as a run of the cell is.  The
comparison has to read it as not correct.

The configurations state float32 at ``highest`` matmul precision.  The
program's next step down is the TPU's default, one bfloat16 pass: its
Pallas kernels refuse ``high`` (three passes) at lowering, so no program
runs there, and the reference computed at ``high`` draws as the reference
does (PERF.md).

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds 10]

On the chip, at the cell's own size and load.  For each seed it prints
each number the check compares beside its limit; the last line of
standard output is a JSON list, one entry per seed.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

#: the program's matmul precision just below each one a configuration may
#: state
BELOW = {"highest": "default"}


def control_numbers(workload: str, seed: int, seconds: float, *,
                    require_chip: bool = True, root: Path = BENCH.parent,
                    log=None) -> dict:
    """{name: (value, limit)} of the check, for the control on one seed."""
    from harness import load_cell, run_cell

    conf = load_cell(workload, root).config
    out = run_cell(workload, seed, seconds, False, t0=time.perf_counter(),
                   require_chip=require_chip, root=root,
                   precision=BELOW[conf["matmul_precision"]], log=log)
    return {k: (v["value"], v["limit"]) for k, v in out["check"].items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    out = []
    for seed in args.seeds:
        numbers = control_numbers(args.workload, seed, args.seconds)
        fails = [k for k, (v, lim) in numbers.items() if not v <= lim]
        for k, (v, lim) in numbers.items():
            print(f"control seed {seed}: {k} = {v!r} limit {lim!r}",
                  file=sys.stderr, flush=True)
        out.append({"seed": seed, "fails": fails,
                    "numbers": {k: v for k, (v, _) in numbers.items()}})
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
