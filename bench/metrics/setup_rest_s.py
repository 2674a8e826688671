"""Seconds of ``setup_s`` that no set-up stage span covers: ``setup_s``
less the Youla, eigen, tree and rejection warm-up stages.  The rest is
imports, backend start, the benchmark's own catalog build and E[trials],
the warm-up wave's other work and, in a traced run, the profiler's
start."""
import setup_stages


def read(run):
    parts = [setup_stages.seconds(s) for s in setup_stages.SPLIT]
    if any(p is None for p in parts):
        return None
    return run.setup_s - sum(parts)
