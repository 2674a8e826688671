"""Device milliseconds per rejection-pool tick under the named scope
``ndpp.logdet_ratio``: the acceptance test's 2K-space submatrices and
their log-dets, once per round (trace, HLO scope join)."""


def read(run):
    if run.trace is None:
        return None
    s = run.trace.scopes.get("ndpp.logdet_ratio", 0.0)
    ticks = run.counter("ndpp_ticks_total", "rejection")
    return 1e3 * s / ticks if ticks and s else None
