"""Device milliseconds per rejection-pool tick under the named scope
``ndpp.descent_operands``: the tree levels flattened into the descent
kernel's node layout, once per round (trace, HLO scope join)."""


def read(run):
    if run.trace is None:
        return None
    s = run.trace.scopes.get("ndpp.descent_operands", 0.0)
    ticks = run.counter("ndpp_ticks_total", "rejection")
    return 1e3 * s / ticks if ticks and s else None
