"""Mean host milliseconds per rejection-pool tick spent handing the round
to the device (``ndpp_phase_seconds_total{backend=rejection,
phase=round_dispatch}`` over ``ndpp_ticks_total{backend=rejection}``)."""


def read(run):
    ticks = run.counter("ndpp_ticks_total", "rejection")
    s = run.counter("ndpp_phase_seconds_total", "rejection", "round_dispatch")
    return 1e3 * s / ticks if ticks and s else None
