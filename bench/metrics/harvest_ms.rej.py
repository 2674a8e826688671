"""Mean host milliseconds per rejection-pool tick spent in the harvest:
the designed device_get, which waits for the round, and the retire loop
(``ndpp_phase_seconds_total{backend=rejection, phase=harvest}`` over
``ndpp_ticks_total{backend=rejection}``)."""


def read(run):
    ticks = run.counter("ndpp_ticks_total", "rejection")
    s = run.counter("ndpp_phase_seconds_total", "rejection", "harvest")
    return 1e3 * s / ticks if ticks and s else None
