"""Mean wall milliseconds of a rejection-pool engine tick over the window
(``ndpp_tick_seconds{backend=rejection}``: sum over count)."""


def read(run):
    n = run.counter("ndpp_tick_seconds:count", "rejection")
    s = run.counter("ndpp_tick_seconds:sum", "rejection")
    return 1e3 * s / n if n else None
