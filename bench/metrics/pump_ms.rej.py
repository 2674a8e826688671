"""Mean host milliseconds per rejection-pool tick spent by the scheduler
and the front door outside the engine: admission from the priority
queue, resolving the callers' futures, and the yield in which callers
send (``ndpp_sched_phase_seconds_total``, all phases, over
``ndpp_ticks_total{backend=rejection}``)."""


def read(run):
    ticks = run.counter("ndpp_ticks_total", "rejection")
    s = sum(v for (name, _), v in run.delta.items()
            if name == "ndpp_sched_phase_seconds_total")
    return 1e3 * s / ticks if ticks and s else None
