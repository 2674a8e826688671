"""Trials per second that rejection requests consumed inside the window,
as the benchmark's clients see them: each answered request's trials spread
over its send-to-answer interval, the part inside the window kept, over
the window.  A draw costs E[trials] of them on average, a property of the
catalog, so draws per second is this over E[trials]."""


def read(run):
    if not run.backend_pools("rejection"):
        return None
    return run.consumed_trials("rejection") / run.window_s
