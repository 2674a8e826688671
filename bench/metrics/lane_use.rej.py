"""Share of the proposal lanes the rejection pool computed that requests
consumed: trials consumed inside the window over ticks x slots x n_spec
(``ndpp_ticks_total{backend=rejection}``).  Lanes after a request's
accepted proposal, and lanes of empty slots, are the rest."""


def read(run):
    pools = run.backend_pools("rejection")
    ticks = run.counter("ndpp_ticks_total", "rejection")
    if len(pools) != 1 or not ticks:
        return None
    p = run.pools[pools[0]]
    return 100.0 * run.consumed_trials("rejection") / (
        ticks * p["n_slots"] * p["n_spec"])
