"""Proposals the rejection pool scored within the requests' budgets, per
second of the window (``ndpp_proposals_total{backend=rejection}``)."""


def read(run):
    n = run.counter("ndpp_proposals_total", "rejection")
    return n / run.window_s if n else None
