"""XLA backend-compile seconds inside the set-up stages, all stages
together (``ndpp_setup_compile_seconds_total``); a load from the
persistent compile cache counts as its compile (JAX times both as one
event)."""
import setup_stages


def read(run):
    got = setup_stages.by_stage("ndpp_setup_compile_seconds_total")
    return None if got is None else sum(got.values())
