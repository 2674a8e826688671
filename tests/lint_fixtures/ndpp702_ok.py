"""Clean twin for NDPP702 — annotations go through the gated span
primitive in repro.obs.trace, which centralizes the NDPP_PROFILE check
and the ndpp_phase/ naming the trace parser keys on."""
from repro.obs.trace import PHASE_PREFIX, annotation, profiling_enabled, span


def tick(i, fn, x, record):
    enabled = profiling_enabled()
    with annotation(f"ndpp_engine_tick/{i}", enabled):
        with span(PHASE_PREFIX + "round_dispatch", record, enabled):
            out = fn(x)
    return out
