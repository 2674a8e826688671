"""Host seconds the rejection pools took to become ready: their
construction (``engine_init/rejection``: placement and the auto n_spec's
``det_ratio_exact``) and their first tick that progressed
(``first_tick/rejection``: the tick program's compile or cache load)."""
import setup_stages


def read(run):
    return setup_stages.seconds(setup_stages.WARMUP_REJ)
