"""Host seconds of the set-up stage ``proposal_eigens``: the proposal
kernel's eigendecomposition in ``preprocess``, blocked on
(``ndpp_setup_seconds_total{stage=proposal_eigens}``)."""
import setup_stages


def read(run):
    return setup_stages.seconds(setup_stages.EIGENS)
