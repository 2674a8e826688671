"""Host seconds of the set-up stage ``youla``: the Youla decomposition of
``preprocess`` (``core.youla.spectral_from_params``), blocked on
(``ndpp_setup_seconds_total{stage=youla}``)."""
import setup_stages


def read(run):
    return setup_stages.seconds(setup_stages.YOULA)
