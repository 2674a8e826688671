"""Host seconds of the set-up stage ``tree_build``: ``construct_tree`` in
``preprocess``, blocked on (``ndpp_setup_seconds_total{stage=tree_build}``)."""
import setup_stages


def read(run):
    return setup_stages.seconds(setup_stages.TREE)
