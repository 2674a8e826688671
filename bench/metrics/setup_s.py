"""Seconds from process start to the window's opening: catalog, preprocess,
pools and the warm-up wave (with compiles or cache loads)."""


def read(run):
    return run.setup_s
