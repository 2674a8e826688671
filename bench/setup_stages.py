"""Set-up stage seconds, read from the program's process-wide registry
(``repro.obs.startup_registry()``): the process is the run, and set-up
ends before the window opens.  A program without the stage spans reads
None, never an error."""
from typing import Optional

#: the stages that split ``setup_s`` (the readers of the set-up metrics)
YOULA = ("youla",)
EIGENS = ("proposal_eigens",)
TREE = ("tree_build",)
WARMUP_REJ = ("engine_init/rejection", "first_tick/rejection")
SPLIT = (YOULA, EIGENS, TREE, WARMUP_REJ)


def by_stage(name: str) -> Optional[dict]:
    """{stage: value} of the counter ``name``, or None where the program
    records no set-up stages."""
    try:
        from repro.obs import startup_registry
    except ImportError:
        return None
    reg = startup_registry()
    if name not in reg.names():
        return None
    return {key[0]: float(v) for key, v in reg.get(name).labelsets()}


def seconds(stages) -> Optional[float]:
    """Host seconds of the named stages together, or None if any of them
    never ran."""
    got = by_stage("ndpp_setup_seconds_total")
    if got is None or any(s not in got for s in stages):
        return None
    return sum(got[s] for s in stages)
