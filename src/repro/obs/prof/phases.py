"""The phase catalog — the shared vocabulary of the observatory.

Three kinds of scopes, with different mechanics and different costs:

**Host phases** (``HOST_PHASES``) are spans (``repro.obs.trace.span``)
opened by host code around sections of a tick.  Each always adds its
host seconds to a counter (the engine's ``ndpp_phase_seconds_total``,
the scheduler's ``ndpp_sched_phase_seconds_total``) and, under
``NDPP_PROFILE=1``, appears in captured traces as an
``ndpp_phase/<name>`` annotation:

  ``admission``       queue → slot assignment, host-side key builds
  ``round_dispatch``  handing one speculative round (or MCMC chain
                      advance) to the device: the jitted call(s) and the
                      async dispatch work they trigger
  ``harvest``         the designed once-per-tick ``jax.device_get`` that
                      brings round outputs to host — the ONLY phase in
                      which blocking on the device is sanctioned
                      (ndpplint NDPP701)
  ``sched_admission`` the scheduler's priority-queue drain into the
                      pools' free slots (``Scheduler.tick``)
  ``resolve``         the front door resolving the callers' futures
                      after a scheduler tick
  ``yield``           the front door's zero sleep after a tick, in which
                      the callers run and send their next requests

**Set-up stages** (``SETUP_STAGES``) are spans opened once per process
or engine by ``repro.obs.setup_stage`` around the work a server does
before its first answer.  They feed ``ndpp_setup_seconds_total`` and
``ndpp_setup_compile_seconds_total`` in the process-wide
``repro.obs.startup_registry()`` and appear in traces as
``ndpp_setup/<stage>``.  Each blocks on its own outputs before it
closes, so it times the work and not its dispatch.

**Device scopes** (``DEVICE_SCOPES``) are ``jax.named_scope`` regions
*inside* the jitted hot paths.  They are always on: a named scope is
compile-time HLO metadata (``op_name="…/ndpp.tree_descent/…"``) with
zero runtime cost, so the bare engine keeps bit-identical draws and an
unchanged compiled program.  The trace parser joins captured HLO-op
events against compiled-module metadata to attribute device busy time
per scope:

  ``ndpp.proposal``      tree-based proposal draw (coins + traversal)
  ``ndpp.descent_operands``  the levels flattened into the descent
                         kernel's node layout, once per round
  ``ndpp.tree_descent``  root→block descent levels of the traversal
  ``ndpp.leaf_scoring``  batched bilinear leaf-block scoring + pick
  ``ndpp.logdet_ratio``  2K-space log det(L_Y) − log det(L̂_Y)
  ``ndpp.accept``        acceptance coin flips
  ``ndpp.mcmc_step``     vmapped MH chain advance
"""
from __future__ import annotations

# host phases ---------------------------------------------------------------
ADMISSION = "admission"
ROUND_DISPATCH = "round_dispatch"
HARVEST = "harvest"

SCHED_ADMISSION = "sched_admission"
RESOLVE = "resolve"
YIELD = "yield"

HOST_PHASES = {
    ADMISSION: "queue drain into free slots (host-only key builds)",
    ROUND_DISPATCH: "jitted round/chain dispatch for the whole pool",
    HARVEST: "the designed once-per-tick device_get sync",
    SCHED_ADMISSION: "scheduler priority-queue drain into the pools",
    RESOLVE: "front door resolving the callers' futures",
    YIELD: "front door yield in which the callers send",
}

#: the engine's phases (``ndpp_phase_seconds_total``); the rest are the
#: scheduler's and the front door's (``ndpp_sched_phase_seconds_total``)
ENGINE_PHASES = (ADMISSION, ROUND_DISPATCH, HARVEST)
SCHED_PHASES = (SCHED_ADMISSION, RESOLVE, YIELD)

#: host phases inside which a blocking device read is sanctioned —
#: everywhere else, ``device_get``/``block_until_ready`` in a phase
#: scope is a profiling bug that charges device wait to the wrong
#: phase (ndpplint NDPP701)
BLOCKING_ALLOWED = frozenset({HARVEST})

# set-up stages -------------------------------------------------------------
YOULA = "youla"
PROPOSAL_EIGENS = "proposal_eigens"
TREE_BUILD = "tree_build"
ENGINE_INIT = "engine_init"      # engine_init/<backend>
FIRST_TICK = "first_tick"        # first_tick/<backend>

SETUP_STAGES = {
    YOULA: "host Youla decomposition (core.youla.spectral_from_params)",
    PROPOSAL_EIGENS: "proposal eigendecomposition (core.tree.proposal_eigens)",
    TREE_BUILD: "proposal tree build (core.tree.construct_tree)",
    ENGINE_INIT: "SamplerEngine.__init__, per backend (placement, "
                 "auto n_spec's det_ratio_exact)",
    FIRST_TICK: "an engine's first tick that progressed, per backend: "
                "its compile or compile-cache load",
}

# device scopes -------------------------------------------------------------
SCOPE_PREFIX = "ndpp."

PROPOSAL = SCOPE_PREFIX + "proposal"
DESCENT_OPERANDS = SCOPE_PREFIX + "descent_operands"
TREE_DESCENT = SCOPE_PREFIX + "tree_descent"
LEAF_SCORING = SCOPE_PREFIX + "leaf_scoring"
LOGDET_RATIO = SCOPE_PREFIX + "logdet_ratio"
ACCEPT = SCOPE_PREFIX + "accept"
MCMC_STEP = SCOPE_PREFIX + "mcmc_step"

DEVICE_SCOPES = {
    PROPOSAL: "proposal DPP draw (eigenvector coins + tree sampling)",
    DESCENT_OPERANDS: "tree levels flattened into the descent kernel's "
                      "node layout, once per round",
    TREE_DESCENT: "root-to-block tree traversal levels",
    LEAF_SCORING: "batched bilinear leaf-block scoring",
    LOGDET_RATIO: "2K-space log-det acceptance ratio",
    ACCEPT: "acceptance coin flips",
    MCMC_STEP: "vmapped Metropolis-Hastings chain advance",
}

#: bucket for device ops that fall under no ``ndpp.*`` named scope
UNATTRIBUTED = "unattributed"
