"""Slot-based batched serving engine for NDPP sampling (two backends).

The LM serving engine (``serve.engine``) keeps a fixed pool of request
slots so decode batches stay full without recompiling; this engine applies
the same pattern to the paper's samplers.

``backend="rejection"`` (default): a fixed pool of ``n_slots`` sampling
requests shares ONE jitted speculative round per tick — every occupied slot
contributes ``n_spec`` i.i.d. proposals to a single fused dispatch that
traces the per-slot ``fold_in`` key fan-out, the batched tree traversal,
and the batched log-det ratio into one jit
(``core.rejection._spec_round_fused``).  A slot retires at its first
accepted proposal.

``backend="mcmc"``: slot = chain.  Every occupied slot is an independent
up/down (or fixed-size swap) Metropolis chain (``core.mcmc``); one jitted
vmapped call advances the whole pool ``mcmc_steps_per_tick`` steps per
tick, and a slot retires with the chain state at step ``burn_in + thin``.
This is the backend of last resort for *unconstrained* NDPP kernels, where
the rejection rate is unbounded and the rejection backend can exhaust
``max_trials`` without accepting: MCMC per-step cost depends only on the
kernel rank, never on the rejection rate.

Both rejection flavors — a static preprocessed ``NDPPSampler`` or a
dynamic ``serve.catalog.Catalog`` — share the pool: in catalog mode each
request *pins* the ``CatalogState`` current at admission (proposal
snapshot + live acceptance target), ``swap_catalog()`` installs a new
version between ticks without draining in-flight slots, and each tick
runs one speculative round per distinct pinned version still in flight.

Exactness: proposal t of request ``rid`` is always generated from
``fold_in(request_key, t)`` (rejection), and MH step t of a chain from
``fold_in(chain_key, t)`` (MCMC), so the draw a request receives is
independent of pool occupancy, admission order, n_spec, and tick size — it
is the same sequence the standalone sampler would consume.  (For MCMC the
inverse-cache refresh fires on the absolute schedule ``step %
refresh_every == 0``, so this holds bit-exactly for tick sizes dividing
``mcmc_refresh_every``; other tick sizes refresh less often, which only
changes float drift, never the chain's exact-arithmetic trajectory.)
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import warnings
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import mcmc as mcmc_core
from repro.core.dynamic import auto_n_spec_dynamic
from repro.core.rejection import (
    NDPPSampler,
    _spec_round_fused,
    auto_n_spec,
    shard_sampler,
)
from repro.core.tree import shard_spectral
from repro.core.types import SpectralNDPP
from repro.obs import (
    PHASE_PREFIX,
    Span,
    Telemetry,
    engine_instruments,
    setup_stage,
)
from repro.obs import span as obs_span
from repro.obs.prof import NULL_ACCOUNTANT, Accountant
from repro.obs.prof import phases as prof_phases
from repro.serve.catalog import Catalog, CatalogState, as_state

#: shared no-op context for the uninstrumented engine's phase scopes
_NULL_PHASE = contextlib.nullcontext()


def _discard(seconds: float) -> None:
    """Span recorder of a tick with nothing to advance."""


class TickBudgetExhausted(RuntimeError):
    """``run(max_ticks=...)`` ended with work still queued or in flight.

    Attributes:
      unfinished: {rid: span-state dict} for requests still holding slots.
      queued: rids never admitted.
    """

    def __init__(self, msg: str, unfinished: Dict[int, dict],
                 queued: List[int]):
        super().__init__(msg)
        self.unfinished = unfinished
        self.queued = queued


#: set once the device-key fallback has warned — the extra admission
#: dispatch should be visible exactly once per process, not per request
_DEVICE_KEY_WARNED = False


@functools.lru_cache(maxsize=None)
def _device_prng_key(impl: str, seed: int) -> np.ndarray:
    """Device-built raw key for PRNG impls with no host-side layout.

    One dispatch per *distinct* (impl, seed), cached for the process —
    re-admitting a seed is free — with a one-time ``RuntimeWarning`` so
    the per-admission dispatch never hides from a profile.  (``impl`` is
    a cache-key argument because the active default impl can change
    between calls under ``jax.default_prng_impl``.)
    """
    global _DEVICE_KEY_WARNED
    if not _DEVICE_KEY_WARNED:
        _DEVICE_KEY_WARNED = True
        warnings.warn(
            f"jax_default_prng_impl={impl!r} has no host-side key "
            f"construction: admission builds request keys on device (one "
            f"cached dispatch per distinct seed)",
            RuntimeWarning, stacklevel=3)
    return jax.device_get(jax.random.PRNGKey(seed))


def _prng_key_words() -> int:
    """uint32 words in a raw key of the active default PRNG impl (the
    engine's ``slot_key`` row width)."""
    impl = str(jax.config.jax_default_prng_impl)
    if impl == "threefry2x32":
        return 2
    if impl in ("rbg", "unsafe_rbg"):
        return 4
    return int(_device_prng_key(impl, 0).shape[0])


def _host_prng_key(seed: int) -> np.ndarray:
    """Raw uint32 key bit-identical to ``jax.random.PRNGKey(seed)``.

    Admission runs inside the tick loop, and building the key on device
    dispatches a scalar convert kernel per request (which recompiles on
    every call under ``jax_check_tracer_leaks``).  The threefry2x32 seed
    layout is just the 64-bit seed split into two uint32 words, and the
    rbg/unsafe_rbg layout is that halfkey tiled twice, so build those on
    host; any other impl falls back to a cached, warned device build
    (``_device_prng_key``) instead of silently dispatching per admission.
    """
    impl = str(jax.config.jax_default_prng_impl)
    s = int(seed)
    if jax.config.jax_enable_x64:
        # threefry_seed: hi = shift_right_logical(seed, 32), lo = low word
        hi = (s & 0xFFFFFFFFFFFFFFFF) >> 32
    else:
        # the seed is canonicalized to int32 first, and a logical shift of
        # a 32-bit value by 32 is zero — the hi word is always 0
        hi = 0
    half = np.array([hi, s & 0xFFFFFFFF], np.uint32)
    if impl == "threefry2x32":
        return half
    if impl in ("rbg", "unsafe_rbg"):
        # rbg_seed = concat([threefry_seed, threefry_seed]): [hi,lo,hi,lo]
        return np.concatenate([half, half])
    return _device_prng_key(impl, s)


@dataclasses.dataclass
class SampleRequest:
    """One sampling request submitted to the engine.

    Attributes:
      rid: caller-chosen request id; keys the ``run()`` result dict.
      seed: PRNG seed — proposal/step t of this request is always drawn
        from ``fold_in(PRNGKey(seed), t)``, independent of scheduling.
      max_trials: rejection-backend proposal budget (ignored by MCMC,
        which always retires at step ``burn_in + thin``).
      result: filled by the engine at retire time.
    """

    rid: int
    seed: int = 0
    max_trials: int = 256
    # filled by the engine at retire time:
    result: Optional["SampleResult"] = None


@dataclasses.dataclass
class SampleResult:
    """A retired request's draw.

    Attributes:
      items: (R,) padded item indices, R = 2K; -1 marks empty slots.
      mask: (R,) validity mask (``items[mask]`` is the sampled subset).
      trials: proposals consumed (rejection) or MH steps taken (MCMC).
      accepted: False iff the rejection budget was exhausted (the last
        proposal is returned anyway; always True for MCMC).
    """

    items: np.ndarray        # (R,) padded item indices (-1 = empty slot)
    mask: np.ndarray         # (R,) validity mask
    trials: int              # proposals consumed by this request
    accepted: bool           # False => max_trials exhausted


class SamplerEngine:
    """Continuous-batching frontend over the NDPP samplers.

    ``backend="rejection"`` speculatively batches Algorithm-2 proposals
    across the pool; ``backend="mcmc"`` runs one Metropolis chain per slot
    (``mcmc_k=None`` = variable-size up/down chain, an integer = fixed-size
    swap chain) and retires a request with the chain state at step
    ``mcmc_burn_in + mcmc_thin``.  The MCMC backend accepts either a
    preprocessed ``NDPPSampler`` or a bare ``SpectralNDPP`` (no proposal
    tree is needed).

    Args:
      sampler: ``NDPPSampler`` (static rejection), a ``Catalog`` /
        ``CatalogState`` (dynamic-catalog mode: requests pin the catalog
        version they were admitted under and ``swap_catalog`` installs new
        versions with zero drain), or, for MCMC, a bare ``SpectralNDPP``.
      n_slots: pool size — concurrent in-flight requests per tick.
      n_spec: rejection speculation depth per slot per tick (default
        auto-sizes to ~E[#trials]).
      backend: "rejection" or "mcmc".
      mcmc_burn_in / mcmc_thin: a chain retires with its state at step
        ``burn_in + thin``.
      mcmc_steps_per_tick: MH steps the whole pool advances per tick
        (default ``min(refresh_every, burn_in + thin)``).
      mcmc_k: None = up/down chain; an integer runs the fixed-size swap
        chain with stochastic-greedy size-k starts.
      mcmc_p_swap: swap-move mixture weight of the up/down chain.
      mcmc_refresh_every: exact O(R^3) inverse-cache refresh period.
      mesh: shard the item axis across the mesh "model" axis.  The
        engine places the sampler arrays once (``shard_sampler`` /
        ``shard_spectral``) and every tick runs the sharded round /
        chain step: per-device catalog memory drops to M/S rows while
        results stay bit-identical to the unsharded engine (the
        fold_in(request_key, t) exactness guarantee is untouched).
        Requires M divisible by the mesh "model" extent.
      telemetry: ``repro.obs.Telemetry`` — per-request spans, labelled
        metrics, and a flight recorder of recent events.  Instrumentation
        is free: draws are bit-identical to an uninstrumented engine, no
        extra compiles, no extra device→host transfers (device stats are
        piggybacked onto the arrays each tick already ``device_get``s).
      on_exhausted: what ``run()`` does when the tick budget ends with
        requests still queued/in flight — "raise" (default,
        ``TickBudgetExhausted``), "warn", or "ignore" (the old silent
        partial-result behavior).  A flight-recorder event is emitted in
        every mode when telemetry is attached.
    """

    def __init__(self, sampler: Union[NDPPSampler, SpectralNDPP, Catalog,
                                      CatalogState],
                 n_slots: int = 8, n_spec: Optional[int] = None,
                 backend: str = "rejection", mcmc_burn_in: int = 256,
                 mcmc_thin: int = 16, mcmc_steps_per_tick: Optional[int] = None,
                 mcmc_k: Optional[int] = None, mcmc_p_swap: float = 0.25,
                 mcmc_refresh_every: int = 64,
                 mesh: Optional[Mesh] = None,
                 telemetry: Optional[Telemetry] = None,
                 on_exhausted: str = "raise"):
        if backend not in ("rejection", "mcmc"):
            raise ValueError(f"unknown backend {backend!r}")
        if on_exhausted not in ("raise", "warn", "ignore"):
            raise ValueError(f"unknown on_exhausted mode {on_exhausted!r}")
        # set-up stage engine_init/<backend>: placement, auto n_spec's
        # det_ratio_exact, the MCMC pool state — blocked on before it closes
        with setup_stage(f"{prof_phases.ENGINE_INIT}/{backend}"):
            self.on_exhausted = on_exhausted
            self.backend = backend
            self.mesh = mesh
            self._cat: Optional[CatalogState] = None
            if isinstance(sampler, (Catalog, CatalogState)):
                # dynamic-catalog mode: the catalog owns preprocessing,
                # mesh placement, and versioning; each request pins the
                # CatalogState current at admission, so swap_catalog
                # never drains the pool
                if isinstance(sampler, Catalog):
                    if mesh is not None and sampler.mesh is not mesh:
                        raise ValueError(
                            "pass the catalog's own mesh (or none) — the "
                            "catalog arrays are already placed on it")
                    self.mesh = mesh = sampler.mesh
                self._cat = as_state(sampler)
                self.sampler = None
                self.sp = self._cat.sp
            elif isinstance(sampler, NDPPSampler):
                self.sampler: Optional[NDPPSampler] = sampler
                self.sp = sampler.sp
            else:
                if backend == "rejection":
                    raise ValueError(
                        "backend='rejection' needs a preprocessed "
                        "NDPPSampler or a Catalog/CatalogState")
                self.sampler = None
                self.sp = sampler
            if mesh is not None and self._cat is None:
                from repro.models.sharding import model_extent

                s = model_extent(mesh)
                if self.sp.M % s != 0:
                    raise ValueError(
                        f"the mesh 'model' extent {s} must divide the "
                        f"catalog size M={self.sp.M} — pad the catalog or "
                        f"shrink the mesh")
                if self.sampler is not None:
                    tree = self.sampler.tree
                    if tree.W.shape[0] % (s * tree.block) != 0:
                        # a "sharded" engine that silently replicates the
                        # tree (the dominant memory) is a config bug, not a
                        # fallback
                        raise ValueError(
                            f"cannot shard the proposal tree: each shard "
                            f"must own whole leaf blocks, i.e. {s} * block="
                            f"{tree.block} must divide M_pad="
                            f"{tree.W.shape[0]} — use a smaller block or "
                            f"shrink the mesh")
                    self.sampler = shard_sampler(self.sampler, mesh)
                    self.sp = self.sampler.sp
                else:
                    self.sp = shard_spectral(self.sp, mesh)
            self.n_slots = n_slots
            if backend == "rejection":
                # default the speculation depth to ~E[#trials] so most
                # requests retire after a single tick
                self._auto_spec = n_spec is None
                if n_spec is not None:
                    self.n_spec = n_spec
                elif self._cat is not None:
                    self.n_spec = auto_n_spec_dynamic(self._cat.proposal,
                                                      self._cat.sp)
                else:
                    self.n_spec = auto_n_spec(sampler)
            else:
                self.mcmc_burn_in = mcmc_burn_in
                self.mcmc_thin = mcmc_thin
                self.mcmc_k = mcmc_k
                self.mcmc_p_swap = mcmc_p_swap
                self.mcmc_refresh_every = mcmc_refresh_every
                self.mcmc_steps_per_tick = (
                    min(mcmc_refresh_every, mcmc_burn_in + mcmc_thin)
                    if mcmc_steps_per_tick is None else mcmc_steps_per_tick)
                init = mcmc_core.init_empty(self.sp)
                self._states = jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (n_slots,) + a.shape),
                    init)
            self.queue: List[SampleRequest] = []
            self.slot_req: List[Optional[SampleRequest]] = [None] * n_slots
            self.slot_key = np.zeros((n_slots, _prng_key_words()), np.uint32)
            self.slot_trials = np.zeros(n_slots, np.int64)
            # catalog mode: the CatalogState each in-flight request samples
            # from — pinned at admission, released at retire
            self.slot_pin: List[Optional[CatalogState]] = [None] * n_slots
            self.finished: Dict[int, SampleResult] = {}
            self.ticks = 0
            # set once the first tick that progresses has run (its own
            # set-up stage: the tick program's compile or cache load)
            self._ticked = False
            self._tel = telemetry
            self._spans: Dict[int, Span] = {}
            # every jitted call / put / designed device_get goes through
            # the accountant, so dispatch and transfer counts are exact at
            # the call boundary (repro.obs.prof.accounting); the bare
            # engine gets the straight-through null twin
            self._acct = NULL_ACCOUNTANT
            if telemetry is not None:
                self._m = engine_instruments(telemetry.registry)
                self._acct = Accountant(backend, instruments=self._m)
                # span recorders, built once: the tick's wall seconds and
                # each phase's host seconds
                self._tick_rec = functools.partial(
                    self._m.tick_seconds.observe, backend=backend)
                self._phase_rec = {
                    p: functools.partial(self._m.phase_seconds.inc,
                                         backend=backend, phase=p)
                    for p in prof_phases.ENGINE_PHASES}
                # compile visibility: poll the process-wide CompileCounter
                # after each tick so unexpected recompiles show up as a
                # counter bump + flight event instead of silent latency
                from repro.analysis.runtime import CompileCounter

                self._cc = CompileCounter.install()
                self._cc_seen = self._cc.count
                telemetry.flight.record(
                    "engine_start", backend=backend, n_slots=n_slots,
                    n_spec=getattr(self, "n_spec", None),
                    catalog_version=None if self._cat is None
                    else self._cat.version)
                if self._cat is not None:
                    self._m.catalog_version.set(self._cat.version)
            jax.block_until_ready((self.sampler, self.sp,
                                   getattr(self, "_states", None)))

    # ------------------------------------------------------------- frontend
    def submit(self, req: SampleRequest, span: Optional[Span] = None):
        """Queue a request.  ``span`` lets a front door hand down the span
        it opened at *its* admission point, so submit→retire latency is
        measured from the moment the request entered the serving stack,
        not from this (possibly much later) staging call."""
        self.queue.append(req)
        if self._tel is not None:
            self._spans[req.rid] = span if span is not None else Span(
                rid=req.rid, seed=req.seed, backend=self.backend)
            self._m.submitted.inc(backend=self.backend)
            self._m.queue_depth.set(len(self.queue))
            self._tel.flight.record("submit", rid=req.rid, seed=req.seed)

    def cancel(self, rid: int, outcome: str = "cancelled") -> bool:
        """Abandon a *queued* (never-admitted) request.

        Returns True iff ``rid`` was waiting in the queue and has been
        removed; its span terminates in the ``shed``/``cancelled`` state
        (per ``outcome``) instead of ``retired``, so the queue-wait and
        latency histograms — which only observe at admit/retire — are
        never polluted by requests that were never served.  In-flight or
        finished requests are not cancellable (returns False): a slot
        that already burned proposals always retires normally.
        """
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                if self._tel is not None:
                    span = self._spans.pop(rid, None)
                    if span is not None:
                        span.abandon(outcome)
                    self._m.abandoned.inc(backend=self.backend,
                                          outcome=outcome)
                    self._m.queue_depth.set(len(self.queue))
                    self._tel.flight.record("abandon", rid=rid,
                                            outcome=outcome)
                return True
        return False

    def swap_catalog(self, cat: Union[Catalog, CatalogState]):
        """Install a new catalog version between ticks — zero drain.

        Rejection backend: in-flight slots keep sampling from the
        ``CatalogState`` they pinned at admission (proposal *and*
        acceptance target — a request's draw is exactly distributed for
        the version it was admitted under, bit-identical to an engine
        that never swapped); only newly admitted requests see the new
        version.  Old versions are garbage once their last slot retires.

        MCMC backend: chains track the *live* kernel, so the pool
        switches target immediately — every cached inverse is re-anchored
        against the new rows (``mcmc.reanchor``) and subset items deleted
        by the new version are dropped; the chains' step counters (and so
        their key schedules) are untouched.
        """
        st = as_state(cat)
        if self.backend == "rejection" and self._cat is None:
            raise ValueError("swap_catalog on a rejection engine requires "
                             "it to have been built from a Catalog")
        if self._tel is not None:
            self._m.swaps.inc()
            self._m.catalog_version.set(st.version)
            self._tel.flight.record(
                "catalog_swap", version=st.version,
                from_version=None if self._cat is None
                else self._cat.version,
                stale=st.stale,
                in_flight=[r.rid for r in self.slot_req if r is not None])
        self._cat = st
        self.sp = st.sp
        if self.backend == "mcmc":
            self._states = mcmc_core.reanchor(st.sp, self._states)
        elif self._auto_spec:
            # keep the speculation depth tuned to the *current* catalog's
            # E[#trials] — a swap can move the rate by an order of magnitude
            self.n_spec = auto_n_spec_dynamic(st.proposal, st.sp)

    def _phase(self, name: str):
        """Span of one engine phase: its host seconds go to
        ``ndpp_phase_seconds_total{backend, phase}``, and under
        ``NDPP_PROFILE=1`` it is the annotation ``ndpp_phase/<name>``
        (a no-op without telemetry)."""
        if self._tel is None:
            return _NULL_PHASE
        return obs_span(PHASE_PREFIX + name, self._phase_rec[name],
                        self._tel.profile)

    def _init_chain_state(self, seed: int) -> mcmc_core.MCMCState:
        """Deterministic per-request chain start (schedule-independent):
        empty for the up/down chain, stochastic-greedy size-k for the swap
        chain (keyed off the chain key, disjoint from the step schedule)."""
        if self.mcmc_k is None:
            return mcmc_core.init_empty(self.sp)
        greedy_key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x67726479)
        st = mcmc_core.init_greedy(self.sp, greedy_key, 1, self.mcmc_k,
                                   mesh=self.mesh)
        return jax.tree_util.tree_map(lambda a: a[0], st)

    def _admit(self):
        for slot in range(self.n_slots):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue.pop(0)
                self.slot_req[slot] = req
                self.slot_key[slot] = _host_prng_key(req.seed)
                self.slot_trials[slot] = 0
                self.slot_pin[slot] = self._cat
                if self.backend == "mcmc":
                    st = self._init_chain_state(req.seed)
                    self._states = jax.tree_util.tree_map(
                        lambda a, v: a.at[slot].set(v), self._states, st)
                if self._tel is not None:
                    span = self._spans[req.rid]
                    span.admit(slot, None if self._cat is None
                               else self._cat.version)
                    self._m.queue_wait.observe(span.queue_wait,
                                               backend=self.backend)
                    self._tel.flight.record(
                        "admit", rid=req.rid, slot=slot, tick=self.ticks,
                        queue_wait_s=round(span.queue_wait, 9))

    def _retire(self, slot: int, result: SampleResult):
        req = self.slot_req[slot]
        req.result = result
        self.finished[req.rid] = result
        self.slot_req[slot] = None
        self.slot_pin[slot] = None
        if self._tel is not None:
            span = self._spans.pop(req.rid, None)
            if span is not None:
                span.retire(result.trials, result.accepted)
                self._m.retired.inc(
                    backend=self.backend,
                    accepted="true" if result.accepted else "false")
                self._m.trials_total.inc(int(result.trials),
                                         backend=self.backend)
                if result.accepted:
                    self._m.request_trials.observe(int(result.trials),
                                                   backend=self.backend)
                self._m.latency.observe(span.wall, backend=self.backend)
                self._m.ticks_held.observe(span.ticks_held,
                                           backend=self.backend)
                self._tel.flight.record(
                    "retire", rid=req.rid, slot=slot,
                    trials=int(result.trials),
                    accepted=bool(result.accepted),
                    ticks_held=span.ticks_held,
                    wall_s=round(span.wall, 9))

    # ----------------------------------------------------------------- core
    def step(self) -> bool:
        """One engine tick: admit from queue, advance the whole pool with
        one jitted fixed-shape call, retire finished slots."""
        # the tick progresses iff it has work: admission fills a free slot
        # whenever the queue holds a request
        busy = bool(self.queue) or any(r is not None for r in self.slot_req)
        if busy and not self._ticked:
            self._ticked = True
            with setup_stage(f"{prof_phases.FIRST_TICK}/{self.backend}"):
                return self._tick(busy)
        return self._tick(busy)

    def _tick(self, busy: bool) -> bool:
        advance = (self._step_mcmc if self.backend == "mcmc"
                   else self._step_rejection)
        if self._tel is None:
            return advance()
        # a tick with work feeds ndpp_tick_seconds (tick_ms.rej reads it)
        with obs_span(f"ndpp_engine_tick/{self.backend}",
                      self._tick_rec if busy else _discard,
                      self._tel.profile):
            progressed = advance()
        if progressed:
            self._m.ticks.inc(backend=self.backend)
        self._m.slots_occupied.set(
            sum(r is not None for r in self.slot_req))
        self._m.queue_depth.set(len(self.queue))
        new_compiles = self._cc.count - self._cc_seen
        if new_compiles:
            self._cc_seen = self._cc.count
            self._m.compiles.inc(new_compiles)
            self._tel.flight.record("compile", n=new_compiles,
                                    tick=self.ticks, backend=self.backend)
        return progressed

    def _step_mcmc(self) -> bool:
        """Advance every chain ``mcmc_steps_per_tick`` MH steps in one
        vmapped call (vacant slots carry dummy chains so shapes never
        change); a slot retires with the chain state at exactly step
        ``burn_in + thin``, read out of the per-step trace."""
        with self._phase(prof_phases.ADMISSION):
            self._admit()
        if all(r is None for r in self.slot_req):
            return False
        self.ticks += 1
        n_steps = self.mcmc_steps_per_tick
        with self._phase(prof_phases.ROUND_DISPATCH):
            key_dev = self._acct.put("slot_key", self.slot_key)
            if self.mesh is None:
                states, items_tr, mask_tr, acc_tr = self._acct.call(
                    "run_chains", mcmc_core.run_chains,
                    self.sp, key_dev, self._states,
                    n_steps=n_steps, fixed=self.mcmc_k is not None,
                    p_swap=self.mcmc_p_swap,
                    refresh_every=self.mcmc_refresh_every)
            else:
                states, items_tr, mask_tr, acc_tr = self._acct.call(
                    "run_chains_sharded", mcmc_core.run_chains_sharded,
                    self.sp, key_dev, self._states,
                    mesh=self.mesh, n_steps=n_steps,
                    fixed=self.mcmc_k is not None, p_swap=self.mcmc_p_swap,
                    refresh_every=self.mcmc_refresh_every)
        self._states = states
        # the designed once-per-tick device→host sync (routed through the
        # accountant; explicit so strict transfer-guard runs see it as
        # intentional).  Telemetry piggybacks the acceptance trace onto
        # the same call — it is already an output of the jitted chain
        # step, so this widens the existing sync, never adds one (and
        # never changes the compiled program).
        with self._phase(prof_phases.HARVEST):
            if self._tel is None:
                items_h, mask_h = self._acct.device_get(
                    (items_tr, mask_tr))  # (S, n_steps, R)
            else:
                items_h, mask_h, acc_h = self._acct.device_get(
                    (items_tr, mask_tr, acc_tr))
        occupied = [s for s in range(self.n_slots)
                    if self.slot_req[s] is not None]
        if self._tel is not None:
            frac = float(np.mean(acc_h[occupied]))
            self._m.mcmc_accept.observe(frac)
            self._m.mcmc_steps.inc(n_steps * len(occupied))
            self._m.proposals.inc(n_steps * len(occupied), backend="mcmc")
            self._m.accepts.inc(int(np.sum(acc_h[occupied])),
                                backend="mcmc")
        target = self.mcmc_burn_in + self.mcmc_thin
        for slot in occupied:
            if self._tel is not None:
                span = self._spans[self.slot_req[slot].rid]
                span.ticks_held += 1
                span.chain_steps += n_steps
            before = int(self.slot_trials[slot])
            self.slot_trials[slot] = before + n_steps
            if before + n_steps >= target:
                idx = target - before - 1
                self._retire(slot, SampleResult(
                    items=items_h[slot, idx], mask=mask_h[slot, idx],
                    trials=target, accepted=True,
                ))
        return True

    def _step_rejection(self) -> bool:
        """One speculative rejection round for the whole pool — a single
        fused dispatch per round: the per-slot ``fold_in`` key fan-out,
        tree descent + leaf scoring, and the bilinear log-det ratio are
        all traced into one jit (``core.rejection._spec_round_fused``),
        so the steady-state tick costs exactly one dispatch plus the one
        designed harvest ``device_get``.

        Catalog mode runs one round per *distinct pinned catalog version*
        among the occupied slots (at most the number of swaps in flight,
        normally 1): every round uses the full fixed-shape pool fan-out,
        and a slot harvests only from its own version's round — so a
        request's proposals and acceptance tests always come from the
        arrays it was admitted under.
        """
        with self._phase(prof_phases.ADMISSION):
            self._admit()
        if all(r is None for r in self.slot_req):
            return False
        self.ticks += 1
        # operands cross the jit boundary as host numpy arrays: op-by-op
        # jnp conversions would dispatch (and, under
        # jax_check_tracer_leaks, recompile) tiny convert/iota kernels on
        # every tick.  The per-slot spec offsets are a traced arange
        # *inside* the fused round, so they never cross the boundary.
        trials_host = np.asarray(self.slot_trials, np.uint32)
        if self._cat is None:
            slot_groups = [(None, [s for s in range(self.n_slots)
                                   if self.slot_req[s] is not None])]
        else:
            # group by pinned-state identity (not just version: states from
            # different Catalog objects could share a version number)
            by_pin: Dict[int, List[int]] = {}
            for s in range(self.n_slots):
                if self.slot_req[s] is not None:
                    by_pin.setdefault(id(self.slot_pin[s]), []).append(s)
            slot_groups = sorted(
                ((self.slot_pin[ss[0]], ss) for ss in by_pin.values()),
                key=lambda g: g[0].version)
        for pin, slots in slot_groups:
            # a pinned version proposes from its snapshot and scores its
            # live kernel; a static sampler scores its own
            kw = {} if self.mesh is None else {"mesh": self.mesh}
            if pin is not None:
                kw["live"] = pin.sp
            # exactly one dispatch per speculative round: fan-out, round
            # body, and accept test ride in the same jit
            with self._phase(prof_phases.ROUND_DISPATCH):
                items, mask, accept = self._acct.call(
                    "_spec_round_fused", _spec_round_fused,
                    self.sampler if pin is None else pin.proposal,
                    self.slot_key, trials_host, n_spec=self.n_spec, **kw)
            self._harvest(slots, items, mask, accept)
        return True

    def _harvest(self, slots: List[int], items, mask, accept):
        """Retire-or-advance the given slots from one round's outputs."""
        r = items.shape[-1]
        # the designed once-per-tick device→host sync (routed through the
        # accountant); explicit so strict transfer-guard runs see it as
        # intentional
        with self._phase(prof_phases.HARVEST):
            items_h, mask_h, acc = self._acct.device_get(
                (items, mask, accept))
        acc = acc.reshape(self.n_slots, self.n_spec)
        items_h = items_h.reshape(self.n_slots, self.n_spec, r)
        mask_h = mask_h.reshape(self.n_slots, self.n_spec, r)
        round_proposals = 0
        round_accepts = 0
        for slot in slots:
            req = self.slot_req[slot]
            # only proposals inside the request's max_trials budget count,
            # so the engine matches sample_batched_many's trial accounting
            # even when the budget is not a multiple of n_spec
            remaining = int(req.max_trials - self.slot_trials[slot])
            usable = min(self.n_spec, remaining)
            row = acc[slot, :usable]
            if self._tel is not None:
                span = self._spans[req.rid]
                span.ticks_held += 1
                span.rounds += 1
                span.proposals += usable
                round_proposals += usable
                round_accepts += int(row.sum())
            if row.any():
                first = int(row.argmax())
                self._retire(slot, SampleResult(
                    items=items_h[slot, first], mask=mask_h[slot, first],
                    trials=int(self.slot_trials[slot]) + first + 1,
                    accepted=True,
                ))
            else:
                self.slot_trials[slot] += usable
                if self.slot_trials[slot] >= req.max_trials:
                    self._retire(slot, SampleResult(
                        items=items_h[slot, usable - 1],
                        mask=mask_h[slot, usable - 1],
                        trials=int(self.slot_trials[slot]), accepted=False,
                    ))
        if self._tel is not None:
            self._m.rounds.inc(backend=self.backend)
            self._m.proposals.inc(round_proposals, backend=self.backend)
            self._m.accepts.inc(round_accepts, backend=self.backend)

    def run(self, max_ticks: int = 10_000) -> Dict[int, SampleResult]:
        """Drain the queue; returns {rid: SampleResult} for every retired
        request (recorded at retire time, not collected from slots).

        If the tick budget runs out with requests still queued or in
        flight, raises ``TickBudgetExhausted`` listing the unfinished
        request ids and their span state (``on_exhausted="warn"`` demotes
        this to a ``RuntimeWarning``, ``"ignore"`` restores the old
        silent partial-result behavior); with telemetry attached a
        ``tick_budget_exhausted`` flight event is recorded first and the
        recorder is dumped to ``Telemetry.dump_on_error`` if configured.
        """
        for _ in range(max_ticks):
            progressed = self.step()
            if not progressed and not self.queue:
                break
        if self.queue or any(r is not None for r in self.slot_req):
            self._report_exhausted(max_ticks)
        return dict(self.finished)

    def _report_exhausted(self, max_ticks: int):
        unfinished: Dict[int, dict] = {}
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            span = self._spans.get(req.rid)
            unfinished[req.rid] = (
                span.snapshot() if span is not None
                else {"rid": req.rid, "state": "active", "slot": slot,
                      "trials": int(self.slot_trials[slot])})
        queued = [req.rid for req in self.queue]
        if self._tel is not None:
            self._tel.flight.record(
                "tick_budget_exhausted", max_ticks=max_ticks,
                in_flight=sorted(unfinished), queued=queued,
                spans=list(unfinished.values()))
            self._tel.on_error()
        if self.on_exhausted == "ignore":
            return
        msg = (f"run(max_ticks={max_ticks}) exhausted the tick budget with "
               f"{len(unfinished)} request(s) still in flight "
               f"(rids {sorted(unfinished)}, span state {unfinished}) and "
               f"{len(queued)} still queued (rids {queued})")
        if self.on_exhausted == "warn":
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
            return
        raise TickBudgetExhausted(msg, unfinished=unfinished, queued=queued)

    # ------------------------------------------------------------ telemetry
    def stats(self) -> dict:
        """Point-in-time engine snapshot (cheap, host-only).

        Always includes pool/queue occupancy; with telemetry attached,
        adds the full metric snapshot and flight-recorder depth.
        """
        out = {
            "backend": self.backend,
            "ticks": self.ticks,
            "queue_depth": len(self.queue),
            "in_flight": sum(r is not None for r in self.slot_req),
            "finished": len(self.finished),
        }
        if self._cat is not None:
            out["catalog_version"] = self._cat.version
        if self._tel is not None:
            out["metrics"] = self._tel.registry.snapshot()
            out["flight_events"] = len(self._tel.flight)
            out["flight_dropped"] = self._tel.flight.dropped
            out["accounting"] = self._acct.totals()
        return out
