"""Runtime telemetry for the NDPP serving stack.

``repro.obs`` is the one place the repo reads clocks and accumulates
runtime statistics.  Design contract (enforced by tests and ndpplint):

  * **host-only** — metrics, spans, and the flight recorder are plain
    Python state; recording never builds a jnp array, never calls
    ``device_get``, and never runs inside a traced body (NDPP601/602);
  * **free** — the engine records only at its existing host-sync points,
    piggybacking device statistics on arrays it already ``device_get``s,
    so an instrumented engine produces bit-identical draws with zero
    extra compiles and zero extra transfers (tests/test_obs.py,
    tests/test_compile_cache.py);
  * **paper-aligned** — the instrument set tracks the quantities the
    paper bounds: ``ndpp_request_trials`` vs Theorem 2's
    ``ondpp_trial_bound(K) = 2^(K/2)``, per-round acceptance
    (``ndpp_accepts_total / ndpp_proposals_total``), MCMC acceptance
    fractions.  See docs/observability.md for the full catalog.

``Telemetry`` bundles a ``MetricRegistry`` + ``FlightRecorder`` (+
profiler gating) for the engine; ``RegistryObserver`` adapts the same
registry to the duck-typed observer hooks on the batch samplers
(``drive_rounds`` / ``sample_mcmc``).  Host time is measured by one
primitive, ``span`` (``repro.obs.trace``); set-up, which runs before
any ``Telemetry`` exists, records through ``setup_stage`` into the
process-wide ``startup_registry()``.
"""
from __future__ import annotations

import contextlib
import functools
import types
from typing import Optional

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    LogHistogram,
    MetricRegistry,
)
from repro.obs.recorder import FlightRecorder
from repro.obs.spans import Span, now
from repro.obs.trace import (
    PHASE_PREFIX,
    PROFILE_ENV,
    SETUP_PREFIX,
    profiling_enabled,
    span,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "LogHistogram", "MetricRegistry",
    "FlightRecorder", "Span", "now", "Telemetry", "RegistryObserver",
    "engine_instruments", "PROFILE_ENV", "PHASE_PREFIX", "SETUP_PREFIX",
    "profiling_enabled", "span", "startup_registry", "setup_stage",
]

_STARTUP: Optional[MetricRegistry] = None


def startup_registry() -> MetricRegistry:
    """The process-wide registry of the set-up counters.

    Set-up runs before any ``Telemetry`` exists (``preprocess`` is called
    bare), so its stages record here: ``ndpp_setup_seconds_total{stage}``
    and ``ndpp_setup_compile_seconds_total{stage}``.  The front door's
    ``/v1/metrics`` appends its exposition.
    """
    global _STARTUP
    if _STARTUP is None:
        _STARTUP = MetricRegistry()
    return _STARTUP


@contextlib.contextmanager
def setup_stage(stage: str):
    """Span of one set-up stage (``repro.obs.prof.phases.SETUP_STAGES``).

    Adds the body's host seconds to ``ndpp_setup_seconds_total{stage}``,
    and the seconds of the XLA compiles or persistent-cache loads inside
    it (``CompileCounter.seconds``) to
    ``ndpp_setup_compile_seconds_total``;
    under ``NDPP_PROFILE=1`` the body is the annotation
    ``ndpp_setup/<stage>``.  The caller blocks on the stage's outputs
    inside the body, so the stage times the work, not its dispatch.
    Eager host code only: never open a stage inside a traced body.
    """
    from repro.analysis.runtime import CompileCounter

    reg = startup_registry()
    secs = reg.counter("ndpp_setup_seconds_total",
                       "host seconds per set-up stage", ("stage",))
    comp = reg.counter("ndpp_setup_compile_seconds_total",
                       "XLA backend-compile seconds inside each set-up "
                       "stage (a persistent-cache load counts as its "
                       "compile: JAX times both as one event)",
                       ("stage",))
    cc = CompileCounter.install()
    c0 = cc.seconds

    def record(seconds: float) -> None:
        secs.inc(seconds, stage=stage)
        comp.inc(cc.seconds - c0, stage=stage)

    with span(SETUP_PREFIX + stage, record, profiling_enabled()):
        yield


def engine_instruments(registry: MetricRegistry) -> types.SimpleNamespace:
    """Declare the engine's instrument set on ``registry`` (idempotent).

    Shared by ``SamplerEngine`` and ``RegistryObserver`` so the batch
    samplers and the serving engine stream into the same metric names.
    Histogram lattices: latencies use quarter-octave buckets (factor
    2^0.25, ≤19% relative error); trial counts use half-octave buckets
    starting at 1 — Theorem 2 bounds E[trials] by 2^(K/2), i.e. exactly
    K buckets of headroom.
    """
    c, g, h = registry.counter, registry.gauge, registry.histogram
    t = dict(start=1e-5, factor=2 ** 0.25)
    return types.SimpleNamespace(
        submitted=c("ndpp_requests_submitted_total",
                    "requests submitted to the engine", ("backend",)),
        retired=c("ndpp_requests_retired_total",
                  "requests retired, by acceptance",
                  ("backend", "accepted")),
        abandoned=c("ndpp_requests_abandoned_total",
                    "queued requests dropped before admission "
                    "(outcome: shed | cancelled) — these never reach the "
                    "queue-wait or latency histograms",
                    ("backend", "outcome")),
        ticks=c("ndpp_ticks_total", "engine ticks that advanced the pool",
                ("backend",)),
        rounds=c("ndpp_spec_rounds_total",
                 "speculative rejection rounds executed", ("backend",)),
        proposals=c("ndpp_proposals_total",
                    "proposals scored (rejection) / MH steps taken (mcmc)",
                    ("backend",)),
        accepts=c("ndpp_accepts_total",
                  "proposals accepted (rejection) / MH moves accepted "
                  "(mcmc)", ("backend",)),
        trials_total=c("ndpp_trials_total",
                       "trials consumed by retired requests — the "
                       "numerator of measured E[trials]", ("backend",)),
        compiles=c("ndpp_compiles_total",
                   "XLA compiles observed while the engine ran"),
        swaps=c("ndpp_catalog_swaps_total",
                "catalog versions installed via swap_catalog"),
        mcmc_steps=c("ndpp_mcmc_steps_total",
                     "MH steps advanced across all chains"),
        dispatches=c("ndpp_dispatches_total",
                     "executable launches at the engine call boundary — "
                     "the per-tick count the fused-megakernel roadmap "
                     "item must drive to 1 (repro.obs.prof.accounting)",
                     ("backend", "fn")),
        transfer=c("ndpp_transfer_bytes_total",
                   "host<->device bytes at the engine call boundary "
                   "(h2d: numpy leaves entering jitted calls / puts; "
                   "d2h: the designed per-tick device_get harvest)",
                   ("backend", "direction")),
        queue_depth=g("ndpp_queue_depth", "requests waiting for a slot"),
        slots_occupied=g("ndpp_slots_occupied",
                         "slots holding an in-flight request"),
        catalog_version=g("ndpp_catalog_version",
                          "catalog version the engine currently serves"),
        latency=h("ndpp_request_latency_seconds",
                  "submit→retire wall seconds", ("backend",), **t),
        queue_wait=h("ndpp_queue_wait_seconds",
                     "submit→admit wall seconds", ("backend",), **t),
        tick_seconds=h("ndpp_tick_seconds",
                       "wall seconds per engine tick", ("backend",), **t),
        request_trials=h("ndpp_request_trials",
                         "trials-to-accept per accepted request (mean of "
                         "this is measured E[trials]; Theorem 2 bounds it "
                         "by 2^(K/2) for ONDPP kernels)", ("backend",),
                         start=1.0, factor=2 ** 0.5),
        phase_seconds=c("ndpp_phase_seconds_total",
                        "host seconds per engine tick phase (admission, "
                        "round_dispatch, harvest: repro.obs.prof.phases)",
                        ("backend", "phase")),
        ticks_held=h("ndpp_request_ticks_held",
                     "engine ticks a request occupied a slot",
                     ("backend",), start=1.0, factor=2.0),
        mcmc_accept=h("ndpp_mcmc_accept_fraction",
                      "per-sync MH acceptance fraction across occupied "
                      "chains", (), start=1e-3, factor=2 ** 0.25),
    )


class Telemetry:
    """Engine-facing bundle: registry + flight recorder + profiler gate.

    Args:
      registry: share one across engines, or default to a fresh one.
      flight: flight recorder (default: fresh, ``flight_capacity`` events).
      dump_on_error: path the flight recorder is dumped to (JSONL) when
        the engine hits an error path (e.g. tick-budget exhaustion).
      profile: open a ``jax.profiler.TraceAnnotation`` with every
        tick and phase span (``repro.obs.trace.span``); default reads
        ``NDPP_PROFILE=1`` once at construction.
    """

    def __init__(self, registry: Optional[MetricRegistry] = None,
                 flight: Optional[FlightRecorder] = None,
                 flight_capacity: int = 1024,
                 dump_on_error: Optional[str] = None,
                 profile: Optional[bool] = None):
        self.registry = MetricRegistry() if registry is None else registry
        self.flight = (FlightRecorder(flight_capacity) if flight is None
                       else flight)
        self.dump_on_error = dump_on_error
        self.profile = (profiling_enabled() if profile is None
                        else bool(profile))

    def on_error(self) -> Optional[str]:
        """Dump the flight recorder to ``dump_on_error`` (if configured)."""
        if self.dump_on_error is None:
            return None
        self.flight.dump(self.dump_on_error)
        return self.dump_on_error


class RegistryObserver:
    """Duck-typed observer feeding batch-sampler stats into a registry.

    The batch samplers (``core.rejection.drive_rounds``,
    ``core.dynamic.sample_dynamic_many``, ``core.mcmc.sample_mcmc``)
    accept an ``observer`` and call these hooks with plain Python numbers
    they already hold after their designed per-round ``device_get`` —
    the traced bodies of ``core`` never touch ``repro.obs``, and the
    hooks never see a traced value.
    """

    def __init__(self, registry: MetricRegistry, backend: str = "rejection"):
        self.registry = registry
        self.backend = backend
        self._m = engine_instruments(registry)
        self._profile = profiling_enabled()

    def phase(self, name: str):
        """Span around a sampler phase (``drive_rounds`` uses this
        duck-typed hook for its round-dispatch/harvest sections): its
        seconds go to ``ndpp_phase_seconds_total{backend, phase}``."""
        return span(PHASE_PREFIX + name,
                    functools.partial(self._m.phase_seconds.inc,
                                      backend=self.backend, phase=name),
                    self._profile)

    def on_round(self, *, n_active: int, n_spec: int, proposals: int,
                 accepts: int) -> None:
        """One speculative round: pool size, fan-out, outcome counts."""
        self._m.rounds.inc(backend=self.backend)
        self._m.proposals.inc(proposals, backend=self.backend)
        self._m.accepts.inc(accepts, backend=self.backend)

    def on_retire(self, *, trials: int, accepted: bool) -> None:
        """One request leaving the pending set (accepted or exhausted)."""
        self._m.retired.inc(backend=self.backend,
                            accepted="true" if accepted else "false")
        self._m.trials_total.inc(trials, backend=self.backend)
        if accepted:
            self._m.request_trials.observe(trials, backend=self.backend)

    def on_mcmc(self, *, steps: int, n_chains: int,
                accept_fraction: float) -> None:
        """One MCMC run: total MH steps and mean acceptance fraction."""
        self._m.mcmc_steps.inc(steps)
        self._m.proposals.inc(steps, backend="mcmc")
        self._m.accepts.inc(steps * accept_fraction, backend="mcmc")
        self._m.mcmc_accept.observe(accept_fraction)
