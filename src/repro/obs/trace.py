"""The one span primitive: host seconds into a counter, and, when
profiling is on, a ``jax.profiler.TraceAnnotation`` of the same name.

``span(name, record, enabled)`` times its body on the host clock
(``repro.obs.now``) and hands the seconds to ``record`` — always, so the
counters a benchmark reads exist with the profiler off.  With
``enabled`` (resolved once by the caller from ``NDPP_PROFILE=1``) it
also opens the gated annotation ``name`` around the body, on the
profiler's clock, which the device trace shares: a device idle gap is
then named by the host span open across it.  Disabled, it builds no
annotation and never imports the profiler.

This module is the ONE place the repo constructs
``jax.profiler.TraceAnnotation`` (enforced by ndpplint NDPP702): every
annotation goes through the same enable gate, so a stray always-on
annotation can never leak profiler overhead into production ticks.
"""
from __future__ import annotations

import contextlib
import os
from typing import Callable

from repro.obs.spans import now

PROFILE_ENV = "NDPP_PROFILE"

#: prefix under which host phase spans appear in captured traces —
#: ``repro.obs.prof.parse`` keys its phase attribution off this
PHASE_PREFIX = "ndpp_phase/"
#: prefix of the set-up stage spans (``repro.obs.setup_stage``)
SETUP_PREFIX = "ndpp_setup/"


def profiling_enabled() -> bool:
    """True iff ``NDPP_PROFILE=1`` in the environment."""
    return os.environ.get(PROFILE_ENV, "") == "1"


class _NullContext(contextlib.AbstractContextManager):
    """Reusable no-op context (cheaper than nullcontext() per tick)."""

    def __exit__(self, *exc):
        return False


_NULL = _NullContext()


def annotation(name: str, enabled: bool):
    """The gated ``TraceAnnotation`` constructor (see module doc): a
    shared no-op context unless ``enabled``."""
    if not enabled:
        return _NULL
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


class _Span:
    """Context manager built by ``span``: times the body, records it."""

    __slots__ = ("_record", "_ann", "_t0")

    def __init__(self, record: Callable[[float], None], ann):
        self._record = record
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = now()
        return self

    def __exit__(self, *exc):
        seconds = now() - self._t0
        self._ann.__exit__(*exc)
        self._record(seconds)
        return False


def span(name: str, record: Callable[[float], None], enabled: bool) -> _Span:
    """Time the ``with`` body: ``record(seconds)`` at exit, and the
    annotation ``name`` around the body when ``enabled``."""
    return _Span(record, annotation(name, True) if enabled else _NULL)
