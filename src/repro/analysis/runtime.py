"""Runtime teeth for the static analyzer's claims.

Two facilities, both used by the test suite:

* :class:`CompileCounter` — a process-wide counter of actual XLA
  compilations, built on ``jax.monitoring``'s
  ``/jax/core/compile/backend_compile_duration`` event.  The event fires
  once per backend compile and never on a cache hit, which makes "this
  tick loop compiles exactly once per (backend, shape)" a testable
  regression property instead of a code-review hope
  (``tests/test_compile_cache.py``).

* :func:`enable_strict` — the ``NDPP_STRICT=1`` pytest mode: runs the
  suite under ``jax_transfer_guard_device_to_host="disallow"`` plus
  ``jax_check_tracer_leaks``, so any *implicit* device→host transfer in a
  hot path (the thing NDPP303 flags lexically) fails loudly at runtime.
  Host→device stays permissive — feeding numpy arrays into jit is the
  normal way tests build operands.  Sanctioned syncs go through
  ``jax.device_get``, which is explicit and therefore allowed.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA backend compilations process-wide.

    ``jax.monitoring`` offers no listener deregistration, so the listener
    is installed once per process (lazily, on first :meth:`install`) and
    tests read *deltas* around the region they care about::

        counter = CompileCounter.install()
        with counter.measure() as m:
            engine.step()
        assert m.compiles == 0

    Any compile inside the region counts — including compiles of helper
    computations like array constructors — which is exactly the property
    a steady-state tick loop must preserve: after warmup, *nothing*
    compiles.  ``seconds`` sums the events' durations the same way
    (``repro.obs.setup_stage`` reads its deltas); with the persistent
    compilation cache on, the event also fires on a cache load and times
    it.
    """

    _instance: Optional["CompileCounter"] = None

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0   # summed backend-compile durations

    @classmethod
    def install(cls) -> "CompileCounter":
        if cls._instance is None:
            from jax import monitoring

            inst = cls()

            def _listener(name: str, secs: float, **kw) -> None:
                if name == _COMPILE_EVENT:
                    inst.count += 1
                    inst.seconds += secs

            monitoring.register_event_duration_secs_listener(_listener)
            cls._instance = inst
        return cls._instance

    @contextlib.contextmanager
    def measure(self) -> Iterator["_Measurement"]:
        m = _Measurement(self)
        try:
            yield m
        finally:
            m.stop()


class _Measurement:
    def __init__(self, counter: CompileCounter) -> None:
        self._counter = counter
        self._start = counter.count
        self._end: Optional[int] = None

    def stop(self) -> None:
        if self._end is None:
            self._end = self._counter.count

    @property
    def compiles(self) -> int:
        end = self._end if self._end is not None else self._counter.count
        return end - self._start


def enable_strict() -> None:
    """Turn on the strict runtime mode (``NDPP_STRICT=1``).

    * implicit device→host transfers raise (``np.asarray(jax_array)``,
      printing a device array, ...) — ``jax.device_get`` remains legal;
    * tracer leaks out of traced functions raise instead of deferring
      the error to a later use.
    """
    import jax

    jax.config.update("jax_transfer_guard_device_to_host", "disallow")
    jax.config.update("jax_check_tracer_leaks", True)
