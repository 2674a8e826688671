"""Where the Pallas kernels run — one rule for every ``kernels/*/ops.py``.

On a TPU backend every op runs its compiled Pallas kernel.  On any other
backend it runs the pure-jnp reference, unless ``force_interpret=True`` or
``REPRO_PALLAS_INTERPRET=1`` asks for the Pallas interpreter (the CPU test
path).  The variable is read when an op is traced, not at import, so the
choice is visible to whoever sets it.
"""
from __future__ import annotations

import os

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def interpret_requested(force_interpret: bool = False) -> bool:
    """True when the caller or ``REPRO_PALLAS_INTERPRET=1`` asks for the
    Pallas interpreter."""
    return force_interpret or os.environ.get("REPRO_PALLAS_INTERPRET") == "1"

