"""Pure-jnp oracle for the batched slogdet kernel.

``slogdet_ref`` is the arithmetic every backend but the TPU executes:
``jnp.linalg.slogdet`` on the whole batch (LAPACK's LU on the CPU), as
the rejection round took it before the kernel, so the golden draws keep
their bits.  The kernel's own rule, elimination with partial pivoting
one lane per matrix, is ``core.learning._slogdet_width_invariant`` in
jnp; the kernel tests hold it to both.
"""
from typing import Tuple

import jax
import jax.numpy as jnp


def slogdet_ref(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(sign, log |det|) of each matrix of a (N, k, k) batch."""
    return jnp.linalg.slogdet(a)
