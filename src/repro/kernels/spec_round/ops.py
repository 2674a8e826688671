"""jit'd public wrapper for the tree-descent spec_round kernel.

Dispatches the rejection hot path's per-round tree traversal: the Pallas
kernel on TPU (or under interpret), the pure-jnp oracle everywhere else.
The oracle *is* the committed CPU arithmetic — ``core.tree`` routes its
unsharded descent through here, and the golden-file suite pins its draws
bit-for-bit — so the ref path must not be "equivalent", it must be
identical (see ref.py).

The kernel reads the levels in a flat (S, 128) node layout.
``descent_operands`` builds it once per round, outside the per-item loop
of ``core.tree.sample_elementary_batch``; ``descend`` then runs one
descent per item step.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..backend import interpret_requested, on_tpu
from .ref import descend_ref
from .spec_round import descend_pallas

#: proposal lanes per grid step: their node DMAs are in flight together
LANES = 16


def flat_nodes(x: jax.Array) -> jax.Array:
    """(n, R, R) -> (n, S, 128) float32: each node flattened and
    zero-padded to S = 8 * ceil(R^2 / 1024) rows of 128 lanes."""
    n = x.shape[0]
    f = x.reshape(n, -1).astype(jnp.float32)
    size = -(-f.shape[1] // 1024) * 1024
    f = jnp.pad(f, ((0, 0), (0, size - f.shape[1])))
    return f.reshape(n, size // 128, 128)


def descent_operands(levels, *, force_interpret: bool = False
                     ) -> Optional[Tuple[jax.Array, ...]]:
    """The levels in the kernel's flat node layout, or None where the
    jnp oracle runs (off TPU, or a one-level tree with nothing to
    descend)."""
    if len(levels) == 1 or not (on_tpu()
                                or interpret_requested(force_interpret)):
        return None
    return tuple(flat_nodes(lvl) for lvl in levels)


def descend(levels, flat_levels: Optional[Tuple[jax.Array, ...]],
            q: jax.Array, us: jax.Array, *,
            force_interpret: bool = False) -> jax.Array:
    """Root-to-block traversal for N proposal lanes.

    levels: tuple of (2^lvl, R, R) tree node arrays (root first);
    flat_levels: ``descent_operands(levels)``; q: (N, R, R) conditioning
    projectors; us: (N, >= depth) descent uniforms.  Returns the chosen
    block ids (N,) int32 — identical between the kernel and the oracle.
    """
    if flat_levels is None:
        return descend_ref(levels, q, us)
    n = q.shape[0]
    depth = len(levels) - 1
    n_pad = (-n) % LANES
    qf = jnp.pad(flat_nodes(q), ((0, n_pad), (0, 0), (0, 0)))
    usp = jnp.pad(us[:, :depth].astype(jnp.float32), ((0, n_pad), (0, 0)))
    blk = descend_pallas(flat_levels, qf, usp, lanes=LANES,
                         interpret=interpret_requested(force_interpret))
    return blk[:n]
