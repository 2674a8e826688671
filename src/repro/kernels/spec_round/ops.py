"""jit'd public wrapper for the tree-descent spec_round kernel.

Dispatches the rejection hot path's per-round tree traversal: the Pallas
kernel on TPU (or under interpret), the pure-jnp oracle everywhere else.
The oracle *is* the committed CPU arithmetic — ``core.tree`` routes its
unsharded descent through here, and the golden-file suite pins its draws
bit-for-bit (see ref.py).

The kernel reads the levels below the root in a flat (S, 128) node
layout.  ``descent_operands`` builds it once per round, outside the
per-item loop of ``core.tree.sample_elementary_batch``; ``descend`` then
runs one descent per item step.  ``descent_lanes`` sizes the kernel's
lane groups from the node slab size and the batch: groups of ``group``
lanes take turns, so the node-pair DMAs of the other groups of a grid
step stay in flight while one group is scored.

The kernel and the oracle take the same rule (both children scored, no
mass carried); they can part only where their float32 sums of products,
taken in another order, fall on two sides of a decision's threshold.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..backend import interpret_requested, on_tpu
from .ref import descend_ref
from .spec_round import descend_pallas, vmem_bytes

#: VMEM the descent kernel's lanes may fill (``spec_round.vmem_bytes``)
DESCENT_VMEM_BYTES = 24 << 20
#: most lanes scored as one vector step and crossing to SMEM together
MAX_GROUP = 32


def descent_lanes(n: int, s: int) -> Tuple[int, int]:
    """(lanes per grid step, lanes per group) for ``n`` lanes of (s, 128)
    node slabs, from the shapes alone.  A group is a multiple of 8 lanes,
    small enough that two fit the VMEM budget, so that one is scored
    while the other's DMAs fly.  A grid step takes at least two groups
    where there are two, and at most what fits, choosing the count that
    pads ``n`` least (then the larger).  ``n`` is padded to a multiple of
    the lanes."""
    fit = DESCENT_VMEM_BYTES // vmem_bytes(1, s)
    group = max(8, min(MAX_GROUP, fit // 2 // 8 * 8, -(-n // 16) * 8))
    n_groups = -(-n // group)
    most = max(1, min(fit // group, n_groups))
    per_step = min(range(min(2, most), most + 1),
                   key=lambda d: (-(-n_groups // d) * d, -d))
    return per_step * group, group


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
    """The levels below the root in the kernel's flat node layout, or None
    where the jnp oracle runs (off TPU, or a one-level tree with nothing
    to descend)."""
    if len(levels) == 1 or not (on_tpu()
                                or interpret_requested(force_interpret)):
        return None
    return tuple(flat_nodes(lvl) for lvl in levels[1:])


def descend(levels, flat_levels: Optional[Tuple[jax.Array, ...]],
            q: jax.Array, us: jax.Array, *,
            force_interpret: bool = False) -> jax.Array:
    """Root-to-block traversal for N proposal lanes.

    levels: tuple of (2^lvl, R, R) tree node arrays (root first);
    flat_levels: ``descent_operands(levels)``; q: (N, R, R) conditioning
    projectors; us: (N, >= depth) descent uniforms.  Returns the chosen
    block ids (N,) int32.
    """
    if flat_levels is None:
        return descend_ref(levels, q, us)
    n = q.shape[0]
    depth = len(levels) - 1
    qf = flat_nodes(q)
    lanes, group = descent_lanes(n, qf.shape[1])
    n_pad = (-n) % lanes
    qf = jnp.pad(qf, ((0, n_pad), (0, 0), (0, 0)))
    usp = jnp.pad(us[:, :depth].astype(jnp.float32), ((0, n_pad), (0, 0)))
    blk = descend_pallas(flat_levels, qf, usp, lanes=lanes, group=group,
                         interpret=interpret_requested(force_interpret))
    return blk[:n]
