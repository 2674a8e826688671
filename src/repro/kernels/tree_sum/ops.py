"""jit'd public wrappers for the tree_sum Pallas kernels."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..backend import interpret_requested, on_tpu
from .ref import block_outer_sums_ref, gathered_block_grams_ref
from .tree_sum import block_outer_sums_pallas, gathered_block_grams_pallas


def block_outer_sums(
    W: jax.Array, block: int, *, force_interpret: bool = False
) -> jax.Array:
    """W: (n*block, R) -> (n, R, R) per-block Gram matrices."""
    interpret = interpret_requested(force_interpret)
    if not (on_tpu() or interpret):
        return block_outer_sums_ref(W, block)
    m, r = W.shape
    r_pad = (-r) % 128
    wp = jnp.pad(W, ((0, 0), (0, r_pad)))
    out = block_outer_sums_pallas(wp, block=block, interpret=interpret)
    return out[:, :r, :r]


def gathered_block_grams(
    W: jax.Array, blks: jax.Array, block: int, *, force_interpret: bool = False
) -> jax.Array:
    """Grams of the leaf blocks named by ``blks`` only: (nb,) -> (nb, R, R)."""
    interpret = interpret_requested(force_interpret)
    if not (on_tpu() or interpret):
        return gathered_block_grams_ref(W, blks, block)
    m, r = W.shape
    r_pad = (-r) % 128
    wp = jnp.pad(W, ((0, 0), (0, r_pad)))
    out = gathered_block_grams_pallas(wp, blks, block=block,
                                      interpret=interpret)
    return out[:, :r, :r]


def tree_update(
    levels: Tuple[jax.Array, ...], W: jax.Array, idx: jax.Array,
    rows: jax.Array, block: int, *, force_interpret: bool = False
) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """Batched row update of a flat level-indexed sample tree.

    ``W[idx] <- rows`` (idx (B,) unique, rows (B, R)), then the touched leaf
    blocks' Grams are *recomputed* (not delta-patched) by the gathered-Gram
    kernel and the touched root paths resummed level by level — each updated
    node goes through the identical arithmetic as ``construct_tree`` (same
    per-block contraction, parent = left child + right child), so the result
    is bit-equal to a from-scratch rebuild on the updated W at O(B (block +
    log M) R^2) cost instead of O(M R^2).  The up-sweep is O(B log M) R x R
    adds — <1% of the leaf-Gram MXU work — and stays in XLA; the one Pallas
    launch is the Gram recompute.

    Returns ``(levels, W)`` updated.  Duplicate touched blocks / path nodes
    scatter identical recomputed values, so duplicates in ``idx``'s *blocks*
    are safe (duplicate row indices are not — last write would be
    scheduling-dependent).
    """
    w_new = W.at[idx].set(rows)
    blks = (idx // block).astype(jnp.int32)
    grams = gathered_block_grams(w_new, blks, block,
                                 force_interpret=force_interpret)
    grams = grams.astype(levels[-1].dtype)
    new_levels = [levels[-1].at[blks].set(grams)]
    nodes = blks
    for lvl in range(len(levels) - 2, -1, -1):
        nodes = nodes // 2
        child = new_levels[0]
        val = child[2 * nodes] + child[2 * nodes + 1]
        new_levels.insert(0, levels[lvl].at[nodes].set(val))
    return tuple(new_levels), w_new
