"""Pallas TPU kernel: batched root-to-block descent of the proposal tree.

At each level a lane scores both children of its current node against
its conditioning projector and goes left iff
``u * max(p_left + p_right, 1e-30) <= max(p_left, 0)``.  Both sides of a
decision are scored at the node's own size: no mass is carried from one
level to the next, so no level inherits the rounding of the levels above
it (a mass carried down by subtraction, ``p_all - p_left``, holds the
rounding of the root's score, about 2^level times the node's own size).

Memory.  Level 1 (the root's two children, the same pair for every lane)
is one VMEM block, read once per call.  Levels 2..depth stay in HBM
(``memory_space=pl.ANY``); at each of them a lane DMAs its node's two
children — nodes 2i and 2i+1, adjacent in the flat level — as one
contiguous (2, S, 128) copy.  VMEM holds one node pair and the
(double-buffered) projector block per lane whatever the tree size, so the
catalog is bounded by HBM, not by VMEM.

Schedule.  One grid step owns ``lanes`` proposal lanes, split into
``lanes / group`` groups that take turns: while one group is scored, the
pair DMAs of every other group are in flight.  A group is scored as one
vector reduction per side — its (group, S, 128) products summed over S
eight lanes at a time, then across the 128 lanes of all its rows at once
into a (1, group) row — and its decisions and node indices are updated
as (1, group) vectors in VMEM.  Only the new node indices cross to SMEM,
as one small VMEM→SMEM copy per group and level, where the scalar core
reads them to address the group's next-level DMAs.  ``ops.descent_lanes``
picks ``lanes`` and ``group`` from the node slab size S and the batch so
that the buffers fit in VMEM.

Node layout: each (R, R) node (and each projector) is flattened and
zero-padded to (S, 128), S = 8 * ceil(R^2 / 1024) (``ops.flat_nodes``),
so one DMA moves whole aligned slabs and the pad is < 1024 floats per
node.  <Q, node> is the same sum of float32 products in either layout.

Grid: (n_lanes / lanes,).  The descent uniforms arrive as
(n_lanes / group, depth, group) rows; the chosen block ids leave as
(n_lanes / group, 1, group).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes scored per vector step: one sublane tile of products
_CHUNK = 8


def vmem_bytes(lanes: int, s: int) -> int:
    """VMEM of ``lanes`` lanes' (s, 128) float32 slabs: one node pair
    each, plus the double-buffered projector block."""
    return 4 * lanes * s * 128 * 4


def _descend_kernel(us_ref, top_ref, q_ref, *refs, depth, group):
    lv_refs = refs[:depth - 1]             # levels 2..depth, in HBM
    blk_ref = refs[depth - 1]              # (groups, 1, group) node ids
    pair_buf, rows_v, idx_v, idx_s, sems = refs[depth:]
    groups = q_ref.shape[0] // group

    def scores(k, pair):
        """<child, q> of group k's lanes for the left and the right child,
        each a (1, group) row; ``pair(lane, side)`` gives eight lanes'
        nodes of one side."""
        def chunk(c, carry):
            lane = pl.multiple_of(k * group + c * _CHUNK, _CHUNK)
            row = pl.multiple_of(c * _CHUNK, _CHUNK)
            q = q_ref[pl.ds(lane, _CHUNK)]
            for side in range(2):
                rows_v[side, pl.ds(row, _CHUNK)] = \
                    jnp.sum(pair(lane, side) * q, axis=1)
            return carry

        jax.lax.fori_loop(0, group // _CHUNK, chunk, 0)
        return tuple(jnp.sum(rows_v[side].T, axis=0, keepdims=True)
                     for side in range(2))

    def copy(lvl, k, i, node):
        return pltpu.make_async_copy(lv_refs[lvl - 2].at[pl.ds(node, 2)],
                                     pair_buf.at[k * group + i], sems.at[k])

    def fetch(lvl, k):
        # both children of each lane's node; clamped so the DMA stays in
        # bounds even on a (impossible by construction) corrupt index
        def body(i, carry):
            node = jnp.minimum(2 * idx_s[k, 0, i], (1 << lvl) - 2)
            copy(lvl, k, i, node).start()
            return carry

        jax.lax.fori_loop(0, group, body, 0)

    def wait(lvl, k):
        def body(i, carry):
            copy(lvl, k, i, 0).wait()
            return carry

        jax.lax.fori_loop(0, group, body, 0)

    def level(lvl):
        def body(k, carry):
            if lvl == 1:
                p_left, p_right = scores(
                    k, lambda lane, side: top_ref[side:side + 1])
                idx = jnp.zeros((1, group), jnp.int32)
            else:
                wait(lvl, k)
                p_left, p_right = scores(
                    k, lambda lane, side: pair_buf[pl.ds(lane, _CHUNK), side])
                idx = blk_ref[k]
            go_left = us_ref[k, lvl - 1:lvl, :] \
                * jnp.maximum(p_left + p_right, 1e-30) \
                <= jnp.maximum(p_left, 0.0)
            idx = 2 * idx + jnp.where(go_left, 0, 1)
            blk_ref[k] = idx
            if lvl < depth:
                idx_v[k, :, 0:group] = idx
                pltpu.sync_copy(idx_v.at[k], idx_s.at[k])
                fetch(lvl + 1, k)
            return carry

        return body

    for lvl in range(1, depth + 1):
        jax.lax.fori_loop(0, groups, level(lvl), 0)


@functools.partial(jax.jit, static_argnames=("lanes", "group", "interpret"))
def descend_pallas(levels, q: jax.Array, us: jax.Array, *, lanes: int,
                   group: int, interpret: bool = False) -> jax.Array:
    """levels: tuple of (2^lvl, S, 128) flat nodes of levels 1..depth (the
    root is not read; depth >= 1); q: (N, S, 128) flat projectors;
    us: (N, depth) descent uniforms, with N a multiple of ``lanes`` and
    ``lanes`` of ``group`` (itself a multiple of 8).  Returns the chosen
    block ids (N,) int32."""
    n, s, lane_w = q.shape
    depth = len(levels)
    assert depth >= 1 and n % lanes == 0 and lanes % group == 0 \
        and group % _CHUNK == 0, (depth, n, lanes, group)
    groups = lanes // group
    g_pad = -(-group // 128) * 128        # a whole lane tile to copy
    us_rows = us.reshape(n // group, group, depth).transpose(0, 2, 1)
    kernel = functools.partial(_descend_kernel, depth=depth, group=group)
    blk = pl.pallas_call(
        kernel,
        grid=(n // lanes,),
        in_specs=[
            pl.BlockSpec((groups, depth, group), lambda i: (i, 0, 0)),
            pl.BlockSpec((2, s, lane_w), lambda i: (0, 0, 0)),
            pl.BlockSpec((lanes, s, lane_w), lambda i: (i, 0, 0)),
        ] + [pl.BlockSpec(memory_space=pl.ANY)] * (depth - 1),
        out_specs=pl.BlockSpec((groups, 1, group), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n // group, 1, group), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((lanes, 2, s, lane_w), jnp.float32),
            pltpu.VMEM((2, group, lane_w), jnp.float32),
            pltpu.VMEM((groups, 1, g_pad), jnp.int32),
            pltpu.SMEM((groups, 1, g_pad), jnp.int32),
            pltpu.SemaphoreType.DMA((groups,)),
        ],
        # the slabs, the level-1 pair (double-buffered), a chunk's
        # products and room for the small blocks
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(lanes, s) + (4 + 4 * _CHUNK) * s * 512
            + (4 << 20)),
        interpret=interpret,
        name="ndpp_tree_descent",
    )(us_rows, levels[0], q, *levels[1:])
    return blk.reshape(n)
