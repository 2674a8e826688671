"""Pallas TPU kernel: batched root-to-block descent of the proposal tree.

One grid step owns ``lanes`` proposal lanes.  The tree levels stay in HBM
(``memory_space=pl.ANY``); at each level the step DMAs exactly one node
per lane — the left child of the lane's current node — into VMEM, scores
it against the lane's conditioning projector, and moves the lane left or
right on the scalar core.  All ``lanes`` DMAs of a level are in flight
together.  VMEM holds ``lanes`` nodes and ``lanes`` projectors whatever
the tree size, so the catalog is bounded by HBM, not by VMEM.

Node layout: each (R, R) node (and each projector) is flattened and
zero-padded to (S, 128), S = 8 * ceil(R^2 / 1024) (``ops.flat_nodes``),
so one DMA moves one whole aligned slab and the pad is < 1024 floats per
node.  <Q, node> is the same sum of products in either layout.

Grid: (n_lanes / lanes,).  The per-lane descent uniforms arrive in SMEM;
the chosen block ids leave through SMEM as (n_lanes / lanes, 1, lanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _descend_kernel(us_ref, root_ref, q_ref, *refs, depth, lanes):
    lv_refs = refs[:depth]                 # levels 1..depth, in HBM
    blk_ref = refs[depth]
    node_buf, idx_s, pall_s, score_s, sems = refs[depth + 1:]

    def init(lane, carry):
        # a vector reduction reaches the scalar core through SMEM
        score_s[lane] = jnp.sum(root_ref[0] * q_ref[lane])
        pall_s[lane] = score_s[lane]
        idx_s[lane] = 0
        return carry

    jax.lax.fori_loop(0, lanes, init, 0)
    for lvl in range(1, depth + 1):
        lv = lv_refs[lvl - 1]
        n_nodes = 1 << lvl

        def fetch(lane, carry, lv=lv, n_nodes=n_nodes):
            # left child of the lane's node; clamped so the DMA stays in
            # bounds even on a (impossible by construction) corrupt index
            node = jnp.minimum(2 * idx_s[lane], n_nodes - 1)
            pltpu.make_async_copy(lv.at[node], node_buf.at[lane],
                                  sems.at[lane]).start()
            return carry

        def step(lane, carry, lv=lv, lvl=lvl):
            pltpu.make_async_copy(lv.at[0], node_buf.at[lane],
                                  sems.at[lane]).wait()
            score_s[lane] = jnp.sum(node_buf[lane] * q_ref[lane])
            p_left = score_s[lane]
            p_all = pall_s[lane]
            go_left = us_ref[lane, lvl - 1] * jnp.maximum(p_all, 1e-30) \
                <= jnp.maximum(p_left, 0.0)
            idx_s[lane] = 2 * idx_s[lane] + jnp.where(go_left, 0, 1)
            pall_s[lane] = jnp.maximum(
                jnp.where(go_left, p_left, p_all - p_left), 0.0)
            return carry

        jax.lax.fori_loop(0, lanes, fetch, 0)
        jax.lax.fori_loop(0, lanes, step, 0)

    def emit(lane, carry):
        blk_ref[0, 0, lane] = idx_s[lane]
        return carry

    jax.lax.fori_loop(0, lanes, emit, 0)


@functools.partial(jax.jit, static_argnames=("lanes", "interpret"))
def descend_pallas(levels, q: jax.Array, us: jax.Array, *, lanes: int,
                   interpret: bool = False) -> jax.Array:
    """levels: tuple of (2^lvl, S, 128) flat nodes (root first, depth >= 1);
    q: (N, S, 128) flat projectors; us: (N, depth) descent uniforms, with
    N a multiple of ``lanes``.  Returns the chosen block ids (N,) int32."""
    n, s, lane_w = q.shape
    depth = len(levels) - 1
    assert depth >= 1 and n % lanes == 0, (depth, n, lanes)
    kernel = functools.partial(_descend_kernel, depth=depth, lanes=lanes)
    smem = pltpu.SMEM
    blk = pl.pallas_call(
        kernel,
        grid=(n // lanes,),
        in_specs=[
            pl.BlockSpec((lanes, depth), lambda i: (i, 0), memory_space=smem),
            pl.BlockSpec((1, s, lane_w), lambda i: (0, 0, 0)),
            pl.BlockSpec((lanes, s, lane_w), lambda i: (i, 0, 0)),
        ] + [pl.BlockSpec(memory_space=pl.ANY)] * depth,
        out_specs=pl.BlockSpec((1, 1, lanes), lambda i: (i, 0, 0),
                               memory_space=smem),
        out_shape=jax.ShapeDtypeStruct((n // lanes, 1, lanes), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((lanes, s, lane_w), jnp.float32),
            pltpu.SMEM((lanes,), jnp.int32),
            pltpu.SMEM((lanes,), jnp.float32),
            pltpu.SMEM((lanes,), jnp.float32),
            pltpu.SemaphoreType.DMA((lanes,)),
        ],
        interpret=interpret,
        name="ndpp_tree_descent",
    )(us, levels[0], q, *levels[1:])
    return blk.reshape(n)
