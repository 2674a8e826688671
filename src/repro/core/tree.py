"""Sublinear-time tree-based DPP sampling (Section 4.2, Algorithm 3).

TPU adaptation (see DESIGN.md §3): instead of a pointer-based binary tree
with one 2K x 2K Σ matrix per node down to single-item leaves (169.5 GB at
M = 1e6, K = 100 in the paper), we store a *flat, level-indexed* tree that is
truncated at blocks of ``block`` items.  A traversal descends
``log2(M / block)`` levels (each step one <Q, Σ> inner product on 2K x 2K
matrices), then scores the whole leaf block at once with a batched bilinear
form — an MXU matmul instead of ``log2(block)`` more pointer hops.  Memory
drops from O(M K^2) to O((M / block) K^2 + M K); the sampled distribution is
identical.

The proposal DPP (Section 4.1) is ``Lhat = Z Xhat Z^T``; its eigenpairs are
obtained from the 2K x 2K Gram matrix (Nakatsukasa 2019), never from the
M x M kernel.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.bilinear import ops as bilinear_ops
from repro.kernels.spec_round import ops as spec_round_ops
from repro.kernels.tree_sum import ops as tree_sum_ops

from .types import SpectralNDPP

# levels with at most this many nodes are replicated on every shard and
# scored with the stacked-matmul shallow path (plain and sharded alike);
# deeper levels shard their node axis across the mesh "model" axis
_SHALLOW_MAX = 32


def proposal_eigens(sp: SpectralNDPP, eps: float = 1e-10,
                    gram: Optional[np.ndarray] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Eigendecomposition of Lhat = A A^T via the 2K x 2K Gram of A = Z Xhat^{1/2}.

    Returns (lam, W): lam (2K,) eigenvalues (>= 0, zeros for the null space),
    W (M, 2K) orthonormal eigenvector columns (zero columns where lam == 0).

    ``gram``: ``A^T A`` in float64 (``youla.spectral_and_gram``), whose
    eigendecomposition is then taken in float64 on the host.  Lhat's
    eigenvalues come in near-degenerate pairs (a Youla pair
    ``sigma_j (y1 y1^T + y2 y2^T)``, split only by V V^T), some within a
    few float32 ulps of each other.  A float32 Gram fixes no basis inside
    such a pair: its eigenvectors come out rotated by up to several
    degrees, and a proposal that keeps one of the pair then descends the
    tree of another elementary DPP than Lhat's own eigenvectors give.
    """
    xhalf = jnp.sqrt(sp.x_diag_hat())
    a = sp.Z * xhalf[None, :]
    if gram is None:
        lam, u = jnp.linalg.eigh(a.T @ a)
    else:
        lam, u = (jnp.asarray(x, a.dtype) for x in np.linalg.eigh(gram))
    lam = jnp.maximum(lam, 0.0)
    good = lam > eps
    denom = jnp.where(good, jnp.sqrt(jnp.maximum(lam, eps)), 1.0)
    w = (a @ u) / denom[None, :]
    w = w * good[None, :]
    lam = lam * good
    return lam, w


@dataclasses.dataclass(frozen=True)
class SampleTree:
    """Flat level-array tree over the rows of W (M x R).

    levels[l] has shape (2^l, R, R); levels[0][0] = sum_j w_j w_j^T.
    The deepest level has ``n_blocks = 2^depth`` nodes, each covering
    ``block`` consecutive (padded) items.
    """

    W: jax.Array                      # (M_pad, R) zero-padded rows
    lam: jax.Array                    # (R,)
    levels: Tuple[jax.Array, ...]     # root .. block level
    block: int
    M: int                            # true item count

    @property
    def depth(self) -> int:
        return len(self.levels) - 1

    @property
    def R(self) -> int:
        return self.W.shape[1]


def _tree_flatten(t: SampleTree):
    return (t.W, t.lam, t.levels), (t.block, t.M)


def _tree_unflatten(aux, children):
    w, lam, levels = children
    return SampleTree(W=w, lam=lam, levels=tuple(levels), block=aux[0], M=aux[1])


jax.tree_util.register_pytree_node(SampleTree, _tree_flatten, _tree_unflatten)


def construct_tree(lam: jax.Array, W: jax.Array, block: int = 64) -> SampleTree:
    """ConstructTree (Alg. 3) in flat form.  O(M R^2 / block) node memory.

    Uses the blocked outer-product reduction (``repro.kernels.tree_sum`` on
    TPU; jnp einsum otherwise) for the leaf level, then pairwise sums.
    """
    m = W.shape[0]
    n_blocks = max(1, 2 ** math.ceil(math.log2(max(1, math.ceil(m / block)))))
    m_pad = n_blocks * block
    wp = jnp.pad(W, ((0, m_pad - m), (0, 0)))
    levels = [tree_sum_ops.block_outer_sums(wp, block)]
    while levels[-1].shape[0] > 1:
        cur = levels[-1]
        levels.append(cur[0::2] + cur[1::2])
    levels.reverse()  # root first
    return SampleTree(W=wp, lam=lam, levels=tuple(levels), block=block, M=m)


def _leaf_scores(w_blk: jax.Array, q: jax.Array) -> jax.Array:
    """Bilinear scores for one leaf block: (block, R) x (R, R) -> (block,)."""
    return jnp.einsum("bi,ij,bj->b", w_blk, q, w_blk, optimize=True)


# --------------------------------------------------------------------------
# Incremental maintenance: a row change perturbs exactly one leaf block and
# its O(log M) ancestors.  Every touched node is *recomputed* through the
# identical arithmetic construct_tree uses (same per-block Gram contraction,
# parent = left + right), never delta-patched, so the maintained tree is
# BIT-equal to a from-scratch rebuild on the mutated rows — the dynamic-
# catalog counterpart of the sharding invariant (docs/architecture.md).
# --------------------------------------------------------------------------


def update_rows(tree: SampleTree, idx: jax.Array, rows: jax.Array,
                lam: Optional[jax.Array] = None) -> SampleTree:
    """Batched O(B (block + log M) R^2) row update: ``W[idx] <- rows``.

    ``idx``: (B,) unique row indices (duplicates hitting the same *block*
    are fine; duplicate row indices are not), ``rows``: (B, R).  Touched
    leaf blocks are recomputed by the ``tree_update`` kernel path and the
    touched root paths resummed — bit-equal to ``construct_tree`` on the
    updated W.  ``lam`` optionally replaces the stored eigenvalues (the
    dual refresh path of ``core.dynamic``).
    """
    levels, w_new = tree_sum_ops.tree_update(tree.levels, tree.W, idx, rows,
                                             tree.block)
    return SampleTree(W=w_new, lam=tree.lam if lam is None else lam,
                      levels=tuple(levels), block=tree.block, M=tree.M)


def _update_rows_local(
    tree: SampleTree, idx: jax.Array, rows: jax.Array, *,
    axis_name: str, m_pad_global: int,
) -> SampleTree:
    """``update_rows`` body inside a ``shard_map`` over an item-sharded tree.

    Each update is routed to the shard owning its rows: the owner scatters
    the W rows, recomputes the touched leaf Gram, and patches its local
    slice of every sharded level; levels that are replicated (the shallow
    levels, `tree_shard_specs`) receive the owner's recomputed value through
    a psum to which every other shard contributes exact 0.0 — so the sharded
    maintained tree stays bit-equal to the plain ``update_rows`` result (and
    hence to a from-scratch ``construct_tree``).
    """
    block, depth = tree.block, tree.depth
    n_blocks_global = m_pad_global // block
    shard = jax.lax.axis_index(axis_name)
    w_loc = tree.W
    rps = w_loc.shape[0]
    w_sharded = rps != m_pad_global
    blks = (idx // block).astype(jnp.int32)
    if w_sharded:
        off = shard * rps
        own = (idx >= off) & (idx < off + rps)
        # non-owned updates get a positive out-of-bounds index -> dropped
        w_loc = w_loc.at[jnp.where(own, idx - off, rps)].set(rows,
                                                             mode="drop")
        bps = rps // block
        own_blk = (blks >= shard * bps) & (blks < (shard + 1) * bps)
        loc_blk = jnp.clip(blks - shard * bps, 0, bps - 1)
        g_loc = tree_sum_ops.gathered_block_grams(w_loc, loc_blk, block)
        vals = jax.lax.psum(
            jnp.where(own_blk[:, None, None], g_loc, 0.0), axis_name)
    else:
        w_loc = w_loc.at[idx].set(rows)
        vals = tree_sum_ops.gathered_block_grams(w_loc, blks, block)
    vals = vals.astype(tree.levels[-1].dtype)

    # walk leaf -> root carrying the *replicated* recomputed node values;
    # sharded levels scatter owner-locally, replicated levels everywhere
    new_levels = []
    nodes = blks
    n_nodes = n_blocks_global
    for lvl in range(depth, -1, -1):
        arr = tree.levels[lvl]
        n_loc = arr.shape[0]
        if n_loc != n_nodes:                      # sharded level
            base = shard * n_loc
            own_n = (nodes >= base) & (nodes < base + n_loc)
            arr = arr.at[jnp.where(own_n, nodes - base, n_loc)].set(
                vals, mode="drop")
        else:                                     # replicated level
            arr = arr.at[nodes].set(vals)
        new_levels.insert(0, arr)
        if lvl == 0:
            break
        parents = nodes // 2
        if n_loc != n_nodes:                      # sharded children: fetch
            base = shard * n_loc                  # each from its owner
            def child(g):
                own_c = (g >= base) & (g < base + n_loc)
                return jnp.where(own_c[:, None, None],
                                 arr[jnp.clip(g - base, 0, n_loc - 1)], 0.0)
            vals = jax.lax.psum(
                child(2 * parents) + child(2 * parents + 1), axis_name)
        else:
            vals = arr[2 * parents] + arr[2 * parents + 1]
        nodes = parents
        n_nodes //= 2
    return SampleTree(W=w_loc, lam=tree.lam, levels=tuple(new_levels),
                      block=tree.block, M=tree.M)


@functools.partial(jax.jit, static_argnames=("mesh",))
def update_rows_sharded(
    tree: SampleTree, idx: jax.Array, rows: jax.Array, mesh: Mesh
) -> SampleTree:
    """``update_rows`` for a mesh-sharded tree (``shard_tree`` layout):
    every update batch is routed to the owning shard, replicated shallow
    levels are patched by a psum of owner-local recomputed values (exact
    zeros elsewhere) — the maintained tree is bit-equal to the plain path
    and to a from-scratch rebuild.  idx/rows are replicated inputs."""
    specs = tree_shard_specs(tree, mesh)
    m_pad = tree.W.shape[0]

    def inner(tree_loc, idx, rows):
        return _update_rows_local(tree_loc, idx, rows, axis_name="model",
                                  m_pad_global=m_pad)

    f = jax.shard_map(inner, mesh=mesh, in_specs=(specs, P(None), P(None)),
                      out_specs=specs, check_vma=False)
    return f(tree, idx, rows)


def dual_q0(u: jax.Array, lam: jax.Array, e_masks: jax.Array,
            eps: float = 1e-10) -> jax.Array:
    """Elementary-DPP projectors for a *dual* tree (rows a_j = z_j x̂_j^1/2).

    With (lam, u) the eigenpairs of the R x R dual Gram C = AᵀA (the tree
    root), the elementary DPP for eigenvector set E has marginal kernel
    A Q0 Aᵀ with Q0 = U_E diag(1/λ_E) U_Eᵀ — the same bilinear-score /
    rank-1-downdate machinery as the orthonormal-row (primal) tree, reached
    by the basis change w_j = diag(λ)^{-1/2} Uᵀ a_j.  e_masks: (N, R) ->
    (N, R, R) per-proposal initial projectors.  Null directions (λ <= eps)
    are never selected (their coin probability λ/(1+λ) is 0) and contribute
    zero here.
    """
    inv = jnp.where(lam > eps, 1.0 / jnp.maximum(lam, eps), 0.0)
    w = e_masks.astype(u.dtype) * inv[None, :]
    return jnp.einsum("ik,nk,jk->nij", u, w, u)


def _descend(tree: SampleTree, q: jax.Array, u: jax.Array) -> jax.Array:
    """One root-to-block traversal.  Returns the chosen block index."""
    idx = jnp.asarray(0, jnp.int32)
    for lvl in range(1, tree.depth + 1):
        nodes = tree.levels[lvl]
        left = nodes[2 * idx]
        parent = tree.levels[lvl - 1][idx]
        p_left = jnp.vdot(q, left)
        p_all = jnp.vdot(q, parent)
        go_left = u[lvl - 1] * jnp.maximum(p_all, 1e-30) <= jnp.maximum(p_left, 0.0)
        idx = 2 * idx + jnp.where(go_left, 0, 1)
    return idx


def sample_elementary(
    tree: SampleTree, e_mask: jax.Array, key: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Sample from the elementary DPP with marginal kernel W_E W_E^T.

    e_mask: (R,) boolean — the eigenvectors E chosen for this draw.
    Returns (items, mask): padded item indices (R,) and validity mask.

    The conditioning state is the projector Q (R x R in the eigenbasis,
    zero outside E); after selecting item j with score p_j = w_j^T Q w_j the
    update is the rank-1 downdate Q <- Q - (Q w_j)(w_j^T Q)/p_j, which is
    algebraically the paper's Q^Y (O(k) x R^2 total instead of k x k
    inversions — see DESIGN.md).
    """
    r = tree.R
    n_e = jnp.sum(e_mask.astype(jnp.int32))
    q0 = jnp.diag(e_mask.astype(tree.W.dtype))
    keys = jax.random.split(key, r)

    def step(carry, t):
        q = carry
        active = t < n_e
        kd, kl = jax.random.split(keys[t])
        us = jax.random.uniform(kd, (tree.depth,), dtype=tree.W.dtype)
        blk = _descend(tree, q, us)
        w_blk = jax.lax.dynamic_slice_in_dim(tree.W, blk * tree.block, tree.block)
        scores = jnp.maximum(_leaf_scores(w_blk, q), 0.0)
        j_local = jax.random.categorical(kl, jnp.log(scores + 1e-30))
        j = blk * tree.block + j_local
        w_j = tree.W[j]
        qw = q @ w_j
        p = jnp.maximum(jnp.dot(w_j, qw), 1e-30)
        q_new = q - jnp.outer(qw, qw) / p
        q = jnp.where(active, q_new, q)
        # pin int32: under JAX_ENABLE_X64 the index math promotes to int64,
        # which breaks while_loop carries typed against the int32 init
        # (core.rejection.sample) and splits dtypes from the batched path
        item = jnp.where(active, j, -1).astype(jnp.int32)
        return q, item

    _, items = jax.lax.scan(step, q0, jnp.arange(r, dtype=jnp.int32))
    return items, items >= 0


def sample_proposal_dpp(
    tree: SampleTree, key: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Draw Y ~ DPP(Lhat): choose the elementary DPP by independent coins
    with probability lam_i/(lam_i + 1), then sample it through the tree."""
    k_e, k_s = jax.random.split(key)
    probs = tree.lam / (tree.lam + 1.0)
    e_mask = jax.random.uniform(k_e, probs.shape, dtype=probs.dtype) < probs
    return sample_elementary(tree, e_mask, k_s)


# --------------------------------------------------------------------------
# Batched traversal: N independent proposals descend the tree together so
# every step is one (N, R, R)-shaped op (MXU-friendly) instead of N scalar
# tree walks.  Used by the speculative rejection engine (core.rejection /
# serve.sampler_engine).
# --------------------------------------------------------------------------


def _gather_row(W: jax.Array, j: jax.Array,
                axis_name: Optional[str]) -> jax.Array:
    """Row fetch via the shared masked-psum gather (plain when axis None)."""
    from repro.models import sharding as msh

    return msh.gather_row(W, j, axis_name)


def _descend_batch(
    tree: SampleTree, q: jax.Array, us: jax.Array, *,
    axis_name: Optional[str] = None,
) -> jax.Array:
    """Root-to-block traversal for N proposals in lockstep.

    q: (N, R, R) per-proposal conditioning projectors; us: (N, depth)
    uniforms.  Returns the chosen block index per proposal (N,).

    The parent's mass is carried down (p_child = p_left or p_all - p_left)
    instead of re-gathering the parent node, so each level costs one
    (N, R, R) gather + one inner product instead of two of each — the
    gathers dominate HBM traffic at batch size N.  Shallow levels (few
    distinct nodes shared by all N lanes) are scored against *every* node
    with one stacked (nodes, R^2) x (R^2, N) matmul instead of per-lane
    matrix gathers; deep levels (nodes >~ lanes) keep the gather.

    With ``axis_name`` set this runs *inside* a ``shard_map``: shallow
    levels (global node count <= _SHALLOW_MAX) are replicated on every
    shard and use the identical stacked matmul; a deep level whose local
    node count is smaller than its global 2^lvl is sharded, and the
    left-child score is computed by its owner shard and psum'd (every other
    shard contributes exact zeros) — so the sharded descent visits exactly
    the same block as the single-device descent, bit for bit.
    """
    n = q.shape[0]
    r = q.shape[-1]
    idx = jnp.zeros((n,), jnp.int32)
    depth = tree.depth
    # levels whose whole node set is cheaper to score than to gather per
    # lane — classified by *global* node count 2^lvl so the plain and
    # sharded paths agree on the split
    shallow = [lvl for lvl in range(1, depth + 1) if (1 << lvl) <= _SHALLOW_MAX]
    p_all = jnp.einsum("ij,nij->n", tree.levels[0][0], q)
    offs = {}
    if shallow:
        stacked = jnp.concatenate(
            [tree.levels[lvl].reshape(-1, r * r) for lvl in shallow]
        )                                            # (sum 2^lvl, R^2)
        all_scores = stacked @ q.reshape(n, r * r).T  # (sum 2^lvl, N)
        off = 0
        for lvl in shallow:
            offs[lvl] = off
            off += tree.levels[lvl].shape[0]
    shard = None if axis_name is None else jax.lax.axis_index(axis_name)
    for lvl in range(1, depth + 1):
        nodes = tree.levels[lvl]
        if lvl in offs:
            s_l = all_scores[offs[lvl]:offs[lvl] + nodes.shape[0]]
            p_left = jnp.take_along_axis(s_l.T, (2 * idx)[:, None], axis=1)[:, 0]
        elif axis_name is None or nodes.shape[0] == (1 << lvl):
            left = nodes[2 * idx]                   # (N, R, R) gather
            p_left = jnp.einsum("nij,nij->n", q, left)
        else:                                       # sharded level
            n_loc = nodes.shape[0]
            base = shard * n_loc
            g = 2 * idx
            own = (g >= base) & (g < base + n_loc)
            left = nodes[jnp.clip(g - base, 0, n_loc - 1)]
            p_left = jax.lax.psum(
                jnp.where(own, jnp.einsum("nij,nij->n", q, left), 0.0),
                axis_name)
        go_left = us[:, lvl - 1] * jnp.maximum(p_all, 1e-30) <= jnp.maximum(p_left, 0.0)
        idx = 2 * idx + jnp.where(go_left, 0, 1)
        p_all = jnp.maximum(jnp.where(go_left, p_left, p_all - p_left), 0.0)
    return idx


def sample_elementary_batch(
    tree: SampleTree, e_masks: jax.Array, keys: jax.Array, *,
    axis_name: Optional[str] = None, m_pad_global: Optional[int] = None,
    q0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """N elementary-DPP draws through the tree in one batched scan.

    e_masks: (N, R) eigenvector selections, keys: (N,) one PRNG key per
    proposal (so a proposal's draw is independent of how it was batched).
    Returns (items, mask), each (N, R).  Identical distribution to
    ``vmap(sample_elementary)`` but leaf scoring runs through the fused
    (N, block, R) kernel and tree nodes are gathered once per level.

    ``q0`` overrides the (N, R, R) initial conditioning projectors — the
    dual-tree path (rows a_j instead of orthonormal w_j) passes
    ``dual_q0(u, lam, e_masks)`` here; the default is the orthonormal-basis
    projector diag(e_mask).

    With ``axis_name`` set (inside a ``shard_map``; ``m_pad_global`` =
    unsharded row count of W), the leaf block is scored by the shard that
    owns its rows and the chosen item's row is fetched the same way — each
    a masked local lookup + psum of exact zeros, so draws stay bit-identical
    to the single-device sampler.
    """
    n, r = e_masks.shape
    n_e = jnp.sum(e_masks.astype(jnp.int32), axis=1)           # (N,)
    n_e_max = jnp.max(n_e)
    if q0 is None:
        q0 = e_masks[:, :, None].astype(tree.W.dtype) \
            * jnp.eye(r, dtype=tree.W.dtype)[None]
    # (r, N, 2): per-proposal, per-step key streams
    step_keys = jnp.swapaxes(
        jax.vmap(lambda k: jax.random.split(k, r))(keys), 0, 1
    )
    depth = max(tree.depth, 1)
    blk_ar = jnp.arange(tree.block, dtype=jnp.int32)
    w_rows = tree.W.shape[0]                       # local rows under shard_map
    w_sharded = (axis_name is not None and m_pad_global is not None
                 and w_rows != m_pad_global)
    shard = None if axis_name is None else jax.lax.axis_index(axis_name)
    # the kernel's node layout is built once here, not once per item step
    # (scope name from the repro.obs.prof.phases catalog)
    with jax.named_scope("ndpp.descent_operands"):
        flat_levels = (spec_round_ops.descent_operands(tree.levels)
                       if axis_name is None else None)

    def cond(state):
        t, _, _ = state
        return t < n_e_max  # dynamic trip count: batch's largest |E|, not R

    def body(state):
        t, q, items = state
        active = t < n_e                                        # (N,)
        kk = jax.vmap(jax.random.split)(step_keys[t])           # (N, 2, 2)
        us = jax.vmap(
            lambda k: jax.random.uniform(k, (depth,), dtype=tree.W.dtype)
        )(kk[:, 0])
        # named scopes are compile-time HLO metadata (free at runtime);
        # names come from the repro.obs.prof.phases catalog — traced
        # bodies never touch repro.obs (NDPP601/602)
        with jax.named_scope("ndpp.tree_descent"):
            if axis_name is None:
                # unsharded hot path: the spec_round kernel on TPU
                blk = spec_round_ops.descend(tree.levels, flat_levels, q, us)
            else:
                blk = _descend_batch(tree, q, us, axis_name=axis_name)
        with jax.named_scope("ndpp.leaf_scoring"):
            if not w_sharded:
                rows = blk[:, None] * tree.block + blk_ar[None, :]
                w_blk = tree.W[rows]                            # (N, block, R)
                scores = jnp.maximum(
                    bilinear_ops.bilinear_batched(w_blk, q), 0.0)
            else:
                bps = w_rows // tree.block             # blocks per shard
                base_blk = shard * bps
                own = (blk >= base_blk) & (blk < base_blk + bps)
                loc = jnp.clip(blk - base_blk, 0, bps - 1)
                rows = loc[:, None] * tree.block + blk_ar[None, :]
                w_blk = tree.W[rows]
                raw = jnp.where(own[:, None],
                                bilinear_ops.bilinear_batched(w_blk, q), 0.0)
                scores = jnp.maximum(jax.lax.psum(raw, axis_name), 0.0)
            j_local = jax.vmap(jax.random.categorical)(
                kk[:, 1], jnp.log(scores + 1e-30)
            )
        j = blk * tree.block + j_local
        w_j = _gather_row(tree.W, j,
                          axis_name if w_sharded else None)     # (N, R)
        qw = jnp.einsum("nij,nj->ni", q, w_j)
        p = jnp.maximum(jnp.einsum("ni,ni->n", w_j, qw), 1e-30)
        q_new = q - qw[:, :, None] * qw[:, None, :] / p[:, None, None]
        q = jnp.where(active[:, None, None], q_new, q)
        items = items.at[:, t].set(jnp.where(active, j, -1))
        return t + 1, q, items

    init = (jnp.asarray(0, jnp.int32), q0, -jnp.ones((n, r), jnp.int32))
    _, _, items = jax.lax.while_loop(cond, body, init)
    return items, items >= 0


def sample_proposal_dpp_batch(
    tree: SampleTree, keys: jax.Array, *,
    axis_name: Optional[str] = None, m_pad_global: Optional[int] = None,
    dual_u: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """N draws Y ~ DPP(Lhat), one per key in ``keys`` (N,): batched
    eigenvector coins, then one batched tree descent for all proposals.
    ``dual_u``: (R, R) eigenvectors of the dual Gram when ``tree`` holds
    dual rows (``core.dynamic``) — the coins still use ``tree.lam`` (the
    dual eigenvalues equal L̂'s nonzero spectrum) and the conditioning
    projectors come from ``dual_q0``.  ``axis_name``/``m_pad_global``
    thread the shard_map context down (see ``sample_elementary_batch``)."""
    ks = jax.vmap(jax.random.split)(keys)                       # (N, 2, 2)
    probs = tree.lam / (tree.lam + 1.0)
    u_e = jax.vmap(
        lambda k: jax.random.uniform(k, probs.shape, dtype=probs.dtype)
    )(ks[:, 0])
    e_masks = u_e < probs[None, :]
    q0 = None if dual_u is None else dual_q0(dual_u, tree.lam, e_masks)
    return sample_elementary_batch(tree, e_masks, ks[:, 1],
                                   axis_name=axis_name,
                                   m_pad_global=m_pad_global, q0=q0)


# --------------------------------------------------------------------------
# Item-axis sharding: the flat tree maps onto a device mesh by splitting
# every array along its item/block axis.  Shard s of S owns leaf blocks
# [s * n_blocks/S, (s+1) * n_blocks/S) and the matching rows of W; levels
# with <= _SHALLOW_MAX nodes (including the root) are replicated.  Because
# the levels are built by pairwise sums of contiguous children, each shard's
# slice of a deep level is exactly the sub-tree over its own blocks — no
# node ever straddles a shard boundary.
# --------------------------------------------------------------------------


def tree_shard_specs(tree: SampleTree, mesh: Mesh) -> SampleTree:
    """PartitionSpecs for a SampleTree on ``mesh`` (a SampleTree-shaped
    pytree of specs, usable as shard_map in_specs or for device_put).

    W and every level with more than ``_SHALLOW_MAX`` nodes shard their
    leading axis over "model" (via the logical "items" axis rules in
    ``repro.models.sharding``); shallow levels and lam replicate.  W is
    only sharded when every shard's row slice is whole leaf blocks
    (``M_pad % (S * block) == 0``) so a leaf block never straddles shards.
    """
    from repro.models import sharding as msh

    s = msh.model_extent(mesh)
    level_specs = []
    for a in tree.levels:
        axes = ("items", None, None) if a.shape[0] > _SHALLOW_MAX \
            else (None, None, None)
        level_specs.append(msh.logical_to_spec(mesh, axes, a.shape))
    if tree.W.shape[0] % max(s * tree.block, 1) == 0:
        w_spec = msh.logical_to_spec(mesh, ("items", None), tree.W.shape)
    else:  # rows per shard would split a leaf block — replicate instead
        w_spec = P(None, None)
    return SampleTree(W=w_spec, lam=P(None), levels=tuple(level_specs),
                      block=tree.block, M=tree.M)


def shard_tree(tree: SampleTree, mesh: Mesh) -> SampleTree:
    """Place a SampleTree on ``mesh``: deep levels and W live item-sharded
    across devices, shallow levels replicated.  The returned tree samples
    identically (bit for bit) through the ``*_sharded`` entry points."""
    specs = tree_shard_specs(tree, mesh)
    put = lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp))  # noqa: E731
    return SampleTree(
        W=put(tree.W, specs.W), lam=put(tree.lam, specs.lam),
        levels=tuple(put(a, sp) for a, sp in zip(tree.levels, specs.levels)),
        block=tree.block, M=tree.M,
    )


def shard_spectral(sp: SpectralNDPP, mesh: Mesh) -> SpectralNDPP:
    """Place a SpectralNDPP on ``mesh``: Z rows item-sharded (replicated
    when M does not divide the mesh), sigma replicated."""
    from repro.models import sharding as msh

    return SpectralNDPP(
        Z=jax.device_put(sp.Z, msh.named(mesh, ("items", None), sp.Z.shape)),
        sigma=jax.device_put(sp.sigma, msh.named(mesh, (None,), sp.sigma.shape)),
    )


@functools.partial(jax.jit, static_argnames=("mesh",))
def sample_proposal_dpp_batch_sharded(
    tree: SampleTree, keys: jax.Array, mesh: Mesh,
    dual_u: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``sample_proposal_dpp_batch`` with the tree sharded over the mesh
    "model" axis: deep-level descent and leaf scoring run on the shard that
    owns the nodes/rows, cross-shard combination is a psum of exact zeros —
    draws are bit-identical to the single-device sampler for any shard
    count.  ``dual_u`` (replicated) switches to the dual-tree projectors
    exactly as in the plain entry point."""
    specs = tree_shard_specs(tree, mesh)
    m_pad = tree.W.shape[0]

    if dual_u is None:
        def inner(tree_loc, keys):
            return sample_proposal_dpp_batch(
                tree_loc, keys, axis_name="model", m_pad_global=m_pad)

        f = jax.shard_map(inner, mesh=mesh, in_specs=(specs, P(None)),
                          out_specs=(P(None), P(None)), check_vma=False)
        return f(tree, keys)

    def inner(tree_loc, keys, u):
        return sample_proposal_dpp_batch(
            tree_loc, keys, axis_name="model", m_pad_global=m_pad, dual_u=u)

    f = jax.shard_map(inner, mesh=mesh,
                      in_specs=(specs, P(None), P(None, None)),
                      out_specs=(P(None), P(None)), check_vma=False)
    return f(tree, keys, dual_u)


@functools.partial(jax.jit, static_argnames=("mesh",))
def sample_elementary_batch_sharded(
    tree: SampleTree, e_masks: jax.Array, keys: jax.Array, mesh: Mesh
) -> Tuple[jax.Array, jax.Array]:
    """``sample_elementary_batch`` through a mesh-sharded tree (see
    ``sample_proposal_dpp_batch_sharded``)."""
    specs = tree_shard_specs(tree, mesh)
    m_pad = tree.W.shape[0]

    def inner(tree_loc, e_masks, keys):
        return sample_elementary_batch(
            tree_loc, e_masks, keys, axis_name="model", m_pad_global=m_pad)

    f = jax.shard_map(inner, mesh=mesh, in_specs=(specs, P(None), P(None)),
                      out_specs=(P(None), P(None)), check_vma=False)
    return f(tree, e_masks, keys)


def sample_elementary_dense(
    W: jax.Array, e_mask: jax.Array, key: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """O(M k R) oracle: identical distribution to ``sample_elementary`` but
    scores every item directly (no tree).  Used in tests and as the
    item-parallel fallback when no tree has been built."""
    m, r = W.shape
    n_e = jnp.sum(e_mask.astype(jnp.int32))
    q0 = jnp.diag(e_mask.astype(W.dtype))
    keys = jax.random.split(key, r)

    def step(q, t):
        active = t < n_e
        scores = jnp.maximum(jnp.einsum("mi,ij,mj->m", W, q, W), 0.0)
        j = jax.random.categorical(keys[t], jnp.log(scores + 1e-30))
        w_j = W[j]
        qw = q @ w_j
        p = jnp.maximum(jnp.dot(w_j, qw), 1e-30)
        q_new = q - jnp.outer(qw, qw) / p
        q = jnp.where(active, q_new, q)
        return q, jnp.where(active, j, -1).astype(jnp.int32)

    _, items = jax.lax.scan(step, q0, jnp.arange(r, dtype=jnp.int32))
    return items, items >= 0
