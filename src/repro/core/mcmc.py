"""MCMC sampling for NDPPs: low-rank up/down/swap Metropolis chains.

The paper's rejection sampler (Section 4) is provably fast only for ONDPP
kernels — for an unconstrained NDPP the ratio det(Lhat+I)/det(L+I) is
unbounded and ``core.rejection`` can exhaust its trial budget without ever
accepting.  Following the authors' follow-up (*Scalable MCMC Sampling for
Nonsymmetric Determinantal Point Processes*, Han et al. 2022) this module
samples the exact target Pr(Y) ∝ det(L_Y) with a Metropolis–Hastings chain
over subsets instead:

  * NDPP (variable size): pick a uniform item and propose toggling it
    (add/remove, symmetric proposal), mixed with an occasional swap move so
    skew-dominated kernels still mix across same-size subsets.
  * k-NDPP (fixed size): pick a uniform occupied slot and a uniform item
    and propose the swap (symmetric; proposals hitting Y are lazy no-ops).

Every proposal is scored in O(K^2) against the cached inverse of the padded
``|Y| x |Y|`` kernel submatrix, never materializing the M x M kernel
(the cached determinant-ratio updates of Barthelmé et al. 2022, *A Faster
Sampler for Discrete DPPs*, adapted to the nonsymmetric low-rank form
``L = Z X Z^T``):

  add j:     det(L_{Y+j})/det(L_Y)   = z_j^T X z_j - v^T P u          (Schur)
  remove s:  det(L_{Y-s})/det(L_Y)   = P[s, s]                        (Cramer)
  swap s->j: det(L_{Y-s+j})/det(L_Y) = P[s,s] (z_j^T X z_j - v^T P u)
                                       + (v^T P)[s] (P u)[s]

with ``P = (L_Y)^{-1}`` (padded to R = 2K with an identity block so shapes
stay static under jit), ``u = Z_Y X z_j`` and ``v = Z_Y X^T z_j``.  Accepted
moves update ``P`` by a rank-1 (block-inverse / Sherman–Morrison) formula in
O(K^2); a periodic full O(K^3) recompute bounds float32 drift.

All three ratios are bilinear forms ``z_j^T A z_j`` for a per-chain
(2K x 2K) matrix ``A`` — ``kernels/mcmc_score`` fuses the all-candidate
version (score every item of the ground set for C chains at once) into a
single batched matmul, used here by the greedy chain initializer.

C independent chains run under ``vmap``; step t of a chain always draws its
randomness from ``fold_in(chain_key, t)`` (the PR-1 exactness convention),
so a chain's trajectory is independent of batching, tick size, and engine
scheduling.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .types import SpectralNDPP

_TINY = 1e-30
_PIVOT_EPS = 1e-8  # smallest remove pivot a composed swap update may divide by


class MCMCState(NamedTuple):
    """Per-chain state: padded subset + cached padded inverse.

    ``minv`` is the inverse of ``Z_Y X Z_Y^T + diag(~mask)`` — block
    diagonal between occupied and padding slots, identity on the padding
    block, so every ratio formula reads off it with static shapes.
    """

    items: jax.Array  # (R,) int32 item ids, -1 on padding slots
    mask: jax.Array   # (R,) bool
    minv: jax.Array   # (R, R) float32 inverse of the padded L_Y
    step: jax.Array   # () int32 — MH steps taken (drives the key schedule)


class MCMCSample(NamedTuple):
    items: jax.Array     # (n, R) padded item ids
    mask: jax.Array      # (n, R)
    steps: jax.Array     # (n,) chain step each sample was read at
    accept_rate: jax.Array  # () mean MH acceptance rate across all steps


# ---------------------------------------------------------------- state core


def _masked_rows(Z: jax.Array, items: jax.Array, mask: jax.Array,
                 axis_name: Optional[str] = None) -> jax.Array:
    """Subset rows ``Z[items] * mask``; with ``axis_name`` (inside a
    shard_map over row-sharded Z) each row is fetched from its owner shard
    by masked psum — bit-identical to the plain gather."""
    from repro.models import sharding as msh

    return msh.gather_rows(Z, items, mask, axis_name)


def _padded_l(Z: jax.Array, x: jax.Array, items: jax.Array,
              mask: jax.Array, axis_name: Optional[str] = None) -> jax.Array:
    zy = _masked_rows(Z, items, mask, axis_name)
    return zy @ x @ zy.T + jnp.diag((~mask).astype(Z.dtype))


def refresh(sp: SpectralNDPP, state: MCMCState) -> MCMCState:
    """Full O(R^3) recompute of the cached inverse (drift control)."""
    ly = _padded_l(sp.Z, sp.x_matrix(), state.items, state.mask)
    return state._replace(minv=jnp.linalg.inv(ly))


@jax.jit
def reanchor(sp: SpectralNDPP, states: MCMCState) -> MCMCState:
    """Re-anchor a pool of chains on a new catalog version.

    After a ``SamplerEngine.swap_catalog`` the cached inverse of every
    in-flight chain refers to the *old* Z rows; this (vmapped over the
    leading chain axis) drops subset items whose live row is now exactly
    zero (deleted items — keeping them would pin the chain on a
    zero-determinant state the up/down moves can only leave through the
    removal pivot), then recomputes each cached inverse exactly against
    the new rows.  Step counters are preserved, so the
    ``fold_in(chain_key, t)`` schedule — and hence a chain's subsequent
    randomness — is unaffected by when the swap happened.
    """
    def one(st: MCMCState) -> MCMCState:
        rows = sp.Z[jnp.maximum(st.items, 0)]
        live = (jnp.abs(rows) > 0).any(axis=1)
        mask = st.mask & live
        items = jnp.where(mask, st.items, -1)
        return refresh(sp, st._replace(items=items, mask=mask))

    return jax.vmap(one)(states)


def init_empty(sp: SpectralNDPP) -> MCMCState:
    """Start at Y = ∅ (det = 1, inverse = identity).

    Returns an ``MCMCState`` with R = 2K padded slots: items (R,) all -1,
    mask (R,) all False, minv = I_R, step = 0.  The up/down chain's
    canonical start; broadcast it over a leading chain axis for
    ``run_chains``.
    """
    r = sp.Z.shape[1]
    return MCMCState(
        items=-jnp.ones((r,), jnp.int32),
        mask=jnp.zeros((r,), bool),
        minv=jnp.eye(r, dtype=jnp.float32),
        step=jnp.asarray(0, jnp.int32),
    )


def _uvt(Z: jax.Array, x: jax.Array, state: MCMCState, j: jax.Array,
         axis_name: Optional[str] = None
         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """u = Z_Y X z_j, v = Z_Y X^T z_j (so v_r = L[j, r]), t = L[j, j]."""
    from repro.models import sharding as msh

    zy = _masked_rows(Z, state.items, state.mask, axis_name)
    zj = msh.gather_row(Z, j, axis_name)
    u = zy @ (x @ zj)
    v = zy @ (x.T @ zj)
    t = zj @ (x @ zj)
    return u, v, t


# ------------------------------------------------------------ ratio formulas


def add_ratio(sp: SpectralNDPP, state: MCMCState, j: jax.Array) -> jax.Array:
    """det(L_{Y∪j}) / det(L_Y) — O(K^2) given the cached inverse."""
    u, v, t = _uvt(sp.Z, sp.x_matrix(), state, j)
    return t - v @ state.minv @ u


def remove_ratio(state: MCMCState, slot: jax.Array) -> jax.Array:
    """det(L_{Y∖items[slot]}) / det(L_Y) = minv[slot, slot] (Cramer)."""
    return state.minv[slot, slot]


def swap_ratio(sp: SpectralNDPP, state: MCMCState, slot: jax.Array,
               j: jax.Array) -> jax.Array:
    """det(L_{Y∖items[slot]∪j}) / det(L_Y) in one O(K^2) pass.

    Composition of the Cramer removal with the Schur addition against the
    rank-1-downdated inverse; the outer-product correction term makes the
    full (un-zeroed) u, v usable directly.
    """
    u, v, t = _uvt(sp.Z, sp.x_matrix(), state, j)
    pu = state.minv @ u
    vp = v @ state.minv
    return state.minv[slot, slot] * (t - v @ pu) + vp[slot] * pu[slot]


def score_matrix(sp: SpectralNDPP, state: MCMCState) -> jax.Array:
    """A = X - X Z_Y^T P Z_Y X: add-ratio(j) = z_j^T A z_j for every j.

    The all-candidate scorer (``kernels.mcmc_score``) consumes one such
    (2K x 2K) matrix per chain; a swap against a fixed slot s is the same
    bilinear form with A_swap = P[s,s] A + p q^T (see ``swap_score_matrix``).
    """
    x = sp.x_matrix()
    zy = _masked_rows(sp.Z, state.items, state.mask)
    g = zy.T @ (state.minv @ zy)
    return x - x @ g @ x


def swap_score_matrix(sp: SpectralNDPP, state: MCMCState,
                      slot: jax.Array) -> jax.Array:
    """A_swap with swap-ratio(slot -> j) = z_j^T A_swap z_j for every j."""
    x = sp.x_matrix()
    zy = _masked_rows(sp.Z, state.items, state.mask)
    p = x @ (zy.T @ state.minv[:, slot])
    q = x.T @ (zy.T @ state.minv[slot, :])
    return state.minv[slot, slot] * score_matrix(sp, state) + jnp.outer(p, q)


# ------------------------------------------------------------- cache updates


def _cond_remove(state: MCMCState, slot: jax.Array,
                 pred: jax.Array) -> MCMCState:
    """Remove the item at ``slot`` iff pred: rank-1 inverse downdate."""
    minv = state.minv
    d = minv[slot, slot]
    d = jnp.where(pred & (jnp.abs(d) > _TINY), d, 1.0)
    new = minv - jnp.outer(minv[:, slot], minv[slot, :]) / d
    # row/col `slot` are ~0 after the downdate; pin them to the exact
    # identity padding so drift cannot accumulate there
    r = minv.shape[0]
    e = jnp.arange(r, dtype=jnp.int32) == slot
    new = jnp.where(e[:, None] | e[None, :], 0.0, new)
    new = new.at[slot, slot].set(1.0)
    return MCMCState(
        items=jnp.where(pred, state.items.at[slot].set(-1), state.items),
        mask=jnp.where(pred, state.mask.at[slot].set(False), state.mask),
        minv=jnp.where(pred, new, minv),
        step=state.step,
    )


def _cond_add(Z: jax.Array, x: jax.Array, state: MCMCState, j: jax.Array,
              slot: jax.Array, pred: jax.Array,
              axis_name: Optional[str] = None) -> MCMCState:
    """Add item j at padding slot ``slot`` iff pred: block-inverse update."""
    u, v, t = _uvt(Z, x, state, j, axis_name)
    minv = state.minv
    pu = minv @ u
    vp = v @ minv
    delta = t - v @ pu
    d = jnp.where(pred & (jnp.abs(delta) > _TINY), delta, 1.0)
    r = minv.shape[0]
    e = (jnp.arange(r, dtype=jnp.int32) == slot).astype(minv.dtype)
    new = (
        minv
        + (jnp.outer(pu, vp) - jnp.outer(pu, e) - jnp.outer(e, vp)) / d
        + (1.0 / d - 1.0) * jnp.outer(e, e)
    )
    return MCMCState(
        items=jnp.where(pred, state.items.at[slot].set(j), state.items),
        mask=jnp.where(pred, state.mask.at[slot].set(True), state.mask),
        minv=jnp.where(pred, new, minv),
        step=state.step,
    )


# ------------------------------------------------------------------ MH steps


def _mh_step(Z: jax.Array, x: jax.Array, state: MCMCState, key: jax.Array,
             *, fixed: bool, p_swap: float,
             axis_name: Optional[str] = None,
             m_total: Optional[int] = None) -> Tuple[MCMCState, jax.Array]:
    """One Metropolis step.  ``fixed=True`` = k-NDPP swap chain (size is an
    invariant); otherwise the up/down chain with a ``p_swap`` swap mixture.
    Returns (new state, accepted?).  All proposals are symmetric, so the
    acceptance probability is min(1, det ratio).  ``axis_name``/``m_total``
    run the step inside a shard_map over row-sharded Z (``m_total`` = global
    catalog size; Z is then the local row block)."""
    m = Z.shape[0] if m_total is None else m_total
    r = state.items.shape[0]
    k_move, k_cand, k_slot, k_acc = jax.random.split(key, 4)

    items, mask, minv = state.items, state.mask, state.minv
    size = mask.sum()
    cand = jax.random.randint(k_cand, (), 0, m)
    cand_hit = (items == cand) & mask
    cand_in = cand_hit.any()
    cand_slot = jnp.argmax(cand_hit)
    free_slot = jnp.argmin(mask)           # first padding slot
    full = size >= r
    # uniform occupied slot (swap removal candidate)
    occ_slot = jax.random.categorical(
        k_slot, jnp.where(mask, 0.0, -jnp.inf))
    occ_slot = jnp.where(size > 0, occ_slot, 0)

    u, v, t = _uvt(Z, x, state, cand, axis_name)
    pu = minv @ u
    vp = v @ minv
    r_add = t - v @ pu
    r_swap = minv[occ_slot, occ_slot] * r_add + vp[occ_slot] * pu[occ_slot]
    r_rem = minv[cand_slot, cand_slot]

    if fixed:
        move_add = move_rem = jnp.asarray(False)
        move_swap = (~cand_in) & (size > 0)
    else:
        is_swap = jax.random.uniform(k_move) < p_swap
        move_swap = is_swap & (~cand_in) & (size > 0)
        move_add = (~is_swap) & (~cand_in) & (~full)
        move_rem = (~is_swap) & cand_in

    ratio = jnp.where(move_add, r_add,
                      jnp.where(move_rem, r_rem,
                                jnp.where(move_swap, r_swap, 0.0)))
    ratio = jnp.where(jnp.isfinite(ratio) & (ratio > 0), ratio, 0.0)
    # an accepted swap is realized as remove-then-add rank-1 updates whose
    # downdate divides by the remove pivot; veto swaps whose pivot is at
    # float-noise scale so that division cannot amplify f32 error into the
    # cached inverse for the rest of the refresh window
    ratio = jnp.where(
        move_swap & (jnp.abs(minv[occ_slot, occ_slot]) < _PIVOT_EPS),
        0.0, ratio)
    accept = jax.random.uniform(k_acc) < jnp.minimum(ratio, 1.0)

    rem_slot = jnp.where(move_rem, cand_slot, occ_slot)
    add_slot = jnp.where(move_add, free_slot, occ_slot)
    state = _cond_remove(state, rem_slot, accept & (move_rem | move_swap))
    state = _cond_add(Z, x, state, cand, add_slot,
                      accept & (move_add | move_swap), axis_name)
    return state._replace(step=state.step + 1), accept


def _chain_trace(Z, x, chain_key, state, *, n_steps: int, fixed: bool,
                 p_swap: float, refresh_every: int,
                 axis_name: Optional[str] = None,
                 m_total: Optional[int] = None):
    """Advance one chain ``n_steps`` steps, recording (items, mask, accept)
    at every step.  The cached inverse is recomputed exactly on the
    *absolute-step* schedule ``state.step % refresh_every == 0``, checked at
    block boundaries (one O(R^3) inverse per block, applied conditionally) —
    so splitting the same steps across calls with tick sizes that divide
    ``refresh_every`` reproduces the exact refresh points, keeping engine
    trajectories bit-identical to the standalone runner.  The recompute is
    exact either way; only float drift depends on it."""

    def refresh_(st):
        ly = _padded_l(Z, x, st.items, st.mask, axis_name)
        hit = st.step % refresh_every == 0
        return st._replace(
            minv=jnp.where(hit, jnp.linalg.inv(ly), st.minv))

    def body(st, step_idx):
        key = jax.random.fold_in(chain_key, step_idx)
        st, acc = _mh_step(Z, x, st, key, fixed=fixed, p_swap=p_swap,
                           axis_name=axis_name, m_total=m_total)
        return st, (st.items, st.mask, acc)

    traces = []
    done = 0
    while done < n_steps:
        nb = min(refresh_every, n_steps - done)
        state = refresh_(state)
        steps = state.step + jnp.arange(nb, dtype=jnp.int32)
        state, ys = jax.lax.scan(body, state, steps)
        traces.append(ys)
        done += nb
    items_tr = jnp.concatenate([y[0] for y in traces])
    mask_tr = jnp.concatenate([y[1] for y in traces])
    acc_tr = jnp.concatenate([y[2] for y in traces])
    return state, items_tr, mask_tr, acc_tr


@functools.partial(
    jax.jit, static_argnames=("n_steps", "fixed", "p_swap", "refresh_every"))
def run_chains(sp: SpectralNDPP, chain_keys: jax.Array, states: MCMCState,
               *, n_steps: int, fixed: bool = False, p_swap: float = 0.25,
               refresh_every: int = 64):
    """Advance C chains ``n_steps`` MH steps under one vmap.

    chain_keys: (C, 2); states: MCMCState with leading dim C.  Returns
    (states, items_trace (C, n_steps, R), mask_trace, accept_trace).
    Step t of chain c is keyed by ``fold_in(chain_keys[c], states.step + t)``
    — trajectories are independent of how many calls the steps are split
    across.
    """
    # scope name from the repro.obs.prof.phases catalog (free HLO
    # metadata; core stays import-free of repro.obs)
    with jax.named_scope("ndpp.mcmc_step"):
        x = sp.x_matrix()
        return jax.vmap(
            lambda k, st: _chain_trace(
                sp.Z, x, k, st, n_steps=n_steps, fixed=fixed, p_swap=p_swap,
                refresh_every=refresh_every)
        )(chain_keys, states)


@functools.partial(
    jax.jit,
    static_argnames=("n_steps", "fixed", "p_swap", "refresh_every", "mesh"))
def run_chains_sharded(sp: SpectralNDPP, chain_keys: jax.Array,
                       states: MCMCState, *, mesh: Mesh, n_steps: int,
                       fixed: bool = False, p_swap: float = 0.25,
                       refresh_every: int = 64):
    """``run_chains`` with the (M, 2K) catalog rows sharded over the mesh
    "model" axis.

    Chain state (padded subset + cached (2K, 2K) inverse) is replicated;
    only the candidate row z_j and the <= 2K subset rows Z_Y cross shards,
    each fetched from its owner by a masked psum of exact zeros — so
    trajectories are bit-identical to the single-device ``run_chains`` while
    per-device catalog memory drops to M/S rows.  Requires M divisible by
    the mesh "model" extent.
    """
    from repro.models import sharding as msh

    s = msh.model_extent(mesh)
    m_total = sp.Z.shape[0]
    if m_total % s != 0:
        raise ValueError(
            f"the mesh 'model' extent {s} must divide the catalog size "
            f"M={m_total}; pad the catalog or use a smaller mesh")
    sp_specs = SpectralNDPP(Z=P("model", None), sigma=P(None))

    def inner(sp_loc, ck, st):
        with jax.named_scope("ndpp.mcmc_step"):
            x = sp_loc.x_matrix()
            return jax.vmap(
                lambda k, s_: _chain_trace(
                    sp_loc.Z, x, k, s_, n_steps=n_steps, fixed=fixed,
                    p_swap=p_swap, refresh_every=refresh_every,
                    axis_name="model", m_total=m_total)
            )(ck, st)

    f = jax.shard_map(inner, mesh=mesh, in_specs=(sp_specs, P(None), P(None)),
                      out_specs=P(None), check_vma=False)
    return f(sp, chain_keys, states)


# --------------------------------------------------------------- greedy init


@functools.partial(jax.jit, static_argnames=("mesh", "force_interpret"))
def _greedy_round(sp: SpectralNDPP, states: MCMCState, chain_keys: jax.Array,
                  round_idx: jax.Array, *, mesh: Optional[Mesh] = None,
                  force_interpret: bool = False):
    """One greedy round: score EVERY candidate for EVERY chain in one fused
    all-candidate pass and add one item per chain ~ its determinant gain.
    ``mesh``: the rows of ``sp.Z`` are sharded over its "model" axis, and
    each shard scores its own rows (XLA cannot partition a Pallas kernel
    itself)."""
    from repro.kernels.mcmc_score import ops as mops

    x = sp.x_matrix()
    a = jax.vmap(lambda st: score_matrix(sp, st))(states)  # (C, 2K, 2K)
    if mesh is None:
        scores = mops.score_all(sp.Z, a, force_interpret=force_interpret)
    else:
        scores = mops.score_all_sharded(sp.Z, a, mesh,
                                        force_interpret=force_interpret)
    taken = jax.vmap(
        lambda st: (jnp.arange(sp.M, dtype=jnp.int32)[None, :] ==
                    jnp.where(st.mask, st.items, -1)[:, None]).any(0)
    )(states)
    # taken items are hard-excluded (-inf), NOT floored: if every untaken
    # candidate had ~0 gain, a floored logit could re-pick a held item and
    # wedge the chain on a duplicate-id, zero-determinant state
    scores = jnp.maximum(scores, 0.0)
    logits = jnp.where(taken, -jnp.inf, jnp.log(jnp.maximum(scores, _TINY)))
    picks = jax.vmap(
        lambda ck, lg: jax.random.categorical(
            jax.random.fold_in(ck, round_idx), lg)
    )(chain_keys, logits)
    return jax.vmap(
        lambda st, j: _cond_add(sp.Z, x, st, j, jnp.argmin(st.mask),
                                jnp.asarray(True))
    )(states, picks)


def init_greedy(sp: SpectralNDPP, key: jax.Array, n_chains: int, k: int,
                *, mesh: Optional[Mesh] = None,
                force_interpret: bool = False) -> MCMCState:
    """Stochastic-greedy size-k initial states for C = ``n_chains`` chains.

    Returns an ``MCMCState`` with leading dim C (items/mask (C, R), minv
    (C, R, R), step (C,)), each chain holding a distinct size-k subset with
    det(L_Y) > 0 and a freshly inverted cache.

    Each of the k rounds scores EVERY candidate item for EVERY chain in one
    fused all-candidate pass (``kernels.mcmc_score.score_all`` — C batched
    bilinear forms against per-chain score matrices, a single matmul on TPU
    instead of a C x M python loop) and samples an item per chain with
    probability proportional to its positive determinant gain.  Used as the
    k-NDPP chain initializer: starting states have det(L_Y) > 0 and are
    spread across high-mass subsets, which shortens burn-in.  ``mesh``:
    the catalog rows are sharded over its "model" axis (the starts are
    bit-identical to the unsharded ones).
    """
    states = jax.vmap(lambda _: init_empty(sp))(jnp.arange(n_chains, dtype=jnp.int32))
    chain_keys = jax.random.split(key, n_chains)
    for i in range(k):
        states = _greedy_round(sp, states, chain_keys,
                               jnp.asarray(i, jnp.int32), mesh=mesh,
                               force_interpret=force_interpret)
    return jax.vmap(lambda st: refresh(sp, st))(states)


# ------------------------------------------------------------------ sampling


def sample_mcmc(
    sp: SpectralNDPP,
    key: jax.Array,
    n_samples: int,
    *,
    k: Optional[int] = None,
    n_chains: int = 64,
    burn_in: int = 512,
    thin: int = 8,
    p_swap: float = 0.25,
    refresh_every: int = 64,
    mesh: Optional[Mesh] = None,
    observer=None,
) -> MCMCSample:
    """Draw ``n_samples`` subsets by MCMC (exact target Pr(Y) ∝ det(L_Y)).

    ``k=None`` runs the variable-size up/down chain from Y = ∅; an integer
    ``k`` runs the fixed-size swap chain from stochastic-greedy size-k
    starts.  ``n_chains`` chains run in one vmap; each contributes
    ``ceil(n_samples / n_chains)`` states taken every ``thin`` steps after
    ``burn_in``.  ``mesh``: keep the catalog rows device-local across the
    mesh "model" axis (``run_chains_sharded``; draws are bit-identical to
    the single-device chains).  ``observer``: duck-typed telemetry sink —
    receives one ``on_mcmc(steps=, n_chains=, accept_fraction=)`` call
    with host scalars read off the acceptance trace the call already
    returns (one extra scalar ``device_get``, outside any jit; draws are
    untouched).
    """
    n_chains = min(n_chains, n_samples)
    per_chain = -(-n_samples // n_chains)
    n_steps = burn_in + thin * per_chain
    chain_keys = jax.random.split(key, n_chains)
    if k is None:
        states = jax.vmap(lambda _: init_empty(sp))(jnp.arange(n_chains, dtype=jnp.int32))
    else:
        states = init_greedy(sp, jax.random.fold_in(key, 0x6d636d63),
                             n_chains, k, mesh=mesh)
    if mesh is None:
        _, items_tr, mask_tr, acc_tr = run_chains(
            sp, chain_keys, states, n_steps=n_steps, fixed=k is not None,
            p_swap=p_swap, refresh_every=refresh_every)
    else:
        _, items_tr, mask_tr, acc_tr = run_chains_sharded(
            sp, chain_keys, states, mesh=mesh, n_steps=n_steps,
            fixed=k is not None, p_swap=p_swap, refresh_every=refresh_every)
    if observer is not None:
        observer.on_mcmc(steps=n_steps * n_chains, n_chains=n_chains,
                         accept_fraction=float(jax.device_get(acc_tr.mean())))
    take = burn_in + thin * np.arange(1, per_chain + 1) - 1  # (per_chain,)
    items = items_tr[:, take].reshape(-1, items_tr.shape[-1])[:n_samples]
    mask = mask_tr[:, take].reshape(-1, mask_tr.shape[-1])[:n_samples]
    steps = jnp.broadcast_to(
        jnp.asarray(take + 1, jnp.int32), (n_chains, per_chain)
    ).reshape(-1)[:n_samples]
    return MCMCSample(items=items, mask=mask, steps=steps,
                      accept_rate=acc_tr.mean())
