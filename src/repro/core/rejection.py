"""Rejection NDPP sampling (Section 4, Algorithm 2).

Target:   Pr_L(Y)    ∝ det(L_Y),      L    = Z X Z^T (nonsymmetric)
Proposal: Pr_Lhat(Y) ∝ det(Lhat_Y),   Lhat = Z Xhat Z^T (symmetric PSD)

Theorem 1 gives det(L_Y) <= det(Lhat_Y) for all Y, so the acceptance
probability is exactly det(L_Y) / det(Lhat_Y) and the expected number of
trials is det(Lhat + I) / det(L + I) — which, for ONDPP kernels (V ⟂ B),
equals prod_j (1 + 2 sigma_j / (sigma_j^2 + 1)) (Theorem 2), independent
of M.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.slogdet.ops import slogdet_batched

from .types import SpectralNDPP
from .tree import (
    SampleTree,
    construct_tree,
    proposal_eigens,
    sample_proposal_dpp,
    sample_proposal_dpp_batch,
    shard_spectral,
    shard_tree,
    tree_shard_specs,
)


#: shared no-op context for drivers whose observer has no ``phase`` hook
#: (one object, reused — never a per-round allocation)
_NO_PHASE = contextlib.nullcontext()


class RejectionSample(NamedTuple):
    items: jax.Array     # (2K,) padded item indices (-1 = empty slot)
    mask: jax.Array      # (2K,) validity mask
    trials: jax.Array    # number of proposals drawn (>= 1)
    accepted: jax.Array  # bool; False => max_trials exhausted (returns last Y)


@dataclasses.dataclass(frozen=True)
class NDPPSampler:
    """Preprocessed state for repeated sublinear-time sampling.

    Preprocess (one-time, O(M K^2)): Youla decomposition -> spectral form,
    proposal eigendecomposition, flat tree construction.  Each sample then
    costs O((K + k^3 log(M/block) + k^2 block) * E[#trials]).
    """

    sp: SpectralNDPP
    tree: SampleTree

    @property
    def M(self) -> int:
        return self.sp.M


def _tf(s):  # pytree registration
    return (s.sp, s.tree), None


jax.tree_util.register_pytree_node(
    NDPPSampler, _tf, lambda _, c: NDPPSampler(sp=c[0], tree=c[1])
)


def preprocess(V: jax.Array, B: jax.Array, D: jax.Array, block: int = 64) -> NDPPSampler:
    """PREPROCESS of Algorithm 2 (+ tree construction of Algorithm 3).

    Eager host code, never traced: each step is a set-up stage
    (``repro.obs.setup_stage``) that blocks on its outputs, so
    ``ndpp_setup_seconds_total`` splits the set-up time between them.
    """
    from repro.obs import setup_stage
    from repro.obs.prof import phases

    from .youla import spectral_and_gram

    with setup_stage(phases.YOULA):
        sp, gram = jax.block_until_ready(spectral_and_gram(V, B, D))
    with setup_stage(phases.PROPOSAL_EIGENS):
        lam, w = jax.block_until_ready(proposal_eigens(sp, gram=gram))
    with setup_stage(phases.TREE_BUILD):
        tree = jax.block_until_ready(construct_tree(lam, w, block=block))
    return NDPPSampler(sp=sp, tree=tree)


def _masked_rows(Z: jax.Array, items: jax.Array, mask: jax.Array) -> jax.Array:
    rows = Z[jnp.maximum(items, 0)]
    return rows * mask[..., None].astype(Z.dtype)


def log_det_ratio(
    sp: SpectralNDPP, items: jax.Array, mask: jax.Array,
    live_z: Optional[jax.Array] = None, live_x: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(log det(L_Y) - log det(Lhat_Y), sign of det(L_Y)) with padded Y.

    Both submatrices are built in the 2K-dim feature space: L_Y = Z_Y X Z_Y^T
    (k_pad x k_pad) with unit diagonal on padding rows so the padding
    contributes a factor of exactly 1.

    ``live_z`` / ``live_x`` override the *numerator* only: the acceptance
    test then scores the current (live) kernel ``live_z X_live live_z^T``
    while the denominator stays the proposal L̂ that ``sp`` actually sampled
    from — the stale-proposal acceptance of the dynamic catalog
    (``core.dynamic`` / ``serve.catalog``).  Draws remain exactly
    distributed as the live kernel whenever the stale proposal still
    dominates it (deletes / row downscales); a live row zeroed by a delete
    makes sign(det L_Y) = 0 here, so deleted items are rejected with
    probability one.
    """
    ratio, sign = log_det_ratio_batch(sp, items[None], mask[None],
                                      live_z=live_z, live_x=live_x)
    return ratio[0], sign[0]


def _log_det_ratio_rows(
    sp: SpectralNDPP, zy: jax.Array, mask: jax.Array,
    live_rows: Optional[jax.Array] = None,
    live_x: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``log_det_ratio`` of N subsets from pre-gathered (N, k_pad, 2K)
    subset rows ``zy`` (padding rows already zeroed) — the sharded round
    gathers rows across shards first and shares this 2K-space math.
    ``live_rows``/``live_x``: pre-gathered numerator overrides (see
    ``log_det_ratio``).  The 2N submatrices are factored by one
    ``slogdet_batched`` call: on a TPU one lane per matrix, to the largest
    live prefix of each 128 (the mask is a prefix of each padded subset)."""
    x = sp.x_matrix() if live_x is None else live_x
    num = zy if live_rows is None else live_rows
    k = mask.shape[-1]
    pad_eye = (~mask)[:, :, None] * jnp.eye(k, dtype=zy.dtype)
    l_y = num @ x @ jnp.swapaxes(num, -1, -2) + pad_eye
    lhat_y = ((zy * sp.x_diag_hat()) @ jnp.swapaxes(zy, -1, -2)
              + pad_eye)
    # rows past the last live one are identity rows
    n_live = jnp.max(jnp.where(mask, jnp.arange(1, k + 1, dtype=jnp.int32),
                               0), axis=-1)
    sign, logdet = slogdet_batched(jnp.concatenate([l_y, lhat_y]),
                                   jnp.concatenate([n_live, n_live]))
    n = mask.shape[0]
    sign_l, sign_h = sign[:n], sign[n:]
    good = (sign_l > 0) & (sign_h > 0)
    return jnp.where(good, logdet[:n] - logdet[n:], -jnp.inf), sign_l


def expected_trials(sp: SpectralNDPP) -> jax.Array:
    """Theorem 2 (requires V ⟂ B): det(Lhat+I)/det(L+I) =
    prod_j (1 + 2 sigma_j/(sigma_j^2+1))."""
    s = sp.sigma
    return jnp.prod(1.0 + 2.0 * s / (s ** 2 + 1.0))


def det_ratio_exact(sp: SpectralNDPP) -> jax.Array:
    """det(Lhat + I) / det(L + I) without the orthogonality assumption,
    via 2K x 2K determinants (identity det(I + Z A Z^T) = det(I + A Z^T Z))."""
    g = sp.Z.T @ sp.Z
    r = g.shape[0]
    eye = jnp.eye(r, dtype=g.dtype)
    _, ld_l = jnp.linalg.slogdet(eye + sp.x_matrix() @ g)
    _, ld_h = jnp.linalg.slogdet(eye + (sp.x_diag_hat()[:, None] * g))
    return jnp.exp(ld_h - ld_l)


def sample(
    sampler: NDPPSampler, key: jax.Array, max_trials: int = 1000
) -> RejectionSample:
    """SAMPLEREJECT of Algorithm 2: draw from DPP(Lhat) via the tree, accept
    with probability det(L_Y)/det(Lhat_Y)."""

    def cond(state):
        _, trials, accepted, _, _ = state
        return (~accepted) & (trials < max_trials)

    def body(state):
        k, trials, _, _, _ = state
        k, k_prop, k_acc = jax.random.split(k, 3)
        items, mask = sample_proposal_dpp(sampler.tree, k_prop)
        log_ratio, _ = log_det_ratio(sampler.sp, items, mask)
        u = jax.random.uniform(k_acc, dtype=jnp.float32)
        accept = jnp.log(u) <= log_ratio
        return (k, trials + 1, accept, items, mask)

    r = sampler.tree.R
    init = (
        key,
        jnp.asarray(0, jnp.int32),
        jnp.asarray(False),
        -jnp.ones((r,), jnp.int32),
        jnp.zeros((r,), bool),
    )
    _, trials, accepted, items, mask = jax.lax.while_loop(cond, body, init)
    return RejectionSample(items=items, mask=mask, trials=trials, accepted=accepted)


def sample_batch(
    sampler: NDPPSampler, key: jax.Array, n: int, max_trials: int = 1000
) -> RejectionSample:
    """vmap'd repeated sampling (the tree is reused across draws)."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: sample(sampler, k, max_trials))(keys)


# --------------------------------------------------------------------------
# Speculative batched rejection sampling.
#
# The sequential sampler pays E[#trials] *serial* tree descents per sample.
# Proposals are i.i.d., so a round can draw n_spec of them at once (one
# batched tree traversal + one batched log-det ratio) and accept the first
# successful candidate; only requests whose entire batch was rejected loop
# again, with the batch size doubling up to ``max_spec``.  Taking the first
# acceptance among i.i.d. proposals in a fixed order is exactly the
# sequential algorithm, so the sampled distribution is unchanged — and so is
# the trial count, because proposal t of a request is always generated from
# fold_in(request_key, t), independent of the batching schedule.
# --------------------------------------------------------------------------


def log_det_ratio_batch(
    sp: SpectralNDPP, items: jax.Array, mask: jax.Array,
    live_z: Optional[jax.Array] = None, live_x: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``log_det_ratio`` over N padded subsets at once.

    items/mask: (N, k_pad).  Returns ((N,) log ratios, (N,) signs): both
    k_pad x k_pad submatrices of every subset are built batched and
    factored by one batched slogdet (``_log_det_ratio_rows``).
    """
    zy = _masked_rows(sp.Z, items, mask)
    live_rows = None if live_z is None else _masked_rows(live_z, items, mask)
    return _log_det_ratio_rows(sp, zy, mask, live_rows=live_rows,
                               live_x=live_x)


def _spec_round_impl(sampler: NDPPSampler, keys: jax.Array):
    """Traced body of one speculative round: draw one proposal per key
    (batched tree traversal), score all of them with one batched log-det
    ratio, and flip each acceptance coin.  Returns (items, mask, accept),
    leading dim N.  Shared by ``_spec_round`` (standalone dispatch),
    ``_spec_round_fused`` (fan-out folded into the same jit), and the
    device-resident round loop of ``_drive_rounds_fused``."""
    # scope names from the repro.obs.prof.phases catalog (free HLO
    # metadata; traced bodies never touch repro.obs)
    ks = jax.vmap(jax.random.split)(keys)
    with jax.named_scope("ndpp.proposal"):
        items, mask = sample_proposal_dpp_batch(sampler.tree, ks[:, 0])
    with jax.named_scope("ndpp.logdet_ratio"):
        log_ratio, _ = log_det_ratio_batch(sampler.sp, items, mask)
    with jax.named_scope("ndpp.accept"):
        u = jax.vmap(
            lambda k: jax.random.uniform(k, dtype=jnp.float32))(ks[:, 1])
        accept = jnp.log(u) <= log_ratio
    return items, mask, accept


@jax.jit
def _spec_round(sampler: NDPPSampler, keys: jax.Array):
    """One speculative round as its own dispatch (see ``_spec_round_impl``)."""
    return _spec_round_impl(sampler, keys)


def shard_sampler(sampler: NDPPSampler, mesh: Mesh) -> NDPPSampler:
    """Place a preprocessed sampler on a device mesh: tree deep levels, W,
    and the Z rows are item-sharded over the mesh "model" axis (shallow
    levels, lam, sigma replicated).  The sharded sampler draws bit-identical
    samples through ``_spec_round_sharded`` / ``sample_batched_many(mesh=)``.
    """
    return NDPPSampler(sp=shard_spectral(sampler.sp, mesh),
                       tree=shard_tree(sampler.tree, mesh))


def _spec_round_sharded_impl(sampler: NDPPSampler, keys: jax.Array,
                             mesh: Mesh):
    """Traced body of ``_spec_round_sharded`` (shared with the fused
    sharded round, which folds the key fan-out into the same jit)."""
    from repro.models import sharding as msh

    s = msh.model_extent(mesh)
    z_spec = msh.logical_to_spec(mesh, ("items", None), sampler.sp.Z.shape)
    z_axis = "model" if (s > 1 and z_spec != P(None, None)
                         and z_spec[0] is not None) else None
    in_specs = (
        NDPPSampler(sp=SpectralNDPP(Z=z_spec, sigma=P(None)),
                    tree=tree_shard_specs(sampler.tree, mesh)),
        P(None),
    )
    m_pad = sampler.tree.W.shape[0]

    def inner(s_loc, keys):
        ks = jax.vmap(jax.random.split)(keys)
        with jax.named_scope("ndpp.proposal"):
            items, mask = sample_proposal_dpp_batch(
                s_loc.tree, ks[:, 0], axis_name="model", m_pad_global=m_pad)
        with jax.named_scope("ndpp.logdet_ratio"):
            zy = msh.gather_rows(s_loc.sp.Z, items, mask, axis_name=z_axis)
            log_ratio, _ = _log_det_ratio_rows(s_loc.sp, zy, mask)
        with jax.named_scope("ndpp.accept"):
            u = jax.vmap(
                lambda k: jax.random.uniform(k, dtype=jnp.float32))(ks[:, 1])
            accept = jnp.log(u) <= log_ratio
        return items, mask, accept

    f = jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                      out_specs=(P(None),) * 3, check_vma=False)
    return f(sampler, keys)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _spec_round_sharded(sampler: NDPPSampler, keys: jax.Array, mesh: Mesh):
    """``_spec_round`` over a device mesh: one shard_map in which the tree
    descent, leaf scoring, and the Z-row gathers for the log-det ratio all
    happen on the shard owning the items, combined by psums of exact zeros.
    Only the (N, R)-shaped proposal subsets and (N,) scores cross shards —
    never an (M, ...)-shaped array.  Bit-identical to ``_spec_round``."""
    return _spec_round_sharded_impl(sampler, keys, mesh)


def _fanout_traced(req_keys: jax.Array, starts: jax.Array,
                   offsets: jax.Array) -> jax.Array:
    """Traced key fan-out: key of proposal t for request i is
    fold_in(req_keys[i], starts[i] + t).  Returns (P * S, 2).  fold_in is
    integer arithmetic, so the keys are bit-identical whether this runs as
    its own dispatch (``_fanout_keys``) or inside a fused round jit."""

    def per_req(k, s):
        return jax.vmap(lambda o: jax.random.fold_in(k, s + o))(offsets)

    return jax.vmap(per_req)(req_keys, starts).reshape(-1, req_keys.shape[-1])


@jax.jit
def _fanout_keys(req_keys: jax.Array, starts: jax.Array, offsets: jax.Array):
    """Standalone-dispatch form of ``_fanout_traced`` (the pre-fusion hot
    path; kept for the observer-instrumented Python driver)."""
    return _fanout_traced(req_keys, starts, offsets)


@functools.partial(jax.jit, static_argnames=("n_spec",))
def _spec_round_fused(sampler: NDPPSampler, slot_keys: jax.Array,
                      trials: jax.Array, *, n_spec: int):
    """One speculative round with the key fan-out folded into the same jit:
    the engine tick's single dispatch.

    ``slot_keys`` (n, 2) are per-request base keys, ``trials`` (n,) uint32
    the per-request proposal counts already spent; proposal t of request i
    is keyed ``fold_in(slot_keys[i], trials[i] + t)`` exactly as in the
    two-dispatch ``_fanout_keys`` + ``_spec_round`` path, so draws are
    bit-identical — the offsets ``arange(n_spec)`` become a traced constant
    instead of a per-tick h2d transfer.  Returns (items, mask, accept) with
    leading dim n * n_spec."""
    offsets = jnp.arange(n_spec, dtype=jnp.uint32)
    keys = _fanout_traced(slot_keys, trials, offsets)
    return _spec_round_impl(sampler, keys)


@functools.partial(jax.jit, static_argnames=("mesh", "n_spec"))
def _spec_round_fused_sharded(sampler: NDPPSampler, slot_keys: jax.Array,
                              trials: jax.Array, mesh: Mesh, *, n_spec: int):
    """``_spec_round_fused`` over a device mesh: fan-out traced on the
    replicated keys, then the one shard_map round.  Bit-identical to the
    two-dispatch sharded path."""
    offsets = jnp.arange(n_spec, dtype=jnp.uint32)
    keys = _fanout_traced(slot_keys, trials, offsets)
    return _spec_round_sharded_impl(sampler, keys, mesh)


def auto_n_spec(sampler: NDPPSampler, max_spec: int = 64) -> int:
    """Speculation depth that accepts most requests in one round: the next
    power of two >= E[#trials] = det(Lhat+I)/det(L+I), capped at max_spec."""
    expect = float(jax.device_get(det_ratio_exact(sampler.sp)))
    return int(min(max_spec, max(2, 1 << int(np.ceil(np.log2(max(1.0, expect)))))))


def sample_batched(
    sampler: NDPPSampler,
    key: jax.Array,
    n_spec: Optional[int] = None,
    max_trials: int = 1000,
    grow: int = 2,
    max_spec: int = 64,
    mesh: Optional[Mesh] = None,
) -> RejectionSample:
    """Speculative SAMPLEREJECT for one request: each round draws a batch of
    ``n_spec`` proposals at once and accepts the first success; the batch
    doubles (x``grow``, capped at ``max_spec``) after a fully rejected round.
    Distribution-identical to ``sample`` (see module comment above)."""
    res = sample_batched_many(
        sampler, key[None], n_spec=n_spec, max_trials=max_trials,
        grow=grow, max_spec=max_spec, split_keys=False, mesh=mesh,
    )
    return RejectionSample(
        items=res.items[0], mask=res.mask[0],
        trials=res.trials[0], accepted=res.accepted[0],
    )


def sample_batched_many(
    sampler: NDPPSampler,
    key: jax.Array,
    n: Optional[int] = None,
    n_spec: Optional[int] = None,
    max_trials: int = 1000,
    grow: int = 2,
    max_spec: int = 64,
    split_keys: bool = True,
    mesh: Optional[Mesh] = None,
    observer=None,
) -> RejectionSample:
    """Speculative rejection sampling for many requests sharing each round.

    All pending requests contribute ``n_spec`` proposals to one batched tree
    traversal + one batched log-det ratio per round; a request retires at its
    first accepted proposal.  Requests that rejected their whole batch stay
    for the next round with a doubled per-request batch.  The pending set is
    padded to a power of two so the number of distinct compiled shapes stays
    logarithmic.

    ``key``: either a single key (``split_keys=True``, split into ``n``
    request keys) or an (n, 2) array of per-request keys.  ``n_spec=None``
    auto-sizes the first round to ~E[#trials] (``auto_n_spec``).
    ``mesh``: run every round item-sharded across the mesh "model" axis
    (``_spec_round_sharded``); pass an already-placed ``shard_sampler``
    output to avoid re-sharding per round.  Draws, trial counts, and
    accept flags are bit-identical to the single-device path.
    ``observer``: duck-typed telemetry sink (e.g.
    ``repro.obs.RegistryObserver``) — see ``drive_rounds``.
    Returns a stacked RejectionSample with leading dim n.
    """
    if n_spec is None:
        n_spec = auto_n_spec(sampler, max_spec)
    if split_keys:
        if n is None:
            raise ValueError("n is required when passing a single key")
        req_keys = jax.random.split(key, n)
    else:
        req_keys = jnp.asarray(key)
        n = req_keys.shape[0]
    if mesh is None and observer is None:
        # the device-resident hot path: the whole accept/reject loop is one
        # dispatch (lax.while_loop over rounds) with no per-round host sync
        return _drive_rounds_fused(sampler, jnp.asarray(req_keys),
                                   n_spec=n_spec, max_trials=max_trials)
    round_fn = (
        (lambda keys: _spec_round(sampler, keys)) if mesh is None
        else (lambda keys: _spec_round_sharded(sampler, keys, mesh)))
    return drive_rounds(round_fn, req_keys, sampler.tree.R, n_spec=n_spec,
                        max_trials=max_trials, grow=grow, max_spec=max_spec,
                        observer=observer)


@functools.partial(jax.jit, static_argnames=("n_spec", "max_trials"))
def _drive_rounds_fused(
    sampler: NDPPSampler, req_keys: jax.Array, *, n_spec: int,
    max_trials: int,
) -> RejectionSample:
    """The whole speculative accept/reject loop inside one jit.

    A ``lax.while_loop`` over constant-width rounds of ``n_spec`` proposals
    per still-pending request: round r covers proposal offsets
    ``[r*n_spec, (r+1)*n_spec)``, keyed ``fold_in(req_keys[i], offset)``
    with the budget truncation traced (lanes past ``max_trials`` are masked,
    never reshaped).  Because proposal t of request i is *always* keyed by
    its position t — never by a split chain or the round layout — the
    draws, trial counts, and accept flags are bit-identical to the Python
    ``drive_rounds`` driver under any batching schedule; the host loop's
    doubling schedule only ever amortized per-round dispatch overhead,
    which a traced loop does not pay, so the fused driver keeps the width
    constant.  Retired requests ride along as masked lanes (shapes are
    loop-invariant); exhausted requests return their last in-budget
    proposal with ``accepted=False`` and ``trials=max_trials``, exactly as
    the host driver does.
    """
    n = req_keys.shape[0]
    r = sampler.tree.R
    offsets = jnp.arange(n_spec, dtype=jnp.uint32)
    lane = jnp.arange(n_spec, dtype=jnp.int32)

    def cond(carry):
        spent, _, _, _, accepted = carry
        return (~jnp.all(accepted)) & (spent < max_trials)

    def body(carry):
        spent, items, mask, trials, accepted = carry
        starts = jnp.broadcast_to(spent.astype(jnp.uint32), (n,))
        keys = _fanout_traced(req_keys, starts, offsets)
        it, mk, ac = _spec_round_impl(sampler, keys)
        it = it.reshape(n, n_spec, r)
        mk = mk.reshape(n, n_spec, r)
        ac = ac.reshape(n, n_spec)
        usable = jnp.minimum(jnp.asarray(n_spec, jnp.int32),
                             max_trials - spent)
        ac = ac & (lane[None, :] < usable)
        any_acc = ac.any(axis=1)
        first = jnp.argmax(ac, axis=1).astype(jnp.int32)
        pend = ~accepted
        newly = pend & any_acc
        # first accepted lane, else the last in-budget lane (the exhaustion
        # payout the host driver takes from its final round)
        pick = jnp.where(any_acc, first, usable - 1)
        it_p = jnp.take_along_axis(it, pick[:, None, None], axis=1)[:, 0]
        mk_p = jnp.take_along_axis(mk, pick[:, None, None], axis=1)[:, 0]
        items = jnp.where(pend[:, None], it_p, items)
        mask = jnp.where(pend[:, None], mk_p, mask)
        trials = jnp.where(newly, spent + first + 1, trials)
        return (spent + usable, items, mask, trials, accepted | newly)

    init = (
        jnp.asarray(0, jnp.int32),
        -jnp.ones((n, r), jnp.int32),
        jnp.zeros((n, r), bool),
        jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), bool),
    )
    _, items, mask, trials, accepted = jax.lax.while_loop(cond, body, init)
    trials = jnp.where(accepted, trials,
                       jnp.asarray(max_trials, jnp.int32))
    return RejectionSample(items=items, mask=mask, trials=trials,
                           accepted=accepted)


def drive_rounds(
    round_fn, req_keys: jax.Array, r: int, *, n_spec: int,
    max_trials: int = 1000, grow: int = 2, max_spec: int = 64,
    observer=None,
) -> RejectionSample:
    """Speculative-round driver shared by the static sampler and the
    dynamic-catalog sampler (``core.dynamic.sample_state_many``).

    ``round_fn(keys)`` scores one proposal per (P, 2) key and returns
    (items, mask, accept); this loop owns the retire-first-acceptance /
    double-on-miss scheduling around it.  Proposal t of request i is always
    keyed ``fold_in(req_keys[i], t)``, so results are independent of the
    batching schedule and of which round function runs the proposals.

    ``observer``: optional duck-typed telemetry sink — after each round's
    designed ``device_get`` it receives ``on_round(n_active=, n_spec=,
    proposals=, accepts=)`` and one ``on_retire(trials=, accepted=)`` per
    request leaving the pending set, all with plain host ints (the stats
    piggyback on arrays this loop already transfers, so observation adds
    no sync points and cannot perturb the draws).  An observer may also
    provide a ``phase(name)`` context-manager hook (profiler scopes: the
    round dispatch and the harvest sync get named ranges —
    ``repro.obs.prof.phases``).  The traced round bodies stay free of
    telemetry; pass e.g. ``repro.obs.RegistryObserver``.
    """
    phase = getattr(observer, "phase", None) or (lambda name: _NO_PHASE)
    n = req_keys.shape[0]
    items_out = np.full((n, r), -1, np.int32)
    mask_out = np.zeros((n, r), bool)
    trials_out = np.zeros((n,), np.int32)
    acc_out = np.zeros((n,), bool)

    active = np.arange(n)
    spent = 0                      # identical for every still-active request
    cur = int(n_spec)
    req_keys_h = jax.device_get(req_keys)   # one sync, outside the loop
    while active.size:
        cur = min(cur, max_spec)
        # budget truncation by *masking*, never by reshaping: the round
        # keeps its power-of-two width (no fresh jit cache entry near
        # exhaustion) and only the first ``usable`` lanes — the in-budget
        # fold_in offsets [spent, spent+usable) — are consumed
        usable = min(cur, max_trials - spent)
        n_act = int(active.size)
        n_pad = 1 << max(0, n_act - 1).bit_length()
        act_keys = jnp.asarray(req_keys_h[active])
        if n_pad > n_act:          # pad with repeats; results are discarded
            act_keys = jnp.concatenate(
                [act_keys, jnp.broadcast_to(act_keys[:1], (n_pad - n_act, 2))]
            )
        with phase("round_dispatch"):
            keys = _fanout_keys(
                act_keys,
                jnp.full((n_pad,), spent, jnp.uint32),
                jnp.arange(cur, dtype=jnp.uint32),
            )
            items, mask, accept = round_fn(keys)
        # the one designed device→host sync per round (the fused
        # ``_drive_rounds_fused`` driver removes it on the default path);
        # explicit so transfer guards see it as intentional
        with phase("harvest"):
            items_h, mask_h, acc = jax.device_get((items, mask, accept))
        acc = acc.reshape(n_pad, cur)[:n_act, :usable]
        items_h = items_h.reshape(n_pad, cur, r)[:n_act]
        mask_h = mask_h.reshape(n_pad, cur, r)[:n_act]

        any_acc = acc.any(axis=1)
        first = acc.argmax(axis=1)
        hit = active[any_acc]
        items_out[hit] = items_h[any_acc, first[any_acc]]
        mask_out[hit] = mask_h[any_acc, first[any_acc]]
        trials_out[hit] = spent + first[any_acc] + 1
        acc_out[hit] = True
        if observer is not None:
            observer.on_round(n_active=n_act, n_spec=usable,
                              proposals=n_act * usable, accepts=int(acc.sum()))
            for t in trials_out[hit]:
                observer.on_retire(trials=int(t), accepted=True)

        spent += usable
        miss = ~any_acc
        if spent >= max_trials:    # exhausted: return the last in-budget
            left = active[miss]    # proposal, as the sequential sampler does
            items_out[left] = items_h[miss, usable - 1]
            mask_out[left] = mask_h[miss, usable - 1]
            trials_out[left] = spent
            if observer is not None:
                for _ in left:
                    observer.on_retire(trials=spent, accepted=False)
            break
        active = active[miss]
        cur *= grow

    return RejectionSample(
        items=jnp.asarray(items_out),
        mask=jnp.asarray(mask_out),
        trials=jnp.asarray(trials_out),
        accepted=jnp.asarray(acc_out),
    )
