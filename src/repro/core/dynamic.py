"""Incremental (dual-form) proposal maintenance for dynamic catalogs.

The static sampler builds its tree over the *orthonormal eigenvector* rows
W of the proposal kernel L̂ (``proposal_eigens``) — a basis in which a
single catalog-row change perturbs every entry of W, forcing a full
O(M R^2) rebuild.  This module keeps the tree in the **dual** basis
instead: rows

    a_j = z_j ⊙ x̂^{1/2}            (so L̂ = A Aᵀ)

are item-local, the R x R dual Gram ``C = Aᵀ A`` is *exactly the tree
root* (the tree levels are pairwise partial sums of leaf-block Grams
``A_blkᵀ A_blk``), and the eigenpairs (λ, U) of C — the paper's dual /
Youla-side spectral state (Gartrell et al. 2020) — are an O(R^3)
eigendecomposition of a matrix the tree already maintains.  Elementary
DPPs are sampled through the *same* descent/score/downdate machinery as
the primal tree under the basis change ``w_j = diag(λ)^{-1/2} Uᵀ a_j``:
the initial conditioning projector becomes ``Q0 = U_E diag(1/λ_E) U_Eᵀ``
(``core.tree.dual_q0``) and everything downstream is untouched.

Consequences, which ``serve.catalog`` turns into a streaming API:

* a batched row change costs O(B (block + log M) R^2) (``update_rows`` /
  the ``tree_update`` kernel) plus one R x R eigendecomposition — never a
  full rebuild;
* the maintained tree is BIT-equal to ``construct_tree`` on the mutated
  rows (touched nodes are recomputed through identical arithmetic, not
  delta-patched), plain and mesh-sharded alike;
* a *stale* proposal snapshot stays usable: the acceptance test rescores
  the live kernel (``log_det_ratio(..., live_z=, live_x=)``), so draws
  remain exactly distributed whenever the snapshot still dominates the
  live kernel (deletes / row downscales — see docs/architecture.md), with
  only the rejection rate degrading by det(L̂_snap + I) / det(L̂_live + I).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .rejection import (
    RejectionSample,
    _fanout_traced,
    _log_det_ratio_rows,
    drive_rounds,
    log_det_ratio_batch,
)
from .tree import (
    SampleTree,
    construct_tree,
    sample_proposal_dpp_batch,
    shard_spectral,
    shard_tree,
    tree_shard_specs,
    update_rows,
    update_rows_sharded,
)
from .types import SpectralNDPP


@dataclasses.dataclass(frozen=True)
class DualProposal:
    """A *consistent* proposal snapshot in the dual basis.

    Attributes:
      tree: flat sample tree over the dual rows A (``tree.W`` holds A,
        ``tree.lam`` the eigenvalues of C = Aᵀ A — equal to L̂'s nonzero
        spectrum).
      u: (R, R) eigenvectors of C (builds the ``dual_q0`` projectors).
      sp: the spectral state A was derived from — the acceptance
        denominator det(L̂_Y) is scored against *these* rows, because this
        is the kernel the tree actually proposes from, even when the live
        catalog has moved on.

    The triple must stay consistent (tree rows, eigens, and sp from one
    catalog version); ``update_proposal`` maintains that invariant.
    """

    tree: SampleTree
    u: jax.Array
    sp: SpectralNDPP

    @property
    def R(self) -> int:
        return self.tree.R


jax.tree_util.register_pytree_node(
    DualProposal,
    lambda p: ((p.tree, p.u, p.sp), None),
    lambda _, c: DualProposal(tree=c[0], u=c[1], sp=c[2]),
)


def dual_rows(sp: SpectralNDPP) -> jax.Array:
    """A = Z diag(x̂)^{1/2}: the item-local factor with L̂ = A Aᵀ."""
    return sp.Z * jnp.sqrt(sp.x_diag_hat())[None, :]


def dual_eigens(root: jax.Array, eps: float = 1e-10
                ) -> Tuple[jax.Array, jax.Array]:
    """Eigenpairs (λ, U) of the R x R dual Gram (= the tree root), with
    null directions (λ <= eps) zeroed so their coin probability is 0."""
    lam, u = jnp.linalg.eigh(root)
    lam = jnp.maximum(lam, 0.0)
    lam = lam * (lam > eps)
    return lam, u


def build_dual_proposal(sp: SpectralNDPP, block: int = 64,
                        mesh: Optional[Mesh] = None) -> DualProposal:
    """Construct the dual tree + eigens from scratch (catalog build /
    doubling rebuild).  With ``mesh``, the tree and Z are placed
    item-sharded (``shard_tree`` / ``shard_spectral``)."""
    a = dual_rows(sp)
    tree = construct_tree(jnp.zeros((a.shape[1],), a.dtype), a, block=block)
    lam, u = dual_eigens(tree.levels[0][0])
    tree = dataclasses.replace(tree, lam=lam)
    if mesh is not None:
        tree = shard_tree(tree, mesh)
        sp = shard_spectral(sp, mesh)
    return DualProposal(tree=tree, u=u, sp=sp)


@functools.partial(jax.jit, static_argnames=("mesh",))
def update_proposal(prop: DualProposal, idx: jax.Array, z_rows: jax.Array,
                    new_sp: SpectralNDPP,
                    mesh: Optional[Mesh] = None) -> DualProposal:
    """Apply a batched row change to a live proposal: O(log M) tree path
    update + O(R^3) dual-eigens refresh from the maintained root.

    Jitted end to end (one dispatch per mutation batch; retraces only on a
    new update-batch size or a capacity change).

    ``idx``: (B,) unique row indices; ``z_rows``: (B, R) new Z rows
    (zeros = delete); ``new_sp``: the already-updated spectral state this
    proposal now matches.  The returned proposal is bit-consistent with
    ``build_dual_proposal(new_sp)`` up to the eigendecomposition (the tree
    arrays are bit-equal to a from-scratch ``construct_tree``).
    """
    xhalf = jnp.sqrt(new_sp.x_diag_hat())
    a_rows = z_rows * xhalf[None, :]
    if mesh is None:
        tree = update_rows(prop.tree, idx, a_rows)
    else:
        tree = update_rows_sharded(prop.tree, idx, a_rows, mesh)
    lam, u = dual_eigens(tree.levels[0][0])
    return DualProposal(tree=dataclasses.replace(tree, lam=lam), u=u,
                        sp=new_sp)


# ------------------------------------------------------------ sampling rounds


def _spec_round_dual_impl(prop: DualProposal, live_sp: SpectralNDPP,
                          keys: jax.Array):
    """Traced body of one dual-proposal round (shared by the standalone
    dispatch and the fused variant that folds the key fan-out in)."""
    # scope names from the repro.obs.prof.phases catalog (free HLO
    # metadata; traced bodies never touch repro.obs)
    ks = jax.vmap(jax.random.split)(keys)
    with jax.named_scope("ndpp.proposal"):
        items, mask = sample_proposal_dpp_batch(prop.tree, ks[:, 0],
                                                dual_u=prop.u)
    with jax.named_scope("ndpp.logdet_ratio"):
        log_ratio, _ = log_det_ratio_batch(prop.sp, items, mask,
                                           live_z=live_sp.Z,
                                           live_x=live_sp.x_matrix())
    with jax.named_scope("ndpp.accept"):
        u = jax.vmap(
            lambda k: jax.random.uniform(k, dtype=jnp.float32))(ks[:, 1])
        accept = jnp.log(u) <= log_ratio
    return items, mask, accept


@jax.jit
def _spec_round_dual(prop: DualProposal, live_sp: SpectralNDPP,
                     keys: jax.Array):
    """One speculative round against a (possibly stale) dual proposal: the
    tree proposes from L̂_snap, the acceptance test rescores the *live*
    kernel.  Key schedule identical to ``rejection._spec_round``, so a
    request's draw is independent of which proposal version served it —
    as long as that version's arrays are the ones passed here (the
    engine's version pinning)."""
    return _spec_round_dual_impl(prop, live_sp, keys)


def _spec_round_dual_sharded_impl(prop: DualProposal, live_sp: SpectralNDPP,
                                  keys: jax.Array, mesh: Mesh):
    """Traced body of ``_spec_round_dual_sharded`` (shared with the fused
    sharded variant)."""
    from repro.models import sharding as msh

    s = msh.model_extent(mesh)
    z_spec = msh.logical_to_spec(mesh, ("items", None), prop.sp.Z.shape)
    z_axis = "model" if (s > 1 and z_spec != P(None, None)
                         and z_spec[0] is not None) else None
    prop_specs = DualProposal(
        tree=tree_shard_specs(prop.tree, mesh), u=P(None, None),
        sp=SpectralNDPP(Z=z_spec, sigma=P(None)))
    live_specs = SpectralNDPP(Z=z_spec, sigma=P(None))
    m_pad = prop.tree.W.shape[0]

    def inner(p_loc, live_loc, keys):
        ks = jax.vmap(jax.random.split)(keys)
        with jax.named_scope("ndpp.proposal"):
            items, mask = sample_proposal_dpp_batch(
                p_loc.tree, ks[:, 0], axis_name="model", m_pad_global=m_pad,
                dual_u=p_loc.u)
        with jax.named_scope("ndpp.logdet_ratio"):
            zy = msh.gather_rows(p_loc.sp.Z, items, mask, axis_name=z_axis)
            zy_live = msh.gather_rows(live_loc.Z, items, mask,
                                      axis_name=z_axis)
            log_ratio, _ = _log_det_ratio_rows(
                p_loc.sp, zy, mask, live_rows=zy_live,
                live_x=live_loc.x_matrix())
        with jax.named_scope("ndpp.accept"):
            u = jax.vmap(
                lambda k: jax.random.uniform(k, dtype=jnp.float32))(ks[:, 1])
            accept = jnp.log(u) <= log_ratio
        return items, mask, accept

    f = jax.shard_map(inner, mesh=mesh,
                      in_specs=(prop_specs, live_specs, P(None)),
                      out_specs=(P(None),) * 3, check_vma=False)
    return f(prop, live_sp, keys)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _spec_round_dual_sharded(prop: DualProposal, live_sp: SpectralNDPP,
                             keys: jax.Array, mesh: Mesh):
    """``_spec_round_dual`` over a device mesh: tree descent, leaf scoring,
    and the snapshot/live Z-row gathers all run on the owning shard and
    combine by psums of exact zeros (the PR-3 invariant) — bit-identical
    to the unsharded round."""
    return _spec_round_dual_sharded_impl(prop, live_sp, keys, mesh)


@functools.partial(jax.jit, static_argnames=("n_spec",))
def _spec_round_dual_fused(prop: DualProposal, live_sp: SpectralNDPP,
                           slot_keys: jax.Array, trials: jax.Array, *,
                           n_spec: int):
    """One dual-proposal round with the key fan-out folded into the same
    jit — the engine's single dispatch per tick for slots pinned to a
    stale proposal snapshot.  Key schedule (``fold_in(slot_keys[i],
    trials[i] + t)``) and every downstream op match the two-dispatch
    ``_fanout_keys`` + ``_spec_round_dual`` path bit for bit."""
    offsets = jnp.arange(n_spec, dtype=jnp.uint32)
    keys = _fanout_traced(slot_keys, trials, offsets)
    return _spec_round_dual_impl(prop, live_sp, keys)


@functools.partial(jax.jit, static_argnames=("mesh", "n_spec"))
def _spec_round_dual_fused_sharded(prop: DualProposal, live_sp: SpectralNDPP,
                                   slot_keys: jax.Array, trials: jax.Array,
                                   mesh: Mesh, *, n_spec: int):
    """``_spec_round_dual_fused`` over a device mesh (fan-out traced on
    the replicated keys, then the one shard_map round)."""
    offsets = jnp.arange(n_spec, dtype=jnp.uint32)
    keys = _fanout_traced(slot_keys, trials, offsets)
    return _spec_round_dual_sharded_impl(prop, live_sp, keys, mesh)


# ------------------------------------------------------------------- drivers


def expected_trials_dynamic(prop: DualProposal,
                            live_sp: SpectralNDPP) -> jax.Array:
    """E[#trials] under a (possibly stale) proposal:
    det(L̂_snap + I) / det(L_live + I).  The numerator is Π (1 + λ) over
    the snapshot's dual eigenvalues (already maintained); the denominator
    is an R x R determinant.  Equals ``det_ratio_exact`` when the snapshot
    is fresh; the stale/fresh quotient Π(1+λ_snap)/Π(1+λ_live) is the
    rejection-rate degradation bound asserted in tests."""
    ld_hat = jnp.sum(jnp.log1p(prop.tree.lam))
    g = live_sp.Z.T @ live_sp.Z
    eye = jnp.eye(g.shape[0], dtype=g.dtype)
    _, ld_l = jnp.linalg.slogdet(eye + live_sp.x_matrix() @ g)
    return jnp.exp(ld_hat - ld_l)


def auto_n_spec_dynamic(prop: DualProposal, live_sp: SpectralNDPP,
                        max_spec: int = 64) -> int:
    """Speculation depth ~ E[#trials] under the current proposal snapshot
    (next power of two, capped) — the dynamic analog of ``auto_n_spec``."""
    expect = float(expected_trials_dynamic(prop, live_sp))
    return int(min(max_spec,
                   max(2, 1 << int(np.ceil(np.log2(max(1.0, expect)))))))


def sample_dynamic_many(
    prop: DualProposal,
    live_sp: SpectralNDPP,
    key: jax.Array,
    n: Optional[int] = None,
    *,
    n_spec: Optional[int] = None,
    max_trials: int = 1000,
    grow: int = 2,
    max_spec: int = 64,
    split_keys: bool = True,
    mesh: Optional[Mesh] = None,
    observer=None,
) -> RejectionSample:
    """Speculative rejection sampling against a dynamic-catalog state.

    Same scheduling/exactness contract as ``rejection.sample_batched_many``
    (shared ``drive_rounds`` loop; proposal t of request i is
    ``fold_in(req_key_i, t)``), but the proposal is a ``DualProposal``
    snapshot and acceptance rescoring runs against ``live_sp`` — exact
    draws from the live kernel whenever the snapshot dominates it.
    """
    if n_spec is None:
        n_spec = auto_n_spec_dynamic(prop, live_sp, max_spec)
    if split_keys:
        if n is None:
            raise ValueError("n is required when passing a single key")
        req_keys = jax.random.split(key, n)
    else:
        req_keys = jnp.asarray(key)
    round_fn = (
        (lambda keys: _spec_round_dual(prop, live_sp, keys)) if mesh is None
        else (lambda keys: _spec_round_dual_sharded(prop, live_sp, keys,
                                                    mesh)))
    return drive_rounds(round_fn, req_keys, prop.R, n_spec=n_spec,
                        max_trials=max_trials, grow=grow, max_spec=max_spec,
                        observer=observer)
