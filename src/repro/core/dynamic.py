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
from jax.sharding import Mesh

from .rejection import RejectionSample, drive_rounds
from .tree import (
    SampleTree,
    construct_tree,
    shard_spectral,
    shard_tree,
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
      dual_u: (R, R) eigenvectors of C (builds the ``dual_q0``
        projectors).
      sp: the spectral state A was derived from — the acceptance
        denominator det(L̂_Y) is scored against *these* rows, because this
        is the kernel the tree actually proposes from, even when the live
        catalog has moved on.

    The triple must stay consistent (tree rows, eigens, and sp from one
    catalog version); ``update_proposal`` maintains that invariant.
    """

    tree: SampleTree
    dual_u: jax.Array
    sp: SpectralNDPP

    @property
    def R(self) -> int:
        return self.tree.R


jax.tree_util.register_pytree_node(
    DualProposal,
    lambda p: ((p.tree, p.dual_u, p.sp), None),
    lambda _, c: DualProposal(tree=c[0], dual_u=c[1], sp=c[2]),
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
    return DualProposal(tree=tree, dual_u=u, sp=sp)


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
    return DualProposal(tree=dataclasses.replace(tree, lam=lam), dual_u=u,
                        sp=new_sp)


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
    return drive_rounds(prop, req_keys, n_spec=n_spec, live=live_sp,
                        mesh=mesh, max_trials=max_trials, grow=grow,
                        max_spec=max_spec, observer=observer)
