"""Core NDPP library — the paper's contribution in JAX.

Public API:
  types      — NDPPParams / ONDPPParams / SpectralNDPP containers
  youla      — O(M K^2) Youla decomposition of the skew part (Alg. 4)
  cholesky   — linear-time O(M K^2) exact sampler (Alg. 1 RHS)
  tree       — proposal eigens + flat tree + elementary DPP sampling (Alg. 3)
  rejection  — sublinear-time rejection sampler (Alg. 2) + Theorem 2 rates
  dynamic    — incremental dual-form proposal maintenance (mutable catalogs)
  mcmc       — exact-target up/down/swap Metropolis chains, O(K^2)/step
  learning   — ONDPP objective (Eq. 14) + baselines + constraint projection
  map_inference — greedy conditioning / MPR
"""
from .types import (  # noqa: F401
    NDPPParams,
    ONDPPParams,
    SpectralNDPP,
    d_from_sigma,
    x_from_sigma,
    dense_l,
    dense_l_spectral,
    dense_l_hat,
)
from .youla import (  # noqa: F401
    youla_decompose,
    youla_transform_np,
    spectral_from_params,
    spectral_from_transform,
)
from .cholesky import (  # noqa: F401
    marginal_inner,
    marginal_inner_from_params,
    sample_cholesky,
    sample_cholesky_inner,
    sample_cholesky_params,
    sample_cholesky_spectral,
    sample_cholesky_blocked,
)
from .tree import (  # noqa: F401
    SampleTree,
    construct_tree,
    dual_q0,
    proposal_eigens,
    sample_proposal_dpp,
    sample_proposal_dpp_batch,
    sample_proposal_dpp_batch_sharded,
    sample_elementary,
    sample_elementary_batch,
    sample_elementary_dense,
    shard_spectral,
    shard_tree,
    tree_shard_specs,
    update_rows,
    update_rows_sharded,
)
from .rejection import (  # noqa: F401
    NDPPSampler,
    RejectionSample,
    preprocess,
    sample,
    sample_batch,
    sample_batched,
    sample_batched_many,
    shard_sampler,
    auto_n_spec,
    expected_trials,
    det_ratio_exact,
    log_det_ratio,
    log_det_ratio_batch,
)
from .learning import (  # noqa: F401
    Baskets,
    ondpp_loss,
    ndpp_loss,
    symmetric_dpp_loss,
    project_constraints,
    init_ondpp,
    init_ndpp,
    item_frequencies,
    log_normalizer,
)
from .map_inference import (  # noqa: F401
    next_item_scores,
    greedy_map,
    conditional_sample,
    mean_percentile_rank,
    mpr_frequency_baseline,
)
from .kdpp import (  # noqa: F401
    elementary_symmetric,
    elementary_symmetric_log,
    sample_fixed_size_e,
    sample_kdpp,
    sample_k_ndpp,
)
from .dynamic import (  # noqa: F401
    DualProposal,
    auto_n_spec_dynamic,
    build_dual_proposal,
    dual_eigens,
    dual_rows,
    expected_trials_dynamic,
    sample_dynamic_many,
    update_proposal,
)
from .mcmc import (  # noqa: F401
    MCMCSample,
    MCMCState,
    add_ratio,
    init_empty,
    init_greedy,
    reanchor,
    remove_ratio,
    run_chains,
    run_chains_sharded,
    sample_mcmc,
    score_matrix,
    swap_ratio,
    swap_score_matrix,
)
