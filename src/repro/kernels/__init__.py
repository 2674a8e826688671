"""Pallas TPU kernels for the performance-critical compute layers.

Each subpackage ships:
  <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, dispatch)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

``backend.py`` holds the one dispatch rule: the kernel on TPU, the oracle
on other backends, the Pallas interpreter when asked for.

Kernels: bilinear (NDPP quadratic forms), tree_sum (tree construction),
spec_round (tree descent), mcmc_score (MCMC move scores), attention
(causal GQA flash), ssd (mamba2 chunked scan).
"""
