"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention import ops as aops
from repro.kernels.attention.ref import mha_ref
from repro.kernels.bilinear import ops as bops
from repro.kernels.bilinear.ref import bilinear_batched_ref, bilinear_ref
from repro.kernels.mcmc_score import ops as mops
from repro.kernels.mcmc_score.ref import score_all_ref
from repro.kernels.spec_round import ops as spops
from repro.kernels.spec_round.ref import descend_ref
from repro.kernels.ssd import ops as sops
from repro.kernels.ssd.ref import ssd_ref
from repro.kernels.tree_sum import ops as tops
from repro.kernels.tree_sum.ref import (
    block_outer_sums_ref,
    gathered_block_grams_ref,
)


@pytest.mark.parametrize("m,r", [(64, 8), (100, 40), (512, 200), (33, 7), (8, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bilinear(rng, m, r, dtype):
    z = jnp.asarray(rng.normal(size=(m, r)), dtype)
    w = jnp.asarray(rng.normal(size=(r, r)), dtype)
    out = bops.bilinear(z, w, force_interpret=True)
    ref = bilinear_ref(z, w)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol * max(1, r))


@pytest.mark.parametrize("n,b,r", [(4, 8, 16), (16, 64, 64), (3, 5, 40)])
def test_bilinear_batched(rng, n, b, r):
    """Per-element inner matrices: the speculative leaf-scoring layout."""
    z = jnp.asarray(rng.normal(size=(n, b, r)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n, r, r)), jnp.float32)
    out = bops.bilinear_batched(z, w, force_interpret=True)
    ref = bilinear_batched_ref(z, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4 * max(1, r))


@pytest.mark.parametrize("m,c,r", [(64, 4, 16), (512, 2, 64), (100, 3, 40),
                                   (33, 7, 130), (8, 1, 256)])
def test_mcmc_score_all(m, c, r):
    """Shared ground-set rows, one score matrix per chain — the MCMC
    all-candidate move scorer."""
    # local generator: later modules' draws from the shared session rng
    # must not shift (their MC tolerances are kernel-dependent)
    rng = np.random.default_rng(m * 1000 + c * 10 + r)
    z = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(c, r, r)), jnp.float32)
    out = mops.score_all(z, a, force_interpret=True)
    ref = score_all_ref(z, a)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5 * max(1, r))


@pytest.mark.parametrize("m,blk,r", [(64, 8, 16), (256, 64, 40), (128, 32, 130)])
def test_tree_sum(rng, m, blk, r):
    w = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
    out = tops.block_outer_sums(w, blk, force_interpret=True)
    ref = block_outer_sums_ref(w, blk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,blk,r,nb", [(64, 8, 16, 3), (256, 64, 40, 5),
                                        (128, 32, 130, 2), (64, 8, 8, 1)])
def test_gathered_block_grams(rng, m, blk, r, nb):
    """Scalar-prefetch gathered-Gram kernel (the tree_update hot path) vs
    the einsum oracle, including repeated block ids (idempotent writes)."""
    w = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
    blks = jnp.asarray(rng.integers(0, m // blk, size=nb), jnp.int32)
    out = tops.gathered_block_grams(w, blks, blk, force_interpret=True)
    ref = gathered_block_grams_ref(w, blks, blk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    # the gathered Grams must agree with the same blocks of a full build
    full = block_outer_sums_ref(w, blk)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(full[blks]),
                               rtol=0, atol=0)


def _random_tree_levels(rng, depth, r):
    """A mass-consistent proposal tree: random PSD leaf nodes, parents the
    sum of their children — so the descent's p_left / p_all - p_left
    carry-down walks real masses, not arbitrary numbers."""
    leaves = rng.normal(size=(1 << depth, r, r)).astype(np.float32)
    nodes = jnp.asarray(np.einsum("nik,njk->nij", leaves, leaves))
    levels = [nodes]
    for _ in range(depth):
        nodes = nodes.reshape(-1, 2, r, r).sum(axis=1)
        levels.append(nodes)
    return tuple(reversed(levels))


@pytest.mark.parametrize("depth,block,r,n", [(3, 4, 8, 5), (5, 8, 16, 12),
                                             (6, 2, 40, 3), (2, 8, 130, 4)])
def test_spec_round_descend_score(depth, block, r, n):
    """HBM-resident descent kernel (interpret mode) vs the jnp oracle:
    identical block choices, then matching raw leaf scores of the chosen
    blocks.  Spans shallow-only trees (depth <= 5 under _SHALLOW_MAX=32),
    deep per-lane gathers, lane counts off the kernel's lane multiple, and
    R past one 1024-float node slab."""
    rng = np.random.default_rng(depth * 1000 + block * 100 + r)
    levels = _random_tree_levels(rng, depth, r)
    m = (1 << depth) * block
    w = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
    qh = rng.normal(size=(n, r, r)).astype(np.float32)
    q = jnp.asarray(np.einsum("nik,njk->nij", qh, qh) / r)
    us = jnp.asarray(rng.uniform(size=(n, depth)), jnp.float32)
    flat = spops.descent_operands(levels, force_interpret=True)
    assert flat is not None and flat[-1].shape[1:] == (
        -(-r * r // 1024) * 8, 128)
    blk = spops.descend(levels, flat, q, us, force_interpret=True)
    blk_ref = descend_ref(levels, q, us)
    np.testing.assert_array_equal(np.asarray(blk), np.asarray(blk_ref))
    rows = blk_ref[:, None] * block + jnp.arange(block)[None, :]
    sc = bops.bilinear_batched(w[rows], q, force_interpret=True)
    np.testing.assert_allclose(np.asarray(sc),
                               np.asarray(bilinear_batched_ref(w[rows], q)),
                               rtol=1e-4, atol=1e-4 * max(1, r))


def test_spec_round_shallow_max_matches_tree():
    """The oracle's shallow/deep level classifier must agree with
    core.tree's, or the fused path and the sharded descent would walk the
    same tree with different stacked-matmul layouts."""
    from repro.core import tree as core_tree
    from repro.kernels.spec_round import ref as spref

    assert spref._SHALLOW_MAX == core_tree._SHALLOW_MAX


@pytest.mark.parametrize(
    "b,h,kvh,s,d", [(1, 4, 2, 128, 64), (2, 4, 4, 256, 64),
                    (1, 8, 2, 128, 128), (1, 2, 1, 384, 64)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(rng, b, h, kvh, s, d, dtype):
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, kvh, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, kvh, s, d)), dtype)
    out = aops.mha(q, k, v, causal=True, force_interpret=True)
    ref = mha_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol * 100, atol=tol * 10,
    )


@pytest.mark.parametrize("b,s,h,p,n,chunk",
                         [(2, 64, 2, 16, 8, 16), (1, 128, 4, 32, 16, 32),
                          (1, 96, 1, 8, 4, 32)])
def test_ssd(rng, b, s, h, p, n, chunk):
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.7, 1.0, size=(b, s, h)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    y, hl = sops.ssd(x, a, bb, c, chunk=chunk, force_interpret=True)
    yr, hr = ssd_ref(x, a, bb, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hr), rtol=1e-3, atol=1e-3)


def test_ssd_decode_matches_scan(rng):
    """Stepwise decode must equal the chunked scan."""
    b, s, h, p, n = 1, 16, 2, 8, 4
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.7, 1.0, size=(b, s, h)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    y_ref, h_ref = ssd_ref(x, a, bb, c)
    hstate = jnp.zeros((b, h, n, p), jnp.float32)
    ys = []
    for t in range(s):
        yt, hstate = sops.ssd_decode_step(x[:, t], a[:, t], bb[:, t], c[:, t], hstate)
        ys.append(yt)
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hstate), np.asarray(h_ref),
                               rtol=1e-4, atol=1e-4)


def test_attention_gqa_kv_len(rng):
    """Ragged decode path: kv_len masking matches a truncated dense call."""
    b, h, kvh, d = 2, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, kvh, 16, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, kvh, 16, d)), jnp.float32)
    out = aops.mha(q, k, v, causal=True, kv_len=jnp.asarray([10, 10]))
    ref = mha_ref(q, k[:, :, :10], v[:, :, :10], causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
