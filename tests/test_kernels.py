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
from repro.kernels.slogdet import ops as slops
from repro.kernels.spec_round import ops as spops
from repro.kernels.spec_round.ref import descend_pair_ref, descend_ref
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
                                             (6, 2, 40, 3), (2, 8, 130, 4),
                                             (4, 4, 8, 70), (3, 2, 16, 200),
                                             (1, 4, 8, 5), (1, 2, 130, 20),
                                             (6, 2, 100, 200)])
def test_spec_round_descend_score(depth, block, r, n):
    """HBM-resident descent kernel (interpret mode) vs the jnp oracles:
    block choices identical to the kernel's own rule
    (``descend_pair_ref``) and to the carried oracle ``descend_ref``, then
    matching raw leaf scores of the chosen blocks.  Spans shallow-only
    trees (depth <= 5 under _SHALLOW_MAX=32), deep per-lane gathers,
    depth 1, lane counts off the kernel's group and step multiples,
    several lane groups taking turns within a grid step (70, 200),
    several grid steps at the benchmark's R = 100, and R past one
    1024-float node slab."""
    rng = np.random.default_rng(depth * 1000 + block * 100 + r)
    levels = _random_tree_levels(rng, depth, r)
    m = (1 << depth) * block
    w = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
    qh = rng.normal(size=(n, r, r)).astype(np.float32)
    q = jnp.asarray(np.einsum("nik,njk->nij", qh, qh) / r)
    us = jnp.asarray(rng.uniform(size=(n, depth)), jnp.float32)
    flat = spops.descent_operands(levels, force_interpret=True)
    assert flat is not None and len(flat) == depth
    assert flat[-1].shape[1:] == (-(-r * r // 1024) * 8, 128)
    blk = spops.descend(levels, flat, q, us, force_interpret=True)
    blk_ref = descend_ref(levels, q, us)
    np.testing.assert_array_equal(np.asarray(blk),
                                  np.asarray(descend_pair_ref(levels, q, us)))
    np.testing.assert_array_equal(np.asarray(blk), np.asarray(blk_ref))
    rows = blk_ref[:, None] * block + jnp.arange(block)[None, :]
    sc = bops.bilinear_batched(w[rows], q, force_interpret=True)
    np.testing.assert_allclose(np.asarray(sc),
                               np.asarray(bilinear_batched_ref(w[rows], q)),
                               rtol=1e-4, atol=1e-4 * max(1, r))


@pytest.mark.parametrize("r", [8, 100, 130, 256])
@pytest.mark.parametrize("n", [1, 3, 12, 70, 200, 512, 2000])
def test_descent_lanes_rule(r, n):
    """The descent kernel's lane groups come from the shapes: whole groups
    of a multiple of 8 lanes, the lanes per grid step a whole number of
    groups dividing the padded batch, padding under one step, every
    buffer within the VMEM budget, and two groups taking turns wherever
    the batch has two."""
    s = spops.flat_nodes(jnp.zeros((1, r, r))).shape[1]
    lanes, group = spops.descent_lanes(n, s)
    n_pad = n + (-n) % lanes
    assert group % 8 == 0 and lanes % group == 0
    assert n_pad % lanes == 0 and n_pad - n < lanes
    assert spops.vmem_bytes(lanes, s) <= spops.DESCENT_VMEM_BYTES
    if n > 8:
        assert lanes >= 2 * group
    if (r, n) == (100, 512):           # the benchmark cell: no padding
        assert n_pad == n and lanes < n


def test_spec_round_descent_deep_low_rank_matches_float64():
    """Late in a draw the projector is low rank and orthogonal to where
    the nodes hold most of their weight, so <Q, node> cancels: here rank 2
    against rows whose variance is 10^4 times larger along one direction
    the projector removes, over a depth-12 tree.  The kernel, scoring both
    children of every node, makes every decision a float64 descent makes.
    The carried oracle (``descend_ref``: the parent's mass carried down as
    p_all - p_left) keeps the rounding of the root's score in the mass, so
    on the float64 path its ratios stray by about 2^level times the pair
    rule's, and it chooses other blocks.  Uniforms lie in [0.6, 1), so
    the paths turn right, where the carried mass is subtracted."""
    depth, block, r, n = 12, 4, 8, 64
    rng = np.random.default_rng(12)
    f = rng.normal(size=r)
    f /= np.linalg.norm(f)
    w = (100.0 * rng.normal(size=((1 << depth) * block, 1)) * f
         + rng.normal(size=((1 << depth) * block, r))).astype(np.float32)
    wb = w.reshape(-1, block, r)
    nodes = np.einsum("nbi,nbj->nij", wb, wb).astype(np.float32)
    lv32, lv64 = [nodes], [nodes.astype(np.float64)]
    for _ in range(depth):
        lv32.append(lv32[-1][0::2] + lv32[-1][1::2])
        lv64.append(lv64[-1][0::2] + lv64[-1][1::2])
    lv32 = tuple(jnp.asarray(x) for x in reversed(lv32))
    lv64 = lv64[::-1]
    proj = []
    for _ in range(n):
        x = rng.normal(size=(r, 2))
        x -= np.outer(f, f @ x)
        basis = np.linalg.qr(x)[0]
        proj.append(basis @ basis.T)
    q = jnp.asarray(np.stack(proj), jnp.float32)
    q64 = np.asarray(q, np.float64)
    us = rng.uniform(0.6, 1.0, size=(n, depth)).astype(np.float32)

    idx = np.zeros(n, np.int64)            # float64 descent: p_left / p_parent
    path, ratio64 = [idx], []
    for lvl in range(1, depth + 1):
        ratio64.append(np.einsum("nij,nij->n", q64, lv64[lvl][2 * idx])
                       / np.einsum("nij,nij->n", q64, lv64[lvl - 1][idx]))
        idx = 2 * idx + (us[:, lvl - 1] > ratio64[-1])
        path.append(idx)
    ratio64 = np.stack(ratio64, 1)

    flat = spops.descent_operands(lv32, force_interpret=True)
    blk = spops.descend(lv32, flat, q, jnp.asarray(us), force_interpret=True)
    np.testing.assert_array_equal(np.asarray(blk), idx)

    # float32 ratios of both rules on the float64 path
    p_all = jnp.einsum("ij,nij->n", lv32[0][0], q)
    carried, pair = [], []
    for lvl in range(1, depth + 1):
        left = jnp.asarray(2 * path[lvl - 1])
        p_left = jnp.einsum("nij,nij->n", q, lv32[lvl][left])
        p_right = jnp.einsum("nij,nij->n", q, lv32[lvl][left + 1])
        carried.append(p_left / p_all)
        pair.append(p_left / (p_left + p_right))
        p_all = jnp.maximum(jnp.where(jnp.asarray(path[lvl]) == left,
                                      p_left, p_all - p_left), 0.0)
    stray_carried = np.abs(np.stack(carried, 1) - ratio64).max(axis=0)
    stray_pair = np.abs(np.stack(pair, 1) - ratio64).max(axis=0)
    assert stray_pair.max() < 1e-3          # 9.8e-5 on this input
    assert stray_carried[-1] > 0.1          # 0.44 on this input
    assert (stray_carried[-4:] > 100 * stray_pair[-4:]).all()
    assert (np.asarray(descend_ref(lv32, q, jnp.asarray(us))) != idx).any()


def test_spec_round_shallow_max_matches_tree():
    """The oracle's shallow/deep level classifier must agree with
    core.tree's, or the fused path and the sharded descent would walk the
    same tree with different stacked-matmul layouts."""
    from repro.core import tree as core_tree
    from repro.kernels.spec_round import ref as spref

    assert spref._SHALLOW_MAX == core_tree._SHALLOW_MAX


#: subsets per size in the acceptance batch, and the sizes it cycles over
_LOGDET_SIZES = (0, 1, 37, 65, 100)
#: the matrix with a zeroed live row, and the one with two rows exchanged
_ZEROED, _SWAPPED = 7, 1000 + 8


@pytest.fixture(scope="module")
def acceptance_batch():
    """L_Y and Lhat_Y, built as the acceptance test builds them, of 1,000
    subsets of a seeded ``synthetic_features`` catalog at R = 100 (|Y|
    cycling over ``_LOGDET_SIZES``, the live rows a prefix): 2,000 float32
    matrices, L_Y first.  A live row of ``_ZEROED`` (an L_Y, |Y| = 37) is
    zeroed, as a deleted item's row is; two rows of ``_SWAPPED`` (an
    Lhat_Y, |Y| = 37, positive definite) are exchanged.  With the kernel's
    results on the whole batch, in order."""
    from repro.core.youla import spectral_from_params
    from repro.data.baskets import synthetic_features

    sp = spectral_from_params(*synthetic_features(256, 50, seed=5))
    rng = np.random.default_rng(18)
    k, n_sub = 100, 1000
    sizes = np.resize(_LOGDET_SIZES, n_sub)
    mask = np.arange(k)[None, :] < sizes[:, None]
    items = np.stack([rng.choice(256, k, replace=False) for _ in sizes])
    zy = np.asarray(sp.Z, np.float64)[items] * mask[..., None]
    pad = (~mask)[:, :, None] * np.eye(k)
    l_y = zy @ np.asarray(sp.x_matrix(), np.float64) @ zy.transpose(0, 2, 1)
    lhat_y = (zy * np.asarray(sp.x_diag_hat(), np.float64)) \
        @ zy.transpose(0, 2, 1)
    a = np.concatenate([l_y + pad, lhat_y + pad]).astype(np.float32)
    a[_ZEROED, 5] = 0.0
    a[_SWAPPED, [0, 1]] = a[_SWAPPED, [1, 0]]
    n_live = np.concatenate([sizes, sizes])
    sign, logdet = slops.slogdet_batched(jnp.asarray(a), jnp.asarray(n_live),
                                         force_interpret=True)
    return a, n_live, np.asarray(sign), np.asarray(logdet)


@pytest.mark.parametrize("n", [1, 513, 2000])
def test_slogdet_batched_matches_oracles(acceptance_batch, n):
    """The lane-batched elimination on ``n`` matrices of the acceptance
    batch, drawn in a seeded order: each matrix's sign and log |det| are
    bit for bit its results in the whole batch (whatever its lane, block
    or batch size: the sharded and plain rounds rely on that); they equal
    float64 LAPACK's within 1e-6 x cond(A) (7.4e-8 x cond on this batch;
    float32 LAPACK 4.9e-8 x cond), the signs wherever cond(A) < 1e5 (a
    full |Y| = 100 L_Y reaches 2e9), and the jnp rule
    ``_slogdet_width_invariant`` where it is finite."""
    from repro.core.learning import _slogdet_width_invariant

    a, n_live, sign_all, logdet_all = acceptance_batch
    idx = np.random.default_rng(n).permutation(len(a))[:n]
    if n == 1:
        idx = np.array([_SWAPPED])
    sign, logdet = slops.slogdet_batched(
        jnp.asarray(a[idx]), jnp.asarray(n_live[idx]), force_interpret=True)
    sign, logdet = np.asarray(sign), np.asarray(logdet)
    np.testing.assert_array_equal(sign, sign_all[idx])
    np.testing.assert_array_equal(logdet.view(np.int32),
                                  logdet_all[idx].view(np.int32))

    a64 = a[idx].astype(np.float64)
    sign64, logdet64 = np.linalg.slogdet(a64)
    cond = np.linalg.cond(a64)
    live = sign64 != 0
    assert np.all(np.abs(logdet[live] - logdet64[live]) <= 1e-6 * cond[live])
    steady = cond < 1e5
    np.testing.assert_array_equal(sign[steady], sign64[steady])
    sign_j, logdet_j = _slogdet_width_invariant(jnp.asarray(a[idx]))
    fin = np.isfinite(np.asarray(logdet_j))
    np.testing.assert_allclose(logdet[fin], np.asarray(logdet_j)[fin],
                               rtol=1e-6, atol=1e-5)
    np.testing.assert_array_equal(sign[fin], np.asarray(sign_j)[fin])


def test_slogdet_batched_zero_pivot_and_negative_det(acceptance_batch):
    """A zeroed live row gives sign 0 and log |det| -inf (a deleted item
    is rejected; the jnp rule gives NaN there), and exchanging two rows
    of a positive definite Lhat_Y gives sign -1; an all-identity matrix
    (|Y| = 0) gives sign 1, log |det| 0."""
    _, n_live, sign, logdet = acceptance_batch
    assert sign[_ZEROED] == 0.0 and logdet[_ZEROED] == -np.inf
    assert sign[_SWAPPED] == -1.0 and np.isfinite(logdet[_SWAPPED])
    empty = n_live == 0
    assert np.all(sign[empty] == 1.0) and np.all(logdet[empty] == 0.0)


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
