"""Sharded-vs-single-device equality for the mesh sampler backends.

The sharding design invariant (docs/sharding.md): a mesh changes where
the (M, R) rows live, never what is sampled.  Cross-shard combination is
always a psum in which exactly one shard holds the value and every other
shard holds an exact 0.0, so sharded draws must be BIT-identical to the
single-device draws — these tests assert exact array equality, not
closeness.

In-process tests run on a 1-device ("model",) mesh (the full shard_map
machinery — specs, masking, psums — with S = 1).  The 2-simulated-device
cases need ``XLA_FLAGS=--xla_force_host_platform_device_count=2`` set
before jax initializes, so they run in a subprocess: bit-equality for
the sharded tree descent / rejection round / MCMC chains and greedy
chain starts, plus
distribution-equality of the sharded rejection sampler against the
enumerated target (the ``tests/_exactness.py`` chi-square bar).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import (
    init_empty,
    init_greedy,
    preprocess,
    run_chains,
    run_chains_sharded,
    sample_batched_many,
    shard_sampler,
    shard_tree,
    sample_proposal_dpp_batch,
    sample_proposal_dpp_batch_sharded,
)
from repro.kernels.bilinear import ops as bops
from repro.kernels.mcmc_score import ops as mops
from repro.serve.sampler_engine import SampleRequest, SamplerEngine

M, K = 256, 4


@pytest.fixture(scope="module")
def sampler():
    # module-local RNG so the session rng fixture's draw sequence (and the
    # MC tolerances downstream of it) is unchanged
    rng = np.random.default_rng(2024)
    v = jnp.asarray(rng.normal(size=(M, K)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=(M, K)) * 0.1, jnp.float32)
    d = jnp.asarray(rng.normal(size=(K, K)), jnp.float32)
    # block=4 -> 64 leaf blocks: the 64-node level shards even on 1 device
    return preprocess(v, b, d, block=4)


@pytest.fixture(scope="module")
def mesh1():
    return Mesh(np.asarray(jax.devices()[:1]), ("model",))


def test_tree_descent_sharded_bit_equal(sampler, mesh1):
    """Sharded batched tree descent == plain descent, bit for bit, on a
    1-device mesh (shard_map + masking + psum path)."""
    from repro.core import tree_shard_specs

    keys = jax.random.split(jax.random.PRNGKey(0), 16)
    it0, mk0 = jax.jit(sample_proposal_dpp_batch)(sampler.tree, keys)
    st = shard_tree(sampler.tree, mesh1)
    # the deep 64-node level (and W) must actually be sharded, not replicated
    specs = tree_shard_specs(sampler.tree, mesh1)
    assert specs.levels[-1] == jax.sharding.PartitionSpec("model", None, None)
    assert specs.W == jax.sharding.PartitionSpec("model", None)
    it1, mk1 = sample_proposal_dpp_batch_sharded(st, keys, mesh1)
    assert np.array_equal(np.asarray(it0), np.asarray(it1))
    assert np.array_equal(np.asarray(mk0), np.asarray(mk1))


def test_score_all_sharded_bit_equal(mesh1):
    z = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
    a = jax.random.normal(jax.random.PRNGKey(2), (3, 8, 8))
    s0 = mops.score_all(z, a)
    s1 = mops.score_all_sharded(z, a, mesh1)
    assert np.array_equal(np.asarray(s0), np.asarray(s1))


def test_score_argmax_sharded_matches_dense(mesh1):
    z = jax.random.normal(jax.random.PRNGKey(3), (64, 8))
    a = jax.random.normal(jax.random.PRNGKey(4), (5, 8, 8))
    s0 = mops.score_all(z, a)
    mx, ai = mops.score_argmax_sharded(z, a, mesh1)
    assert np.array_equal(np.asarray(ai), np.asarray(s0.argmax(1)))
    assert np.array_equal(np.asarray(mx), np.asarray(s0.max(1)))


def test_bilinear_sharded_bit_equal(mesh1):
    z = jax.random.normal(jax.random.PRNGKey(5), (64, 8))
    w = jax.random.normal(jax.random.PRNGKey(6), (8, 8))
    assert np.array_equal(np.asarray(bops.bilinear(z, w)),
                          np.asarray(bops.bilinear_sharded(z, w, mesh1)))


def test_rejection_sharded_bit_equal(sampler, mesh1):
    """sample_batched_many(mesh=) == plain: items, mask, trials, accepted."""
    res0 = sample_batched_many(sampler, jax.random.PRNGKey(7), 32, n_spec=4)
    sh = shard_sampler(sampler, mesh1)
    res1 = sample_batched_many(sh, jax.random.PRNGKey(7), 32, n_spec=4,
                               mesh=mesh1)
    for f in ("items", "mask", "trials", "accepted"):
        assert np.array_equal(np.asarray(getattr(res0, f)),
                              np.asarray(getattr(res1, f))), f


def test_mcmc_sharded_bit_equal(sampler, mesh1):
    """run_chains_sharded == run_chains: identical trajectories."""
    sp = sampler.sp
    keys = jax.random.split(jax.random.PRNGKey(8), 4)
    states = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (4,) + a.shape), init_empty(sp))
    _, it0, mk0, ac0 = run_chains(sp, keys, states, n_steps=96)
    sh = shard_sampler(sampler, mesh1)
    _, it1, mk1, ac1 = run_chains_sharded(sh.sp, keys, states, mesh=mesh1,
                                          n_steps=96)
    assert np.array_equal(np.asarray(it0), np.asarray(it1))
    assert np.array_equal(np.asarray(mk0), np.asarray(mk1))
    assert np.array_equal(np.asarray(ac0), np.asarray(ac1))


def test_init_greedy_sharded_bit_equal(sampler, mesh1):
    """Greedy size-k starts with the rows sharded (each shard scores its
    own rows) == the unsharded starts."""
    key = jax.random.PRNGKey(9)
    st0 = init_greedy(sampler.sp, key, 4, 3)
    sh = shard_sampler(sampler, mesh1)
    st1 = init_greedy(sh.sp, key, 4, 3, mesh=mesh1)
    assert np.array_equal(np.asarray(st0.items), np.asarray(st1.items))
    assert np.array_equal(np.asarray(st0.mask), np.asarray(st1.mask))
    assert np.array_equal(np.asarray(st0.minv), np.asarray(st1.minv))


def test_engine_mesh_parity(sampler, mesh1):
    """SamplerEngine(mesh=) retires every request with the exact result
    the meshless engine produces, for both backends."""
    def drain(mesh, backend, **kw):
        eng = SamplerEngine(sampler, n_slots=3, mesh=mesh, backend=backend,
                            **kw)
        for i in range(7):
            eng.submit(SampleRequest(rid=i, seed=100 + i))
        return eng.run()

    for backend, kw in (("rejection", dict(n_spec=4)),
                        ("mcmc", dict(mcmc_burn_in=32, mcmc_thin=8)),
                        ("mcmc", dict(mcmc_k=3, mcmc_burn_in=32,
                                      mcmc_thin=8))):
        o0 = drain(None, backend, **kw)
        o1 = drain(mesh1, backend, **kw)
        assert sorted(o0) == sorted(o1) == list(range(7))
        for i in o0:
            assert np.array_equal(o0[i].items, o1[i].items), (backend, i)
            assert np.array_equal(o0[i].mask, o1[i].mask), (backend, i)
            assert o0[i].trials == o1[i].trials, (backend, i)


_TWO_DEV_SCRIPT = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh

    assert len(jax.devices()) == 2, jax.devices()
    mesh = Mesh(np.asarray(jax.devices()), ("model",))

    from repro.core import (init_empty, init_greedy, preprocess,
                            run_chains, run_chains_sharded,
                            sample_batched_many, shard_sampler)
    from repro.core.types import NDPPParams, dense_l
    from _exactness import (assert_chi_square_close, enumerate_subset_probs,
                            histogram)

    # --- bit-equality on a catalog big enough to shard deep tree levels ---
    rng = np.random.default_rng(2024)
    v = jnp.asarray(rng.normal(size=(256, 4)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=(256, 4)) * 0.1, jnp.float32)
    d = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    sampler = preprocess(v, b, d, block=4)
    res0 = sample_batched_many(sampler, jax.random.PRNGKey(0), 32, n_spec=4)
    sh = shard_sampler(sampler, mesh)
    # the deep levels and W really are split: half the rows per device
    assert sh.tree.W.addressable_shards[0].data.shape[0] * 2 \\
        == sh.tree.W.shape[0]
    res1 = sample_batched_many(sh, jax.random.PRNGKey(0), 32, n_spec=4,
                               mesh=mesh)
    for f in ("items", "mask", "trials", "accepted"):
        a0, a1 = np.asarray(getattr(res0, f)), np.asarray(getattr(res1, f))
        assert np.array_equal(a0, a1), f
    print("rejection 2-dev bit-equality ok")

    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    states = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (4,) + a.shape), init_empty(sampler.sp))
    _, it0, mk0, ac0 = run_chains(sampler.sp, keys, states, n_steps=96)
    _, it1, mk1, ac1 = run_chains_sharded(sh.sp, keys, states, mesh=mesh,
                                          n_steps=96)
    assert np.array_equal(np.asarray(it0), np.asarray(it1))
    assert np.array_equal(np.asarray(mk0), np.asarray(mk1))
    assert np.array_equal(np.asarray(ac0), np.asarray(ac1))
    print("mcmc 2-dev bit-equality ok")

    g0 = init_greedy(sampler.sp, jax.random.PRNGKey(2), 4, 3)
    g1 = init_greedy(sh.sp, jax.random.PRNGKey(2), 4, 3, mesh=mesh)
    assert np.array_equal(np.asarray(g0.items), np.asarray(g1.items))
    assert np.array_equal(np.asarray(g0.minv), np.asarray(g1.minv))
    print("greedy init 2-dev bit-equality ok")

    # --- distribution equality of the sharded rejection sampler ----------
    # tiny ground set -> exact target by enumeration, chi-square bar
    rng = np.random.default_rng(7)
    v = jnp.asarray(rng.normal(size=(8, 4)) * 0.6, jnp.float32)
    b = jnp.asarray(rng.normal(size=(8, 4)) * 0.6, jnp.float32)
    d = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    params = NDPPParams(v, b, d)
    small = shard_sampler(preprocess(v, b, d, block=2), mesh)
    n = 4000
    res = sample_batched_many(small, jax.random.PRNGKey(3), n, n_spec=4,
                              mesh=mesh)
    assert bool(np.asarray(res.accepted).all())
    probs = enumerate_subset_probs(dense_l(params))
    emp = histogram(res.items, res.mask)
    assert set(emp) <= set(probs)
    assert_chi_square_close(emp, probs, n)
    print("sharded rejection chi-square ok")
    print("SHARDED-2DEV-OK")
""")


def test_sharded_two_simulated_devices():
    """Run the 2-device checks in a subprocess (the host device count must
    be forced before jax initializes): sharded tree/rejection/MCMC are
    bit-identical to single-device, and the sharded rejection sampler
    passes the chi-square exactness bar against the enumerated target."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join(
            [os.path.join(root, "src"), os.path.join(root, "tests")]
            + ([env_p] if (env_p := env.get("PYTHONPATH")) else [])),
    )
    proc = subprocess.run(
        [sys.executable, "-c", _TWO_DEV_SCRIPT], env=env, cwd=root,
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "SHARDED-2DEV-OK" in proc.stdout, proc.stdout


_ONE_RULE_SCRIPT = textwrap.dedent("""
    import functools, json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    assert len(jax.devices()) == 2, jax.devices()
    mesh = Mesh(np.asarray(jax.devices()), ("model",))

    from repro.core import preprocess, shard_sampler, shard_tree
    from repro.core.dynamic import build_dual_proposal
    from repro.core.rejection import _spec_round_fused
    from repro.core.tree import (_descend_batch, construct_tree,
                                 shard_spectral, tree_shard_specs)
    from repro.kernels.spec_round.ref import descend_ref

    out = {}

    def descent_case(name, w, q, us, block):
        tree = construct_tree(jnp.zeros(w.shape[1]), jnp.asarray(w), block)
        specs = tree_shard_specs(tree, mesh)
        assert any(s != P(None, None, None) for s in specs.levels), name
        one = jax.jit(descend_ref)(tree.levels, q, us)
        f = jax.shard_map(
            functools.partial(_descend_batch, axis_name="model"), mesh=mesh,
            in_specs=(specs, P(), P()), out_specs=P(), check_vma=False)
        two = jax.jit(f)(shard_tree(tree, mesh), q, us)
        out[name] = bool(np.array_equal(np.asarray(one), np.asarray(two)))

    rng = np.random.default_rng(6)
    for depth in (6, 8):                 # levels 6.. hold > 32 nodes
        r, n = 8, 64
        w = rng.normal(size=((1 << depth) * 4, r)).astype(np.float32)
        qh = rng.normal(size=(n, r, r)).astype(np.float32)
        q = jnp.asarray(np.einsum("nik,njk->nij", qh, qh) / r)
        us = jnp.asarray(rng.uniform(size=(n, depth)), jnp.float32)
        descent_case(f"descent-depth{depth}", w, q, us, 4)

    # depth 12, low rank: a rank-2 projector orthogonal to the direction
    # that holds 10^4 of the rows' variance (test_kernels' deep case)
    depth, r, n = 12, 8, 64
    rng = np.random.default_rng(12)
    f = rng.normal(size=r)
    f /= np.linalg.norm(f)
    w = (100.0 * rng.normal(size=((1 << depth) * 4, 1)) * f
         + rng.normal(size=((1 << depth) * 4, r))).astype(np.float32)
    proj = []
    for _ in range(n):
        x = rng.normal(size=(r, 2))
        x -= np.outer(f, f @ x)
        basis = np.linalg.qr(x)[0]
        proj.append(basis @ basis.T)
    q = jnp.asarray(np.stack(proj), jnp.float32)
    us = jnp.asarray(rng.uniform(0.6, 1.0, size=(n, depth)), jnp.float32)
    descent_case("descent-deep-low-rank", w, q, us, 4)

    # one round on a mesh against one device: 1024 items, block 4, so
    # levels 6-8 and W are split across the two devices
    rng = np.random.default_rng(2024)
    v = jnp.asarray(rng.normal(size=(1024, 4)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=(1024, 4)) * 0.1, jnp.float32)
    d = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    sampler = preprocess(v, b, d, block=4)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(0), 8))
    trials = np.arange(8, dtype=np.uint32) * 3

    def round_case(name, one, two, one_live=None, two_live=None):
        a = _spec_round_fused(one, keys, trials, n_spec=8, live=one_live)
        c = _spec_round_fused(two, keys, trials, n_spec=8, live=two_live,
                              mesh=mesh)
        out[name] = all(np.array_equal(np.asarray(x), np.asarray(y))
                        for x, y in zip(a, c))

    sh = shard_sampler(sampler, mesh)
    assert sh.tree.W.addressable_shards[0].data.shape[0] * 2 \\
        == sh.tree.W.shape[0]
    round_case("round-static", sampler, sh)
    sp = sampler.sp
    round_case("round-dual", build_dual_proposal(sp, block=4),
               build_dual_proposal(sp, block=4, mesh=mesh),
               sp, shard_spectral(sp, mesh))
    print("ONE-RULE-JSON:" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def one_rule_two_devices():
    """Each case of the 2-simulated-device script below: True where the
    mesh result equals the one-device result bit for bit."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join(
            [os.path.join(root, "src")]
            + ([env_p] if (env_p := env.get("PYTHONPATH")) else [])),
    )
    proc = subprocess.run(
        [sys.executable, "-c", _ONE_RULE_SCRIPT], env=env, cwd=root,
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("ONE-RULE-JSON:"))
    return json.loads(line[len("ONE-RULE-JSON:"):])


@pytest.mark.parametrize("case", ["descent-depth6", "descent-depth8",
                                  "descent-deep-low-rank"])
def test_descend_batch_two_devices_equals_descend_ref(one_rule_two_devices,
                                                      case):
    """The sharded descent (``core.tree._descend_batch``, levels past
    ``_SHALLOW_MAX`` split over 2 devices) walks every lane to the block
    the one-device oracle ``descend_ref`` picks, bit for bit, including on
    the depth-12 tree whose projector cancels 10^4 to 1."""
    assert one_rule_two_devices[case]


@pytest.mark.parametrize("case", ["round-static", "round-dual"])
def test_spec_round_fused_mesh_equals_one_device(one_rule_two_devices, case):
    """``_spec_round_fused(mesh=)`` over 2 devices returns the one-device
    round's items, masks and accept flags bit for bit: for a static
    sampler, and for an unmutated ``DualProposal`` scored against its live
    kernel (``live=``)."""
    assert one_rule_two_devices[case]
