"""``chip_smoke.py``'s phases on CPU at a small catalog, kernels interpreted.

The script itself refuses to run without a TPU; these tests drive its
phase functions directly so a change that breaks the chip smoke fails
here first.  ``REPRO_PALLAS_INTERPRET=1`` sends every op through its
Pallas kernel in the interpreter, and the jit caches are cleared around
each test so nothing traced on the jnp path is reused.
"""
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_serve_phase_interpret(smoke, interpret):
    """Catalog, two pools behind the front door, host checks: the smoke's
    main path at M = 2^10, K = 16."""
    m = 1 << 10
    sampler, pre_s = smoke.build_catalog(m, 16)
    assert pre_s > 0 and sampler.tree.depth == 4
    expect = float(smoke.det_ratio_exact(sampler.sp))
    max_trials = int(smoke.BUDGET_FACTOR * np.ceil(expect))
    srv = smoke.serve(sampler, n_rej=4, n_mcmc=2, max_trials=max_trials)
    rej = list(srv["warm"]["rej"][0]) + srv["window"]["rej"]
    mcmc = list(srv["warm"]["mcmc"][0]) + srv["window"]["mcmc"]
    assert len(rej) == smoke.REJ_SLOTS + 4
    assert len(mcmc) == smoke.MCMC_SLOTS + 2
    assert all(r.accepted for r in rej)
    smoke.check_draws(sampler, rej)
    smoke.check_draws(sampler, mcmc, size=smoke.MCMC_K)
    assert srv["window_compiles"] == 0
    assert set(srv["compile"]) == {"rej", "mcmc"}
    assert all(c["n"] > 0 and c["s"] > 0 for c in srv["compile"].values())
    # interpreted kernels lower to plain HLO: the names only exist on TPU
    assert smoke.tick_kernels(srv["pools"]) == {
        "rej_tick": [], "mcmc_tick": [], "mcmc_init": []}


def test_exactness_phase_interpret(smoke, interpret):
    """The on-chip exactness phase, with the descent kernel interpreted."""
    out = smoke.exactness(n_draws=4000)
    assert out["kernel_path"] and out["depth"] == 2
    assert out["tv"] < 0.08


def test_trials_band_from_sampling_noise(smoke):
    """1 -/+ 4 relative standard deviations of a mean of n geometric
    trial counts."""
    lo, hi = smoke.trials_band(24)
    assert np.isclose(lo, 1 - 4 / np.sqrt(24))
    assert np.isclose(hi, 1 + 4 / np.sqrt(24))
    assert smoke.trials_band(400) == pytest.approx((0.8, 1.2))


def test_pallas_kernels_parses_compiled_hlo(smoke):
    text = ('%ndpp_tree_descent.3 = s32[2,1,16]{2,1,0} custom-call(%a), '
            'custom_call_target="tpu_custom_call", backend_config={}\n'
            '%fusion.1 = f32[8]{0} fusion(%b), kind=kLoop\n'
            '%ndpp_bilinear_batched = f32[4,1,64]{2,1,0} custom-call(%c), '
            'custom_call_target="tpu_custom_call"\n')
    assert smoke.pallas_kernels(text) == ["ndpp_bilinear_batched",
                                          "ndpp_tree_descent"]


@pytest.mark.parametrize("interp", ["0", "1"])
def test_main_refuses_cpu_and_interpret(smoke, monkeypatch, capsys, interp):
    """No TPU, or the interpreter requested: non-zero, no verdict line."""
    if interp == "1":
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(smoke, "enable_compile_cache", lambda: "unused")
    with pytest.raises(RuntimeError, match="REPRO_PALLAS_INTERPRET|no TPU"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins; otherwise <repo>/.jax_cache."""
    from repro.launch import cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert cache.compile_cache_dir() == "/elsewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cache.compile_cache_dir() == str(ROOT / ".jax_cache")
