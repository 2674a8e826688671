"""A run on the CPU with the timed path broken underneath: the comparison
has to read it as not correct.  The look for a chip is skipped; the rest
of the run is the benchmark's own.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import dataclasses
import functools
import time

import numpy as np

from tiny import CELL, tiny_root

import repro.serve.sampler_engine as se
from harness import run_cell


def _run(tmp_path, seed=7):
    return run_cell(CELL, seed, 2.0, False, t0=time.perf_counter(),
                    require_chip=False, root=tiny_root(tmp_path),
                    log=lambda m: None)


def test_sound_run_is_correct(tmp_path):
    out = _run(tmp_path)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_altered_answer(tmp_path, monkeypatch):
    """One item of every answer changed where the engine produces it."""
    orig = se.SamplerEngine._retire

    def altered(self, slot, result):
        items = np.array(result.items)
        if result.mask.any():
            items[0] = (items[0] + 1) % self.sp.M
        return orig(self, slot, dataclasses.replace(result, items=items))

    monkeypatch.setattr(se.SamplerEngine, "_retire", altered)
    assert not _run(tmp_path)["correct"]


def test_half_the_lanes_left_out(tmp_path, monkeypatch):
    """The second half of every slot's proposal lanes carry the first
    half's items: half of the batch was never drawn."""
    orig = se._spec_round_fused

    def half(sampler, keys, trials, *, n_spec):
        items, mask, acc = orig(sampler, keys, trials, n_spec=n_spec)
        h = n_spec // 2
        items = items.reshape(-1, n_spec, items.shape[-1])
        mask = mask.reshape(items.shape)
        items = items.at[:, h:].set(items[:, :h]).reshape(-1, items.shape[-1])
        mask = mask.at[:, h:].set(mask[:, :h]).reshape(items.shape)
        return items, mask, acc

    monkeypatch.setattr(se, "_spec_round_fused", half)
    assert not _run(tmp_path)["correct"]


def test_half_the_lanes_skipped(tmp_path, monkeypatch):
    """The second half of every slot's proposal lanes is never tested and
    counts as rejected: the first accepted proposal is passed over."""
    orig = se._spec_round_fused

    def skipped(sampler, keys, trials, *, n_spec):
        items, mask, acc = orig(sampler, keys, trials, n_spec=n_spec)
        acc = acc.reshape(-1, n_spec).at[:, n_spec // 2:].set(False)
        return items, mask, acc.reshape(-1)

    monkeypatch.setattr(se, "_spec_round_fused", skipped)
    assert not _run(tmp_path)["correct"]


def test_raised_acceptance(tmp_path, monkeypatch):
    """The log-det ratio reads one nat high, so the acceptance test passes
    proposals it should reject."""
    import jax
    import jax.numpy as jnp

    import repro.core.rejection as rej

    @functools.partial(jax.jit, static_argnames=("n_spec",))
    def raised(sampler, keys, trials, *, n_spec):
        keys = rej._fanout_traced(keys, trials,
                                  jnp.arange(n_spec, dtype=jnp.uint32))
        ks = jax.vmap(jax.random.split)(keys)
        items, mask = rej.sample_proposal_dpp_batch(sampler.tree, ks[:, 0])
        log_ratio, _ = rej.log_det_ratio_batch(sampler.sp, items, mask)
        u = jax.vmap(lambda k: jax.random.uniform(k))(ks[:, 1])
        return items, mask, jnp.log(u) <= log_ratio + 1.0

    monkeypatch.setattr(se, "_spec_round_fused", raised)
    assert not _run(tmp_path)["correct"]


def test_stale_answer(tmp_path, monkeypatch):
    """A slot hands out its previous draw again (state left unchanged)."""
    orig = se.SamplerEngine._retire
    last = {}

    def stale(self, slot, result):
        prev = last.get((id(self), slot))
        last[(id(self), slot)] = result
        return orig(self, slot, result if prev is None else dataclasses.replace(
            result, items=prev.items, mask=prev.mask))

    monkeypatch.setattr(se.SamplerEngine, "_retire", stale)
    assert not _run(tmp_path)["correct"]
