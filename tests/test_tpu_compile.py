"""Ahead-of-time compiles of the sampling path's Pallas kernels for a
described TPU v5e, at the widths ``chip_smoke.py`` runs.

Nothing runs: the TPU compiler (installed with JAX) compiles each kernel
for a chip that is described, not attached, and raises what the chip's
compiler would raise — a block that breaks the (8, 128) tiling rule, or
more VMEM than a kernel may use.  Interpret mode shows neither.

Two widths, each laid out as the ops wrappers pass it:

* catalog — M = 2^20 items at rank R = 100, leaf block 64 (tree depth
  14), 8 slots x 64 speculative proposals = 512 lanes, 16 MCMC chains;
* exactness — M = 8, R = 4, leaf block 2 (depth 2), 500 requests x 4
  proposals = 2000 lanes: the smoke's on-chip distribution check.

The ops wrappers take their CPU branch here, so the kernel tests call
the ``*_pallas`` functions directly; the sharded-path test tells the ops
wrappers they are on a TPU instead, and compiles whole jitted programs
over all four chips of the described host.  The topology is described
inside a fixture only: the TPU library may be loaded by one process at a
time, and every test worker imports this file.
"""
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import mcmc as mcmc_core
from repro.core.rejection import NDPPSampler, _spec_round_fused
from repro.core.tree import construct_tree, proposal_eigens, tree_shard_specs
from repro.core.types import SpectralNDPP
from repro.kernels.bilinear import ops as bilinear_ops
from repro.kernels.bilinear.bilinear import bilinear_batched_pallas
from repro.kernels.mcmc_score import ops as mcmc_score_ops
from repro.kernels.mcmc_score.mcmc_score import score_all_pallas
from repro.kernels.slogdet import ops as slogdet_ops
from repro.kernels.slogdet.slogdet import slogdet_pallas
from repro.kernels.spec_round import ops as spec_round_ops
from repro.kernels.spec_round.spec_round import descend_pallas
from repro.kernels.tree_sum.tree_sum import (
    block_outer_sums_pallas,
    gathered_block_grams_pallas,
)


class Width(NamedTuple):
    m: int
    r: int
    block: int
    lanes: int
    chains: int

    @property
    def depth(self) -> int:
        return (self.m // self.block).bit_length() - 1

    @property
    def r_pad(self) -> int:          # tree_sum / score_all lane padding
        return -(-self.r // 128) * 128


WIDTHS = {
    "catalog": Width(m=1 << 20, r=100, block=64, lanes=8 * 64, chains=16),
    "exactness": Width(m=8, r=4, block=2, lanes=500 * 4, chains=4),
}


@pytest.fixture(scope="module")
def v5e_devices():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(v5e_devices):
    return SingleDeviceSharding(v5e_devices[0])


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("width", WIDTHS)
def test_descend_compiles(one_chip, width):
    """The HBM-resident tree descent: levels below the first stay in HBM,
    both children of each lane's node are DMA'd per level as one pair, so
    a 2^14-block tree compiles, with the lane groups ``descent_lanes``
    picks for the width's slab size."""
    w = WIDTHS[width]
    s = spec_round_ops.flat_nodes(jnp.zeros((1, w.r, w.r))).shape[1]
    lanes, group = spec_round_ops.descent_lanes(w.lanes, s)
    n = -(-w.lanes // lanes) * lanes
    levels = tuple(_shape(one_chip, (1 << lvl, s, 128))
                   for lvl in range(1, w.depth + 1))
    c = descend_pallas.lower(
        levels, _shape(one_chip, (n, s, 128)),
        _shape(one_chip, (n, w.depth)), lanes=lanes, group=group,
    ).compile()
    assert _kernels(c) == 1


@pytest.mark.parametrize("width", WIDTHS)
def test_bilinear_batched_compiles(one_chip, width):
    """Leaf-block scoring: one (block, R) x (R, R) form per lane."""
    w = WIDTHS[width]
    c = bilinear_batched_pallas.lower(
        _shape(one_chip, (w.lanes, w.block, w.r)),
        _shape(one_chip, (w.lanes, w.r, w.r)),
    ).compile()
    assert _kernels(c) == 1


@pytest.mark.parametrize("width", WIDTHS)
def test_score_all_compiles(one_chip, width):
    """MCMC all-candidate scores: every item against every chain."""
    w = WIDTHS[width]
    c = score_all_pallas.lower(
        _shape(one_chip, (max(w.m, 8), w.r_pad)),
        _shape(one_chip, (w.chains, w.r_pad, w.r_pad)),
        block_m=min(512, max(w.m, 8)),
    ).compile()
    assert _kernels(c) == 1


@pytest.mark.parametrize("width", WIDTHS)
def test_block_outer_sums_compiles(one_chip, width):
    """Leaf-level Grams of tree construction, any leaf block size."""
    w = WIDTHS[width]
    c = block_outer_sums_pallas.lower(
        _shape(one_chip, (w.m, w.r_pad)), block=w.block).compile()
    assert _kernels(c) == 1


@pytest.mark.parametrize("width", WIDTHS)
def test_gathered_block_grams_compiles(one_chip, width):
    """Leaf Grams recomputed for 16 updated blocks (catalog updates)."""
    w = WIDTHS[width]
    c = gathered_block_grams_pallas.lower(
        _shape(one_chip, (w.m, w.r_pad)), _shape(one_chip, (16,), jnp.int32),
        block=w.block).compile()
    assert _kernels(c) == 1


@pytest.mark.parametrize("width", WIDTHS)
def test_slogdet_compiles(one_chip, width):
    """The acceptance test's log-dets: L_Y and Lhat_Y of every lane, one
    matrix per vector lane, k = R rows on the leading dim and R columns
    on sublanes; the block's working copy must fit the VMEM limit."""
    w = WIDTHS[width]
    n = -(-2 * w.lanes // slogdet_ops.LANES) * slogdet_ops.LANES
    t = -(-w.r // 8)
    c = slogdet_pallas.lower(
        _shape(one_chip, (w.r, t, 8, n)),
        _shape(one_chip, (n // slogdet_ops.LANES,), jnp.int32),
    ).compile()
    assert _kernels(c) == 1


def _sampler_shapes(w: Width):
    def build(z, sigma):
        sp = SpectralNDPP(Z=z, sigma=sigma)
        return NDPPSampler(sp=sp, tree=construct_tree(*proposal_eigens(sp),
                                                      block=w.block))

    return jax.eval_shape(
        build, jax.ShapeDtypeStruct((w.m, w.r), jnp.float32),
        jax.ShapeDtypeStruct((w.r // 4,), jnp.float32))


def test_spec_round_fused_has_no_lu(one_chip, monkeypatch):
    """On a TPU the rejection tick factors its log-dets in the slogdet
    kernel, not in XLA's per-matrix ``LuDecomposition`` custom call."""
    for ops in (bilinear_ops, spec_round_ops, slogdet_ops):
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
    w = WIDTHS["catalog"]
    sampler = jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                           _sampler_shapes(w))
    slots = 8
    text = _spec_round_fused.lower(
        sampler, _shape(one_chip, (slots, 2), jnp.uint32),
        _shape(one_chip, (slots,), jnp.uint32), n_spec=w.lanes // slots,
    ).as_text()
    assert "ndpp_slogdet" in text
    assert "LuDecomposition" not in text


def test_sharded_paths_compile(v5e_devices, monkeypatch):
    """The item-sharded engines' programs over a 4-chip mesh at the catalog
    width: the rejection tick and the MCMC greedy init.  XLA cannot
    partition a Pallas kernel, so each one must sit inside a shard_map."""
    for ops in (bilinear_ops, mcmc_score_ops, slogdet_ops):
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
    w = WIDTHS["catalog"]
    mesh = Mesh(np.asarray(v5e_devices[:4]), ("model",))
    shapes = _sampler_shapes(w)
    specs = NDPPSampler(sp=SpectralNDPP(Z=P("model", None), sigma=P(None)),
                        tree=tree_shard_specs(shapes.tree, mesh))
    sampler = jax.tree.map(
        lambda a, spec: _shape(NamedSharding(mesh, spec), a.shape, a.dtype),
        shapes, specs, is_leaf=lambda x: isinstance(x, P))
    rep = NamedSharding(mesh, P())
    slots = 8
    tick = _spec_round_fused.lower(
        sampler, _shape(rep, (slots, 2), jnp.uint32),
        _shape(rep, (slots,), jnp.uint32), n_spec=w.lanes // slots,
        mesh=mesh,
    ).compile()
    assert _kernels(tick) >= 1
    states = jax.tree.map(
        lambda a: _shape(rep, a.shape, a.dtype),
        jax.eval_shape(lambda sp: jax.vmap(
            lambda _: mcmc_core.init_empty(sp))(jnp.arange(1)), sampler.sp))
    init = mcmc_core._greedy_round.lower(
        sampler.sp, states, _shape(rep, (1, 2), jnp.uint32),
        _shape(rep, (), jnp.int32), mesh=mesh,
    ).compile()
    assert _kernels(init) >= 1
