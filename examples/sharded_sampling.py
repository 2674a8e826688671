"""Sharded full-catalog sampling across a device mesh — runnable walkthrough.

Builds a mesh over every local device, shards one NDPP kernel's item axis
across it, and draws samples with all three backends:

  * speculative batched rejection (``sample_batched_many(mesh=...)``),
  * MCMC up/down chains (``run_chains_sharded``),
  * the slot-pool ``SamplerEngine`` with ``mesh=`` (rejection + MCMC ticks).

Every sharded draw is bit-identical to its single-device counterpart
that runs the same XLA arithmetic — the mesh changes where the (M, R)
rows live, never what is sampled; the script asserts this for each
backend and prints the per-device bytes of the sharded proposal tree.
On a TPU the one-device rejection descent is the Pallas kernel, which
sums <Q, node> in another order than the sharded XLA descent, so there
the rejection comparison is reported, not asserted.

On a host without accelerators, simulate devices through the environment:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python examples/sharded_sampling.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    init_empty,
    preprocess,
    run_chains,
    run_chains_sharded,
    sample_batched_many,
    shard_sampler,
)
from repro.kernels.backend import on_tpu
from repro.launch.mesh import make_sampler_mesh
from repro.serve.sampler_engine import (
    SampleRequest,
    SamplerEngine,
)


def main():
    mesh = make_sampler_mesh()
    n_dev = mesh.shape["model"]
    print(f"mesh: {mesh} ({n_dev} devices)")

    # a small synthetic catalog; block=16 -> 64 leaf blocks to shard
    rng = np.random.default_rng(0)
    m, k = 1024, 8
    v = jnp.asarray(rng.normal(size=(m, k)) / np.sqrt(m), jnp.float32)
    b = jnp.asarray(rng.normal(size=(m, k)) / np.sqrt(m), jnp.float32)
    d = jnp.asarray(rng.normal(size=(k, k)), jnp.float32)

    sampler = preprocess(v, b, d, block=16)
    sharded = shard_sampler(sampler, mesh)

    print("per-device bytes of the sharded tree:")
    for lvl, arr in enumerate(sharded.tree.levels):
        per_dev = sorted({s.data.nbytes for s in arr.addressable_shards})
        kind = "sharded" if per_dev[0] < arr.nbytes else "replicated"
        print(f"  level {lvl}: {arr.shape[0]:4d} nodes  {kind:10s} "
              f"{per_dev[0]:8d} B/device")
    w_per_dev = sharded.tree.W.addressable_shards[0].data.nbytes
    print(f"  W rows : {sharded.tree.W.shape[0]:4d} rows   sharded    "
          f"{w_per_dev:8d} B/device")

    # 1) speculative batched rejection, item-sharded
    key = jax.random.PRNGKey(0)
    res = sample_batched_many(sharded, key, 32, n_spec=4, mesh=mesh)
    ref = sample_batched_many(sampler, key, 32, n_spec=4)
    same = np.array_equal(np.asarray(res.items), np.asarray(ref.items))
    assert same or on_tpu()
    sizes = np.asarray(res.mask).sum(1)
    print(f"rejection: 32 draws, mean |Y| = {sizes.mean():.2f}, "
          f"mean trials = {float(np.asarray(res.trials).mean()):.2f} "
          f"({'bit-identical to' if same else 'differs from'} "
          f"single-device)")

    # 2) MCMC up/down chains, catalog rows device-local
    n_chains, n_steps = 4, 128
    keys = jax.random.split(jax.random.PRNGKey(1), n_chains)
    states = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (n_chains,) + a.shape),
        init_empty(sharded.sp))
    _, items_tr, mask_tr, acc = run_chains_sharded(
        sharded.sp, keys, states, mesh=mesh, n_steps=n_steps)
    _, ref_items, _, _ = run_chains(sampler.sp, keys, states, n_steps=n_steps)
    assert np.array_equal(np.asarray(items_tr), np.asarray(ref_items))
    print(f"mcmc: {n_chains} chains x {n_steps} steps, accept rate "
          f"{float(np.asarray(acc).mean()):.2f} (bit-identical trajectories)")

    # 3) the serving engine with mesh= — same API, sharded ticks
    for backend in ("rejection", "mcmc"):
        eng = SamplerEngine(sampler, n_slots=4, backend=backend, mesh=mesh,
                            mcmc_burn_in=64, mcmc_thin=8,
                            **({"n_spec": 4} if backend == "rejection" else {}))
        for i in range(8):
            eng.submit(SampleRequest(rid=i, seed=i))
        out = eng.run()
        print(f"engine[{backend}]: retired {len(out)}/8 requests on the mesh")


if __name__ == "__main__":
    main()
