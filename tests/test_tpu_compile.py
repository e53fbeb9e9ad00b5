"""Compile-only checks for a described TPU v5e: the six Pallas kernels of
the main path at the shapes ``chip_smoke.py`` launches, the ragged stump
scan at the shapes of the LEAF Sent140 fleet, and the 4-client
``fed_mesh`` step over a 2x2 mesh.  Nothing runs; the TPU compiler (Mosaic
for the kernels) refuses here what it would refuse on the chip.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and a module that loaded it while being
collected would give xdist workers different test sets.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.paper_fedboost import FedBoostConfig
from repro.core import fed_mesh
from repro.kernels import dispatch

# (kernel, operand shapes) — the largest shape of each kernel that
# chip_smoke.py launches: the mobile_100k fit wave (2^17 slots of 4 rows),
# dist_update over that wave's rows, the event engine's biggest client
# shard, and the serving batches of the five tenants; and the ragged scan
# over the Sent140 cell's fleet: 16,384 clients, 39,645 rows padded to
# 39,936, 7,500 features, 16 thresholds on whole 128-lane tiles (an int32
# operand is marked so)
I32 = jnp.int32
KERNEL_CASES = [
    ("stump_scan", [(1280, 32), (1280,), (1280,), (32, 16)]),
    ("stump_scan_batched",
     [(131072, 4, 48), (131072, 4), (131072, 4), (131072, 48, 16)]),
    ("dist_update", [(), (524288,), (524288,), (524288,)]),
    ("ensemble_vote_batched", [(5, 256, 128), (5, 256)]),
    ("stump_vote_batched", [(4, 256, 64), (4, 256), (4, 256), (4, 256)]),
    ("stump_vote_fp_batched", [(1, 96, 64), (1, 96), (1, 96), (1, 96)]),
    ("stump_scan_ragged", [(39936, 7500), (39936,), (39936,),
                           ((39936,), I32), (16384, 16, 7552)]),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel,shapes", KERNEL_CASES,
                         ids=[k for k, _ in KERNEL_CASES])
def test_kernel_compiles_for_tpu(kernel, shapes, topo, no_compile_cache):
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = []
    for s in shapes:
        shape, dtype = s if s and s[-1] is I32 else (s, jnp.float32)
        args.append(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip))
    layout = dispatch.DEFAULT_LAYOUTS[kernel]
    fn = lambda *a: dispatch._PALLAS_IMPLS[kernel](*a, interpret=False,
                                                   **layout)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_fed_mesh_step_compiles_for_four_chips(topo, no_compile_cache):
    from repro.sim.scenarios import DOMAINS
    K, n_local, n_val = 4, 95, 150
    F = DOMAINS["edge_vision"].n_features
    mesh = fed_mesh.client_mesh(topo.devices[:K])
    cfg = FedBoostConfig(n_clients=K)
    step = fed_mesh.make_fed_boost_step(cfg, mesh, "clients",
                                        jnp.zeros((F, 16)))
    state = jax.eval_shape(lambda: fed_mesh.init_state(
        cfg, K, n_local, n_val, buffer_cap=8, ens_cap=1024,
        key=jax.random.key(0)))
    specs = fed_mesh.state_shardings(mesh, "clients")
    state = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=NamedSharding(mesh, p)),
        state, specs, is_leaf=lambda v: isinstance(v, P))
    dsh = NamedSharding(mesh, P("clients"))
    data = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=dsh) for s in
            ((K, n_local, F), (K, n_local), (K, n_val, F), (K, n_val))]
    compiled = jax.jit(step).lower(state, *data).compile()
    assert "all-gather" in compiled.as_text()
    out = compiled.output_shardings
    assert all(len(s.device_set) == K for s in jax.tree.leaves(out))
