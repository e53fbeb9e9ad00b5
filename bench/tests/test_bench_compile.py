"""Compile-only checks, for a described TPU v5e, of the programs that the
benchmark's cells launch at their full size and that the repository's
other compile tests do not cover: the FEMNIST fleet's threshold grid and
its fit wave, each of which must fit one chip (the fleet keeps its shards
on the host between them).  Nothing runs.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and xdist workers must all collect
the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness
from bench.drivers import fleet

HBM_BYTES = 15.75e9         # what the v5e compiler may allocate


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
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def fleet_wave():
    """(slots, rows, features, thresholds) of the FEMNIST cell's waves:
    every client in one power-of-two wave, padded to the largest count."""
    c = harness.load_config("femnist_fleet")
    B = 1 << (c["devices"] - 1).bit_length()
    N = int(fleet.device_counts(c).max())
    return B, N, c["features"], c["thresholds_per_feature"]


def compiled_bytes(fn, *shapes):
    mem = jax.jit(fn).lower(*shapes).compile().memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)


@pytest.mark.parametrize("program", ["thresholds", "fit"])
def test_fleet_wave_fits_one_chip(program, topo, no_compile_cache):
    from repro.kernels import dispatch
    from repro.models.weak import stump_thresholds_batched
    one_chip = SingleDeviceSharding(topo.devices[0])
    S = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                        sharding=one_chip)
    B, N, F, T = fleet_wave()
    if program == "thresholds":
        used = compiled_bytes(stump_thresholds_batched,
                              S(B, N, F), S(B, dt=jnp.int32))
    else:
        kernel = "stump_scan_batched"
        layout = dispatch.DEFAULT_LAYOUTS[kernel]
        used = compiled_bytes(
            lambda *a: dispatch._PALLAS_IMPLS[kernel](*a, interpret=False,
                                                      **layout),
            S(B, N, F), S(B, N), S(B, N), S(B, F, T))
    assert used < HBM_BYTES, used
