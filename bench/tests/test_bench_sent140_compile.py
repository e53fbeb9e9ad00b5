"""Compile-only checks, for a described TPU v5e, of the two device
programs of the Sent140 cell at its full size: the segmented threshold
grid over the packed rows and the ragged fit over every client.  Each
must fit one chip (15.75 GB) on its own: the job keeps the packed rows and
the grid on the chip between them.  The parameters take the compiler's
layouts, as arrays on the chip do: the packed rows' (39,936, 7,500) is not
row-major, so the fit copies them for the kernel; the grid, on whole
128-lane tiles, is.  Nothing runs.

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
from bench.drivers import ragged_fleet

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


def sent140_shapes():
    """(clients, packed rows, features, thresholds) of the cell: 39,645
    rows padded to the fleet's row alignment."""
    from repro.core.fleet import ROW_ALIGN
    c = harness.load_config("sent140_fleet")
    R = int(ragged_fleet.device_counts(c).sum())
    return (c["devices"], -(-R // ROW_ALIGN) * ROW_ALIGN, c["features"],
            c["thresholds_per_feature"])


def compiled_bytes(fn, *shapes):
    mem = jax.jit(fn).lower(*shapes).compile().memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)


@pytest.mark.parametrize("program", ["grid", "fit"])
def test_sent140_program_fits_one_chip(program, topo, no_compile_cache):
    from repro.kernels import dispatch
    from repro.models import weak
    one_chip = SingleDeviceSharding(topo.devices[0])
    S = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                        sharding=one_chip)
    B, R, F, T = sent140_shapes()
    assert (B, R, F, T) == (16384, 39936, 7500, 16)
    if program == "grid":
        used = compiled_bytes(weak.stump_thresholds_segmented,
                              S(R, F), S(R, dt=jnp.int32),
                              S(B, dt=jnp.int32))
    else:
        kernel = "stump_scan_ragged"
        layout = dispatch.DEFAULT_LAYOUTS[kernel]
        used = compiled_bytes(
            lambda *a: weak._pick_ragged(dispatch._PALLAS_IMPLS[kernel](
                *a, interpret=False, **layout), T),
            S(R, F), S(R), S(R), S(R, dt=jnp.int32), S(B, T, 7552))
    assert used < HBM_BYTES, used
