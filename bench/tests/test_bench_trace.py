"""The benchmark's yardstick off the chip: the trace reduction on a small
trace recorded here on the CPU, and the work functions and peak table."""
import re
import time

import jax
import jax.numpy as jnp
import pytest

from bench import peaks, trace as tr, work

# on the CPU, device ops run on the XLA client's threads of the host plane
CPU_OPS = tr.DeviceLines(plane=r"^/host:CPU$", line=r"^tf_XLA",
                         skip=r"^ThreadpoolListener|^end: ")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace of two annotated bursts of device work with a host-only
    sleep between them."""
    d = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.compute"):
            for _ in range(5):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.05)
        with jax.profiler.TraceAnnotation("bench.compute"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    return tr.find_xplane(d)


def test_summary_of_a_recorded_trace(recorded):
    s = tr.summarize(recorded, 1, lines=CPU_OPS)
    assert 0.05 <= s.window_s < 5.0
    assert 0.0 < s.busy_s < s.window_s
    assert 0.0 < s.idle_share < 1.0
    assert s.op_seconds(r"dot") > 0.0
    assert s.op_seconds(r"no-such-op") == 0.0
    # the sleep is the longest idle stretch, and it is named by its span
    assert max(s.gap_s, key=s.gap_s.get) == "bench.sleep"
    assert s.gap_s["bench.sleep"] >= 0.045
    assert s.host_s["bench.compute"] > 0.0
    b = s.breakdown(top=3)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 3 and len(b["idle_gaps"]) <= 3
    assert all(isinstance(n, str) and sec > 0 for n, sec in b["device_ops"])


def test_busy_time_averages_over_devices(recorded):
    one = tr.summarize(recorded, 1, lines=CPU_OPS)
    two = tr.summarize(recorded, 2, lines=CPU_OPS)
    assert two.busy_s == pytest.approx(one.busy_s / 2)
    assert two.window_s == one.window_s


def test_tpu_lines_find_no_device_ops_in_a_cpu_trace(recorded):
    s = tr.summarize(recorded, 1)
    assert s.busy_s == 0.0 and s.op_s == {}


@pytest.mark.parametrize("intervals,merged", [
    ([], []),
    ([(0, 1)], [(0, 1)]),
    ([(0, 1), (0.5, 2)], [(0, 2)]),
    ([(3, 4), (0, 1), (1, 2)], [(0, 2), (3, 4)]),
    ([(0, 5), (1, 2), (3, 4)], [(0, 5)]),
])
def test_union(intervals, merged):
    assert tr.union(intervals) == merged


@pytest.mark.parametrize("shape,nbytes,ops", [
    # (rows, slots, F, T): one client of one row, one feature, one threshold
    ((1, 1, 1, 1), 4 * (1 + 2 + 2), 2),
    # a FEMNIST wave: 1,024 clients holding 232,361 rows, 784 features
    ((232361, 1024, 784, 16),
     4 * (232361 * 784 + 2 * 232361 + 2 * 1024 * 784 * 16),
     2 * 232361 * 784 * 16),
    # 8 slots holding 100 real rows in all: rows padded to the largest
    # client count are no part of the work
    ((100, 8, 28, 16), 4 * (100 * 28 + 200 + 2 * 8 * 28 * 16),
     2 * 100 * 28 * 16),
])
def test_stump_scan_work(shape, nbytes, ops):
    assert work.stump_scan(*shape) == (nbytes, ops)


def test_roofline_share_names_its_bound():
    p = peaks.peaks_for("TPU v5 lite")
    # 819 MB in 1 ms at 819 GB/s: the memory bound, fully used
    share, bound = work.roofline_share([(819e6, 1.0)], 1e-3, p)
    assert bound == "memory" and share == pytest.approx(1.0)
    share, bound = work.roofline_share([(1.0, 197e9)], 2e-3, p)
    assert bound == "compute" and share == pytest.approx(0.5)
    # several calls add up before the bound is taken
    share, _ = work.roofline_share([(409.5e6, 0.0)] * 2, 1e-3, p)
    assert share == pytest.approx(1.0)


@pytest.mark.parametrize("kind,key,value", [
    ("TPU v5 lite", "flops_bf16", 197e12),
    ("TPU v5 lite", "ops_int8", 393e12),
    ("TPU v5 lite", "hbm_bytes", 16e9),
    ("TPU v5 lite", "hbm_bytes_per_s", 819e9),
])
def test_peak_table(kind, key, value):
    assert peaks.peaks_for(kind)[key] == value


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v5p", "", "tpu v5 lite"])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for(kind)


def test_no_peaks_off_the_accelerator():
    assert peaks.peaks_for("cpu", platform="cpu") == {}


@pytest.mark.parametrize("instants,labels", [
    ([0.5], ["bench.a"]),
    ([1.5], ["bench.b"]),          # innermost of a inside window
    ([2.5], ["bench.a"]),
    ([3.5], [tr.IDLE_HOST]),       # only the window covers it
    ([9.0], [tr.IDLE_HOST]),       # nothing covers it
    ([0.5, 1.5, 3.5], ["bench.a", "bench.b", tr.IDLE_HOST]),
])
def test_gap_labels(instants, labels):
    spans = [(0.0, 5.0, tr.WINDOW), (0.0, 3.0, "bench.a"),
             (1.0, 2.0, "bench.b")]
    assert tr.label_instants(instants, spans) == labels


@pytest.mark.parametrize("event,name", [
    ("%copy.11 = f32[131072,48,16]{1,2,0:T(8,128)} copy(f32[131072,48,16]"
     "{0,2,1:T(8,128)} %thresholds.1)", "copy.11 f32[131072,48,16]"),
    ("%stump_vote_batched_kernel.1 = f32[1,1,128]{2,1,0:T(1,128)} "
     "custom-call(f32[1,512,128]{2,1,0:T(8,128)S(1)} %copy-done.2)",
     "stump_vote_batched_kernel.1 f32[1,1,128]"),
    ("%fusion = f32[131072]{0:T(1024)} fusion(f32[131072,48,16] "
     "%stump_scan_kernel.1)", "fusion f32[131072]"),
    ("dot_general.1", "dot_general.1"),
])
def test_op_names_drop_operands(event, name):
    assert tr.op_name(event) == name
    # a kernel's name matches only its own op, not an op reading its output
    assert bool(re.match(r"^stump_scan_kernel", tr.op_name(event))) == (
        "stump_scan_kernel" in name)
