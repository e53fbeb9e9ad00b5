"""Kernel-backend dispatch: resolution order, layout-canonical shape
bucketing, the v2 calibration-table round-trip (backend + block layout),
layout-kwarg injection, the deprecated interpret shim, and per-call
re-resolution in the serving evaluator."""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.dispatch import (
    BACKENDS, DEFAULT_LAYOUTS, ENV_VAR, CalEntry, KernelPolicy, bucket_of,
    canonical, layout_key, on_tpu, platform_default)


def _vote_case(T=9, N=33, seed=0):
    k = jax.random.split(jax.random.key(seed), 2)
    m = jnp.sign(jax.random.normal(k[0], (T, N)))
    a = jax.random.normal(k[1], (T,))
    return m, a


# -------------------------------------------------------- resolution order

def test_resolution_priority_chain(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    bucket = (8, 128)
    # platform default at the bottom
    pol = KernelPolicy()
    assert pol.resolve_name("ensemble_vote", bucket) == platform_default()
    # calibration table beats platform default
    pol.record("ensemble_vote", bucket, "xla")
    assert pol.resolve_name("ensemble_vote", bucket) == "xla"
    # env var beats the table
    monkeypatch.setenv(ENV_VAR, "interpret")
    assert pol.resolve_name("ensemble_vote", bucket) == "interpret"
    # forced policy backend beats env
    forced = KernelPolicy(backend="xla")
    assert forced.resolve_name("ensemble_vote", bucket) == "xla"
    # explicit per-call arg beats everything
    assert forced.resolve_name("ensemble_vote", bucket,
                               explicit="interpret") == "interpret"


@pytest.mark.skipif(on_tpu(), reason="CPU-only fallback semantics")
def test_unavailable_backend_falls_through(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    pol = KernelPolicy()
    with pytest.warns(RuntimeWarning, match="unavailable"):
        name = pol.resolve_name("ensemble_vote", (8, 128),
                                explicit="mosaic")
    assert name == "interpret"
    # a mosaic-calibrated table degrades gracefully off-TPU too
    pol2 = KernelPolicy(table={("ensemble_vote", (8, 128)): "mosaic"})
    with pytest.warns(RuntimeWarning):
        assert pol2.resolve_name("ensemble_vote", (8, 128)) == "interpret"


def test_tpu_refuses_interpret_from_every_level(monkeypatch):
    """On the TPU no resolution level may land on the interpreter: an
    explicit argument, a forced policy, the env var and a table entry all
    raise instead of falling through."""
    from repro.kernels import dispatch
    monkeypatch.delenv(ENV_VAR, raising=False)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    bucket = (8, 128)
    assert KernelPolicy().resolve_name("ensemble_vote", bucket) == "mosaic"
    with pytest.raises(RuntimeError, match="cannot run on the TPU"):
        KernelPolicy().resolve_name("ensemble_vote", bucket,
                                    explicit="interpret")
    with pytest.raises(RuntimeError, match="cannot run on the TPU"):
        KernelPolicy(backend="interpret").resolve_name("ensemble_vote",
                                                       bucket)
    table = KernelPolicy(table={("ensemble_vote", bucket): "interpret"})
    with pytest.raises(RuntimeError, match="cannot run on the TPU"):
        table.resolve_name("ensemble_vote", bucket)
    monkeypatch.setenv(ENV_VAR, "interpret")
    with pytest.raises(RuntimeError, match=ENV_VAR):
        KernelPolicy().resolve("ensemble_vote", bucket)


def test_tpu_calibration_skips_interpret(monkeypatch):
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    assert "interpret" not in dispatch.available_backends()
    assert dispatch.available_backends() == ["mosaic", "xla"]


def test_env_change_takes_effect_without_rebuild(monkeypatch):
    """The dispatch cache must never pin a stale env-driven choice."""
    monkeypatch.delenv(ENV_VAR, raising=False)
    pol = KernelPolicy()
    m, a = _vote_case()
    bucket = bucket_of("ensemble_vote", (m, a))
    ops.ensemble_vote(m, a, policy=pol)
    assert pol.choices[("ensemble_vote", bucket)] == platform_default()
    monkeypatch.setenv(ENV_VAR, "xla")
    ops.ensemble_vote(m, a, policy=pol)
    assert pol.choices[("ensemble_vote", bucket)] == "xla"


def test_platform_change_not_masked_by_dispatch_cache(monkeypatch):
    """A TPU hot-attach re-steers cached (kernel, bucket) resolutions: the
    cache key includes the live platform, never pinning a stale choice."""
    monkeypatch.delenv(ENV_VAR, raising=False)
    import jax as _jax
    pol = KernelPolicy()
    bucket = (8, 128)
    monkeypatch.setattr(_jax, "default_backend", lambda: "cpu")
    assert pol.resolve("ensemble_vote", bucket).name == "interpret"
    monkeypatch.setattr(_jax, "default_backend", lambda: "tpu")
    assert pol.resolve("ensemble_vote", bucket).name == "mosaic"


def test_canonical_names_and_aliases():
    assert canonical("XLA") == "xla"
    assert canonical("ref") == "xla"
    assert canonical("pallas") == "interpret"
    assert canonical("tpu") == "mosaic"
    with pytest.raises(KeyError):
        canonical("cuda")


# --------------------------------------------------------------- bucketing

def test_ragged_shapes_share_buckets_and_dispatch_cache(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    # both round up to the same padded kernel shape
    b1 = bucket_of("ensemble_vote", _vote_case(T=5, N=90))
    b2 = bucket_of("ensemble_vote", _vote_case(T=7, N=100))
    assert b1 == b2
    assert bucket_of("ensemble_vote", _vote_case(T=9, N=300)) != b1
    pol = KernelPolicy()
    ops.ensemble_vote(*_vote_case(T=5, N=90), policy=pol)
    hits0 = pol.cache_hits
    ops.ensemble_vote(*_vote_case(T=7, N=100), policy=pol)
    assert pol.cache_hits == hits0 + 1


def test_batched_bucket_tracks_padded_dims():
    m = jnp.zeros((3, 37, 100))
    a = jnp.zeros((3, 37))
    assert bucket_of("ensemble_vote_batched", (m, a)) == (4, 64, 128)


def test_bucketing_is_layout_canonical():
    """Every candidate layout of one call maps to the same bucket — buckets
    come from the reference layout, never the layout under test, so a
    sweep's candidates share a single calibration entry."""
    m, a = _vote_case(T=6, N=50)
    base = bucket_of("ensemble_vote", (m, a))
    for layout in ({"block_t": 64, "block_n": 256},
                   {"block_t": 256, "block_n": 2048},
                   {"block_t": None, "block_n": None}):
        assert bucket_of("ensemble_vote", (m, a), layout) == base
    x = jnp.zeros((100, 5))
    args = (x, jnp.ones(100), jnp.ones(100), jnp.zeros((5, 6)))
    assert (bucket_of("stump_scan", args, {"block_n": 1024})
            == bucket_of("stump_scan", args))
    q = jnp.zeros((1, 2, 192, 64))
    assert (bucket_of("flash_attention", (q, q, q), {"block_q": 64,
                                                     "block_k": 64})
            == bucket_of("flash_attention", (q, q, q)))


# ------------------------------------------------------------- calibration

def test_calibration_roundtrip(tmp_path, monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    pol = KernelPolicy()
    m, a = _vote_case(T=6, N=50)
    bucket, samples = pol.calibrate_call("ensemble_vote", m, a, reps=2)
    assert bucket == bucket_of("ensemble_vote", (m, a))
    # sample keys are (backend, layout_key): xla measured once with the
    # empty layout, pallas backends swept over the kernel's grid
    assert set(samples) and all(len(ts) == 2 for ts in samples.values())
    assert all(isinstance(k, tuple) and len(k) == 2 for k in samples)
    assert ("xla", ()) in samples
    assert sum(1 for b, _ in samples if b == "interpret") > 1
    winner = pol.table[("ensemble_vote", bucket)]
    assert isinstance(winner, CalEntry)
    assert (winner.backend, winner.layout) in samples
    path = pol.save(str(tmp_path / "cal.json"))
    assert json.loads((tmp_path / "cal.json").read_text())["version"] == 2
    loaded = KernelPolicy.load(path)
    assert loaded.table == pol.table
    assert loaded.resolve_name("ensemble_vote", bucket) == winner.backend
    # an uncalibrated bucket still falls back to the platform default
    assert loaded.resolve_name("ensemble_vote", (1024, 4096)) == \
        platform_default()


def test_v1_table_loads_transparently(tmp_path):
    """Backend-only v1 tables (no version field, no layout key) load as
    layout-less entries — the reference layout then applies at dispatch."""
    p = tmp_path / "cal_v1.json"
    p.write_text(json.dumps({
        "env_var": ENV_VAR, "backend": None,
        "table": [{"kernel": "ensemble_vote", "bucket": [8, 128],
                   "backend": "xla"}]}))
    loaded = KernelPolicy.load(str(p))
    assert loaded.table[("ensemble_vote", (8, 128))] == CalEntry("xla", ())
    assert loaded.resolve_name("ensemble_vote", (8, 128)) == "xla"
    # and a v2 re-save of the v1 load is a valid v2 table
    loaded.save(str(tmp_path / "cal_v2.json"))
    again = KernelPolicy.load(str(tmp_path / "cal_v2.json"))
    assert again.table == loaded.table


def test_future_schema_version_rejected(tmp_path):
    p = tmp_path / "cal_v99.json"
    p.write_text(json.dumps({"version": 99, "table": []}))
    with pytest.raises(ValueError, match="schema v99"):
        KernelPolicy.load(str(p))


def test_save_records_measuring_platform(tmp_path):
    pol = KernelPolicy()
    pol.record("ensemble_vote", (8, 128), "xla")
    path = pol.save(str(tmp_path / "cal.json"))
    data = json.loads((tmp_path / "cal.json").read_text())
    assert data["measured_on"] == jax.default_backend()
    loaded = KernelPolicy.load(path)
    assert loaded.measured_on == jax.default_backend()
    # explicit override for tables assembled off-process
    pol.save(str(tmp_path / "cal_tpu.json"), measured_on="tpu")
    assert json.loads(
        (tmp_path / "cal_tpu.json").read_text())["measured_on"] == "tpu"


def test_cross_platform_table_warns_exactly_once(tmp_path):
    """A tuned table measured on another platform is refused at load
    time, every time, rather than steering this platform's dispatch."""
    here = jax.default_backend()
    other = "tpu" if here != "tpu" else "gpu"
    p = tmp_path / "cal_other.json"
    p.write_text(json.dumps({
        "version": 2, "backend": None, "measured_on": other,
        "table": [{"kernel": "ensemble_vote", "bucket": [8, 128],
                   "backend": "xla", "layout": {}}]}))
    for _ in range(2):
        with pytest.raises(ValueError, match=f"measured on '{other}'"):
            KernelPolicy.load(str(p))


def test_same_platform_and_empty_tables_load_silently(tmp_path):
    here = jax.default_backend()
    same = tmp_path / "cal_same.json"
    same.write_text(json.dumps({
        "version": 2, "backend": None, "measured_on": here,
        "table": [{"kernel": "ensemble_vote", "bucket": [8, 128],
                   "backend": "xla", "layout": {}}]}))
    empty = tmp_path / "cal_empty.json"
    empty.write_text(json.dumps({
        "version": 2, "backend": None, "measured_on": "tpu", "table": []}))
    v1 = tmp_path / "cal_v1.json"          # pre-measured_on tables: silent
    v1.write_text(json.dumps({
        "table": [{"kernel": "ensemble_vote", "bucket": [8, 128],
                   "backend": "xla"}]}))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert KernelPolicy.load(str(same)).measured_on == here
        KernelPolicy.load(str(empty))      # nothing tuned -> nothing to warn
        assert KernelPolicy.load(str(v1)).measured_on is None


# -------------------------------------------------------- layout injection

def _spy_backend(monkeypatch, name, captured):
    be = BACKENDS[name]
    orig = type(be).run

    def run(kernel, *args, **kwargs):
        captured.append(dict(kwargs))
        return orig(be, kernel, *args, **kwargs)

    monkeypatch.setattr(be, "run", run)


def test_tuned_layout_injected_on_matching_backend(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    m, a = _vote_case(T=6, N=50)
    bucket = bucket_of("ensemble_vote", (m, a))
    pol = KernelPolicy(table={
        ("ensemble_vote", bucket):
            ("interpret", {"block_t": 64, "block_n": 256})})
    captured = []
    _spy_backend(monkeypatch, "interpret", captured)
    ops.ensemble_vote(m, a, policy=pol)
    assert captured[-1] == {"block_t": 64, "block_n": 256}
    assert pol.layout_choices[("ensemble_vote", bucket)] == \
        {"block_t": 64, "block_n": 256}
    # explicit caller kwarg outranks the tuned layout
    ops.ensemble_vote(m, a, policy=pol, block_t=128)
    assert captured[-1] == {"block_t": 128, "block_n": 256}


def test_tuned_layout_not_leaked_to_other_backend(monkeypatch):
    """A layout measured for one substrate says nothing about another: a
    call resolving to a different backend gets the reference layout."""
    monkeypatch.delenv(ENV_VAR, raising=False)
    m, a = _vote_case(T=6, N=50)
    bucket = bucket_of("ensemble_vote", (m, a))
    pol = KernelPolicy(table={
        ("ensemble_vote", bucket):
            ("interpret", {"block_t": 64, "block_n": 256})})
    ops.ensemble_vote(m, a, policy=pol, backend="xla")
    assert pol.layout_choices[("ensemble_vote", bucket)] == \
        DEFAULT_LAYOUTS["ensemble_vote"]


def test_none_layout_kwargs_resolve_to_reference_layout(monkeypatch):
    """ops wrappers pass block kwargs as None ("table decides"); with no
    tuned entry the reference DEFAULT_LAYOUTS reach the backend."""
    monkeypatch.delenv(ENV_VAR, raising=False)
    m, a = _vote_case(T=6, N=50)
    captured = []
    _spy_backend(monkeypatch, "interpret", captured)
    ops.ensemble_vote(m, a, policy=KernelPolicy(), backend="interpret")
    assert captured[-1] == DEFAULT_LAYOUTS["ensemble_vote"]


def test_table_accepts_legacy_string_values():
    pol = KernelPolicy(table={("ensemble_vote", (8, 128)): "xla"})
    assert pol.table[("ensemble_vote", (8, 128))] == CalEntry("xla", ())
    assert layout_key({"block_n": 256, "block_t": 64}) == \
        (("block_n", 256), ("block_t", 64))


# ------------------------------------------------------- deprecated shims

def test_ops_interpret_shim_warns_and_matches():
    m, a = _vote_case()
    with pytest.warns(DeprecationWarning, match="interpret"):
        got = ops.ensemble_vote(m, a, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.ensemble_vote_ref(m, a)),
                               rtol=1e-5, atol=1e-5)


def test_server_interpret_shim_warns():
    from repro.serve import BatchConfig, EnsembleRegistry, EnsembleServer
    from repro.serve.engine import BatchEvaluator
    reg = EnsembleRegistry()
    with pytest.warns(DeprecationWarning):
        srv = EnsembleServer(reg, BatchConfig(), interpret=True)
    assert srv.policy.backend == "interpret"
    with pytest.warns(DeprecationWarning):
        ev = BatchEvaluator(reg, interpret=True)
    assert ev._backend_override == "interpret"


def test_server_interpret_shim_outranks_policy():
    """Like the explicit arg it replaces, the deprecated bool pins the
    backend even when a (e.g. calibration) policy is passed alongside —
    the policy's table survives, its resolution is overridden."""
    from repro.serve import BatchConfig, EnsembleRegistry, EnsembleServer
    reg = EnsembleRegistry()
    cal = KernelPolicy(table={("ensemble_vote", (8, 128)): "xla"})
    with pytest.warns(DeprecationWarning):
        srv = EnsembleServer(reg, BatchConfig(), policy=cal, interpret=True)
    assert srv.policy.backend == "interpret"
    assert srv.policy.table == cal.table


# -------------------------------------- serving evaluator re-resolution fix

def test_evaluator_reresolves_backend_per_call(monkeypatch):
    """A policy/env change after construction must steer the very next
    evaluate() — nothing about the backend is captured at build time."""
    monkeypatch.delenv(ENV_VAR, raising=False)
    from repro.serve import EnsembleRegistry
    from repro.serve.batching import Request
    from repro.serve.engine import BatchEvaluator
    rng = np.random.RandomState(0)
    reg = EnsembleRegistry()
    params = np.zeros((4, 4), np.float32)
    params[:, 0] = rng.randint(0, 6, size=4)
    params[:, 1] = rng.randn(4)
    params[:, 2] = 1.0
    reg.publish_packed("t", jnp.asarray(params),
                       jnp.ones((4,), jnp.float32), clock=0.0)
    pol = KernelPolicy()
    ev = BatchEvaluator(reg, policy=pol)
    batch = [Request(rid=0, tenant="t", x=rng.randn(6).astype(np.float32),
                     t_submit=0.0)]
    r1 = ev.evaluate(batch)
    (bucket,) = [b for (k, b) in pol.choices if k == "stump_vote_batched"]
    assert pol.choices[("stump_vote_batched", bucket)] == platform_default()
    monkeypatch.setenv(ENV_VAR, "xla")
    r2 = ev.evaluate(batch)
    assert pol.choices[("stump_vote_batched", bucket)] == "xla"
    # and the two backends served identical margins
    assert r1[0].margin == pytest.approx(r2[0].margin, abs=1e-5)
