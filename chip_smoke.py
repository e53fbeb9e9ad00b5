#!/usr/bin/env python3
"""Chip smoke test: drive the federated boosting trainer and the ensemble
server once on a TPU, through their normal entry points, and check what
comes out against the repository's own oracles.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # fed_mesh on a four-chip mesh

Default phases (one chip, one process):

* ``fleet``   — the ``mobile_100k`` deployment (100,000 clients) through
  ``FederatedBoostEngine`` in enhanced mode on the fleet profile, with a
  ``KernelPolicy()`` so the batched stump fits resolve to Mosaic; the first
  fit wave is checked against the XLA oracle, and ``dist_update`` against
  ``ref.dist_update_ref`` on that wave's rows.
* ``train``   — the five paper domains at their registry sizes through the
  event engine, publishing into a three-host ``ShardCluster`` that gossips
  to quiescence.
* ``serve``   — requests drawn from the tenants' test pools through
  ``ShardedEnsembleServer`` with measured service time and the result
  cache on, one tenant on the fused fingerprint kernel; every response is
  checked against ``ref.py`` on the snapshot that served it.

``--four-chips`` runs only the ``fed_mesh`` step with one client per chip
and the same step on four host CPU devices, and compares the two.

Everything is built from ``--seed``.  Without a TPU the script exits
non-zero before any phase.  Its last line is one JSON object naming the
device; any failed check exits non-zero before that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

FLEET_SCENARIO = "mobile_100k"
FLEET_ROUNDS = 2            # every fit wave runs and clients sync
DOMAINS = ("edge_vision", "blockchain", "mobile", "iot", "healthcare")
DOMAIN_ROUNDS = 8
SERVE_HOSTS = 3
SERVE_REQUESTS = 1200       # accepted requests to serve
SERVE_RATE = 2000.0         # requests per simulated second (Poisson)
FUSED_TENANT = "edge_vision"
MESH_CLIENTS = 4
MESH_ROUNDS = 40
TIE_TOL = 1e-6              # error-grid ties that may pick either stump
# fed_mesh chip vs host CPU: stumps (feature, threshold, polarity) must
# be equal; a stump's local eps and its vote weight are float32 results of
# up to 40 rounds of exp() distribution updates, which round differently
# on the two backends
MESH_TOL = 1e-4


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileLog:
    """Totals of backend compile time and persistent-cache hits, read by
    JAX's monitoring events (one listener pair for the whole process)."""

    def __init__(self, jax) -> None:
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compile_s, self.compiles, self.cache_hits


class Phase:
    """Times one phase and prints its wall time, its compile time and the
    kernel buckets its policies launched."""

    def __init__(self, name: str, log: CompileLog, *policies) -> None:
        self.name, self.log, self.policies = name, log, policies

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.log.snapshot()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        c1 = self.log.snapshot()
        launched = {}
        for pol in self.policies:
            launched.update(pol.choices)
        print(f"phase {self.name}: wall {wall:.3f} s, first-call compile "
              f"{c1[0] - self.c0[0]:.3f} s over {c1[1] - self.c0[1]} "
              f"programs, {c1[2] - self.c0[2]} persistent-cache hits",
              flush=True)
        for (kernel, bucket), backend in sorted(launched.items()):
            print(f"  kernel {kernel} bucket "
                  f"{'x'.join(map(str, bucket))} -> {backend}")
        others = sorted({b for b in launched.values() if b != "mosaic"})
        check(launched and not others,
              f"{self.name}: kernels resolved to {others or 'nothing'}, "
              f"not mosaic")
        print(f"  all {len(launched)} kernel buckets of phase {self.name} "
              f"resolved to mosaic")
        return False


def sum_tol(alphas, n_terms: int) -> float:
    """Bound on float32 summation error of a weighted vote: the recursive
    summation bound n * 2^-23 * sum|alpha|, plus an absolute floor."""
    import numpy as np
    return n_terms * 2.0 ** -23 * float(np.sum(np.abs(alphas))) + 1e-6


# --------------------------------------------------------------------- fleet
def run_fleet(seed: int, log: CompileLog, n_clients=None) -> None:
    """``n_clients`` shrinks the fleet for a rehearsal off the chip; the
    smoke runs the registry size."""
    import numpy as np

    import repro.models.weak as weak
    from repro.core import FederatedBoostEngine
    from repro.kernels import ops, ref
    from repro.kernels.dispatch import KernelPolicy
    from repro.sim.scenarios import get_scenario

    sc = get_scenario(FLEET_SCENARIO)
    if n_clients is not None:
        sc = dataclasses.replace(sc, domain=dataclasses.replace(
            sc.domain, n_clients=n_clients, n_samples=4 * n_clients))
    policy = KernelPolicy()
    with Phase("fleet", log, policy):
        data = sc.make_data(seed)
        cfg = sc.fedboost_config(seed=seed, n_rounds=FLEET_ROUNDS)
        eng = FederatedBoostEngine(cfg, data, "enhanced",
                                   behavior_for=sc.behavior_for("legacy",
                                                                seed),
                                   kernel_policy=policy, fleet=sc.fleet)
        check(eng.fleet_profile, "mobile_100k did not select the fleet "
                                 "profile")
        # count the fit waves and keep the first as launched, to replay it
        # on the oracle
        first_wave, n_waves = None, 0
        fit = weak.fit_stump_batched

        def recording_fit(*args, backend=None):
            nonlocal first_wave, n_waves
            out = fit(*args, backend=backend)
            first_wave = first_wave or (args, backend, out)
            n_waves += 1
            return out

        weak.fit_stump_batched = recording_fit
        try:
            m = eng.run()
        finally:
            weak.fit_stump_batched = fit
        print(f"  fleet: {sc.domain.n_clients} clients, {FLEET_ROUNDS} "
              f"rounds, {n_waves} fit waves, {m.n_syncs} syncs, "
              f"{m.learners_merged} learners merged, val_err "
              f"{m.final_val_error:.4f}, test_err {m.final_test_error:.4f}")
        check(n_waves >= 1 and m.n_syncs >= 1, "no fit wave or no sync ran")

        args, backend, got = first_wave
        check(backend == "mosaic", f"first wave ran on {backend}")
        want = fit(*args, backend="xla")
        grid = np.asarray(ops.stump_scan_batched(*args, backend="xla"))
        best = np.minimum(grid, 1.0 - grid).reshape(len(grid), -1).min(1)
        f = np.asarray(got["feature"])
        pol = np.asarray(got["polarity"])
        thr = np.asarray(got["threshold"])
        thr_grid = np.asarray(args[3])
        differ = ((f != np.asarray(want["feature"]))
                  | (thr != np.asarray(want["threshold"]))
                  | (pol != np.asarray(want["polarity"])))
        for b in np.flatnonzero(differ):
            t = int(np.flatnonzero(thr_grid[b, f[b]] == thr[b])[0])
            e = grid[b, f[b], t]
            chosen = e if pol[b] > 0 else 1.0 - e
            check(chosen - best[b] <= TIE_TOL,
                  f"slot {b}: mosaic stump err {chosen} vs xla best "
                  f"{best[b]}")
        print(f"  first wave: {len(f)} slots, {int(differ.sum())} stumps "
              f"differ from the XLA oracle, all within error-grid ties of "
              f"{TIE_TOL}")

        # dist_update on the first wave's rows, normalized fleet-wide
        xb, yb, wb, _ = (np.asarray(a) for a in args)
        xsel = np.take_along_axis(xb, f[:, None, None], axis=2)[:, :, 0]
        h = (pol[:, None] * np.sign(xsel - thr[:, None] + 1e-12)).ravel()
        D = wb.ravel() / wb.sum()
        y = yb.ravel()
        d_got, z_got = ops.dist_update(0.5, D, y, h, policy=policy)
        d_want, z_want = ref.dist_update_ref(0.5, D, y, h)
        d_err = float(np.max(np.abs(np.asarray(d_got) - np.asarray(d_want))
                             / np.maximum(np.asarray(d_want), 1e-30)))
        z_err = abs(float(z_got) - float(z_want)) / float(z_want)
        print(f"  dist_update over {D.size} rows: max rel err D' "
              f"{d_err:.3g}, Z {z_err:.3g} (limit 1e-4)")
        check(d_err <= 1e-4 and z_err <= 1e-4, "dist_update disagrees "
                                               "with ref.dist_update_ref")


# ------------------------------------------------------------ train + serve
def run_train(seed: int, log: CompileLog, rounds: int = DOMAIN_ROUNDS):
    import numpy as np

    from repro.core import FederatedBoostEngine
    from repro.kernels.dispatch import KernelPolicy
    from repro.serve import GossipConfig, ShardCluster
    from repro.sim.scenarios import get_scenario

    policy = KernelPolicy()
    cluster = ShardCluster(SERVE_HOSTS, GossipConfig(seed=seed))
    pools = {}
    with Phase("train", log, policy):
        for name in DOMAINS:
            sc = get_scenario(name)
            data = sc.make_data(seed)
            cfg = sc.fedboost_config(seed=seed, n_rounds=rounds)
            eng = FederatedBoostEngine(cfg, data, "enhanced",
                                       kernel_policy=policy)
            eng.attach_registry(cluster, name)
            m = eng.run()
            pools[name] = np.asarray(data["test"][0], np.float32)
            snap = cluster.latest(name)
            print(f"  trained {name}: {sc.domain.n_clients} clients, "
                  f"{sc.domain.n_samples} samples, {sc.domain.n_features} "
                  f"features, val_err {m.final_val_error:.4f}, "
                  f"v{snap.version} with {snap.n_learners} learners")
        gossip_rounds = cluster.run_until_quiescent(now=0.0)
        check(cluster.converged(), "gossip did not converge")
        print(f"  gossip converged in {gossip_rounds} round(s)")
        cluster.rebase_clock(0.0)
    return cluster, pools


def run_serve(cluster, pools, seed: int, log: CompileLog,
              n_requests: int = SERVE_REQUESTS) -> None:
    import jax
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.dispatch import KernelPolicy
    from repro.serve import BatchConfig, PolicyTable, ShardedEnsembleServer
    from repro.serve.cache import fingerprint_key

    policy = KernelPolicy()
    fused = KernelPolicy(fused_fingerprint=True)
    table = PolicyTable()
    table.set_tenant(FUSED_TENANT, kernel=fused)
    with Phase("serve", log, policy, fused):
        server = ShardedEnsembleServer(
            cluster, BatchConfig(cache_capacity=4096), service_model=None,
            policy=policy, policy_table=table)
        rng = np.random.RandomState(seed)
        tenants = sorted(pools)
        accepted, responses, t, offered = [], [], 0.0, 0
        # admission control sheds load while first-call compiles hold the
        # server; keep offering until enough requests were accepted
        while len(accepted) < n_requests and offered < 4 * n_requests:
            offered += 1
            t += rng.exponential(1.0 / SERVE_RATE)
            tenant = tenants[rng.randint(len(tenants))]
            x = pools[tenant][rng.randint(pools[tenant].shape[0])]
            ok, out = server.submit(tenant, x, t)
            responses.extend(out)
            if ok:
                accepted.append((tenant, x))
        responses.extend(server.drain())
        rep = server.report()
        print(f"  smoke timing (not a benchmark metric): serve p50 "
              f"{rep['p50_ms']:.3f} ms, p99 {rep['p99_ms']:.3f} ms over "
              f"{rep['completed']} requests ({rep['rejected']} of "
              f"{offered} offered shed by admission control), mean batch "
              f"{rep['mean_batch']:.1f}, cache hit rate "
              f"{rep['cache']['hit_rate']:.3f}")

        check(len(accepted) == n_requests,
              f"only {len(accepted)} of {offered} offered were accepted")
        rids = sorted(r.rid for r in responses)
        check(rids == list(range(len(accepted))),
              f"{len(accepted)} accepted, {len(rids)} answered: a request "
              f"was lost or duplicated")

        vote = jax.jit(ref.stump_vote_batched_ref)
        lanes = jax.jit(ref._fp_lanes)
        groups = {}
        for r in responses:
            groups.setdefault((r.tenant, r.snapshot_version), []).append(r)
        n_checked = worst = 0
        for (tenant, version), rs in sorted(groups.items()):
            snap = cluster.get(tenant, version)
            check(snap is not None, f"{tenant} v{version} not retained")
            sp = np.asarray(snap.stump_params, np.float32)
            alphas = np.asarray(snap.alphas, np.float32)
            X = np.stack([accepted[r.rid][1] for r in rs])
            xsel = X[:, sp[:, 0].astype(np.int32)].T[None]
            want = np.asarray(vote(xsel, sp[None, :, 1], sp[None, :, 2],
                                   alphas[None]))[0]
            got = np.array([r.margin for r in rs])
            tol = sum_tol(alphas, len(alphas))
            err = np.abs(got - want)
            worst = max(worst, float(err.max()))
            check(bool(np.all(err <= tol)),
                  f"{tenant}: margin off the oracle by {err.max()} > {tol}")
            sure = np.abs(want) > tol
            labels = np.array([r.label for r in rs])
            check(bool(np.all(labels[sure] == np.where(want[sure] > 0, 1.0,
                                                       -1.0))),
                  f"{tenant}: label differs from the oracle")
            n_checked += len(rs)
            if tenant == FUSED_TENANT:
                f0, f1 = (np.asarray(v)[0] for v in lanes(xsel,
                                                          alphas[None]))
                keys = {fingerprint_key(a, b) for a, b in zip(f0, f1)}
                cached = {k[2] for s in server.servers.values()
                          if s.cache is not None
                          for k in s.cache.keys()
                          if k[0] == tenant and k[1] == version}
                check(keys == cached,
                      f"{tenant}: kernel fingerprints differ from the "
                      f"oracle's ({len(keys ^ cached)} keys)")
                print(f"  fused tenant {tenant}: {len(keys)} distinct "
                      f"fingerprints bit-equal to ref._fp_lanes")
        print(f"  {n_checked} responses match ref.stump_vote_batched_ref: "
              f"max |margin err| {worst:.3g}, labels equal wherever "
              f"|margin| > tol")

        # the generic-learner vote on the same snapshots
        snaps = [cluster.latest(t) for t in tenants]
        T = max(s.n_learners for s in snaps)
        n = min(128, min(p.shape[0] for p in pools.values()))
        margins = np.zeros((len(snaps), T, n), np.float32)
        alphas = np.zeros((len(snaps), T), np.float32)
        for b, (s, tenant) in enumerate(zip(snaps, tenants)):
            sp = np.asarray(s.stump_params, np.float32)
            xs = pools[tenant][:n][:, sp[:, 0].astype(np.int32)].T
            margins[b, :s.n_learners] = (sp[:, 2:3] * np.sign(
                xs - sp[:, 1:2] + 1e-12))
            alphas[b, :s.n_learners] = np.asarray(s.alphas)
        got = np.asarray(ops.ensemble_vote_batched(margins, alphas,
                                                   policy=policy))
        want = np.asarray(jax.jit(ref.ensemble_vote_batched_ref)(margins,
                                                                 alphas))
        for b, tenant in enumerate(tenants):
            tol = sum_tol(alphas[b], T)
            check(bool(np.all(np.abs(got[b] - want[b]) <= tol)),
                  f"ensemble_vote_batched off the oracle for {tenant}")
        print(f"  ensemble_vote_batched {margins.shape} matches "
              f"ref.ensemble_vote_batched_ref")


# --------------------------------------------------------------- four chips
def run_mesh(devices, seed: int, thresholds, data):
    """40 fed_mesh rounds with one client per device; returns the final
    state on the host, the per-round (syncs, ensemble size, val_err)
    history and the compiled step's HLO text."""
    import jax
    import numpy as np

    from repro.configs.paper_fedboost import FedBoostConfig
    from repro.core import fed_mesh

    K = len(devices)
    mesh = fed_mesh.client_mesh(devices)
    cfg = FedBoostConfig(n_clients=K)
    x, y, xv, yv = data
    with jax.default_device(devices[0]):
        step = fed_mesh.make_fed_boost_step(cfg, mesh, "clients",
                                            jax.numpy.asarray(thresholds))
        state = fed_mesh.init_state(cfg, K, x.shape[1], xv.shape[1],
                                    buffer_cap=8, ens_cap=1024,
                                    key=jax.random.key(seed))
        state, x, y, xv, yv = fed_mesh.place(mesh, "clients", state,
                                             x, y, xv, yv)
        compiled = jax.jit(step, donate_argnums=0).lower(
            state, x, y, xv, yv).compile()
        history = []
        for _ in range(MESH_ROUNDS):
            state = compiled(state, x, y, xv, yv)
            history.append((int(state.sync_count), int(state.ens_count),
                            float(state.prev_err)))
    spread = {len(leaf.sharding.device_set)
              for leaf in jax.tree.leaves(state)}
    check(spread == {K}, f"a state leaf sits on {sorted(spread)} devices, "
                         f"not all {K}")
    return (jax.tree.map(np.asarray, state._replace(key=None)), history,
            compiled.as_text())


def gather_groups(hlo: str):
    """Device groups of every all-gather in an HLO text, one list each."""
    out = []
    for line in re.findall(r"all-gather(?:-start)?\([^\n]*", hlo):
        m = re.search(r"replica_groups=(\{\{[\d,{}]*\}\}|\[\d+,\d+\]<=\S+?)"
                      r"(?=[,\s}]|$)", line)
        if m is None:
            out.append(None)
        elif m.group(1).startswith("{"):
            out.append([len(g.split(",")) for g in
                        re.findall(r"\{([\d,]+)\}", m.group(1))])
        else:
            n_groups, size = map(int, re.findall(r"\d+", m.group(1))[:2])
            out.append([size] * n_groups)
    return out


def run_four_chips(seed: int, log: CompileLog) -> None:
    import jax
    import numpy as np

    from repro.core import fed_mesh
    from repro.data import make_domain_data
    from repro.models.weak import stump_thresholds
    from repro.sim.scenarios import DOMAINS as REGISTRY

    tpus, cpus = jax.devices()[:MESH_CLIENTS], jax.devices("cpu")
    check(len(tpus) == MESH_CLIENTS, f"{len(tpus)} TPU chips, need "
                                     f"{MESH_CLIENTS}")
    check(len(cpus) >= MESH_CLIENTS, f"{len(cpus)} host CPU devices, need "
                                     f"{MESH_CLIENTS}")
    t0 = time.perf_counter()
    c0 = log.snapshot()
    dom = dataclasses.replace(REGISTRY["edge_vision"], n_clients=MESH_CLIENTS)
    raw = make_domain_data(dom, seed=seed, as_numpy=True)
    with jax.default_device(cpus[0]):
        data = tuple(np.asarray(a) for a in
                     fed_mesh.pack_clients(raw, MESH_CLIENTS))
        # one threshold grid for both runs, so they see the same stumps
        thresholds = np.asarray(stump_thresholds(
            data[0].reshape(-1, data[0].shape[-1])))
    print(f"  fed_mesh: {MESH_CLIENTS} clients x {data[0].shape[1]} "
          f"samples x {data[0].shape[2]} features, {MESH_ROUNDS} rounds")
    chip, chip_hist, hlo = run_mesh(tpus, seed, thresholds, data)
    host, host_hist, _ = run_mesh(cpus[:MESH_CLIENTS], seed, thresholds,
                                  data)
    split = next((r for r, (a, b) in enumerate(zip(chip_hist, host_hist))
                  if a != b), None)
    if split is not None:
        print(f"  first round that differs: {split + 1}, chip "
              f"{chip_hist[split]}, host {host_hist[split]}")

    groups = gather_groups(hlo)
    check(groups, "no all-gather in the compiled chip step")
    check(all(g == [MESH_CLIENTS] for g in groups),
          f"an all-gather does not span the {MESH_CLIENTS} devices: "
          f"{groups}")
    print(f"  compiled chip step: {len(groups)} all-gather(s), each over "
          f"all {MESH_CLIENTS} devices; every state leaf on all "
          f"{MESH_CLIENTS} devices")

    for k in ("sync_count", "ens_count", "counter"):
        a, b = int(getattr(chip, k)), int(getattr(host, k))
        print(f"  {k}: chip {a}, host cpu {b}")
        check(a == b, f"{k} differs: chip {a}, host {b}")
    n = int(chip.ens_count)
    stumps = slice(0, 3)
    same = np.array_equal(chip.ens_params[:n, stumps],
                          host.ens_params[:n, stumps])
    print(f"  ens_params[:{n}] feature/threshold/polarity "
          f"{'equal' if same else 'DIFFER'}")
    check(same, "the chip merged different stumps than the host")
    for name, a, b in (("local eps", chip.ens_params[:n, 3],
                        host.ens_params[:n, 3]),
                       ("ens_alpha", chip.ens_alpha[:n],
                        host.ens_alpha[:n])):
        d = float(np.max(np.abs(a - b), initial=0.0))
        print(f"  {name} max |chip - host| {d:.3g} (limit {MESH_TOL})")
        check(d <= MESH_TOL, f"{name} differs by {d}")
    d = abs(float(chip.prev_err) - float(host.prev_err))
    print(f"  val_err chip {float(chip.prev_err):.6f}, host "
          f"{float(host.prev_err):.6f}")
    check(d <= MESH_TOL, f"val_err differs by {d}")
    c1 = log.snapshot()
    print(f"phase four_chips: wall {time.perf_counter() - t0:.3f} s, "
          f"first-call compile {c1[0] - c0[0]:.3f} s over {c1[1] - c0[1]} "
          f"programs, {c1[2] - c0[2]} persistent-cache hits")


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only fed_mesh on four chips against four "
                         "host CPU devices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.four_chips:
        # the host CPU side of the compare needs four devices, and the flag
        # is read when JAX starts
        os.environ["XLA_FLAGS"] = " ".join(filter(None, (
            os.environ.get("XLA_FLAGS"),
            f"--xla_force_host_platform_device_count={MESH_CLIENTS}")))

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke test runs only on the "
              "chip", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    log = CompileLog(jax)
    try:
        if args.four_chips:
            run_four_chips(args.seed, log)
        else:
            run_fleet(args.seed, log)
            cluster, pools = run_train(args.seed, log)
            run_serve(cluster, pools, args.seed, log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
