"""Fleet training cell: whole federated boosting jobs of a cross-device
fleet, through ``FederatedBoostEngine`` on its vectorized fleet profile.

A job is the engine's construction plus ``run()``: every client trains
``rounds`` local rounds and syncs through the event loop, and its stump
fits resolve in batched waves on the chip.  The window runs whole jobs
back to back, and starts one only while the time left holds a job as
long as the last; ``train_round_s`` is all job time over all rounds those
jobs ran.  Data (rows made on the device from the seed, dealt to the
devices by the configuration's per-device counts) is set-up, and so are
direct calls of the job's device programs at its shapes.

Correctness: in every job of the window the reference follows a sample of
clients drawn from the seed through all of their fit waves, from the
initial distribution on their own rows: at each wave, the weighted error
the program took for its stump against the least error over the
reference's own threshold grid, and the distribution the program wrote
against the reference's update of the reference's own distribution with
the program's stump.  Nothing the program made enters the reference but
the stump it chose, which the error gap judges.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import reference as ref
from bench.harness import Check, Window, annotate, sub_seeds


def held_out_total(train: int, val_frac: float, test_frac: float) -> int:
    """The sample count whose split leaves exactly ``train`` training rows
    after ``make_domain_data`` takes ``int(n * frac)`` rows for validation
    and for test."""
    n = int(train / (1.0 - val_frac - test_frac))
    while n - int(n * val_frac) - int(n * test_frac) > train:
        n -= 1
    while n - int(n * val_frac) - int(n * test_frac) < train:
        n += 1
    return n


def device_counts(c: dict) -> np.ndarray:
    """The configuration's per-device sample counts: one fixed draw
    (``counts_seed``) from a gamma distribution with the source's mean and
    standard deviation, rounded, at least 1.  The same for every run."""
    m, s = c["samples_per_device_mean"], c["samples_per_device_stdev"]
    rng = np.random.default_rng(c["counts_seed"])
    counts = rng.gamma((m / s) ** 2, s * s / m, c["devices"])
    return np.maximum(1, np.rint(counts)).astype(np.int64)


def cluster_rows(seed: int, n: int, f: int, pos_frac: float, noise: float,
                 n_clusters: int = 6):
    """``n`` rows of ``f`` features and their labels, made on the device
    in one jitted call from ``seed``: the repository's cluster problem
    (``repro.data.synthetic._base_problem``), each row a cluster centre
    (scale 2) plus unit noise, its label the cluster's class (the first
    two clusters one of each, the others positive with ``pos_frac``),
    flipped with ``noise``.  Returned on the host."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kc, kl, ka, kx, kf = jax.random.split(key, 5)
        centers = 2.0 * jax.random.normal(kc, (n_clusters, f), jnp.float32)
        label = jnp.where(jax.random.uniform(kl, (n_clusters,)) < pos_frac,
                          1.0, -1.0).at[0].set(1.0).at[1].set(-1.0)
        assign = jax.random.randint(ka, (n,), 0, n_clusters)
        x = centers[assign] + jax.random.normal(kx, (n, f), jnp.float32)
        flip = jax.random.uniform(kf, (n,)) < noise
        return x, jnp.where(flip, -label[assign], label[assign])

    x, y = make(jax.random.key(seed))
    return np.asarray(x), np.asarray(y, np.float32)


def deal(x, y, counts, rng):
    """Split the rows into one shard per device: the counts are dealt to
    the devices in an order drawn from ``rng``, the rows in theirs."""
    counts = counts[rng.permutation(len(counts))]
    cut = np.cumsum(counts)[:-1]
    return [(np.ascontiguousarray(a), np.ascontiguousarray(b))
            for a, b in zip(np.split(x, cut), np.split(y, cut))]


class FleetCell:
    def __init__(self, config: dict, workload: dict, seed: int, devices):
        from repro.configs.paper_fedboost import (CompensationConfig,
                                                  SchedulerConfig)
        from repro.core import fleet
        from repro.kernels.dispatch import KernelPolicy
        from repro.sim.scenarios import get_scenario
        import repro.models.weak as weak

        c = config
        counts = device_counts(c)
        if int(counts.sum()) != c["samples"]:
            raise ValueError(f"the per-device counts sum to {counts.sum()}, "
                             f"the configuration states {c['samples']}")
        base = get_scenario(c["base_scenario"])
        total = held_out_total(c["samples"], c["val_frac"], c["test_frac"])
        dom = dataclasses.replace(
            base.domain, name=c["name"], n_clients=c["devices"],
            n_samples=total, n_features=c["features"])
        self.sc = dataclasses.replace(
            base, name=c["name"], domain=dom,
            config_overrides={
                "catch_up_cap": c["catch_up_cap"],
                "compensation": CompensationConfig(decay=c["decay"]),
                "scheduler": SchedulerConfig(i_init=c["i_init"])})
        if c["behaviour_trace"] != "legacy":
            raise ValueError("the fleet driver runs the legacy behaviours "
                             "only (per-client scalars of the deployment)")
        self.mode = c["mode"]
        self.rounds = int(c["rounds"])
        self.n_thresholds = int(c["thresholds_per_feature"])
        self.n_check = int(c["check_clients_per_job"])
        self.limits = c["limits"]
        seeds = sub_seeds(seed, 4)
        t0 = time.perf_counter()
        with annotate("bench.setup.data"):
            x, y = cluster_rows(seeds[0], total, c["features"],
                                dom.label_imbalance, dom.noise)
            n_val, n_test = int(total * c["val_frac"]), int(
                total * c["test_frac"])
            data = {"val": (x[:n_val], y[:n_val]),
                    "test": (x[n_val:n_val + n_test], y[n_val:n_val + n_test]),
                    "clients": deal(x[n_val + n_test:], y[n_val + n_test:],
                                    counts, np.random.default_rng(seeds[3]))}
        self.data = data
        self.cfg = self.sc.fedboost_config(seed=seeds[1] % 2**31,
                                           n_rounds=self.rounds)
        self.policy = KernelPolicy()
        self.rng = np.random.default_rng(seeds[2])

        # taps, while recording: the shape of every batched fit, the rows
        # and slots of each wave, and what each wave did to the clients
        # followed in the current job
        self.recording = False
        self.fit_shapes = []
        self.wave_rows = []
        self.jobs = []
        cell = self
        fit, update = weak.fit_stump_batched, fleet.FleetCore._local_update

        def fit_tap(*args, **kw):
            if cell.recording:
                cell.fit_shapes.append(tuple(args[0].shape)
                                       + (args[3].shape[2],))
            with annotate("bench.fit_wave"):
                return fit(*args, **kw)

        def update_tap(core, slots, f, thr, pol):
            eps, alpha = update(core, slots, f, thr, pol)
            if cell.recording:
                cell.wave_rows.append(
                    (len(slots), int(core.n_valid[slots].sum())))
                job = cell.jobs[-1]
                at = {cid: j for j, cid in enumerate(slots.tolist())}
                hit = [i for i, cid in enumerate(job["cid"].tolist())
                       if cid in at]
                pos = np.array([at[job["cid"][i]] for i in hit], np.int64)
                job["waves"].append({
                    "who": np.array(hit, np.int64),
                    "f": f[pos].copy(), "thr": thr[pos].copy(),
                    "pol": pol[pos].copy(), "eps": eps[pos].copy(),
                    "D_after": core.D[slots[pos]].copy()})
            return eps, alpha

        weak.fit_stump_batched = fit_tap
        fleet.FleetCore._local_update = update_tap
        self._untap = lambda: (setattr(weak, "fit_stump_batched", fit),
                               setattr(fleet.FleetCore, "_local_update",
                                       update))
        t1 = time.perf_counter()
        with annotate("bench.setup.warm"):
            self.warm()
        self.setup_note = (f"fleet setup: data {t1 - t0:.3f} s, direct "
                           f"calls {time.perf_counter() - t1:.3f} s")

    def engine(self):
        from repro.core import FederatedBoostEngine
        return FederatedBoostEngine(self.cfg, self.data, self.mode,
                                    kernel_policy=self.policy, fleet=True)

    def warm(self) -> None:
        """Compile the job's device programs by direct calls at its
        shapes, on zeros made on the device: the fleet's threshold grid
        over a chunk of clients, one fit wave over every client (on the
        backend the fleet resolves for it), and the engine's final error
        readings over the held-out margins."""
        import types
        import jax
        import jax.numpy as jnp
        from repro.core import fleet
        from repro.models.weak import (fit_stump_batched,
                                       stump_thresholds_batched)
        clients = self.data["clients"]
        B = len(clients)
        N = max(len(cy) for _, cy in clients)
        F = clients[0][0].shape[1]
        chunk = min(fleet.THRESHOLD_CHUNK, fleet._next_pow2(B))
        grid = stump_thresholds_batched(jnp.zeros((chunk, N, F), jnp.float32),
                                        jnp.ones(chunk, jnp.int32))
        BP = max(8, fleet._next_pow2(B))
        args = (jnp.zeros((BP, N, F), jnp.float32),
                jnp.zeros((BP, N), jnp.float32),
                jnp.zeros((BP, N), jnp.float32),
                jnp.zeros((BP, F, self.n_thresholds), jnp.float32))
        eng = self.engine()
        backend = fleet.FleetCore._fit_backend(
            types.SimpleNamespace(eng=eng), args)
        params = fit_stump_batched(*args, backend=backend)
        jax.block_until_ready((grid, params))
        eng._val_margin = jnp.zeros(len(self.data["val"][1]), jnp.float32)
        eng._test_margin = jnp.zeros(len(self.data["test"][1]), jnp.float32)
        eng._finalize()

    def job(self):
        if self.recording:
            n = len(self.data["clients"])
            self.jobs.append({"cid": np.sort(self.rng.choice(
                n, min(self.n_check, n), replace=False)), "waves": []})
        with annotate("bench.job.build"):
            eng = self.engine()
        with annotate("bench.job.run"):
            return eng.run()

    def window(self, seconds: float) -> Window:
        self.recording = True
        times, syncs = [], 0
        t0 = time.perf_counter()
        while not times or (time.perf_counter() - t0 + times[-1]
                            <= seconds):
            tj = time.perf_counter()
            m = self.job()
            times.append(time.perf_counter() - tj)
            syncs += m.n_syncs
        self.recording = False
        rounds = self.rounds * len(times)
        return Window(
            metrics={"train_round_s": sum(times) / rounds},
            attempted=len(times), failed=0,
            notes=[self.setup_note,
                   f"fleet: {len(times)} jobs of {self.rounds} rounds in "
                   f"{time.perf_counter() - t0:.3f} s, job seconds "
                   f"{[round(t, 3) for t in times]}, {syncs} syncs, "
                   f"{len(self.fit_shapes)} fit waves "
                   f"{sorted(set(self.fit_shapes))}, "
                   f"{sum(s for s, _ in self.wave_rows)} client fits"])

    def layer_info(self) -> dict:
        return {"fit_shapes": list(self.fit_shapes),
                "wave_rows": list(self.wave_rows),
                "rounds": self.rounds}

    def free(self) -> None:
        self._untap()

    def verify(self):
        """The reference followed through every recorded job."""
        checks, note = compare([self.followed(j) for j in self.jobs],
                               self.limits)
        print(note, flush=True)
        return checks

    def control(self):
        """The same comparison with the bfloat16 reference in the
        program's place: its own stumps, errors and updates, chained."""
        jobs = []
        for j in self.jobs:
            inputs = self.followed(j)[1:]
            jobs.append((control_waves(j["waves"], *inputs), *inputs))
        return compare(jobs, self.limits)[0]

    def followed(self, job: dict):
        """A recorded job's waves, with the followed clients' own rows
        padded to the fleet's row count, the reference's threshold grids
        and their initial distributions."""
        clients = self.data["clients"]
        N = max(len(cy) for _, cy in clients)
        F = clients[0][0].shape[1]
        S = len(job["cid"])
        x = np.zeros((S, N, F), np.float32)
        y = np.zeros((S, N), np.float32)
        grid = np.zeros((S, F, self.n_thresholds), np.float32)
        D0 = np.zeros((S, N), np.float32)
        for i, c in enumerate(job["cid"].tolist()):
            cx, cy = clients[c]
            n = len(cy)
            x[i, :n], y[i, :n] = cx, cy
            grid[i] = ref.quantile_grid(cx, n, self.n_thresholds)
            D0[i, :n] = ref.initial_distribution(cy, self.cfg.balanced_init)
        return job["waves"], x, y, grid, D0


def compare(jobs, limits: dict):
    """The fleet cell's numbers over every followed client of every job,
    each on the reference's own distribution: ``fit_err_gap``, the largest
    amount by which the error of the program's stump, scored as the fit
    scores a stump (+1 side x > thr), exceeds the least such error over
    the reference's grid; ``eps_gap``, the largest distance between the
    error the program took for its stump and the reference's error of
    that stump under its predictor (x == thr on the +1 side);
    ``dist_l1``, the largest L1 distance between the distribution the
    program wrote and the reference's update with the program's stump.
    Also returns how many fits the predictor scores worse than the fit
    did (a threshold on a row's value), and the most it adds."""
    fit_gaps, eps_gaps, dists, tie = [], [], [], []
    for waves, x, y, grid, D in jobs:
        D = D.copy()
        for w in waves:
            i = w["who"]
            if not len(i):
                continue
            xs, ys, Ds = x[i], y[i], D[i]
            best = ref.best_stump_error(xs, ys, Ds, grid[i])
            err = ref.stump_error(xs, ys, Ds, w["f"], w["thr"], w["pol"])
            eps, D_ref = ref.local_update(xs, ys, Ds, w["f"], w["thr"],
                                          w["pol"])
            fit_gaps.append(err - best)
            eps_gaps.append(np.abs(w["eps"] - eps))
            dists.append(np.sum(np.abs(w["D_after"] - D_ref), axis=1))
            tie.append(eps - err)
            D[i] = D_ref
    if not fit_gaps:
        return [Check("clients_compared_missing", 1.0, 0.0)], ""
    tie = np.concatenate(tie)
    note = (f"fleet check: {len(tie)} fits followed; the predictor scores "
            f"{int(np.sum(tie > 1e-6))} of them worse than the fit did, "
            f"by at most {float(tie.max(initial=0.0))!r}")
    return [
        Check("fit_err_gap", float(np.max(np.concatenate(fit_gaps))),
              limits["fit_err_gap"]),
        Check("eps_gap", float(np.max(np.concatenate(eps_gaps))),
              limits["eps_gap"]),
        Check("dist_l1", float(np.max(np.concatenate(dists))),
              limits["dist_l1"])], note


def control_waves(waves, x, y, grid, D, prec: str = "bf16"):
    """What the reference in lower precision produces in the program's
    place for the same clients of the same waves, chained from the same
    initial distributions."""
    D = D.copy()
    out = []
    for w in waves:
        i = w["who"]
        f, thr, pol = ref.best_stumps(x[i], y[i], D[i], grid[i], prec)
        eps, D_after = ref.local_update(x[i], y[i], D[i], f, thr, pol, prec)
        D[i] = D_after
        out.append({"who": i, "f": f, "thr": thr, "pol": pol, "eps": eps,
                    "D_after": D_after})
    return out


def build(config, workload, seed, devices, seconds):
    return FleetCell(config, workload, seed, devices)
