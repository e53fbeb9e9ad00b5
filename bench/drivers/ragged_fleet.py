"""Heavy-tailed fleet training cell: whole federated boosting jobs of a
fleet whose devices mostly hold one or two rows, through
``FederatedBoostEngine`` on its fleet profile, where such a fleet's shards
are packed (rows of every client back to back) and fit by the ragged
stump scan.

Jobs, the window and ``train_round_s`` are as in the fleet cell
(``bench/drivers/fleet.py``, whose helpers this driver imports): rows made
on the device from the seed, dealt to the devices by the configuration's
per-device counts, and direct calls of the job's device programs at its
shapes are set-up.  The counts are the mid-quantiles of 1 + a negative
binomial with the source's mean and variance above the one row each
device holds, the same in every run.

Correctness is the fleet cell's comparison (``compare``) over the clients
each job follows, in two groups each padded to its own largest count: a
sample drawn from the seed, mostly of one or two rows, and the devices
with the most rows, whose rows span several of the scan's row blocks.

The packed layout's entry points are imported before any data is made, so
a program without them stops at once with an ImportError.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference as ref
from bench.drivers import fleet as fd
from bench.harness import annotate


def device_counts(c: dict) -> np.ndarray:
    """The configuration's per-device sample counts: 1 + the quantiles at
    (i + 0.5) / devices of a negative binomial whose mean and variance
    are the source's less the one row (r and p from the moments), in
    ascending order.  The same for every run."""
    m = c["samples_per_device_mean"] - 1.0
    var = c["samples_per_device_stdev"] ** 2
    p = m / var
    r = m * p / (1.0 - p)
    q = (np.arange(c["devices"]) + 0.5) / c["devices"]
    pmf = [p ** r]
    while sum(pmf) < q[-1]:
        k = len(pmf)
        pmf.append(pmf[-1] * (k - 1 + r) / k * (1.0 - p))
    cdf = np.cumsum(pmf)
    return 1 + np.searchsorted(cdf, q, side="left").astype(np.int64)


class RaggedFleetCell(fd.FleetCell):
    def __init__(self, config: dict, workload: dict, seed: int, devices):
        # the packed layout's entry points, before any data: a program
        # that lacks them stops here
        from repro.core.fleet import PACKED_BELOW_FILL, ROW_ALIGN
        from repro.models.weak import (fit_stump_ragged,
                                       stump_thresholds_segmented)
        del PACKED_BELOW_FILL, fit_stump_ragged, stump_thresholds_segmented
        import dataclasses

        from repro import obs
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
        total = fd.held_out_total(c["samples"], c["val_frac"], c["test_frac"])
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
        self.n_largest = int(c["check_largest"])
        self.limits = c["limits"]
        self.row_align = ROW_ALIGN
        seeds = fd.sub_seeds(seed, 4)
        t0 = time.perf_counter()
        with annotate("bench.setup.data"):
            x, y = fd.cluster_rows(seeds[0], total, c["features"],
                                   dom.label_imbalance, dom.noise)
            n_val, n_test = int(total * c["val_frac"]), int(
                total * c["test_frac"])
            data = {"val": (x[:n_val], y[:n_val]),
                    "test": (x[n_val:n_val + n_test], y[n_val:n_val + n_test]),
                    "clients": fd.deal(x[n_val + n_test:],
                                       y[n_val + n_test:], counts,
                                       np.random.default_rng(seeds[3]))}
        self.data = data
        self.n_rows = np.array([len(cy) for _, cy in data["clients"]])
        self.largest = np.sort(np.argsort(-self.n_rows, kind="stable")
                               [:self.n_largest])
        self.cfg = self.sc.fedboost_config(seed=seeds[1] % 2**31,
                                           n_rounds=self.rounds)
        self.policy = KernelPolicy()
        self.rng = np.random.default_rng(seeds[2])

        # taps, while recording: the shape of every ragged fit, the
        # clients and real rows of each wave, and what each wave did to
        # the clients followed in the current job; the fleet's counters
        # at the window's ends
        self.recording = False
        self.fit_shapes = []
        self.wave_rows = []
        self.jobs = []
        self.counted = {}
        self.obs = obs
        cell = self
        fit, update = weak.fit_stump_ragged, fleet.FleetCore._local_update

        def fit_tap(*args, **kw):
            if cell.recording:
                B, T, F = args[4].shape
                cell.fit_shapes.append((args[0].shape[0], F, B, T))
            with annotate("bench.fit_wave"):
                return fit(*args, **kw)

        def update_tap(core, slots, f, thr, pol):
            eps, alpha = update(core, slots, f, thr, pol)
            if cell.recording:
                cell.wave_rows.append(
                    (len(slots), int(core.n_valid[slots].sum())))
                at = {cid: j for j, cid in enumerate(slots.tolist())}
                for job in cell.jobs[-2:]:
                    hit = [i for i, cid in enumerate(job["cid"].tolist())
                           if cid in at]
                    pos = np.array([at[job["cid"][i]] for i in hit],
                                   np.int64)
                    after = np.zeros((len(hit), job["rows"]), np.float32)
                    for k, b in enumerate(slots[pos].tolist()):
                        rows = core.D[core.off[b]:core.off[b + 1]]
                        after[k, :len(rows)] = rows
                    job["waves"].append({
                        "who": np.array(hit, np.int64),
                        "f": f[pos].copy(), "thr": thr[pos].copy(),
                        "pol": pol[pos].copy(), "eps": eps[pos].copy(),
                        "D_after": after})
            return eps, alpha

        weak.fit_stump_ragged = fit_tap
        fleet.FleetCore._local_update = update_tap
        self._untap = lambda: (setattr(weak, "fit_stump_ragged", fit),
                               setattr(fleet.FleetCore, "_local_update",
                                       update))
        t1 = time.perf_counter()
        with annotate("bench.setup.warm"):
            self.warm()
        self.setup_note = (f"fleet setup: data {t1 - t0:.3f} s, direct "
                           f"calls {time.perf_counter() - t1:.3f} s")

    def warm(self) -> None:
        """Compile the job's device programs by direct calls at its
        shapes, on zeros made on the device: the segmented grid over the
        packed rows, one ragged fit over every client (on the backend the
        fleet resolves for it), and the engine's final error readings."""
        import types
        import jax
        import jax.numpy as jnp
        from repro.core import fleet
        from repro.models.weak import (fit_stump_ragged,
                                       stump_thresholds_segmented)
        B = len(self.n_rows)
        R = -(-int(self.n_rows.sum()) // self.row_align) * self.row_align
        F = self.data["clients"][0][0].shape[1]
        x = jnp.zeros((R, F), jnp.float32)
        seg = jnp.zeros(R, jnp.int32)
        grid = stump_thresholds_segmented(x, seg, jnp.ones(B, jnp.int32))
        args = (x, jnp.zeros(R, jnp.float32), jnp.zeros(R, jnp.float32),
                seg, grid)
        eng = self.engine()
        backend = fleet.FleetCore._fit_backend(
            types.SimpleNamespace(eng=eng), args, "stump_scan_ragged")
        params = fit_stump_ragged(*args, backend=backend)
        jax.block_until_ready(params)
        del grid, args, x
        eng._val_margin = jnp.zeros(len(self.data["val"][1]), jnp.float32)
        eng._test_margin = jnp.zeros(len(self.data["test"][1]), jnp.float32)
        eng._finalize()

    def job(self):
        """One job; while recording, it follows two groups of clients, the
        drawn ones and the largest, each padded to its own largest count
        (two entries of ``jobs``)."""
        if self.recording:
            n = len(self.n_rows)
            drawn = np.sort(self.rng.choice(n, min(self.n_check, n),
                                            replace=False))
            for cid in (drawn, self.largest):
                self.jobs.append({"cid": cid,
                                  "rows": int(self.n_rows[cid].max()),
                                  "waves": []})
        with annotate("bench.job.build"):
            eng = self.engine()
        with annotate("bench.job.run"):
            return eng.run()

    def _counters(self) -> dict:
        reg = self.obs.get_registry()
        return {k: reg.counter(f"train.fleet.{k}").value
                for k in ("real_rows", "packed_rows_launched",
                          "segments_launched")}

    def window(self, seconds: float):
        before = self._counters()
        win = super().window(seconds)
        after = self._counters()
        self.counted = {k: after[k] - before[k] for k in after}
        return win

    def layer_info(self) -> dict:
        return {"ragged_fits": list(self.fit_shapes),
                "wave_rows": list(self.wave_rows),
                "counters": dict(self.counted), "rounds": self.rounds}

    def followed(self, job: dict):
        """A recorded job's waves, with the followed clients' own rows
        padded to their largest count, the reference's threshold grids
        and their initial distributions."""
        clients = self.data["clients"]
        N, F = job["rows"], clients[0][0].shape[1]
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


def build(config, workload, seed, devices, seconds):
    return RaggedFleetCell(config, workload, seed, devices)
