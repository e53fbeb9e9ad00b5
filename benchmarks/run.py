"""Benchmark harness — one module per paper table/figure plus the roofline
report.  Prints ``name,us_per_call,derived`` CSV lines at the end (harness
convention); the human-readable tables precede them.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --quick    # 1 seed, fewer rounds
    PYTHONPATH=src python -m benchmarks.run backend_matrix serving_load
                                                       # named subset only

Each benchmark additionally persists its raw result as
``BENCH_<name>.json`` under ``--out-dir`` (default ``artifacts/bench``) so
runs are diffable across commits — the perf trajectory.  ``--timestamp``
stamps the files (CI passes the commit SHA); ``--out-dir ''`` disables
the JSON emission entirely.

``--baseline DIR`` turns a run into a trajectory point *and* a
comparison: every fresh result is diffed against ``DIR/BENCH_<name>.json``
(normally the checked-in ``artifacts/bench`` set), a ratio table is
printed, and the process exits non-zero if any benchmark's wall time
regressed past ``--regress-threshold`` (default 3.0x — CI noise on shared
runners is real; the gate is for order-of-magnitude breakage, not
single-digit percent drift).  Benchmarks with no baseline file are
reported as new; baselines recorded under a different ``quick`` config
are compared but never gate.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time


def _jsonable(x):
    """Best-effort conversion of a benchmark result to JSON-serializable
    plain data: dataclasses -> dicts, numpy scalars/arrays -> python,
    tuples/sets -> lists, anything else unknown -> str."""
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        return _jsonable(dataclasses.asdict(x))
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple, set, frozenset)):
        return [_jsonable(v) for v in x]
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    if hasattr(x, "item") and not hasattr(x, "__len__"):   # numpy scalar
        return _jsonable(x.item())
    if hasattr(x, "tolist"):                               # numpy array
        return _jsonable(x.tolist())
    return str(x)


def write_bench_json(out_dir: str, name: str, result, *, wall_us: float,
                     quick: bool, seeds, n_rounds: int,
                     timestamp: str) -> str:
    """One ``BENCH_<name>.json`` per benchmark: the raw result plus enough
    config to reproduce it.  Returns the path written."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    doc = {
        "name": name,
        "config": {"quick": quick, "seeds": list(seeds),
                   "n_rounds": n_rounds},
        "seeds": list(seeds),
        "wall_us": round(wall_us, 1),
        "metrics": _jsonable(result),
        "timestamp": timestamp,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def compare_to_baseline(baseline_dir: str, fresh: dict, threshold: float
                        ) -> int:
    """Diff fresh ``{name: wall_us-bearing doc}`` results against the
    ``BENCH_<name>.json`` set in ``baseline_dir``; print the trajectory
    table and return the number of gating regressions (fresh wall time
    > ``threshold`` x baseline under a comparable config)."""
    regressions = 0
    print(f"\n--- perf trajectory vs {baseline_dir} "
          f"(gate: >{threshold:g}x wall) ---")
    print(f"{'benchmark':<22} {'baseline_us':>14} {'fresh_us':>14} "
          f"{'ratio':>7}  verdict")
    for name in sorted(fresh):
        doc = fresh[name]
        base_path = os.path.join(baseline_dir, f"BENCH_{name}.json")
        if not os.path.exists(base_path):
            print(f"{name:<22} {'-':>14} {doc['wall_us']:>14.1f} "
                  f"{'-':>7}  new (no baseline)")
            continue
        with open(base_path) as f:
            base = json.load(f)
        base_us = float(base.get("wall_us", 0.0))
        fresh_us = float(doc["wall_us"])
        ratio = fresh_us / base_us if base_us > 0 else float("inf")
        comparable = (base.get("config", {}).get("quick")
                      == doc.get("config", {}).get("quick"))
        if not comparable:
            verdict = "config mismatch (quick differs; not gating)"
        elif ratio > threshold:
            verdict = "REGRESSION"
            regressions += 1
        else:
            verdict = "ok"
        print(f"{name:<22} {base_us:>14.1f} {fresh_us:>14.1f} "
              f"{ratio:>6.2f}x  {verdict}")
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out-dir", default="artifacts/bench", metavar="DIR",
                    help="write BENCH_<name>.json result files here "
                         "('' disables; default: artifacts/bench)")
    ap.add_argument("--timestamp", default=None, metavar="TAG",
                    help="stamp for the BENCH json files (e.g. a commit "
                         "SHA; default: current UTC time)")
    ap.add_argument("--baseline", default=None, metavar="DIR",
                    help="diff results against the BENCH_<name>.json set "
                         "in DIR and exit non-zero on wall-time "
                         "regressions past --regress-threshold")
    ap.add_argument("--regress-threshold", type=float, default=3.0,
                    metavar="X",
                    help="gating wall-time ratio for --baseline "
                         "(default: 3.0)")
    ap.add_argument("only", nargs="*", metavar="BENCH",
                    help="run only the named benchmarks (default: all)")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    seeds = (0,) if args.quick else (0, 1, 2)
    n_rounds = 20 if args.quick else 30

    from benchmarks import (autoscale_load, backend_matrix,
                            controller_compare, domains, fedavg_compare,
                            kernel_bench, multipod_compare, relevance_filter,
                            roofline, scale_matrix, scenario_matrix,
                            scheduler_ablation, serving_load, shard_gossip,
                            staleness, sustained_slo)

    # the single benchmark registry: name -> thunk, in run order
    benches = {
        # Table 1 (the paper's main quantitative claim)
        "table1_domains": lambda: domains.main(n_rounds=n_rounds,
                                               seeds=seeds),
        # scenario registry: domains x behavior traces, train -> serve
        # (picks its own seed count: 2-seed means for the band checks)
        "scenario_matrix": lambda: scenario_matrix.main(quick=args.quick),
        # scheduling-rule ablation (paper eq. 1)
        "scheduler_ablation": scheduler_ablation.main,
        # staleness compensation sweep (paper eq. 2)
        "staleness_sweep": staleness.main,
        # FL baselines comparison (paper's framing vs FedAvg/FedAsync)
        "fedavg_compare": fedavg_compare.main,
        # beyond-paper: relevance-filtered buffers + alt controllers
        "relevance_filter": relevance_filter.main,
        "controller_compare": controller_compare.main,
        # roofline report from the dry-run artifacts (§Roofline)
        "roofline_report": roofline.main,
        # single- vs multi-pod scaling census
        "multipod_compare": multipod_compare.main,
        # serving: adaptive micro-batch window vs fixed, closed-loop load
        "serving_load": lambda: serving_load.main(quick=args.quick),
        # sharded registry: gossip convergence + result-cache p99 A/B
        "shard_gossip": lambda: shard_gossip.main(quick=args.quick),
        # fleet autoscaling: eq.-(1) pressure controller vs fixed fleet
        "autoscale_load": lambda: autoscale_load.main(quick=args.quick),
        # SLO error budgets + burn-rate alerting through a latency burst
        "sustained_slo": lambda: sustained_slo.main(quick=args.quick),
        # kernel x backend x shape-bucket wall-clock + calibration table
        "backend_matrix": lambda: backend_matrix.main(quick=args.quick),
        # 100k-client fleet-scale smoke through the vectorized fleet profile
        "scale_matrix": lambda: scale_matrix.main(quick=args.quick),
        # per-kernel microbench rows (not wall-timed by the harness)
        "kernel_bench": kernel_bench.rows,
    }
    unknown = sorted(set(args.only) - set(benches))
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; "
                 f"choose from {', '.join(benches)}")

    stamp = args.timestamp or time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                            time.gmtime())
    csv_rows = []
    results = {}
    written = []
    fresh_docs = {}
    for name, fn in benches.items():
        if args.only and name not in args.only:
            continue
        t0 = time.time()
        results[name] = fn()
        wall_us = (time.time() - t0) * 1e6
        if name != "kernel_bench":        # kernel_bench emits its own CSV
            csv_rows.append((name, wall_us, "bench-wall"))
        fresh_docs[name] = {"wall_us": round(wall_us, 1),
                            "config": {"quick": args.quick}}
        if args.out_dir:
            written.append(write_bench_json(
                args.out_dir, name, results[name], wall_us=wall_us,
                quick=args.quick, seeds=seeds, n_rounds=n_rounds,
                timestamp=stamp))

    print("\n--- kernel microbench + harness CSV ---")
    csv_rows.extend(results.get("kernel_bench", []))
    for d in results.get("table1_domains", []):
        csv_rows.append((
            f"table1_{d['domain']}", 0.0,
            f"time_down={d['time_down']:.1f}%;comm_down={d['comm_down']:.1f}%;"
            f"conv_down={d['conv_down']:.1f}%;acc_delta={d['acc_delta_pp']:+.1f}pp"))
    for r in results.get("serving_load", []):
        csv_rows.append((
            f"serve_{r['policy']}_{r['rate']:.0f}rps", 0.0,
            f"thr={r['throughput_rps']:.0f}rps;p50={r['p50_ms']:.2f}ms;"
            f"p99={r['p99_ms']:.2f}ms;batch={r['mean_batch']:.1f};"
            f"rej={r['rejected']}"))
    for r in results.get("shard_gossip", []):
        csv_rows.append((
            f"shard_{r['mode']}_{r['rate']:.0f}rps", 0.0,
            f"p99={r['p99_ms']:.2f}ms;hit={r['hit_rate']:.2f};"
            f"identical={int(r['identical_predictions'])};"
            f"lag={r['mean_lag_rounds']:.1f}r"))
    for r in results.get("sustained_slo", []):
        if r.get("tenant") == "__fleet__":
            csv_rows.append((
                "sustained_slo_fleet", 0.0,
                f"p99={r['p99_ms']:.2f}ms;fired={r['alerts_fired']};"
                f"in_burst={r['alerts_in_burst']};"
                f"resolved={r['alerts_resolved']};rej={r['rejected']}"))
    for r in results.get("autoscale_load", []):
        csv_rows.append((
            f"autoscale_{r['fleet']}_{r['rate']:.0f}rps", 0.0,
            f"p99={r['p99_ms']:.2f}ms;rej={100 * r['rej_rate']:.1f}%;"
            f"hosts={r['hosts_final']};out={r['scale_outs']};"
            f"in={r['scale_ins']};rerouted={r['rerouted']}"))
    csv_rows.extend(results.get("backend_matrix", []))
    csv_rows.extend(scenario_matrix.csv_rows(
        results.get("scenario_matrix", [])))
    csv_rows.extend(scale_matrix.csv_rows(
        results.get("scale_matrix", [])))
    for name, us, derived in csv_rows:
        print(f"{name},{us:.1f},{derived}")
    if written:
        print(f"\nwrote {len(written)} BENCH json file(s) "
              f"[{stamp}]: {', '.join(written)}")
    if args.baseline:
        regressions = compare_to_baseline(args.baseline, fresh_docs,
                                          args.regress_threshold)
        if regressions:
            print(f"{regressions} benchmark(s) regressed past "
                  f"{args.regress_threshold:g}x — failing the run")
            sys.exit(1)


if __name__ == "__main__":
    main()
