#!/usr/bin/env python3
"""Readings for the limits of a cell's comparison with the reference: for
each seed, the numbers that sound runs of the program give, and, for the
first ``--control`` seeds, the numbers the control gives (the reference
in bfloat16 put in the program's place).  One process, so set-up that
several seeds share is paid once.

    python3 bench/calibrate.py --workload femnist_fleet.train \\
        --seeds 101,102,103 --seconds 5 --control 3

Prints one JSON line per seed.  Limits are set by hand from these
readings into the configuration file, between the largest program
reading and the smallest control reading.
"""
import json
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])


def main(argv=None) -> int:
    import argparse
    import importlib
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)

    from bench import harness
    wl = harness.load_workload(args.workload)
    cfg = harness.load_config(wl["config"])
    import jax
    try:
        devices = harness.require_chips(jax, int(wl["chips"]))
    except harness.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache(jax)
    sys.path.insert(0, str(harness.SRC))
    driver = importlib.import_module(f"bench.drivers.{wl['driver']}")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = driver.build(cfg, wl, seed, devices, args.seconds)
        t1 = time.perf_counter()
        win = cell.window(args.seconds)
        cell.free()
        out = {"seed": seed, "setup_s": t1 - t0, "metrics": win.metrics,
               "program": {c.name: c.value for c in cell.verify()}}
        if i < args.control:
            out["control"] = {c.name: c.value for c in cell.control()}
        out["check_s"] = time.perf_counter() - t1 - args.seconds
        print(json.dumps(out), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
