#!/usr/bin/env python3
"""Readings that limits and rates are set from; not part of a benchmark
run. One process (one chip) runs a cell once per seed and prints, per
seed, one JSON line with the numbers compared, the control's and the
faults' readings (``--mode calibrate``), or the load reached at each rate
(``--mode sweep`` with ``--rates``; no reference):

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10
    python3 bench/calibrate.py --workload <cell> --mode sweep \\
        --rates 2,3,4 --seeds 1 --seconds 20

Each (seed, rate) runs in a process of its own, one after another; the
parent never touches JAX, so each child has the chip to itself and starts
with the whole of its memory.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import env  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=("calibrate", "sweep"),
                    default="calibrate")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    if len(seeds) * len(rates) > 1:
        import subprocess
        rc = 0
        for seed in seeds:
            for rate in rates:
                cmd = [sys.executable, os.path.abspath(__file__),
                       "--workload", args.workload, "--seeds", str(seed),
                       "--seconds", str(args.seconds), "--mode", args.mode]
                if rate is not None:
                    cmd += ["--rates", str(rate)]
                rc = subprocess.run(cmd).returncode or rc
        return rc
    return one(args, seeds[0], rates[0])


def one(args, seed, rate) -> int:
    env.configure()
    import numpy as np
    from harness.cell import run_cell
    t0 = T_START
    keep: dict = {}
    ov = {"traffic": {"rate_per_s": rate}} if rate else None
    if args.mode == "sweep":
        ov = {"traffic": {"rate_per_s": rate, "drain": False}}
    res = run_cell(args.workload, seed, args.seconds, False, t_start=t0,
                   overrides=ov, mode=args.mode, keep=keep)
    rec = keep["record"]
    line = {"seed": seed, "rate": rate,
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "checks": res["checks"], "readings": rec["readings"],
            "setup": rec["setup"],
            "memory_peak_bytes": rec["memory_peak_bytes"],
            "wall_s": time.perf_counter() - t0}
    if rec["job"] == "serve":
        ttft = [r["times"][0] - r["due"] for r in rec["requests"]
                if r["times"]]
        done = [r for r in rec["requests"] if len(r["times"]) >= r["out"]]
        line["load"] = {
            "offered_per_s": len(rec["requests"]) / args.seconds,
            "finished_per_s": len(done) / rec["window_s"],
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p90_s": float(np.percentile(ttft, 90)),
            "started": len(ttft)}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
