#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line of
standard output:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; it names a configuration
(``bench/configs``), a traffic mix (``bench/traffic``) and the limits of
its correctness comparison. The configuration names its job
(``bench/jobs/<job>.py``), and every metric is read by
``bench/metrics/<metric>.py``. With ``--trace 0`` the line carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Exits 2 and prints no result when JAX finds no TPU or fewer chips than the
cell asks for, and 1 on any other failure.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import env  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    env.configure()
    from harness.cell import NoChip, log, run_cell
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    except Exception:  # noqa: BLE001 -- any failure: no result line
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
