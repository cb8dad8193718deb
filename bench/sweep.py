#!/usr/bin/env python3
"""Offered rate against what an open-loop cell sustains, on the chip.

    python3 bench/sweep.py --workload <name> --rates 1,2,4 --seconds 20 \
        --seed <n>

Runs the cell once per rate (its traffic file's ``rate`` replaced, the
ramp's and the window's catalogs sized to ``--ramp`` and ``--seconds``)
without the reference check, and
prints one JSON line per rate: the end-to-end metrics and the queue
left at the window's close.  A rate is sustained while the queue does
not grow over the window.  The cell's rate is fixed in its traffic file
from one such sweep; the benchmark's own runs never sweep.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--ramp", type=float, default=40.0,
                    help="seconds of arrivals before the window")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, spec

    base = spec.load_cell(args.workload)
    t = T_START
    for rate in [float(r) for r in args.rates.split(",")]:
        cell = copy.deepcopy(base)
        cell.traffic["rate"] = rate
        cell.traffic["ramp_requests"] = math.ceil(rate * args.ramp)
        cell.traffic["requests"] = (cell.traffic["ramp_requests"]
                                    + math.ceil(rate * args.seconds) + 8)
        try:
            r = harness.run_cell(cell, args.seed, args.seconds, False,
                                 t_start=t, check=False)
        except harness.NoDevice as e:
            print(f"sweep: {e}", file=sys.stderr)
            return 2
        t = time.perf_counter()
        print(json.dumps({"rate": rate, "window": r["window"],
                          "attempted": r["attempted"],
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
