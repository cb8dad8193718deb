#!/usr/bin/env python3
"""Readings for a cell's correctness limit, on the chip, in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 \
        --seconds <s> [--control fp8]

For each seed: one run of the cell (its own traffic and sizes, a
window of ``--seconds``), then, on the same sample of finished
requests, the program's widest gap (the lower reading) and the
control's: the plain reference computed in fp8 put in the program's
place, the gap under the float32 reference of the token it puts first
(the upper reading), judged by the run's own check.  One JSON line per
seed, then a summary line; exits 1 unless every program run is correct
and every control run is not.  The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="fp8")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, spec

    cell = spec.load_cell(args.workload)
    lower, upper, verdicts = [], [], []
    t = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            r = harness.run_cell(cell, seed, args.seconds, False, t_start=t,
                                 control=args.control)
        except harness.NoDevice as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        t = time.perf_counter()
        lo = r["checks"]["max_gap_logits"]["value"]
        up = r["control"]["checks"]["max_gap_logits"]["value"]
        lower.append(lo)
        upper.append(up)
        verdicts.append((r["correct"], r["control"]["correct"]))
        print(json.dumps({"seed": seed, "program": lo, "control": up,
                          "correct": r["correct"],
                          "control_correct": r["control"]["correct"],
                          "requests": r["checks"]["requests_checked"],
                          "metrics": r["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "lower": max(lower),
                      "upper": min(upper), "program": lower,
                      "control": upper}), flush=True)
    # the limit separates them: every program run correct, every
    # control run not
    return 0 if all(p and not c for p, c in verdicts) else 1


if __name__ == "__main__":
    sys.exit(main())
