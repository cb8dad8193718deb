#!/usr/bin/env python3
"""Records the small chip trace the trace-reduction tests read.

    python3 tests/bench/record_trace.py --workload <name> --seed <n>

Runs the cell with a short window, traces its last ``--trace-s``
seconds, and keeps the trace as ``tests/bench/data/<workload>.xplane.pb.gz``
together with the host's record of the traced ticks
(``<workload>.ticks.json``: per tick, the context of every decoding slot
and the chunks of every prefilling slot).  Needs a TPU.
"""

import argparse
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--trace-s", type=float, default=0.3)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import time

    from bench import harness, spec

    cell = spec.load_cell(args.workload)
    harness.TRACE_S = args.trace_s
    kept = {}
    real = harness.devtrace.reduce

    def keep(events):
        red = real(events)
        kept["red"] = red
        return red

    harness.devtrace.reduce = keep
    feeds = []

    class Keep(harness.Feed):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            feeds.append(self)

    harness.Feed = Keep
    r = harness.run_cell(cell, args.seed, args.seconds, True,
                         t_start=time.perf_counter(), check=False)
    xp = sorted(harness.TRACE_DIR.glob("**/*.xplane.pb"))[-1]
    DATA.mkdir(exist_ok=True)
    out = DATA / f"{args.workload}.xplane.pb.gz"
    out.write_bytes(gzip.compress(xp.read_bytes(), 9))
    n = len(kept["red"].ticks)
    ticks = [{"decode": t.decode, "prefill": t.prefill}
             for t in feeds[-1].ticks[len(feeds[-1].ticks) - n:]]
    (DATA / f"{args.workload}.ticks.json").write_text(json.dumps(
        {"ticks": ticks, "metrics": r["metrics"], "device": r["device"]}))
    print(json.dumps({"trace": str(out), "bytes": out.stat().st_size,
                      "ticks": len(ticks)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
