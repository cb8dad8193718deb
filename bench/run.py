#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

``<name>`` is an entry of ``workloads`` in ``BENCHMARK.json``.  The run
needs the program (``src/repro``) and a TPU with as many chips as the
cell asks for; without either it exits 2 and prints no result.  The
last line of standard output is one JSON object; the numbers compared
for ``correct`` are the last lines of standard error and the result's
last key, ``checks``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, spec

    cell = spec.load_cell(args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
