"""Benchmark harness — one module per paper table/figure + the
beyond-paper engines.  Prints ``name,us_per_call,derived`` CSV at the
end (per-benchmark sections print richer tables above).

``--smoke`` runs a CI-sized subset: one distributed-tuning cell through
the full ``repro.tune`` path (grid engine + cache hit/miss) plus the
Table 3 model sweep — end-to-end tuning in well under a minute — and
writes the machine-readable ``BENCH_smoke.json`` (per-bench timings +
derived counters + wall seconds) that CI uploads as the perf-trajectory
artifact.
``--measure`` runs only the modeled-vs-measured comparison (the
``measure`` engine on real kernels, interpret mode on CPU, tiny shapes).
``--prefill`` runs only the chunked-vs-tokenwise serving prefill drain.
``--paged`` runs only the paged-vs-contiguous KV cache drain.
``--spec`` runs only the speculative-vs-one-token decode drain.
``--traffic`` runs only the trace-driven scheduling/prefix-sharing
benchmark (writes ``BENCH_traffic.json`` plus ``TRACE_traffic.json``,
a Perfetto-loadable ``repro.obs`` trace of the monitored drain).
``--calibrate`` runs only the platform-calibration probes + trajectory
(writes ``BENCH_calibrate.json`` and appends ``BENCH_calibration.json``).

Artifact writing goes through :mod:`benchmarks.emit` (the shared
``BENCH_*.json`` emitter).
"""

from __future__ import annotations

import argparse
import time

from benchmarks.emit import emit
from repro.launch.compile_cache import use_compile_cache


def main(argv=None) -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: one tuning benchmark end-to-end")
    ap.add_argument("--measure", action="store_true",
                    help="measure-engine smoke only (modeled vs measured)")
    ap.add_argument("--prefill", action="store_true",
                    help="chunked-vs-tokenwise serving prefill drain only")
    ap.add_argument("--paged", action="store_true",
                    help="paged-vs-contiguous KV cache drain only")
    ap.add_argument("--spec", action="store_true",
                    help="speculative-vs-one-token decode drain only")
    ap.add_argument("--traffic", action="store_true",
                    help="trace-driven scheduling + prefix-sharing "
                         "benchmark only")
    ap.add_argument("--calibrate", action="store_true",
                    help="platform-calibration probes + modeled-vs-"
                         "measured trajectory only")
    ap.add_argument("--json-out", default=None,
                    help="write the CSV as machine-readable JSON here "
                         "(default BENCH_smoke.json with --smoke, "
                         "BENCH_traffic.json with --traffic, "
                         "BENCH_calibrate.json with --calibrate)")
    args = ap.parse_args(argv)

    from benchmarks import (bench_calibrate, bench_measure, bench_paged,
                            bench_prefill, bench_roofline, bench_spec,
                            bench_sweep, bench_table1, bench_table2,
                            bench_table3, bench_tpu_tuning, bench_traffic)

    csv: list[str] = []
    t0 = time.perf_counter()
    if args.measure:
        bench_measure.run(csv)
    elif args.calibrate:
        bench_calibrate.run(csv)
    elif args.prefill:
        bench_prefill.run(csv, **bench_prefill.SMOKE)
    elif args.paged:
        bench_paged.run(csv, **bench_paged.SMOKE)
    elif args.spec:
        bench_spec.run(csv, **bench_spec.SMOKE)
    elif args.traffic:
        bench_traffic.run(csv, **bench_traffic.SMOKE,
                          trace_out="TRACE_traffic.json")
    elif args.smoke:
        bench_table3.run(csv)
        bench_tpu_tuning.run(csv, cells=[("minitron-8b", "train_4k", 1)])
        bench_tpu_tuning.run_cache(csv)
        bench_measure.run(csv)
        bench_prefill.run(csv, **bench_prefill.SMOKE)
        bench_paged.run(csv, **bench_paged.SMOKE)
        bench_spec.run(csv, **bench_spec.SMOKE)
    else:
        bench_table1.run(csv)
        bench_table2.run(csv)
        bench_table3.run(csv)
        bench_sweep.run(csv)
        bench_sweep.run_warp_ablation(csv)
        bench_tpu_tuning.run(csv)
        bench_tpu_tuning.run_cache(csv)
        bench_measure.run(csv, cases=bench_measure.FULL_CASES,
                          top_k=4, repeats=3)
        bench_prefill.run(csv, **bench_prefill.FULL)
        bench_paged.run(csv, **bench_paged.FULL)
        bench_spec.run(csv, **bench_spec.FULL)
        bench_traffic.run(csv, **bench_traffic.FULL,
                          trace_out="TRACE_traffic.json")
        bench_roofline.run(csv)
    dt = time.perf_counter() - t0

    print("\n== CSV (name,us_per_call,derived) ==")
    for line in csv:
        print(line)
    print(f"\ntotal benchmark wall time: {dt:.1f}s")

    json_out = args.json_out or ("BENCH_smoke.json" if args.smoke
                                 else "BENCH_traffic.json" if args.traffic
                                 else "BENCH_calibrate.json"
                                 if args.calibrate else None)
    if json_out:
        emit(csv, dt, json_out)


if __name__ == "__main__":
    main()
