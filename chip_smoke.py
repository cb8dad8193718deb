#!/usr/bin/env python3
"""Bring-up run on one TPU chip: the serving path and the paper's tuning loop.

    python chip_smoke.py [--out DIR] [--seed N]

Phase ``serve`` drives ``python -m repro.launch.serve --preset full`` in
this process: smollm-135m at its published widths (30 layers, d_model 576,
9/3 heads, vocab 49,152, bf16 params from a seed) behind the paged
``Server`` (page 16, fcfs, chunked prefill), 16 requests with 512-token
seeded prompts, 8 slots, context 2048, 32 new tokens each.  Every request
must complete, and every generated token is checked against the
full-sequence forward (``api.forward``) of its prompt plus the server's
output: it is that forward's argmax, or within ``LOGIT_MARGIN`` of it.

Phase ``tune`` runs the paper's loop through ``repro.tune``: a cost-model
shortlist of 4 block configurations for a 4096^3 bf16 matmul, each
compiled and timed on the chip (``engine="measure"``), and checks that the
cache entry records ``provenance: "measured"`` on this TPU.

Phase ``kernels`` runs flash attention at qwen1.5-4b's prefill shape,
``reduce_1d`` over 2**26 f32 elements and ``sweep_eval`` over a dense
2**20-point lattice, compiled with tuned blocks, against their
references, and checks that each compiled program holds the Mosaic kernel
(``tpu_custom_call``), i.e. did not run in interpret mode.

Without a TPU the script exits 2 and prints no result.  The tuning cache
and the calibration spec are files under ``--out``; JAX's compile cache is
placed by :func:`repro.launch.compile_cache.use_compile_cache`.  Every
number printed is a bring-up observation from one run, not a benchmark.
The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SERVE_ARGS = ["--arch", "smollm-135m", "--preset", "full",
              "--requests", "16", "--prompt-len", "512", "--batch", "8",
              "--context", "2048", "--max-new", "32", "--paged",
              "--page-size", "16", "--scheduler", "fcfs",
              "--prefill-chunk", "128"]
# A generated token passes when the forward's logit for it is within this
# many logits of the forward's max.  The server's cached, chunked bf16 path
# and the full-sequence bf16 forward round differently; bf16 keeps 8
# significant bits, so near ±2.5 (the logits' range here) one step is
# 1/64-1/32, and the margin allows ~10 such steps.  A token drawn at random
# sits about 2 logits below the max (printed as the baseline).
LOGIT_MARGIN = 0.25
MATMUL = (4096, 4096, 4096)
BUDGET = 4
FLASH = (1, 20, 4096, 128)      # qwen1.5-4b prefill: 20 heads, S=4096, D=128
REDUCE_N = 2**26
SWEEP_SIDE = 1024               # WG x TS lattice of SWEEP_SIDE**2 points

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HITS = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Seconds spent in XLA backend compiles (persistent-cache reads
    included), per jitted function, and persistent-cache hits, from
    JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.hits = 0
        self.by_fun: dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, *, fun_name=None, **kw):
        if event == _BACKEND_COMPILE:
            self.seconds += secs
            name = str(fun_name)
            self.by_fun[name] = self.by_fun.get(name, 0.0) + secs

    def _event(self, event, **kw):
        if event == _CACHE_HITS:
            self.hits += 1


class SmokeFailure(AssertionError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def has_kernel(jitted, *args, **kw) -> bool:
    """Whether the compiled program holds a Mosaic TPU kernel."""

    return "tpu_custom_call" in jitted.lower(*args, **kw).compile().as_text()


def phase_serve(seed: int, log) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import main as serve_main

    server = serve_main(SERVE_ARGS + ["--seed", str(seed)])
    api, cfg = server.api, server.api.cfg
    log(f"widths: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab} "
        f"params={api.param_count():,} dtype="
        f"{jax.tree.leaves(server.params)[0].dtype}")
    done = sorted(server.completed, key=lambda r: r.rid)
    check(len(done) == 16, f"{len(done)}/16 requests completed")
    check(all(len(r.out) == 32 for r in done),
          f"output lengths {sorted({len(r.out) for r in done})}, want 32")
    log(f"served {len(done)}/16 requests, "
        f"{sum(len(r.out) for r in done)} tokens")

    prompt_len = len(done[0].prompt)
    fwd = jax.jit(lambda p, t: api.forward(p, {"tokens": t})
                  [:, prompt_len - 1:-1].astype(jnp.float32))
    gaps, hits, baseline = [], 0, []
    for i in range(0, len(done), 8):
        rows = done[i:i + 8]
        seqs = jnp.asarray([r.prompt + r.out for r in rows], jnp.int32)
        logits = np.asarray(fwd(server.params, seqs))
        out = np.asarray([r.out for r in rows])
        top = logits.max(-1)
        got = np.take_along_axis(logits, out[..., None], -1)[..., 0]
        gaps.append(top - got)
        hits += int((logits.argmax(-1) == out).sum())
        baseline.append(top - logits.mean(-1))
    gaps = np.concatenate(gaps)
    n = gaps.size
    log(f"token check vs api.forward: argmax {hits}/{n}, max gap "
        f"{gaps.max():.4f} logits (margin {LOGIT_MARGIN}), random-token "
        f"baseline gap {np.concatenate(baseline).mean():.4f}")
    check(bool((gaps <= LOGIT_MARGIN).all()),
          f"{int((gaps > LOGIT_MARGIN).sum())}/{n} tokens beyond the margin")


def phase_tune(log) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.common import resolve_interpret
    from repro.kernels.matmul_tuned.ops import (MatmulTunable, _matmul_call,
                                                matmul_ref, matmul_tuned)
    from repro.tune import cache_key, default_cache, tune

    check(resolve_interpret(None) is False, "interpret mode on a TPU")
    tunable = MatmulTunable(*MATMUL)
    res = tune(tunable, engine="measure", budget=BUDGET)
    cands = res.stats["candidates"]
    check(len(cands) == BUDGET, f"{len(cands)} candidates timed")
    for c in cands:
        log(f"  candidate {c['config']}: modeled {c['modeled']:.1f} us, "
            f"measured {c['measured']:.1f} us")
        check(c["measured"] > 0, f"no time for {c['config']}")
    log(f"measure pick {res.best_config} ({res.t_min:.1f} us); "
        f"model pick {res.stats['modeled_pick']['config']}")

    cache = default_cache()
    key, _ = cache_key(tunable, "measure", params={"budget": BUDGET})
    entry = cache.entries.get(key)
    check(entry is not None, "no cache entry for the measured matmul")
    platform = entry["fingerprint"]["platform"]
    kind = jax.devices()[0].device_kind
    log(f"cache entry: provenance={entry['provenance']} "
        f"backend={platform['backend']} device_kind={platform['device_kind']}")
    check(entry["provenance"] == "measured", "entry not measured")
    check(platform["backend"] == "tpu" and platform["device_kind"] == kind,
          f"entry keyed to {platform}")
    cache.save()

    ka, kb = jax.random.split(jax.random.PRNGKey(1))
    M, N, K = MATMUL
    a = jax.random.normal(ka, (M, K), jnp.bfloat16)
    b = jax.random.normal(kb, (K, N), jnp.bfloat16)
    got = matmul_tuned(a, b, **res.best_config).astype(jnp.float32)
    ref = matmul_ref(a, b).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    log(f"matmul {res.best_config} vs ref: max rel err {err:.2e}")
    check(err < 1e-2, f"matmul differs from ref by {err}")
    check(has_kernel(_matmul_call, a, b, interpret=None, **res.best_config),
          "no tpu_custom_call in the matmul program")


def phase_kernels(log) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.wave_model import WaveParams
    from repro.kernels.flash_attention.ops import (_flash_call,
                                                   attention_ref,
                                                   flash_attention)
    from repro.kernels.sweep_eval.ops import sweep_eval, sweep_ref
    from repro.kernels.tuned_reduction.ops import reduce_1d, reduce_ref

    kq, kk, kv, kx = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v = (jax.random.normal(key, FLASH, jnp.bfloat16)
               for key in (kq, kk, kv))
    blocks = flash_attention.tune(q, k, v, causal=True).best_config
    got = flash_attention(q, k, v, causal=True).astype(jnp.float32)
    ref = attention_ref(q, k, v, causal=True).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - ref)))
    top = float(jnp.max(jnp.abs(ref)))
    log(f"flash_attention {FLASH} blocks {blocks}: max abs err {err:.2e} "
        f"(max |ref| {top:.3f})")
    # both outputs are bf16: allow two bf16 steps (2**-7 relative each)
    # at the reference's largest magnitude
    check(err <= 2 * 2**-7 * top, f"flash differs from ref by {err}")
    check(has_kernel(_flash_call, q, k, v, causal=True, window=None,
                     interpret=None, **blocks),
          "no tpu_custom_call in the flash program")

    x = jax.random.normal(kx, (REDUCE_N,), jnp.float32)
    rows = reduce_1d.tune(x, op="min").best_config
    got, ref = float(reduce_1d(x, op="min")), float(reduce_ref(x, "min"))
    log(f"reduce_1d {REDUCE_N} f32 {rows}: {got} vs ref {ref}")
    check(got == ref, "reduction differs from ref")
    check(has_kernel(reduce_1d.__wrapped__, x, op="min", interpret=None,
                     **rows),
          "no tpu_custom_call in the reduction program")

    # dense lattice: every WG, TS in 1..SWEEP_SIDE on a 2**20-element
    # Minimum platform (the bench_sweep platform)
    p = WaveParams(size=2**20, NP=128, GMT=16, kind="minimum", NU=15)
    i = jnp.arange(SWEEP_SIDE**2, dtype=jnp.int32)
    wg, ts = 1 + i % SWEEP_SIDE, 1 + i // SWEEP_SIDE
    rows = sweep_eval.tune(wg, ts, p).best_config
    got = sweep_eval(wg, ts, p)
    ref = sweep_ref(p, wg, ts)
    bad = int(jnp.sum(got != ref))
    log(f"sweep_eval {wg.size} configs {rows}: {bad} mismatches vs ref")
    check(bad == 0, f"sweep_eval differs from ref at {bad} points")
    check(has_kernel(sweep_eval.__wrapped__, wg, ts, p, interpret=None,
                     **rows),
          "no tpu_custom_call in the sweep_eval program")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(ROOT / "chip_smoke_out"),
                    help="directory for the tuning cache and spec files")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2

    out = Path(args.out).resolve()
    out.mkdir(parents=True, exist_ok=True)
    # the calibration spec and tuning cache of this run live under --out;
    # no spec file is written, so the chip resolves to its datasheet entry
    os.environ["REPRO_PLATFORM_SPEC"] = str(out / "platform_spec.json")
    tune_cache = out / "tune_cache.json"
    tune_cache.unlink(missing_ok=True)       # every run measures afresh

    from repro.calibrate import get_platform_spec
    from repro.launch.compile_cache import use_compile_cache
    from repro.tune import TuningCache, set_default_cache

    cache_dir = use_compile_cache()
    set_default_cache(TuningCache(tune_cache))
    meter = CompileMeter()
    spec = get_platform_spec()
    print(f"[bring-up, not a benchmark] device {dev.platform} "
          f"'{dev.device_kind}' x{len(devices)}, jax {jax.__version__}, "
          f"compile cache {cache_dir}", flush=True)
    print(f"spec for '{dev.device_kind}': peak {spec.peak_flops:.3g} FLOP/s, "
          f"HBM {spec.hbm_bw:.3g} B/s ({spec.source})", flush=True)

    phases = [("serve", lambda log: phase_serve(args.seed, log)),
              ("tune", phase_tune), ("kernels", phase_kernels)]
    failed = []
    t_all = time.perf_counter()
    for name, run in phases:
        def log(msg, name=name):
            print(f"[{name}] {msg}", flush=True)
        c0, h0, t0 = meter.seconds, meter.hits, time.perf_counter()
        f0 = dict(meter.by_fun)
        try:
            run(log)
            status = "ok"
        except Exception:
            # one failed phase must not hide the others' results
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        log(f"{status}: wall {time.perf_counter() - t0:.2f} s, compile "
            f"{meter.seconds - c0:.2f} s, compile-cache hits "
            f"{meter.hits - h0}")
        spent = sorted(((s - f0.get(k, 0.0), k)
                        for k, s in meter.by_fun.items()), reverse=True)
        log("compile by function: " + ", ".join(
            f"{k} {s:.2f} s" for s, k in spent[:5] if s > 0))
    print(f"[bring-up, not a benchmark] total wall "
          f"{time.perf_counter() - t_all:.2f} s, compile "
          f"{meter.seconds:.2f} s, compile-cache hits {meter.hits}",
          flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
