"""One run of one cell: set-up, the measured window, the readings, the
check against the plain reference, and the result line.

Set-up makes the weights from the seed, builds the program's paged
``Server`` (fcfs, bf16 params and KV), warms the cell's two step
programs with one request of its own traffic cut to two tokens, and
brings the traffic to its steady start: a closed loop sends every
client's first request and ticks until each has its first token; an
open loop sends its ramp's requests before the window opens.

With ``trace`` the end of the window is traced by ``jax.profiler`` and
the cell's per-layer metrics are read; without it, its end-to-end
metrics.  The trace covers at least the last ``TRACE_S`` seconds; it
starts up to ``SEARCH_S`` seconds earlier, at the first tick after
which a prefill is due (a request waits, or one is a token or two from
its end, so its client's next request follows), so that the rarer
prefill step shows in every trace.
"""

from __future__ import annotations

import gc
import json
import shutil
import sys
import time
from dataclasses import dataclass, field, replace
import numpy as np

from . import cost, devtrace, spec, stats, traffic
from .feed import Feed

TRACE_S = 3.0
UNATTRIBUTED_WARN = 0.05
SEARCH_S = 6.0
TRACE_DIR = spec.ROOT / ".bench_trace"
CACHE_DIR = spec.ROOT / ".jax_cache"
_JAX_EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/")


class NoDevice(RuntimeError):
    """The run found no TPU, or fewer chips than the cell asks for."""


@dataclass
class RunData:
    """What the metric readers read (``bench/metrics/<name>.py``)."""

    cell: spec.Cell
    seconds: float
    setup_s: float
    t0: float
    t1: float
    recs: list
    ticks: list
    batch: int
    counters: dict
    model: cost.Model
    peaks: dict | None
    red: devtrace.Reduction | None = None
    traced_ticks: list = field(default_factory=list)

    def window_ticks(self):
        return [t for t in self.ticks if self.t0 <= t.t1 < self.t1]

    def stamps(self):
        return [r.stamps for r in self.recs]


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def check_device(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devs)}")
    return devs[:chips]


def use_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program kept, so only the first run compiles."""

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` at the sizes the file states."""

    from repro.configs import get_config

    return get_config(cfg["arch"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=cfg["qkv_bias"], tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], window=None)


def sample(done: list, n: int, seed: int) -> list:
    """``n`` finished requests drawn from the seed, the longest first."""

    if not done:
        return []
    longest = max(done, key=lambda r: r.prompt_len + len(r.handle.out))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(params, cfg: dict, recs: list, context: int,
                   quant: str | None = None, batch: int = 4):
    """Teacher-forced plain reference over each prompt with its served
    tokens.  Returns per request ``(gaps of the served tokens, gaps of
    the tokens the reference itself puts first)``; with ``quant`` the
    second are the gaps, under the float32 reference, of the tokens the
    quantised control puts first."""

    import jax.numpy as jnp

    ref = spec.load_module("references", cfg["reference"])
    out = []
    for i in range(0, len(recs), batch):
        rows = recs[i:i + batch]
        # a short last block is padded, so one program serves every block
        toks = np.zeros((batch, context), np.int32)
        score = np.zeros_like(toks)
        for b, r in enumerate(rows):
            seq = list(r.treq.prompt) + list(r.handle.out)
            toks[b, :len(seq)] = seq
            score[b, :len(seq) - 1] = seq[1:]
        gap, top = ref.run(params, cfg, jnp.asarray(toks), jnp.asarray(score))
        gap, top = np.asarray(gap), np.asarray(top)
        if quant is not None:
            _, qtop = ref.run(params, cfg, jnp.asarray(toks),
                              jnp.asarray(score), quant=quant)
            qgap, _ = ref.run(params, cfg, jnp.asarray(toks), qtop)
            qgap = np.asarray(qgap)
        for b, r in enumerate(rows):
            lo, hi = r.prompt_len - 1, r.prompt_len - 1 + len(r.handle.out)
            out.append((gap[b, lo:hi],
                        (qgap if quant is not None else gap * 0)[b, lo:hi]))
    return out


class Pauses:
    """What can stall the host inside the window, on the host clock:
    Python's garbage collections and JAX's tracing, lowering, compiling
    and compile-cache reads."""

    def __init__(self):
        import jax.monitoring

        self.gc: list[tuple[float, float, int]] = []    # (start, s, gen)
        self.jax: list[tuple[float, str, float]] = []   # (end, event, s)
        self._gc_t0 = None
        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_duration_secs_listener(self._on_jax)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc.append((self._gc_t0, time.perf_counter() - self._gc_t0,
                            int(info["generation"])))
            self._gc_t0 = None

    def _on_jax(self, event, secs, **kw):
        if event.startswith(_JAX_EVENTS):
            self.jax.append((time.perf_counter(), event, float(secs)))

    def close(self):
        gc.callbacks.remove(self._on_gc)

    def report(self, t0: float, t1: float) -> int:
        """Logs the window's pauses; returns the number of JAX compile
        events in it (traces, lowerings, compiles, cache reads)."""

        g = [x for x in self.gc if t0 <= x[0] < t1]
        by_gen = {k: (sum(1 for x in g if x[2] == k),
                      sum(x[1] for x in g if x[2] == k)) for k in (0, 1, 2)}
        longest = max(g, key=lambda x: x[1], default=None)
        log("window: garbage collections " + ", ".join(
            f"gen{k} {n} in {s:.4f} s" for k, (n, s) in by_gen.items())
            + ("" if longest is None else
               f"; longest {longest[1] * 1e3:.1f} ms (gen{longest[2]}) at "
               f"{longest[0] - t0:.2f} s"))
        j = [x for x in self.jax if t0 <= x[0] < t1]
        names: dict[str, int] = {}
        for _, e, _s in j:
            names[e] = names.get(e, 0) + 1
        log(f"window: JAX compile events {len(j)} "
            + (str(names) if names else ""))
        return len(j)


def build(cell: spec.Cell, seed: int):
    """The program's model and ``Server`` with the benchmark's weights."""

    import jax

    from repro.models.api import build_model
    from repro.runtime.serve import Server

    from .weights import make_params

    cfg = cell.config
    api = build_model(arch_config(cfg))
    abstract = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    dts = {str(a.dtype) for a in jax.tree.leaves(abstract)}
    if dts != {cfg["dtype"]}:
        raise ValueError(f"program declares params in {dts}, the "
                         f"configuration states {cfg['dtype']}")
    params = make_params(abstract, seed, d_model=cfg["hidden_size"],
                         tied=cfg["tie_word_embeddings"])
    s = cell.server
    server = Server(api, params, batch=s["slots"], context=s["context"],
                    prefill_chunk=s["prefill_chunk"], paged=True,
                    page_size=s["page_size"], kv_pages=s["kv_pages"],
                    scheduler=s["scheduler"])
    kv = {str(a.dtype) for a in jax.tree.leaves(server.state)}
    if kv != {cfg["kv_dtype"]}:
        raise ValueError(f"KV pool in {kv}, the configuration states "
                         f"{cfg['kv_dtype']}")
    return api, params, server


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             control: str | None = None, check: bool = True) -> dict:
    """One run; ``control`` (e.g. ``"fp8"``) also puts that reference in
    the program's place on the same sample and judges it by the same
    check, under ``result["control"]`` (its gap is the limit's upper
    reading); ``check=False`` skips the reference (the rate sweep)."""
    import jax

    devices = (check_device(cell.chips) if require_tpu
               else jax.devices()[:cell.chips])
    use_cache()
    pauses = Pauses()
    mix, srv_cfg = cell.traffic, cell.server
    log(f"set-up: JAX up at {time.perf_counter() - t_start:.3f} s")
    api, params, server = build(cell, seed)
    jax.block_until_ready(params)
    log(f"set-up: weights and server at "
        f"{time.perf_counter() - t_start:.3f} s")
    gen = traffic.Generator(mix, cell.config["vocab_size"], seed)
    annotate = _null
    if trace:
        annotate = jax.profiler.TraceAnnotation
    drv = Feed(server, gen, annotate=annotate)

    # warm the two step programs with the mix's own first request
    w = gen.next()
    drv.submit(replace(w, max_new=2), due=drv.clock())
    while drv.busy():
        drv.tick()
    drv.done.clear()
    drv.ticks.clear()
    log(f"set-up: steps warm at {time.perf_counter() - t_start:.3f} s")

    t_base = None
    if mix["loop"] == "closed":
        drv.start_closed(int(mix["clients"]))
        while any(not r.stamps for r in drv.live):
            drv.tick()
        t0 = drv.clock()
    else:
        t_base = drv.clock()
        t0 = t_base + traffic.ramp_s(mix)
    t_end = t0 + seconds
    setup_s = t0 - t_start

    state = {"counted": False, "traced": False}
    counters = {}
    trace_t0 = t_end - min(TRACE_S, seconds / 2)
    win_span = None
    k_traced = None

    def hook(now):
        nonlocal win_span, k_traced
        if not state["counted"] and now >= t0:
            state["counted"] = True
            counters.update(ticks0=server.ticks,
                            slot_ticks0=server.slot_ticks)
        if not trace or state["traced"] or now < trace_t0 - SEARCH_S:
            return
        due = server.queue or any(
            r.treq.max_new - len(r.handle.out) <= 2 for r in drv.live)
        if due or now >= trace_t0:
            state["traced"] = True
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            a = time.perf_counter()
            jax.profiler.start_trace(str(TRACE_DIR))
            log(f"trace: start_trace took {time.perf_counter() - a:.4f} s")
            win_span = jax.profiler.TraceAnnotation("bench.window")
            win_span.__enter__()
            k_traced = len(drv.ticks)

    drv.run_until(t_end, t_base=t_base, hook=hook)
    t_close = drv.clock()
    counters.update(ticks1=server.ticks, slot_ticks1=server.slot_ticks)
    peak = 0
    for d in devices:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))

    red = None
    traced_ticks = []
    if trace and win_span is not None:
        win_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        a = time.perf_counter()
        xplanes = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        red = devtrace.reduce(devtrace.load(xplanes[-1]))
        traced_ticks = drv.ticks[k_traced:]
        log(f"trace: {len(red.ticks)} tick spans, {len(traced_ticks)} host "
            f"ticks, reduced in {time.perf_counter() - a:.2f} s")
        if len(red.ticks) != len(traced_ticks):
            log("trace: tick spans and host ticks differ in number; "
                "per-tick readings are left out")
            traced_ticks = []

    n_compile = pauses.report(t0, t_close)
    pauses.close()
    wt = [t for t in drv.ticks if t0 <= t.t1 < t_end]
    gaps = stats.token_gaps([r.stamps for r in drv.done + drv.live], t0,
                            t_end)
    if gaps:
        log("window: token gaps p50/p90/p95/p99 "
            + "/".join(f"{np.percentile(gaps, q) * 1e3:.2f}"
                       for q in (50, 90, 95, 99)) + f" ms over {len(gaps)}")
    longest = sorted(wt, key=lambda t: t.t0 - t.t1)[:5]
    log("window: longest ticks (ms at s) " + ", ".join(
        f"{(t.t1 - t.t0) * 1e3:.1f} at {t.t0 - t0:.2f}"
        + (" prefill" if t.prefill else "") for t in longest))
    between = sorted(((b.t0 - a.t1, a.t1 - t0) for a, b in zip(wt, wt[1:])),
                     reverse=True)[:3]
    log("window: longest waits between ticks (ms at s) " + ", ".join(
        f"{w * 1e3:.1f} at {at:.2f}" for w, at in between))
    log(f"window: {sum(bool(t.prefill) for t in wt)} of {len(wt)} ticks "
        f"ran the prefill step; queue at close {len(server.queue)}, "
        f"slots live {len(server.live_slots())}")
    closing = {"queue": len(server.queue), "ticks": len(wt),
               "prefill_ticks": sum(bool(t.prefill) for t in wt)}
    late = np.asarray(drv.lateness, np.float64)
    log(f"window: {seconds} s from set-up {setup_s:.3f} s; ticks "
        f"{len(wt)}; compile events in "
        f"window {n_compile}; host/token count mismatches "
        f"{drv.mismatches}; deferrals {server.deferrals} preemptions "
        f"{server.preemptions}")
    if late.size:
        log(f"generator lateness: mean {late.mean() * 1e3:.3f} ms, p99 "
            f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
            f"{late.max() * 1e3:.3f} ms over {late.size} sends")

    recs = drv.done + drv.live
    peaks = spec.load_peaks(devices[0].device_kind) if require_tpu else None
    run = RunData(cell=cell, seconds=seconds, setup_s=setup_s, t0=t0,
                  t1=t_end, recs=recs, ticks=drv.ticks,
                  batch=server.batch, counters=counters,
                  model=cost.Model.from_config(cell.config), peaks=peaks,
                  red=red, traced_ticks=traced_ticks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.load_module("metrics", m["name"]).read(run)
        if v is None:
            log(f"WARNING: metric {m['name']} read nothing in this run and "
                f"is left out of the result line")
        else:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if red is not None:
        named = named_programs(spec.load_benchmark()["per_layer"])
        lost = red.unattributed_ns(named)
        log("trace: program time "
            + ", ".join(f"{k} {v * 1e-9:.4f} s"
                        for k, v in sorted(red.programs.items()))
            + f"; unattributed device time (outside "
            f"{', '.join(sorted(named))}) {lost * 1e-9:.4f} s of "
            f"{red.busy_ns * 1e-9:.4f} s busy")
        if lost > UNATTRIBUTED_WARN * red.busy_ns:
            log(f"WARNING: {100 * lost / red.busy_ns:.1f}% of the device's "
                f"busy time lies outside the programs the readers name; a "
                f"step was renamed or merged, and its readers miss it")
        log("trace: idle by span "
            + ", ".join(f"{k} {v:.4f} s"
                        for k, v in red.idle_by_span.items()))

    # correctness: the served tokens of a sample of finished requests
    # against the plain float32 reference, after the program's state is
    # freed
    result = {"correct": False, "attempted": len(recs), "failed": 0,
              "metrics": metrics, "device": _device(devices, peak, red),
              "window": closing}
    if red is not None:
        result["breakdown"] = {"device_ops": [list(x) for x in red.top_ops],
                               "idle_gaps": [list(x) for x in red.idle_gaps]}
    if not check:
        return result
    picked = sample(list(drv.done), int(srv_cfg["sample"]["requests"]), seed)
    server.state = None
    del server, drv
    gc.collect()
    a = time.perf_counter()
    rows = reference_gaps(params, cell.config, picked, srv_cfg["context"],
                          quant=control)
    served = int(sum(len(g) for g, _ in rows))
    widest = max((float(g.max()) for g, _ in rows if g.size), default=None)
    agree = sum(int((g <= 0).sum()) for g, _ in rows)
    short = sum(len(r.handle.out) != r.treq.max_new for r in picked)
    log(f"reference: {len(picked)} requests, {served} served tokens, "
        f"{agree} the reference's argmax, in {time.perf_counter() - a:.2f} s")
    if control is not None:
        # the control stands in the program's place and is judged by
        # the same check: its tokens' gaps under the float32 reference
        qwidest = max((float(q.max()) for _, q in rows if q.size),
                      default=None)
        ok, qchecks = judge(qwidest, len(picked), short, srv_cfg)
        for k, c in qchecks.items():
            log(f"control check: {k} {c['value']} limit {c['limit']}")
        result["control"] = {"correct": ok, "checks": qchecks}
    correct, checks = judge(widest, len(picked), short, srv_cfg)
    for k, c in checks.items():
        log(f"check: {k} {c['value']} limit {c['limit']}")
    result["correct"] = correct
    result["checks"] = checks
    return result


def judge(widest, n_checked: int, wrong_lengths: int,
          srv_cfg: dict) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit."""

    limit = srv_cfg["limits"]["max_gap_logits"]
    want = int(srv_cfg["sample"]["requests"])
    checks = {
        "max_gap_logits": {"value": widest, "limit": limit},
        "requests_checked": {"value": n_checked, "limit": want},
        "wrong_lengths": {"value": wrong_lengths, "limit": 0},
    }
    correct = (limit is not None and widest is not None and widest <= limit
               and n_checked >= want and wrong_lengths == 0)
    return bool(correct), checks


def named_programs(metrics: list[dict]) -> set[str]:
    """The device programs that these metrics' readers look for by name
    (a reader's ``PROGRAMS``)."""

    return set().union(*(getattr(spec.load_module("metrics", m["name"]),
                                 "PROGRAMS", ()) for m in metrics))


def _device(devices, peak: int, red) -> dict:
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if red is not None:
        dev["busy_s"] = red.busy_ns * 1e-9
        dev["window_s"] = red.window_ns * 1e-9
    return dev


def _null(name):
    import contextlib

    return contextlib.nullcontext()


def emit(result: dict) -> None:
    print(json.dumps(result), flush=True)
