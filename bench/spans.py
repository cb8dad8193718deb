"""The program's own spans and named scopes in a profiler trace.

``bench/devtrace.py`` reduces the benchmark's ``bench.*`` spans; this
module reads the same ``.xplane.pb`` for what the program itself marks:

* the engine's host spans (``repro.obs.trace.span``): ``serve.tick``
  around a ``Server.tick`` and, inside it, ``serve.admit``,
  ``serve.draft``, ``serve.back``, ``serve.trim`` and per step
  ``serve.pack.<step>``, ``serve.dispatch.<step>``,
  ``serve.readback.<step>``, ``serve.commit.<step>``;
* the step programs' ``jax.named_scope``s.  An operation's scope is the
  innermost of ``SCOPES`` in its ``tf_op`` path (e.g.
  ``jit(step_paged)/layers/while/body/kv_write/select_n``), which the
  file keeps in each device plane's event metadata and
  ``ProfileData`` does not expose; it is read here from the bytes.
  The profiler gives an operation the compiler made (a layout copy, a
  hoisted convert, an async slice) the path of the instruction that
  holds it, so such operations inside the layer scan fall to
  ``layers``, beside the scan's own stacking of each layer's new
  state.  An operation with no scope in its path is ``unscoped``.

Per traced tick (a ``bench.tick`` span inside the window) the device's
idle time is split four ways: inside the host-prep spans (``PREP``),
inside the commit spans (``COMMIT``), inside ``serve.tick`` outside its
children, and inside ``bench.tick`` outside ``serve.tick``.  The four
add up to the tick's host time as ``host_ms_per_tick`` reads it.  A
program's table by scope counts leaf operations (as ``top_ops`` does)
of the calls that lie wholly inside the window.
"""

from __future__ import annotations

import bisect
import sys
from dataclasses import dataclass, field
from pathlib import Path

from bench import devtrace
from bench.devtrace import Ev

SCOPES = ("layers", "qkv", "kv_write", "kv_read", "attend", "mlp", "norm",
          "head")
UNSCOPED = "unscoped"
TICK = "serve.tick"
PREP = ("serve.admit", "serve.draft", "serve.back", "serve.pack.",
        "serve.dispatch.")
COMMIT = ("serve.trim", "serve.readback.", "serve.commit.")
PREFIXES = ("bench.", "serve.")
TF_OP = "tf_op"


def _varint(b: bytes, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b: bytes, lo: int, hi: int):
    """``(field, value)`` of the protobuf message in ``b[lo:hi]``; a
    length-delimited value is its ``(start, end)``, a fixed-width one
    ``None``."""

    i = lo
    while i < hi:
        key, i = _varint(b, i)
        f, t = key >> 3, key & 7
        if t == 0:
            v, i = _varint(b, i)
        elif t == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif t in (1, 5):
            v, i = None, i + (8 if t == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {t} at byte {i}")
        yield f, v


def op_paths(data: bytes) -> dict[str, dict[str, str]]:
    """Per device plane: op event name -> its ``tf_op`` path.

    ``XSpace.planes`` (1) -> ``XPlane`` name (2), ``event_metadata`` (4:
    map entry, value 2 = ``XEventMetadata``: name 2, stats 5) and
    ``stat_metadata`` (5: map entry, value 2 = ``XStatMetadata``: id 1,
    name 2); an ``XStat`` holds its metadata id (1) and a string (5) or
    a reference to a stat name (7).  The planes' event lines are
    skipped unread."""

    def text(v):
        return data[v[0]:v[1]].decode("utf-8", "replace")

    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(data, 0, len(data)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for f2, v2 in _fields(data, *plane):
            if f2 == 2:
                name = text(v2)
            elif f2 in (4, 5):
                for f3, v3 in _fields(data, *v2):
                    if f3 != 2:
                        continue
                    if f2 == 5:
                        sid, sname = 0, ""
                        for f4, v4 in _fields(data, *v3):
                            if f4 == 1:
                                sid = v4
                            elif f4 == 2:
                                sname = text(v4)
                        stat_names[sid] = sname
                        continue
                    ename, stats = "", []
                    for f4, v4 in _fields(data, *v3):
                        if f4 == 2:
                            ename = text(v4)
                        elif f4 == 5:
                            st = dict(_fields(data, *v4))
                            stats.append(st)
                    events.append((ename, stats))
        if not name.startswith(devtrace.DEVICE_PREFIX):
            continue
        paths = out.setdefault(name, {})
        for ename, stats in events:
            for st in stats:
                if stat_names.get(st.get(1)) != TF_OP:
                    continue
                if 5 in st:
                    path = text(st[5])
                elif 7 in st:
                    path = stat_names.get(st[7], "")
                else:
                    continue
                paths[ename] = path.rsplit(":", 1)[0]
    return out


def scope_of(path: str) -> str:
    """The innermost named scope of a ``tf_op`` path."""

    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def load(path: str | Path) -> tuple[list[Ev], dict[str, dict[str, str]]]:
    """The ``bench.*`` and ``serve.*`` host spans, the device planes' op
    and module events, and the ops' ``tf_op`` paths."""

    from jax.profiler import ProfileData

    data = _read(path)
    pd = ProfileData.from_serialized_xspace(data)
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith(devtrace.DEVICE_PREFIX)
        if not dev and plane.name != devtrace.HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name not in (devtrace.OPS_LINE,
                                         devtrace.MODULES_LINE):
                continue
            for e in line.events:
                if not dev and not e.name.startswith(PREFIXES):
                    continue
                out.append(Ev(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.end_ns)))
    return out, op_paths(data)


def _read(path: str | Path) -> bytes:
    import gzip

    path = Path(path)
    data = path.read_bytes()
    return gzip.decompress(data) if path.suffix == ".gz" else data


def _kind(name: str) -> str | None:
    if name.startswith(PREP):
        return "prep"
    if name.startswith(COMMIT):
        return "commit"
    return None


@dataclass
class SpanReduction:
    window: tuple[float, float]
    ticks: list[tuple[float, float]]        # bench.tick spans, in order
    serve_spans: int                        # serve.* spans in the ticks
    host_ns: list[float]                    # per tick: idle in bench.tick
    prep_ns: list[float]                    # ... inside PREP spans
    commit_ns: list[float]                  # ... inside COMMIT spans
    tick_self_ns: list[float]               # ... serve.tick, no child
    outside_ns: list[float]                 # ... outside serve.tick
    scopes: dict[str, dict[str, float]]     # program -> scope -> op ns
    calls: dict[str, int]                   # program -> calls counted
    idle_gaps: list[tuple[str, float]]      # (span, s), longest first
    idle_by_span: dict[str, float] = field(default_factory=dict)

    def mean_ms(self, per_tick: list[float]) -> float | None:
        """Mean over the traced ticks in ms; ``None`` where the trace
        holds no ``serve.*`` span (a program without them)."""

        if not self.serve_spans or not per_tick:
            return None
        return sum(per_tick) / len(per_tick) * 1e-6

    def scoped(self, program: str) -> bool:
        """Does any op of ``program`` carry a named scope?"""

        t = self.scopes.get(program, {})
        return any(v > 0 for k, v in t.items() if k != UNSCOPED)


def reduce(events: list[Ev], paths: dict[str, dict[str, str]],
           top: int = 10) -> SpanReduction:
    spans = [e for e in events if e.plane == devtrace.HOST_PLANE]
    win = [e for e in spans if e.name == "bench.window"]
    if not win:
        raise ValueError("trace holds no bench.window span")
    w0, w1 = win[0].start, win[0].end
    chips = sorted({e.plane for e in events
                    if e.plane.startswith(devtrace.DEVICE_PREFIX)})
    if not chips:
        raise ValueError("trace holds no TPU device plane")
    c0 = chips[0]
    ops = [e for e in events if e.plane == c0 and e.line == devtrace.OPS_LINE]
    m0 = devtrace.union([iv for iv in ((max(e.start, w0), min(e.end, w1))
                                       for e in ops if e.end > w0
                                       and e.start < w1) if iv[1] > iv[0]])

    def idle(a: float, b: float) -> float:
        return (b - a) - devtrace.covered(m0, a, b)

    def gaps(a: float, b: float, inner: list[tuple[float, float]]):
        out, prev = [], a
        for x, y in sorted(inner):
            if x > prev:
                out.append((prev, x))
            prev = max(prev, y)
        if b > prev:
            out.append((prev, b))
        return out

    ticks = sorted((e.start, e.end) for e in spans
                   if e.name == "bench.tick" and e.start >= w0
                   and e.end <= w1)
    serve = sorted(((e.start, e.end, e.name) for e in spans
                    if e.name.startswith("serve.")), key=lambda s: s[0])
    starts = [s[0] for s in serve]
    n_serve = 0
    host, prep, commit, tself, outside = [], [], [], [], []
    for a, b in ticks:
        lo, hi = bisect.bisect_left(starts, a), bisect.bisect_right(starts, b)
        inside = [s for s in serve[lo:hi] if s[1] <= b]
        n_serve += len(inside)
        tk = [(x, y) for x, y, n in inside if n == TICK]
        kids = [(x, y, _kind(n)) for x, y, n in inside if n != TICK]
        host.append(idle(a, b))
        prep.append(sum(idle(x, y) for x, y, k in kids if k == "prep"))
        commit.append(sum(idle(x, y) for x, y, k in kids if k == "commit"))
        tself.append(sum(idle(x, y) for ta, tb in tk
                         for x, y in gaps(ta, tb, [(x, y) for x, y, _ in kids
                                                   if ta <= x and y <= tb])))
        outside.append(sum(idle(x, y) for x, y in gaps(a, b, tk)))

    # device time by scope: leaf ops (as devtrace's top_ops) of the calls
    # that lie wholly inside the window
    mods = sorted((e.start, e.end, devtrace.program_name(e.name))
                  for e in events if e.plane == c0
                  and e.line == devtrace.MODULES_LINE
                  and e.start >= w0 and e.end <= w1)
    calls: dict[str, int] = {}
    for _, _, name in mods:
        calls[name] = calls.get(name, 0) + 1
    mstarts = [m[0] for m in mods]
    named = paths.get(c0, {})
    scopes: dict[str, dict[str, float]] = {}
    ops.sort(key=lambda e: (e.start, -e.end))
    for i, e in enumerate(ops):
        if i + 1 < len(ops) and ops[i + 1].start < e.end:
            continue
        j = bisect.bisect_right(mstarts, e.start) - 1
        if j < 0 or e.end > mods[j][1]:
            continue
        t = scopes.setdefault(mods[j][2], {})
        sc = scope_of(named.get(e.name, ""))
        t[sc] = t.get(sc, 0.0) + (e.end - e.start)

    # idle gaps on chip 0, named by the innermost covering span of either
    # the benchmark or the program
    inner = sorted((e for e in spans if e.name != "bench.window"
                    and e.end > w0 and e.start < w1),
                   key=lambda e: (e.start, -e.end))
    istarts = [e.start for e in inner]

    def label(mid: float) -> str:
        for k in range(bisect.bisect_right(istarts, mid) - 1, -1, -1):
            if inner[k].end >= mid:
                return inner[k].name
        return "bench.loop"

    named_gaps = [(label((a + b) / 2), (b - a) * 1e-9)
                  for a, b in gaps(w0, w1, m0)]
    by_span: dict[str, float] = {}
    for n, s in named_gaps:
        by_span[n] = by_span.get(n, 0.0) + s
    named_gaps.sort(key=lambda kv: -kv[1])
    return SpanReduction(window=(w0, w1), ticks=ticks, serve_spans=n_serve,
                         host_ns=host, prep_ns=prep, commit_ns=commit,
                         tick_self_ns=tself, outside_ns=outside,
                         scopes=scopes, calls=calls,
                         idle_gaps=named_gaps[:top], idle_by_span=by_span)


def log_reduction(r: SpanReduction) -> None:
    """The scope table of every program, the tick's host time split by
    span, and the longest idle gaps, to standard error."""

    def log(*a):
        print(*a, file=sys.stderr, flush=True)

    for prog in sorted(r.scopes, key=lambda p: -sum(r.scopes[p].values())):
        t = r.scopes[prog]
        total = sum(t.values())
        n = r.calls.get(prog, 0)
        log(f"trace: device time by scope, {prog} ({n} calls, "
            f"{total * 1e-9:.4f} s): " + ", ".join(
                f"{k} {v * 1e-9:.4f} s ({100 * v / total:.1f}%)"
                for k, v in sorted(t.items(), key=lambda kv: -kv[1])))
    host = sum(r.host_ns)
    if r.serve_spans and host > 0:
        unnamed = sum(r.tick_self_ns) + sum(r.outside_ns)
        log(f"trace: host time per tick by span (ms, {len(r.ticks)} ticks): "
            f"prep {r.mean_ms(r.prep_ns):.4f}, commit "
            f"{r.mean_ms(r.commit_ns):.4f}, serve.tick outside its "
            f"children {r.mean_ms(r.tick_self_ns):.4f}, bench.tick outside "
            f"serve.tick {r.mean_ms(r.outside_ns):.4f}, of "
            f"{r.mean_ms(r.host_ns):.4f}; unnamed "
            f"{100 * unnamed / host:.2f}%")
    else:
        log("trace: no serve.* span in the traced ticks")
    log("trace: longest idle gaps by span (s) " + ", ".join(
        f"{n} {s:.6f}" for n, s in r.idle_gaps))
    log("trace: idle by span, engine spans included (s) " + ", ".join(
        f"{n} {s:.4f}" for n, s in sorted(r.idle_by_span.items(),
                                          key=lambda kv: -kv[1])))


def of(run) -> SpanReduction | None:
    """The run's span reduction, read once from the trace the harness
    kept (``run.spans`` where a caller supplies it)."""

    got = getattr(run, "spans", None)
    if got is not None or getattr(run, "red", None) is None:
        return got
    from bench import harness

    xp = sorted(harness.TRACE_DIR.glob("**/*.xplane.pb"))
    if not xp:
        return None
    got = reduce(*load(xp[-1]))
    if got.window != run.red.window:
        print("trace: the kept trace is not this run's; spans not read",
              file=sys.stderr, flush=True)
        return None
    run.spans = got
    log_reduction(got)
    return got
