"""Reduction of a ``jax.profiler`` trace to device busy time, program
time and host attribution.

The trace is read with ``jax.profiler.ProfileData`` (nothing but JAX).
Device planes are ``/device:TPU:<n>``; on each, operations sit on the
``XLA Ops`` line and whole programs on ``XLA Modules``.  The host plane
carries the benchmark's own ``jax.profiler.TraceAnnotation`` spans
(``bench.window``, ``bench.tick``, ``bench.submit``, ``bench.idle``) on
the same timeline.

* busy: the union of operation intervals on a chip, inside the window;
* a program's time: the summed durations of its module events, its
  name with ``jit_`` and the ``(<id>)`` suffix taken off; busy time
  outside the programs that the metric readers name is unattributed;
* host time of a tick: its span minus the busy union inside it;
* an idle gap: a stretch of the window with no operation on the chip,
  named by the innermost benchmark span that covers its middle, or
  ``bench.loop`` (the harness's own bookkeeping) where none does.
"""

from __future__ import annotations

import gzip
import re
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


@dataclass(frozen=True)
class Ev:
    plane: str
    line: str
    name: str
    start: float      # ns on the trace's timeline
    end: float


def load(path: str | Path) -> list[Ev]:
    """Every event of interest in an ``.xplane.pb`` (or ``.xplane.pb.gz``)."""

    from jax.profiler import ProfileData

    path = Path(path)
    data = path.read_bytes()
    if path.suffix == ".gz":
        data = gzip.decompress(data)
    pd = ProfileData.from_serialized_xspace(data)
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith(DEVICE_PREFIX)
        if not dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not dev and not e.name.startswith(SPAN_PREFIX):
                    continue
                out.append(Ev(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.end_ns)))
    return out


def union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: list[tuple[float, float]], a: float, b: float) -> float:
    """Length of ``[a, b]`` covered by the disjoint sorted ``merged``."""

    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged
               if y > a and x < b)


def program_name(module: str) -> str:
    """``jit_step_paged(1234)`` -> ``step_paged``."""

    name = re.sub(r"\(.*\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def op_label(hlo: str, width: int = 100) -> str:
    """An op event's HLO text cut to its name and result type."""

    head = hlo.split("{", 1)[0]
    return head[:width]


@dataclass
class Reduction:
    window: tuple[float, float]
    chips: list[str]
    busy_ns: float                          # mean over chips
    ticks: list[tuple[float, float]]        # host tick spans, in order
    tick_busy_ns: list[float]               # chip-0 busy inside each tick
    programs: dict[str, float]              # name -> summed module ns
    tick_programs: list[dict[str, float]]   # per tick: name -> module ns
    top_ops: list[tuple[str, float]]        # (op, s), most time first
    idle_gaps: list[tuple[str, float]]      # (span, s), longest first
    idle_by_span: dict[str, float] = field(default_factory=dict)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def unattributed_ns(self, named) -> float:
        """Busy time outside the ``named`` programs: what a reader that
        looks for those names cannot see (a renamed or merged step
        lands here)."""

        return self.busy_ns - sum(self.programs.get(n, 0.0) for n in named)


def reduce(events: list[Ev], top: int = 10) -> Reduction:
    spans = [e for e in events if e.plane == HOST_PLANE]
    win = [e for e in spans if e.name == "bench.window"]
    if not win:
        raise ValueError("trace holds no bench.window span")
    w0, w1 = win[0].start, win[0].end
    chips = sorted({e.plane for e in events
                    if e.plane.startswith(DEVICE_PREFIX)})
    if not chips:
        raise ValueError("trace holds no TPU device plane")

    def clip(e):
        return max(e.start, w0), min(e.end, w1)

    merged = {}
    for c in chips:
        ops = [clip(e) for e in events
               if e.plane == c and e.line == OPS_LINE and e.end > w0
               and e.start < w1]
        merged[c] = union([iv for iv in ops if iv[1] > iv[0]])
    busy = sum(covered(m, w0, w1) for m in merged.values()) / len(chips)
    m0 = merged[chips[0]]

    ticks = sorted((e.start, e.end) for e in spans
                   if e.name == "bench.tick" and e.start >= w0
                   and e.end <= w1)
    tick_busy = [covered(m0, a, b) for a, b in ticks]

    programs: dict[str, float] = {}
    tick_programs: list[dict[str, float]] = [{} for _ in ticks]
    starts = [a for a, _ in ticks]
    import bisect
    for e in events:
        if e.plane != chips[0] or e.line != MODULES_LINE:
            continue
        if e.end <= w0 or e.start >= w1:
            continue
        a, b = clip(e)
        name = program_name(e.name)
        programs[name] = programs.get(name, 0.0) + (b - a)
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= ticks[i][1]:
            d = tick_programs[i]
            d[name] = d.get(name, 0.0) + (e.end - e.start)

    # leaf operations only: a loop or call op spans the ops it runs
    ops0 = sorted((e for e in events if e.plane == chips[0]
                   and e.line == OPS_LINE), key=lambda e: (e.start, -e.end))
    op_time: dict[str, float] = {}
    for i, e in enumerate(ops0):
        if i + 1 < len(ops0) and ops0[i + 1].start < e.end:
            continue
        a, b = clip(e)
        if b > a:
            name = op_label(e.name)
            op_time[name] = op_time.get(name, 0.0) + (b - a)
    top_ops = sorted(((k, v * 1e-9) for k, v in op_time.items()),
                     key=lambda kv: -kv[1])[:top]

    # idle gaps on chip 0, named by the innermost covering span
    gaps = []
    prev = w0
    for a, b in m0 + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    inner = sorted((e for e in spans if e.name != "bench.window"
                    and e.end > w0 and e.start < w1),
                   key=lambda e: e.end - e.start)

    def label(mid):
        for e in inner:
            if e.start <= mid <= e.end:
                return e.name
        return "bench.loop"

    named = [(label((a + b) / 2), (b - a) * 1e-9) for a, b in gaps]
    by_span: dict[str, float] = {}
    for n, s in named:
        by_span[n] = by_span.get(n, 0.0) + s
    named.sort(key=lambda kv: -kv[1])
    return Reduction(window=(w0, w1), chips=chips, busy_ns=busy,
                     ticks=ticks, tick_busy_ns=tick_busy, programs=programs,
                     tick_programs=tick_programs, top_ops=top_ops,
                     idle_gaps=named[:top], idle_by_span=by_span)
