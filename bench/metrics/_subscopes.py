"""Device time of a step program by the program's inner named scopes,
which ``bench/spans.py``'s ``SCOPES`` do not list: ``route`` and
``experts`` (inside ``mlp``, the dropless expert layer) and ``absorb``
(inside ``attend``, latent attention's ``q_nope·W_uk`` and
``o_lat·W_uv``).  ``spans.py`` gives these ops to ``mlp`` and
``attend``; here an op counts for a sub-scope where its ``tf_op`` path
holds ``/<scope>/``.  XLA lowers ``jax.lax.ragged_dot`` (the expert
layer's grouped matmuls) to kernels of its own, ``ragged-dot-*``, whose
op metadata holds that name and no scope path: they count as
``experts``.

It reads the trace the harness kept (``spans.load``), takes the leaf
ops (as ``spans.reduce`` does) of the calls of the program that lie
wholly inside the run's window, and sums them by sub-scope; a reader
divides by the calls counted.  A program without these scopes reads
zero in each, and its readers read nothing.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass

from bench import devtrace

SUB = ("route", "experts", "absorb")
RAGGED = "ragged-dot"


@dataclass
class SubScopes:
    program: str
    ns: dict[str, float]      # sub-scope -> leaf-op ns
    calls: int

    def ms_per_call(self, *subs: str) -> float | None:
        """Device ms a call in ``subs``; ``None`` where none ran."""

        ns = sum(self.ns[s] for s in subs)
        return ns / self.calls * 1e-6 if self.calls and ns > 0 else None


def reduce(events, paths, window, program: str) -> SubScopes:
    w0, w1 = window
    chips = sorted({e.plane for e in events
                    if e.plane.startswith(devtrace.DEVICE_PREFIX)})
    ns = dict.fromkeys(SUB, 0.0)
    if not chips:
        return SubScopes(program, ns, 0)
    c0 = chips[0]
    mods = sorted((e.start, e.end, devtrace.program_name(e.name))
                  for e in events if e.plane == c0
                  and e.line == devtrace.MODULES_LINE
                  and e.start >= w0 and e.end <= w1)
    calls = sum(1 for m in mods if m[2] == program)
    starts = [m[0] for m in mods]
    named = paths.get(c0, {})
    ops = sorted((e for e in events if e.plane == c0
                  and e.line == devtrace.OPS_LINE),
                 key=lambda e: (e.start, -e.end))
    for i, e in enumerate(ops):
        if i + 1 < len(ops) and ops[i + 1].start < e.end:
            continue                                   # not a leaf
        j = bisect.bisect_right(starts, e.start) - 1
        if j < 0 or e.end > mods[j][1] or mods[j][2] != program:
            continue
        path = named.get(e.name, "")
        if path.startswith(RAGGED):
            ns["experts"] += e.end - e.start
            continue
        for s in SUB:
            if f"/{s}/" in path:
                ns[s] += e.end - e.start
    return SubScopes(program, ns, calls)


def of(run, program: str = "step_paged") -> SubScopes | None:
    """The run's sub-scope times of ``program``, read once from the trace
    the harness kept; ``None`` without a trace of this run."""

    cache = getattr(run, "subscopes", None)
    if cache is None:
        cache = run.subscopes = {}
    if program in cache:
        return cache[program]
    got = None
    red = getattr(run, "red", None)
    if red is not None:
        from bench import harness, spans

        xp = sorted(harness.TRACE_DIR.glob("**/*.xplane.pb"))
        if xp:
            events, paths = spans.load(xp[-1])
            got = reduce(events, paths, red.window, program)
    cache[program] = got
    return got
