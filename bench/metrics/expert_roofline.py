"""Roofline share of the held experts' work in the paged decode step,
in %: the mean least time of that work over the traced ticks that ran
``step_paged`` (``Model.experts_decode`` of the configuration's count:
the rows routed here and the weights of the held experts hit), over
the mean device time a call spends under the ``experts`` scope
(``bench/metrics/_subscopes.py``).  Reads nothing where the count has
no expert layer or the step no such scope."""

from bench import cost
from bench.metrics import _subscopes

PROGRAMS = ("step_paged",)


def read(run):
    r = _subscopes.of(run, PROGRAMS[0])
    work = getattr(run.model, "experts_decode", None)
    spent = None if r is None else r.ms_per_call("experts")
    if spent is None or work is None or run.peaks is None:
        return None
    least = [cost.least_time(*work(len(tk.decode)), run.peaks)
             for tk, progs in zip(run.traced_ticks, run.red.tick_programs)
             if PROGRAMS[0] in progs and tk.decode]
    if not least:
        return None
    return 100.0 * (sum(least) / len(least)) / (spent * 1e-3)
