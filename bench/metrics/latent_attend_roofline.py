"""Roofline share of latent attention in the paged decode step, in %:
the mean least time of the absorbed attention over the live latent
rows (``Model.attend_decode`` of the configuration's count: every
layer's live rows read and new ones written, ``W_uk``, ``W_uv`` and
``W_o`` read once) over the traced ticks that ran ``step_paged``, over
the mean device time a call spends in leaf ops whose innermost scope
(``bench/spans.py``) is ``kv_read`` (the page gather and its upcast)
or ``attend`` (which holds ``absorb``).  Reads nothing where the count
has no latent attention or the step no named scope."""

from bench import cost, spans

PROGRAMS = ("step_paged",)


def read(run):
    r = spans.of(run)
    prog = PROGRAMS[0]
    work = getattr(run.model, "attend_decode", None)
    if (r is None or work is None or run.peaks is None
            or not r.scoped(prog) or not r.calls.get(prog)):
        return None
    t = r.scopes[prog]
    spent = (t.get("kv_read", 0.0) + t.get("attend", 0.0)) / r.calls[prog]
    least = [cost.least_time(*work(tk.decode), run.peaks)
             for tk, progs in zip(run.traced_ticks, run.red.tick_programs)
             if prog in progs and tk.decode]
    if not least or spent <= 0:
        return None
    return 100.0 * (sum(least) / len(least)) / (spent * 1e-9)
