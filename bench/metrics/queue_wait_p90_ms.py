"""90th percentile of admitted minus due, over requests admitted inside
the window, in ms.  ``admitted`` is the end of the first tick after
which the request holds a slot, so the resolution is one tick."""

from bench import stats


def read(run):
    w = [r.admitted - r.due for r in run.recs
         if r.admitted is not None and run.t0 <= r.admitted < run.t1]
    p = stats.percentile(w, 90)
    return None if p is None else p * 1e3
