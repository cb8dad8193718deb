"""90th percentile of the program's own admission wait, in ms: a
request's ``t_admitted`` (its first placement into a slot) minus its
``t_submitted`` (``Server.submit``), both stamped by the ``Server`` on
``time.perf_counter()``, over requests admitted inside the window.
Reads nothing where the program keeps no such stamps."""

from bench import stats


def read(run):
    w = []
    for r in run.recs:
        a = getattr(r.handle, "t_admitted", None)
        s = getattr(r.handle, "t_submitted", None)
        if a is not None and s is not None and run.t0 <= a < run.t1:
            w.append(a - s)
    p = stats.percentile(w, 90)
    return None if p is None else p * 1e3
