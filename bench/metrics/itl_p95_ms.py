"""95th percentile of every gap between consecutive output tokens of
every request, both tokens inside the window, in ms."""

from bench import stats


def read(run):
    p = stats.percentile(stats.token_gaps(run.stamps(), run.t0, run.t1), 95)
    return None if p is None else p * 1e3
