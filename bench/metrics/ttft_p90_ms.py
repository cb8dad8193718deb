"""90th percentile, over every request whose first token falls in the
window, of first-token time minus the time it was due, in ms."""

from bench import stats


def read(run):
    p = stats.percentile(stats.first_token_latencies(run.recs, run.t0,
                                                     run.t1), 90)
    return None if p is None else p * 1e3
