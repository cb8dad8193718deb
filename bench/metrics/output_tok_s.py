"""Output tokens emitted inside the window over the window's seconds;
tokens of requests that began before the window count."""

from bench import stats


def read(run):
    return stats.tokens_in(run.stamps(), run.t0, run.t1) / run.seconds
