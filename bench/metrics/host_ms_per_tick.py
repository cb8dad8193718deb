"""Mean over the traced ticks of a tick span's wall time minus the
device-busy union inside it, in ms: the host's own part of a tick."""


def read(run):
    red = run.red
    if red is None or not red.ticks:
        return None
    host = [(b - a) - busy for (a, b), busy in zip(red.ticks,
                                                  red.tick_busy_ns)]
    return sum(host) / len(host) * 1e-6
