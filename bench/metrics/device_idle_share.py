"""1 - (union of device operation intervals) / (traced window), in %,
averaged over the chips used."""


def read(run):
    red = run.red
    if red is None or red.window_ns <= 0:
        return None
    return 100.0 * (1.0 - red.busy_ns / red.window_ns)
