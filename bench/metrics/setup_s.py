"""Seconds from process start to the window's start: weights, server,
compiles or cache reads, warm-up and the traffic's ramp."""


def read(run):
    return run.setup_s
