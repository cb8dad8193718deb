"""Roofline share of the paged decode step (the program jitted as
``step_paged``): sum of least times over sum of device times, in %.
Least time per call: max(flops / peak, bytes / HBM bandwidth) for the
slots that decode, their live keys and new rows (``bench.cost``)."""

from bench.metrics import _roofline

PROGRAMS = ("step_paged",)


def read(run):
    return _roofline.share(run, PROGRAMS[0],
                           lambda m, tk: m.decode(tk.decode))
