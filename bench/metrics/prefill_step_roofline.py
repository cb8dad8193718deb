"""Roofline share of the chunked paged prefill step (the program jitted
as ``pstep_paged``), counting only the valid chunk tokens, in %."""

from bench.metrics import _roofline

PROGRAMS = ("pstep_paged",)


def read(run):
    return _roofline.share(run, PROGRAMS[0],
                           lambda m, tk: m.prefill(tk.prefill))
