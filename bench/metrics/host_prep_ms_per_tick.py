"""Mean over the traced ticks of the device's idle time inside the
engine's host-prep spans, in ms: ``serve.admit``, ``serve.draft``,
``serve.back``, ``serve.pack.<step>`` and ``serve.dispatch.<step>``
(``bench/spans.py``).  Reads nothing where the program marks no
``serve.*`` span."""

from bench import spans


def read(run):
    r = spans.of(run)
    return None if r is None else r.mean_ms(r.prep_ns)
