"""Mean over the traced ticks of the device's idle time inside the
engine's commit spans, in ms: ``serve.readback.<step>``,
``serve.commit.<step>`` and ``serve.trim`` (``bench/spans.py``).
Reads nothing where the program marks no ``serve.*`` span."""

from bench import spans


def read(run):
    r = spans.of(run)
    return None if r is None else r.mean_ms(r.commit_ns)
