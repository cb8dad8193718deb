"""Device time per call of the paged decode step (``step_paged``) spent
moving the KV pool, in ms: its leaf operations whose innermost named
scope is ``kv_write`` (the one-hot scatter and pool-wide select) or
``kv_read`` (the page gather and its float32 upcast), and the ``layers``
scope's own time (the layer scan's stacking of each layer's new pool,
and the copies and converts the compiler adds inside the scan;
``bench/spans.py``).  Reads nothing where the step carries no named
scope."""

from bench import spans

PROGRAMS = ("step_paged",)
POOL = ("kv_write", "kv_read", "layers")


def read(run):
    r = spans.of(run)
    prog = PROGRAMS[0]
    if r is None or not r.scoped(prog) or not r.calls.get(prog):
        return None
    t = r.scopes[prog]
    return sum(t.get(k, 0.0) for k in POOL) / r.calls[prog] * 1e-6
