"""Device time per call of the paged decode step (``step_paged``) in
the dropless expert layer, in ms: its leaf ops under the ``route``
scope (router, softmax, top-k, the sort by expert) or the ``experts``
scope (the grouped matmuls and the weighted combine); the shared
experts and dense layers stay in plain ``mlp`` and are not counted
(``bench/metrics/_subscopes.py``).  Reads nothing where the step has
no such scopes."""

from bench.metrics import _subscopes

PROGRAMS = ("step_paged",)


def read(run):
    r = _subscopes.of(run, PROGRAMS[0])
    return None if r is None else r.ms_per_call("route", "experts")
