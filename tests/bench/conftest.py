"""Puts the checkout root on ``sys.path`` so the tests import ``bench``,
and drives whole runs of small cells on the CPU."""

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture
def run_small(monkeypatch):
    """Drive a whole run of a small cell on the CPU: the look for a chip
    and the persistent compile cache are skipped, the rest is the run."""

    from bench import harness

    monkeypatch.setattr(harness, "use_cache", lambda: None)

    def run(cell, seed, **kw):
        return harness.run_cell(cell, seed, 1.5, False,
                                t_start=time.perf_counter(),
                                require_tpu=False, **kw)

    return run
