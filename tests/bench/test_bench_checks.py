"""``correct`` comes out false when the timed path is broken underneath
a whole run, and when the fp8 control stands in for the program.

Each fault is planted in the program's model step, the run is the
benchmark's own (traffic, server, window, sample, reference), and the
limit is the tightest of the benchmark's cells.  The exchange between
chips has no fault to plant: every cell runs on one chip."""

import jax.numpy as jnp
import pytest
from small_cell import cell_limit, small_cell

from repro.models.api import ModelAPI

ORIG = ModelAPI.decode_step


def unchanged_state(self, params, state, *a, **kw):
    logits, _ = ORIG(self, params, state, *a, **kw)
    return logits, state


def half_the_batch(self, params, state, *a, **kw):
    logits, new = ORIG(self, params, state, *a, **kw)
    h = logits.shape[0] // 2
    # only the first half is computed; the rest repeats it
    return jnp.concatenate([logits[:logits.shape[0] - h], logits[:h]]), new


def altered_token(self, params, state, *a, **kw):
    logits, new = ORIG(self, params, state, *a, **kw)
    # every decoded token becomes its neighbour in the vocabulary
    return jnp.roll(logits, 1, axis=-1), new


def test_sound_run_is_correct(run_small):
    r = run_small(small_cell(64, 2, 256, cell_limit()), 2**33 + 11)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", [unchanged_state, half_the_batch,
                                   altered_token])
def test_fault_is_not_correct(run_small, monkeypatch, fault):
    monkeypatch.setattr(ModelAPI, "decode_step", fault)
    r = run_small(small_cell(64, 2, 256, cell_limit()), 2**33 + 11)
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_gap_logits"]["value"] > cell_limit()


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_fp8_control_is_not_correct(run_small, seed):
    """The control: the plain reference computed in fp8, one step below
    the bf16 the configurations state, read at the positions the
    program served.  At d_model 256 it fails the limit that the
    program's bf16 path passes."""

    r = run_small(small_cell(256, 4, 4096, cell_limit()), seed,
                  control="fp8")
    assert r["checks"]["max_gap_logits"]["value"] <= cell_limit()
    assert not r["control"]["correct"], r["control"]["checks"]
    assert r["control"]["checks"]["max_gap_logits"]["value"] > cell_limit()
