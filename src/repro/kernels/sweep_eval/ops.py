"""Jitted wrapper + ``repro.tune`` integration for the on-device lattice
sweep kernel — the tuner tuning its own evaluator: ``block_rows`` for
the sweep kernel is itself resolved through ``@autotune`` when omitted.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, ClassVar, Mapping

import jax
import jax.numpy as jnp

from ...core.search_space import Param, SearchSpace
from ...core.wave_model import WaveParams
from ...tune import autotune
from ..common import fits_vmem, resolve_interpret, time_fn
from .kernel import SENTINEL, sweep_eval_rows
from .ref import sweep_ref

_LANES = 128
# tile-sized int32 temporaries the wave-model body keeps live at once: the
# TPU compiler needed 13-14 of them at 256-2048 rows, so 16 bounds it
_TEMPS = 16


def tuning_space(n: int) -> SearchSpace:
    """block_rows lattice: powers of two up to the (padded) data or the
    VMEM bound — three double-buffered int32 streams (WG, TS in; time
    out) per tile, plus the body's tile-sized int32 temporaries."""

    rows_total = max(8, -(-n // _LANES))
    vals = []
    r = 8
    while r <= max(8, rows_total) and fits_vmem(
            blocks=3 * r * _LANES * 4, scratch=_TEMPS * r * _LANES * 4):
        vals.append(r)
        r *= 2
    return SearchSpace(params=[Param("block_rows", tuple(vals) or (8,))])


def cost_model(cfg: dict, *, n: int, dtype_bytes: int = 4,
               hbm_gbps: float = 819.0, grid_overhead_us: float = 1.0) -> float:
    """Modeled microseconds: HBM streaming of the padded (WG, TS, out)
    arrays + per-grid-step dispatch.  Padding charges oversized blocks
    on small lattices; dispatch count charges undersized blocks."""

    tile = cfg["block_rows"] * _LANES
    padded = max(tile, -(-n // tile) * tile)
    steps = padded // tile
    stream_us = (3 * padded * dtype_bytes) / (hbm_gbps * 1e3)
    return stream_us + steps * grid_overhead_us


@dataclass(frozen=True)
class SweepEvalTunable:
    """``repro.tune`` Tunable: block_rows for an n-point lattice sweep."""

    n: int
    name: ClassVar[str] = "kernels.sweep_eval"

    def space(self) -> SearchSpace:
        return tuning_space(self.n)

    def cost(self, cfg: Mapping[str, Any]) -> float:
        return cost_model(cfg, n=self.n)

    def measure(self, cfg: Mapping[str, Any], *, warmup: int = 1,
                iters: int = 3) -> float:
        """Wall-clock microseconds of the real sweep kernel at this
        block config, on a representative platform (timing depends on
        the lattice size and block_rows, not the wave parameters)."""

        p = WaveParams(size=max(4, self.n), NP=4, GMT=4, kind="minimum")
        wg = jnp.ones((self.n,), jnp.int32)
        ts = jnp.ones((self.n,), jnp.int32)
        run = lambda: sweep_eval(wg, ts, p,
                                 block_rows=cfg["block_rows"], interpret=None)
        return time_fn(run, warmup=warmup, iters=iters)

    def fingerprint(self) -> dict[str, Any]:
        return {"tunable": self.name, "n": self.n}


@autotune(lambda wg, ts, p, **kw: SweepEvalTunable(n=int(wg.shape[0])),
          params=("block_rows",))
@functools.partial(jax.jit, static_argnames=("p", "block_rows", "interpret"))
def sweep_eval(wg: jax.Array, ts: jax.Array, p: WaveParams, *,
               block_rows: int | None = None, interpret: bool | None = None
               ) -> jax.Array:
    """Evaluate the Minimum-model time for flat config arrays (n,).

    Pads to a (rows, 128) view, runs the Pallas kernel, returns (n,).
    An omitted ``block_rows`` is auto-tuned (cached)."""

    interpret = resolve_interpret(interpret)
    n = wg.shape[0]
    tile = block_rows * _LANES
    padded = max(tile, -(-n // tile) * tile)
    pad = padded - n
    wg2 = jnp.pad(wg.astype(jnp.int32), (0, pad), constant_values=1)
    ts2 = jnp.pad(ts.astype(jnp.int32), (0, pad),
                  constant_values=p.size + 1)   # -> sentinel
    out = sweep_eval_rows(wg2.reshape(-1, _LANES), ts2.reshape(-1, _LANES),
                          p, block_rows=block_rows, interpret=interpret)
    return out.reshape(-1)[:n]


__all__ = ["sweep_eval", "SweepEvalTunable", "tuning_space", "cost_model",
           "sweep_ref", "SENTINEL"]
