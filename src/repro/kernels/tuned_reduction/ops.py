"""Jitted public wrapper + ``repro.tune`` integration for the tuned
reduction kernel.

``reduce_1d`` handles arbitrary 1-D inputs: pad with the monoid identity
to a (rows, 128) view with rows divisible by block_rows, run the Pallas
kernel, fold the remaining (8, 128) tile with jnp.  ``block_rows`` is
the paper's TS; when omitted it resolves through ``@autotune`` (the
:class:`ReductionTunable` cost model is the TPU analogue of the abstract
platform's timing — HBM streaming dominates, the reduction is
memory-bound) and the persistent tuning cache.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, ClassVar, Mapping

import jax
import jax.numpy as jnp

from ...core.search_space import Param, SearchSpace
from ...tune import autotune
from ..common import fits_vmem, resolve_interpret, time_fn
from .kernel import _combine, _identity, reduce_rows
from .ref import reduce_ref

_LANES = 128


def tuning_space(n: int, dtype_bytes: int = 4) -> SearchSpace:
    """block_rows lattice: powers of two that (a) keep the double-buffered
    tile and the (8, 128) accumulator in VMEM and (b) do not exceed the
    data."""

    rows_total = max(8, n // _LANES)
    vals = []
    r = 8
    while r <= rows_total and fits_vmem(
            blocks=(r + 8) * _LANES * dtype_bytes):
        vals.append(r)
        r *= 2
    return SearchSpace(params=[Param("block_rows", tuple(vals) or (8,))])


def cost_model(cfg: dict, *, n: int, dtype_bytes: int = 4,
               hbm_gbps: float = 819.0, grid_overhead_us: float = 1.0) -> float:
    """Modeled kernel time in microseconds on one TPU v5e core.

    time = HBM streaming time + per-grid-step dispatch overhead.  This is
    the paper's GMT abstraction transposed: global-memory traffic
    dominates; the tunable tile size trades VMEM residency against grid
    dispatch count (the paper's TS ↔ launch-overhead trade-off)."""

    block_rows = cfg["block_rows"]
    tile = block_rows * _LANES
    steps = max(1, -(-n // tile))
    stream_us = (n * dtype_bytes) / (hbm_gbps * 1e3)  # bytes / (GB/s) -> us
    return stream_us + steps * grid_overhead_us


@dataclass(frozen=True)
class ReductionTunable:
    """``repro.tune`` Tunable: block_rows for an n-element reduction."""

    n: int
    op: str = "min"
    dtype_bytes: int = 4
    name: ClassVar[str] = "kernels.tuned_reduction"

    def space(self) -> SearchSpace:
        return tuning_space(self.n, dtype_bytes=self.dtype_bytes)

    def cost(self, cfg: Mapping[str, Any]) -> float:
        return cost_model(cfg, n=self.n, dtype_bytes=self.dtype_bytes)

    def measure(self, cfg: Mapping[str, Any], *, warmup: int = 1,
                iters: int = 3) -> float:
        """Wall-clock microseconds of the real kernel at this block
        config (hardware oracle; interpret mode on CPU)."""

        dtype = jnp.float32 if self.dtype_bytes == 4 else jnp.bfloat16
        x = jnp.ones((self.n,), dtype)
        run = lambda: reduce_1d(x, op=self.op,
                                block_rows=cfg["block_rows"], interpret=None)
        return time_fn(run, warmup=warmup, iters=iters)

    def fingerprint(self) -> dict[str, Any]:
        return {"tunable": self.name, "n": self.n, "op": self.op,
                "dtype_bytes": self.dtype_bytes}


@autotune(lambda x, **kw: ReductionTunable(n=int(x.shape[0]),
                                           op=kw.get("op", "min"),
                                           dtype_bytes=x.dtype.itemsize),
          params=("block_rows",))
@functools.partial(jax.jit, static_argnames=("op", "block_rows", "interpret"))
def reduce_1d(x: jax.Array, *, op: str = "min", block_rows: int | None = None,
              interpret: bool | None = None) -> jax.Array:
    """Reduce a 1-D array with the Pallas kernel (minimum by default);
    an omitted ``block_rows`` is auto-tuned (cached)."""

    interpret = resolve_interpret(interpret)
    ident = _identity(op, x.dtype)

    n = x.shape[0]
    tile = block_rows * _LANES
    padded = -(-n // tile) * tile
    if padded != n:
        x = jnp.concatenate([x, jnp.full((padded - n,), ident, x.dtype)])
    view = x.reshape(-1, _LANES)

    part = reduce_rows(view, block_rows=block_rows, op=op, interpret=interpret)
    full = {"min": jnp.min, "max": jnp.max, "sum": jnp.sum}[op]
    return full(part)


__all__ = ["reduce_1d", "ReductionTunable", "tuning_space", "cost_model",
           "reduce_ref"]
