"""Jitted wrapper + ``repro.tune`` integration for the blocked matmul.

``matmul_tuned(a, b)`` with block sizes omitted resolves (bm, bn, bk)
through the ``@autotune`` decorator: the :class:`MatmulTunable` built
from the operand shapes is tuned on first sight (grid over the cost
model) and served from the persistent :class:`~repro.tune.TuningCache`
afterwards.  Explicit block sizes bypass tuning entirely.
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
from .kernel import matmul
from .ref import matmul_ref


def tuning_space(M: int, N: int, K: int, dtype_bytes: int = 2
                 ) -> SearchSpace:
    """Block lattices: MXU-aligned powers of two dividing the problem,
    whose VMEM footprint fits the kernels' scoped limit."""

    def divisors_pow2(dim: int, lo: int) -> tuple[int, ...]:
        vals = []
        v = lo
        while v <= dim:
            if dim % v == 0:
                vals.append(v)
            v *= 2
        return tuple(vals) or (min(lo, dim),)

    space = SearchSpace(params=[
        Param("bm", divisors_pow2(M, 128)),
        Param("bn", divisors_pow2(N, 128)),
        Param("bk", divisors_pow2(K, 128)),
    ])
    # a, b and out blocks are double-buffered; the compiler stages one
    # more copy of the a block for the MXU, and the f32 accumulator and
    # the f32 product of one k step are allocated once
    space.constraints.append(lambda c: fits_vmem(
        blocks=(c["bm"] * c["bk"] + c["bk"] * c["bn"]
                + c["bm"] * c["bn"]) * dtype_bytes,
        scratch=c["bm"] * c["bk"] * dtype_bytes + 2 * c["bm"] * c["bn"] * 4))
    return space


def cost_model(cfg: dict, *, M: int, N: int, K: int, dtype_bytes: int = 2,
               peak_tflops: float = 197.0, hbm_gbps: float = 819.0,
               grid_overhead_us: float = 0.6) -> float:
    """Modeled microseconds for the full matmul on one v5e chip.

    HBM traffic counts the *re-streaming* of A and B panels: A is read
    N/bn times, B is read M/bm times — exactly the tile-size trade-off
    the paper tunes with TS, transposed to the MXU/VMEM world.  Compute
    and memory overlap on TPU (async copy engines), so time is the max
    of the two plus grid dispatch overhead."""

    bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
    flops = 2 * M * N * K
    compute_us = flops / (peak_tflops * 1e6)
    a_bytes = M * K * dtype_bytes * (N // bn)
    b_bytes = K * N * dtype_bytes * (M // bm)
    o_bytes = M * N * dtype_bytes
    mem_us = (a_bytes + b_bytes + o_bytes) / (hbm_gbps * 1e3)
    steps = (M // bm) * (N // bn) * (K // bk)
    return max(compute_us, mem_us) + steps * grid_overhead_us


@dataclass(frozen=True)
class MatmulTunable:
    """``repro.tune`` Tunable: (bm, bn, bk) block sizes for an
    (M, K) x (K, N) matmul."""

    M: int
    N: int
    K: int
    dtype_bytes: int = 2
    name: ClassVar[str] = "kernels.matmul_tuned"

    def space(self) -> SearchSpace:
        return tuning_space(self.M, self.N, self.K, self.dtype_bytes)

    def cost(self, cfg: Mapping[str, Any]) -> float:
        return cost_model(cfg, M=self.M, N=self.N, K=self.K,
                          dtype_bytes=self.dtype_bytes)

    def measure(self, cfg: Mapping[str, Any], *, warmup: int = 1,
                iters: int = 3) -> float:
        """Wall-clock microseconds of the real kernel at this block
        config (hardware oracle; interpret mode on CPU)."""

        dtype = jnp.bfloat16 if self.dtype_bytes == 2 else jnp.float32
        a = jnp.ones((self.M, self.K), dtype)
        b = jnp.ones((self.K, self.N), dtype)
        run = lambda: _matmul_call(a, b, bm=cfg["bm"], bn=cfg["bn"],
                                   bk=cfg["bk"], interpret=None)
        return time_fn(run, warmup=warmup, iters=iters)

    def fingerprint(self) -> dict[str, Any]:
        return {"tunable": self.name, "M": self.M, "N": self.N, "K": self.K,
                "dtype_bytes": self.dtype_bytes}


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _matmul_call(a: jax.Array, b: jax.Array, *, bm: int, bn: int, bk: int,
                 interpret: bool | None) -> jax.Array:
    return matmul(a, b, bm=bm, bn=bn, bk=bk,
                  interpret=resolve_interpret(interpret))


@autotune(lambda a, b, **kw: MatmulTunable(M=a.shape[0], N=b.shape[1],
                                           K=a.shape[1],
                                           dtype_bytes=a.dtype.itemsize),
          params=("bm", "bn", "bk"))
def matmul_tuned(a: jax.Array, b: jax.Array, *, bm: int | None = None,
                 bn: int | None = None, bk: int | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """Blocked matmul; omitted block sizes are auto-tuned (cached)."""

    return _matmul_call(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)


__all__ = ["matmul_tuned", "MatmulTunable", "tuning_space", "cost_model",
           "matmul_ref"]
