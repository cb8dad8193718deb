"""Jitted wrapper + ``repro.tune`` integration for flash attention.

``flash_attention(q, k, v)`` with block sizes omitted resolves
(block_q, block_k) through ``@autotune``: the
:class:`FlashAttentionTunable` built from the call's shapes/causality is
tuned on first sight and served from the persistent cache afterwards.
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
from .kernel import flash_attention_bhsd
from .ref import attention_ref


def tuning_space(S: int, D: int, dtype_bytes: int = 2) -> SearchSpace:
    def blocks(dim: int) -> tuple[int, ...]:
        vals = []
        v = 128
        while v <= min(dim, 4096):
            if dim % v == 0:
                vals.append(v)
            v *= 2
        return tuple(vals) or (min(dim, 128),)

    space = SearchSpace(params=[Param("block_q", blocks(S)),
                                Param("block_k", blocks(S))])
    # q, k, v and out blocks are double-buffered; the f32 m/l/acc scratch,
    # the f32 copies of q/k/v and the f32 (block_q, block_k) scores and
    # probabilities are allocated once
    space.constraints.append(lambda c: fits_vmem(
        blocks=2 * (c["block_q"] + c["block_k"]) * D * dtype_bytes,
        scratch=(c["block_q"] * (2 * 128 + 2 * D)
                 + 2 * c["block_k"] * D
                 + 2 * c["block_q"] * c["block_k"]) * 4))
    return space


def cost_model(cfg: dict, *, S: int, D: int, BH: int, causal: bool = True,
               window: int | None = None, dtype_bytes: int = 2,
               peak_tflops: float = 197.0, hbm_gbps: float = 819.0,
               grid_overhead_us: float = 0.6) -> float:
    """Modeled microseconds per chip: MXU time on visited blocks vs HBM
    re-streaming of K/V per q block (the block-size trade-off)."""

    bq, bk = cfg["block_q"], cfg["block_k"]
    nq, nk = S // bq, S // bk
    # visited (i, j) block pairs under causal (+ sliding-window) block
    # sparsity: a k block is visited iff it overlaps [qi - window + 1, qi]
    # for some query qi in the q block
    if causal:
        visited = 0
        for i in range(nq):
            hi = min(nk, ((i + 1) * bq - 1) // bk + 1)
            lo = 0 if window is None else max(0, (i * bq - window + 1) // bk)
            visited += hi - lo
    else:
        visited = nq * nk
    flops = 4 * BH * visited * bq * bk * D          # qk^T + pv
    compute_us = flops / (peak_tflops * 1e6)
    kv_bytes = BH * visited * bk * D * 2 * dtype_bytes
    q_bytes = BH * S * D * dtype_bytes * 2          # q read + o write
    mem_us = (kv_bytes + q_bytes) / (hbm_gbps * 1e3)
    return max(compute_us, mem_us) + BH * visited * grid_overhead_us / 16


@dataclass(frozen=True)
class FlashAttentionTunable:
    """``repro.tune`` Tunable: (block_q, block_k) for (B·H, S, D)
    attention under a causality mask."""

    S: int
    D: int
    BH: int
    causal: bool = True
    window: int | None = None
    dtype_bytes: int = 2
    name: ClassVar[str] = "kernels.flash_attention"

    def space(self) -> SearchSpace:
        return tuning_space(self.S, self.D, self.dtype_bytes)

    def cost(self, cfg: Mapping[str, Any]) -> float:
        return cost_model(cfg, S=self.S, D=self.D, BH=self.BH,
                          causal=self.causal, window=self.window,
                          dtype_bytes=self.dtype_bytes)

    def measure(self, cfg: Mapping[str, Any], *, warmup: int = 1,
                iters: int = 3) -> float:
        """Wall-clock microseconds of the real kernel at this block
        config (hardware oracle; interpret mode on CPU)."""

        dtype = jnp.bfloat16 if self.dtype_bytes == 2 else jnp.float32
        q = jnp.ones((1, self.BH, self.S, self.D), dtype)
        run = lambda: _flash_call(q, q, q, causal=self.causal,
                                  window=self.window,
                                  block_q=cfg["block_q"],
                                  block_k=cfg["block_k"], interpret=None)
        return time_fn(run, warmup=warmup, iters=iters)

    def fingerprint(self) -> dict[str, Any]:
        return {"tunable": self.name, "S": self.S, "D": self.D,
                "BH": self.BH, "causal": self.causal, "window": self.window,
                "dtype_bytes": self.dtype_bytes}


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def _flash_call(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
                window: int | None, block_q: int, block_k: int,
                interpret: bool | None) -> jax.Array:
    interpret = resolve_interpret(interpret)
    B, H, S, D = q.shape
    fold = lambda x: x.reshape(B * H, S, D)
    o = flash_attention_bhsd(fold(q), fold(k), fold(v), causal=causal,
                             window=window, block_q=block_q,
                             block_k=block_k, interpret=interpret)
    return o.reshape(B, H, S, D)


@autotune(lambda q, k, v, **kw: FlashAttentionTunable(
              S=q.shape[2], D=q.shape[3], BH=q.shape[0] * q.shape[1],
              causal=kw.get("causal", True), window=kw.get("window"),
              dtype_bytes=q.dtype.itemsize),
          params=("block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """q, k, v: (B, H, S, D).  GQA callers broadcast KV heads first.
    Omitted block sizes are auto-tuned (cached)."""

    return _flash_call(q, k, v, causal=causal, window=window,
                       block_q=block_q, block_k=block_k, interpret=interpret)


__all__ = ["flash_attention", "FlashAttentionTunable", "tuning_space",
           "cost_model", "attention_ref"]
