"""Flash attention as a Pallas TPU kernel with tunable block sizes.

The online-softmax recurrence streams K/V blocks through VMEM while the
(block_q, D) query block and its f32 running statistics (m, l, acc) stay
resident — the FlashAttention insight re-tiled for the TPU memory
hierarchy (HBM → VMEM → MXU):

* grid = (batch·heads, S/block_q, S/block_k); the k axis is innermost,
  so the scratch accumulators carry across sequential k steps;
* block_q/block_k are the paper-style tuning parameters: they trade
  VMEM residency against HBM re-streaming and grid overhead; the
  auto-tuner searches them (ops.tuning_space);
* out-of-range blocks (above the causal diagonal / beyond the sliding
  window) are skipped with ``pl.when`` — block-level sparsity, the TPU
  analogue of the paper's warp-divergence discussion;
* numerics: logits masked to a large negative, probabilities re-masked
  multiplicatively so fully-masked blocks contribute exact zeros; the
  final normalization guards l == 0 (rows with no visible keys).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import tpu_compiler_params

MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int | None,
                  block_q: int, block_k: int, k_steps: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level sparsity: is any (q, k) pair in range for this block?
    q_lo, q_hi = i * block_q, (i + 1) * block_q - 1
    k_lo, k_hi = j * block_k, (j + 1) * block_k - 1
    relevant = True
    if causal:
        relevant = jnp.logical_and(relevant, k_lo <= q_hi)
    if window is not None:
        relevant = jnp.logical_and(relevant, k_hi >= q_lo - window + 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0].astype(jnp.float32)          # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        qi = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        ki = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= ki <= qi
        if window is not None:
            mask &= ki >= qi - window + 1
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_ref[...]                        # (bq, 128) replicated
        m_curr = jnp.max(s, axis=-1, keepdims=True)           # (bq, 1)
        m_next = jnp.maximum(m_prev, jnp.broadcast_to(m_curr, m_prev.shape))
        alpha = jnp.exp(m_prev - m_next)                       # (bq, 128)
        p = jnp.exp(s - m_next[:, :1]) * mask                  # (bq, bk)
        l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), alpha.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_next

    @pl.when(j == k_steps - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         scale: float | None = None, causal: bool = True,
                         window: int | None = None, block_q: int = 512,
                         block_k: int = 512, interpret: bool = False
                         ) -> jax.Array:
    """q, k, v: (BH, S, D) with S divisible by the blocks."""

    BH, S, D = q.shape
    block_q, block_k = min(block_q, S), min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    scale = D ** -0.5 if scale is None else scale
    k_steps = S // block_k

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, k_steps=k_steps)

    return pl.pallas_call(
        kern,
        grid=(BH, S // block_q, k_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, D), jnp.float32),     # output accumulator
        ],
        compiler_params=tpu_compiler_params(),
        interpret=interpret,
    )(q, k, v)


__all__ = ["flash_attention_bhsd", "MASK_VALUE"]
