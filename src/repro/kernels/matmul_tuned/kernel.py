"""Tunable blocked matmul in Pallas (the paper's §8 planned case study).

Grid (M/bm, N/bn, K/bk); the (bm, bn) output block has a constant
index_map over k, so it stays VMEM-resident while the sequential k steps
accumulate into it in f32 (MXU-native accumulation).  The tunables are
the paper's tile sizes transposed to the MXU world: bm/bn/bk must be
multiples of the (8, 128) vreg / 128×128 MXU geometry; the auto-tuner
searches them against a VMEM/HBM/MXU cost model in ops.py."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import tpu_compiler_params


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
           bk: int = 512, interpret: bool = False) -> jax.Array:
    """a: (M, K), b: (K, N) -> (M, N); dims divisible by the blocks."""

    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    k_steps = K // bk

    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=tpu_compiler_params(),
        interpret=interpret,
    )(a, b)


__all__ = ["matmul"]
