"""Pallas kernel: evaluate the abstract-platform wave model over a batch
of (WG, TS) configurations — the auto-tuner's inner loop as a TPU kernel.

The closed-form timing recurrence (repro/core/wave_model.py) is pure
elementwise integer arithmetic, a perfect VPU job: each grid step streams
a (block, 128) tile of configuration pairs through VMEM and emits model
times.  This is the logical endpoint of the beyond-paper speedup story:
SPIN explored the lattice state-by-state for hours; the vectorized sweep
does it in microseconds on host; this kernel does the same math on the
accelerator the framework is tuning — the tuner tunes *on* its target.

Supports kind="minimum" (the paper's §7 use case, warp-aware).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.wave_model import WaveParams
from ..common import tpu_compiler_params

SENTINEL = jnp.iinfo(jnp.int32).max


def _cdiv(a, b):
    return -(-a // b)


def _sweep_kernel(wg_ref, ts_ref, o_ref, *, p: WaveParams):
    WG = wg_ref[...].astype(jnp.int32)
    TS = ts_ref[...].astype(jnp.int32)
    size, NP, GMT, L = (jnp.int32(p.size), jnp.int32(p.NP),
                        jnp.int32(p.GMT), jnp.int32(p.L))

    items = size // jnp.maximum(TS, 1)
    full = items // jnp.maximum(WG, 1)
    rem = items % jnp.maximum(WG, 1)
    short = full == 0
    full = jnp.where(short, 0, full)
    rem = jnp.where(short, items, rem)
    g_total = full + (rem > 0).astype(jnp.int32)
    cnt_full = jnp.minimum(WG, items)

    def gmt_eff(resident):
        if p.warp is None:
            return jnp.broadcast_to(GMT, resident.shape)
        n_warps = jnp.maximum(1, _cdiv(resident, jnp.int32(p.warp)))
        return jnp.maximum(1, _cdiv(GMT, n_warps))

    def group_time(cnt):
        waves = _cdiv(cnt, NP)
        resident = jnp.minimum(cnt, NP)
        g = gmt_eff(resident)
        t = waves * g * TS                     # minimum-kernel wave time
        t = t + (resident - 1) + g
        return t + L

    U = jnp.int32(p.ND * p.NU)
    t_full = group_time(cnt_full)
    t_rem = jnp.where(rem > 0, group_time(jnp.maximum(rem, 1)), 0)
    count0 = _cdiv(g_total, U)
    r = (g_total - 1) % U
    count_r = _cdiv(g_total - r, U)
    t0 = count0 * t_full - jnp.where(r == 0, t_full - t_rem, 0)
    tr = count_r * t_full - (t_full - t_rem)
    device_t = jnp.where(rem > 0, jnp.maximum(t0, tr), count0 * t_full)
    t = device_t + g_total                     # host-side final reduce
    o_ref[...] = jnp.where(items >= 1, t, SENTINEL)


def sweep_eval_rows(wg: jax.Array, ts: jax.Array, p: WaveParams, *,
                    block_rows: int = 64, interpret: bool = False
                    ) -> jax.Array:
    """wg, ts: (rows, 128) int32 -> model times (rows, 128) int32."""

    assert p.kind == "minimum", "kernel implements the §7 Minimum model"
    rows, lanes = wg.shape
    assert lanes == 128 and rows % block_rows == 0, (wg.shape, block_rows)
    return pl.pallas_call(
        functools.partial(_sweep_kernel, p=p),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.int32),
        compiler_params=tpu_compiler_params(),
        interpret=interpret,
    )(wg, ts)


__all__ = ["sweep_eval_rows", "SENTINEL"]
