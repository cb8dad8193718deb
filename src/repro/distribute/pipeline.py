"""Pipeline parallelism: GPipe microbatch schedule over a "pp" mesh axis.

The stacked layer-blocks pytree is sharded over "pp" (each stage holds a
contiguous slice); microbatch activations flow stage-to-stage via
``lax.ppermute`` inside a ``shard_map``.  The schedule runs
``M + P − 1`` ticks; stage p processes microbatch ``t − p`` at tick t
(classic GPipe bubbles).  Because the schedule is pure JAX, reverse-mode
autodiff through the scan+ppermute yields the backward pipeline
automatically (cooldown order), so the same function serves training.

This complements the DP/TP/EP/FSDP axes of the main mesh: for depth-
dominated models a "pp" axis can replace part of "model"
(mesh (pp, data, model)); the dry-run exercises it via
tests/test_pipeline.py on an 8-device host mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(stage_fn: Callable, stage_params, x: jax.Array, *, mesh: Mesh,
          microbatches: int, axis: str = "pp") -> jax.Array:
    """Run ``x`` through P pipeline stages.

    stage_fn(params_stage, act) -> act applies ONE stage's layer slice;
    stage_params: pytree with leading dim = total stages' units stacked,
    shardable over ``axis`` (leading dim must equal the axis size);
    x: (B, ...) with B divisible by ``microbatches``."""

    n_stages = mesh.shape[axis]
    M = microbatches
    B = x.shape[0]
    assert B % M == 0, (B, M)
    xm = x.reshape(M, B // M, *x.shape[1:])

    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def per_stage(params_local, xm_rep):
        p = jax.lax.axis_index(axis)
        T = M + n_stages - 1
        zero = jnp.zeros_like(xm_rep[0])

        def tick(carry, t):
            prev_out, outs = carry
            recv = jax.lax.ppermute(prev_out, axis, perm)
            mb = t - p
            active = (mb >= 0) & (mb < M)
            mb_c = jnp.clip(mb, 0, M - 1)
            inp = jnp.where(p == 0, xm_rep[mb_c], recv)
            out = stage_fn(params_local, inp)
            out = jnp.where(active, out, zero)
            write = ((p == n_stages - 1) & active).astype(out.dtype)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, outs[mb_c] * (1 - write) + out * write, mb_c, 0)
            return (out, outs), None

        (last, outs), _ = jax.lax.scan(
            tick, (zero, jnp.zeros_like(xm_rep)), jnp.arange(T))
        # only the last stage holds real outputs; psum replicates them
        return jax.lax.psum(outs, axis)

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_stage, mesh=mesh, in_specs=(spec_params, P()),
                       out_specs=P(), check_vma=False)
    outs = fn(stage_params, xm)
    return outs.reshape(B, *x.shape[1:])


__all__ = ["gpipe"]
