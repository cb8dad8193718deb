"""Pallas TPU kernel for the Minimum problem (paper §7), TPU-adapted.

The paper's OpenCL kernel (Listing 10) maps a TS-element tile per work
item into GPU local memory and tree-reduces per workgroup.  The TPU
re-think (DESIGN.md §2):

* "local memory" is VMEM: the tunable tile is the *block* a grid step
  streams HBM→VMEM, shaped (block_rows, 128) so the trailing dim fills
  the VPU lanes (the reduction is a VPU job; there is no MXU work here);
* "workgroup" is a grid step: TPU grids are executed sequentially per
  core, so the cross-"workgroup" REDUCE (host-side in the paper's
  Listing 11) becomes an accumulator block that every grid step updates
  in place — Pallas guarantees the output block with a constant
  ``index_map`` stays resident in VMEM across the sequential grid;
* the paper's two tuning parameters survive: ``block_rows`` is TS (tile
  streamed per step) and the grid size plays WG's role (how many "work
  groups" the data splits into); the auto-tuner searches ``block_rows``.

The kernel reduces a (rows, 128) view; `ops.py` handles padding/reshape
from arbitrary 1-D inputs and the final 128-lane fold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import tpu_compiler_params


def _identity(op: str, dtype) -> jnp.ndarray:
    if op == "sum":
        return jnp.zeros((), dtype)
    info = (jnp.iinfo if jnp.issubdtype(dtype, jnp.integer) else jnp.finfo)(dtype)
    return jnp.array(info.max if op == "min" else info.min, dtype)


def _combine(op: str):
    return {"min": jnp.minimum, "max": jnp.maximum, "sum": jnp.add}[op]


def _reduce_kernel(x_ref, o_ref, *, op: str):
    """One grid step: fold this (block_rows, 128) tile into the
    (8, 128) accumulator block (kept in VMEM across steps)."""

    i = pl.program_id(0)
    comb = _combine(op)
    tile = x_ref[...]
    # fold block_rows -> 8 sublanes (keep a (8, 128) running tile so the
    # store stays aligned to the TPU (8, 128) vreg shape)
    r = tile.reshape(-1, 8, 128)
    part = {"min": jnp.min, "max": jnp.max, "sum": jnp.sum}[op](r, axis=0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = part

    @pl.when(i != 0)
    def _acc():
        o_ref[...] = comb(o_ref[...], part)


def reduce_rows(x: jax.Array, *, block_rows: int = 256, op: str = "min",
                interpret: bool = False) -> jax.Array:
    """Reduce a (rows, 128) array to an (8, 128) partial tile.

    rows must be a multiple of block_rows; block_rows a multiple of 8.
    """

    rows, lanes = x.shape
    assert lanes == 128, "kernel operates on 128-lane views"
    assert rows % block_rows == 0 and block_rows % 8 == 0, (rows, block_rows)
    grid = (rows // block_rows,)

    return pl.pallas_call(
        functools.partial(_reduce_kernel, op=op),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), x.dtype),
        compiler_params=tpu_compiler_params(),
        interpret=interpret,
    )(x)


__all__ = ["reduce_rows"]
