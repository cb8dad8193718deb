"""Shared model substrate: parameter specs with logical sharding axes,
norms, rotary embeddings, MLPs.

Parameters are declared as :class:`PSpec` pytrees (shape + logical axis
names + init).  The same spec tree serves three consumers:

* ``init_params``     — materialize arrays (smoke tests / real training),
* ``abstract_params`` — ShapeDtypeStructs (dry-run lowering, no alloc),
* ``axes_tree``       — logical-axis tree, resolved to PartitionSpecs by
  :mod:`repro.distribute.sharding` rules (which the auto-tuner mutates).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_DTYPE = jnp.bfloat16


@dataclass(frozen=True)
class PSpec:
    """Declarative parameter: shape, logical axes (one name per dim, or
    None for unsharded), init kind, dtype."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"          # normal | zeros | ones
    dtype: Any = DEFAULT_DTYPE
    scale: float | None = None    # stddev override for "normal"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_pspec(x) -> bool:
    return isinstance(x, PSpec)


def stack_specs(tree, n: int, axis_name: str | None = "layers"):
    """Add a leading stacked-layers dim of size n to every spec."""

    return jax.tree.map(
        lambda s: dataclasses.replace(
            s, shape=(n, *s.shape), axes=(axis_name, *s.axes)),
        tree, is_leaf=is_pspec)


def init_params(tree, rng: jax.Array):
    """Materialize a PSpec tree into arrays (deterministic per-leaf keys)."""

    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_pspec)
    keys = jax.random.split(rng, len(leaves))

    def make(spec: PSpec, key):
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, spec.dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, spec.dtype)
        fan_in = spec.shape[-1] if len(spec.shape) >= 1 else 1
        scale = spec.scale if spec.scale is not None else fan_in ** -0.5
        return (jax.random.normal(key, spec.shape, jnp.float32) * scale
                ).astype(spec.dtype)

    return treedef.unflatten([make(s, k) for s, k in zip(leaves, keys)])


def abstract_params(tree):
    """ShapeDtypeStructs for dry-run lowering — no device allocation."""

    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                        tree, is_leaf=is_pspec)


def axes_tree(tree):
    """The logical-axes pytree (leaf = tuple of axis names)."""

    return jax.tree.map(lambda s: s.axes, tree, is_leaf=is_pspec)


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------


@jax.named_scope("norm")
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding; x: (..., S, D), positions: (..., S)."""

    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq       # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # broadcast over head dims: x is (B, H, S, D), ang is (B, S, half)
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if d > 2 * half:  # odd head dims: pass through the tail
        rotated = jnp.concatenate([rotated, x[..., 2 * half:]], axis=-1)
    return rotated.astype(x.dtype)


# YaRN (arXiv:2309.00071) as DeepSeek-V2's modelling code computes it:
# below the correction range the frequencies keep their extrapolated
# value, above it they are divided by ``factor``, and a linear ramp
# over the range blends the two.


def yarn_correction_range(dim: int, theta: float, yarn) -> tuple[int, int]:
    """The rotary pair indices between which the ramp runs: where a pair
    turns ``beta_fast`` and ``beta_slow`` times over the original
    context."""

    def dim_of(rotations):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = math.floor(dim_of(yarn.beta_fast))
    high = math.ceil(dim_of(yarn.beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """(dim // 2,) float32 inverse frequencies of a YaRN-scaled rope."""

    expo = np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)
    extra = np.float32(1.0) / np.float32(theta) ** expo
    inter = extra / np.float32(yarn.factor)
    low, high = yarn_correction_range(dim, theta, yarn)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / np.float32(high - low), 0, 1)
    keep = np.float32(1.0) - ramp         # 1: extrapolate, 0: interpolate
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_attn_factor(yarn) -> float:
    """The factor on the softmax scale: ``mscale(mscale_all_dim)`` squared
    (1 without ``mscale_all_dim``)."""

    if yarn is None or not yarn.mscale_all_dim:
        return 1.0
    return yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2


def rope_yarn(x: jax.Array, positions: jax.Array, theta: float,
              yarn) -> jax.Array:
    """Rotary embedding on interleaved pairs ``(x[2i], x[2i+1])`` at the
    YaRN frequencies, cos and sin times ``mscale / mscale_all_dim``
    (DeepSeek-V2).  The output is in the published order: the pairs'
    first elements, then their second.  x: (..., S, D); positions:
    (..., S), broadcast over head dims as :func:`rope`."""

    d = x.shape[-1]
    freq = jnp.asarray(yarn_inv_freq(d, theta, yarn))
    ratio = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    ang = positions[..., None].astype(jnp.float32) * freq    # (..., S, d/2)
    cos, sin = jnp.cos(ang) * ratio, jnp.sin(ang) * ratio
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Token-mean CE; logits (..., V) any float dtype, computed in f32."""

    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


__all__ = [
    "PSpec", "is_pspec", "stack_specs", "init_params", "abstract_params",
    "axes_tree", "rms_norm", "rope", "rope_yarn", "yarn_inv_freq",
    "yarn_attn_factor", "swiglu", "softmax_cross_entropy",
    "DEFAULT_DTYPE",
]
