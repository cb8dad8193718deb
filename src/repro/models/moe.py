"""Mixture-of-Experts layer: token-choice top-k routing with capacity,
scatter-based dispatch (MODEL_FLOPS-aligned, unlike one-hot GShard
dispatch whose (T,E,C) tensors are quadratic in tokens).

Sharding: expert weights carry ("experts", "embed", "expert_mlp") logical
axes.  Rules decide expert parallelism ("experts" -> "model", llama4's
128 experts) vs per-expert tensor parallelism ("expert_mlp" -> "model",
mixtral's 8 × 16384).  The auto-tuner flips these — the arch-dependent
tuning parameter of DESIGN.md §4.

A reference one-hot einsum dispatch (``moe_forward_einsum``) validates
the scatter path numerically on small shapes.

``MoECfg.dropless`` selects the other layer, :func:`held_experts_forward`
(DeepSeek-V2): every token is routed, none dropped, and the layer
computes the part of the result that the experts it holds give — an
expert-parallel share ``held_from .. held_from + held - 1`` of the
router's outputs, all of them by default — as grouped matmuls over the
rows routed to each (``jax.lax.ragged_dot``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..distribute.sharding import logical_constraint as lc
from .common import PSpec


def moe_specs(cfg: ArchConfig) -> dict:
    """The router over all ``num_experts``; the weights of the experts
    held (``n_held``) at width ``d_expert``; the shared experts as one
    SwiGLU of ``shared_experts * d_expert``."""

    m, d = cfg.moe, cfg.d_model
    f = m.d_expert or cfg.d_ff
    E, n = m.num_experts, m.n_held
    specs = {
        "router": PSpec((d, E), ("embed", "experts"), dtype=jnp.float32),
        "wg": PSpec((n, d, f), ("experts", "embed", "expert_mlp")),
        "wu": PSpec((n, d, f), ("experts", "embed", "expert_mlp")),
        "wd": PSpec((n, f, d), ("experts", "expert_mlp", "embed")),
    }
    if m.shared_experts:
        fs = m.shared_experts * f
        specs["shared_wg"] = PSpec((d, fs), ("embed", "mlp"))
        specs["shared_wu"] = PSpec((d, fs), ("embed", "mlp"))
        specs["shared_wd"] = PSpec((fs, d), ("mlp", "embed"))
    return specs


def capacity(cfg: ArchConfig, tokens: int) -> int:
    m = cfg.moe
    c = int(m.top_k * tokens * m.capacity_factor / m.num_experts)
    return max(8, -(-c // 8) * 8)  # pad to 8 for TPU-friendly shapes


def _route(cfg: ArchConfig, p: dict, x2: jax.Array):
    """x2: (T, d) -> gates (T, k) f32, idx (T, k) int32."""

    logits = (x2.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    k = cfg.moe.top_k
    if not cfg.moe.renormalize:
        # softmax over every expert, then its top-k as they are
        return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    top, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)  # renormalize over the top-k
    return gates, idx


def _shared(p: dict, x2: jax.Array) -> jax.Array:
    return (jax.nn.silu(x2 @ p["shared_wg"]) * (x2 @ p["shared_wu"])
            ) @ p["shared_wd"]


def held_routed(p: dict, cfg: ArchConfig, x2: jax.Array,
                valid: jax.Array | None = None) -> jax.Array:
    """The routed part of the dropless layer that this chip's experts
    give: x2 (T, d) -> (T, d), ``Σ gate_e · E_e(x)`` over the top-k
    experts ``e`` of each token that lie in the held share.  Rows where
    ``valid`` (T,) is False (padding, idle slots) are routed to no one.

    The (token, choice) pairs are sorted by held expert, those of no
    held expert last; the held ones' rows go through the experts as
    three grouped matmuls, and are put back in pair order (a gather,
    not a scatter-add) and summed over each token's choices.  No
    capacity: the shapes are bounded by T·k.  The grouped matmuls
    promise nothing for the rows past the held groups' total (on a TPU
    they are left unwritten), so a pair's output is selected by
    ``mine``, never multiplied by a zero gate."""

    m = cfg.moe
    T, d = x2.shape
    k, n = m.top_k, m.n_held
    with jax.named_scope("route"):
        gates, idx = _route(cfg, p, x2)                 # (T, k)
        local = idx - m.held_from
        mine = (local >= 0) & (local < n)
        if valid is not None:
            mine &= valid[:, None]
        e = jnp.where(mine, local, n).reshape(T * k)    # n: no held expert
        order = jnp.argsort(e, stable=True)
        sizes = jnp.zeros((n + 1,), jnp.int32).at[e].add(1)[:n]
        xs = jnp.take(x2, order // k, axis=0)           # (T*k, d)
    with jax.named_scope("experts"):
        h = jax.nn.silu(jax.lax.ragged_dot(xs, p["wg"], sizes)) * \
            jax.lax.ragged_dot(xs, p["wu"], sizes)
        y = jax.lax.ragged_dot(h, p["wd"], sizes)       # (T*k, d), sorted
        y = jnp.take(y, jnp.argsort(order), axis=0).reshape(T, k, d)
        out = jnp.where(mine[..., None],
                        y.astype(jnp.float32) * gates[..., None], 0.0)
        return out.sum(axis=1).astype(x2.dtype)


def held_experts_forward(p: dict, cfg: ArchConfig, x: jax.Array,
                         valid: jax.Array | None = None) -> jax.Array:
    """The dropless layer: x (B, S, d) -> (B, S, d), the held experts'
    part (:func:`held_routed`) plus the shared experts, which every
    chip computes alike.  ``valid`` (B, S) marks the rows that are
    tokens."""

    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    y = held_routed(p, cfg, x2,
                    None if valid is None else valid.reshape(B * S))
    if cfg.moe.shared_experts:
        y = y + _shared(p, x2)
    return y.reshape(B, S, d)


@jax.named_scope("mlp")
def moe_forward(p: dict, cfg: ArchConfig, x: jax.Array,
                valid: jax.Array | None = None) -> jax.Array:
    """x: (B, S, d) -> (B, S, d).  ``MoECfg.dropless`` takes
    :func:`held_experts_forward` (``valid``: (B, S), the rows that are
    tokens); otherwise the capacity dispatch below, which routes every
    row.

    GShard-style *group-wise* dispatch with the batch row as the group:
    tokens compete for expert capacity only within their own sequence, so
    every dispatch tensor keeps the batch dim — shardable over the data
    axes (a globally-flattened dispatch would force E·C·d to be
    replicated per device; see EXPERIMENTS.md §Perf).  Scatter/gather
    dispatch keeps HLO FLOPs at the model level (one-hot einsum dispatch
    is quadratic in tokens)."""

    if cfg.moe.dropless:
        return held_experts_forward(p, cfg, x, valid)
    B, S, d = x.shape
    E, k = cfg.moe.num_experts, cfg.moe.top_k
    C = capacity(cfg, S)                                  # per-row capacity
    gates, idx = _route(cfg, p, x.reshape(B * S, d))
    gates = gates.reshape(B, S, k)
    idx = idx.reshape(B, S, k)

    # rank of each (token, choice) within its expert, per row
    flat_e = idx.reshape(B, S * k)                        # (B, S*k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)   # (B, S*k, E)
    pos = jnp.cumsum(onehot, axis=1) - onehot
    my_pos = jnp.take_along_axis(
        pos, flat_e[..., None], axis=2)[..., 0]           # (B, S*k)
    keep = my_pos < C
    slot = jnp.where(keep, flat_e * C + my_pos, E * C)    # E*C = trash row

    x_rep = jnp.repeat(x, k, axis=1)                      # (B, S*k, d)
    rows = jnp.arange(B)[:, None]
    # scatter stays local per batch shard (slot indices are row-local);
    # the reshard to expert sharding below IS the EP all-to-all
    xd = jnp.zeros((B, E * C + 1, d), x.dtype).at[rows, slot].add(x_rep)
    xd = lc(xd, "batch", None, "embed")
    xd = xd[:, :E * C].reshape(B, E, C, d)
    xd = lc(xd, "batch", "experts", None, "embed")

    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xd, p["wg"])) * \
        jnp.einsum("becd,edf->becf", xd, p["wu"])
    h = lc(h, "batch", "experts", None, "expert_mlp")
    yo = jnp.einsum("becf,efd->becd", h, p["wd"])
    yo = lc(yo, "batch", "experts", None, "embed")

    flat = yo.reshape(B, E * C, d)
    flat = lc(flat, "batch", None, "embed")   # combine all-to-all back
    picked = jnp.take_along_axis(
        flat, jnp.clip(slot, 0, E * C - 1)[..., None], axis=1)
    picked = picked * (keep[..., None] *
                       gates.reshape(B, S * k)[..., None]).astype(x.dtype)
    y = picked.reshape(B, S, k, d).sum(axis=2)

    if cfg.moe.shared_experts:
        x2 = x.reshape(B * S, d)
        y = y + ((jax.nn.silu(x2 @ p["shared_wg"]) * (x2 @ p["shared_wu"])
                  ) @ p["shared_wd"]).reshape(B, S, d)
    return lc(y, "batch", "seq", "embed")


def moe_forward_einsum(p: dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """GShard-style one-hot dispatch oracle (small shapes only); same
    per-row capacity semantics as the scatter path."""

    B, S, d = x.shape
    E, k = cfg.moe.num_experts, cfg.moe.top_k
    C = capacity(cfg, S)
    gates, idx = _route(cfg, p, x.reshape(B * S, d))
    gates = gates.reshape(B, S * k)
    flat_e = idx.reshape(B, S * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=1) - onehot
    my_pos = jnp.take_along_axis(pos, flat_e[..., None], axis=2)[..., 0]
    keep = my_pos < C

    # dispatch/combine tensors (B, S*k, E, C)
    disp = (jax.nn.one_hot(flat_e, E)[..., None] *
            jax.nn.one_hot(jnp.clip(my_pos, 0, C - 1), C)[:, :, None, :])
    disp = disp * keep[..., None, None]

    x_rep = jnp.repeat(x, k, axis=1)                     # (B, S*k, d)
    xd = jnp.einsum("btec,btd->becd", disp.astype(x.dtype), x_rep)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xd, p["wg"])) * \
        jnp.einsum("becd,edf->becf", xd, p["wu"])
    yo = jnp.einsum("becf,efd->becd", h, p["wd"])
    comb = disp * gates[..., None, None]
    y = jnp.einsum("btec,becd->btd", comb.astype(x.dtype), yo)
    y = y.reshape(B, S, k, d).sum(axis=2)

    if cfg.moe.shared_experts:
        x2 = x.reshape(B * S, d)
        y = y + ((jax.nn.silu(x2 @ p["shared_wg"]) * (x2 @ p["shared_wu"])
                  ) @ p["shared_wd"]).reshape(B, S, d)
    return y


__all__ = ["moe_specs", "moe_forward", "moe_forward_einsum", "capacity",
           "held_routed", "held_experts_forward"]
