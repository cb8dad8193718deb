"""Unified decoder stack + enc-dec variant covering all 10 assigned
architectures, with scan-over-layers (O(1) HLO in depth) and
configurable remat.

Layer kinds (picked per arch family):

* dense   — pre-norm attention + MLP (minitron, qwen3, qwen1.5, smollm)
* moe     — attention + MoE FFN (mixtral every layer; llama4 every 2nd;
            deepseek-v2-lite: latent attention, a dropless layer over
            its held experts, after one leading dense layer)
* ssm     — Mamba2 SSD block + (optional) MLP; d_ff == 0 -> pure SSD stack
* hybrid  — parallel attention (SWA) and SSD heads on the same input,
            learned per-dim mix (hymba)
* cross   — gated cross-attention to stub image embeddings every N
            layers (llama-3.2-vision)
* enc-dec — whisper: bidirectional encoder over stub frame embeddings,
            causal decoder with per-layer cross-attention (sinusoidal
            positions; the learned-positions detail of real Whisper is
            immaterial to systems behaviour and noted in DESIGN.md)
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, ShapeSpec
from ..distribute.sharding import logical_constraint as lc
from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .common import (PSpec, abstract_params, axes_tree, init_params,
                     rms_norm, softmax_cross_entropy, stack_specs)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_specs(cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_act == "gelu":
        return {"w1": PSpec((d, f), ("embed", "mlp")),
                "b1": PSpec((f,), ("mlp",), init="zeros"),
                "w2": PSpec((f, d), ("mlp", "embed")),
                "b2": PSpec((d,), ("embed",), init="zeros")}
    return {"wg": PSpec((d, f), ("embed", "mlp")),
            "wu": PSpec((d, f), ("embed", "mlp")),
            "wd": PSpec((f, d), ("mlp", "embed"))}


@jax.named_scope("mlp")
def mlp_forward(p: dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    if cfg.mlp_act == "gelu":
        h = jax.nn.gelu(x @ p["w1"] + p["b1"])
        h = lc(h, "batch", "seq", "mlp")
        return lc(h @ p["w2"] + p["b2"], "batch", "seq", "embed")
    h = jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])
    h = lc(h, "batch", "seq", "mlp")
    return lc(h @ p["wd"], "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def _norm_spec(cfg):
    return PSpec((cfg.d_model,), ("embed",), init="ones")


def layer_specs(cfg: ArchConfig, kind: str) -> dict:
    d = cfg.d_model
    s: dict[str, Any] = {"ln1": _norm_spec(cfg)}
    if kind == "dense" or kind == "moe":
        s["attn"] = attn.attn_specs(cfg)
        s["ln2"] = _norm_spec(cfg)
        s["ffn"] = moe_mod.moe_specs(cfg) if kind == "moe" else mlp_specs(cfg)
    elif kind == "ssm":
        s["ssm"] = ssm_mod.ssm_specs(cfg)
        if cfg.d_ff:
            s["ln2"] = _norm_spec(cfg)
            s["ffn"] = mlp_specs(cfg)
    elif kind == "hybrid":
        s["attn"] = attn.attn_specs(cfg)
        s["ssm"] = ssm_mod.ssm_specs(cfg)
        s["mix"] = PSpec((2, d), (None, "embed"), init="ones", scale=0.5)
        s["ln2"] = _norm_spec(cfg)
        s["ffn"] = mlp_specs(cfg)
    elif kind == "cross":
        s["xattn"] = attn.attn_specs(cfg, cross=True)
        s["gate"] = PSpec((1,), (None,), init="zeros", dtype=jnp.float32)
        s["ln2"] = _norm_spec(cfg)
        s["ffn"] = mlp_specs(cfg)
    else:  # encoder layer (bidirectional dense)
        s["attn"] = attn.attn_specs(cfg)
        s["ln2"] = _norm_spec(cfg)
        s["ffn"] = mlp_specs(cfg)
    return s


def layer_forward(p: dict, cfg: ArchConfig, kind: str, x: jax.Array,
                  positions: jax.Array, *, enc_out: jax.Array | None = None,
                  causal: bool = True) -> jax.Array:
    h = rms_norm(x, p["ln1"])
    if kind in ("dense", "moe", "encoder"):
        a = attn.attention(p["attn"], cfg, h, positions, causal=causal,
                           window=cfg.window, use_flash=cfg.use_flash)
        x = x + a
    elif kind == "ssm":
        x = x + ssm_mod.ssm_forward(p["ssm"], cfg, h)
    elif kind == "hybrid":
        a = attn.attention(p["attn"], cfg, h, positions, causal=True,
                           window=cfg.window, use_flash=cfg.use_flash)
        m = ssm_mod.ssm_forward(p["ssm"], cfg, h)
        x = x + p["mix"][0] * a + p["mix"][1] * m
    elif kind == "cross":
        a = attn.attention(p["xattn"], cfg, h, positions, x_kv=enc_out)
        x = x + jnp.tanh(p["gate"]).astype(x.dtype) * a
    if "ffn" in p:
        h2 = rms_norm(x, p["ln2"])
        if kind == "moe":
            x = x + moe_mod.moe_forward(p["ffn"], cfg, h2)
        else:
            x = x + mlp_forward(p["ffn"], cfg, h2)
    return lc(x, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Stacks (scan over layers; blocks for interleaved patterns)
# ---------------------------------------------------------------------------


def _periods(cfg: ArchConfig, layers: int, every: int, what: str) -> int:
    if layers % every:
        raise ValueError(f"{cfg.name}: {layers} layers are no whole number "
                         f"of periods of {every} ({what})")
    return layers // every


def _lead_plan(cfg: ArchConfig) -> tuple[list[str], int]:
    """(kinds, number) of the leading layers that run before the scanned
    blocks, as a stack of their own: ``MoECfg.first_dense`` dense layers
    (deepseek-v2-lite's first), else none."""

    if cfg.moe is not None and cfg.moe.first_dense:
        return ["dense"], cfg.moe.first_dense
    return [], 0


def _block_plan(cfg: ArchConfig) -> tuple[list[str], int]:
    """Returns (kinds within one block, number of blocks).  The stack is
    ``n_blocks`` repetitions of the block, scanned, after the leading
    layers of :func:`_lead_plan`."""

    if cfg.family == "dense":
        return ["dense"], cfg.n_layers
    if cfg.family == "ssm":
        return ["ssm"], cfg.n_layers
    if cfg.family == "hybrid":
        return ["hybrid"], cfg.n_layers
    if cfg.family == "moe":
        every = cfg.moe.every
        layers = cfg.n_layers - _lead_plan(cfg)[1]
        if every == 1:
            return ["moe"], layers
        return (["dense"] * (every - 1) + ["moe"],
                _periods(cfg, layers, every, "MoE layer every"))
    if cfg.family == "vlm":
        every = cfg.cross_attn_every
        return (["dense"] * (every - 1) + ["cross"],
                _periods(cfg, cfg.n_layers, every, "cross-attention every"))
    if cfg.family == "audio":
        return ["dense"], cfg.n_layers      # decoder; encoder built apart
    raise ValueError(cfg.family)


def stack_param_specs(cfg: ArchConfig) -> dict:
    kinds, n_blocks = _block_plan(cfg)
    block = {f"{i}_{kind}": layer_specs(cfg, kind)
             for i, kind in enumerate(kinds)}
    specs: dict[str, Any] = {
        "embed": PSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=0.02),
        "ln_f": _norm_spec(cfg),
        "blocks": stack_specs(block, n_blocks),
    }
    lead, n_lead = _lead_plan(cfg)
    if n_lead:
        specs["lead"] = stack_specs(
            {f"{i}_{kind}": layer_specs(cfg, kind)
             for i, kind in enumerate(lead)}, n_lead)
    if not cfg.tie_embeddings:
        specs["unembed"] = PSpec((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    if cfg.family == "audio":
        enc_block = {"0_encoder": layer_specs(cfg, "encoder")}
        specs["enc_blocks"] = stack_specs(enc_block, cfg.encoder_layers)
        specs["enc_ln_f"] = _norm_spec(cfg)
        # decoder cross-attention lives in each decoder block
        xblock = {"x": attn.attn_specs(cfg, cross=True),
                  "ln_x": _norm_spec(cfg)}
        specs["xattn_blocks"] = stack_specs(xblock, cfg.n_layers)
    return specs


def _remat(cfg: ArchConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)  # full


def _scan_blocks(cfg: ArchConfig, blocks, x, positions, *, enc_out=None,
                 causal=True, kinds=None):
    kinds = kinds or _block_plan(cfg)[0]

    def body(carry, bp):
        h = carry
        for i, kind in enumerate(kinds):
            h = layer_forward(bp[f"{i}_{kind}"], cfg, kind, h, positions,
                              enc_out=enc_out, causal=causal)
        return h, None

    body = _remat(cfg, body)
    x, _ = jax.lax.scan(body, x, blocks)
    return x


def _scan_stack(cfg: ArchConfig, params: dict, x, positions, enc_out=None):
    """The decoder's layers: the leading ones, if any, then the blocks."""

    if "lead" in params:
        x = _scan_blocks(cfg, params["lead"], x, positions,
                         kinds=_lead_plan(cfg)[0])
    return _scan_blocks(cfg, params["blocks"], x, positions,
                        enc_out=enc_out)


@jax.named_scope("head")
def _logits(params: dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["ln_f"])
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = x @ params["unembed"]
    logits = lc(logits, "batch", "seq", "vocab")
    return logits.astype(jnp.dtype(cfg.logits_dtype))


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def forward_lm(params: dict, cfg: ArchConfig, tokens: jax.Array,
               img_embeds: jax.Array | None = None) -> jax.Array:
    """Decoder-only forward -> logits (B, S, V)."""

    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = lc(x, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = _scan_stack(cfg, params, x, positions, enc_out=img_embeds)
    return _logits(params, cfg, x)


def forward_encdec(params: dict, cfg: ArchConfig, tokens: jax.Array,
                   frames: jax.Array) -> jax.Array:
    """Whisper-style: encode stub frame embeddings, decode tokens."""

    B, Senc, d = frames.shape
    pos_e = jnp.broadcast_to(jnp.arange(Senc, dtype=jnp.int32), (B, Senc))
    enc = frames + _sinusoid(Senc, d, frames.dtype)
    enc = _scan_blocks(cfg, params["enc_blocks"], enc, pos_e, causal=False,
                       kinds=["encoder"])
    enc = rms_norm(enc, params["enc_ln_f"])

    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x + _sinusoid(S, d, x.dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(carry, bp):
        h = carry
        dp, xp = bp
        h = layer_forward(dp["0_dense"], cfg, "dense", h, positions)
        a = attn.attention(xp["x"], cfg, rms_norm(h, xp["ln_x"]), positions,
                           x_kv=enc)
        return h + a, None

    body = _remat(cfg, body)
    x, _ = jax.lax.scan(body, x, (params["blocks"], params["xattn_blocks"]))
    return _logits(params, cfg, x)


def _sinusoid(S: int, d: int, dtype) -> jax.Array:
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1
                           ).astype(dtype)[None]


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def hidden_lm(params: dict, cfg: ArchConfig, tokens: jax.Array,
              img_embeds: jax.Array | None = None) -> jax.Array:
    """Decoder trunk up to (and including) the final norm — no logits."""

    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = lc(x, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = _scan_stack(cfg, params, x, positions, enc_out=img_embeds)
    return rms_norm(x, params["ln_f"])


def _chunked_ce(params: dict, cfg: ArchConfig, h: jax.Array,
                labels: jax.Array, chunk: int) -> jax.Array:
    """CE without materializing (B, S, V): lax.map over sequence chunks
    (memory-term optimization; numerically identical to the fused CE —
    the per-token losses are averaged in one (B, S) mean, as there, not
    summed per chunk, which reassociates)."""

    B, S, d = h.shape
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    hc = h.reshape(B, nc, chunk, d).swapaxes(0, 1)       # (nc, B, chunk, d)
    lc_ = labels.reshape(B, nc, chunk).swapaxes(0, 1)

    w = params["embed"] if cfg.tie_embeddings else params["unembed"]

    def one(args):
        hx, lx = args
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", hx, w)
        else:
            logits = hx @ w
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lx[..., None], axis=-1)[..., 0]
        return lse - gold                                # (B, chunk)

    per_token = jax.lax.map(one, (hc, lc_))              # (nc, B, chunk)
    per_token = per_token.swapaxes(0, 1).reshape(B, nc * chunk)[:, :S]
    return jnp.mean(per_token)


def lm_loss(params: dict, cfg: ArchConfig, batch: dict) -> jax.Array:
    if cfg.is_encdec:
        logits = forward_encdec(params, cfg, batch["tokens"], batch["frames"])
        return softmax_cross_entropy(logits[:, :-1], batch["labels"][:, 1:])
    if cfg.loss_seq_chunk:
        h = hidden_lm(params, cfg, batch["tokens"], batch.get("img_embeds"))
        return _chunked_ce(params, cfg, h[:, :-1],
                           batch["labels"][:, 1:], cfg.loss_seq_chunk)
    logits = forward_lm(params, cfg, batch["tokens"],
                        batch.get("img_embeds"))
    return softmax_cross_entropy(logits[:, :-1], batch["labels"][:, 1:])


__all__ = [
    "mlp_specs", "mlp_forward", "layer_specs", "layer_forward",
    "stack_param_specs", "forward_lm", "forward_encdec", "lm_loss",
]
