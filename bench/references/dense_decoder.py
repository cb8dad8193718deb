"""Plain float32 reference of a dense pre-norm decoder (Llama/Qwen1.5).

Per layer: ``h += Wo·attn(rope(q), rope(k), v)`` on ``rms_norm(h)``,
then ``h += Wd·(silu(Wg·x) * (Wu·x))`` on ``rms_norm(h)``; causal
grouped-query attention, rotate-half RoPE, optional q/k/v bias, tied or
separate head.  No cache, no kernels, no batching tricks; every matmul
at ``jax.default_matmul_precision("highest")``.

It reads the parameter tree by name (``embed``, ``ln_f``, ``unembed``,
``blocks/0_dense/{ln1, attn/{wq, wk, wv, wo, bq, bk, bv}, ln2,
ffn/{wg, wu, wd}}``, layers stacked on the leading axis) and the sizes
from the configuration file; it imports nothing of the program.

``quant="fp8"`` is the control: the same mathematics with every matmul
operand (weights per output channel, activations per row) and the K/V
rounded through float8_e4m3fn, one step below the bf16 the
configurations state.

The sequences are run layer by layer, so only one layer's weights are
in float32 at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
E4M3_MAX = 448.0


def _q(x: jax.Array, axis, quant: str | None) -> jax.Array:
    """Round ``x`` through fp8 with one scale per slice along ``axis``
    (the contraction axes); identity when ``quant`` is None."""

    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quantisation {quant!r}")
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (B, H, S, D); rotate-half with frequencies theta^(-i/(D/2))."""

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None, :, None].astype(F32) * freq          # (B,1,S,half)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(p, h, cfg, quant):
    B, S, d = h.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    p = jax.tree.map(lambda a: a.astype(F32), p)
    a = p["attn"]
    x = _q(_rms(h, p["ln1"], eps), -1, quant)
    q = jnp.einsum("bsd,dhk->bhsk", x, _q(a["wq"], 0, quant))
    k = jnp.einsum("bsd,dhk->bhsk", x, _q(a["wk"], 0, quant))
    v = jnp.einsum("bsd,dhk->bhsk", x, _q(a["wv"], 0, quant))
    if cfg["qkv_bias"]:
        q = q + a["bq"][None, :, None, :]
        k = k + a["bk"][None, :, None, :]
        v = v + a["bv"][None, :, None, :]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    q = _rope(q, pos, cfg["rope_theta"])
    k = _q(_rope(k, pos, cfg["rope_theta"]), -1, quant)
    v = _q(v, -1, quant)
    g = H // Hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqk,bhsk->bhqs", q, k) * hd ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqs,bhsk->bhqk", jax.nn.softmax(s, -1), v)
    o = _q(o, (1, 3), quant)
    h = h + jnp.einsum("bhsk,hkd->bsd", o, _q(a["wo"], (0, 1), quant))
    f = p["ffn"]
    x = _q(_rms(h, p["ln2"], eps), -1, quant)
    m = jax.nn.silu(x @ _q(f["wg"], 0, quant)) * (x @ _q(f["wu"], 0, quant))
    return h + _q(m, -1, quant) @ _q(f["wd"], 0, quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer_jit(p, h, cfg_items, quant):
    with jax.default_matmul_precision("highest"):
        return _layer(p, h, dict(cfg_items), quant)


def _logits(params, h, cfg, quant):
    x = _rms(h, params["ln_f"].astype(F32), cfg["rms_norm_eps"])
    x = _q(x, -1, quant)
    if cfg["tie_word_embeddings"]:
        w = _q(params["embed"].astype(F32), 1, quant)
        return jnp.einsum("bsd,vd->bsv", x, w)
    return x @ _q(params["unembed"].astype(F32), 0, quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _head(params, h, score, cfg_items, quant):
    """(gap of ``score`` below the best logit, argmax) per position."""

    with jax.default_matmul_precision("highest"):
        logits = _logits(params, h, dict(cfg_items), quant)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, score[..., None], -1)[..., 0]
    return best - got, jnp.argmax(logits, -1).astype(jnp.int32)


_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "qkv_bias", "rope_theta", "tie_word_embeddings")


def hidden(params, cfg: dict, tokens: jax.Array,
           quant: str | None = None) -> jax.Array:
    """(B, S, d) float32 residual stream after the last layer."""

    items = tuple((k, cfg[k]) for k in _KEYS)
    h = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    h = _q(h, -1, quant)
    blocks = params["blocks"]["0_dense"]
    for i in range(cfg["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[i], blocks)
        h = _layer_jit(p, h, items, quant)
    return h


def logits(params, cfg: dict, tokens: jax.Array,
           quant: str | None = None) -> jax.Array:
    """(B, S, vocab) float32 logits; for small sizes (tests)."""

    h = hidden(params, cfg, tokens, quant)
    with jax.default_matmul_precision("highest"):
        return _logits(params, h, cfg, quant)


def run(params, cfg: dict, tokens: jax.Array, score: jax.Array,
        quant: str | None = None) -> tuple[jax.Array, jax.Array]:
    """tokens, score: (B, S) int32.  Returns ``(gap, argmax)``, each
    (B, S): at each position the reference's best logit minus its logit
    for ``score``, and the token it puts first."""

    items = tuple((k, cfg[k]) for k in _KEYS)
    h = hidden(params, cfg, tokens, quant)
    # one row at a time: a row's (S, vocab) float32 logits are the
    # largest array here
    rows = [_head(params, h[b:b + 1], score[b:b + 1], items, quant)
            for b in range(h.shape[0])]
    return (jnp.concatenate([r[0] for r in rows]),
            jnp.concatenate([r[1] for r in rows]))
