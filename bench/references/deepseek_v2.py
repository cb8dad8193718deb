"""Plain float32 reference of DeepSeek-V2 (the deepseek-v2-lite file).

Layer ``i``: ``h += W_o·attn(rms_norm(h))``, then ``h += ffn(rms_norm(h))``
where ``ffn`` is a dense SwiGLU for the first ``first_k_dense_replace``
layers and the expert layer after them.

* Attention (MLA, ``q_lora_rank`` null), decompressed: ``q = x·W_q``
  splits per head into ``q_nope`` (``qk_nope_head_dim``) and ``q_pe``
  (``qk_rope_head_dim``); ``[c, k_pe] = x·W_kv_a``, ``c = rms_norm(c)``;
  per head ``k_nope = c·W_uk`` and ``v = c·W_uv`` (the published
  ``kv_b_proj``, its two halves as two weights); YaRN rope on ``q_pe``
  and the one shared ``k_pe``; score ``(q_nope·k_nope + q_pe·k_pe) ·
  qk_head_dim^-½ · m²``, ``m = 0.1·mscale_all_dim·ln(factor) + 1``;
  causal softmax, ``·v``, then ``W_o``.
* YaRN as the published modelling code: frequencies blended by a linear
  ramp between the correction dims of ``beta_fast`` and ``beta_slow``
  over ``original_max_position_embeddings``, cos and sin times
  ``mscale(mscale) / mscale(mscale_all_dim)``, rotate-half after taking
  the rope dims' interleaved pairs apart.
* Experts: ``s = softmax(x·W_r)`` over every routed expert in float32,
  the top ``num_experts_per_tok`` of ``s`` not renormalised
  (``norm_topk_prob`` false, ``routed_scaling_factor`` 1), ``Σ s_e·E_e(x)``
  over the chosen experts that this chip holds (experts
  ``held_from .. held_from + n_routed_experts - 1`` of the router's
  outputs: the file's ``n_routed_experts`` and its ``program``), plus the
  shared experts, one SwiGLU.

No cache, no absorption, no grouped matmul: every held expert runs on
every token and is weighted by its gate (0 where not chosen).  Every
matmul at ``jax.default_matmul_precision("highest")``, layer by layer.
It reads the parameter tree by name (``embed``, ``unembed``, ``ln_f``,
``lead/0_dense`` and ``blocks/0_moe``, each ``{ln1, attn/{wq, wkv_a,
kv_norm, w_uk, w_uv, wo}, ln2, ffn/...}``, stacked on the leading axis)
and the sizes from the configuration file; it imports nothing of the
program.  ``quant="fp8"`` is the control, as in ``dense_decoder``: every
matmul operand and the cached latent rounded through float8_e4m3fn.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.references.dense_decoder import _q, _rms

F32 = jnp.float32


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(dim: int, theta: float, rs: dict):
    """(inverse frequencies (dim/2,), cos/sin factor, softmax factor)."""

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    expo = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / theta ** expo
    inter = extra / rs["factor"]
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv = inter * ramp + extra * (1 - ramp)
    cs = (_mscale(rs["factor"], rs["mscale"])
          / _mscale(rs["factor"], rs["mscale_all_dim"]))
    sm = _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), cs, sm


def _rope(x, pos, inv, cs):
    """x: (B, H, S, D); interleaved pairs taken apart, then rotate-half."""

    B, H, S, D = x.shape
    x = x.reshape(B, H, S, D // 2, 2).swapaxes(-1, -2).reshape(B, H, S, D)
    ang = pos[:, None, :, None].astype(F32) * jnp.asarray(inv)
    ang = jnp.concatenate([ang, ang], -1)
    c, s = jnp.cos(ang) * cs, jnp.sin(ang) * cs
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * c + rot * s


def _swiglu(x, wg, wu, wd, quant):
    m = jax.nn.silu(x @ _q(wg, -2, quant)) * (x @ _q(wu, -2, quant))
    return _q(m, -1, quant) @ _q(wd, -2, quant)


def _layer(p, h, cfg, quant, moe):
    B, S, d = h.shape
    H = cfg["num_attention_heads"]
    nope, rdim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r = cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    inv, cs, sm = yarn(rdim, cfg["rope_theta"], cfg["rope_scaling"])
    p = jax.tree.map(lambda a: a.astype(F32), p)
    a = p["attn"]
    x = _q(_rms(h, p["ln1"], eps), -1, quant)
    q = jnp.einsum("bsd,dhk->bhsk", x, _q(a["wq"], 0, quant))
    kv = x @ _q(a["wkv_a"], 0, quant)
    c = _q(_rms(kv[..., :r], a["kv_norm"], eps), -1, quant)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    k_pe = _q(_rope(kv[:, None, :, r:], pos, inv, cs), -1, quant)
    q_pe = _rope(q[..., nope:], pos, inv, cs)
    k_nope = jnp.einsum("bsc,chn->bhsn", c, _q(a["w_uk"], 0, quant))
    v = jnp.einsum("bsc,chv->bhsv", c, _q(a["w_uv"], 0, quant))
    s = (jnp.einsum("bhqn,bhsn->bhqs", q[..., :nope], k_nope)
         + jnp.einsum("bhqr,bxsr->bhqs", q_pe, k_pe))
    s = s * (nope + rdim) ** -0.5 * sm
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqs,bhsv->bhqv", jax.nn.softmax(s, -1), v)
    o = _q(o, (1, 3), quant)
    h = h + jnp.einsum("bhsv,hvd->bsd", o, _q(a["wo"], (0, 1), quant))
    f = p["ffn"]
    x = _q(_rms(h, p["ln2"], eps), -1, quant)
    if not moe:
        return h + _swiglu(x, f["wg"], f["wu"], f["wd"], quant)
    lo = cfg.get("program", {}).get("moe", {}).get("held_from", 0)
    k = cfg["num_experts_per_tok"]
    sc = jax.nn.softmax(x @ _q(f["router"], 0, quant), -1)   # (B, S, E)
    kth = jax.lax.top_k(sc, k)[0][..., -1:]
    y = _swiglu(x, f["shared_wg"], f["shared_wu"], f["shared_wd"], quant)
    for j in range(f["wg"].shape[0]):
        g = jnp.where(sc[..., lo + j:lo + j + 1] >= kth,
                      sc[..., lo + j:lo + j + 1], 0.0)
        y = y + g * _swiglu(x, f["wg"][j], f["wu"][j], f["wd"][j], quant)
    return h + y


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant", "moe"))
def _layer_jit(p, h, cfg_items, quant, moe):
    with jax.default_matmul_precision("highest"):
        return _layer(p, h, _unfreeze(cfg_items), quant, moe)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(params, h, score, eps, quant):
    """(gap of ``score`` below the best logit, argmax) per position."""

    with jax.default_matmul_precision("highest"):
        logits = _logits(params, h, eps, quant)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, score[..., None], -1)[..., 0]
    return best - got, jnp.argmax(logits, -1).astype(jnp.int32)


def _logits(params, h, eps, quant):
    x = _q(_rms(h, params["ln_f"].astype(F32), eps), -1, quant)
    return x @ _q(params["unembed"].astype(F32), 0, quant)


_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
         "kv_lora_rank", "rms_norm_eps", "rope_theta", "num_experts_per_tok")


def _freeze(cfg: dict):
    items = [(k, cfg[k]) for k in _KEYS]
    items.append(("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))))
    lo = cfg.get("program", {}).get("moe", {}).get("held_from", 0)
    items.append(("program", (("moe", (("held_from", lo),)),)))
    return tuple(items)


def _unfreeze(items):
    cfg = dict(items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    cfg["program"] = {"moe": dict(dict(cfg["program"])["moe"])}
    return cfg


def hidden(params, cfg: dict, tokens: jax.Array,
           quant: str | None = None) -> jax.Array:
    """(B, S, d) float32 residual stream after the last layer."""

    items = _freeze(cfg)
    h = _q(jnp.take(params["embed"], tokens, axis=0).astype(F32), -1, quant)
    for stack, moe in (("lead", False), ("blocks", True)):
        (blocks,) = params[stack].values()
        for i in range(jax.tree.leaves(blocks)[0].shape[0]):
            p = jax.tree.map(lambda a: a[i], blocks)
            h = _layer_jit(p, h, items, quant, moe)
    return h


def logits(params, cfg: dict, tokens: jax.Array,
           quant: str | None = None) -> jax.Array:
    """(B, S, vocab) float32 logits; for small sizes (tests)."""

    h = hidden(params, cfg, tokens, quant)
    with jax.default_matmul_precision("highest"):
        return _logits(params, h, cfg["rms_norm_eps"], quant)


def run(params, cfg: dict, tokens: jax.Array, score: jax.Array,
        quant: str | None = None) -> tuple[jax.Array, jax.Array]:
    """tokens, score: (B, S) int32.  Returns ``(gap, argmax)``, each
    (B, S), as ``dense_decoder.run``."""

    h = hidden(params, cfg, tokens, quant)
    rows = [_head(params, h[b:b + 1], score[b:b + 1], cfg["rms_norm_eps"],
                  quant) for b in range(h.shape[0])]
    return (jnp.concatenate([r[0] for r in rows]),
            jnp.concatenate([r[1] for r in rows]))
