"""GQA attention: qk-norm / qkv-bias / sliding-window / RoPE variants,
and DeepSeek-V2's multi-head latent attention (MLA); full-sequence
(train / prefill), single-token cached decode, and chunked cached
prefill (multi-token serving steps) paths.  The cached paths take a
cache of one or more leaves of per-token rows: K and V, or MLA's one
latent row, attended absorbed.

Pure-JAX math by default (XLA fuses this well on TPU); the Pallas flash
kernel (`repro.kernels.flash_attention`) is the opt-in runtime path via
``use_flash=True`` — block sizes resolve through ``@autotune`` and the
persistent tuning cache, interpret mode keeps it runnable on CPU, and
shapes the kernel cannot tile take the pure-JAX math.  The choice
depends on the shape alone, never on the backend: on a TPU every
tileable call runs the compiled kernel.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..distribute.sharding import logical_constraint as lc
from .common import (DEFAULT_DTYPE, PSpec, rms_norm, rope, rope_yarn,
                     yarn_attn_factor)

NEG_INF = -1e30


def attn_specs(cfg: ArchConfig, cross: bool = False) -> dict:
    if cfg.mla is not None and not cross:
        return mla_specs(cfg)
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    # q/k/v contract over d_model, so that is their fan-in (the default,
    # the last dim, is the head dim: q·k logits came out ~(d/hd)x too
    # large and a random model near-hard attention, chaotic under
    # rounding — bf16 paths then disagreed on almost every token)
    proj = d ** -0.5
    specs: dict[str, Any] = {
        "wq": PSpec((d, H, hd), ("embed", "heads", None), scale=proj),
        "wk": PSpec((d, Hkv, hd), ("embed", "kv_heads", None), scale=proj),
        "wv": PSpec((d, Hkv, hd), ("embed", "kv_heads", None), scale=proj),
        "wo": PSpec((H, hd, d), ("heads", None, "embed")),
    }
    if cfg.qkv_bias and not cross:
        specs["bq"] = PSpec((H, hd), ("heads", None), init="zeros")
        specs["bk"] = PSpec((Hkv, hd), ("kv_heads", None), init="zeros")
        specs["bv"] = PSpec((Hkv, hd), ("kv_heads", None), init="zeros")
    if cfg.qk_norm:
        specs["q_norm"] = PSpec((hd,), (None,), init="ones")
        specs["k_norm"] = PSpec((hd,), (None,), init="ones")
    return specs


def _project_qkv(p: dict, cfg: ArchConfig, xq: jax.Array, xkv: jax.Array):
    q = jnp.einsum("bsd,dhk->bhsk", xq, p["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", xkv, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", xkv, p["wv"])
    if "bq" in p:
        q = q + p["bq"][None, :, None, :]
        k = k + p["bk"][None, :, None, :]
        v = v + p["bv"][None, :, None, :]
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    return q, k, v


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    B, Hkv, S, hd = k.shape
    return jnp.repeat(k, n_rep, axis=1)


def _sdpa(q, k, v, mask, scale):
    s = jnp.einsum("bhqk,bhsk->bhqs", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bhsk->bhqk", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------
#
# q = x·W_q splits per head into q_nope and q_pe; [c, k_pe] = x·W_kv_a,
# c = rms_norm(c); per head k_nope = c·W_uk and v = c·W_uv.  rope (YaRN,
# ``cfg.rope_scaling``) acts on q_pe and on the one k_pe all heads
# share.  score = (q_nope·k_nope + q_pe·k_pe) · scale, the scale with
# YaRN's m².  What a token caches is its row [c ‖ k_pe]: the
# serving paths keep it as a cache of one "head" of width kv_lora_rank +
# qk_rope_head_dim, and attend absorbed: q_lat = q_nope·W_ukᵀ scores the
# latent directly, and o_lat = Σ p·c is lifted by W_uv after the sum.


def mla_specs(cfg: ArchConfig) -> dict:
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    r = m.kv_lora_rank
    return {
        "wq": PSpec((d, H, m.qk_head_dim), ("embed", "heads", None),
                    scale=d ** -0.5),
        "wkv_a": PSpec((d, m.cache_dim), ("embed", None), scale=d ** -0.5),
        "kv_norm": PSpec((r,), (None,), init="ones"),
        "w_uk": PSpec((r, H, m.qk_nope_head_dim), (None, "heads", None),
                      scale=r ** -0.5),
        "w_uv": PSpec((r, H, m.v_head_dim), (None, "heads", None),
                      scale=r ** -0.5),
        "wo": PSpec((H, m.v_head_dim, d), ("heads", None, "embed")),
    }


def _mla_scale(cfg: ArchConfig) -> float:
    return cfg.mla.qk_head_dim ** -0.5 * yarn_attn_factor(cfg.rope_scaling)


def _mla_project(p: dict, cfg: ArchConfig, x: jax.Array, pos: jax.Array):
    """x: (B, T, d) at positions pos (B, T) -> ``((q_nope, q_pe), {"ckv":
    rows})``: the roped query parts (B, H, T, ·) and each token's cached
    row [rms_norm(c) ‖ rope(k_pe)], (B, 1, T, cache_dim)."""

    r, nope = cfg.mla.kv_lora_rank, cfg.mla.qk_nope_head_dim
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv = x @ p["wkv_a"]                                 # (B, T, cache_dim)
    c = rms_norm(kv[..., :r], p["kv_norm"])
    k_pe = rope_yarn(kv[:, None, :, r:], pos, cfg.rope_theta,
                     cfg.rope_scaling)                  # (B, 1, T, rope)
    q_pe = rope_yarn(q_pe, pos, cfg.rope_theta, cfg.rope_scaling)
    row = jnp.concatenate([c[:, None], k_pe.astype(c.dtype)], axis=-1)
    return (q_nope, q_pe), {"ckv": row}


def mla_attention(p: dict, cfg: ArchConfig, x: jax.Array,
                  positions: jax.Array, *, causal: bool = True) -> jax.Array:
    """Full-sequence latent attention, decompressed: every head's keys
    and values are lifted from the latent (train / prefill forward)."""

    B, S, d = x.shape
    r = cfg.mla.kv_lora_rank
    (q_nope, q_pe), rows = _mla_project(p, cfg, x, positions)
    row = rows["ckv"][:, 0]                             # (B, S, cache_dim)
    c, k_pe = row[..., :r], row[:, None, :, r:]
    k_nope = jnp.einsum("bsc,chn->bhsn", c, p["w_uk"])
    v = jnp.einsum("bsc,chv->bhsv", c, p["w_uv"])
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:-1] + k_pe.shape[-1:])],
        axis=-1)
    if causal:
        mask = positions[:, None, None, :] <= positions[:, None, :, None]
    else:
        mask = jnp.ones((1, 1, S, S), bool)
    o = _sdpa(q, k, v, mask, _mla_scale(cfg))
    out = jnp.einsum("bhsv,hvd->bsd", o, p["wo"])
    return lc(out, "batch", "seq", "embed")


def _mla_attend(p: dict, cfg: ArchConfig, q, kv: dict, mask: jax.Array,
                dtype) -> jax.Array:
    """Absorbed latent attention of queries ``q = (q_nope, q_pe)`` (B, H,
    T, ·) over cached rows ``kv["ckv"]`` (B, 1, S, cache_dim) under
    ``mask`` (B, T, S); returns (B, T, d)."""

    q_nope, q_pe = q
    r = cfg.mla.kv_lora_rank
    with jax.named_scope("kv_read"):
        lat = kv["ckv"][:, 0].astype(jnp.float32)       # (B, S, cache_dim)
    with jax.named_scope("attend"):
        with jax.named_scope("absorb"):
            q_lat = jnp.einsum("bhtn,chn->bhtc", q_nope.astype(jnp.float32),
                               p["w_uk"].astype(jnp.float32))
        qc = jnp.concatenate([q_lat, q_pe.astype(jnp.float32)], axis=-1)
        s = jnp.einsum("bhtc,bsc->bhts", qc * _mla_scale(cfg), lat)
        s = jnp.where(mask[:, None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhts,bsc->bhtc", pr, lat[..., :r])
        with jax.named_scope("absorb"):
            o = jnp.einsum("bhtc,chv->bhtv", o_lat,
                           p["w_uv"].astype(jnp.float32))
        return jnp.einsum("bhtv,hvd->btd", o.astype(dtype), p["wo"])


# ---------------------------------------------------------------------------
# What the cached paths share: new rows, and attention over cached ones
# ---------------------------------------------------------------------------


def _project(p: dict, cfg: ArchConfig, x: jax.Array, pos: jax.Array):
    """x (B, T, d) at positions pos (B, T) -> (queries, new cache rows by
    leaf, each (B, heads, T, width)): roped q and {"k", "v"}, or MLA's
    query parts and {"ckv"}."""

    if cfg.mla is not None:
        return _mla_project(p, cfg, x, pos)
    q, k, v = _project_qkv(p, cfg, x, x)
    if cfg.use_rope:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    return q, {"k": k, "v": v}


def _attend(p: dict, cfg: ArchConfig, q, kv: dict, mask: jax.Array,
            dtype) -> jax.Array:
    """Queries over the cached rows ``kv`` (leaves (B, heads, S, width))
    under ``mask`` (B, T, S), through the output projection: (B, T, d)."""

    if cfg.mla is not None:
        return _mla_attend(p, cfg, q, kv, mask, dtype)
    o = _grouped_sdpa(q, kv["k"], kv["v"], mask[:, None, None],
                      cfg.hd ** -0.5).astype(dtype)
    return jnp.einsum("bhsk,hkd->bsd", o, p["wo"])


def _row_shape(cfg: ArchConfig) -> tuple[int, int]:
    """(heads, width) of one token's cached row in each cache leaf."""

    if cfg.mla is not None:
        return 1, cfg.mla.cache_dim
    return cfg.n_kv_heads, cfg.hd


def _cache_leaves(cfg: ArchConfig) -> tuple[str, ...]:
    return ("ckv",) if cfg.mla is not None else ("k", "v")


# Above this query length, attention processes queries in chunks so the
# f32 score tensor stays O(chunk·S) instead of O(S²) — the pure-JAX
# flash-attention-lite used by 32k prefill/train (the Pallas kernel is
# the TPU runtime path).  Chunk size is a tuning parameter.
Q_CHUNK_THRESHOLD = 8192
Q_CHUNK = 1024


def _sdpa_qchunked(q, k, v, positions, scale, *, causal, window,
                   chunk=Q_CHUNK):
    B, H, S, hd = q.shape
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        # padded queries mask out every key (position -1 precedes all
        # keys under the causal mask); their rows are sliced off below
        positions = jnp.pad(positions, ((0, 0), (0, pad)),
                            constant_values=-1)
    qs = q.reshape(B, H, nc, chunk, hd).transpose(2, 0, 1, 3, 4)
    # the caller's per-query positions, chunked alongside q — the mask
    # must honor them (offset prefill), not assume 0-based contiguity
    ps = positions.reshape(B, nc, chunk).transpose(1, 0, 2)  # (nc,B,chunk)
    ki = positions[:, None, None, :S]                   # (B,1,1,S)

    def one(args):
        qc, pc = args
        qi = pc[:, None, :, None]                       # (B,1,chunk,1)
        if causal:
            m = ki <= qi
            if window is not None:
                m &= ki >= qi - window + 1
        else:
            m = jnp.ones((1, 1, 1, S), bool)
        return _sdpa(qc, k, v, m, scale)

    out = jax.lax.map(one, (qs, ps))                    # (nc,B,H,chunk,hd)
    out = out.transpose(1, 2, 0, 3, 4).reshape(B, H, nc * chunk, hd)
    return out[:, :, :S]


def _flash_supported(S: int) -> bool:
    """Can the Pallas flash kernel serve this full-sequence call?  The
    kernel tiles S into >=128 blocks, so S must divide by 128.  The
    backend plays no part: the kernel compiles for a TPU and runs in
    interpret mode on the CPU."""

    return S % 128 == 0


def _positions_standard(positions: jax.Array, S: int) -> bool:
    """The flash kernel masks by absolute 0-based indices, so it
    requires ``positions == arange(S)``.  Concrete arrays are checked
    (offset prefill falls back to the pure-JAX path, which honors the
    caller's positions); under a trace the contiguity precondition is
    the caller's documented responsibility."""

    if isinstance(positions, jax.core.Tracer):
        return True
    try:
        return bool(jnp.all(positions ==
                            jnp.arange(S, dtype=positions.dtype)))
    except jax.errors.ConcretizationTypeError:
        # a concrete array can still be swept into an enclosing trace
        # (e.g. jax.checkpoint lifts closed-over constants); same
        # contract as the Tracer case above
        return True


def attention(p: dict, cfg: ArchConfig, x: jax.Array, positions: jax.Array,
              *, causal: bool = True, window: int | None = None,
              x_kv: jax.Array | None = None,
              use_flash: bool = False) -> jax.Array:
    """Full-sequence attention.  ``x_kv`` switches to cross-attention
    (no causal mask, no rope on kv positions beyond their own index).

    ``use_flash=True`` routes self-attention through the ``@autotune``d
    Pallas flash kernel (block sizes from the tuning cache; interpret
    mode on CPU).  The kernel derives its mask from absolute 0-based
    query/key indices, so the flash path requires the standard
    contiguous ``positions == arange(S)`` of train/prefill; untileable
    shapes take the pure-JAX math.
    """

    if cfg.mla is not None and x_kv is None:
        return mla_attention(p, cfg, x, positions, causal=causal)
    B, S, d = x.shape
    cross = x_kv is not None
    xkv = x_kv if cross else x
    q, k, v = _project_qkv(p, cfg, x, xkv)
    if cfg.use_rope and not cross:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    q = lc(q, "batch", "heads", "seq", None)
    k = lc(k, "batch", "heads", "seq", None)

    Skv = xkv.shape[1]
    if use_flash and not cross and _flash_supported(S) \
            and _positions_standard(positions, S):
        from ..kernels.flash_attention.ops import flash_attention
        # window only applies under causality in the pure-JAX paths;
        # match that here so use_flash never changes semantics
        o = flash_attention(q, k, v, causal=causal,
                            window=window if causal else None)
    elif (not cross) and causal and S > Q_CHUNK_THRESHOLD:
        o = _sdpa_qchunked(q, k, v, positions, cfg.hd ** -0.5,
                           causal=True, window=window)
    else:
        if cross or not causal:
            mask = jnp.ones((1, 1, S, Skv), bool)
        else:
            qi = positions[:, None, :, None]           # (B,1,S,1)
            ki = positions[:, None, None, :]           # (B,1,1,S)
            mask = ki <= qi
            if window is not None:
                mask &= ki >= qi - window + 1
        o = _sdpa(q, k, v, mask, cfg.hd ** -0.5)
    o = lc(o, "batch", "heads", "seq", None)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
    return lc(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Cached decode
# ---------------------------------------------------------------------------


def kv_cache_specs(cfg: ArchConfig, batch: int, cache_len: int,
                   dtype: Any = None) -> dict:
    # cache_seq -> "model" keeps 32k caches shardable even when kv_heads
    # do not divide the model axis (GQA kv=8 on 16-way TP); the axis
    # dedup keeps whichever dim claims "model" first.  ``dtype`` lets
    # callers match the cache to the params' compute dtype (a float32
    # model wants float32 K/V — quantizing through bfloat16 costs exact
    # greedy-parity guarantees downstream consumers rely on).
    # An MLA cache is one leaf, "ckv", of one "head" holding each
    # token's latent row.
    Hkv, hd = _row_shape(cfg)
    dt = dtype if dtype is not None else DEFAULT_DTYPE
    return {n: PSpec((batch, Hkv, cache_len, hd),
                     ("cache_batch", "kv_heads", "cache_seq", "head_dim"),
                     init="zeros", dtype=dt)
            for n in _cache_leaves(cfg)}


def decode_attention(p: dict, cfg: ArchConfig, x: jax.Array, cache: dict,
                     cur_len: jax.Array, *, window: int | None = None,
                     x_kv_cache: dict | None = None) -> tuple[jax.Array, dict]:
    """One-token attention against a KV cache.

    x: (B, 1, d); cache["k"/"v"]: (B, Hkv, C, hd) where C is the cache
    length (= window size for SWA — a ring buffer — else max context);
    cur_len: count of tokens already in the cache — a scalar, or a (B,)
    vector of per-slot counts so mixed-progress serving slots each get
    their own RoPE rotation, ring slot, and validity mask.  Keys are
    stored post-RoPE.  Returns (output, updated cache)."""

    B, one, d = x.shape
    C = next(iter(cache.values())).shape[2]
    cur_len = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (B,))
    positions = cur_len[:, None]                  # (B, 1)
    q, new = _project(p, cfg, x, positions)

    slot = jnp.mod(cur_len, C)                    # (B,) ring for SWA
    # one-hot masked update instead of dynamic_update_slice: elementwise,
    # so it stays local under ANY cache sharding (dynamic updates on a
    # sharded dim made GSPMD replicate the whole cache — §Perf cell B)
    hot = (jnp.arange(C)[None, :] == slot[:, None])[:, None, :, None]
    new_cache = {n: jnp.where(hot, new[n].astype(c.dtype), c)
                 for n, c in cache.items()}

    # validity per slot: ring index i last held absolute position
    # cur_len[b] - ((slot[b] - i) mod C)
    idx = jnp.arange(C)[None, :]                  # (1, C)
    cl = cur_len[:, None]                         # (B, 1)
    if window is not None:
        abs_pos = cl - jnp.mod(slot[:, None] - idx, C)
        valid = (abs_pos >= jnp.maximum(0, cl - window + 1)) & \
                (abs_pos <= cl)
    else:
        valid = idx <= cl                         # (B, C)

    # grouped GQA attention: contract q head-groups against the kv-head
    # cache directly — jnp.repeat's broadcast made GSPMD all-gather the
    # whole cache per layer (§Perf cell B, 8 GiB/block)
    out = _attend(p, cfg, q, new_cache, valid[:, None, :], x.dtype)
    return lc(out, "batch", "seq", "embed"), new_cache


def decode_attention_chunked(p: dict, cfg: ArchConfig, x: jax.Array,
                             cache: dict, cur_len: jax.Array,
                             lengths: jax.Array, *,
                             window: int | None = None
                             ) -> tuple[jax.Array, dict]:
    """Chunked cached prefill: advance T tokens against the decode cache
    in one call (the multi-token sibling of :func:`decode_attention`).

    x: (B, T, d); cache["k"/"v"]: (B, Hkv, C, hd); cur_len: (B,) tokens
    already in each slot's cache; lengths: (B,) valid tokens of this
    chunk per slot (rows past a slot's length are padding — they neither
    read into the cache nor write it, so mixed prefill/decode serving
    slots share one static-shape step).

    Queries attend to the *pre-chunk* cache snapshot concatenated with
    the in-chunk keys under a chunk-causal mask (the ``_sdpa_qchunked``
    offset-position discipline: masks come from absolute positions, not
    0-based contiguity).  Attending the snapshot rather than the updated
    ring is load-bearing for SWA: with a ring of C slots and a chunk
    longer than C, a late in-chunk token overwrites the ring slot an
    early query still needs.  The returned cache has the valid chunk K/V
    scattered in ring order, last writer per slot winning."""

    B, T, d = x.shape
    C = next(iter(cache.values())).shape[2]
    cur_len = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (B,))
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    t_idx = jnp.arange(T, dtype=jnp.int32)
    pos = cur_len[:, None] + t_idx[None, :]            # (B, T) absolute
    valid = t_idx[None, :] < lengths[:, None]          # (B, T)

    q, new = _project(p, cfg, x, pos)

    # ring scatter: chunk token t lands in slot pos[t] mod C; for each
    # ring index take the LAST valid writer (one-hot + argmax keeps the
    # update elementwise — same sharding rationale as decode_attention)
    ring = jnp.mod(pos, C)                             # (B, T)
    match = (ring[:, :, None] == jnp.arange(C)[None, None, :]) \
        & valid[:, :, None]                            # (B, T, C)
    hit = match.any(axis=1)                            # (B, C)
    last_t = jnp.argmax(match * (t_idx[None, :, None] + 1), axis=1)

    def scatter(new, old):
        vals = jnp.take_along_axis(new, last_t[:, None, :, None], axis=2)
        return jnp.where(hit[:, None, :, None], vals.astype(old.dtype), old)

    new_cache = {n: scatter(new[n], c) for n, c in cache.items()}

    # pre-chunk snapshot key positions: ring index i last held absolute
    # position (cur_len-1) - ((slot_last - i) mod C); never-written
    # indices come out negative and mask off
    last = cur_len - 1
    slot_last = jnp.mod(last, C)
    idx = jnp.arange(C, dtype=jnp.int32)[None, :]      # (1, C)
    abs_old = last[:, None] - jnp.mod(slot_last[:, None] - idx, C)

    kp = jnp.concatenate([abs_old, pos], axis=1)       # (B, C+T)
    k_ok = jnp.concatenate([abs_old >= 0, valid], axis=1)
    qp = pos[:, :, None]                               # (B, T, 1)
    mask = k_ok[:, None, :] & (kp[:, None, :] <= qp)   # (B, T, C+T)
    if window is not None:
        mask &= kp[:, None, :] >= qp - window + 1

    # grouped GQA over snapshot-cache + in-chunk keys (no head repeat)
    every = {n: jnp.concatenate([c.astype(jnp.float32),
                                 new[n].astype(jnp.float32)], axis=2)
             for n, c in cache.items()}
    out = _attend(p, cfg, q, every, mask, x.dtype)
    return lc(out, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------------------
# Paged cached decode (shared page pool instead of per-slot rings)
# ---------------------------------------------------------------------------


def kv_pool_specs(cfg: ArchConfig, n_pages: int, page_size: int,
                  dtype: Any = None) -> dict:
    """Paged KV storage for one block: a POOL of ``n_pages`` fixed-size
    pages shared by every serving slot, addressed through per-slot page
    tables (:mod:`repro.runtime.kv`) — the paged sibling of
    :func:`kv_cache_specs`.  Pages play the batch role of the
    contiguous layout, so they take its sharding axis.

    A page's ``(Hkv, page_size, hd)`` block is stored flat, row-major,
    as ``(R, W)`` rows of ``W = gcd(Hkv * page_size * hd, 128)``
    elements: rows as wide as the TPU's 128 lanes, whatever the head
    size.  The chip then keeps the pool row-major and reads and writes
    whole pages by index; with the head size as the minor dim a 64-wide
    head is laid out with the page index minor, and every page access
    relayouts the whole pool.  The decode state stacks the pool over
    layers; the paged attention paths take that stacked ``(L, P, R,
    W)`` pool and a layer index.  ``dtype`` as in
    :func:`kv_cache_specs`.  An MLA pool is one leaf, ``"ckv"``: a page
    of ``page_size`` latent rows of ``kv_lora_rank + qk_rope_head_dim``."""

    Hkv, hd = _row_shape(cfg)
    dt = dtype if dtype is not None else DEFAULT_DTYPE
    E = Hkv * page_size * hd
    W = math.gcd(E, 128)
    return {n: PSpec((n_pages, E // W, W),
                     ("cache_batch", None, None), init="zeros", dtype=dt)
            for n in _cache_leaves(cfg)}


def _pool_page_size(pool_kv: jax.Array, Hkv: int, hd: int) -> int:
    """Tokens a page of the stacked (L, P, R, W) pool holds."""

    return pool_kv.shape[2] * pool_kv.shape[3] // (Hkv * hd)


def _gather_pool(pool_kv: jax.Array, layer: jax.Array,
                 page_table: jax.Array, Hkv: int, hd: int) -> jax.Array:
    """Layer ``layer`` of the stacked (L, P, R, W) pool -> (B, Hkv, M*ps,
    hd) per-slot linear view through the page table (unallocated
    entries gather page 0 — callers mask them by ``page_table >= 0``).
    One gather indexes layer and page together, so no slice of the
    layer's whole pool is ever materialised."""

    ps = _pool_page_size(pool_kv, Hkv, hd)
    with jax.named_scope("kv_read"):
        g = pool_kv[layer, jnp.clip(page_table, 0)]   # (B, M, R, W)
        B, M = page_table.shape
        return g.reshape(B, M, Hkv, ps, hd).transpose(
            0, 2, 1, 3, 4).reshape(B, Hkv, M * ps, hd)


def _write_pool(pool: dict, layer: jax.Array, page_table: jax.Array,
                cur_len: jax.Array, lengths: jax.Array, new: dict) -> dict:
    """Write each slot's first ``lengths[b]`` new rows (``new``: a (B,
    Hkv, T, hd) array for each leaf of the pool, token ``t`` at position
    ``cur_len[b] + t``) into layer ``layer`` of the stacked pool,
    touching only their pages.

    A run of T tokens spans at most ``nP = (T + 2*ps - 2) // ps``
    pages.  Those pages are gathered by index, the new rows merged in
    over the old ones (token ``t`` lands at row ``cur_len % ps + t`` of
    the pages laid end to end), and the pages scattered back whole —
    full-row windows, which the TPU scatters natively into the
    row-major pool.  A page no valid row lands in (inactive slot,
    ``t >= lengths`` padding, unbacked or past the table) gets the
    out-of-range index ``P`` and ``mode="drop"`` discards it; a ``-1``
    would wrap to the last page under NumPy indexing.  Pages belong to
    one slot (allocator invariant), so no two writes hit one page.
    With the pool carried through the layer scan and donated at the
    jit boundary, XLA updates it in place: the bytes written scale
    with B·T, not with the pool."""

    name = next(iter(pool))
    B, Hkv, T, hd = new[name].shape
    P, R, W = pool[name].shape[1:]
    ps = _pool_page_size(pool[name], Hkv, hd)
    M = page_table.shape[1]
    nP = (T + 2 * ps - 2) // ps
    first = cur_len % ps                                # (B,)
    j = jnp.arange(nP, dtype=jnp.int32)
    lp = cur_len[:, None] // ps + j                     # (B, nP) logical
    touched = (lengths[:, None] > 0) & (j * ps < (first + lengths)[:, None]) \
        & (lp < M)
    phys = jnp.take_along_axis(page_table, jnp.minimum(lp, M - 1), axis=1)
    phys = jnp.where(touched & (phys >= 0), phys, P)    # (B, nP)
    t = jnp.arange(nP * ps, dtype=jnp.int32)[None, :] - first[:, None]
    fresh = ((t >= 0) & (t < lengths[:, None])).reshape(B, nP, 1, ps, 1)
    src = jnp.clip(t, 0, T - 1)[:, None, :, None]       # (B, 1, nP*ps, 1)

    def put(pool_n, new_n):
        rows = jnp.take_along_axis(new_n, src, axis=2)  # (B,Hkv,nP*ps,hd)
        rows = rows.reshape(B, Hkv, nP, ps, hd).transpose(0, 2, 1, 3, 4)
        old = pool_n[layer, jnp.minimum(phys, P - 1)].reshape(
            B, nP, Hkv, ps, hd)
        pages = jnp.where(fresh, rows.astype(pool_n.dtype), old)
        return pool_n.at[layer, phys].set(pages.reshape(B, nP, R, W),
                                          mode="drop")

    return {n: put(a, new[n]) for n, a in pool.items()}


def _pool_validity(page_table: jax.Array, page_size: int) -> jax.Array:
    """(B, M*ps) bool: which linear positions are backed by a live page."""

    return jnp.repeat(page_table >= 0, page_size, axis=1)


def _grouped_sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: jax.Array, scale: float) -> jax.Array:
    """Grouped GQA attention: contract q head-groups against the
    kv-head cache directly (no head repeat — same sharding rationale as
    :func:`decode_attention`).  q: (B, H, T, hd); k/v: (B, Hkv, S, hd);
    mask broadcastable to (B, Hkv, g, T, S); returns (B, H, T, hd)."""

    B, H, T, hd = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    with jax.named_scope("kv_read"):
        k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    with jax.named_scope("attend"):
        qg = q.reshape(B, Hkv, g, T, hd).astype(jnp.float32) * scale
        s = jnp.einsum("bkgtd,bksd->bkgts", qg, k)
        s = jnp.where(mask, s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        og = jnp.einsum("bkgts,bksd->bkgtd", pr, v)
        return og.reshape(B, H, T, hd)


def decode_attention_paged(p: dict, cfg: ArchConfig, x: jax.Array,
                           pool: dict, page_table: jax.Array,
                           cur_len: jax.Array, *, layer: jax.Array,
                           window: int | None = None,
                           active: jax.Array | None = None
                           ) -> tuple[jax.Array, dict]:
    """One-token attention against a PAGED KV pool — the paged sibling
    of :func:`decode_attention`, same numerics.

    x: (B, 1, d); pool["k"/"v"]: (L, P, R, W), every layer's pages
    (:func:`kv_pool_specs`) stacked, shared by all slots; ``layer``: this layer's index into it; page_table: (B, M)
    physical page per logical page (-1 = unallocated); cur_len: (B,)
    tokens already cached per slot.

    The new K/V row lands at offset ``pos % ps`` of physical page
    ``page_table[b, pos // ps]``, written by index (:func:`_write_pool`):
    B pages are touched, not the pool.  ``active`` gates the write per
    slot — the pool is SHARED, so the server cannot gate the merged
    state per slot afterwards the way it can with per-slot rings — and
    an inactive slot's page index is out of range, so the scatter drops
    it.  Queries then attend the slot's pages through a page-table
    gather of the updated pool under the same absolute-position
    causal/window masks as the contiguous path.  Returns the stacked
    pool, to be carried through the layer scan (see
    :meth:`ModelAPI.decode_step`)."""

    B, one, d = x.shape
    Hkv, hd = _row_shape(cfg)
    ps = _pool_page_size(next(iter(pool.values())), Hkv, hd)
    M = page_table.shape[1]
    cur_len = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (B,))
    if active is None:
        active = jnp.ones((B,), bool)
    positions = cur_len[:, None]                  # (B, 1)
    with jax.named_scope("qkv"):
        q, new = _project(p, cfg, x, positions)

    with jax.named_scope("kv_write"):
        new_pool = _write_pool(pool, layer, page_table, cur_len,
                               active.astype(jnp.int32), new)

    # gather the slot's linear view from the UPDATED pool; position t
    # lives at page t//ps — validity is the same absolute-position mask
    # as the contiguous path plus "is the page live"
    kv = {n: _gather_pool(a, layer, page_table, Hkv, hd)
          for n, a in new_pool.items()}
    with jax.named_scope("attend"):
        t = jnp.arange(M * ps, dtype=jnp.int32)[None, :]    # (1, M*ps)
        cl = cur_len[:, None]                               # (B, 1)
        valid = (t <= cl) & _pool_validity(page_table, ps)
        if window is not None:
            valid &= t >= cl - window + 1
        out = _attend(p, cfg, q, kv, valid[:, None, :], x.dtype)
    return lc(out, "batch", "seq", "embed"), new_pool


def decode_attention_chunked_paged(p: dict, cfg: ArchConfig, x: jax.Array,
                                   pool: dict, page_table: jax.Array,
                                   cur_len: jax.Array, lengths: jax.Array,
                                   *, layer: jax.Array,
                                   window: int | None = None
                                   ) -> tuple[jax.Array, dict]:
    """Chunked cached prefill against a paged pool — the paged sibling
    of :func:`decode_attention_chunked`.  ``pool`` and ``layer`` as in
    :func:`decode_attention_paged`.

    Unlike the ring layout, paged positions are unique (no wraparound
    inside a chunk), so the chunk K/V is written first and queries
    attend the *updated* pool directly: every key position ``<= qp`` is
    genuinely written, the chunk-causal mask does the rest.  The write
    (:func:`_write_pool`) touches only the pages the chunk's valid rows
    land in; rows at ``t >= lengths`` (padding of idle/decoding
    neighbours) are masked out of them, and a page no valid row reaches
    goes to an out-of-range index that the scatter drops."""

    B, T, d = x.shape
    Hkv, hd = _row_shape(cfg)
    ps = _pool_page_size(next(iter(pool.values())), Hkv, hd)
    cur_len = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (B,))
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    t_idx = jnp.arange(T, dtype=jnp.int32)
    pos = cur_len[:, None] + t_idx[None, :]            # (B, T) absolute
    M = page_table.shape[1]

    with jax.named_scope("qkv"):
        q, new = _project(p, cfg, x, pos)

    with jax.named_scope("kv_write"):
        new_pool = _write_pool(pool, layer, page_table, cur_len, lengths,
                               new)

    # chunk-causal read over the updated pool: key position t is valid
    # for query position qp when t <= qp (all such positions are
    # written — this request's earlier ticks or this chunk) and its
    # page is live
    kv = {n: _gather_pool(a, layer, page_table, Hkv, hd)
          for n, a in new_pool.items()}
    with jax.named_scope("attend"):
        kp = jnp.arange(M * ps, dtype=jnp.int32)[None, None, :]
        qp = pos[:, :, None]                           # (B, T, 1)
        mask = (kp <= qp) & _pool_validity(page_table, ps)[:, None, :]
        if window is not None:
            mask &= kp >= qp - window + 1
        out = _attend(p, cfg, q, kv, mask, x.dtype)
    return lc(out, "batch", "seq", "embed"), new_pool


def decode_cross_attention(p: dict, cfg: ArchConfig, x: jax.Array,
                           enc_kv: dict) -> jax.Array:
    """Decode-time cross attention against precomputed encoder K/V."""

    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
    n_rep = cfg.n_heads // cfg.n_kv_heads
    k, v = _repeat_kv(enc_kv["k"], n_rep), _repeat_kv(enc_kv["v"], n_rep)
    mask = jnp.ones((1, 1, 1, k.shape[2]), bool)
    o = _sdpa(q, k, v, mask, cfg.hd ** -0.5)
    return jnp.einsum("bhsk,hkd->bsd", o, p["wo"])


__all__ = ["attn_specs", "mla_specs", "attention", "mla_attention",
           "decode_attention",
           "decode_attention_chunked", "decode_attention_paged",
           "decode_attention_chunked_paged", "kv_cache_specs",
           "kv_pool_specs", "decode_cross_attention"]
