"""GQA attention: qk-norm / qkv-bias / sliding-window / RoPE variants,
full-sequence (train / prefill), single-token cached decode, and
chunked cached prefill (multi-token serving steps) paths.

Pure-JAX math by default (XLA fuses this well on TPU); the Pallas flash
kernel (`repro.kernels.flash_attention`) is the opt-in runtime path via
``use_flash=True`` — block sizes resolve through ``@autotune`` and the
persistent tuning cache, interpret mode keeps it runnable on CPU, and
shapes the kernel cannot tile take the pure-JAX math.  The choice
depends on the shape alone, never on the backend: on a TPU every
tileable call runs the compiled kernel.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..distribute.sharding import logical_constraint as lc
from .common import DEFAULT_DTYPE, PSpec, rms_norm, rope

NEG_INF = -1e30


def attn_specs(cfg: ArchConfig, cross: bool = False) -> dict:
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
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    dt = dtype if dtype is not None else DEFAULT_DTYPE
    return {
        "k": PSpec((batch, Hkv, cache_len, hd),
                   ("cache_batch", "kv_heads", "cache_seq", "head_dim"),
                   init="zeros", dtype=dt),
        "v": PSpec((batch, Hkv, cache_len, hd),
                   ("cache_batch", "kv_heads", "cache_seq", "head_dim"),
                   init="zeros", dtype=dt),
    }


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
    C = cache["k"].shape[2]
    cur_len = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (B,))
    positions = cur_len[:, None]                  # (B, 1)
    q, k_new, v_new = _project_qkv(p, cfg, x, x)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k_new = rope(k_new, positions, cfg.rope_theta)

    slot = jnp.mod(cur_len, C)                    # (B,) ring for SWA
    # one-hot masked update instead of dynamic_update_slice: elementwise,
    # so it stays local under ANY cache sharding (dynamic updates on a
    # sharded dim made GSPMD replicate the whole cache — §Perf cell B)
    hot = (jnp.arange(C)[None, :] == slot[:, None])[:, None, :, None]
    k = jnp.where(hot, k_new.astype(cache["k"].dtype), cache["k"])
    v = jnp.where(hot, v_new.astype(cache["v"].dtype), cache["v"])
    new_cache = {"k": k, "v": v}

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
    mask = valid[:, None, None, None, :]          # (B, 1, 1, 1, C)

    # grouped GQA attention: contract q head-groups against the kv-head
    # cache directly — jnp.repeat's broadcast made GSPMD all-gather the
    # whole cache per layer (§Perf cell B, 8 GiB/block)
    o = _grouped_sdpa(q, k, v, mask, cfg.hd ** -0.5).astype(x.dtype)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
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
    C = cache["k"].shape[2]
    cur_len = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (B,))
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    t_idx = jnp.arange(T, dtype=jnp.int32)
    pos = cur_len[:, None] + t_idx[None, :]            # (B, T) absolute
    valid = t_idx[None, :] < lengths[:, None]          # (B, T)

    q, k_new, v_new = _project_qkv(p, cfg, x, x)
    if cfg.use_rope:
        q = rope(q, pos, cfg.rope_theta)
        k_new = rope(k_new, pos, cfg.rope_theta)

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

    new_cache = {"k": scatter(k_new, cache["k"]),
                 "v": scatter(v_new, cache["v"])}

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
    mask = mask[:, None, None, :, :]                   # (B, 1, 1, T, C+T)

    # grouped GQA over snapshot-cache + in-chunk keys (no head repeat)
    k_all = jnp.concatenate([cache["k"].astype(jnp.float32),
                             k_new.astype(jnp.float32)], axis=2)
    v_all = jnp.concatenate([cache["v"].astype(jnp.float32),
                             v_new.astype(jnp.float32)], axis=2)
    o = _grouped_sdpa(q, k_all, v_all, mask, cfg.hd ** -0.5).astype(x.dtype)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
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
    contiguous layout, so they take its sharding axis.  ``dtype`` as in
    :func:`kv_cache_specs`."""

    Hkv, hd = cfg.n_kv_heads, cfg.hd
    dt = dtype if dtype is not None else DEFAULT_DTYPE
    return {
        "k": PSpec((n_pages, Hkv, page_size, hd),
                   ("cache_batch", "kv_heads", None, "head_dim"),
                   init="zeros", dtype=dt),
        "v": PSpec((n_pages, Hkv, page_size, hd),
                   ("cache_batch", "kv_heads", None, "head_dim"),
                   init="zeros", dtype=dt),
    }


def _gather_pool(pool_kv: jax.Array, page_table: jax.Array) -> jax.Array:
    """(P, Hkv, ps, hd) pool -> (B, Hkv, M*ps, hd) per-slot linear view
    through the page table (unallocated entries gather page 0 — callers
    mask them by ``page_table >= 0``)."""

    g = pool_kv[jnp.clip(page_table, 0)]          # (B, M, Hkv, ps, hd)
    B, M, Hkv, ps, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, M * ps, hd)


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
    qg = q.reshape(B, Hkv, g, T, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bkgtd,bksd->bkgts", qg, k.astype(jnp.float32))
    s = jnp.where(mask, s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    og = jnp.einsum("bkgts,bksd->bkgtd", pr, v.astype(jnp.float32))
    return og.reshape(B, H, T, hd)


def decode_attention_paged(p: dict, cfg: ArchConfig, x: jax.Array,
                           pool: dict, page_table: jax.Array,
                           cur_len: jax.Array, *,
                           window: int | None = None,
                           active: jax.Array | None = None
                           ) -> tuple[jax.Array, dict]:
    """One-token attention against a PAGED KV pool — the paged sibling
    of :func:`decode_attention`, same numerics.

    x: (B, 1, d); pool["k"/"v"]: (P, Hkv, page_size, hd) shared by all
    slots; page_table: (B, M) physical page per logical page (-1 =
    unallocated); cur_len: (B,) tokens already cached per slot.  The
    new K/V lands at physical page ``page_table[b, pos // ps]``, offset
    ``pos % ps``; queries then attend the slot's pages through a
    page-table gather under the same absolute-position causal/window
    masks as the contiguous path.  ``active`` gates writes per slot —
    the pool is SHARED, so the server cannot gate the merged state
    per-slot afterwards the way it can with per-slot rings; an idle or
    prefilling neighbour must not scatter a garbage token here."""

    B, one, d = x.shape
    P, Hkv, ps, hd = pool["k"].shape
    M = page_table.shape[1]
    cur_len = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (B,))
    if active is None:
        active = jnp.ones((B,), bool)
    positions = cur_len[:, None]                  # (B, 1)
    q, k_new, v_new = _project_qkv(p, cfg, x, x)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k_new = rope(k_new, positions, cfg.rope_theta)

    # scatter: slot b writes page page_table[b, pos//ps] offset pos%ps.
    # Pages are slot-exclusive (allocator invariant), so at most one
    # slot writes any (page, offset); one-hot + argmax keeps the update
    # elementwise under pool sharding, as in decode_attention.
    lp = jnp.clip(cur_len // ps, 0, M - 1)        # (B,) logical page
    off = cur_len % ps                            # (B,)
    phys = jnp.take_along_axis(page_table, lp[:, None], axis=1)[:, 0]
    phys = jnp.where(active, phys, -1)            # inactive: never match
    hot = (jnp.arange(P)[None, :] == phys[:, None])[:, :, None] \
        & (jnp.arange(ps)[None, :] == off[:, None])[:, None, :]  # (B,P,ps)
    written = hot.any(axis=0)                     # (P, ps)
    writer = jnp.argmax(hot, axis=0)              # (P, ps) -> slot index

    def scatter(new, old):                        # new: (B, Hkv, 1, hd)
        vals = new[writer, :, 0, :].transpose(0, 2, 1, 3)  # (P,Hkv,ps,hd)
        return jnp.where(written[:, None, :, None], vals.astype(old.dtype),
                         old)

    new_pool = {"k": scatter(k_new, pool["k"]),
                "v": scatter(v_new, pool["v"])}

    # gather the slot's linear view from the UPDATED pool; position t
    # lives at page t//ps — validity is the same absolute-position mask
    # as the contiguous path plus "is the page live"
    k = _gather_pool(new_pool["k"], page_table)   # (B, Hkv, M*ps, hd)
    v = _gather_pool(new_pool["v"], page_table)
    t = jnp.arange(M * ps, dtype=jnp.int32)[None, :]        # (1, M*ps)
    cl = cur_len[:, None]                                   # (B, 1)
    valid = (t <= cl) & _pool_validity(page_table, ps)
    if window is not None:
        valid &= t >= cl - window + 1
    mask = valid[:, None, None, None, :]          # (B, 1, 1, 1, M*ps)

    o = _grouped_sdpa(q, k, v, mask, cfg.hd ** -0.5).astype(x.dtype)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
    return lc(out, "batch", "seq", "embed"), new_pool


def decode_attention_chunked_paged(p: dict, cfg: ArchConfig, x: jax.Array,
                                   pool: dict, page_table: jax.Array,
                                   cur_len: jax.Array, lengths: jax.Array,
                                   *, window: int | None = None
                                   ) -> tuple[jax.Array, dict]:
    """Chunked cached prefill against a paged pool — the paged sibling
    of :func:`decode_attention_chunked`.

    Unlike the ring layout, paged positions are unique (no wraparound
    inside a chunk), so the chunk K/V is scattered first and queries
    attend the *updated* pool directly: every key position ``<= qp`` is
    genuinely written, the chunk-causal mask does the rest.  ``lengths``
    gates both the scatter and nothing else is needed per slot —
    padding rows (idle/decoding neighbours) write no page."""

    B, T, d = x.shape
    P, Hkv, ps, hd = pool["k"].shape
    M = page_table.shape[1]
    cur_len = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (B,))
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    t_idx = jnp.arange(T, dtype=jnp.int32)
    pos = cur_len[:, None] + t_idx[None, :]            # (B, T) absolute
    valid = t_idx[None, :] < lengths[:, None]          # (B, T)

    q, k_new, v_new = _project_qkv(p, cfg, x, x)
    if cfg.use_rope:
        q = rope(q, pos, cfg.rope_theta)
        k_new = rope(k_new, pos, cfg.rope_theta)

    # scatter: chunk token (b, t) -> page page_table[b, pos//ps], offset
    # pos%ps; valid tokens only.  Positions are unique and pages
    # slot-exclusive, so at most one (b, t) writes any (page, offset).
    lp = jnp.clip(pos // ps, 0, M - 1)                 # (B, T)
    off = pos % ps
    phys = jnp.take_along_axis(page_table, lp, axis=1)  # (B, T)
    phys = jnp.where(valid, phys, -1)
    hot = (phys[:, :, None] == jnp.arange(P)[None, None, :])[..., None] \
        & (off[:, :, None] == jnp.arange(ps)[None, None, :])[:, :, None, :]
    hot = hot.reshape(B * T, P, ps)                    # (B*T, P, ps)
    written = hot.any(axis=0)                          # (P, ps)
    writer = jnp.argmax(hot, axis=0)                   # (P, ps) -> b*T+t

    def scatter(new, old):                             # new: (B,Hkv,T,hd)
        flat = new.transpose(0, 2, 1, 3).reshape(B * T, Hkv, hd)
        vals = flat[writer].transpose(0, 2, 1, 3)      # (P, Hkv, ps, hd)
        return jnp.where(written[:, None, :, None], vals.astype(old.dtype),
                         old)

    new_pool = {"k": scatter(k_new, pool["k"]),
                "v": scatter(v_new, pool["v"])}

    # chunk-causal read over the updated pool: key position t is valid
    # for query position qp when t <= qp (all such positions are
    # written — this request's earlier ticks or this chunk) and its
    # page is live
    k = _gather_pool(new_pool["k"], page_table)        # (B, Hkv, M*ps, hd)
    v = _gather_pool(new_pool["v"], page_table)
    kp = jnp.arange(M * ps, dtype=jnp.int32)[None, None, :]   # (1,1,M*ps)
    qp = pos[:, :, None]                               # (B, T, 1)
    mask = (kp <= qp) & _pool_validity(page_table, ps)[:, None, :]
    if window is not None:
        mask &= kp >= qp - window + 1
    mask = mask[:, None, None, :, :]                   # (B,1,1,T,M*ps)

    o = _grouped_sdpa(q, k, v, mask, cfg.hd ** -0.5).astype(x.dtype)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
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


__all__ = ["attn_specs", "attention", "decode_attention",
           "decode_attention_chunked", "decode_attention_paged",
           "decode_attention_chunked_paged", "kv_cache_specs",
           "kv_pool_specs", "decode_cross_attention"]
