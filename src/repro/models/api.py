"""Unified model API: init / abstract params / loss / decode, per arch.

``build_model(cfg)`` returns a :class:`ModelAPI` whose functions are pure
(params-first) and jit/pjit-friendly:

* ``loss(params, batch)``          — train/prefill forward + CE loss
* ``forward(params, batch)``       — logits (prefill benchmark form)
* ``decode_state_specs(B, ctx)``   — per-arch decode state as PSpec tree
  (KV ring caches, SSM states, static encoder/image cross-K/V)
* ``decode_step(params, state, tokens)`` — one-token serve step

Decode state is a pytree with a stacked leading blocks dim, scanned in
lock-step with the stacked block params; a model with leading layers
(:func:`~repro.models.transformer._lead_plan`) has a ``"lead"`` stack
of its own, scanned first.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, ShapeSpec
from ..distribute.sharding import logical_constraint as lc
from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .common import (PSpec, abstract_params, axes_tree, init_params,
                     rms_norm, stack_specs)
from .transformer import (_block_plan, _lead_plan, _logits, _sinusoid,
                          forward_encdec, forward_lm, lm_loss, mlp_forward,
                          stack_param_specs)


def _cache_len(cfg: ArchConfig, context: int) -> int:
    if cfg.window is not None:
        return min(cfg.window, context)
    return context


@dataclass
class ModelAPI:
    cfg: ArchConfig
    specs: dict = field(default_factory=dict)

    def __post_init__(self):
        if not self.specs:
            self.specs = stack_param_specs(self.cfg)

    # -- params ---------------------------------------------------------
    def init(self, rng: jax.Array):
        return init_params(self.specs, rng)

    def abstract(self):
        return abstract_params(self.specs)

    def axes(self):
        return axes_tree(self.specs)

    def param_count(self) -> int:
        import numpy as np
        return int(sum(np.prod(s.shape) for s in
                       jax.tree.leaves(self.specs,
                                       is_leaf=lambda x: isinstance(x, PSpec))))

    # -- train / prefill ---------------------------------------------------
    def loss(self, params, batch) -> jax.Array:
        return lm_loss(params, self.cfg, batch)

    def forward(self, params, batch) -> jax.Array:
        if self.cfg.is_encdec:
            return forward_encdec(params, self.cfg, batch["tokens"],
                                  batch["frames"])
        return forward_lm(params, self.cfg, batch["tokens"],
                          batch.get("img_embeds"))

    # -- decode ---------------------------------------------------------
    def decode_block_specs(self, batch: int, context: int,
                           paged: Any = None, dtype: Any = None,
                           kinds: list[str] | None = None) -> dict:
        """Decode state of ONE block (unstacked) — also used by the
        dry-run's block-level cost lowering.

        ``paged`` (a :class:`~repro.runtime.kv.PagedKVSpec`) swaps the
        per-slot KV rings for a shared page pool — attention state
        becomes ``n_pages`` pages, each a flat row-major
        ``(Hkv, page_size, hd)`` block (:func:`attn.kv_pool_specs`), and
        slots address it through the page table fed to
        :meth:`decode_step` / :meth:`prefill_step`.  Recurrent (SSM) and cross/encoder state
        stay per-slot: they are O(1) in context, paging buys nothing.
        ``dtype`` overrides the KV storage dtype (default bfloat16) —
        pass the params' dtype to keep a float32 model float32 through
        the cache.  ``kinds`` defaults to the block's
        (:func:`_block_plan`)."""

        cfg = self.cfg
        kinds = kinds or _block_plan(cfg)[0]
        C = _cache_len(cfg, context)
        per_block: dict[str, Any] = {}
        for i, kind in enumerate(kinds):
            entry: dict[str, Any] = {}
            if kind in ("dense", "moe", "hybrid", "encoder"):
                entry["kv"] = (attn.kv_pool_specs(cfg, paged.n_pages,
                                                  paged.page_size,
                                                  dtype=dtype)
                               if paged is not None
                               else attn.kv_cache_specs(cfg, batch, C,
                                                        dtype=dtype))
            if kind in ("ssm", "hybrid"):
                entry["ssm"] = ssm_mod.ssm_state_specs(cfg, batch,
                                                       dtype=dtype)
            if kind == "cross":
                Hkv, hd = cfg.n_kv_heads, cfg.hd
                entry["enc_kv"] = {
                    "k": PSpec((batch, Hkv, cfg.n_img_tokens, hd),
                               ("cache_batch", "kv_heads", None, None),
                               init="zeros"),
                    "v": PSpec((batch, Hkv, cfg.n_img_tokens, hd),
                               ("cache_batch", "kv_heads", None, None),
                               init="zeros")}
            per_block[f"{i}_{kind}"] = entry
        return per_block

    def decode_state_specs(self, batch: int, context: int,
                           paged: Any = None, dtype: Any = None) -> dict:
        """The decode state: ``"blocks"`` stacked over the blocks, and
        ``"lead"`` over the leading layers where the model has them.
        Paged, there is one pool for every layer: the stacked ``"kv"``
        of the block's attention holds the leading layers' pages first
        (``(n_lead + n_blocks, P, R, W)``), and ``"lead"`` only their
        per-slot state."""

        cfg = self.cfg
        kinds, n_blocks = _block_plan(cfg)
        lead, n_lead = _lead_plan(cfg)
        per_block = self.decode_block_specs(batch, context, paged, dtype)
        state: dict[str, Any] = {"blocks": stack_specs(per_block, n_blocks)}
        if n_lead:
            per_lead = self.decode_block_specs(batch, context, paged, dtype,
                                               kinds=lead)
            if paged is not None:
                key = _pool_key(cfg, kinds)
                per_lead = {k: {n: v for n, v in e.items() if n != "kv"}
                            for k, e in per_lead.items()}
                state["blocks"][key]["kv"] = stack_specs(
                    per_block[key]["kv"], n_lead + n_blocks)
            state["lead"] = stack_specs(per_lead, n_lead)
        if cfg.is_encdec:
            Hkv, hd = cfg.n_kv_heads, cfg.hd
            xkv = {"k": PSpec((batch, Hkv, cfg.enc_seq, hd),
                              ("cache_batch", "kv_heads", None, None),
                              init="zeros"),
                   "v": PSpec((batch, Hkv, cfg.enc_seq, hd),
                              ("cache_batch", "kv_heads", None, None),
                              init="zeros")}
            state["xattn"] = stack_specs(xkv, cfg.n_layers)
        return state

    def init_decode_state(self, batch: int, context: int, paged: Any = None,
                          dtype: Any = None):
        return init_params(self.decode_state_specs(batch, context, paged,
                                                   dtype),
                           jax.random.PRNGKey(0))

    def decode_step(self, params, state, tokens: jax.Array,
                    cur_len: jax.Array, page_table: jax.Array | None = None,
                    active: jax.Array | None = None):
        """tokens: (B, 1) -> (logits (B, V), new state).

        ``cur_len`` is a scalar token count, or a (B,) vector of
        per-slot counts — the continuous-batching server feeds each
        slot's own position so mixed-progress slots decode correctly
        in one batch.

        ``page_table`` ((B, M) int32, -1 = unallocated) switches the
        attention state to the paged pool layout; ``active`` ((B,)
        bool) then gates pool writes per slot INSIDE attention — the
        pool is shared, so the caller cannot slice a per-slot merge out
        of the returned state the way it can with per-slot rings.  The
        stacked ``(L, P, R, W)`` pool rides through the layer scan as
        part of the carry, next to the hidden state: each layer writes
        the B new rows by index into the pages they land in (an
        inactive slot's page index is out of range and dropped) and
        gathers its pages from it, so the pool is never re-stacked as
        a scan output.  A caller that donates ``state`` at its jit
        boundary (the paged ``Server`` does) then has XLA update the
        pool in place."""

        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)       # (B,1,d)
        cur_len = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32),
                                   (tokens.shape[0],))
        if cfg.is_encdec:
            x = x + _sinusoid_at(cur_len[:, None], cfg.d_model, x.dtype)

        def make(kinds, pool_of):
            return make_decode_body(cfg, kinds, cur_len, page_table, active,
                                    pool_of=pool_of)

        x, new_state = _scan_layers(cfg, make, params, state, x,
                                    page_table is not None)
        return _logits(params, cfg, x)[:, 0], new_state

    def prefill_step(self, params, state, tokens: jax.Array,
                     positions: jax.Array, lengths: jax.Array | None = None,
                     page_table: jax.Array | None = None):
        """Chunked serving-side prefill: advance a CHUNK of prompt
        tokens per call against the decode caches.

        tokens: (B, T) — one chunk per slot; positions: (B,) per-slot
        count of tokens already in the cache; lengths: (B,) valid tokens
        of this chunk per slot (default: all T).  Slots with length 0
        (decoding or idle while others prefill) are untouched: padding
        tokens neither write the KV ring nor advance SSM state.
        ``page_table`` switches the KV writes/reads to the paged pool
        (``lengths`` already gates the scatter per slot, so no separate
        ``active`` mask is needed).

        Returns ``(logits (B, V), new state)`` where each slot's logits
        are read at its LAST valid chunk token — the next-token
        distribution a tokenwise prefill would reach after feeding the
        same tokens one tick at a time."""

        x, new_state, lengths = self._chunk_forward(
            params, state, tokens, positions, lengths, page_table)
        # logits only at each slot's last valid token: (B, T, V) never
        # materializes
        li = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
        h_last = jnp.take_along_axis(x, li[:, None, None], axis=1)
        logits = _logits(params, self.cfg, h_last)[:, 0]
        return logits, new_state

    def verify_step(self, params, state, tokens: jax.Array,
                    positions: jax.Array, lengths: jax.Array | None = None,
                    page_table: jax.Array | None = None):
        """Speculative-decode verifier: the chunked prefill forward with
        logits at EVERY chunk position instead of only the last.

        Same contract as :meth:`prefill_step` — tokens (B, T) at
        absolute positions ``positions + [0, T)``, per-slot ``lengths``
        gating writes — but returns ``(logits (B, T, V), new state)``:
        ``logits[b, t]`` is the next-token distribution after token
        ``t``, so comparing ``argmax(logits[b, t])`` against the drafted
        token at ``t+1`` scores a whole draft in one forward.  Callers
        DISCARD the returned state (it contains the rejected tokens'
        cache writes) and commit the accepted prefix with a second
        ``prefill_step(lengths=accepted)`` — the only uniform way to
        keep recurrent (SSM/hybrid) state exact under partial
        acceptance.  Positions past ``lengths`` hold garbage logits."""

        x, new_state, _ = self._chunk_forward(
            params, state, tokens, positions, lengths, page_table)
        return _logits(params, self.cfg, x), new_state

    def _chunk_forward(self, params, state, tokens, positions, lengths,
                       page_table):
        """Shared multi-token cached forward under ``prefill_step`` and
        ``verify_step``: embed + chunk-attention scan over the blocks.
        Returns ``(hidden (B, T, d), new state, lengths (B,))``."""

        cfg = self.cfg
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B,))
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
        valid = jnp.arange(T, dtype=jnp.int32)[None, :] < lengths[:, None]
        x = jnp.take(params["embed"], tokens, axis=0)       # (B,T,d)
        if cfg.is_encdec:
            pos_grid = positions[:, None] + jnp.arange(T, dtype=jnp.int32)
            x = x + _sinusoid_at(pos_grid, cfg.d_model, x.dtype)

        def make(kinds, pool_of):
            return make_prefill_body(cfg, kinds, positions, lengths, valid,
                                     page_table, pool_of=pool_of)

        x, new_state = _scan_layers(cfg, make, params, state, x,
                                    page_table is not None)
        return x, new_state, lengths

    def encode_cross_kv(self, params, frames: jax.Array) -> dict:
        """Enc-dec serving prefill: run the encoder and project per-layer
        cross-attention K/V.  Returns {"k","v"}: (L, B, Hkv, enc_seq, hd)."""

        cfg = self.cfg
        assert cfg.is_encdec
        from .transformer import _scan_blocks, _sinusoid
        B, Senc, d = frames.shape
        pos = jnp.broadcast_to(jnp.arange(Senc, dtype=jnp.int32), (B, Senc))
        enc = frames + _sinusoid(Senc, d, frames.dtype)
        enc = _scan_blocks(cfg, params["enc_blocks"], enc, pos, causal=False,
                           kinds=["encoder"])
        enc = rms_norm(enc, params["enc_ln_f"])

        def one(xp):
            k = jnp.einsum("bsd,dhk->bhsk", enc, xp["x"]["wk"])
            v = jnp.einsum("bsd,dhk->bhsk", enc, xp["x"]["wv"])
            if "k_norm" in xp["x"]:
                from .common import rms_norm as _rn
                k = _rn(k, xp["x"]["k_norm"])
            return {"k": k, "v": v}

        return jax.lax.map(one, params["xattn_blocks"])

    # -- assigned-shape input specs ----------------------------------------
    def input_specs(self, shape: ShapeSpec, *, reduced: bool = False,
                    prefill_chunk: int | None = None,
                    paged: Any = None) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of a shape
        (the dry-run contract; no allocation).

        For decode shapes, ``cur_len`` is the (B,) per-slot position
        vector the continuous-batching server actually feeds — a scalar
        spec lowered a different ``decode_step`` than serving runs.
        ``prefill_chunk=T`` instead describes the chunked
        :meth:`prefill_step` inputs (tokens (B, T) + per-slot positions
        and lengths).  ``paged`` (a
        :class:`~repro.runtime.kv.PagedKVSpec`) switches the state tree
        to the page-pool layout and adds the ``page_table`` (and, for
        decode, the per-slot ``active`` write gate) the paged steps
        take."""

        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
        if shape.kind in ("train", "prefill"):
            out = {"tokens": tok, "labels": jax.ShapeDtypeStruct((B, S),
                                                                 jnp.int32)}
            if cfg.family == "vlm":
                out["img_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16)
            if cfg.is_encdec:
                out["frames"] = jax.ShapeDtypeStruct(
                    (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
            return out
        state = abstract_params(self.decode_state_specs(B, S, paged))
        paged_specs = {} if paged is None else {
            "page_table": jax.ShapeDtypeStruct((B, paged.pages_per_slot),
                                               jnp.int32)}
        if prefill_chunk is not None:
            # chunked serving-side prefill step
            return {"tokens": jax.ShapeDtypeStruct((B, prefill_chunk),
                                                   jnp.int32),
                    "state": state,
                    "positions": jax.ShapeDtypeStruct((B,), jnp.int32),
                    "lengths": jax.ShapeDtypeStruct((B,), jnp.int32),
                    **paged_specs}
        # decode: one new token per slot + state of length S
        if paged is not None:
            paged_specs["active"] = jax.ShapeDtypeStruct((B,), jnp.bool_)
        return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                "state": state,
                "cur_len": jax.ShapeDtypeStruct((B,), jnp.int32),
                **paged_specs}


def _scan_layers(cfg: ArchConfig, make_body, params, state, x, paged: bool):
    """The layer scans of ``decode_step`` and ``_chunk_forward``: the
    leading layers' stack where the model has one, then the blocks'.
    ``make_body(kinds, pool_of)`` builds a scan body (:func:`_make_body`).
    Returns ``(hidden, new state)``.

    Per-slot state (KV rings, SSM, cross K/V) is scanned in lock-step
    with the stacked params and stacked again as the scan's output.
    Paged, every block's ``"kv"`` pool — already stacked ``(L, P, R,
    W)`` — leaves ``xs``/``ys`` and rides in the carry next to ``h``,
    with the layer index scanned: each layer writes its pages of the
    carried pool, and no scan output re-stacks it.  The leading layers
    take the pool's first indices and the blocks the rest."""

    kinds, n = _block_plan(cfg)
    lead, n_lead = _lead_plan(cfg)
    new_state = dict(state)
    cross = ((params["xattn_blocks"], state["xattn"]) if cfg.is_encdec
             else ())
    with jax.named_scope("layers"):
        if not paged:
            if n_lead:
                x, new_state["lead"] = jax.lax.scan(
                    make_body(lead, None), x,
                    (params["lead"], state["lead"]))
            x, new_state["blocks"] = jax.lax.scan(
                make_body(kinds, None), x,
                (params["blocks"], state["blocks"], *cross))
        else:
            pool, rest = _split_pool(state["blocks"])
            if n_lead:
                key = _pool_key(cfg, kinds)
                (x, pool), new_state["lead"] = jax.lax.scan(
                    make_body(lead, {f"{i}_{k}": key
                                     for i, k in enumerate(lead)}),
                    (x, pool),
                    (jnp.arange(n_lead), params["lead"], state["lead"]))
            (x, pool), rest = jax.lax.scan(
                make_body(kinds, None), (x, pool),
                (jnp.arange(n_lead, n_lead + n), params["blocks"], rest,
                 *cross))
            new_state["blocks"] = _join_pool(pool, rest)
    return x, new_state


def _pool_key(cfg: ArchConfig, kinds: list[str]) -> str:
    """The block key whose ``"kv"`` pool the leading layers share."""

    keys = [f"{i}_{k}" for i, k in enumerate(kinds)
            if k in ("dense", "moe", "hybrid", "encoder")]
    if len(keys) != 1:
        raise ValueError(f"{cfg.name}: leading layers share the block's "
                         f"pool, and its block has {len(keys)} attention "
                         f"layers, not one")
    return keys[0]


def _split_pool(blocks: dict) -> tuple[dict, dict]:
    """Stacked decode state -> (each block's paged ``"kv"`` pool, the
    per-slot rest)."""

    pool = {key: e["kv"] for key, e in blocks.items() if "kv" in e}
    rest = {key: {n: v for n, v in e.items() if n != "kv"}
            for key, e in blocks.items()}
    return pool, rest


def _join_pool(pool: dict, rest: dict) -> dict:
    """Inverse of :func:`_split_pool`."""

    return {key: {**e, "kv": pool[key]} if key in pool else e
            for key, e in rest.items()}


def _make_body(cfg: ArchConfig, kinds: list[str], one_attn, one_ssm,
               paged: bool, valid: jax.Array | None = None,
               pool_of: dict | None = None):
    """One block over all its kinds — the scan body shared by decode and
    chunked prefill, which differ only in ``one_attn(p, hn, kv, layer)``
    and ``one_ssm(p, hn, state)``, and in ``valid`` ((B, T) or (B, 1):
    the rows that are tokens, which the dropless expert layer alone
    routes).

    Contiguous: ``body(h, (bp, cache[, xp, xkv])) -> (h, new_cache)``.
    Paged: ``body((h, pool), (layer, bp, cache[, xp, xkv])) -> ((h,
    pool), new_cache)``, the stacked pool carried (see
    :func:`_scan_layers`) and ``cache`` without its ``"kv"``; a key of
    ``pool_of`` attends the pool of the key it maps to."""

    pool_of = pool_of or {}

    def body(carry, scanned):
        if paged:
            (h, pool), (layer, bp, cache, *cross) = carry, scanned
        else:
            h, pool, layer = carry, None, None
            bp, cache, *cross = scanned
        new_cache = {}
        for i, kind in enumerate(kinds):
            key = f"{i}_{kind}"
            p, c = bp[key], cache[key]
            nc: dict[str, Any] = {}
            hn = rms_norm(h, p["ln1"])
            if kind in ("dense", "moe", "encoder", "hybrid"):
                pk = pool_of.get(key, key)
                a, kv = one_attn(p["attn"], hn,
                                 pool[pk] if paged else c["kv"], layer)
                if paged:
                    pool = {**pool, pk: kv}
                else:
                    nc["kv"] = kv
            if kind in ("ssm", "hybrid"):
                m, nc["ssm"] = one_ssm(p["ssm"], hn, c["ssm"])
            if kind == "hybrid":
                h = h + p["mix"][0] * a + p["mix"][1] * m
            elif kind == "ssm":
                h = h + m
            elif kind == "cross":
                a = attn.decode_cross_attention(p["xattn"], cfg, hn,
                                                c["enc_kv"])
                h = h + jnp.tanh(p["gate"]).astype(h.dtype) * a
                nc["enc_kv"] = c["enc_kv"]
            else:
                h = h + a
            if "ffn" in p:
                h2 = rms_norm(h, p["ln2"])
                if kind == "moe":
                    h = h + moe_mod.moe_forward(p["ffn"], cfg, h2, valid)
                else:
                    h = h + mlp_forward(p["ffn"], cfg, h2)
            new_cache[key] = nc
        if cfg.is_encdec:
            xp, xkv = cross
            a = attn.decode_cross_attention(
                xp["x"], cfg, rms_norm(h, xp["ln_x"]), xkv)
            h = h + a
        return ((h, pool) if paged else h), new_cache

    return body


def make_decode_body(cfg: ArchConfig, kinds: list[str], cur_len: jax.Array,
                     page_table: jax.Array | None = None,
                     active: jax.Array | None = None,
                     pool_of: dict | None = None):
    """One decode block: the scan body of ``decode_step`` and the unit
    lowered by the dry-run's block-cost analysis.  With ``page_table``
    the attention state is the shared paged pool, carried (``active``
    gates its writes per slot); recurrent/cross state is per-slot
    either way.  Signatures in :func:`_make_body`."""

    def one_attn(p, hn, kv, layer):
        if page_table is not None:
            return attn.decode_attention_paged(
                p, cfg, hn, kv, page_table, cur_len, layer=layer,
                window=cfg.window, active=active)
        return attn.decode_attention(p, cfg, hn, kv, cur_len,
                                     window=cfg.window)

    def one_ssm(p, hn, st):
        return ssm_mod.ssm_decode_step(p, cfg, hn, st)

    return _make_body(cfg, kinds, one_attn, one_ssm, page_table is not None,
                      None if active is None else active[:, None], pool_of)


def make_prefill_body(cfg: ArchConfig, kinds: list[str],
                      positions: jax.Array, lengths: jax.Array,
                      valid: jax.Array,
                      page_table: jax.Array | None = None,
                      pool_of: dict | None = None):
    """One chunked-prefill block: the scan body of ``prefill_step`` —
    the multi-token sibling of :func:`make_decode_body`.  Attention
    advances the chunk through :func:`attn.decode_attention_chunked`
    (chunk-wide KV scatter, chunk-causal masking) — or its paged
    sibling when a ``page_table`` is given — SSM/hybrid state steps the
    chunk via scan, the enc-dec cross path is unchanged (already
    chunk-shape agnostic)."""

    def one_attn(p, hn, kv, layer):
        if page_table is not None:
            return attn.decode_attention_chunked_paged(
                p, cfg, hn, kv, page_table, positions, lengths,
                layer=layer, window=cfg.window)
        return attn.decode_attention_chunked(
            p, cfg, hn, kv, positions, lengths, window=cfg.window)

    def one_ssm(p, hn, st):
        return ssm_mod.ssm_prefill_step(p, cfg, hn, st, valid)

    return _make_body(cfg, kinds, one_attn, one_ssm, page_table is not None,
                      valid, pool_of)


def _sinusoid_at(pos: jax.Array, d: int, dtype) -> jax.Array:
    """Sinusoidal embedding at absolute position(s): (...,) positions
    -> (..., d); callers shape the position grid ((B, 1) per-slot
    decode, (B, T) chunked prefill)."""

    pos = jnp.asarray(pos, jnp.float32)
    dim = jnp.arange(d // 2, dtype=jnp.float32)
    ang = pos[..., None] / jnp.power(10000.0, 2 * dim / d)   # (..., d/2)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1
                           ).astype(dtype)


def build_model(cfg: ArchConfig) -> ModelAPI:
    return ModelAPI(cfg)


__all__ = ["ModelAPI", "build_model"]
