"""Attention-path tests: the Pallas flash runtime path
(``use_flash=True``) against the pure-JAX math, chunked-prefill
position handling, and per-slot decode positions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as A
from repro.models.common import init_params


def _setup(S=128, B=2, seed=0, **cfg_overrides):
    cfg = get_config("smollm-135m").reduced()
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    p = init_params(A.attn_specs(cfg), jax.random.PRNGKey(seed))
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, S, cfg.d_model)) * 0.3,
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return cfg, p, x, pos


# ---------------------------------------------------------------------------
# use_flash runtime path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 32])
def test_attention_use_flash_matches_pure_jax(window):
    """The @autotune'd Pallas flash kernel (interpret mode on CPU) must
    agree with the pure-JAX math at fp32 tolerance."""

    cfg, p, x, pos = _setup(S=128)
    ref = A.attention(p, cfg, x, pos, causal=True, window=window)
    got = A.attention(p, cfg, x, pos, causal=True, window=window,
                      use_flash=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-4)


def test_attention_use_flash_non_causal():
    cfg, p, x, pos = _setup(S=128)
    ref = A.attention(p, cfg, x, pos, causal=False)
    got = A.attention(p, cfg, x, pos, causal=False, use_flash=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-4)


def test_forward_lm_threads_use_flash():
    """``cfg.use_flash`` routes whole-model self-attention through the
    flash path (the per-call-site flag threaded via the config); the
    logits must match the pure-JAX default at fp32 tolerance."""

    from repro.configs import get_config as _gc
    from repro.models import build_model
    cfg = _gc("smollm-135m").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (2, 128)), jnp.int32)
    ref = api.forward(params, {"tokens": toks})
    got = build_model(cfg.replace(use_flash=True)).forward(
        params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-4)


def test_registry_flash_default_and_smoke_fallback():
    """qwen1.5-4b opts into the flash path by default; its reduced
    smoke shapes are untileable so forward still runs (pure-JAX
    fallback per call site)."""

    from repro.configs import get_config as _gc
    from repro.models import build_model
    cfg = _gc("qwen1.5-4b")
    assert cfg.use_flash
    api = build_model(cfg.reduced())
    assert api.cfg.use_flash                     # survives reduced()
    params = api.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, api.cfg.vocab, (1, 20)), jnp.int32)   # 20 % 128 != 0 -> fallback
    out = api.forward(params, {"tokens": toks})
    assert np.isfinite(np.asarray(out)).all()


def test_attention_use_flash_falls_back_on_untileable_seq():
    """S not divisible by the 128-lane block cannot go through the
    kernel; use_flash must silently take the pure-JAX path."""

    cfg, p, x, pos = _setup(S=100)
    assert not A._flash_supported(100)
    ref = A.attention(p, cfg, x, pos)
    got = A.attention(p, cfg, x, pos, use_flash=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# chunked-prefill positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("offset", [0, 7])
@pytest.mark.parametrize("window,chunk,S", [(None, 16, 64), (24, 16, 60),
                                            (None, 32, 50)])
def test_qchunked_honors_caller_positions(offset, window, chunk, S):
    """The q-chunked path must mask with the caller's ``positions``
    (offset prefill), exactly like the un-chunked path — it used to
    assume 0-based contiguous query indices."""

    B, H, hd = 1, 2, 16
    rng = np.random.default_rng(offset * 31 + S)
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, hd)), jnp.float32)
               for _ in range(3))
    positions = (jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
                 + offset)

    qi = positions[:, None, :, None]
    ki = positions[:, None, None, :]
    mask = ki <= qi
    if window is not None:
        mask &= ki >= qi - window + 1
    ref = A._sdpa(q, k, v, mask, hd ** -0.5)
    got = A._sdpa_qchunked(q, k, v, positions, hd ** -0.5, causal=True,
                           window=window, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------------------
# per-slot decode positions
# ---------------------------------------------------------------------------

def test_decode_attention_per_slot_positions_match_scalar():
    """A (B,) vector of per-slot cache lengths must decode each row
    exactly as a solo scalar-position call would."""

    cfg, p, _, _ = _setup()
    B, C = 3, 16
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((B, 1, cfg.d_model)) * 0.3,
                    jnp.float32)
    cache = {
        "k": jnp.asarray(rng.standard_normal(
            (B, cfg.n_kv_heads, C, cfg.hd)) * 0.3, jnp.float32),
        "v": jnp.asarray(rng.standard_normal(
            (B, cfg.n_kv_heads, C, cfg.hd)) * 0.3, jnp.float32),
    }
    cur = [5, 0, 2]
    out_vec, cache_vec = A.decode_attention(p, cfg, x, cache,
                                            jnp.asarray(cur, jnp.int32))
    for b, c in enumerate(cur):
        sliced = {k: v[b:b + 1] for k, v in cache.items()}
        out_b, cache_b = A.decode_attention(p, cfg, x[b:b + 1], sliced,
                                            jnp.int32(c))
        np.testing.assert_allclose(np.asarray(out_vec[b]),
                                   np.asarray(out_b[0]),
                                   rtol=1e-5, atol=1e-5)
        # the written row holds the new K/V: the batched and the solo
        # projection + RoPE may reassociate, so compare it at f32
        # tolerance; every row the step must not touch stays bit-exact
        written = c % C
        untouched = [i for i in range(C) if i != written]
        for key in ("k", "v"):
            vec = np.asarray(cache_vec[key][b])
            solo = np.asarray(cache_b[key][0])
            np.testing.assert_allclose(vec[:, written], solo[:, written],
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_array_equal(vec[:, untouched],
                                          solo[:, untouched])
            np.testing.assert_array_equal(
                vec[:, untouched], np.asarray(cache[key][b])[:, untouched])


# ---------------------------------------------------------------------------
# chunked cached prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,C", [(None, 32), (8, 8)])
def test_decode_attention_chunked_matches_sequential(window, C):
    """One chunked call over T tokens must produce the same outputs and
    final cache as T sequential decode_attention steps — including the
    SWA ring case where the chunk (12) exceeds the ring (C=8), so
    in-chunk tokens overwrite slots earlier queries still need."""

    cfg, p, _, _ = _setup()
    B, T = 2, 12
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((B, T, cfg.d_model)) * 0.3,
                    jnp.float32)
    zero = {
        "k": jnp.zeros((B, cfg.n_kv_heads, C, cfg.hd), jnp.float32),
        "v": jnp.zeros((B, cfg.n_kv_heads, C, cfg.hd), jnp.float32),
    }
    cur = jnp.asarray([0, 3], jnp.int32)        # mixed-progress slots
    lengths = jnp.asarray([T, T], jnp.int32)

    out_c, cache_c = A.decode_attention_chunked(p, cfg, x, zero, cur,
                                                lengths, window=window)

    cache_s = {k: v for k, v in zero.items()}
    outs = []
    for t in range(T):
        o, cache_s = A.decode_attention(p, cfg, x[:, t:t + 1], cache_s,
                                        cur + t, window=window)
        outs.append(o)
    out_s = jnp.concatenate(outs, axis=1)

    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_s),
                               rtol=2e-5, atol=2e-5)
    for key in ("k", "v"):
        np.testing.assert_allclose(np.asarray(cache_c[key]),
                                   np.asarray(cache_s[key]),
                                   rtol=2e-5, atol=2e-5)


def test_decode_attention_chunked_length_gating():
    """Rows past a slot's chunk length are padding: they must not write
    the cache, and a zero-length slot's cache must be untouched."""

    cfg, p, _, _ = _setup()
    B, T, C = 2, 8, 16
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((B, T, cfg.d_model)) * 0.3,
                    jnp.float32)
    cache = {
        "k": jnp.asarray(rng.standard_normal(
            (B, cfg.n_kv_heads, C, cfg.hd)) * 0.3, jnp.float32),
        "v": jnp.asarray(rng.standard_normal(
            (B, cfg.n_kv_heads, C, cfg.hd)) * 0.3, jnp.float32),
    }
    cur = jnp.asarray([2, 5], jnp.int32)
    lengths = jnp.asarray([3, 0], jnp.int32)    # slot 1 inert

    out, new_cache = A.decode_attention_chunked(p, cfg, x, cache, cur,
                                                lengths)
    for key in ("k", "v"):
        got = np.asarray(new_cache[key])
        ref = np.asarray(cache[key])
        # slot 0: exactly positions 2..4 rewritten, everything else kept
        changed = np.any(got[0] != ref[0], axis=(0, 2))
        np.testing.assert_array_equal(changed.nonzero()[0], [2, 3, 4])
        # slot 1 (length 0): bit-identical cache
        np.testing.assert_array_equal(got[1], ref[1])

    # the valid prefix must equal the same tokens chunked at full length
    out3, _ = A.decode_attention_chunked(p, cfg, x[:1, :3],
                                         {k: v[:1] for k, v in cache.items()},
                                         cur[:1], jnp.asarray([3], jnp.int32))
    np.testing.assert_allclose(np.asarray(out[0, :3]), np.asarray(out3[0]),
                               rtol=2e-5, atol=2e-5)
