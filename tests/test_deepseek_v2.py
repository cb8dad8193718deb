"""deepseek-v2-lite's mechanisms at ``.reduced()`` sizes on the CPU:
YaRN rope against the published formulas, latent attention absorbed
against decompressed, the dropless held-experts layer against a dense
oracle and its expert-parallel shares against the uncut layer, and the
leading dense layer through the paged server."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoECfg, YarnCfg, get_config
from repro.models import attention as A
from repro.models import build_model
from repro.models import moe as M
from repro.models.common import (init_params, rope_yarn, yarn_attn_factor,
                                 yarn_inv_freq)
from repro.runtime.serve import Server

YARN = get_config("deepseek-v2-lite").rope_scaling


def reduced(**moe):
    cfg = get_config("deepseek-v2-lite").reduced().replace(
        logits_dtype="float32")
    if moe:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, **moe))
    return cfg


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dim,low,high", [(64, 10, 23), (8, 1, 3)])
def test_yarn_frequencies_and_scale_by_hand(dim, low, high):
    """The published recipe, written out here by hand: the correction
    range from beta_fast / beta_slow over the original 4,096 positions
    (worked out for the two sizes: pairs 10..23 of 32 and 1..3 of 4),
    extrapolated frequencies below it, ``1/factor`` of them above, a
    linear ramp between; the softmax scale gains ``m**2`` with ``m =
    0.1 * 0.707 * ln 40 + 1``."""

    theta = 10_000.0

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(theta))

    assert (math.floor(corr(32)), math.ceil(corr(1))) == (low, high)
    want = []
    for i in range(dim // 2):
        extra = 1.0 / theta ** (2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / 40 * ramp + extra * (1 - ramp))
    np.testing.assert_allclose(yarn_inv_freq(dim, theta, YARN), want,
                               rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_attn_factor(YARN) == pytest.approx(m * m, rel=1e-12)
    assert yarn_attn_factor(YARN) == pytest.approx(1.5896, abs=1e-4)


def test_yarn_rope_rotates_interleaved_pairs():
    """Pair i is (x[2i], x[2i+1]), turned by pos * freq_i; the output
    holds the pairs' first elements, then their second (the published
    order); cos and sin carry mscale / mscale_all_dim, here 1."""

    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 2, 5, 8)).astype(np.float32)
    pos = np.arange(5, dtype=np.int32)[None]
    got = np.asarray(rope_yarn(jnp.asarray(x), jnp.asarray(pos), 1e4, YARN))
    freq = yarn_inv_freq(8, 1e4, YARN)
    for i in range(4):
        a = pos[0, :, None].astype(np.float64) * freq[i]
        ev, od = x[..., 2 * i], x[..., 2 * i + 1]
        np.testing.assert_allclose(got[..., i], ev * np.cos(a[:, 0])
                                   - od * np.sin(a[:, 0]), atol=1e-5)
        np.testing.assert_allclose(got[..., 4 + i], od * np.cos(a[:, 0])
                                   + ev * np.sin(a[:, 0]), atol=1e-5)
    half = YarnCfg(factor=40.0, original_max_position_embeddings=4096,
                   mscale=1.0, mscale_all_dim=0.707)
    ratio = (0.1 * math.log(40) + 1) / (0.1 * 0.707 * math.log(40) + 1)
    got2 = np.asarray(rope_yarn(jnp.asarray(x), jnp.asarray(pos), 1e4, half))
    np.testing.assert_allclose(got2, got * ratio, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Latent attention
# ---------------------------------------------------------------------------


def _mla_setup(B=2, S=24, seed=0):
    cfg = reduced()
    p = f32(init_params(A.attn_specs(cfg), jax.random.PRNGKey(seed)))
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, S, cfg.d_model)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return cfg, p, x, pos


def test_absorbed_decode_equals_decompressed_attention():
    """Token-by-token absorbed decode over the latent cache, and the
    chunked paged prefill over the latent pool, give the decompressed
    full-sequence attention (float32, so to rounding)."""

    cfg, p, x, pos = _mla_setup()
    B, S, _ = x.shape
    with jax.default_matmul_precision("highest"):
        want = np.asarray(A.mla_attention(p, cfg, x, pos))
        cache = init_params(A.kv_cache_specs(cfg, B, S, jnp.float32),
                            jax.random.PRNGKey(0))
        assert set(cache) == {"ckv"}
        assert cache["ckv"].shape == (B, 1, S, cfg.mla.cache_dim)
        outs = []
        for t in range(S):
            o, cache = A.decode_attention(p, cfg, x[:, t:t + 1], cache,
                                          jnp.int32(t))
            outs.append(o)
        got = np.asarray(jnp.concatenate(outs, axis=1))
        ps, n_pages = 4, 16
        pool = init_params(A.kv_pool_specs(cfg, n_pages, ps, jnp.float32),
                           jax.random.PRNGKey(0))
        pool = {n: a[None] for n, a in pool.items()}    # one layer
        table = jnp.asarray([[3, 7, 1, 9, 2, 11], [0, 4, 5, 6, 8, 10]],
                            jnp.int32)
        chunk, _ = A.decode_attention_chunked_paged(
            p, cfg, x, pool, table, jnp.zeros((B,), jnp.int32),
            jnp.full((B,), S, jnp.int32), layer=jnp.int32(0))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-5 * scale
    assert np.abs(np.asarray(chunk) - want).max() <= 1e-5 * scale


# ---------------------------------------------------------------------------
# The dropless held-experts layer
# ---------------------------------------------------------------------------


def _moe_setup(n_experts=16, held=None, held_from=0, T=40, seed=0):
    base = reduced()
    cfg = base.replace(moe=dataclasses.replace(
        base.moe, num_experts=n_experts, top_k=3, held=held,
        held_from=held_from))
    p = f32(init_params(M.moe_specs(cfg), jax.random.PRNGKey(seed)))
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (T, cfg.d_model)), jnp.float32)
    return cfg, p, x


def oracle(p, cfg, x, valid=None):
    """Every held expert on every token, weighted by the token's gate
    for it (the softmax over all experts where the expert is among its
    top-k and held, else 0)."""

    m = cfg.moe
    s = jax.nn.softmax(x @ p["router"], axis=-1)
    kth = jnp.sort(s, axis=-1)[:, -m.top_k][:, None]
    out = jnp.zeros_like(x)
    for j in range(m.n_held):
        g = jnp.where(s[:, m.held_from + j] >= kth[:, 0],
                      s[:, m.held_from + j], 0.0)
        if valid is not None:
            g = g * valid
        y = (jax.nn.silu(x @ p["wg"][j]) * (x @ p["wu"][j])) @ p["wd"][j]
        out = out + g[:, None] * y
    return out


@pytest.mark.parametrize("held,held_from", [(None, 0), (4, 8)])
def test_held_experts_equal_the_dense_oracle(held, held_from):
    cfg, p, x = _moe_setup(held=held, held_from=held_from)
    valid = jnp.arange(x.shape[0]) % 5 != 3             # some padding rows
    with jax.default_matmul_precision("highest"):
        got = M.held_routed(p, cfg, x, valid)
        want = oracle(p, cfg, x, valid.astype(jnp.float32))
        whole = M.held_experts_forward(p, cfg, x[None])[0]
        shared = whole - M.held_routed(p, cfg, x)
        want_shared = M._shared(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(got)[~np.asarray(valid)]).max() == 0.0
    np.testing.assert_allclose(np.asarray(shared), np.asarray(want_shared),
                               rtol=1e-5, atol=1e-5)


def test_rows_past_the_held_groups_are_never_read(monkeypatch):
    """A TPU's grouped matmul leaves the rows past the groups' total
    unwritten: filled with NaN here, they must not reach the output."""

    real = jax.lax.ragged_dot

    def unwritten(lhs, rhs, sizes, **kw):
        out = real(lhs, rhs, sizes, **kw)
        past = jnp.arange(out.shape[0]) >= sizes.sum()
        return jnp.where(past[:, None], jnp.nan, out)

    cfg, p, x = _moe_setup(held=4, held_from=8)
    valid = jnp.arange(x.shape[0]) % 5 != 3
    with jax.default_matmul_precision("highest"):
        want = M.held_routed(p, cfg, x, valid)
        monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
        got = M.held_routed(p, cfg, x, valid)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of an expert-parallel 4 deployment hold 4 of 16
    experts each (offsets 0, 4, 8, 12); their routed parts, with the
    shared experts every chip computes counted once, are the uncut
    layer."""

    cfg, p, x = _moe_setup(n_experts=16)
    with jax.default_matmul_precision("highest"):
        whole = M.held_experts_forward(p, cfg, x[None])[0]
        parts = []
        for lo in (0, 4, 8, 12):
            c = cfg.replace(moe=dataclasses.replace(cfg.moe, held=4,
                                                    held_from=lo))
            share = {**p, **{k: p[k][lo:lo + 4] for k in ("wg", "wu", "wd")}}
            assert jax.tree.map(jnp.shape, share) == jax.tree.map(
                lambda s: s.shape, M.moe_specs(c),
                is_leaf=lambda s: hasattr(s, "axes"))
            parts.append(M.held_routed(share, c, x))
        total = sum(parts) + M._shared(p, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_a_held_share_needs_the_dropless_layer_and_fits_the_router():
    with pytest.raises(ValueError, match="dropless"):
        MoECfg(num_experts=8, top_k=2, held=4)
    with pytest.raises(ValueError, match="outside"):
        MoECfg(num_experts=8, top_k=2, held=4, held_from=6, dropless=True)


# ---------------------------------------------------------------------------
# The leading dense layer, through the model and the paged server
# ---------------------------------------------------------------------------


def test_leading_dense_layer_and_one_pool_for_every_layer():
    cfg = reduced()
    api = build_model(cfg)
    assert cfg.n_layers == 3 and cfg.moe.first_dense == 1
    params = api.abstract()
    assert jax.tree.leaves(params["lead"])[0].shape[0] == 1
    assert params["lead"]["0_dense"]["ffn"]["wg"].shape == (
        1, cfg.d_model, cfg.d_ff)
    assert params["blocks"]["0_moe"]["ffn"]["wg"].shape == (
        2, cfg.moe.n_held, cfg.d_model, cfg.moe.d_expert)
    srv = Server(api, api.init(jax.random.PRNGKey(0)), batch=2, context=32,
                 paged=True, page_size=8)
    pool = srv.state["blocks"]["0_moe"]["kv"]
    assert set(pool) == {"ckv"} and pool["ckv"].shape[0] == 3
    assert jax.tree.leaves(srv.state["lead"]) == []


@pytest.mark.parametrize("paged", [False, True])
def test_server_greedy_matches_offline_forward(paged):
    """Chunked prefill then decode through the latent cache (paged: the
    one pool of all three layers) continue a prompt as repeated full
    forwards do, in float32."""

    cfg = reduced()
    api = build_model(cfg)
    params = f32(api.init(jax.random.PRNGKey(1)))
    srv = Server(api, params, batch=2, context=48, prefill_chunk=8,
                 paged=paged, page_size=8)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (13, 6)]
    reqs = [srv.submit(pr, max_new=5) for pr in prompts]
    srv.run_until_drained()
    for pr, req in zip(prompts, reqs):
        toks = list(pr)
        for _ in range(5):
            logits = api.forward(params, {"tokens": jnp.asarray([toks])})
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert req.out == toks[len(pr):]


def test_block_plan_names_the_config_it_cannot_split():
    cfg = get_config("llama4-maverick-400b-a17b").reduced().replace(
        n_layers=5)
    with pytest.raises(ValueError, match="llama4-maverick"):
        build_model(cfg)
