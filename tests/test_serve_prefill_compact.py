"""The paged server's compacted prefill step: a tick that prefills runs
``prefill_step`` over the prefilling slots' rows alone, padded to a rung
of :func:`~repro.runtime.serve.prefill_rungs`, wherever the decode state
is the page-indexed pool alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.runtime.kv import NO_PAGE
from repro.runtime.serve import Server, prefill_rung, prefill_rungs

MODELS = ["qwen1.5-4b", "deepseek-v2-lite"]


def _model(arch, seed=0):
    cfg = get_config(arch).reduced().replace(logits_dtype="float32")
    api = build_model(cfg)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          api.init(jax.random.PRNGKey(seed)))
    return cfg, api, params


def _spy(srv, name):
    """Record every call of the server's jitted step ``name`` as (tick,
    row count, lengths or active mask)."""

    calls, step = [], getattr(srv, name)

    def call(params, state, tokens, positions, gate, *table):
        calls.append((srv.ticks, tokens.shape[0], np.asarray(gate)))
        return step(params, state, tokens, positions, gate, *table)

    setattr(srv, name, call)
    return calls


def _greedy(forward, params, prompt, max_new, width):
    """Repeated full causal forwards, each over the stream padded to
    ``width`` (one compile; padding after a position cannot reach it)."""

    toks = list(prompt)
    for _ in range(max_new):
        padded = toks + [0] * (width - len(toks))
        logits = forward(params, {"tokens": jnp.asarray([padded])})
        toks.append(int(jnp.argmax(logits[0, len(toks) - 1])))
    return toks[len(prompt):]


# ---------------------------------------------------------------------------
# the rung ladder
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch,want", [
    (4, {1: 1, 2: 4, 3: 4, 4: 4}),
    (8, {1: 1, 2: 4, 4: 4, 5: 8, 8: 8}),
    (48, {1: 1, 3: 4, 4: 4, 5: 16, 16: 16, 17: 48, 48: 48}),
    (64, {1: 1, 4: 4, 5: 16, 16: 16, 17: 64, 63: 64, 64: 64}),
])
def test_rung_rule(batch, want):
    assert prefill_rungs(batch)[-1] == batch
    assert all(r < batch for r in prefill_rungs(batch)[:-1])
    for n, rung in want.items():
        assert prefill_rung(n, batch) == rung
    assert {prefill_rung(n, batch) for n in range(1, batch + 1)} == set(
        prefill_rungs(batch))


# ---------------------------------------------------------------------------
# the model step: compacted rows equal the full batch's
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", MODELS)
def test_compacted_prefill_step_equals_full_batch(arch):
    """Slots 1 and 4 of six prefill a chunk while the others decode or
    idle: the same rows given alone, with two padding rows (length 0,
    position 0, no pages), give the same logits and the same pool, up
    to float32's reduction order over a different row count (the
    expert layer groups its rows by expert)."""

    cfg, api, params = _model(arch)
    srv = Server(api, params, batch=6, context=32, prefill_chunk=4,
                 paged=True, page_size=4)
    rng = np.random.default_rng(5)
    # earlier traffic leaves pages and positions behind in four slots
    for n in (9, 6, 3, 11):
        srv.submit(rng.integers(0, cfg.vocab, n).tolist(), max_new=3)
    for _ in range(3):
        srv.tick()
    state = jax.tree.map(jnp.copy, srv.state)
    pos = srv.slot_pos.copy()
    prefill = [1, 4]
    for s, n in zip(prefill, (4, 3)):
        srv.alloc.ensure(s, int(pos[s]) + n)
    table = srv.alloc.page_table.copy()

    T = 4
    tokens = np.zeros((6, T), np.int32)
    lengths = np.zeros(6, np.int32)
    for s, n in zip(prefill, (4, 3)):
        tokens[s, :n] = rng.integers(0, cfg.vocab, n)
        lengths[s] = n
    # the server's own jitted step, rungs warm; it donates the state
    step = srv._prefill_step
    want_logits, want_state = step(params, jax.tree.map(jnp.copy, state),
                                   jnp.asarray(tokens),
                                   jnp.asarray(pos), jnp.asarray(lengths),
                                   jnp.asarray(table))

    K = prefill_rung(len(prefill), 6)
    assert K == 4
    c_tokens = np.zeros((K, T), np.int32)
    c_pos = np.zeros(K, np.int32)
    c_len = np.zeros(K, np.int32)
    c_table = np.full((K, table.shape[1]), NO_PAGE, np.int32)
    c_tokens[:2], c_pos[:2] = tokens[prefill], pos[prefill]
    c_len[:2], c_table[:2] = lengths[prefill], table[prefill]
    got_logits, got_state = step(params, jax.tree.map(jnp.copy, state),
                                 jnp.asarray(c_tokens),
                                 jnp.asarray(c_pos), jnp.asarray(c_len),
                                 jnp.asarray(c_table))

    want = np.asarray(want_logits)[prefill]
    np.testing.assert_allclose(np.asarray(got_logits)[:2], want, rtol=0,
                               atol=1e-5 * np.abs(want).max())
    for got, want in zip(jax.tree.leaves(got_state),
                         jax.tree.leaves(want_state)):
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=1e-5 * np.abs(want).max())


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", MODELS)
def test_paged_drain_matches_offline_greedy(arch):
    """Staggered arrivals give ticks in which some slots prefill and
    others decode, and rungs that carry padding rows; every request
    still continues its prompt as repeated full forwards do."""

    cfg, api, params = _model(arch, seed=1)
    srv = Server(api, params, batch=6, context=48, prefill_chunk=4,
                 paged=True, page_size=8)
    assert srv._compact_prefill
    prefills = _spy(srv, "_prefill_step")
    decodes = _spy(srv, "_step")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n).tolist()
               for n in (13, 6, 9, 3, 10)]
    reqs = [srv.submit(prompts[0], max_new=5)]
    srv.tick()
    for pr in prompts[1:3]:
        reqs.append(srv.submit(pr, max_new=5))
    for _ in range(3):
        srv.tick()
    for pr in prompts[3:]:
        reqs.append(srv.submit(pr, max_new=5))
    srv.run_until_drained()

    forward = jax.jit(api.forward)
    for pr, req in zip(prompts, reqs):
        assert req.out == _greedy(forward, params, pr, 5, width=24)
    real = [(t, K, ln) for t, K, ln in prefills if ln.any()]
    assert any(K > np.count_nonzero(ln) for _, K, ln in real)
    assert {t for t, _, _ in real} & {t for t, _, _ in decodes}
    assert {K for _, K, _ in real} <= set(prefill_rungs(6))


def test_per_slot_state_keeps_the_full_batch():
    """A hybrid model's SSM recurrence is per slot, so its prefill step
    runs the whole batch on every dispatch, and its paged drain matches
    the contiguous one."""

    cfg = get_config("hymba-1.5b").reduced().replace(logits_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (14, 6, 9)]
    outs = {}
    for paged in (False, True):
        srv = Server(api, params, batch=4, context=32, prefill_chunk=4,
                     paged=paged, page_size=8)
        assert not srv._compact_prefill
        calls = _spy(srv, "_prefill_step")
        reqs = [srv.submit(prompts[0], max_new=4)]
        srv.tick()
        reqs += [srv.submit(pr, max_new=4) for pr in prompts[1:]]
        srv.run_until_drained()
        assert calls and all(K == 4 for _, K, _ in calls)
        st = srv.stats()
        assert st["prefill_rows_dispatched"] == 4 * 4 * len(calls)
        outs[paged] = [r.out for r in reqs]
    assert outs[True] == outs[False]


def test_prefill_compiles_nothing_after_the_first_prefill_tick():
    """The first prefill dispatch warms every rung and its readback; once
    a first request has drained, a drain in which the number of
    prefilling slots varies compiles nothing."""

    cfg = get_config("smollm-135m").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    srv = Server(api, params, batch=8, context=64, prefill_chunk=4,
                 paged=True, page_size=8)
    calls = _spy(srv, "_prefill_step")
    rng = np.random.default_rng(2)
    srv.submit(rng.integers(0, cfg.vocab, 6).tolist(), max_new=3)
    srv.tick()
    jitted = api._server_steps[True][1]
    size = jitted._cache_size()
    srv.run_until_drained()          # the decode step compiles here

    compiles = []

    def on_event(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for k, n in enumerate((5, 17, 9, 12, 3, 21, 8)):
            srv.submit(rng.integers(0, cfg.vocab, n).tolist(),
                       max_new=3 + k)
            if k % 3 == 0:
                srv.tick()
        srv.run_until_drained()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []
    assert jitted._cache_size() == size
    assert {K for _, K, ln in calls if ln.any()} == {1, 4, 8}


def test_prefill_pad_share_counts_the_dispatched_rungs():
    cfg = get_config("smollm-135m").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    srv = Server(api, params, batch=8, context=64, prefill_chunk=4,
                 paged=True, page_size=8)
    calls = _spy(srv, "_prefill_step")
    rng = np.random.default_rng(4)
    for n in (7, 2, 10, 5, 13):
        srv.submit(rng.integers(0, cfg.vocab, n).tolist(), max_new=2)
    srv.tick()
    srv.submit(rng.integers(0, cfg.vocab, 6).tolist(), max_new=2)
    srv.run_until_drained()

    real = [(K, ln) for _, K, ln in calls if ln.any()]
    valid = sum(int(ln.sum()) for _, ln in real)
    dispatched = sum(K * 4 for K, _ in real)
    st = srv.stats()
    assert valid == 7 + 2 + 10 + 5 + 13 + 6
    assert st["prefill_rows_valid"] == valid
    assert st["prefill_rows_dispatched"] == dispatched
    assert st["prefill_pad_share"] == pytest.approx(1 - valid / dispatched)
    assert 0 < st["prefill_pad_share"] < 1
