"""Paged KV-cache subsystem tests: allocator invariants, paged-vs-
contiguous decode numerics (dense / GQA / MHA / SWA / hybrid, staggered
mixed-phase admissions), page-exhaustion deferral, and the page-size
tunable's plan/cache integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models.attention import (attn_specs, decode_attention,
                                    decode_attention_chunked_paged,
                                    decode_attention_paged, kv_pool_specs)
from repro.models.common import init_params
from repro.runtime.kv import NO_PAGE, PagedKVAllocator, PagedKVSpec
from repro.runtime.serve import KVPageTunable, Server


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------


def make_alloc(n_pages=8, page_size=4, pages_per_slot=4, n_slots=3):
    spec = PagedKVSpec(n_pages=n_pages, page_size=page_size,
                       pages_per_slot=pages_per_slot)
    return PagedKVAllocator(spec, n_slots)


def test_allocator_pages_never_shared_between_live_slots():
    alloc = make_alloc()
    assert alloc.ensure(0, 10) and alloc.ensure(1, 7) and alloc.ensure(2, 3)
    owned = {}
    for s in range(3):
        for p in alloc.slot_pages(s):
            assert p not in owned, f"page {p} owned by {owned[p]} and {s}"
            owned[p] = s
    # ownership array agrees with the page tables
    for p, s in owned.items():
        assert alloc.owner[p] == s
    assert len(owned) == alloc.used_pages == 3 + 2 + 1


def test_allocator_ensure_is_all_or_nothing():
    alloc = make_alloc(n_pages=4)
    assert alloc.ensure(0, 9)                  # 3 pages
    free_before = alloc.free_pages
    assert not alloc.ensure(1, 9)              # needs 3, only 1 free
    assert alloc.free_pages == free_before     # nothing leaked
    assert alloc.slot_pages(1) == []
    assert alloc.ensure(1, 4)                  # 1 page still fits


def test_allocator_free_list_reuse_after_release():
    alloc = make_alloc()
    alloc.ensure(0, 16)                        # 4 pages
    released = set(alloc.slot_pages(0))
    assert alloc.release(0) == 4
    assert alloc.page_table[0].tolist() == [NO_PAGE] * 4
    assert alloc.free_pages == 8
    # a fresh slot reuses the just-released pages (LIFO free list)
    alloc.ensure(1, 16)
    assert set(alloc.slot_pages(1)) == released
    # and release is idempotent on an empty slot
    assert alloc.release(0) == 0


def test_allocator_ensure_grows_monotonically():
    alloc = make_alloc()
    alloc.ensure(0, 3)                         # 1 page
    first = alloc.slot_pages(0)
    alloc.ensure(0, 4)                         # same page covers it
    assert alloc.slot_pages(0) == first
    alloc.ensure(0, 5)                         # needs a second page
    assert len(alloc.slot_pages(0)) == 2
    assert alloc.slot_pages(0)[0] == first[0]  # prefix untouched


def test_allocator_trim_frees_only_whole_dead_pages():
    alloc = make_alloc(page_size=4)
    alloc.ensure(0, 16)                        # pages for positions 0..15
    assert alloc.trim(0, 3) == 0               # page 0 still partly live
    assert alloc.trim(0, 4) == 1               # positions 0..3 dead
    assert alloc.page_table[0, 0] == NO_PAGE
    assert alloc.trim(0, 11) == 1              # page 1 dead, page 2 not
    # trimmed logical pages are never re-backed: the high-water mark
    # keeps ensure() from resurrecting positions already written
    assert alloc.ensure(0, 16)
    assert alloc.page_table[0, 0] == NO_PAGE
    assert alloc.page_table[0, 1] == NO_PAGE


def test_allocator_rewind_frees_pages_above_keep():
    alloc = make_alloc(page_size=4)
    alloc.ensure(0, 14)                        # 4 pages (positions 0..13)
    kept = alloc.slot_pages(0)[:2]
    assert alloc.rewind(0, 7) == 2             # keep 2 pages (0..6)
    assert alloc.slot_pages(0) == kept
    assert alloc.free_pages == 8 - 2
    # unlike trim, rewind LOWERS the high-water mark: the freed logical
    # pages can be re-backed by a later ensure (draft rejected, decode
    # continues through those positions)
    assert alloc.ensure(0, 14)
    assert len(alloc.slot_pages(0)) == 4
    assert alloc.slot_pages(0)[:2] == kept     # kept prefix untouched


def test_allocator_rewind_noop_within_kept_pages():
    alloc = make_alloc(page_size=4)
    alloc.ensure(0, 8)                         # 2 pages
    before = alloc.slot_pages(0)
    assert alloc.rewind(0, 8) == 0             # boundary: nothing above
    assert alloc.rewind(0, 5) == 0             # page 1 still partly kept
    assert alloc.slot_pages(0) == before
    assert alloc.rewind(0, 4) == 1             # positions 4..7 dropped
    assert alloc.slot_pages(0) == before[:1]
    # empty slot: rewind to zero frees everything and is idempotent
    assert alloc.rewind(0, 0) == 1
    assert alloc.rewind(0, 0) == 0
    assert alloc.slot_pages(0) == [] and alloc.free_pages == 8


def test_allocator_overflowing_page_table_raises():
    alloc = make_alloc(pages_per_slot=2, page_size=4)
    with pytest.raises(ValueError, match="exceed the page table"):
        alloc.ensure(0, 9)


def test_paged_spec_rejects_pool_smaller_than_one_slot():
    with pytest.raises(ValueError, match="single request could deadlock"):
        PagedKVSpec.for_server(context=64, page_size=8, n_pages=4)


def test_allocator_stats_fragmentation():
    alloc = make_alloc(page_size=4)
    alloc.ensure(0, 5)                         # 2 pages = 8 token capacity
    st = alloc.stats(live_tokens=5)
    assert st["used_pages"] == 2 and st["occupancy"] == 2 / 8
    assert st["fragmentation"] == pytest.approx(3 / 8)


# ---------------------------------------------------------------------------
# copy-on-write sharing invariants
# ---------------------------------------------------------------------------


def test_allocator_share_maps_pages_and_bumps_refcounts():
    alloc = make_alloc(page_size=4)
    alloc.ensure(0, 10)                        # 3 pages
    used_before = alloc.used_pages
    assert alloc.share(0, 1, 8) == 2           # map the 2 full pages
    assert alloc.slot_pages(1) == alloc.slot_pages(0)[:2]
    assert alloc.used_pages == used_before     # no new physical pages
    assert alloc.shared_pages == 2
    for p in alloc.slot_pages(1):
        assert alloc.refcount[p] == 2
    assert alloc.refcount[alloc.slot_pages(0)[2]] == 1  # unshared page
    # sharing covers the table: the sharer grows ABOVE the prefix only
    assert alloc.ensure(1, 12)
    assert len(alloc.slot_pages(1)) == 3
    assert alloc.slot_pages(1)[2] != alloc.slot_pages(0)[2]


def test_allocator_share_rejects_bad_src_or_dst():
    alloc = make_alloc(page_size=4)
    alloc.ensure(0, 10)
    alloc.ensure(1, 4)
    with pytest.raises(ValueError, match="not empty"):
        alloc.share(0, 1, 8)                   # dst already holds pages
    with pytest.raises(ValueError, match="does not back"):
        alloc.share(0, 2, 16)                  # src backs only 10 tokens
    assert alloc.share(0, 2, 0) == 0           # degenerate share is a noop
    assert alloc.slot_pages(2) == []


def test_allocator_shared_pages_survive_source_release():
    alloc = make_alloc(page_size=4)
    alloc.ensure(0, 10)                        # 3 pages
    alloc.share(0, 1, 8)
    shared = alloc.slot_pages(1)
    assert alloc.release(0) == 3               # src lets go of all three
    assert alloc.free_pages == 8 - 2           # only the unshared one freed
    for p in shared:
        assert alloc.refcount[p] == 1          # now exclusive to slot 1
        assert alloc.owner[p] == 1             # ownership reassigned
    assert alloc.release(1) == 2
    assert alloc.free_pages == 8
    assert not alloc.refcount.any()


def test_allocator_sharer_release_keeps_source_pages():
    alloc = make_alloc(page_size=4)
    alloc.ensure(0, 8)
    alloc.share(0, 1, 8)
    assert alloc.release(1) == 2
    assert alloc.free_pages == 8 - 2           # source still holds them
    assert alloc.shared_pages == 0
    for p in alloc.slot_pages(0):
        assert alloc.refcount[p] == 1 and alloc.owner[p] == 0


def test_allocator_cow_breaks_exactly_the_shared_pages_in_range():
    alloc = make_alloc(page_size=4, n_pages=8)
    alloc.ensure(0, 8)                         # 2 pages
    alloc.share(0, 1, 6)                       # both pages, 2nd partial
    # the sharer writes positions [6, 9): page 1 is shared (COW), page 2
    # is unmapped (plain ensure territory, not COW's business)
    pairs = alloc.cow_pages(1, 6, 9)
    assert len(pairs) == 1
    old, new = pairs[0]
    assert old == alloc.slot_pages(0)[1]       # src keeps the original
    assert alloc.page_table[1, 1] == new
    assert alloc.refcount[old] == 1 and alloc.refcount[new] == 1
    assert alloc.owner[new] == 1
    assert alloc.shared_pages == 1             # page 0 still shared
    # a second write to the now-private page needs no copy
    assert alloc.cow_pages(1, 6, 9) == []


def test_allocator_cow_is_all_or_nothing_under_pressure():
    alloc = make_alloc(page_size=4, n_pages=2, n_slots=2,
                       pages_per_slot=2)
    alloc.ensure(0, 8)                         # both pages taken
    alloc.share(0, 1, 6)
    table_before = alloc.page_table.copy()
    assert alloc.cow_pages(1, 4, 6) is None    # no free page for the copy
    assert (alloc.page_table == table_before).all()
    assert alloc.free_pages == 0


def test_allocator_rewind_and_trim_deref_shared_pages():
    alloc = make_alloc(page_size=4)
    alloc.ensure(0, 8)
    alloc.share(0, 1, 8)
    free_before = alloc.free_pages
    # the source trims its low page: still mapped by the sharer, so the
    # page must NOT hit the free list (freed count is 0)
    assert alloc.trim(0, 4) == 0
    assert alloc.free_pages == free_before
    assert alloc.refcount[alloc.slot_pages(1)[0]] == 1
    # the sharer rewinds off its top page (also shared): same deal
    assert alloc.rewind(1, 4) == 0
    assert alloc.free_pages == free_before
    # last holders letting go really free them
    assert alloc.release(0) == 1
    assert alloc.release(1) == 1
    assert alloc.free_pages == 8


def test_allocator_stats_reports_shared_pages():
    alloc = make_alloc(page_size=4)
    alloc.ensure(0, 8)
    alloc.share(0, 1, 8)
    assert alloc.stats()["shared_pages"] == 2.0


# ---------------------------------------------------------------------------
# paged attention numerics (unit level: shuffled physical pages)
# ---------------------------------------------------------------------------


def _pooled(cfg, pages):
    """(L, P, Hkv, ps, hd) page blocks -> the stacked (L, P, R, W) pool
    of :func:`kv_pool_specs` (a row-major reshape)."""

    L, P, Hkv, ps, hd = pages.shape
    return jnp.asarray(
        pages.reshape((L,) + kv_pool_specs(cfg, P, ps)["k"].shape))


def _paged(pool, Hkv, hd):
    """Inverse of :func:`_pooled`: (L, P, R, W) -> (L, P, Hkv, ps, hd)."""

    a = np.asarray(pool)
    L, P, R, W = a.shape
    return a.reshape(L, P, Hkv, R * W // (Hkv * hd), hd)


def _written(before, after):
    """(layer, page, offset) of every pool row that changed."""

    return np.argwhere((before != after).any(axis=(2, 4))).tolist()


def test_decode_attention_paged_matches_contiguous_unit():
    """The paged gather/scatter is pure indirection: with the same K/V
    laid out through an arbitrary (shuffled) page table, one-token
    attention must reproduce the contiguous path allclose."""

    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32")
    p = init_params(attn_specs(cfg), jax.random.PRNGKey(1))
    B, C, ps = 3, 32, 8
    M, P = C // ps, 3 * (C // ps)
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    cur_len = np.array([5, 0, 17], np.int32)

    rng = np.random.default_rng(0)
    k_cont = np.zeros((B, Hkv, C, hd), np.float32)
    v_cont = np.zeros((B, Hkv, C, hd), np.float32)
    for b, n in enumerate(cur_len):
        k_cont[b, :, :n] = rng.standard_normal((Hkv, n, hd))
        v_cont[b, :, :n] = rng.standard_normal((Hkv, n, hd))

    # shuffled physical layout of the same data
    perm = rng.permutation(P)
    page_table = np.full((B, M), -1, np.int32)
    pool_k = np.zeros((P, Hkv, ps, hd), np.float32)
    pool_v = np.zeros((P, Hkv, ps, hd), np.float32)
    next_page = 0
    for b, n in enumerate(cur_len):
        for m in range(-(-int(n + 1) // ps)):   # cover the write position
            page = int(perm[next_page])
            next_page += 1
            page_table[b, m] = page
            pool_k[page] = k_cont[b, :, m * ps:(m + 1) * ps]
            pool_v[page] = v_cont[b, :, m * ps:(m + 1) * ps]

    x = rng.standard_normal((B, 1, cfg.d_model)).astype(np.float32)
    out_c, new_c = decode_attention(
        p, cfg, jnp.asarray(x), {"k": jnp.asarray(k_cont),
                                 "v": jnp.asarray(v_cont)},
        jnp.asarray(cur_len))
    out_p, new_p = decode_attention_paged(
        p, cfg, jnp.asarray(x), {"k": _pooled(cfg, pool_k[None]),
                                 "v": _pooled(cfg, pool_v[None])},
        jnp.asarray(page_table), jnp.asarray(cur_len), layer=0)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_p),
                               atol=1e-5, rtol=1e-5)

    # the new token landed at its page-table target, matching the
    # contiguous write at index cur_len
    for b, n in enumerate(cur_len):
        page = page_table[b, n // ps]
        np.testing.assert_allclose(
            _paged(new_p["k"], Hkv, hd)[0, page, :, n % ps],
            np.asarray(new_c["k"])[b, :, n], atol=1e-6)


def test_decode_attention_paged_inactive_slots_write_nothing():
    """``active`` gates pool writes per slot: the pool is shared, so an
    idle/prefilling neighbour's garbage token must not land."""

    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32")
    p = init_params(attn_specs(cfg), jax.random.PRNGKey(1))
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    zeros = np.zeros((1, 4, Hkv, 4, hd), np.float32)
    pool = {"k": _pooled(cfg, zeros), "v": _pooled(cfg, zeros)}
    page_table = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    x = jnp.ones((2, 1, cfg.d_model))
    _, new_pool = decode_attention_paged(
        p, cfg, x, pool, page_table, jnp.asarray([0, 0]), layer=0,
        active=jnp.asarray([True, False]))
    k = _paged(new_pool["k"], Hkv, hd)
    assert k[0, 0].any()                       # slot 0 wrote
    assert not k[0, 2:].any()                  # slot 1 did not


def test_paged_inactive_slot_leaves_every_page_untouched():
    """A row that must not write is dropped, not wrapped: the inactive
    slot's table holds the LAST page, where a -1 index would land under
    NumPy indexing, and an active slot whose write page is unbacked
    writes nothing either.  Only the one live write changes the pool."""

    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32")
    p = init_params(attn_specs(cfg), jax.random.PRNGKey(1))
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    P, ps = 6, 4
    rng = np.random.default_rng(3)
    pages = rng.standard_normal((1, P, Hkv, ps, hd)).astype(np.float32)
    pool = {"k": _pooled(cfg, pages), "v": _pooled(cfg, pages + 1)}
    # slot 0 inactive; slot 1 active at position ps, logical page 1
    # unbacked; slot 2 active, writes page 2 at offset 1
    page_table = jnp.asarray([[P - 1, 0], [1, -1], [2, 3]], jnp.int32)
    x = jnp.asarray(rng.standard_normal((3, 1, cfg.d_model)), jnp.float32)
    _, new = decode_attention_paged(
        p, cfg, x, pool, page_table, jnp.asarray([2, ps, 1]), layer=0,
        active=jnp.asarray([False, True, True]))
    for n, before in (("k", pages), ("v", pages + 1)):
        assert _written(before, _paged(new[n], Hkv, hd)) == [[0, 2, 1]]


def test_paged_chunk_rows_past_length_write_nothing():
    """Chunk rows at ``t >= lengths`` write nothing, even where their
    page is backed; valid rows on an unbacked page write nothing (and
    do not wrap to the last page); an idle slot (length 0) whose table
    holds the last page writes nothing at all."""

    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32")
    p = init_params(attn_specs(cfg), jax.random.PRNGKey(1))
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    P, ps, T = 8, 4, 6
    rng = np.random.default_rng(4)
    pages = rng.standard_normal((1, P, Hkv, ps, hd)).astype(np.float32)
    pool = {"k": _pooled(cfg, pages), "v": _pooled(cfg, pages)}
    page_table = jnp.asarray([[4, 5, 6], [1, -1, 2], [P - 1, 0, 3]],
                             jnp.int32)
    x = jnp.asarray(rng.standard_normal((3, T, cfg.d_model)), jnp.float32)
    # slot 0: positions 3..6 valid (page 4 offset 3, page 5 offsets
    # 0..2); its rows 4, 5 (positions 7, 8) are padding.  Slot 1:
    # positions 2..4, the last on its unbacked second page
    _, new = decode_attention_chunked_paged(
        p, cfg, x, pool, page_table, jnp.asarray([3, 2, 2]),
        jnp.asarray([4, 3, 0]), layer=0)
    for n in ("k", "v"):
        assert _written(pages, _paged(new[n], Hkv, hd)) == [
            [0, 1, 2], [0, 1, 3], [0, 4, 3], [0, 5, 0], [0, 5, 1],
            [0, 5, 2]]


def test_paged_write_touches_only_its_layer():
    """The stacked (L, P, ...) pool is carried whole: layer ``l``'s
    write changes layer ``l`` and leaves every other layer bitwise."""

    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32")
    p = init_params(attn_specs(cfg), jax.random.PRNGKey(1))
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    L, P, ps, T = 3, 6, 4, 5
    rng = np.random.default_rng(5)
    pages = rng.standard_normal((L, P, Hkv, ps, hd)).astype(np.float32)
    pool = {"k": _pooled(cfg, pages), "v": _pooled(cfg, pages)}
    page_table = jnp.asarray([[3, 0], [1, 4]], jnp.int32)
    x = jnp.asarray(rng.standard_normal((2, T, cfg.d_model)), jnp.float32)
    _, dec = decode_attention_paged(
        p, cfg, x[:, :1], pool, page_table, jnp.asarray([1, 6]), layer=1)
    _, chk = decode_attention_chunked_paged(
        p, cfg, x, pool, page_table, jnp.asarray([0, 2]),
        jnp.asarray([T, 3]), layer=1)
    for new in (dec, chk):
        for n in ("k", "v"):
            after = _paged(new[n], Hkv, hd)
            assert {w[0] for w in _written(pages, after)} == {1}


def _one_hot_write(pages, page_table, cur_len, lengths, rows):
    """NumPy model of the write the scatter replaced: a one-hot over
    every (page, offset) of the pool picks its writer (b, t) by argmax
    and a pool-wide select lands it.  pages: (P, Hkv, ps, hd); rows:
    (B, Hkv, T, hd)."""

    P, Hkv, ps, hd = pages.shape
    B, _, T, _ = rows.shape
    M = page_table.shape[1]
    pos = cur_len[:, None] + np.arange(T)[None, :]
    phys = np.take_along_axis(page_table, np.clip(pos // ps, 0, M - 1), 1)
    phys = np.where(np.arange(T)[None, :] < lengths[:, None], phys, -1)
    hot = ((phys[:, :, None] == np.arange(P))[..., None]
           & ((pos % ps)[:, :, None] == np.arange(ps))[:, :, None, :])
    hot = hot.reshape(B * T, P, ps)
    written, writer = hot.any(axis=0), hot.argmax(axis=0)
    flat = rows.transpose(0, 2, 1, 3).reshape(B * T, Hkv, hd)
    vals = flat[writer].transpose(0, 2, 1, 3)          # (P, Hkv, ps, hd)
    return np.where(written[:, None, :, None], vals, pages)


@pytest.mark.parametrize("T", [1, 7])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paged_scatter_matches_one_hot_select(T, seed):
    """The indexed write is bit-identical to the one-hot select it
    replaced, on shuffled page tables, bf16 pool, mixed lengths (0
    included) and start offsets; T=1 is the decode write."""

    from repro.models.attention import _write_pool

    cfg = get_config("smollm-135m").reduced()
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    B, M, ps, P = 4, 5, 4, 24
    rng = np.random.default_rng(seed)
    cur_len = rng.integers(0, M * ps - T, B).astype(np.int32)
    lengths = rng.integers(0, T + 1, B).astype(np.int32)
    lengths[0] = T
    perm = iter(rng.permutation(P).tolist())
    page_table = np.full((B, M), -1, np.int32)
    for b in range(B):
        for m in range(-(-int(cur_len[b] + lengths[b]) // ps)):
            page_table[b, m] = next(perm)
    pages = rng.standard_normal((P, Hkv, ps, hd)).astype(jnp.bfloat16)
    rows = rng.standard_normal((B, Hkv, T, hd)).astype(np.float32)

    pool = _pooled(cfg, pages[None])
    new = _write_pool({"k": pool, "v": pool}, 0, jnp.asarray(page_table),
                      jnp.asarray(cur_len), jnp.asarray(lengths),
                      {"k": jnp.asarray(rows), "v": jnp.asarray(-rows)})
    want = _one_hot_write(pages, page_table, cur_len, lengths,
                          rows.astype(jnp.bfloat16))
    np.testing.assert_array_equal(_paged(new["k"], Hkv, hd)[0], want)
    want = _one_hot_write(pages, page_table, cur_len, lengths,
                          (-rows).astype(jnp.bfloat16))
    np.testing.assert_array_equal(_paged(new["v"], Hkv, hd)[0], want)


# ---------------------------------------------------------------------------
# paged serving end-to-end vs contiguous
# ---------------------------------------------------------------------------


def _solo_out(api, params, prompt, max_new, **kw):
    solo = Server(api, params, batch=1, context=32, **kw)
    ref = solo.submit(prompt, max_new=max_new)
    solo.run_until_drained()
    return ref.out


@pytest.mark.parametrize("arch,extra", [
    ("smollm-135m", {}),                       # dense GQA (4 heads / 2 kv)
    ("qwen1.5-4b", {}),                        # dense MHA + qkv bias
    ("smollm-135m", {"window": 8}),            # sliding window (ring vs
                                               # paged trim reclamation)
    ("hymba-1.5b", {}),                        # hybrid attn + SSM state
])
def test_paged_matches_contiguous_staggered_mixed_phase(arch, extra):
    """Paged mode is an allocation change, not a semantics change: under
    staggered admissions with mixed prefill/decode phases in one tick,
    every request must decode exactly as it would through the contiguous
    ring (which itself matches the solo drain)."""

    cfg = get_config(arch).reduced().replace(logits_dtype="float32",
                                             **extra)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    prompt_a = rng.integers(0, cfg.vocab, 14).tolist()
    prompt_b = rng.integers(0, cfg.vocab, 6).tolist()

    srv = Server(api, params, batch=2, context=32, prefill_chunk=4,
                 paged=True, page_size=8)
    req_a = srv.submit(prompt_a, max_new=4)
    for _ in range(2):
        srv.tick()               # A mid-prefill when B arrives
    req_b = srv.submit(prompt_b, max_new=4)
    srv.run_until_drained()
    assert req_a.done and req_b.done

    for prompt, req in ((prompt_a, req_a), (prompt_b, req_b)):
        assert req.out == _solo_out(api, params, prompt, 4,
                                    prefill_chunk=4)


def test_paged_server_donates_state_and_keeps_tokens():
    """The paged decode and prefill steps donate the server's state: the
    state a tick hands them is deleted afterwards (the pool was written
    in place), and a drain through a tick in which one slot decodes,
    one prefills and one idles gives every request its solo tokens."""

    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(12)
    prompt_a = rng.integers(0, cfg.vocab, 5).tolist()
    prompt_b = rng.integers(0, cfg.vocab, 11).tolist()

    srv = Server(api, params, batch=3, context=32, prefill_chunk=4,
                 paged=True, page_size=8)
    req_a = srv.submit(prompt_a, max_new=6)
    while not req_a.out:
        srv.tick()               # A through its prefill
    req_b = srv.submit(prompt_b, max_new=4)
    handed = srv.state
    srv.tick()                   # A decodes, B prefills, slot 2 idles
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(handed))
    srv.run_until_drained()
    for prompt, req, n in ((prompt_a, req_a, 6), (prompt_b, req_b, 4)):
        assert req.out == _solo_out(api, params, prompt, n,
                                    prefill_chunk=4)


def test_paged_admission_waits_for_free_pages():
    """A pool that holds one request's pages at a time: the second
    request queues until the first retires and releases, then reuses
    the freed pages — and still decodes correctly."""

    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    srv = Server(api, params, batch=2, context=32, paged=True,
                 page_size=8, kv_pages=4)        # pool == one full slot
    p1, p2 = list(range(1, 21)), list(range(3, 20))
    r1 = srv.submit(p1, max_new=4)
    r2 = srv.submit(p2, max_new=4)
    srv.tick()
    assert srv.queue and srv.queue[0] is r2      # no pages -> not admitted
    srv.run_until_drained()
    assert r1.done and r2.done
    assert r1.out == _solo_out(api, params, p1, 4)
    assert r2.out == _solo_out(api, params, p2, 4)


def test_paged_oom_at_tick_defers_youngest_and_restarts():
    """Decode growth exhausting the pool mid-flight defers the YOUNGEST
    slot (pages released, request requeued with its progress kept and
    re-prefilled on resume); the oldest keeps progressing, both finish
    with solo-exact outputs."""

    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    srv = Server(api, params, batch=2, context=32, paged=True,
                 page_size=8, kv_pages=4)
    p1, p2 = list(range(1, 21)), list(range(1, 15))
    r1 = srv.submit(p1, max_new=4)
    r2 = srv.submit(p2, max_new=4)
    srv.run_until_drained()
    assert r1.done and r2.done
    assert srv.deferrals >= 1                    # the pool really choked
    assert r1.out == _solo_out(api, params, p1, 4)
    assert r2.out == _solo_out(api, params, p2, 4)


def test_paged_sliding_window_trims_dead_pages():
    """SWA reclamation: pages that fell wholly out of the window free
    mid-request, so a long SWA request occupies O(window), not O(len)."""

    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32", window=8)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    srv = Server(api, params, batch=1, context=32, paged=True,
                 page_size=4, prefill_chunk=4)
    prompt = list(range(1, 25))
    req = srv.submit(prompt, max_new=4)
    peak = 0
    while not req.done:
        srv.tick()
        peak = max(peak, srv.alloc.used_pages)
    # window=8 at page_size=4 needs at most 3 live pages (window spans
    # at most ceil(w/ps)+1 partially-filled pages)
    assert peak <= 3
    assert req.out == _solo_out(api, params, prompt, 4, prefill_chunk=4)


def test_paged_slot_reuse_after_retire():
    """Retired slots release pages and a reused slot starts clean."""

    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    srv = Server(api, params, batch=1, context=32, paged=True, page_size=8)
    prompt = list(range(1, 13))
    r1 = srv.submit(prompt, max_new=3)
    srv.run_until_drained()
    assert srv.alloc.used_pages == 0             # retire released all
    r2 = srv.submit(prompt, max_new=3)
    srv.run_until_drained()
    assert r1.out == r2.out


# ---------------------------------------------------------------------------
# KVPageTunable
# ---------------------------------------------------------------------------


def test_kv_page_tunable_space_and_cost_tradeoff():
    tb = KVPageTunable(param_bytes=1 << 22, layers=2, d_model=64,
                       kv_width=32, context=256, prompt_lens=(16, 200),
                       requests=16, mean_new=16, batch=8, pool_tokens=512)
    pages = [c["page"] for c in tb.space()]
    assert pages == [4, 8, 16, 32, 64, 128]
    costs = {ps: tb.cost({"page": ps}) for ps in pages}
    best = min(costs, key=costs.get)
    # a genuine tradeoff: the optimum is interior — tiny pages lose to
    # gather overhead, huge pages lose to fragmentation waste
    assert best not in (pages[0], pages[-1])
    fp = tb.fingerprint()
    assert fp["tunable"] == "serve.kv_page" and fp["unit"] == "us"
    assert fp["prompt_lens"] == [16, 200]
    assert "api" not in fp and "params" not in fp


def test_kv_page_tunable_measure_requires_model():
    tb = KVPageTunable(param_bytes=1 << 20, layers=2, d_model=64,
                       kv_width=32, context=32, prompt_lens=(8,),
                       requests=2, mean_new=2, batch=1)
    with pytest.raises(RuntimeError, match="api=/params="):
        tb.measure({"page": 8})


def test_kv_page_plan_roundtrip_zero_engine_runs(tmp_path):
    """Acceptance slice: ``serve.kv_page`` resolves from a warmed cache
    through a pure-JSON plan spec with ZERO engine runs (api/params
    handles excluded from the fingerprint)."""

    from repro.runtime.serve import kv_page_tunable
    from repro.tune import TuningCache, TuningPlan, tune

    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    cache = TuningCache(tmp_path / "c.json")

    tb = kv_page_tunable(api, context=32, prompt_lens=[4, 12], requests=2,
                         max_new=2, batch=2, params=params)
    res = tune(tb, engine="measure", cache=cache, budget=1, repeats=1)
    assert res.stats["provenance"] == "measured"

    spec = {"name": "kv-warmup", "jobs": [
        {"tunable": "serve.kv_page",
         "params": {"param_bytes": api.param_count() * 2,
                    "layers": cfg.n_layers, "d_model": cfg.d_model,
                    "kv_width": cfg.n_kv_heads * cfg.hd, "context": 32,
                    "prompt_lens": [4, 12], "requests": 2, "mean_new": 2,
                    "batch": 2},
         "engine": "measure",
         "engine_kwargs": {"budget": 1, "repeats": 1}}]}
    report = TuningPlan.from_spec(spec).run(cache=cache)
    assert report.ok and report.results[0].status == "hit"
    assert report.results[0].provenance == "measured"
    assert report.results[0].best_config == dict(res.best_config)
