"""Paged KV cache: allocator semantics and scatter-write correctness."""

import jax.numpy as jnp
import numpy as np
import pytest

from llms_on_kubernetes_tpu.engine.cache import (
    CacheConfig, KVPool, PageAllocator, init_pages, write_tokens,
)


def test_allocator_reserves_trash_page_and_reuses_freed():
    a = PageAllocator(num_pages=8, page_size=4, num_slots=2, pages_per_slot=4)
    assert a.num_free_pages == 7  # page 0 reserved
    a.allocate(0, 9)              # 3 pages
    assert a.slot_pages[0] == [1, 2, 3]
    assert (a.page_tables[0, :3] == [1, 2, 3]).all()
    a.allocate(0, 10)             # still 3 pages — idempotent growth
    assert len(a.slot_pages[0]) == 3
    a.free(0)
    assert a.num_free_pages == 7
    assert (a.page_tables[0] == 0).all()
    a.allocate(1, 1)
    assert a.slot_pages[1] == [3]  # LIFO reuse


def test_allocator_exhaustion_and_overflow():
    a = PageAllocator(num_pages=4, page_size=2, num_slots=1, pages_per_slot=2)
    with pytest.raises(ValueError):
        a.allocate(0, 100)  # exceeds pages_per_slot
    a2 = PageAllocator(num_pages=3, page_size=2, num_slots=2, pages_per_slot=4)
    a2.allocate(0, 4)
    with pytest.raises(MemoryError):
        a2.allocate(1, 2)


def test_eviction_refuses_pinned_page():
    """The cached-page LRU must only ever hold refcount-0 pages; if a bug
    parks a still-referenced page there, eviction must fail loudly instead
    of silently corrupting the pinning slot's KV."""
    a = PageAllocator(num_pages=3, page_size=2, num_slots=2, pages_per_slot=2,
                      prefix_caching=True)
    a.allocate(0, 4)
    a.register_prefix(0, [1, 2, 3, 4])
    a.free(0)                      # both pages parked, content kept
    assert a.num_evictable_pages == 2 and a.num_free_pages == 0
    assert a.adopt_prefix(1, [1, 2, 3, 4, 9]) == 4   # pinned by slot 1
    # corrupt the invariant the way a buggy caller would: re-list a pinned
    # page as evictable, then force an eviction (free list is empty)
    a._lru[a.slot_pages[1][0]] = None
    with pytest.raises(RuntimeError, match="still referenced"):
        a._take_page()


def test_double_free_detected():
    """Freeing pages that already dropped their last reference (a stale
    alias of another slot's list) must raise, not hand the same page to
    two sequences."""
    a = PageAllocator(num_pages=4, page_size=2, num_slots=2, pages_per_slot=2)
    a.allocate(0, 4)
    a.slot_pages[1] = list(a.slot_pages[0])   # stale alias
    a.free(0)
    with pytest.raises(RuntimeError, match="double free"):
        a.free(1)


def test_write_tokens_places_kv_in_pages_and_trash_for_padding():
    P, page, KV, d = 5, 4, 2, 3
    k_pages = KVPool(jnp.zeros((KV, P, page, d)))
    v_pages = KVPool(jnp.zeros((KV, P, page, d)))
    B, T = 1, 6
    k = jnp.arange(B * T * KV * d, dtype=jnp.float32).reshape(B, T, KV, d) + 1
    v = -k
    page_table = jnp.asarray([[2, 4, 0, 0]], jnp.int32)
    # positions 0..4 valid, position 5 is padding (-1 => trash page 0)
    positions = jnp.asarray([[0, 1, 2, 3, 4, -1]], jnp.int32)
    k_pages, v_pages = write_tokens(k_pages, v_pages, k, v, page_table, positions)
    kn = np.asarray(k_pages.data)  # [KV, P, page, d]
    np.testing.assert_allclose(kn[:, 2, 0], np.asarray(k)[0, 0])
    np.testing.assert_allclose(kn[:, 2, 3], np.asarray(k)[0, 3])
    np.testing.assert_allclose(kn[:, 4, 0], np.asarray(k)[0, 4])
    assert np.asarray(v_pages.data)[0, 2, 1, 0] == -np.asarray(k)[0, 1, 0, 0]
    # pages other than 2, 4 and trash are untouched
    assert (kn[:, 1] == 0).all() and (kn[:, 3] == 0).all()


def test_write_tokens_scatter_fallback_matches_dus_path():
    """Chunks spanning > _MAX_RMW_PAGES pages take the HLO-scatter fallback
    (round-2 advisor finding: previously unreachable in any tested config).
    page_size=1 with a 64-token chunk forces n_touch=65 > 33; the scatter
    result must match the per-page DUS path bit for bit."""
    from llms_on_kubernetes_tpu.engine.cache import _MAX_RMW_PAGES

    P, page, KV, d = 80, 1, 2, 3
    B, T = 2, 64
    assert (T - 1) // page + 2 > _MAX_RMW_PAGES  # scatter path engaged
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(B, T, KV, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, d)), jnp.float32)
    # row 0: full chunk from position 3; row 1: 10 valid tokens, rest padding
    pt = np.zeros((B, 70), np.int32)
    pt[0] = rng.permutation(np.arange(1, 71))
    pt[1] = rng.permutation(np.arange(1, 80))[:70]
    positions = np.full((B, T), -1, np.int32)
    positions[0] = np.arange(3, 3 + T)
    positions[1, :10] = np.arange(10)
    pt_j, pos_j = jnp.asarray(pt), jnp.asarray(positions)

    kp0 = KVPool(jnp.zeros((KV, P, page, d)))
    vp0 = KVPool(jnp.zeros((KV, P, page, d)))
    ks, vs = write_tokens(kp0, vp0, k, v, pt_j, pos_j)  # scatter (n_touch>33)

    # reference: same writes through the small-chunk DUS path, one
    # page-sized (=1-token) sub-chunk at a time
    kd, vd = kp0, vp0
    for b in range(B):
        for t in range(T):
            if positions[b, t] < 0:
                continue
            kd, vd = write_tokens(
                kd, vd, k[b:b + 1, t:t + 1], v[b:b + 1, t:t + 1],
                pt_j[b:b + 1], pos_j[b:b + 1, t:t + 1])
    # trash page 0 may differ (padding lands there); compare real pages
    np.testing.assert_array_equal(np.asarray(ks.data)[:, 1:], np.asarray(kd.data)[:, 1:])
    np.testing.assert_array_equal(np.asarray(vs.data)[:, 1:], np.asarray(vd.data)[:, 1:])


def test_cache_config_accounting():
    cc = CacheConfig(num_layers=2, num_kv_heads=4, head_dim=8,
                     num_pages=16, page_size=8, pages_per_slot=4, dtype="bfloat16")
    assert cc.max_seq_len == 32
    assert cc.bytes_per_page == 2 * 2 * 8 * 4 * 8 * 2  # k&v · L · page · kv · hd · bf16
    k, v = init_pages(cc)
    # flat layout: [KV, L*P, page, d] (layer l's block starts at l*P)
    assert k.shape == (4, 2 * 16, 8, 8) and k.dtype == jnp.bfloat16
    assert not k.quantized

    cq = CacheConfig(num_layers=2, num_kv_heads=4, head_dim=8,
                     num_pages=16, page_size=8, pages_per_slot=4,
                     dtype="bfloat16", kv_dtype="int8")
    kq, vq = init_pages(cq)
    assert kq.quantized and kq.dtype == jnp.int8
    assert kq.scale.shape == (4, 2 * 16, 8)
    # int8 halves the per-page bytes vs bf16 (scale adds 4B per token)
    assert cq.bytes_per_page < cc.bytes_per_page
