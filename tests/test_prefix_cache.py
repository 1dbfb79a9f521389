"""Prefix caching: page-level reuse of shared prompt prefixes.

The capability the reference got from its vLLM image (SURVEY §2.3 row 1):
a request whose prompt shares a prefix with an earlier one must not
re-prefill that prefix — its KV pages are adopted from the cache — while
producing EXACTLY the tokens a cold run produces (the cached KV values
are deterministic, so outputs are bit-identical on CPU). Covers the
allocator unit semantics, engine-level reuse (sync + async), eviction
under memory pressure, preemption interaction, and chunked prefill.
"""

import numpy as np
import pytest

from llms_on_kubernetes_tpu.engine.cache import PageAllocator
from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig, SamplingParams


# ---------------------------------------------------------------------------
# allocator unit semantics
# ---------------------------------------------------------------------------

def test_allocator_match_adopt_register_roundtrip():
    a = PageAllocator(num_pages=32, page_size=4, num_slots=4,
                      pages_per_slot=8, prefix_caching=True)
    prompt = list(range(10, 23))  # 13 tokens = 3 full pages + 1 partial

    assert a.match_prefix(prompt) == 0  # nothing cached yet
    a.allocate(0, len(prompt) + 1)
    a.register_prefix(0, prompt)

    # same prompt: all 3 full pages match
    assert a.match_prefix(prompt) == 12
    # a prompt extending the prefix matches the same 3 pages
    assert a.match_prefix(prompt + [99, 98]) == 12
    # diverging within page 2 only matches pages 0-1
    div = prompt[:6] + [77] + prompt[7:]
    assert a.match_prefix(div) == 4
    # too short to cover a page: no match
    assert a.match_prefix(prompt[:4]) == 0  # cap: >= 1 token must prefill

    # adoption increfs and fills the table with the SAME physical pages
    hit = a.adopt_prefix(1, prompt)
    assert hit == 12
    assert list(a.page_tables[1, :3]) == list(a.page_tables[0, :3])
    a.allocate(1, len(prompt) + 1)  # grows private pages past the prefix
    assert a.page_tables[1, 3] != a.page_tables[0, 3]

    # freeing the writer keeps the shared pages alive for the adopter
    a.free(0)
    assert a.match_prefix(prompt) == 12
    a.free(1)
    # now refcount 0 but cached: evictable, still matchable
    assert a.num_evictable_pages >= 3
    assert a.match_prefix(prompt) == 12


def test_allocator_exact_page_multiple_prompt_keeps_one_token():
    a = PageAllocator(num_pages=32, page_size=4, num_slots=2,
                      pages_per_slot=8, prefix_caching=True)
    prompt = list(range(8))  # exactly 2 pages
    a.allocate(0, len(prompt) + 1)
    a.register_prefix(0, prompt)
    # at least one token must prefill to produce sampling logits
    assert a.match_prefix(prompt) == 4


def test_allocator_eviction_reclaims_lru_cached_pages():
    a = PageAllocator(num_pages=9, page_size=4, num_slots=2,
                      pages_per_slot=8, prefix_caching=True)  # 8 usable
    p1 = list(range(100, 108))   # 2 pages
    a.allocate(0, 8)
    a.register_prefix(0, p1)
    a.free(0)                     # 2 cached evictable + 6 free
    p2 = list(range(200, 212))    # 3 pages
    a.allocate(1, 12)
    a.register_prefix(1, p2)
    a.free(1)
    assert a.match_prefix(p1) == 4 and a.match_prefix(p2) == 8
    # demand 7 fresh pages: 3 free remain, so LRU (p1's) get evicted
    a.allocate(0, 28)
    assert a.match_prefix(p1 + [1]) == 0   # p1 evicted (oldest)
    a.free(0)


def test_allocator_caching_off_is_inert():
    a = PageAllocator(num_pages=8, page_size=4, num_slots=2,
                      pages_per_slot=4, prefix_caching=False)
    prompt = list(range(9))
    a.allocate(0, 9)
    a.register_prefix(0, prompt)
    assert a.match_prefix(prompt) == 0
    assert a.adopt_prefix(1, prompt) == 0


# ---------------------------------------------------------------------------
# engine-level reuse
# ---------------------------------------------------------------------------

def _mk(async_scheduling=True, prefix_caching=True, **kw):
    base = dict(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=8, num_pages=64, pages_per_slot=8,
        prefill_buckets=(16, 32), async_scheduling=async_scheduling,
        async_depth=2, prefix_caching=prefix_caching,
    )
    base.update(kw)
    return Engine(EngineConfig(**base))


def _run(eng, prompt, max_tokens=8, **params):
    req = eng.submit(list(prompt), SamplingParams(
        temperature=0.0, max_tokens=max_tokens, **params))
    steps = 0
    while not req.finished:
        eng.step()
        steps += 1
        assert steps < 10_000
    return req


SYSTEM = list(range(1, 21))  # 20 tokens: 2 full pages at page_size=8


@pytest.mark.parametrize("async_scheduling", [False, True])
def test_second_request_skips_cached_prefix_and_matches_cold(async_scheduling):
    eng = _mk(async_scheduling)
    cold = _run(eng, SYSTEM + [30, 31, 32])
    assert eng.allocator.hit_tokens_total == 0

    hot = _run(eng, SYSTEM + [30, 31, 32])   # identical prompt
    assert eng.allocator.hit_tokens_total == 16   # both full pages adopted
    assert hot.output == cold.output              # bit-identical generation

    # shared system prompt + different user turn: prefix pages still hit
    other = _run(eng, SYSTEM + [40, 41])
    assert eng.allocator.hit_tokens_total == 32

    # cold-equivalence of the divergent prompt against a cache-less engine
    ref = _mk(async_scheduling, prefix_caching=False)
    ref_out = _run(ref, SYSTEM + [40, 41])
    assert other.output == ref_out.output


def test_finished_requests_prefix_is_used_and_not_live():
    """``llm_kv_pages_used`` counts a finished request's cached prefix
    (the pages are allocated, evictable, and full of valid KV), so it
    climbs to the pool's size and stays there; ``llm_kv_pages_live`` is
    what live sequences hold, and falls back to 0 when they finish."""
    eng = _mk()
    a = eng.allocator

    def used():
        return eng.config.num_pages - 1 - a.num_free_pages

    assert used() == a.num_live_pages == 0
    req = eng.submit(SYSTEM + [30, 31, 32], SamplingParams(
        temperature=0.0, max_tokens=30))
    while not req.output:
        eng.step()
    assert a.num_live_pages == used() > 0       # decoding: all of it live
    while not req.finished:
        eng.step()
    eng._drain_async()
    assert a.num_live_pages == 0
    assert used() == a.num_evictable_pages == 2  # the prompt's two full pages
    # adopted again, the cached pages are live again
    req = eng.submit(SYSTEM + [40], SamplingParams(
        temperature=0.0, max_tokens=30))
    while not req.output:
        eng.step()
    assert a.num_live_pages >= 2 and a.num_evictable_pages == 0
    assert a.num_live_pages == used()


def test_prefix_cache_off_by_flag():
    eng = _mk(prefix_caching=False)
    _run(eng, SYSTEM)
    _run(eng, SYSTEM)
    assert eng.allocator.hit_tokens_total == 0


def test_concurrent_requests_share_prefix_pages():
    eng = _mk()
    warm = _run(eng, SYSTEM + [5])  # populate the cache
    reqs = [eng.submit(SYSTEM + [60 + i], SamplingParams(
        temperature=0.0, max_tokens=6)) for i in range(3)]
    steps = 0
    while any(not r.finished for r in reqs):
        eng.step()
        steps += 1
        assert steps < 10_000
    assert eng.allocator.hit_tokens_total >= 3 * 16
    # all finished; outputs match cache-less engine
    ref = _mk(prefix_caching=False)
    for i, r in enumerate(reqs):
        assert r.output == _run(ref, SYSTEM + [60 + i], max_tokens=6).output
    del warm


def test_prefix_cache_with_chunked_prefill_remainder():
    """A prompt longer than the largest bucket with a cached prefix:
    the remainder runs the chunked path starting at the adopted length."""
    eng = _mk()
    long_prompt = list(range(1, 41))  # 40 tokens > bucket 32
    cold = _run(eng, long_prompt)
    hot = _run(eng, long_prompt)
    assert hot.output == cold.output
    # 40 tokens = 5 full pages; cap leaves >= 1 token -> 32 tokens adopted
    assert eng.allocator.hit_tokens_total == 32


def test_prefix_cache_under_preemption():
    """Preempted requests resume correctly with caching on; outputs match
    the cache-less engine."""
    kw = dict(num_pages=11, max_decode_slots=4)
    eng = _mk(**kw)
    ref = _mk(prefix_caching=False, **kw)
    outs = {}
    for e in (eng, ref):
        reqs = [e.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                   max_tokens=20))
                for _ in range(4)]
        steps = 0
        while any(not r.finished for r in reqs):
            e.step()
            steps += 1
            assert steps < 10_000
        outs[e] = [r.output for r in reqs]
    assert eng.preemptions > 0
    assert outs[eng] == outs[ref]


def test_penalties_correct_on_cache_hit():
    """Frequency/presence penalties count only OUTPUT tokens; a cache-hit
    admission (chunk path with history>0) must reset the slot's counts —
    outputs must match a cache-less engine."""
    eng = _mk()
    ref = _mk(prefix_caching=False)
    p = dict(max_tokens=10, frequency_penalty=0.9, presence_penalty=0.4)
    cold = _run(eng, SYSTEM + [7], **p)
    hot = _run(eng, SYSTEM + [7], **p)     # cache hit
    ref_out = _run(ref, SYSTEM + [7], **p)
    assert eng.allocator.hit_tokens_total == 16
    assert cold.output == ref_out.output
    assert hot.output == ref_out.output


def test_mm_prefix_caching_image_aware():
    """Multimodal prompts (gemma-3 path) reuse cached prefixes only for
    the SAME image bytes; different images with identical token streams
    never alias (the digest chain is salted with the pixel hash)."""
    from llms_on_kubernetes_tpu.configs import get_config

    mcfg = get_config("debug-mm")
    run = ([mcfg.boi_token_id] + [mcfg.image_token_id] * 4
           + [mcfg.eoi_token_id])
    # image run first, then enough text that full pages cover the run
    prompt = run + list(range(1, 21))
    rng = np.random.default_rng(0)
    size = mcfg.vision.image_size
    img_a = rng.standard_normal((1, size, size, 3)).astype(np.float32)
    img_b = rng.standard_normal((1, size, size, 3)).astype(np.float32)

    def mk():
        return Engine(EngineConfig(
            model="debug-mm", dtype="float32", max_decode_slots=2,
            page_size=8, num_pages=64, pages_per_slot=8,
            prefill_buckets=(32,)))

    def run_req(eng, img):
        req = eng.submit(list(prompt), SamplingParams(
            temperature=0.0, max_tokens=5), images=img)
        steps = 0
        while not req.finished:
            eng.step()
            steps += 1
            assert steps < 10_000
        return req

    eng = mk()
    cold = run_req(eng, img_a)
    assert eng.allocator.hit_tokens_total == 0
    hot = run_req(eng, img_a)               # same image: cache hit
    assert eng.allocator.hit_tokens_total > 0
    assert hot.output == cold.output

    hits_after_a = eng.allocator.hit_tokens_total
    other = run_req(eng, img_b)             # different image: NO aliasing
    assert eng.allocator.hit_tokens_total == hits_after_a  # salt diverged
    ref = run_req(mk(), img_b)
    assert other.output == ref.output


def test_mm_prefix_caching_qwen_mrope():
    """Qwen3-VL (mrope) prompts are cacheable (round-4 verdict item 5): a
    second-turn prompt adopts the image-region pages, its TEXT remainder
    replays through the chunk path at mrope-shifted rotary positions
    (forward_chunk pos_delta), and the output matches a cold run
    exactly."""
    from llms_on_kubernetes_tpu.configs import get_config

    qcfg = get_config("debug-qwen-mm")
    rng = np.random.default_rng(1)
    size = qcfg.vision.image_size
    img = rng.standard_normal((1, size, size, 3)).astype(np.float32)
    qrun = ([qcfg.boi_token_id] + [qcfg.image_token_id] * 4
            + [qcfg.eoi_token_id])
    turn1 = qrun + list(range(1, 21))          # 26 tokens: 3 full pages
    turn2 = turn1 + [21, 22, 23, 24]           # same prefix, longer chat

    def mk():
        return Engine(EngineConfig(
            model="debug-qwen-mm", dtype="float32", max_decode_slots=2,
            page_size=8, num_pages=64, pages_per_slot=8,
            prefill_buckets=(32,)))

    def run_req(eng, prompt):
        req = eng.submit(list(prompt), SamplingParams(
            temperature=0.0, max_tokens=4), images=img)
        steps = 0
        while not req.finished:
            eng.step()
            steps += 1
            assert steps < 10_000
        return req

    eng = mk()
    cold1 = run_req(eng, turn1)
    assert eng.allocator.hit_tokens_total == 0
    # second turn: adopts the image-covering prefix pages
    hot2 = run_req(eng, turn2)
    assert eng.allocator.hit_tokens_total > 0
    # identical to a cold run of the same prompt on a fresh engine
    ref2 = run_req(mk(), turn2)
    assert hot2.output == ref2.output
    # and re-running turn1 hits too, reproducing its own cold output
    hits_before = eng.allocator.hit_tokens_total
    hot1 = run_req(eng, turn1)
    assert eng.allocator.hit_tokens_total > hits_before
    assert hot1.output == cold1.output
