"""Chunked prefill: prompts beyond the largest bucket split across steps.

The reference's vLLM image served any prompt up to max-model-len (SURVEY
§2.3 row 1); the engine equivalent is prefill-with-history against the
paged pool (`forward_chunk`). Invariants pinned here:

- model-level: chunked forward == one-shot prefill (same logits, same
  cache contents);
- engine-level: a prompt 4x the largest bucket generates exactly what a
  one-shot engine generates (greedy AND seeded sampling), on both the
  sync and async scheduler paths;
- the chunk count is ceil(n / largest_bucket);
- preemption of a partially-decoded long request resumes correctly.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import jax
from llms_on_kubernetes_tpu.configs import get_config
from llms_on_kubernetes_tpu.engine.cache import CacheConfig, PageAllocator, init_pages
from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig, SamplingParams
from llms_on_kubernetes_tpu.models.decoder import (
    forward_chunk, forward_prefill, init_params,
)

GREEDY = dict(temperature=0.0)


def make_engine(**kw):
    defaults = dict(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=4, num_pages=128, pages_per_slot=16,
        prefill_buckets=(8,),
    )
    defaults.update(kw)
    return Engine(EngineConfig(**defaults))


def test_forward_chunk_matches_one_shot_prefill():
    cfg = get_config("debug-tiny")
    params = init_params(cfg, jax.random.key(0), dtype="float32")
    cc = CacheConfig(num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
                     head_dim=cfg.head_dim, num_pages=32, page_size=4,
                     pages_per_slot=8, dtype="float32")
    rng = np.random.default_rng(0)
    n = 12
    prompt = rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)

    def alloc():
        al = PageAllocator(cc.num_pages, cc.page_size, 1, cc.pages_per_slot)
        al.allocate(0, n)
        return jnp.asarray(al.page_tables)

    # one-shot reference
    kp, vp = init_pages(cc)
    pt = alloc()
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :n] = prompt
    want, kp_ref, vp_ref = forward_prefill(
        params, cfg, jnp.asarray(tokens), jnp.asarray([n], jnp.int32), kp, vp, pt)

    # chunked: 3 chunks of 4
    kp, vp = init_pages(cc)
    pt = alloc()
    got = None
    for pos in range(0, n, 4):
        chunk = np.zeros((1, 4), np.int32)
        chunk[0] = prompt[pos:pos + 4]
        got, kp, vp = forward_chunk(
            params, cfg, jnp.asarray(chunk), jnp.asarray([pos], jnp.int32),
            jnp.asarray([4], jnp.int32), kp, vp, pt)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # cache contents must match on the ALLOCATED pages. Excluded: each
    # layer's trash page (flat index l*P) — padding/filler writes land
    # there and legitimately differ between chunked and one-shot runs.
    keep = np.ones(cfg.num_layers * cc.num_pages, bool)
    keep[np.arange(cfg.num_layers) * cc.num_pages] = False
    np.testing.assert_allclose(np.asarray(kp.data)[:, keep],
                               np.asarray(kp_ref.data)[:, keep],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(vp.data)[:, keep],
                               np.asarray(vp_ref.data)[:, keep],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("async_sched", [False, True])
@pytest.mark.parametrize("sampling", [
    dict(temperature=0.0),
    dict(temperature=0.9, top_k=8, seed=1234),
])
def test_long_prompt_matches_one_shot_engine(async_sched, sampling):
    """Prompt 4x the largest bucket: chunked engine == one-bucket engine."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 256, size=33).tolist()  # 33 = 4x8 + 1
    p = SamplingParams(max_tokens=8, **sampling)

    one_shot = make_engine(prefill_buckets=(64,), async_scheduling=async_sched)
    want = one_shot.generate(prompt, p)

    chunked = make_engine(prefill_buckets=(8,), async_scheduling=async_sched)
    got = chunked.generate(prompt, p)
    assert got == want
    assert len(got) == 8


def test_chunk_count_is_ceil_n_over_bucket():
    eng = make_engine(prefill_buckets=(8,))
    calls = []
    orig = eng._chunk_packed

    def counting(*args, **kw):
        calls.append(args[2].shape)  # tokens [1, bucket]
        return orig(*args, **kw)

    eng._chunk_packed = counting
    prompt = list(range(1, 30))  # 29 tokens -> ceil(29/8) = 4 chunks
    eng.generate(prompt, SamplingParams(max_tokens=2, **GREEDY))
    assert len(calls) == 4


def test_long_prompt_mixed_with_short_requests():
    """A long (chunked) and several short prompts batched together produce
    the same outputs as solo runs — continuous batching stays invisible."""
    p = SamplingParams(max_tokens=6, **GREEDY)
    rng = np.random.default_rng(3)
    long_prompt = rng.integers(0, 256, size=20).tolist()
    prompts = [long_prompt, [3, 17, 9], [40, 2, 8, 11]]
    solo = [make_engine().generate(pr, p) for pr in prompts]

    eng = make_engine()
    reqs = [eng.submit(pr, p) for pr in prompts]
    for _ in range(300):
        if not eng.has_work():
            break
        eng.step()
    assert all(r.finished for r in reqs)
    for r, expected in zip(reqs, solo):
        assert r.output == expected


@pytest.mark.parametrize("async_sched", [False, True])
def test_preempted_long_request_resumes_chunked(async_sched):
    """KV pressure preempts the youngest request; a long one re-prefills in
    chunks (prompt + generated) and its output must be unaffected."""
    p = SamplingParams(max_tokens=10, **GREEDY)
    rng = np.random.default_rng(11)
    long_prompt = rng.integers(0, 256, size=21).tolist()
    solo = make_engine(async_scheduling=async_sched).generate(long_prompt, p)

    tight = make_engine(num_pages=12, pages_per_slot=12, max_decode_slots=2,
                        async_scheduling=async_sched)
    # (the older stream decodes while the long prompt's chunks are written:
    # it needs tokens enough to still hold its pages when the pool runs out)
    first = tight.submit(rng.integers(0, 256, size=9).tolist(),
                         SamplingParams(max_tokens=16, **GREEDY))
    second = tight.submit(long_prompt, p)
    for _ in range(500):
        if not tight.has_work():
            break
        tight.step()
    assert first.finished and second.finished
    assert second.output == solo
    assert tight.preemptions >= 1


def test_submit_accepts_out_of_bucket_prompt_within_pages():
    eng = make_engine(prefill_buckets=(8,))  # max_model_len = 64
    req = eng.submit(list(range(1, 41)), SamplingParams(max_tokens=2, **GREEDY))
    while not req.finished:
        eng.step()
    assert len(req.output) == 2
    with pytest.raises(ValueError, match="max_model_len"):
        eng.submit(list(range(70)), SamplingParams(max_tokens=2, **GREEDY))
