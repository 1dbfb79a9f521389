"""OpenAI surface extras: logprobs, penalties, 429 backpressure,
stream_options usage, echo, best_of, suffix rejection, top_k cap.

vLLM-parity features the reference's clients would exercise against the
pulled image (SURVEY §2.3 row 1); VERDICT r1 items #8/#9 and weak #5.
"""

import asyncio
import json

import jax
import numpy as np

from aiohttp.test_utils import TestClient, TestServer

from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig
from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer


def make_server(**engine_kw):
    defaults = dict(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=4, num_pages=256, pages_per_slot=32,
        prefill_buckets=(32, 64),
    )
    defaults.update(engine_kw)
    eng = Engine(EngineConfig(**defaults))
    return OpenAIServer(eng, ByteTokenizer(), "debug-tiny")


def with_client(fn, **engine_kw):
    async def go():
        server = make_server(**engine_kw)
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            await fn(client)
        finally:
            await client.close()
    asyncio.run(go())


def test_completions_logprobs_legacy_format():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "ab", "max_tokens": 4,
            "temperature": 0, "logprobs": 3,
        })
        assert r.status == 200
        lp = (await r.json())["choices"][0]["logprobs"]
        assert len(lp["tokens"]) == 4
        assert len(lp["token_logprobs"]) == 4
        assert all(x <= 1e-4 for x in lp["token_logprobs"])  # <=0 up to fp eps
        # dict-keyed by token STRING (legacy format): distinct ids that
        # decode to the same text (byte tokenizer "?") may collide
        assert all(1 <= len(t) <= 3 for t in lp["top_logprobs"])
        # offsets are cumulative over the completion text
        assert lp["text_offset"][0] == 0
        assert lp["text_offset"] == sorted(lp["text_offset"])
    with_client(body)


def test_chat_logprobs_format():
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 3, "temperature": 0,
            "logprobs": True, "top_logprobs": 2,
        })
        assert r.status == 200
        choice = (await r.json())["choices"][0]
        content = choice["logprobs"]["content"]
        assert len(content) == 3
        for e in content:
            assert set(e) == {"token", "logprob", "bytes", "top_logprobs"}
            assert len(e["top_logprobs"]) == 2
            assert e["logprob"] <= 1e-4
            # greedy: the chosen token IS the top-1 alternative (compare
            # logprobs: the chosen "token" string is the EMITTED piece,
            # which may be held back ("") for a mid-UTF-8 byte while the
            # isolated-decoded alternative shows a replacement char)
            assert e["top_logprobs"][0]["logprob"] == e["logprob"]
        # emitted pieces concatenate exactly to the message text
        assert "".join(e["token"] for e in content) == \
            choice["message"]["content"]
    with_client(body)


def test_logprobs_cap_rejected():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "a", "logprobs": 50,
        })
        assert r.status == 400
        assert "at most" in (await r.json())["error"]["message"]
    with_client(body)


def test_top_k_above_pool_rejected():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "a", "top_k": 200,
        })
        assert r.status == 400
        assert "top_k" in (await r.json())["error"]["message"]
    with_client(body)


def test_penalties_accepted_and_validated():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "aaaa", "max_tokens": 6,
            "temperature": 0, "presence_penalty": 1.5,
            "frequency_penalty": 0.5,
        })
        assert r.status == 200
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "a", "presence_penalty": 3.0,
        })
        assert r.status == 400
    with_client(body)


def test_queue_full_returns_429():
    async def body(client):
        # max_waiting=1 and a server whose engine loop is NOT running (we
        # drive requests concurrently): flood fast enough that the queue
        # bound trips before admission drains it
        results = await asyncio.gather(*[
            client.post("/v1/completions", json={
                "model": "debug-tiny", "prompt": "abc", "max_tokens": 32,
                "temperature": 0,
            })
            for _ in range(12)
        ])
        statuses = sorted(r.status for r in results)
        assert statuses[0] == 200          # admitted requests succeed
        assert 429 in statuses             # the flood hits the bound
        for r in results:
            if r.status == 429:
                assert r.headers.get("Retry-After") == "1"
    with_client(body, max_waiting=1, max_decode_slots=1)


def test_stream_options_include_usage():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "abcd", "max_tokens": 5,
            "temperature": 0, "stream": True,
            "stream_options": {"include_usage": True},
        })
        assert r.status == 200
        raw = (await r.read()).decode()
        frames = [json.loads(line[6:]) for line in raw.splitlines()
                  if line.startswith("data: ") and line != "data: [DONE]"]
        usage_frames = [f for f in frames if f.get("usage")]
        assert len(usage_frames) == 1
        u = usage_frames[-1]["usage"]
        assert u["prompt_tokens"] == 4 and u["completion_tokens"] == 5
        assert usage_frames[0]["choices"] == []
    with_client(body)


def test_echo_prepends_prompt():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "hello", "max_tokens": 2,
            "temperature": 0, "echo": True,
        })
        assert r.status == 200
        text = (await r.json())["choices"][0]["text"]
        assert text.startswith("hello")
        # echo+logprobs (round 4): PROMPT-token logprobs via the scoring
        # forward — first entry null, offsets cover the echoed text, and
        # the generated tokens' entries follow (OpenAI semantics)
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "hello", "max_tokens": 2,
            "temperature": 0, "echo": True, "logprobs": 2,
        })
        assert r.status == 200
        data = await r.json()
        ch = data["choices"][0]
        lp = ch["logprobs"]
        n_prompt, n_gen = len("hello"), 2  # byte tokenizer: 1 tok/char
        assert len(lp["tokens"]) == n_prompt + n_gen
        assert lp["token_logprobs"][0] is None
        assert lp["top_logprobs"][0] is None
        assert all(isinstance(x, float) and x <= 0.0
                   for x in lp["token_logprobs"][1:])
        # dict keys are decoded token STRINGS: distinct ids may decode to
        # the same replacement char under the byte tokenizer, so entries
        # hold 1..nlp keys
        assert all(1 <= len(d) <= 2 for d in lp["top_logprobs"][1:])
        # offsets index into the FULL echoed text
        assert lp["text_offset"][0] == 0
        for i, t in enumerate(lp["tokens"]):
            assert ch["text"][lp["text_offset"][i]:][:len(t)] == t
    with_client(body)


def test_prompt_scoring_matches_full_softmax():
    """engine.score_prompt's per-position logprobs must equal a direct
    log-softmax of the model's logits at each prefix (pinned on the tiny
    model against an independent forward)."""
    import jax.numpy as jnp

    from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig
    from llms_on_kubernetes_tpu.engine.cache import (
        CacheConfig, PageAllocator, init_pages,
    )
    from llms_on_kubernetes_tpu.models.decoder import forward_prefill

    eng = Engine(EngineConfig(
        model="debug-tiny", dtype="float32", max_decode_slots=2,
        page_size=8, num_pages=32, pages_per_slot=4, prefill_buckets=(16,)))
    prompt = [5, 9, 42, 17, 3, 7]
    lps, top_ids, top_lps = eng.score_prompt(prompt)
    assert len(lps) == len(prompt) - 1
    assert len(top_ids) == len(prompt)

    # reference: run the SERVING prefill on each prefix and log-softmax
    cfg = eng.model_config
    for i in range(1, len(prompt)):
        cc = CacheConfig(num_layers=cfg.num_layers,
                         num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                         num_pages=8, page_size=8, pages_per_slot=4,
                         dtype="float32")
        kp, vp = init_pages(cc)
        al = PageAllocator(cc.num_pages, cc.page_size, 1, cc.pages_per_slot)
        al.allocate(0, i)
        toks = np.zeros((1, 16), np.int32)
        toks[0, :i] = prompt[:i]
        logits, _, _ = forward_prefill(
            eng.params, cfg, jnp.asarray(toks), jnp.asarray([i], jnp.int32),
            kp, vp, jnp.asarray(al.page_tables))
        ref = np.asarray(logits[0] - jax.nn.logsumexp(logits[0]))
        np.testing.assert_allclose(lps[i - 1], ref[prompt[i]],
                                   rtol=1e-4, atol=1e-4)
        # top-k of the same position agrees
        want_top = np.argsort(ref)[::-1][:4]
        assert set(top_ids[i - 1][:2]) <= set(want_top.tolist())



def test_best_of_selects_n_best():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "ab", "max_tokens": 4,
            "temperature": 0.9, "seed": 7, "n": 2, "best_of": 5,
            "logprobs": 1,
        })
        assert r.status == 200
        data = await r.json()
        assert len(data["choices"]) == 2
        assert [c["index"] for c in data["choices"]] == [0, 1]
        # best_of with stream is rejected
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "ab", "n": 1, "best_of": 3,
            "stream": True,
        })
        assert r.status == 400
        # best_of < n is invalid
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "ab", "n": 4, "best_of": 2,
        })
        assert r.status == 400
    with_client(body)


def test_suffix_rejected():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "ab", "suffix": "end",
        })
        assert r.status == 400
        assert "suffix" in (await r.json())["error"]["message"]
    with_client(body)


def test_streaming_chat_logprobs():
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "yo"}],
            "max_tokens": 3, "temperature": 0, "stream": True,
            "logprobs": True, "top_logprobs": 1,
        })
        assert r.status == 200
        raw = (await r.read()).decode()
        frames = [json.loads(line[6:]) for line in raw.splitlines()
                  if line.startswith("data: ") and line != "data: [DONE]"]
        lp_frames = [f for f in frames
                     if f["choices"] and f["choices"][0].get("logprobs")]
        assert lp_frames, "no logprobs in any stream chunk"
        entry = lp_frames[0]["choices"][0]["logprobs"]["content"][0]
        assert entry["logprob"] <= 1e-4 and len(entry["top_logprobs"]) == 1
    with_client(body)


def test_logprobs_truncated_at_stop_sequence():
    """Entries must stop where the text does when a stop sequence matches
    (OpenAI truncates logprobs at the stop)."""
    async def body(client):
        # find greedy output first, then stop on a substring of it
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "abc", "max_tokens": 8,
            "temperature": 0,
        })
        full = (await r.json())["choices"][0]["text"]
        if len(full) < 3:
            return  # degenerate model output; nothing to cut
        stop = full[1]
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "abc", "max_tokens": 8,
            "temperature": 0, "stop": [stop], "logprobs": 1,
        })
        data = (await r.json())["choices"][0]
        lp = data["logprobs"]
        joined = "".join(lp["tokens"])
        assert stop not in data["text"]
        # no entry may start beyond the visible text
        assert all(off <= len(data["text"]) for off in lp["text_offset"])
        assert len(joined) <= len(data["text"]) + len(stop)
    with_client(body)


def test_negative_logprobs_rejected():
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "x"}],
            "logprobs": True, "top_logprobs": -1,
        })
        assert r.status == 400
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "x", "logprobs": -2,
        })
        assert r.status == 400
    with_client(body)


def test_prompt_scoring_moe_not_zeroed():
    """MoE models must score with the experts ACTIVE: the scoring forward
    routes writes to trash, and an all-invalid write mask must not leak
    into the MoE routing validity (round-4 review: every expert claim was
    masked, zeroing the MLP)."""
    from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig

    eng = Engine(EngineConfig(
        model="debug-moe", dtype="float32", max_decode_slots=2,
        page_size=8, num_pages=32, pages_per_slot=4, prefill_buckets=(16,)))
    prompt = [5, 9, 42, 17, 3, 7]
    lps, _, _ = eng.score_prompt(prompt)

    # reference: serving prefill per prefix (experts active there)
    import jax.numpy as jnp

    from llms_on_kubernetes_tpu.engine.cache import (
        CacheConfig, PageAllocator, init_pages,
    )
    from llms_on_kubernetes_tpu.models.decoder import forward_prefill

    cfg = eng.model_config
    for i in (2, len(prompt) - 1):
        cc = CacheConfig(num_layers=cfg.num_layers,
                         num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                         num_pages=8, page_size=8, pages_per_slot=4,
                         dtype="float32")
        kp, vp = init_pages(cc)
        al = PageAllocator(cc.num_pages, cc.page_size, 1, cc.pages_per_slot)
        al.allocate(0, i)
        toks = np.zeros((1, 16), np.int32)
        toks[0, :i] = prompt[:i]
        logits, _, _ = forward_prefill(
            eng.params, cfg, jnp.asarray(toks), jnp.asarray([i], jnp.int32),
            kp, vp, jnp.asarray(al.page_tables))
        ref = np.asarray(logits[0] - jax.nn.logsumexp(logits[0]))
        np.testing.assert_allclose(lps[i - 1], ref[prompt[i]],
                                   rtol=1e-4, atol=1e-4)


def test_echo_logprobs_stream_is_400():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "hi", "max_tokens": 2,
            "temperature": 0, "echo": True, "logprobs": 1, "stream": True,
        })
        assert r.status == 400
        assert "streamed" in (await r.json())["error"]["message"]
    with_client(body)


# -- vllm-openai utility endpoints (/tokenize, /detokenize, /version,
# 501 embeddings — VERDICT r4 missing #5) ------------------------------

def test_tokenize_prompt_and_messages():
    async def body(client):
        r = await client.post("/tokenize", json={"prompt": "hello"})
        assert r.status == 200
        out = await r.json()
        assert out["tokens"] == [ord(c) for c in "hello"]
        assert out["count"] == 5
        assert out["max_model_len"] == 4 * 32  # page_size * pages_per_slot
        r = await client.post("/tokenize", json={
            "messages": [{"role": "user", "content": "hi"}]})
        assert r.status == 200
        out = await r.json()
        assert out["count"] == len(out["tokens"]) > 0
        # neither form -> 400
        r = await client.post("/tokenize", json={"nope": 1})
        assert r.status == 400
    with_client(body)


def test_detokenize_roundtrip_and_validation():
    async def body(client):
        ids = [ord(c) for c in "round trip"]
        r = await client.post("/detokenize", json={"tokens": ids})
        assert r.status == 200
        assert (await r.json())["prompt"] == "round trip"
        r = await client.post("/detokenize", json={"tokens": [0, 10 ** 9]})
        assert r.status == 400
        r = await client.post("/detokenize", json={"tokens": "abc"})
        assert r.status == 400
        r = await client.post("/detokenize", json={"tokens": [1, True]})
        assert r.status == 400
    with_client(body)


def test_version_and_embeddings_501():
    async def body(client):
        r = await client.get("/version")
        assert r.status == 200
        assert (await r.json())["version"]
        r = await client.post("/v1/embeddings", json={
            "model": "debug-tiny", "input": "x"})
        assert r.status == 501
        assert "not supported" in (await r.json())["error"]["message"]
    with_client(body)


def test_logit_bias_duplicate_ids_rejected():
    """Direct submit() with duplicate logit_bias ids must 400, not apply
    the bias twice (round-4 advisor finding)."""
    import pytest

    from llms_on_kubernetes_tpu.engine.engine import SamplingParams

    eng = Engine(EngineConfig(
        model="debug-tiny", dtype="float32", max_decode_slots=2,
        page_size=4, num_pages=32, pages_per_slot=8, prefill_buckets=(16,)))
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit([1, 2, 3], SamplingParams(
            logit_bias=((5, 10.0), (5, 10.0))))
