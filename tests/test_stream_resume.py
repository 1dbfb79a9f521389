"""Zero-drop streams: deterministic mid-stream failover (PR 9).

Three layers under test:

- engine: ``SamplingParams.prefix_tokens`` rides the preemption-resume
  admission path, so a resumed request draws exactly the tokens it would
  have drawn uninterrupted (greedy trivially; seeded sampling because the
  per-token key is ``fold_in(request_key, position)``).
- API: the router-internal resume protocol — ``X-LLMK-Journal`` turns on
  ``: llmk-tok`` comments, ``X-LLMK-Resume-Tokens`` replays a journaled
  prefix idempotently (same stream id, no duplicate role chunk).
- router: the stream journal records what the client has, and on a
  mid-stream upstream death splices a continuation from another replica
  into the SAME client SSE stream — or ends it with an explicit error
  event (finish_reason=upstream_lost) when no resume is possible.

The end-to-end proof: two real engines behind the router, one killed
mid-stream by ``LLMK_FAULT=kill_mid_stream``, and the client-visible
text is byte-identical to an uninterrupted run.
"""

import asyncio
import dataclasses
import json

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from llms_on_kubernetes_tpu import faults
from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig, SamplingParams
from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer
from llms_on_kubernetes_tpu.server.router import Router

GREEDY = dict(temperature=0.0)
SEEDED = dict(temperature=0.9, top_k=20, seed=1234)


def make_engine(**kw):
    defaults = dict(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=4, num_pages=128, pages_per_slot=16,
        prefill_buckets=(16, 32),
    )
    defaults.update(kw)
    return Engine(EngineConfig(**defaults))


# ---------------------------------------------------------------------------
# engine: prefix_tokens resume determinism


@pytest.mark.parametrize("decode_steps", [1, 4])
@pytest.mark.parametrize("sampling", [GREEDY, SEEDED],
                         ids=["greedy", "seeded"])
def test_resume_bit_identical(decode_steps, sampling):
    """Kill-after-N + resume-with-prefix must reproduce the uninterrupted
    stream token for token, at every cut point, for greedy AND seeded
    sampling, with single-step and fused multi-step decode."""
    p = SamplingParams(max_tokens=12, **sampling)
    prompt = [3, 17, 9, 5]
    full = make_engine(decode_steps=decode_steps).generate(prompt, p)
    assert len(full) == 12
    for cut in (1, 5, 11):
        p2 = dataclasses.replace(p, prefix_tokens=tuple(full[:cut]))
        eng = make_engine(decode_steps=decode_steps)
        req = eng.submit(prompt, p2)
        for _ in range(300):
            if req.finished:
                break
            eng.step()
        assert req.finished
        assert req.output == full, f"resume diverged at cut={cut}"


def test_resume_with_penalties_matches_uninterrupted():
    """Penalty counts are rebuilt from the replayed prefix (positions past
    prompt_len count as output), so penalized resumes are exact too."""
    p = SamplingParams(max_tokens=10, presence_penalty=1.5,
                       frequency_penalty=0.5, **GREEDY)
    prompt = [3, 17, 9, 5]
    full = make_engine().generate(prompt, p)
    p2 = dataclasses.replace(p, prefix_tokens=tuple(full[:4]))
    eng = make_engine()
    req = eng.submit(prompt, p2)
    while not req.finished:
        eng.step()
    assert req.output == full


def test_prefix_counts_toward_max_tokens():
    eng = make_engine()
    p = SamplingParams(max_tokens=8, **GREEDY)
    full = eng.generate([1, 2, 3], p)
    eng2 = make_engine()
    req = eng2.submit([1, 2, 3], dataclasses.replace(
        p, prefix_tokens=tuple(full[:5])))
    while not req.finished:
        eng2.step()
    assert len(req.output) == 8
    assert req.finish_reason == "length"


def test_prefix_validation():
    eng = make_engine()
    with pytest.raises(ValueError, match="outside the vocabulary"):
        eng.submit([1, 2], SamplingParams(
            max_tokens=4, prefix_tokens=(10 ** 9,), **GREEDY))
    with pytest.raises(ValueError, match="max_tokens"):
        eng.submit([1, 2], SamplingParams(
            max_tokens=2, prefix_tokens=(5, 6), **GREEDY))


# ---------------------------------------------------------------------------
# API: journal comments, resume replay, keepalive


def make_server():
    return OpenAIServer(make_engine(num_pages=256, pages_per_slot=32,
                                    prefill_buckets=(32, 64)),
                        ByteTokenizer(), "debug-tiny")


def with_client(fn):
    async def go():
        server = make_server()
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            await fn(client)
        finally:
            await client.close()
    asyncio.run(go())


def sse_events(raw: str) -> list[dict]:
    return [json.loads(l[6:]) for l in raw.splitlines()
            if l.startswith("data: ") and l != "data: [DONE]"]


def stream_text(raw: str) -> str:
    return "".join(e["choices"][0]["delta"].get("content", "")
                   for e in sse_events(raw))


STREAM_BODY = {
    "model": "debug-tiny",
    "messages": [{"role": "user", "content": "hello"}],
    "max_tokens": 8, "temperature": 0, "stream": True,
}


def test_journal_header_emits_tok_comments_after_data():
    async def body(client):
        r = await client.post("/v1/chat/completions", json=STREAM_BODY,
                              headers={"X-LLMK-Journal": "1"})
        raw = await r.text()
        toks = []
        data_seen = 0
        for line in raw.splitlines():
            if line.startswith("data: "):
                data_seen += 1
            elif line.startswith(": llmk-tok"):
                # every comment follows at least one data line (the
                # comment-AFTER-data splice invariant)
                assert data_seen > 0
                toks += [int(x) for x in line[len(": llmk-tok"):].split(",")
                         if x.strip()]
        assert len(toks) == 8  # every generated token journaled
        # without the header: no journal comments
        r = await client.post("/v1/chat/completions", json=STREAM_BODY)
        assert ": llmk-tok" not in await r.text()
    with_client(body)


def test_resume_headers_replay_idempotently():
    """A resumed stream continues the original: same id, no role chunk,
    and journal(prefix) + continuation == the uninterrupted stream."""
    async def body(client):
        r = await client.post("/v1/chat/completions", json=STREAM_BODY,
                              headers={"X-LLMK-Journal": "1"})
        raw = await r.text()
        full_text = stream_text(raw)
        # walk the original stream to the point where the journal held
        # `cut` tokens: the text delivered by then is what a dead replica's
        # client would have seen (NOT a finalized max_tokens=cut run — the
        # detokenizer's partial-UTF-8 holdback is still in flight here)
        cut = 3
        toks: list[int] = []
        delivered = ""
        at_cut = None
        for line in raw.splitlines():
            if line.startswith("data: ") and line != "data: [DONE]":
                delivered += json.loads(line[6:])["choices"][0][
                    "delta"].get("content", "")
            elif line.startswith(": llmk-tok"):
                toks += [int(x) for x in line[len(": llmk-tok"):].split(",")
                         if x.strip()]
                if at_cut is None and len(toks) >= cut:
                    at_cut = delivered
        assert at_cut is not None
        r2 = await client.post(
            "/v1/chat/completions", json=STREAM_BODY,
            headers={"X-LLMK-Resume-Tokens": ",".join(map(str, toks[:cut])),
                     "X-LLMK-Resume-Stream-Id": "chatcmpl-orig",
                     "X-LLMK-Resume-Created": "12345"})
        raw2 = await r2.text()
        events = sse_events(raw2)
        assert events, raw2
        assert all(e["id"] == "chatcmpl-orig" for e in events)
        assert all(e["created"] == 12345 for e in events)
        # no duplicate role delta on a splice
        assert not any(e["choices"][0]["delta"].get("role") for e in events)
        # continuation picks up exactly where the delivered text ended
        assert at_cut + stream_text(raw2) == full_text
        assert "[DONE]" in raw2
    with_client(body)


def test_resume_rejected_on_non_streaming_and_malformed():
    async def body(client):
        r = await client.post(
            "/v1/chat/completions",
            json={**STREAM_BODY, "stream": False},
            headers={"X-LLMK-Resume-Tokens": "1,2"})
        assert r.status == 400
        r = await client.post(
            "/v1/chat/completions", json=STREAM_BODY,
            headers={"X-LLMK-Resume-Tokens": "1,zap"})
        assert r.status == 400
        assert "malformed" in (await r.json())["error"]["message"]
    with_client(body)


def test_sse_keepalive_pings(monkeypatch):
    monkeypatch.setenv("LLMK_SSE_KEEPALIVE_S", "0.001")

    async def body(client):
        r = await client.post("/v1/chat/completions",
                              json={**STREAM_BODY, "max_tokens": 16})
        raw = await r.text()
        assert ": ping" in raw
        # comments must not disturb the data stream
        assert stream_text(raw)
        assert "[DONE]" in raw
    with_client(body)


def test_kill_mid_stream_fault_severs_socket(monkeypatch):
    monkeypatch.setenv("LLMK_FAULT", "kill_mid_stream:3")
    faults.reset_claims()

    async def body(client):
        r = await client.post("/v1/chat/completions",
                              json={**STREAM_BODY, "max_tokens": 12})
        try:
            raw = await r.text()
            # if the abort raced the read, we must NOT have a full stream
            assert "[DONE]" not in raw
        except (aiohttp_client_error, ConnectionResetError):
            pass
        # one-shot: the next stream survives
        faults_active = faults.claim("kill_mid_stream")
        assert not faults_active
        r2 = await client.post("/v1/chat/completions", json=STREAM_BODY)
        assert "[DONE]" in await r2.text()

    import aiohttp
    aiohttp_client_error = aiohttp.ClientError
    try:
        with_client(body)
    finally:
        faults.reset_claims()


# ---------------------------------------------------------------------------
# router: journal splice against protocol-faithful fake backends

TOKENS = list(range(101, 109))  # the fake model's deterministic stream


def tok_text(i: int) -> str:
    return f"t{i} "


FULL_TEXT = "".join(tok_text(i) for i in range(len(TOKENS)))


def make_gen_backend(name: str, fail: dict | None = None) -> web.Application:
    """A fake replica speaking the resume protocol: deterministic token
    stream, ``: llmk-tok`` comments when journaling is requested, honest
    continuation from ``X-LLMK-Resume-Tokens``. ``fail`` kills the socket
    once: {"mode": "before_comment"|"after_comment"|"after_finish",
    "after": N}.
    """
    async def chat(request: web.Request) -> web.StreamResponse:
        body = await request.json()
        assert body.get("stream") is True
        journal_on = "X-LLMK-Journal" in request.headers
        raw_resume = request.headers.get("X-LLMK-Resume-Tokens")
        resumed = raw_resume is not None
        prefix = ([int(t) for t in raw_resume.split(",") if t.strip()]
                  if resumed else [])
        assert prefix == TOKENS[:len(prefix)]
        rid = request.headers.get("X-LLMK-Resume-Stream-Id") or f"cmpl-{name}"
        created = int(request.headers.get("X-LLMK-Resume-Created") or 111)
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)

        def chunk(delta: dict, fr=None) -> bytes:
            return ("data: " + json.dumps({
                "id": rid, "object": "chat.completion.chunk",
                "created": created, "model": body.get("model"),
                "choices": [{"index": 0, "delta": delta,
                             "finish_reason": fr}]}) + "\n\n").encode()

        async def die():
            fail["done"] = True
            request.transport.abort()

        if not resumed:
            await resp.write(chunk({"role": "assistant"}))
        armed = fail is not None and not fail.get("done")
        sent = 0
        for i in range(len(prefix), len(TOKENS)):
            await resp.write(chunk({"content": tok_text(i)}))
            sent += 1
            if armed and fail["mode"] == "before_comment" \
                    and sent >= fail["after"]:
                await die()
                return resp
            if journal_on:
                await resp.write(f": llmk-tok {TOKENS[i]}\n\n".encode())
            if armed and fail["mode"] == "after_comment" \
                    and sent >= fail["after"]:
                await die()
                return resp
            await asyncio.sleep(0)
        await resp.write(chunk({}, "stop"))
        if armed and fail["mode"] == "after_finish":
            await die()
            return resp
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    app = web.Application()
    app.router.add_post("/v1/chat/completions", chat)
    return app


def run_two_replicas(fn, fail1=None, fail2=None, **router_kw):
    async def go():
        b1 = TestClient(TestServer(make_gen_backend("r1", fail1)))
        b2 = TestClient(TestServer(make_gen_backend("r2", fail2)))
        await b1.start_server()
        await b2.start_server()
        u1 = str(b1.make_url("")).rstrip("/")
        u2 = str(b2.make_url("")).rstrip("/")
        router = Router({"m": [u1, u2]}, breaker_threshold=100, **router_kw)
        client = TestClient(TestServer(router.make_app()))
        await client.start_server()
        try:
            await fn(client, router)
        finally:
            await client.close()
            await b1.close()
            await b2.close()
    asyncio.run(go())


STREAM_REQ = {"model": "m", "stream": True,
              "messages": [{"role": "user", "content": "go"}]}


def assert_clean_client_stream(raw: str, resumed: bool = True):
    """The spliced stream must be indistinguishable from an uninterrupted
    one: full text exactly once, one role delta, one finish, terminated,
    and no internal journal comments leaked."""
    assert ": llmk-tok" not in raw
    events = sse_events(raw)
    text = "".join(e["choices"][0]["delta"].get("content", "")
                   for e in events)
    assert text == FULL_TEXT, f"client text diverged: {text!r}"
    roles = [e for e in events if e["choices"][0]["delta"].get("role")]
    assert len(roles) == 1
    finals = [e for e in events if e["choices"][0]["finish_reason"]]
    assert len(finals) == 1 and finals[0]["choices"][0][
        "finish_reason"] == "stop"
    assert raw.rstrip().endswith("data: [DONE]")
    # the splice keeps the original stream identity end to end
    assert len({e["id"] for e in events}) == 1


def test_mid_stream_death_resumes_on_other_replica():
    async def body(client, router):
        r = await client.post("/v1/chat/completions", json=STREAM_REQ)
        assert r.status == 200
        raw = await r.text()
        assert_clean_client_stream(raw)
        assert router.metrics["stream_resume"].labeled_value(
            outcome="ok") == 1
        assert router.metrics["stream_truncated"].labeled_value(
            model="m") is None
    # whichever replica gets the request dies after 3 tokens
    fail = {"mode": "after_comment", "after": 3}
    run_two_replicas(body, fail1=fail, fail2=fail)


def test_client_hang_up_closes_the_upstream():
    """A client that disconnects mid-stream must take the upstream down
    with it: left open, the replica keeps decoding the abandoned stream
    to its end (seen from chip_smoke.py as a SIGTERM'd server that would
    not drain)."""
    async def go():
        gone = asyncio.Event()

        async def chat(request: web.Request) -> web.StreamResponse:
            resp = web.StreamResponse(
                headers={"Content-Type": "text/event-stream"})
            await resp.prepare(request)
            frame = ("data: " + json.dumps({"choices": [
                {"index": 0, "delta": {"content": "x"},
                 "finish_reason": None}]}) + "\n\n").encode()
            try:
                while True:             # a stream with no end of its own
                    await resp.write(frame)
                    await asyncio.sleep(0.01)
            except (ConnectionResetError, asyncio.CancelledError):
                gone.set()
                raise

        app = web.Application()
        app.router.add_post("/v1/chat/completions", chat)
        backend = TestClient(TestServer(app))
        await backend.start_server()
        router = Router({"m": [str(backend.make_url("")).rstrip("/")]})
        client = TestClient(TestServer(router.make_app()))
        await client.start_server()
        try:
            r = await client.post("/v1/chat/completions", json=STREAM_REQ)
            assert r.status == 200
            await r.content.readany()
            r.close()
            await asyncio.wait_for(gone.wait(), timeout=10)
        finally:
            await client.close()
            await backend.close()
    asyncio.run(go())


def test_resume_trims_replayed_echo():
    """Death BETWEEN a data chunk and its tok comment: the client has text
    the journal does not. The resumed replica deterministically re-emits
    that token's text and the router must drop the echo."""
    async def body(client, router):
        r = await client.post("/v1/chat/completions", json=STREAM_REQ)
        raw = await r.text()
        assert_clean_client_stream(raw)
        assert router.metrics["stream_resume"].labeled_value(
            outcome="ok") == 1
    fail = {"mode": "before_comment", "after": 2}
    run_two_replicas(body, fail1=fail, fail2=fail)


def test_death_after_finish_completes_without_resume():
    """finish_reason already relayed, only [DONE] lost: the router finishes
    the stream itself instead of splicing past a completed generation."""
    async def body(client, router):
        r = await client.post("/v1/chat/completions", json=STREAM_REQ)
        raw = await r.text()
        assert_clean_client_stream(raw)
        assert router.metrics["stream_resume"].labeled_value(
            outcome="ok") is None
    fail = {"mode": "after_finish", "after": 0}
    run_two_replicas(body, fail1=fail, fail2=fail)


def test_resume_disabled_truncates_with_error_event():
    async def body(client, router):
        r = await client.post("/v1/chat/completions", json=STREAM_REQ)
        raw = await r.text()
        assert "event: error" in raw
        finals = [e for e in sse_events(raw)
                  if e["choices"][0].get("finish_reason")]
        assert finals[-1]["choices"][0]["finish_reason"] == "upstream_lost"
        assert router.metrics["stream_truncated"].labeled_value(
            model="m") == 1
        assert router.metrics["stream_resume"].labeled_value(
            outcome="ok") is None
    fail = {"mode": "after_comment", "after": 3}
    run_two_replicas(body, fail1=fail, fail2=fail, stream_resume=False)


def test_resume_gave_up_when_attempts_exhausted():
    async def body(client, router):
        r = await client.post("/v1/chat/completions", json=STREAM_REQ)
        raw = await r.text()
        assert "event: error" in raw
        assert router.metrics["stream_resume"].labeled_value(
            outcome="gave_up") == 1
        assert router.metrics["stream_resume"].labeled_value(
            outcome="ok") is None
        assert router.metrics["stream_truncated"].labeled_value(
            model="m") == 1

    fail = {"mode": "after_comment", "after": 3}
    run_two_replicas(body, fail1=fail, fail2=fail, resume_attempts=0)


def test_journal_comments_never_reach_client_even_unresumed():
    async def body(client, router):
        r = await client.post("/v1/chat/completions", json=STREAM_REQ)
        raw = await r.text()
        assert ": llmk-tok" not in raw
        assert stream_text(raw) == FULL_TEXT
        assert router.metrics["stream_resume"].labeled_value(
            outcome="ok") is None
    run_two_replicas(body)


def test_resume_attempts_cap(monkeypatch):
    """Both replicas die mid-stream repeatedly; with LLMK_RESUME_ATTEMPTS=1
    the second death truncates instead of splicing forever."""
    async def body(client, router):
        r = await client.post("/v1/chat/completions", json=STREAM_REQ)
        raw = await r.text()
        assert "event: error" in raw
        # one successful splice, then the second death exhausts the cap
        assert router.metrics["stream_resume"].labeled_value(
            outcome="ok") == 1
        assert router.metrics["stream_resume"].labeled_value(
            outcome="gave_up") == 1

    class Always(dict):
        def get(self, k, default=None):  # never marks itself done
            if k == "done":
                return False
            return super().get(k, default)

        def __setitem__(self, k, v):
            if k == "done":
                return
            super().__setitem__(k, v)

    fail1 = Always(mode="after_comment", after=3)
    fail2 = Always(mode="after_comment", after=3)
    run_two_replicas(body, fail1=fail1, fail2=fail2, resume_attempts=1)


# ---------------------------------------------------------------------------
# router: hedged requests


def make_laggy_backend(name: str, first_byte_delay: float) -> web.Application:
    async def chat(request: web.Request) -> web.StreamResponse:
        body = await request.json()
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        try:
            await asyncio.sleep(first_byte_delay)
            for i in range(len(TOKENS)):
                await resp.write(
                    ("data: " + json.dumps({
                        "id": f"cmpl-{name}", "object": "chat.completion.chunk",
                        "created": 111, "model": body.get("model"),
                        "choices": [{"index": 0,
                                     "delta": {"content": tok_text(i)},
                                     "finish_reason": None}]}) + "\n\n").encode())
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
        except (ConnectionResetError, asyncio.CancelledError):
            pass  # we lost the hedge race; the router hung up
        return resp

    app = web.Application()
    app.router.add_post("/v1/chat/completions", chat)
    return app


def run_hedge(fn, delay1, delay2, hedge_ms, **router_kw):
    async def go():
        b1 = TestClient(TestServer(make_laggy_backend("slow", delay1)))
        b2 = TestClient(TestServer(make_laggy_backend("fast", delay2)))
        await b1.start_server()
        await b2.start_server()
        u1 = str(b1.make_url("")).rstrip("/")
        u2 = str(b2.make_url("")).rstrip("/")
        router = Router({"m": [u1, u2]}, hedge_ms=hedge_ms, **router_kw)
        # force the first backend to be the P2C primary: the second starts
        # with artificial load, so hedging must be what reaches it
        router.replicas["m"][1].inflight = 50
        client = TestClient(TestServer(router.make_app()))
        await client.start_server()
        try:
            await fn(client, router)
        finally:
            await client.close()
            await b1.close()
            await b2.close()
    asyncio.run(go())


def test_hedge_secondary_wins_when_primary_stalls():
    async def body(client, router):
        r = await client.post("/v1/chat/completions", json=STREAM_REQ)
        raw = await r.text()
        events = sse_events(raw)
        # exactly one stream reached the client — the fast hedge
        assert {e["id"] for e in events} == {"cmpl-fast"}
        assert "".join(e["choices"][0]["delta"].get("content", "")
                       for e in events) == FULL_TEXT
        assert router.metrics["hedged"].labeled_value(
            outcome="hedge_won") == 1
        assert router.metrics["hedged"].labeled_value(
            outcome="primary_won") is None
    run_hedge(body, delay1=2.0, delay2=0.0, hedge_ms=40)


def test_hedge_primary_wins_when_faster():
    async def body(client, router):
        r = await client.post("/v1/chat/completions", json=STREAM_REQ)
        raw = await r.text()
        events = sse_events(raw)
        assert {e["id"] for e in events} == {"cmpl-slow"}
        assert router.metrics["hedged"].labeled_value(
            outcome="primary_won") == 1
    # primary's first byte lands after the hedge fires but well before the
    # (much slower) secondary's
    run_hedge(body, delay1=0.3, delay2=2.0, hedge_ms=40)


def test_hedge_downgrades_to_single_attempt_on_exhausted_budget():
    """A hedge is a speculative retry, so it draws from the cluster retry
    budget; with the budget exhausted the hedge must NOT launch — the
    request downgrades to the plain single-attempt path (keep waiting on
    the primary) instead of erroring, and the shed is counted."""
    async def body(client, router):
        r = await client.post("/v1/chat/completions", json=STREAM_REQ)
        assert r.status == 200
        raw = await r.text()
        events = sse_events(raw)
        # the slow primary served it — the fast secondary would have won
        # any hedge race, so its absence proves the hedge never launched
        assert {e["id"] for e in events} == {"cmpl-slow"}
        assert "".join(e["choices"][0]["delta"].get("content", "")
                       for e in events) == FULL_TEXT
        assert router.metrics["hedged"].labeled_value(
            outcome="hedge_won") is None
        assert router.metrics["hedged"].labeled_value(
            outcome="primary_won") is None
        assert router.metrics["retry_budget_exhausted"].value == 1
    run_hedge(body, delay1=0.3, delay2=0.0, hedge_ms=40,
              retry_budget={"ratio": 0, "min_per_s": 0, "burst": 0})


def test_hedge_off_by_default():
    async def body(client, router):
        assert router.hedge_ms == 0.0
        r = await client.post("/v1/chat/completions", json=STREAM_REQ)
        await r.text()
        assert router.metrics["hedged"].labeled_value(
            outcome="hedge_won") is None
    run_two_replicas(body)


# ---------------------------------------------------------------------------
# end to end: real engines, real kill, zero client-visible drops


def test_e2e_kill_mid_stream_splices_identical_text(monkeypatch):
    """Two real replicas behind the router; LLMK_FAULT=kill_mid_stream RSTs
    one mid-generation. The client stream must be byte-identical to an
    uninterrupted run — the PR's acceptance bar."""
    body_json = {
        "model": "debug-tiny",
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 10, "temperature": 0, "stream": True,
    }

    async def go():
        s1, s2 = make_server(), make_server()
        b1 = TestClient(TestServer(s1.make_app()))
        b2 = TestClient(TestServer(s2.make_app()))
        await b1.start_server()
        await b2.start_server()
        u1 = str(b1.make_url("")).rstrip("/")
        u2 = str(b2.make_url("")).rstrip("/")
        router = Router({"debug-tiny": [u1, u2]}, breaker_threshold=100)
        client = TestClient(TestServer(router.make_app()))
        await client.start_server()
        try:
            # uninterrupted reference (fault not yet armed)
            r = await client.post("/v1/chat/completions", json=body_json)
            reference = await r.text()
            ref_text = stream_text(reference)
            assert ref_text

            monkeypatch.setenv("LLMK_FAULT", "kill_mid_stream:4")
            faults.reset_claims()
            r = await client.post("/v1/chat/completions", json=body_json)
            assert r.status == 200
            raw = await r.text()
            assert stream_text(raw) == ref_text
            assert ": llmk-tok" not in raw
            assert raw.rstrip().endswith("data: [DONE]")
            assert router.metrics["stream_resume"].labeled_value(
                outcome="ok") == 1
            assert router.metrics["stream_truncated"].labeled_value(
                model="debug-tiny") is None
        finally:
            faults.reset_claims()
            monkeypatch.delenv("LLMK_FAULT", raising=False)
            await client.close()
            await b1.close()
            await b2.close()
    asyncio.run(go())
