"""OpenAI server tests: endpoints, streaming SSE, error handling, metrics.

Driven through real HTTP (aiohttp TestClient) against a debug-tiny engine
with the byte tokenizer — the reference's black-box curl runbook
(reference vllm-models/README.md:219-251) turned into automated tests.
"""

import asyncio
import json
import pathlib

import pytest
from aiohttp.test_utils import TestClient, TestServer

from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig
from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
from llms_on_kubernetes_tpu.server.openai_api import IncrementalDetokenizer, OpenAIServer


def make_server():
    eng = Engine(EngineConfig(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=4, num_pages=256, pages_per_slot=32,
        prefill_buckets=(32, 64),
    ))
    return OpenAIServer(eng, ByteTokenizer(), "debug-tiny")


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


def test_health_and_models():
    async def body(client):
        r = await client.get("/health")
        assert r.status == 200 and (await r.text()) == "OK"
        r = await client.get("/v1/models")
        data = await r.json()
        assert data["object"] == "list"
        assert data["data"][0]["id"] == "debug-tiny"
    with_client(body)


def test_chat_completion_non_streaming():
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 8, "temperature": 0,
        })
        assert r.status == 200
        data = await r.json()
        assert data["object"] == "chat.completion"
        assert data["choices"][0]["message"]["role"] == "assistant"
        assert data["choices"][0]["finish_reason"] in ("length", "stop")
        assert data["usage"]["completion_tokens"] <= 8
    with_client(body)


def test_completions_endpoint():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "abc", "max_tokens": 4,
            "temperature": 0,
        })
        data = await r.json()
        assert r.status == 200
        assert data["object"] == "text_completion"
        assert isinstance(data["choices"][0]["text"], str)
    with_client(body)


def test_streaming_sse_chunks():
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 6, "temperature": 0, "stream": True,
        })
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/event-stream")
        raw = await r.text()
        events = [l[6:] for l in raw.splitlines() if l.startswith("data: ")]
        assert events[-1] == "[DONE]"
        parsed = [json.loads(e) for e in events[:-1]]
        assert parsed[0]["choices"][0]["delta"].get("role") == "assistant"
        finals = [p for p in parsed if p["choices"][0]["finish_reason"]]
        assert len(finals) == 1
        assert parsed[0]["object"] == "chat.completion.chunk"
    with_client(body)


def test_streaming_matches_non_streaming_greedy():
    async def body(client):
        payload = {
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "xyz"}],
            "max_tokens": 8, "temperature": 0,
        }
        r1 = await client.post("/v1/chat/completions", json=payload)
        full = (await r1.json())["choices"][0]["message"]["content"]
        r2 = await client.post("/v1/chat/completions", json={**payload, "stream": True})
        raw = await r2.text()
        events = [l[6:] for l in raw.splitlines() if l.startswith("data: ")][:-1]
        text = "".join(
            json.loads(e)["choices"][0]["delta"].get("content", "") for e in events
        )
        assert text == full
    with_client(body)


def test_error_handling():
    async def body(client):
        r = await client.post("/v1/chat/completions", data=b"{not json")
        assert r.status == 400
        r = await client.post("/v1/chat/completions", json={"messages": []})
        assert r.status == 400
        r = await client.post("/v1/completions", json={"prompt": ""})
        assert r.status == 400
        # prompt longer than the largest bucket
        r = await client.post("/v1/completions", json={"prompt": "x" * 500})
        assert r.status == 400
    with_client(body)


def test_metrics_endpoint_counts():
    async def body(client):
        await client.post("/v1/completions", json={
            "prompt": "abc", "max_tokens": 3, "temperature": 0})
        # the response completes on event delivery; the engine loop's
        # metrics accounting for that step may land a moment later
        # (Prometheus scrapes are periodic — freshness is best-effort)
        for _ in range(50):
            r = await client.get("/metrics")
            text = await r.text()
            if "llm_tokens_generated_total 3.0" in text:
                break
            await asyncio.sleep(0.02)
        assert "llm_requests_total 1.0" in text
        assert "llm_tokens_generated_total 3.0" in text
        # TTFT and e2e histograms carry a per-model label now
        assert 'llm_ttft_seconds_count{model="debug-tiny"} 1' in text
        assert 'llm_e2e_latency_seconds_count{model="debug-tiny"} 1' in text
    with_client(body)


def test_incremental_detokenizer_holds_partial_utf8():
    tok = ByteTokenizer()
    d = IncrementalDetokenizer(tok)
    snowman = "☃".encode()  # 3 bytes
    assert d.push([snowman[0]]) == ""
    assert d.push([snowman[1]]) == ""
    assert d.push([snowman[2]]) == "☃"
    assert d.push(list("ok".encode()), final=True) == "ok"


def test_stop_sequence_truncates_and_aborts():
    """OpenAI `stop` strings end generation server-side (review finding:
    previously silently ignored)."""
    async def body(client):
        # greedy output of debug-tiny from "abc" is deterministic; find it
        r = await client.post("/v1/completions", json={
            "prompt": "abc", "temperature": 0.0, "max_tokens": 12,
        })
        base = (await r.json())["choices"][0]["text"]
        assert len(base) > 2
        stop = base[1:3]  # a substring the model definitely emits
        r = await client.post("/v1/completions", json={
            "prompt": "abc", "temperature": 0.0, "max_tokens": 12,
            "stop": [stop],
        })
        out = (await r.json())["choices"][0]
        assert out["finish_reason"] == "stop"
        assert stop not in out["text"]
        assert out["text"] == base[:base.find(stop)]
    with_client(body)


def test_stop_sequence_streaming():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "prompt": "abc", "temperature": 0.0, "max_tokens": 12,
        })
        base = (await r.json())["choices"][0]["text"]
        stop = base[1:3]
        r = await client.post("/v1/completions", json={
            "prompt": "abc", "temperature": 0.0, "max_tokens": 12,
            "stop": stop, "stream": True,
        })
        text, reasons = "", []
        async for line in r.content:
            line = line.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            c = json.loads(line[6:])["choices"][0]
            text += c.get("text", "")
            if c["finish_reason"]:
                reasons.append(c["finish_reason"])
        assert reasons == ["stop"]
        assert stop not in text
        assert text == base[:base.find(stop)]
    with_client(body)


def test_stop_checker_earliest_match_wins():
    """With stop=["b","a"] and text "a...b", output truncates at "a" — the
    EARLIEST occurrence in the text, not the first stop in list order
    (OpenAI semantics; round-2 review finding)."""
    from llms_on_kubernetes_tpu.server.openai_api import StopChecker

    sc = StopChecker(["b", "a"])
    out, hit = sc.push("xya__b", final=True)
    assert hit and out == "xy"

    # same rule when the earlier-in-text stop arrives in an earlier delta
    sc = StopChecker(["bb", "aa"])
    out1, hit1 = sc.push("zzaa")
    assert hit1 and out1 == "zz"

    # and when both land in ONE delta with overlapping holdback windows
    sc = StopChecker(["cd", "ab"])
    out, hit = sc.push("__abcd")
    assert hit and out == "__"

    # cross-delta: a short stop completing first must NOT preempt a
    # longer stop that started earlier and completes in the next delta
    sc = StopChecker(["abc", "b"])
    out1, hit1 = sc.push("ab")
    assert not hit1 and out1 == ""          # deferred, nothing emitted
    out2, hit2 = sc.push("c")
    assert hit2 and out1 + out2 == ""       # truncated at "abc" (idx 0)

    # ...but when the longer candidate fails to complete, the short stop
    # fires at its own (earliest actual) index
    sc = StopChecker(["abc", "b"])
    sc.push("ab")
    out, hit = sc.push("x")
    assert hit and out == "a"               # truncated at "b" (idx 1)

    # ...and at final, a pending prefix can no longer complete: the
    # completed match wins
    sc = StopChecker(["abc", "b"])
    sc.push("ab")
    out, hit = sc.push("", final=True)
    assert hit and out == "a"


def test_completions_list_of_prompts():
    """A list of string prompts yields one indexed choice per prompt
    (review finding: previously dropped all but the first)."""
    async def body(client):
        r = await client.post("/v1/completions", json={
            "prompt": ["ab", "xy"], "temperature": 0.0, "max_tokens": 4,
        })
        data = await r.json()
        assert [c["index"] for c in data["choices"]] == [0, 1]
        assert all(isinstance(c["text"], str) for c in data["choices"])
        # each choice must match the same prompt served alone
        for prompt, choice in zip(["ab", "xy"], data["choices"]):
            r1 = await client.post("/v1/completions", json={
                "prompt": prompt, "temperature": 0.0, "max_tokens": 4,
            })
            solo = (await r1.json())["choices"][0]["text"]
            assert choice["text"] == solo
        assert data["usage"]["prompt_tokens"] == 4
    with_client(body)


def test_completions_token_id_prompt():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "prompt": [97, 98, 99], "temperature": 0.0, "max_tokens": 4,
        })
        data = await r.json()
        assert r.status == 200
        assert len(data["choices"]) == 1
        r2 = await client.post("/v1/completions", json={
            "prompt": "abc", "temperature": 0.0, "max_tokens": 4,
        })
        assert data["choices"][0]["text"] == (await r2.json())["choices"][0]["text"]
    with_client(body)


def test_multi_prompt_streaming_interleaves_indices():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "prompt": ["ab", "xy"], "temperature": 0.0, "max_tokens": 4,
            "stream": True,
        })
        per_index = {0: "", 1: ""}
        finishes = set()
        async for line in r.content:
            line = line.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            c = json.loads(line[6:])["choices"][0]
            per_index[c["index"]] += c.get("text", "")
            if c["finish_reason"]:
                finishes.add(c["index"])
        assert finishes == {0, 1}
        assert all(per_index.values())
    with_client(body)


def test_chat_n_choices():
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 6, "temperature": 0.9, "n": 3, "seed": 5,
        })
        assert r.status == 200
        data = await r.json()
        assert [c["index"] for c in data["choices"]] == [0, 1, 2]
        texts = [c["message"]["content"] for c in data["choices"]]
        # per-choice derived seeds: deterministic but not identical
        assert len(set(texts)) > 1

        # greedy n>1: all choices identical (same argmax stream)
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 6, "temperature": 0, "n": 2,
        })
        data = await r.json()
        t = [c["message"]["content"] for c in data["choices"]]
        assert t[0] == t[1]

        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 6, "n": 99,
        })
        assert r.status == 400
    with_client(body)


def test_completions_n_choices_and_usage():
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "abc",
            "max_tokens": 4, "temperature": 0.7, "n": 2,
        })
        data = await r.json()
        assert len(data["choices"]) == 2
        assert [c["index"] for c in data["choices"]] == [0, 1]
        # unique prompt counted ONCE in usage even with n=2
        assert data["usage"]["prompt_tokens"] == 3
        assert data["usage"]["completion_tokens"] <= 8
    with_client(body)


def test_request_id_echo_and_trace_spans():
    """PR4 acceptance path: every response carries X-LLMK-Request-Id
    (minted when absent, forwarded verbatim when present) and
    /debug/traces?id= returns the per-phase spans whose durations are
    non-negative and sum to no more than the measured e2e latency."""
    import time

    async def body(client):
        # minted id
        r = await client.post("/v1/completions", json={
            "prompt": "abc", "max_tokens": 3, "temperature": 0})
        assert r.status == 200
        minted = r.headers.get("X-LLMK-Request-Id")
        assert minted and len(minted) == 32

        # forwarded verbatim + traced
        t0 = time.monotonic()
        r = await client.post(
            "/v1/completions",
            json={"prompt": "abc", "max_tokens": 4, "temperature": 0},
            headers={"X-LLMK-Request-Id": "trace-me-7"})
        assert r.status == 200
        wall_ms = (time.monotonic() - t0) * 1000.0
        assert r.headers["X-LLMK-Request-Id"] == "trace-me-7"

        r = await client.get("/debug/traces", params={"id": "trace-me-7"})
        traces = (await r.json())["traces"]
        assert len(traces) == 1
        tr = traces[0]
        assert tr["id"] == "trace-me-7"
        assert tr["model"] == "debug-tiny"
        assert tr["status"] == "ok"
        spans = {s["name"]: s for s in tr["spans"]}
        for phase in ("queue", "prefill", "decode"):
            assert phase in spans, f"missing {phase} span: {sorted(spans)}"
        assert all(s["duration_ms"] >= 0.0 for s in tr["spans"]
                   if s["duration_ms"] is not None)
        # the phases (children of the fragment's root) are disjoint, so
        # their total can never exceed the client-observed wall time; the
        # first token's four parts are children of the prefill phase
        durations = [s["duration_ms"] for s in tr["spans"]
                     if s["duration_ms"] is not None
                     and not s["name"].startswith("prefill.")]
        assert sum(durations) <= wall_ms
        parts = [s for s in tr["spans"] if s["name"].startswith("prefill.")]
        assert len(parts) == 4 and all(
            s["parent_span_id"] == spans["prefill"]["span_id"] for s in parts)
        assert sum(s["duration_ms"] for s in parts) == pytest.approx(
            spans["prefill"]["duration_ms"], abs=0.1)
        assert 0.0 <= tr["e2e_ms"] <= wall_ms

        # error responses carry an id too
        r = await client.post("/v1/chat/completions", data=b"{not json")
        assert r.status == 400
        assert r.headers.get("X-LLMK-Request-Id")
    with_client(body)


def test_metrics_runtime_telemetry_series():
    """ISSUE 5 acceptance: /metrics carries the device-memory and
    compile-cache series (CPU fallback: live-buffer bytes per device) plus
    build info; the device's seconds are the dispatch records' (the
    counters of seconds blocked on reads that stood in for them are gone)."""
    async def body(client):
        await client.post("/v1/completions", json={
            "prompt": "abc", "max_tokens": 3, "temperature": 0})
        r = await client.get("/metrics")
        text = await r.text()
        assert "llm_build_info{" in text and 'jax="' in text
        assert "llm_process_uptime_seconds" in text
        assert "llm_device_memory_bytes{" in text
        assert "llm_device_live_buffer_bytes{" in text
        assert "llm_jit_compiles_total" in text
        assert "llm_jit_cache_hits_total" in text
        assert 'llm_dispatch_device_seconds_total{kind="decode"}' in text
        assert 'llm_device_idle_seconds_total{host="no_work"}' in text
        assert "llm_kv_pages_live" in text
        assert "llm_step_device_seconds_total" not in text
        assert "llm_step_host_seconds_total" not in text
    with_client(body)


def test_debug_engine_reports_device_host_split():
    """Flight frames attribute each step's wall time to device wait vs
    host work; the two parts can never exceed the step itself."""
    async def body(client):
        await client.post("/v1/completions", json={
            "prompt": "abc", "max_tokens": 3, "temperature": 0})
        r = await client.get("/debug/engine")
        snap = await r.json()
        assert snap["steps"], "no flight frames recorded"
        for step in snap["steps"]:
            assert step["device_ms"] >= 0.0
            assert step["host_ms"] >= 0.0
            total = step["device_ms"] + step["host_ms"]
            assert total <= step["step_ms"] + 1.0  # rounding slack
    with_client(body)


def test_debug_profile_capture_list_download(tmp_path, monkeypatch):
    """ISSUE 5 acceptance (CPU e2e): POST /debug/profile answers a capture
    id, GET lists a non-empty capture, GET /debug/profile/<id> downloads a
    tar.gz of it; malformed ids and durations are rejected.

    No longer slow: the ~50 s this took were the python tracer's stop,
    and a capture is taken without it now (4 s here)."""
    import io
    import tarfile

    monkeypatch.setenv("LLMK_PROFILE_DIR", str(tmp_path))

    async def body(client):
        r = await client.post("/debug/profile", json={"duration_ms": 120})
        assert r.status == 200, await r.text()
        meta = await r.json()
        assert meta["id"].startswith("cap-")
        assert meta["source"] in ("jax-profiler", "py-sampler")
        assert meta["files"], "capture produced no files"

        r = await client.get("/debug/profile")
        listing = await r.json()
        assert listing["busy"] is False
        mine = [c for c in listing["captures"] if c["id"] == meta["id"]]
        assert mine and mine[0]["files"]

        r = await client.get(f"/debug/profile/{meta['id']}")
        assert r.status == 200
        assert r.headers["Content-Type"] == "application/gzip"
        data = await r.read()
        with tarfile.open(fileobj=io.BytesIO(data), mode="r:gz") as tar:
            names = tar.getnames()
        assert any(n.endswith("capture.json") for n in names)

        # unknown/malformed ids: 404, never a path traversal
        r = await client.get("/debug/profile/cap-999-999")
        assert r.status == 404
        r = await client.get("/debug/profile/%2e%2e%2fetc")
        assert r.status == 404

        # non-positive duration: 400
        r = await client.post("/debug/profile", json={"duration_ms": -5})
        assert r.status == 400
    with_client(body)


def _drive(eng, reqs, preempt_after=None):
    """Step ``eng`` until ``reqs`` finish; with ``preempt_after`` = n,
    preempt the youngest once it has n tokens (it re-prefills and goes on)."""
    for _ in range(5000):
        if all(r.finished for r in reqs):
            break
        eng.step()
        if preempt_after is not None and len(reqs[-1].output) >= preempt_after:
            eng._drain_async()
            if not reqs[-1].finished:
                eng._preempt_youngest()
            preempt_after = None
    eng._drain_async()
    assert all(r.finished for r in reqs)


@pytest.mark.parametrize("case", ["bucketed", "chunked", "resumed"])
def test_first_token_span_splits_into_four_children(case):
    """The ``prefill`` span (admission to first token) has four disjoint
    children, from the timestamps the request took off its prefill's
    dispatch record, that sum to it: on the bucketed path, on the chunk
    path (one record for the chain) and for a request that was preempted
    and re-prefilled after its first token (whose admission, launch and
    read stay those of the prefill that produced the token)."""
    from llms_on_kubernetes_tpu.engine.engine import SamplingParams
    from llms_on_kubernetes_tpu.server import tracing

    srv = make_server()
    eng = srv.engine
    n = {"bucketed": 20, "chunked": 100, "resumed": 20}[case]
    trace = tracing.Trace("split-" + case, model="debug-tiny")
    busy = eng.submit(list(range(1, 9)),
                      SamplingParams(temperature=0.0, max_tokens=48))
    for _ in range(6):
        eng.step()              # a decode dispatch is in flight ahead of it
    req = eng.submit([50 + i for i in range(n)],     # no prefix in common
                     SamplingParams(temperature=0.0, max_tokens=24))
    _drive(eng, [busy, req], preempt_after=8 if case == "resumed" else None)
    if case == "resumed":
        assert eng.preemptions == 1 and len(req.output) == 24
    assert req.admitted_at < req.first_token_at
    assert (req.admitted_at <= req.prefill_launched_at
            <= req.prefill_started_at)
    assert req.prefill_read_at <= req.first_token_at
    trace.engine_reqs = [req]
    srv._finalize_trace(trace, "ok", None)
    spans = trace.to_dict()["spans"]
    by_name = {s["name"]: s for s in spans}
    parent = by_name["prefill"]
    kids = [by_name[k] for k in ("prefill.pack", "prefill.behind",
                                 "prefill.device", "prefill.emit")]
    assert all(k["parent_span_id"] == parent["span_id"] for k in kids)
    assert all(k["duration_ms"] >= 0.0 for k in kids)
    assert sum(k["duration_ms"] for k in kids) == pytest.approx(
        parent["duration_ms"], abs=0.1)
    assert parent["duration_ms"] == pytest.approx(
        (req.first_token_at - req.admitted_at) * 1000.0, abs=0.01)
    # disjoint and in order: each starts where the one before it ended
    for a, b in zip(kids, kids[1:]):
        assert b["start_ms"] == pytest.approx(
            a["start_ms"] + a["duration_ms"], abs=0.01)
    # ``admitted_at``, where ``queue`` ends and ``prefill`` starts, is
    # written ONCE, where the request takes its slot (before the host KV
    # commit and the packing), on every prefill path: a chunked request
    # has a ``prefill`` span like any other, and a resumed one keeps its
    # first admission (the re-prefill after the preemption moves neither)
    queue = by_name["queue"]
    assert queue["duration_ms"] == pytest.approx(
        (req.admitted_at - req.submitted_at) * 1000.0, abs=0.01)
    assert parent["start_ms"] == pytest.approx(
        queue["start_ms"] + queue["duration_ms"], abs=0.01)
    assert kids[0]["start_ms"] == pytest.approx(parent["start_ms"], abs=0.01)
    assert [s["name"] for s in spans].count("prefill") == 1
    assert [s["name"] for s in spans].count("queue") == 1
    kinds = {d["kind"] for d in eng.ledger.dispatches_view(2048)}
    # (a resumed request re-prefills through its own cached prefix)
    assert ("chunk" in kinds) == (case != "bucketed")


@pytest.mark.parametrize("series,label,values,moved", [
    ("llm_first_tokens_total", "delivered", ("backpressure", "step"), 1.0),
    # the 12 tokens of one request: a window behind its prefill, and more
    ("llm_decode_launches_total", "when",
     ("timed", "late", "admission", "depth"), None),
])
def test_labelled_counter_has_every_label_from_the_start(series, label,
                                                         values, moved):
    """``llm_first_tokens_total{delivered}`` and
    ``llm_decode_launches_total{when}`` are on a fresh exposition with
    every label at 0 (lint-clean, in the constructor-derived inventory);
    a request's first token moves exactly one label of the first, and its
    decode windows the second: one ``admission`` launch, then the rest."""
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "scripts"))
    import metrics_lint

    assert series in metrics_lint.known_emitted_names()

    def counts(text):
        return {v: float(line.rsplit(" ", 1)[1])
                for line in text.splitlines()
                for v in values
                if line.startswith('%s{%s="%s"}' % (series, label, v))}

    async def body(client):
        text = await (await client.get("/metrics")).text()
        assert metrics_lint.lint(text, "fresh") == []
        assert counts(text) == dict.fromkeys(values, 0.0)
        await client.post("/v1/completions", json={
            "prompt": "abc", "max_tokens": 12, "temperature": 0})
        for _ in range(50):
            text = await (await client.get("/metrics")).text()
            if sum(counts(text).values()) >= (moved or 2.0):
                break
            await asyncio.sleep(0.02)
        if moved is not None:
            assert sum(counts(text).values()) == moved
        else:
            assert counts(text)["admission"] == 1.0
            assert sum(counts(text).values()) >= 2.0
        assert metrics_lint.lint(text, "after") == []
    with_client(body)


def test_decode_windows_by_sampler_on_metrics_and_debug_engine():
    """``llm_decode_windows_total{sampler}`` starts with both labels at 0;
    requests that ask for nothing move ``plain`` alone, one with a
    ``logit_bias`` moves ``shaped``; a decode window's record in
    ``GET /debug/engine`` says which it ran."""
    def counts(text):
        return {v: float(line.rsplit(" ", 1)[1])
                for line in text.splitlines() for v in ("plain", "shaped")
                if line.startswith(
                    'llm_decode_windows_total{sampler="%s"}' % v)}

    async def settled(client, done):
        for _ in range(100):
            got = counts(await (await client.get("/metrics")).text())
            if done(got):
                break
            await asyncio.sleep(0.02)
        return got

    async def body(client):
        got = counts(await (await client.get("/metrics")).text())
        assert got == {"plain": 0.0, "shaped": 0.0}
        await client.post("/v1/completions", json={
            "prompt": "abc", "max_tokens": 12, "temperature": 0})
        got = await settled(client, lambda c: c["plain"] >= 2.0)
        assert got["plain"] >= 2.0 and got["shaped"] == 0.0
        plain = got["plain"]
        await client.post("/v1/completions", json={
            "prompt": "abc", "max_tokens": 12, "temperature": 0,
            "logit_bias": {"101": 4.0}})
        got = await settled(client, lambda c: c["shaped"] >= 2.0)
        assert got["shaped"] >= 2.0 and got["plain"] == plain
        recs = (await (await client.get("/debug/engine")).json())[
            "dispatches"]
        said = [d.get("sampler") for d in recs if d["kind"] == "decode"]
        assert said.count("shaped") == got["shaped"]
        assert said.count("plain") == got["plain"]
        assert not any("sampler" in d for d in recs if d["kind"] != "decode")
    with_client(body)


def test_debug_engine_lists_dispatch_records():
    """GET /debug/engine carries, beside its frames, the ledger's newest
    dispatch records; ``?limit`` trims both."""
    async def body(client):
        await client.post("/v1/completions", json={
            "prompt": "abc", "max_tokens": 12, "temperature": 0})
        snap = await (await client.get("/debug/engine")).json()
        recs = snap["dispatches"]
        assert recs and [d["seq"] for d in recs] == sorted(
            d["seq"] for d in recs)
        assert recs[0]["kind"] == "prefill" and recs[0]["shape"] == "1x32"
        assert recs[0]["name"] == "_prefill_packed_step"
        assert any(d["kind"] == "decode" for d in recs)
        for d in recs:
            assert {"seq", "kind", "name", "shape", "tokens", "launch_ms",
                    "enqueue_ms", "retraced", "behind_ms", "device_ms",
                    "idle_before_ms"} <= set(d)
            assert (d["idle_before_ms"] > 0) == ("idle_host" in d)
        one = await (await client.get("/debug/engine?limit=1")).json()
        assert len(one["dispatches"]) == 1 and len(one["steps"]) == 1
        assert one["dispatches"][0]["seq"] == recs[-1]["seq"]
        # what times the next decode step: the lead, the device time each
        # shape last took, the launches by rule
        launch = snap["launch"]
        assert launch["lead_ms"] >= 4.0
        assert {"prefill 1x32", "decode 4x1"} <= set(launch["estimates_ms"])
        assert all(ms > 0 for ms in launch["estimates_ms"].values())
        assert set(launch["launches"]) == {"timed", "late", "admission",
                                           "depth"}
        assert launch["launches"]["admission"] == 1
    with_client(body)


def test_profile_capture_holds_the_engine_phases(tmp_path, monkeypatch):
    """One capture through POST /debug/profile, taken with the python
    tracer off, carries the engine thread's phases on a host plane:
    ``llmk.dispatch`` with its kind and seq, and ``llmk.harvest``."""
    import glob

    import jax

    monkeypatch.setenv("LLMK_PROFILE_DIR", str(tmp_path))

    async def body(client):
        gen = {"prompt": "abcdefgh", "max_tokens": 32, "temperature": 0}
        for _ in range(2):      # compile first: the prompt's bucket, then
            await client.post("/v1/completions", json=gen)  # its cached prefix
        cap = asyncio.ensure_future(
            client.post("/debug/profile", json={"duration_ms": 600}))
        while not cap.done():       # traffic for as long as it captures
            await client.post("/v1/completions", json=gen)
        r = await cap
        assert r.status == 200, await r.text()
        assert (await r.json())["source"] == "jax-profiler"
    with_client(body)
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert files, "the capture wrote no xplane"
    data = jax.profiler.ProfileData.from_file(files[0])
    seen: dict = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("llmk."):
                    seen.setdefault(ev.name.split("#")[0], []).append(
                        (plane.name, dict(ev.stats)))
    assert "llmk.dispatch" in seen and "llmk.harvest" in seen, sorted(seen)
    assert all(p.startswith("/host:") for evs in seen.values()
               for p, _ in evs)
    stats = [st for _, st in seen["llmk.dispatch"]]
    assert {"prefill", "decode"} & {st.get("kind") for st in stats}
    assert all(isinstance(st.get("seq"), int) or str(st.get("seq")).isdigit()
               for st in stats)


def test_debug_engine_flight_recorder():
    async def body(client):
        await client.post("/v1/completions", json={
            "prompt": "abc", "max_tokens": 3, "temperature": 0})
        r = await client.get("/debug/engine")
        assert r.status == 200
        snap = await r.json()
        assert snap["model"] == "debug-tiny"
        assert snap["state"] in ("loading", "serving", "draining")
        assert snap["steps_recorded"] >= 1
        assert len(snap["steps"]) >= 1
        step = snap["steps"][-1]
        assert step["step"] == snap["steps_recorded"]
        # limit trims the window
        r = await client.get("/debug/engine", params={"limit": 1})
        assert len((await r.json())["steps"]) == 1
    with_client(body)


# ---------------------------------------------------------------------------
# multi-tenant LoRA surface (model=base:adapter)
# ---------------------------------------------------------------------------

def make_adapter_server(tmp_path):
    from test_adapters import write_peft

    adapters = {f"ad{i}": str(write_peft(tmp_path / f"ad{i}", rank=2,
                                         alpha=16, seed=40 + i))
                for i in range(2)}
    eng = Engine(EngineConfig(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=4, num_pages=256, pages_per_slot=32,
        prefill_buckets=(32, 64),
        adapters=adapters, adapter_slots=2, adapter_rank=4,
    ))
    return OpenAIServer(eng, ByteTokenizer(), "debug-tiny")


def test_adapter_requests_resolve_404_and_label(tmp_path):
    async def go():
        server = make_adapter_server(tmp_path)
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            # /v1/models lists base + base:adapter ids
            r = await client.get("/v1/models")
            ids = [m["id"] for m in (await r.json())["data"]]
            assert ids == ["debug-tiny", "debug-tiny:ad0", "debug-tiny:ad1"]

            # base:adapter request serves and echoes the full model id
            r = await client.post("/v1/completions", json={
                "model": "debug-tiny:ad0", "prompt": "abc",
                "max_tokens": 4, "temperature": 0})
            assert r.status == 200
            doc = await r.json()
            assert doc["model"] == "debug-tiny:ad0"

            # the adapter's output differs from the base model's
            r = await client.post("/v1/completions", json={
                "model": "debug-tiny", "prompt": "abc",
                "max_tokens": 4, "temperature": 0})
            base_doc = await r.json()
            assert base_doc["model"] == "debug-tiny"
            assert doc["choices"][0]["text"] != base_doc["choices"][0]["text"]

            # unknown adapter: structured 404, not a base-model fallback
            r = await client.post("/v1/completions", json={
                "model": "debug-tiny:nope", "prompt": "abc",
                "max_tokens": 4})
            assert r.status == 404
            err = await r.json()
            assert err["error"]["code"] == "adapter_not_found"
            assert err["error"]["type"] == "invalid_request_error"

            # metrics: adapter-labelled latency series + cache counters
            r = await client.get("/metrics")
            text = await r.text()
            assert 'model="debug-tiny:ad0"' in text
            assert "llm_adapter_cache_misses_total 1.0" in text
            assert "llm_adapter_load_seconds_count 1" in text
        finally:
            await client.close()
    asyncio.run(go())


def test_adapter_streaming_echoes_model_id(tmp_path):
    async def go():
        server = make_adapter_server(tmp_path)
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            r = await client.post("/v1/completions", json={
                "model": "debug-tiny:ad1", "prompt": "abc",
                "max_tokens": 4, "temperature": 0, "stream": True})
            assert r.status == 200
            payloads = []
            async for line in r.content:
                line = line.decode().strip()
                if line.startswith("data:") and line != "data: [DONE]":
                    payloads.append(json.loads(line[5:]))
            assert payloads and all(
                p["model"] == "debug-tiny:ad1" for p in payloads)
        finally:
            await client.close()
    asyncio.run(go())
