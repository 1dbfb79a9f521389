"""Where a request's token gap goes (PR 40): the ``decode`` span ends at
the hand-over of its last token, carries its parts as attributes
(``ride_ms``, ``prefill_ms``, ``other_ms``, ``idle_ms``) and has one
child, ``decode.emit``; ``llm_decode_emit_seconds_total`` is the same lag
summed over every window; MFU/MBU are computed when ``/metrics`` is read.

- ledger level: the account on made-up records, to the microsecond;
- engine level: on a simulated device and clock (``test_engine_async``'s)
  and on the real one through every path the scheduler has;
- server level: ``/debug/traces`` and ``/metrics`` of a CPU server, and
  the benchmark's reader over those traces.
"""

import asyncio
import itertools
import os
import re
import sys
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from llms_on_kubernetes_tpu.configs import get_config
from llms_on_kubernetes_tpu.engine.engine import (
    Engine, EngineConfig, SamplingParams,
)
from llms_on_kubernetes_tpu.engine.ledger import MAX_OPEN, GoodputLedger
from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
from llms_on_kubernetes_tpu.server import tracing
from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer
from test_engine_async import _GREEDY, _SEEDED, _sim

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = ("ride", "prefill", "other")


# ---------------------------------------------------------------------------
# ledger level: made-up records
# ---------------------------------------------------------------------------

class _Req:
    """What the ledger touches of an engine Request."""

    def __init__(self, launched=0.0):
        self.tenant, self.chip_ms, self.ride_seqs = "", {}, []
        self.prefill_launched_at = launched


_seqs = itertools.count()


def _ledger():
    return GoodputLedger(get_config("debug-tiny"), peak_flops=1e12,
                         peak_bytes_s=1e11)


def _book(led, kind, t_launch, t_done, rows):
    """One dispatch, read as soon as it is launched; its seq."""
    seq = next(_seqs)
    rec = led.open(seq, kind, f"_{kind}_step", "1x1", t_launch)
    led.launched(rec, t_launch)
    led.close(seq, t_done, rows, window=4)
    return seq


def _story(led, me, other):
    """A request's life on the device, in seconds: its prefill, a window
    it rides (already running when its first token is handed over at
    0.035), another request's prefill, a window it does NOT ride, an idle
    gap, a window, a chunked prefill's chain, a speculative window of
    which it keeps half. Its last token leaves at 0.37."""
    _book(led, "prefill", 0.00, 0.03, [(me, "prefill", 8)])
    _book(led, "decode", 0.03, 0.09, [(me, "decode", 4)])
    _book(led, "prefill", 0.04, 0.12, [(other, "prefill", 8)])
    _book(led, "decode", 0.05, 0.18, [(other, "decode", 4)])
    _book(led, "decode", 0.20, 0.26, [(me, "decode", 1), (me, "early_exit", 3),
                                      (other, "decode", 4)])
    _book(led, "chunk", 0.21, 0.30, [(other, "prefill", 64)])
    _book(led, "spec", 0.22, 0.36, [(me, "decode", 2), (me, "spec_waste", 2)])
    return 0.035, 0.37


def test_the_account_on_made_up_records():
    led = _ledger()
    me, other = _Req(), _Req()
    t0, t1 = _story(led, me, other)
    acct = led.decode_account(me, t0, t1)
    assert acct["done"] == pytest.approx(0.36)
    assert acct["ride"] == pytest.approx(0.055 + 0.06 + 0.06)
    assert acct["prefill"] == pytest.approx(0.03 + 0.04)
    assert acct["other"] == pytest.approx(0.06 + 0.02)
    assert acct["idle"] == pytest.approx(0.02)
    assert sum(acct[p] for p in PARTS) + (t1 - acct["done"]) == (
        pytest.approx(t1 - t0))
    assert len(me.ride_seqs) == 3 and len(other.ride_seqs) == 2


def test_a_ride_counts_the_whole_dispatch_once_whatever_its_rows():
    """Two requests of one window, one with four tokens of it and one
    with a single token beside a wasted tail: both waited for ALL of it
    (``chip_ms`` stays the share by rows)."""
    led = _ledger()
    a, b = _Req(), _Req()
    _book(led, "prefill", 0.0, 0.01, [(a, "prefill", 4), (b, "prefill", 4)])
    seq = _book(led, "decode", 0.01, 0.09,
                [(a, "decode", 4), (b, "decode", 1), (b, "early_exit", 3)])
    assert a.ride_seqs == b.ride_seqs == [seq]
    for req in (a, b):
        acct = led.decode_account(req, 0.01, 0.1)
        assert acct["ride"] == pytest.approx(0.08)
        assert acct["prefill"] == acct["other"] == 0.0
    assert a.chip_ms["decode"] == pytest.approx(40.0)
    assert b.chip_ms["decode"] == pytest.approx(10.0)


@pytest.mark.parametrize("case", ["past_the_ring", "reset", "lost", "wedged",
                                  "no_prefill_record"])
def test_no_account_where_the_records_no_longer_tell(case):
    led = _ledger()
    me, other = _Req(), _Req()
    t0, t1 = _story(led, me, other)
    assert led.decode_account(me, t0, t1) is not None
    if case == "past_the_ring":
        t = 0.4
        for _ in range(led._records.maxlen):
            _book(led, "decode", t, t + 0.001, [(other, "decode", 1)])
            t += 0.001
    elif case == "reset":
        led.reset()
    elif case == "lost":
        # a launch nobody ever reads, dropped with MAX_OPEN behind it
        rec = led.open(next(_seqs), "decode", "_decode_step", "1x1", 0.5)
        led.launched(rec, 0.5)
        for i in range(MAX_OPEN):
            led.open(next(_seqs), "decode", "_decode_step", "1x1",
                     0.5 + i * 1e-3)
        assert led.lost == 1
    elif case == "wedged":
        led.open(next(_seqs), "decode", "_decode_step", "1x1", 0.5)
        led.abandon()
    else:
        me.prefill_launched_at = None
    assert led.decode_account(me, t0, t1) is None
    # a request whose prefill was launched after the loss is told again
    if case in ("lost", "wedged"):
        led.abandon()
        late = _Req(launched=1.0)
        _book(led, "prefill", 1.0, 1.01, [(late, "prefill", 4)])
        _book(led, "decode", 1.01, 1.05, [(late, "decode", 4)])
        assert led.decode_account(late, 1.02, 1.06)["ride"] == (
            pytest.approx(0.03))


def test_a_launch_that_raised_loses_nothing():
    led = _ledger()
    me, other = _Req(), _Req()
    t0, t1 = _story(led, me, other)
    seq = next(_seqs)
    led.open(seq, "decode", "_decode_step", "1x1", 0.5)
    led.abandon(seq)
    assert led.decode_account(me, t0, t1) is not None


# ---------------------------------------------------------------------------
# engine level, simulated device and clock
# ---------------------------------------------------------------------------

def test_decode_emit_is_the_last_hand_over_less_the_windows_done_time():
    """One request of 40 tokens: ten windows. ``done`` is the completion
    the simulated device stamped on the last, ``last_token_at`` the
    clock's reading where that window's last event was put on the
    request's queue, and what lies between is ``decode.emit``."""
    eng, sim = _sim(tick=0.0002, estimates=_SEEDED)
    seen = []
    req = eng.submit([1, 2, 3], _GREEDY,
                     on_event=lambda ev: seen.append((sim.clock.t, ev[1])))
    sim.drive(lambda: req.finished)
    launches = sim.decodes()
    assert len(launches) == 10 == len(req.ride_seqs)
    done = sim.ends[launches[-1][0]]
    acct = eng.ledger.decode_account(req, req.first_token_at,
                                     req.last_token_at)
    assert acct["done"] == done
    # stamped where the finishing event leaves, before anyone can see it
    handed, finished = seen[-1]
    assert finished and done < req.last_token_at <= handed
    assert sum(acct[p] for p in PARTS) + (req.last_token_at - done) == (
        pytest.approx(req.last_token_at - req.first_token_at, abs=1e-9))
    # alone on the device: every window is its own, from its first token
    # on (what is not ridden is a gap the launch left)
    assert acct["prefill"] == 0.0 and acct["other"] == pytest.approx(
        acct["idle"], abs=1e-9)
    assert acct["ride"] + acct["other"] == pytest.approx(
        done - req.first_token_at)


def test_the_emit_total_grows_once_a_window():
    """``Engine.decode_emit_s`` (``llm_decode_emit_seconds_total``) moves
    in the ``step()`` that collected a window, by that window's wait from
    its completion to the ONE reading of the clock after the hand-over."""
    eng, sim = _sim(tick=0.0002, estimates=_SEEDED)
    req = eng.submit([1, 2, 3], _GREEDY)
    grew = []
    while not req.finished:
        before, n = eng.decode_emit_s["decode"], eng.decode_dispatches
        eng.step()
        got = eng.decode_emit_s["decode"] - before
        collected = eng.decode_dispatches - n
        assert (got > 0.0) == (collected > 0)
        if collected:
            ends = sorted(sim.ends[k] for k, _t in sim.decodes()
                          if sim.ends[k] <= sim.clock.t)[-collected:]
            assert got <= sum(sim.clock.t - e for e in ends) + 1e-9
            assert got >= collected * 0.0002
            grew.append(got)
        assert not eng._collected
    assert len(grew) == 10 == eng.ledger.snapshot()["kinds"]["decode"][
        "dispatches"]
    assert eng.decode_emit_s["spec"] == 0.0


# ---------------------------------------------------------------------------
# engine level, every path, through the trace the server publishes
# ---------------------------------------------------------------------------

def _server(**kw):
    base = dict(model="debug-tiny", dtype="float32", max_decode_slots=4,
                page_size=4, num_pages=256, pages_per_slot=32,
                prefill_buckets=(32, 64), async_scheduling=True,
                async_depth=2, decode_steps=4)
    base.update(kw)
    return OpenAIServer(Engine(EngineConfig(**base)), ByteTokenizer(),
                        "debug-tiny")


def _drive(eng, reqs, at=None, then=None):
    """Step ``eng`` until ``reqs`` finish; once the first has ``at``
    tokens, ``then()`` (which may return more requests to wait for)."""
    for _ in range(5000):
        if all(r.finished for r in reqs):
            break
        eng.step()
        if then is not None and len(reqs[0].output) >= at:
            reqs = reqs + (then() or [])
            then = None
    eng._drain_async()
    assert then is None and all(r.finished for r in reqs)
    return reqs


def _spans(srv, req):
    trace = tracing.Trace("acct", model="debug-tiny")
    trace.t0 = req.submitted_at     # as if it had been there from the start
    trace.engine_reqs = [req]
    srv._finalize_trace(trace, "ok", None)
    return trace.to_dict()["spans"]


_CASES = {
    # name: (engine options, what happens once the request has 8 tokens)
    "plain": (dict(async_scheduling=False, decode_steps=1), None),
    "k4": ({}, None),
    "prefill_between": ({}, "submit"),
    "chunked_prefill": ({}, "submit_long"),
    "preempted": ({}, "preempt"),
    "speculative": (dict(speculation="ngram"), None),
    "aborted": ({}, "abort"),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_the_parts_sum_to_the_decode_span(case):
    """``ride_ms + prefill_ms + other_ms + decode.emit`` is the ``decode``
    span, first token's hand-over to the last's, on every path: the sync
    engine at K = 1, fused K = 4 windows, another request's prefill (or a
    chunked one's chain) between two windows, a preemption (its own
    re-prefill is a prefill like any other), speculative windows, and an
    abort, whose span ends where the abort's event left. (A window the
    request did not ride: the made-up records above; the scheduler puts a
    stream into every window it launches behind its first token.)"""
    opts, what = _CASES[case]
    srv = _server(**opts)
    eng = srv.engine
    rep = [5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6]      # something to draft from
    older = []
    if what == "preempt":       # the youngest is preempted: not this one
        older = [eng.submit([9, 8, 7], SamplingParams(temperature=0.0,
                                                      max_tokens=60))]
        for _ in range(4):
            eng.step()
    req = eng.submit(rep, SamplingParams(temperature=0.0, max_tokens=40))
    then = {
        None: None,
        "submit": lambda: [eng.submit(
            [50 + i for i in range(20)],
            SamplingParams(temperature=0.0, max_tokens=8))],
        "submit_long": lambda: [eng.submit(
            [50 + i for i in range(100)],
            SamplingParams(temperature=0.0, max_tokens=8))],
        "preempt": lambda: (eng._drain_async(), req.finished
                            or eng._preempt_youngest()) and None,
        "abort": lambda: eng.abort(req),
    }[what]
    _drive(eng, [req] + older, at=8, then=then)
    spans = _spans(srv, req)
    dec, = [s for s in spans if s["name"] == "decode"]
    emit, = [s for s in spans if s["name"] == "decode.emit"]
    assert emit["parent_span_id"] == dec["span_id"]
    assert dec["duration_ms"] == pytest.approx(
        (req.last_token_at - req.first_token_at) * 1000.0, abs=0.01)
    assert req.finished_at <= req.last_token_at
    parts = [dec["ride_ms"], dec["prefill_ms"], dec["other_ms"],
             emit["duration_ms"]]
    assert all(p >= 0.0 for p in parts)
    assert sum(parts) == pytest.approx(dec["duration_ms"], abs=0.01)
    assert 0.0 <= dec["idle_ms"] <= dec["other_ms"]
    # the child closes the span
    assert emit["start_ms"] + emit["duration_ms"] == pytest.approx(
        dec["start_ms"] + dec["duration_ms"], abs=0.01)
    assert dec["tokens"] == len(req.output)
    kinds = {d["kind"] for d in eng.ledger.dispatches_view(2048)}
    if case == "aborted":
        assert req.finish_reason == "abort" and len(req.output) < 40
    else:
        assert len(req.output) == 40 and dec["ride_ms"] > 0.0
    if case in ("prefill_between", "chunked_prefill", "preempted"):
        assert dec["prefill_ms"] > 0.0
        # (a resumed request re-prefills through its own cached prefix)
        assert ("chunk" in kinds) == (case != "prefill_between")
    if case in ("plain", "k4"):
        assert dec["prefill_ms"] == 0.0
    if case == "preempted":
        assert eng.preemptions == 1
    if case == "speculative":
        assert "spec" in kinds and eng.decode_emit_s["spec"] > 0.0
    # windows ridden, each once: a K = 4 engine needs at least 39 / 4
    assert len(req.ride_seqs) == len(set(req.ride_seqs))
    if case == "k4":
        assert len(req.ride_seqs) == 10
    if case == "plain":
        assert len(req.ride_seqs) == 39


def test_no_attributes_without_the_ledger():
    srv = _server(ledger=False)
    eng = srv.engine
    req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                               max_tokens=12))
    _drive(eng, [req])
    spans = _spans(srv, req)
    dec, = [s for s in spans if s["name"] == "decode"]
    assert not any(s["name"] == "decode.emit" for s in spans)
    assert not any(k.endswith("_ms") and k != "duration_ms" and k != "start_ms"
                   for k in dec)
    # the span ends at the last hand-over all the same
    assert dec["duration_ms"] == pytest.approx(
        (req.last_token_at - req.first_token_at) * 1000.0, abs=0.01)
    assert req.ride_seqs == []
    # and nothing is kept for a counter only the ledger's drain reads
    assert eng.decode_emit_s == {"decode": 0.0, "spec": 0.0}
    assert not eng._collected


# ---------------------------------------------------------------------------
# server level
# ---------------------------------------------------------------------------

def _with_client(srv, fn):
    async def go():
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            await fn(client)
        finally:
            await client.close()
    asyncio.run(go())


def _series(text, name):
    return {lab: float(v) for lab, v in re.findall(
        rf'^{name}{{\w+="(\w+)"}} (\S+)$', text, re.M)}


@pytest.mark.e2e
def test_a_served_request_is_tiled_and_the_reader_adds_its_parts_up():
    """Over HTTP: ``decode`` ends where ``stream`` starts (the last
    hand-over), the phases still tile the request, every finished trace
    of ``/debug/traces`` carries the parts, the benchmark's reader over
    those traces gives four parts that sum to its span, and the emit
    counter moved once a decode window."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    from harness import manifest
    from harness.cell import Context

    srv = _server()

    async def body(client):
        async def ask(i, n):
            r = await client.post(
                "/v1/completions",
                json={"prompt": "abcdef"[: 2 + i % 4] * (1 + i % 3),
                      "max_tokens": n, "temperature": 0},
                headers={"X-LLMK-Request-Id": f"acct-{i}"})
            assert r.status == 200
        w0 = time.monotonic()
        await ask(0, 8)                         # compiles
        await asyncio.gather(*[ask(i, 6 + 5 * (i % 4)) for i in range(1, 9)])
        traces = (await (await client.get(
            "/debug/traces", params={"limit": "64"})).json())["traces"]
        assert len(traces) == 9
        for t in traces:
            by = {s["name"]: s for s in t["spans"]}
            dec, emit, stream = by["decode"], by["decode.emit"], by["stream"]
            assert (dec["ride_ms"] + dec["prefill_ms"] + dec["other_ms"]
                    + emit["duration_ms"]) == pytest.approx(
                        dec["duration_ms"], abs=0.01)
            assert stream["start_ms"] == pytest.approx(
                dec["start_ms"] + dec["duration_ms"], abs=0.01)
            # (the trace is finished a log line after its spans are cut)
            assert t["e2e_ms"] - 50.0 <= (
                stream["start_ms"] + stream["duration_ms"]) <= t["e2e_ms"]
            top = [s for s in t["spans"] if "." not in s["name"]]
            assert sum(s["duration_ms"] for s in top) <= t["e2e_ms"] + 0.5
        # some stream had another's prefill between two of its windows
        assert any(s["prefill_ms"] > 0.0 for t in traces
                   for s in t["spans"] if s["name"] == "decode")

        ctx = Context(window=(w0, time.monotonic() + 1.0),
                      spans={t["id"]: t for t in traces})
        got = {n: manifest.read_metric("per_layer", f"tpot_tail_{n}_ms", ctx)
               for n in ("span", "ride", "prefill", "other", "emit")}
        assert all(v is not None and v >= 0.0 for v in got.values())
        assert sum(v for n, v in got.items() if n != "span") == (
            pytest.approx(got["span"], rel=0.01))

        text = await (await client.get("/metrics")).text()
        emit_s = _series(text, "llm_decode_emit_seconds_total")
        assert set(emit_s) == {"decode", "spec"} and emit_s["spec"] == 0.0
        assert emit_s["decode"] == pytest.approx(
            srv.engine.decode_emit_s["decode"], abs=1e-9) and (
                emit_s["decode"] > 0.0)
        assert _series(text, "llm_dispatches_total")["decode"] >= 9
        assert "llm_decode_step_seconds" not in text
    _with_client(srv, body)


@pytest.mark.e2e
def test_mfu_and_mbu_are_computed_at_the_scrape(monkeypatch):
    """``llm_mfu_ratio`` / ``llm_mbu_ratio`` read at a scrape what the
    loop used to set after every step (the ledger's trailing minute up to
    its newest record), and the engine loop never asks."""
    monkeypatch.setenv("LLMK_PEAK_TFLOPS", "0.001")
    monkeypatch.setenv("LLMK_PEAK_GBPS", "0.1")
    srv = _server()
    led = srv.engine.ledger
    assert led.peak_flops == 1e9
    calls = []
    real = led.utilization
    monkeypatch.setattr(led, "utilization",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))

    def gauges(text):
        return [float(re.search(rf"^{n} (\S+)$", text, re.M).group(1))
                for n in ("llm_mfu_ratio", "llm_mbu_ratio")]

    async def body(client):
        r = await client.post("/v1/completions", json={
            "prompt": "abc", "max_tokens": 16, "temperature": 0})
        assert r.status == 200
        for _ in range(100):            # the loop goes idle: nothing moves
            if not srv.engine.has_work():
                break
            await asyncio.sleep(0.02)
        assert calls == []
        snap = await (await client.get("/debug/engine")).json()
        assert not any("mfu" in s for s in snap["steps"])
        text = await (await client.get("/metrics")).text()
        assert len(calls) == 1
        want = real()
        assert gauges(text) == [pytest.approx(want[0]),
                                pytest.approx(want[1])]
        assert 0.0 < want[0] <= 1.0 and 0.0 < want[1] <= 1.0
    _with_client(srv, body)
