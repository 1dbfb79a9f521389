"""Deterministic fault-tolerance tests for the serving spine (ISSUE 1).

Every failure path runs CPU-only and deterministically: the ``LLMK_FAULT=``
hooks (llms_on_kubernetes_tpu/faults.py) wedge the engine's device reads
and the entry points' backend init, while raw-socket fake upstreams inject
connection resets and stalls for the Python router. Covered here:

- fault-spec parsing and the inject_* hook semantics;
- the CircuitBreaker state machine under an injected fake clock;
- Python router: retry-then-success, retry-exhausted 502, breaker
  open -> half-open -> close, stalled-upstream bounded failure;
- engine watchdog: a stalled device step is shed with reason "stalled"
  and the engine wedges (submit rejects, step no-ops);
- /health vs /ready lifecycle (loading/serving/draining/wedged) and the
  llm_engine_state gauge;
- dryrun_multichip on the devices it is given (subprocess:
  one parseable error JSON line / CPU path untouched by the hang).

The native router's equivalents live in tests/test_native_router.py and
tests/test_native_sanitizers.py.
"""

import asyncio
import os
import socket
import struct
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest
from aiohttp.test_utils import TestClient, TestServer

from llms_on_kubernetes_tpu import faults
from llms_on_kubernetes_tpu.server.router import CircuitBreaker, Router

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# fault-spec parsing + hooks
# ---------------------------------------------------------------------------

def test_fault_spec_parsing(monkeypatch):
    monkeypatch.setenv("LLMK_FAULT", "engine_stall; slow_step:0.5")
    assert faults.is_active("engine_stall")
    assert faults.get("engine_stall") == ""
    assert faults.get_float("slow_step", 0.2) == 0.5
    assert faults.get_float("engine_stall", 7.0) == 7.0  # bare -> default
    assert not faults.is_active("queue_stall")
    assert faults.get_float("queue_stall", 1.0) is None
    monkeypatch.delenv("LLMK_FAULT")
    assert not faults.is_active("engine_stall")  # read at call time


def test_inject_hooks_noop_when_inactive(monkeypatch):
    monkeypatch.delenv("LLMK_FAULT", raising=False)
    t0 = time.monotonic()
    faults.inject_hang("engine_stall")
    faults.inject_delay("slow_step", 5.0)
    assert time.monotonic() - t0 < 0.5


def test_inject_delay_sleeps_its_arg(monkeypatch):
    monkeypatch.setenv("LLMK_FAULT", "slow_step:0.05")
    t0 = time.monotonic()
    faults.inject_delay("slow_step", 5.0)
    assert 0.04 <= time.monotonic() - t0 < 1.0


def test_gray_failure_fault_specs(monkeypatch):
    """degraded_replica is a one-shot single-victim fault (claim), with a
    default slowdown factor of 8; net_jitter is unclaimed (every replica
    jitters) with a default of 25 ms."""
    faults.reset_claims()
    monkeypatch.setenv("LLMK_FAULT", "degraded_replica;net_jitter")
    assert faults.get_float("degraded_replica", 8.0) == 8.0
    assert faults.get_float("net_jitter", 25.0) == 25.0
    assert faults.claim("degraded_replica")        # first replica wins
    assert not faults.claim("degraded_replica")    # second stays healthy
    monkeypatch.setenv("LLMK_FAULT", "degraded_replica:4;net_jitter:5")
    assert faults.get_float("degraded_replica", 8.0) == 4.0
    assert faults.get_float("net_jitter", 25.0) == 5.0
    faults.reset_claims()


@pytest.mark.e2e
def test_degraded_replica_stays_probe_green(monkeypatch):
    """The gray-failure victim claims the slowdown at startup but keeps
    answering /health and /ready 200 and still serves requests — only
    its in-band latency degrades (the router's probes must NOT save it;
    that is the outlier detector's job)."""
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer

    faults.reset_claims()
    monkeypatch.setenv("LLMK_FAULT", "degraded_replica:3")
    srv = OpenAIServer(_mk_engine(), ByteTokenizer(), "debug-tiny")
    srv2 = OpenAIServer(_mk_engine(), ByteTokenizer(), "debug-tiny")

    async def go():
        client = TestClient(TestServer(srv.make_app()))
        client2 = TestClient(TestServer(srv2.make_app()))
        await client.start_server()
        await client2.start_server()
        try:
            # exactly one in-process replica degrades (single-victim)
            assert srv._degraded_factor == 3.0
            assert srv2._degraded_factor == 1.0
            assert (await client.get("/health")).status == 200
            r = await client.get("/ready")
            assert r.status == 200 and (await r.json())["state"] == "serving"
            r = await client.post("/v1/chat/completions", json={
                "model": "debug-tiny",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4,
            })
            assert r.status == 200  # slow, not broken
        finally:
            await client.close()
            await client2.close()
    asyncio.run(go())
    faults.reset_claims()


@pytest.mark.e2e
def test_net_jitter_delays_every_stream_but_serves(monkeypatch):
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer

    monkeypatch.setenv("LLMK_FAULT", "net_jitter:2")
    srv = OpenAIServer(_mk_engine(), ByteTokenizer(), "debug-tiny")

    async def go():
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            r = await client.post("/v1/completions", json={
                "model": "debug-tiny", "prompt": "hi", "max_tokens": 4,
                "stream": True,
            })
            assert r.status == 200
            body = await r.read()
            assert b"data: [DONE]" in body
        finally:
            await client.close()
    asyncio.run(go())


# ---------------------------------------------------------------------------
# circuit breaker state machine (fake clock: fully deterministic)
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def test_breaker_state_machine():
    clk = FakeClock()
    b = CircuitBreaker(threshold=3, open_s=10.0, clock=clk)
    assert b.allow() and b.state == b.CLOSED
    b.record_failure()
    b.record_failure()
    assert b.allow()                       # below threshold: still closed
    b.record_failure()
    assert b.state == b.OPEN and not b.allow()
    assert 0 < b.retry_after_s() <= 10.0
    clk.advance(9.9)
    assert not b.allow()                   # still inside the open window
    clk.advance(0.2)
    assert b.allow()                       # half-open: one probe admitted
    assert b.state == b.HALF_OPEN
    assert not b.allow()                   # ...and only one
    b.record_success()
    assert b.state == b.CLOSED and b.failures == 0 and b.allow()


def test_breaker_halfopen_failure_reopens_and_stuck_probe_frees():
    clk = FakeClock()
    b = CircuitBreaker(threshold=2, open_s=5.0, clock=clk)
    b.record_failure()
    b.record_failure()
    assert b.state == b.OPEN
    clk.advance(5.1)
    assert b.allow()                       # probe
    b.record_failure()                     # ONE failure re-opens half-open
    assert b.state == b.OPEN and not b.allow()
    clk.advance(5.1)
    assert b.allow()                       # probe admitted, never reported
    assert not b.allow()                   # slot held by the stuck probe
    clk.advance(5.1)
    assert b.allow()                       # stuck probe freed after open_s


# ---------------------------------------------------------------------------
# Python router vs dying/stalling fake upstreams
# ---------------------------------------------------------------------------

class FlakyUpstream(threading.Thread):
    """Raw-socket upstream: RSTs the first ``fail_first`` connections
    (SO_LINGER 0 close -> connection reset on the client, a retryable
    connect-phase failure) and answers a canned HTTP 200 JSON after."""

    def __init__(self, fail_first: int):
        super().__init__(daemon=True)
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(16)
        self.port = self.sock.getsockname()[1]
        self.fail_first = fail_first
        self.hits = 0
        self._stop = False

    def run(self):
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            self.hits += 1
            if self.hits <= self.fail_first:
                conn.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                struct.pack("ii", 1, 0))
                conn.close()               # RST, not FIN
                continue
            try:
                conn.settimeout(5)
                data = b""
                while b"\r\n\r\n" not in data:
                    chunk = conn.recv(65536)
                    if not chunk:
                        break
                    data += chunk
                body = b'{"served_by": "flaky"}'
                conn.sendall(
                    b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
                    b"Content-Length: " + str(len(body)).encode()
                    + b"\r\nConnection: close\r\n\r\n" + body)
            except OSError:
                pass
            finally:
                conn.close()

    def stop(self):
        self._stop = True
        try:
            self.sock.close()
        except OSError:
            pass


class StallingUpstream(threading.Thread):
    """Accepts and reads the request, then never answers — the router's
    read timeout (not the client's patience) must bound the request."""

    def __init__(self):
        super().__init__(daemon=True)
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(16)
        self.port = self.sock.getsockname()[1]
        self.hits = 0
        self._stop = threading.Event()
        self._conns: list = []

    def run(self):
        while not self._stop.is_set():
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            self.hits += 1
            self._conns.append(conn)       # hold open, never respond

    def stop(self):
        self._stop.set()
        for c in [self.sock] + self._conns:
            try:
                c.close()
            except OSError:
                pass


def _drive_router(router: Router, fn):
    async def go():
        client = TestClient(TestServer(router.make_app()))
        await client.start_server()
        try:
            await fn(client)
        finally:
            await client.close()
    asyncio.run(go())


def test_router_retry_then_success():
    up = FlakyUpstream(fail_first=2)
    up.start()
    router = Router({"m": f"http://127.0.0.1:{up.port}"},
                    retry_attempts=3, retry_backoff_s=0.01)

    async def body(client):
        r = await client.post("/v1/chat/completions", json={"model": "m"})
        assert r.status == 200, await r.text()
        assert (await r.json())["served_by"] == "flaky"

    try:
        _drive_router(router, body)
    finally:
        up.stop()
    assert up.hits == 3  # two resets + the successful retry


def test_router_retry_exhausted_502():
    up = FlakyUpstream(fail_first=10 ** 9)
    up.start()
    router = Router({"m": f"http://127.0.0.1:{up.port}"},
                    retry_attempts=3, retry_backoff_s=0.01,
                    breaker_threshold=10)

    async def body(client):
        r = await client.post("/v1/chat/completions", json={"model": "m"})
        assert r.status == 502
        err = await r.json()
        assert err["error"]["type"] == "bad_gateway"
        assert err["error"]["code"] == "upstream_error"

    try:
        _drive_router(router, body)
    finally:
        up.stop()
    assert up.hits == 3  # bounded: exactly retry_attempts connections


def test_router_breaker_open_halfopen_close():
    clk = FakeClock()
    up = FlakyUpstream(fail_first=2)
    up.start()
    router = Router({"m": f"http://127.0.0.1:{up.port}"},
                    retry_attempts=1, retry_backoff_s=0.0,
                    breaker_threshold=2, breaker_open_s=30.0, clock=clk)

    async def body(client):
        for _ in range(2):                 # trip the breaker
            r = await client.post("/v1/chat/completions", json={"model": "m"})
            assert r.status == 502
        r = await client.post("/v1/chat/completions", json={"model": "m"})
        assert r.status == 503             # OPEN: rejected at the gateway
        err = await r.json()
        assert err["error"]["code"] == "upstream_circuit_open"
        assert int(r.headers["Retry-After"]) >= 1
        assert up.hits == 2                # no connect burned while open
        clk.advance(31.0)                  # -> half-open
        r = await client.post("/v1/chat/completions", json={"model": "m"})
        assert r.status == 200             # probe hits the now-healthy
        assert (await r.json())["served_by"] == "flaky"
        r = await client.post("/v1/chat/completions", json={"model": "m"})
        assert r.status == 200             # closed again
        assert (router.breakers[f"http://127.0.0.1:{up.port}"].state
                == CircuitBreaker.CLOSED)

    try:
        _drive_router(router, body)
    finally:
        up.stop()


def test_router_stalled_upstream_bounded_502():
    up = StallingUpstream()
    up.start()
    router = Router({"m": f"http://127.0.0.1:{up.port}"},
                    upstream_timeout=5.0, read_timeout=0.3,
                    retry_attempts=2, retry_backoff_s=0.01)

    async def body(client):
        t0 = time.monotonic()
        r = await client.post("/v1/chat/completions", json={"model": "m"})
        elapsed = time.monotonic() - t0
        assert r.status == 502
        assert elapsed < 4.0, "stalled upstream must not pin the gateway"

    try:
        _drive_router(router, body)
    finally:
        up.stop()
    assert up.hits <= 2


# ---------------------------------------------------------------------------
# engine watchdog (LLMK_FAULT=engine_stall wedges the harvester's read)
# ---------------------------------------------------------------------------

def _mk_engine(**kw):
    from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig
    base = dict(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=8, num_pages=64, pages_per_slot=8,
        prefill_buckets=(16, 32), async_scheduling=True, async_depth=2,
    )
    base.update(kw)
    return Engine(EngineConfig(**base))


@pytest.mark.e2e
def test_engine_watchdog_sheds_stalled_step(monkeypatch):
    from llms_on_kubernetes_tpu.engine.engine import (
        EngineStallError, SamplingParams)

    eng = _mk_engine(watchdog_stall_s=0.5)
    monkeypatch.setenv("LLMK_FAULT", "engine_stall")
    reqs = [eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                 max_tokens=8)),
            eng.submit([4, 5, 6, 7], SamplingParams(temperature=0.0,
                                                    max_tokens=8))]
    deadline = time.monotonic() + 120
    while not all(r.finished for r in reqs):
        assert time.monotonic() < deadline, "watchdog never fired"
        eng.step()
    assert [r.finish_reason for r in reqs] == ["stalled", "stalled"]
    assert eng.wedged
    with pytest.raises(EngineStallError):
        eng.submit([1, 2], SamplingParams(max_tokens=4))
    assert eng.step() == []                # wedged engine no-ops
    monkeypatch.delenv("LLMK_FAULT")       # release the hung harvester


@pytest.mark.e2e
def test_engine_watchdog_disabled_and_healthy_paths(monkeypatch):
    from llms_on_kubernetes_tpu.engine.engine import SamplingParams

    # watchdog armed but the device is healthy: generation completes
    # normally, nothing sheds
    monkeypatch.delenv("LLMK_FAULT", raising=False)
    eng = _mk_engine(watchdog_stall_s=30.0)
    req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=6))
    deadline = time.monotonic() + 120
    while not req.finished and time.monotonic() < deadline:
        eng.step()
    assert req.finish_reason in ("length", "stop") and not eng.wedged
    # <= 0 disables: _stall_budget resolves to None (waits block forever,
    # pre-watchdog behavior)
    assert _mk_engine(watchdog_stall_s=0)._stall_budget() is None


# ---------------------------------------------------------------------------
# /health vs /ready lifecycle + state gauge
# ---------------------------------------------------------------------------

@pytest.mark.e2e
def test_ready_health_lifecycle_and_state_gauge():
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer

    srv = OpenAIServer(_mk_engine(), ByteTokenizer(), "debug-tiny")
    assert srv.state == "loading"          # constructed but not started

    async def go():
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()        # on_startup -> serving
        try:
            r = await client.get("/ready")
            assert r.status == 200 and (await r.json())["state"] == "serving"
            assert (await client.get("/health")).status == 200
            text = await (await client.get("/metrics")).text()
            assert "llm_engine_state 1" in text

            srv.engine.wedged = True       # what the watchdog sets
            r = await client.get("/ready")
            assert r.status == 503 and (await r.json())["state"] == "wedged"
            # liveness fails ONLY when wedged: restart is the cure here
            assert (await client.get("/health")).status == 503
            text = await (await client.get("/metrics")).text()
            assert "llm_engine_state 3" in text

            srv.engine.wedged = False
            await srv._stop_loop(None)     # preStop/cleanup -> draining
            r = await client.get("/ready")
            assert r.status == 503 and (await r.json())["state"] == "draining"
            # draining is HEALTHY: restarting a draining pod loses work
            assert (await client.get("/health")).status == 200
        finally:
            await client.close()
    asyncio.run(go())


@pytest.mark.e2e
def test_wedged_engine_503s_submissions():
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer

    srv = OpenAIServer(_mk_engine(), ByteTokenizer(), "debug-tiny")

    async def go():
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            srv.engine.wedged = True
            r = await client.post("/v1/chat/completions", json={
                "model": "debug-tiny",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4,
            })
            assert r.status == 503
            err = await r.json()
            assert err["error"]["code"] == "engine_stalled"
            assert r.headers.get("Retry-After")
        finally:
            srv.engine.wedged = False
            await client.close()
    asyncio.run(go())


# ---------------------------------------------------------------------------
# end-to-end deadlines: queue shed, in-flight abort, API 504
# ---------------------------------------------------------------------------

@pytest.mark.e2e
def test_queue_stall_deadline_sheds_without_admission(monkeypatch):
    """LLMK_FAULT=queue_stall wedges admission; an expired deadline sheds
    the waiting request with finish_reason 'timeout' WITHOUT it ever being
    admitted (no prefill burned: admitted_at stays None)."""
    from llms_on_kubernetes_tpu.engine.engine import SamplingParams

    eng = _mk_engine()
    monkeypatch.setenv("LLMK_FAULT", "queue_stall")
    req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=8),
                     deadline=time.monotonic() + 0.1)
    deadline = time.monotonic() + 30
    while not req.finished:
        assert time.monotonic() < deadline, "queue shed never happened"
        eng.step()
        time.sleep(0.01)
    assert req.finish_reason == "timeout"
    assert req.admitted_at is None          # never admitted
    assert req.output == []                 # no tokens burned


@pytest.mark.e2e
def test_inflight_deadline_aborts_with_timeout_reason(monkeypatch):
    """A request admitted in time but still decoding at its deadline is
    aborted mid-flight with finish_reason 'timeout'. slow_step paces the
    decode so the budget deterministically runs out mid-generation."""
    from llms_on_kubernetes_tpu.engine.engine import SamplingParams

    monkeypatch.setenv("LLMK_FAULT", "slow_step:0.05")
    eng = _mk_engine()
    req = eng.submit([1, 2, 3],
                     SamplingParams(temperature=0.0, max_tokens=4096))
    hard = time.monotonic() + 120
    while req.admitted_at is None:
        assert time.monotonic() < hard, "never admitted"
        eng.step()
    req.deadline = time.monotonic()         # budget exhausted mid-flight
    while not req.finished:
        assert time.monotonic() < hard, "deadline abort never happened"
        eng.step()
    assert req.finish_reason == "timeout"
    assert req.admitted_at is not None      # it WAS generating


@pytest.mark.e2e
def test_midwindow_abort_discards_tokens_and_preserves_kv(monkeypatch):
    """ISSUE 8 bugfix: a deadline abort while a fused K-step decode
    window is in flight must discard the unharvested tail — no tokens
    appended past the abort point — WITHOUT corrupting the paged-KV
    accounting: every page comes back reclaimable, and a fresh request
    on the recycled slot decodes exactly like on a fresh engine (stale
    window writes past the abort point are never read)."""
    from llms_on_kubernetes_tpu.engine.engine import SamplingParams

    monkeypatch.setenv("LLMK_FAULT", "slow_step:0.05")
    eng = _mk_engine(decode_steps=4)
    alloc = eng.allocator
    reclaimable0 = alloc.num_free_pages + alloc.num_evictable_pages
    victim = eng.submit([1, 2, 3],
                        SamplingParams(temperature=0.0, max_tokens=4096))
    mate = eng.submit([4, 5, 6, 7],
                      SamplingParams(temperature=0.0, max_tokens=8))
    hard = time.monotonic() + 120
    while victim.admitted_at is None or not victim.output:
        assert time.monotonic() < hard, "victim never started decoding"
        eng.step()
    victim.deadline = time.monotonic()  # expires with windows in flight
    while not (victim.finished and mate.finished):
        assert time.monotonic() < hard, "abort or drain never happened"
        eng.step()
    monkeypatch.delenv("LLMK_FAULT")
    assert victim.finish_reason == "timeout"
    n_at_abort = len(victim.output)
    eng.step()
    eng._drain_async()
    assert len(victim.output) == n_at_abort  # tail really discarded
    assert (alloc.num_free_pages + alloc.num_evictable_pages
            == reclaimable0), "pages leaked by the mid-window abort"
    # recycled slot parity: same prompt, fresh engine
    replay = eng.submit([9, 10, 11],
                        SamplingParams(temperature=0.0, max_tokens=8))
    while not replay.finished:
        assert time.monotonic() < hard
        eng.step()
    fresh_eng = _mk_engine(decode_steps=4)
    fresh = fresh_eng.submit([9, 10, 11],
                             SamplingParams(temperature=0.0, max_tokens=8))
    while not fresh.finished:
        assert time.monotonic() < hard
        fresh_eng.step()
    assert replay.output == fresh.output
    assert replay.finish_reason == fresh.finish_reason


@pytest.mark.e2e
def test_api_rejects_expired_deadline_504():
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer

    srv = OpenAIServer(_mk_engine(), ByteTokenizer(), "debug-tiny")

    async def go():
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            r = await client.post(
                "/v1/completions",
                json={"model": "debug-tiny", "prompt": "hi", "max_tokens": 4},
                headers={"X-LLMK-Deadline-Ms": "0"})
            assert r.status == 504
            err = await r.json()
            assert err["error"]["code"] == "deadline_exceeded"
            text = await (await client.get("/metrics")).text()
            assert 'llm_deadline_exceeded_total{phase="queue"} 1' in text
        finally:
            await client.close()
    asyncio.run(go())


@pytest.mark.e2e
def test_queue_full_429_retry_after_tracks_backlog(monkeypatch):
    """429 Retry-After = queue depth x observed step time (clamped to
    [1, 60]), not a constant inviting a thundering herd."""
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer

    eng = _mk_engine(max_waiting=2)
    srv = OpenAIServer(eng, ByteTokenizer(), "debug-tiny")
    # queue_stall keeps the two queued requests unadmitted so the third
    # submission deterministically hits QueueFullError
    monkeypatch.setenv("LLMK_FAULT", "queue_stall")

    async def go():
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            # the queued requests carry a deadline so they shed themselves
            # (504) once the test is done with them
            body = {"model": "debug-tiny", "prompt": "hi", "max_tokens": 4,
                    "timeout": 3.0}
            t1 = asyncio.create_task(client.post("/v1/completions", json=body))
            t2 = asyncio.create_task(client.post("/v1/completions", json=body))
            deadline = time.monotonic() + 5
            while len(eng.waiting) < 2 and time.monotonic() < deadline:
                await asyncio.sleep(0.01)
            assert len(eng.waiting) == 2
            eng._est_step = 5.0             # 2 waiting x 5 s -> Retry-After 10
            r3 = await client.post("/v1/completions", json=body)
            assert r3.status == 429
            assert (await r3.json())["error"]["type"] == "rate_limit_exceeded"
            assert r3.headers["Retry-After"] == "10"
            r1, r2 = await t1, await t2     # shed at their own deadline
            assert r1.status == r2.status == 504
        finally:
            await client.close()
    asyncio.run(go())


# ---------------------------------------------------------------------------
# readiness flapping + drain lifecycle
# ---------------------------------------------------------------------------

@pytest.mark.e2e
def test_flappy_replica_readiness_alternates(monkeypatch):
    """LLMK_FAULT=flappy_replica:P makes /ready alternate serving/draining
    every P seconds while the engine itself keeps serving — the CPU stand-in
    for a replica repeatedly joining and leaving Service endpoints."""
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer

    srv = OpenAIServer(_mk_engine(), ByteTokenizer(), "debug-tiny")
    monkeypatch.setenv("LLMK_FAULT", "flappy_replica:0.1")

    async def go():
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            statuses = set()
            deadline = time.monotonic() + 5
            while len(statuses) < 2 and time.monotonic() < deadline:
                r = await client.get("/ready")
                statuses.add(r.status)
                if r.status == 503:
                    assert (await r.json())["state"] == "draining"
                assert (await client.get("/health")).status == 200
                await asyncio.sleep(0.025)
            assert statuses == {200, 503}, statuses
        finally:
            await client.close()
    asyncio.run(go())


@pytest.mark.e2e
def test_drain_lifecycle_completes_inflight_stream():
    """The preStop drain contract end-to-end: once shutdown begins,
    /ready flips to 503 draining, NEW submissions are refused with
    code shutting_down, and the in-flight SSE stream still runs to
    completion (graceful drain in the engine loop)."""
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer

    srv = OpenAIServer(_mk_engine(), ByteTokenizer(), "debug-tiny")

    async def go():
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            resp = await client.post("/v1/completions", json={
                "model": "debug-tiny", "prompt": "hello", "max_tokens": 8,
                "stream": True})
            assert resp.status == 200
            # wait for the first SSE payload: the request is now in flight
            first = b""
            while b"data:" not in first:
                first = await resp.content.readline()

            stop_task = asyncio.create_task(srv._stop_loop(None))
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:     # _stop_loop task has run
                r = await client.get("/ready")
                if r.status == 503:
                    break
                await asyncio.sleep(0.01)
            assert r.status == 503 and (await r.json())["state"] == "draining"

            r = await client.post("/v1/completions", json={
                "model": "debug-tiny", "prompt": "new", "max_tokens": 4})
            assert r.status == 503
            err = await r.json()
            assert err["error"]["code"] == "shutting_down"
            assert r.headers.get("Retry-After")

            rest = await resp.content.read()       # stream runs to the end
            text = (first + rest).decode()
            assert '"finish_reason": "length"' in text
            assert "data: [DONE]" in text
            await stop_task
        finally:
            await client.close()
    asyncio.run(go())


# ---------------------------------------------------------------------------
# ISSUE 7: cold-start + preemption faults
# ---------------------------------------------------------------------------

def test_fault_claim_is_one_shot_per_process(monkeypatch):
    """claim() is the single-victim gate: N in-process replicas share one
    LLMK_FAULT env, exactly the first claimer acts on it."""
    monkeypatch.setenv("LLMK_FAULT", "preempt_replica:0.1")
    faults.reset_claims()
    try:
        assert faults.claim("preempt_replica") is True
        assert faults.claim("preempt_replica") is False   # second replica
        # inactive faults never claim, and do not consume the slot
        assert faults.claim("slow_cold_start") is False
        faults.reset_claims()
        assert faults.claim("preempt_replica") is True    # test isolation
    finally:
        faults.reset_claims()


def test_fault_claim_n_spans_process(monkeypatch):
    """claim_n() is the N-shot sibling: drop_handoff:3 drops exactly
    three handoff ingests process-wide, however many replicas share the
    env; a bare fault name uses the hook's default count."""
    monkeypatch.setenv("LLMK_FAULT", "drop_handoff:3")
    faults.reset_claims()
    try:
        assert [faults.claim_n("drop_handoff") for _ in range(5)] \
            == [True, True, True, False, False]
        faults.reset_claims()
        assert faults.claim_n("drop_handoff") is True     # test isolation
        # bare name: default_n governs
        monkeypatch.setenv("LLMK_FAULT", "drop_handoff")
        faults.reset_claims()
        assert faults.claim_n("drop_handoff") is True
        assert faults.claim_n("drop_handoff") is False
        # inactive fault names never claim
        assert faults.claim_n("kill_prefill_replica") is False
    finally:
        faults.reset_claims()


@pytest.mark.e2e
def test_slow_cold_start_delays_readiness(monkeypatch):
    """LLMK_FAULT=slow_cold_start:S holds startup for S seconds — the
    compile-cache-miss cold start in miniature. Once serving, the
    cold-start histogram carries the phase="ready" observation that the
    spike bench and the LLMKColdStartSlow alert read."""
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server import metrics as server_metrics
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer

    monkeypatch.setenv("LLMK_FAULT", "slow_cold_start:0.5")
    faults.reset_claims()
    server_metrics.cold_start.reset()
    srv = OpenAIServer(_mk_engine(), ByteTokenizer(), "debug-tiny")

    async def go():
        t0 = time.monotonic()
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()        # on_startup holds for the fault
        startup_s = time.monotonic() - t0
        try:
            assert startup_s >= 0.5, startup_s
            assert (await client.get("/ready")).status == 200
            text = await (await client.get("/metrics")).text()
            assert 'llm_cold_start_seconds_count{phase="ready"} 1' in text
            # the observed ready time includes the injected delay
            for line in text.splitlines():
                if line.startswith('llm_cold_start_seconds_sum{phase="ready"}'):
                    assert float(line.split()[-1]) >= 0.5
                    break
            else:
                pytest.fail("no cold_start sum sample")
        finally:
            await client.close()
    asyncio.run(go())


@pytest.mark.e2e
def test_preempt_replica_drains_single_victim_without_drops(monkeypatch):
    """The scale-in/preemption contract end-to-end: with TWO in-process
    replicas sharing LLMK_FAULT=preempt_replica, exactly one receives the
    simulated preemption notice, flips to draining (readiness 503 so the
    router/endpoints eject it), REFUSES new work, and still runs its
    in-flight stream to completion — zero dropped streams. The survivor
    keeps serving."""
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer

    monkeypatch.setenv("LLMK_FAULT", "preempt_replica:0.2")
    faults.reset_claims()
    srv_a = OpenAIServer(_mk_engine(), ByteTokenizer(), "debug-tiny")
    srv_b = OpenAIServer(_mk_engine(), ByteTokenizer(), "debug-tiny")

    async def go():
        ca = TestClient(TestServer(srv_a.make_app()))
        cb = TestClient(TestServer(srv_b.make_app()))
        await ca.start_server()
        # the in-flight stream on the victim BEFORE the notice fires
        resp = await ca.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "hello", "max_tokens": 8,
            "stream": True})
        assert resp.status == 200
        first = b""
        while b"data:" not in first:
            first = await resp.content.readline()
        await cb.start_server()
        try:
            # only the first replica to start claims the fault
            deadline = time.monotonic() + 10
            while srv_a.state != "draining" and time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            assert srv_a.state == "draining"
            assert srv_b.state == "serving"     # survivor untouched

            r = await ca.get("/ready")          # endpoints eject the victim
            assert r.status == 503
            assert (await r.json())["state"] == "draining"
            r = await ca.post("/v1/completions", json={
                "model": "debug-tiny", "prompt": "new", "max_tokens": 4})
            assert r.status == 503              # no new work on the victim
            assert (await r.json())["error"]["code"] == "shutting_down"

            # the in-flight stream survives the preemption drain
            text = (first + await resp.content.read()).decode()
            assert '"finish_reason": "length"' in text
            assert "data: [DONE]" in text

            # the survivor absorbs the traffic
            r = await cb.post("/v1/completions", json={
                "model": "debug-tiny", "prompt": "failover", "max_tokens": 4})
            assert r.status == 200
        finally:
            faults.reset_claims()
            await ca.close()
            await cb.close()
    asyncio.run(go())


# ---------------------------------------------------------------------------
# the layout check runs on the devices it is given
# ---------------------------------------------------------------------------

@pytest.mark.e2e
def test_layout_check_names_the_devices_it_ran_on():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run([sys.executable, "__graft_entry__.py", "2"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "dryrun_multichip(2): OK on 2 cpu device(s)" in r.stdout
    # fewer devices than asked for is an error, not a smaller mesh
    r = subprocess.run([sys.executable, "__graft_entry__.py", "4"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "need 4 devices, have 2" in r.stderr
