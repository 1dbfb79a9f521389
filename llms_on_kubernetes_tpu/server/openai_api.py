"""OpenAI-compatible HTTP server over the engine.

The per-model serving surface the reference got from the vLLM image
(`vllm serve ... --port 8080`, reference
vllm-models/helm-chart/templates/model-deployments.yaml:26-39) and from
`llama-server` (reference ramalama model-deployments.yaml:26-35):

    GET  /health               -> 200 "OK"          (probe target, :48-63)
    GET  /v1/models            -> model list
    POST /v1/chat/completions  -> chat completion (+ SSE streaming)
    POST /v1/completions       -> text completion (+ SSE streaming)
    GET  /metrics              -> Prometheus text (gap fixed vs reference)

SSE streaming is end-to-end: engine events flow through an asyncio bridge
into chunked responses — by design, since the reference's Python gateway
demonstrably buffered whole upstream responses and broke streaming
(reference api-gateway.yaml:99; SURVEY §3.1).

The engine runs on a dedicated thread (JAX dispatch is blocking); the
aiohttp event loop never blocks on device work.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import json
import threading
import time
import uuid
from typing import Optional

from aiohttp import web

from llms_on_kubernetes_tpu.engine.engine import Engine, Request, SamplingParams
from llms_on_kubernetes_tpu.engine.tokenizer import TokenizerLike
from llms_on_kubernetes_tpu.server import tracing
from llms_on_kubernetes_tpu.server.metrics import (
    Registry, build_info_metrics, cold_start, engine_metrics,
)
from llms_on_kubernetes_tpu.server.profiling import ProfileManager
from llms_on_kubernetes_tpu.server.qos import (
    PRIORITIES, PRIORITY_HEADER, retry_after_s, tenant_of,
)
from llms_on_kubernetes_tpu.server.runtime_telemetry import RuntimeTelemetry
# Stream-resume protocol headers (canonical definitions and the
# comment-after-data splice invariant are documented at server/router.py):
# the router re-issues a died-mid-stream request with the token ids it
# already relayed; the engine continues decoding from that exact position,
# and this layer journals token ids / suppresses the replayed prefix.
from llms_on_kubernetes_tpu.server.router import (
    CACHE_DIGESTS_HEADER, DEADLINE_HEADER, HANDOFF_ADOPTED_HEADER,
    HANDOFF_DIGESTS_HEADER, HANDOFF_HEADER, HANDOFF_SEED_HEADER,
    HANDOFF_SOURCE_HEADER, HANDOFF_TENANT_HEADER, HANDOFF_TICKET_HEADER,
    JOURNAL_HEADER, RESUME_CREATED_HEADER, RESUME_STREAM_ID_HEADER,
    RESUME_TOKENS_HEADER,
)
from llms_on_kubernetes_tpu.server.tracing import REQUEST_ID_HEADER

# goodput-ledger per-request attribution: total device milliseconds this
# request consumed (all phases, waste included); the phase breakdown rides
# the response body's usage.chip_ms object
CHIP_MS_HEADER = "X-LLMK-Chip-Ms"

# cache-aware routing: CACHE_DIGESTS_HEADER (canonical definition at
# server/router.py) carries the engine digest chain of the request's full
# prompt pages on every completion response; capped so the header stays
# ~2 KiB (routers cap further at their configured max_digests)
CACHE_DIGESTS_MAX = 32


def _chip_ms_total(reqs) -> dict:
    """Summed per-phase chip-time attribution across a request group
    (n>1 / best_of fan-out serves one HTTP request with many engine
    requests)."""
    chip: dict = {}
    for r in reqs:
        for ph, v in getattr(r, "chip_ms", {}).items():
            chip[ph] = chip.get(ph, 0.0) + v
    return chip


def _encode_kv_payload(pl: dict) -> dict:
    """Wire form of one host-tier KV page for /internal/kv/fetch: each
    array as base64 raw bytes + shape + dtype + a truncated sha256 so a
    truncated or bit-flipped transfer is detected at ingest (and treated
    as a missing page) instead of landing wrong bytes in the cache."""
    import base64
    import hashlib

    import numpy as np

    def arr(a):
        if a is None:
            return None
        a = np.ascontiguousarray(a)
        raw = a.tobytes()
        return {"b64": base64.b64encode(raw).decode("ascii"),
                "shape": list(a.shape), "dtype": str(a.dtype),
                "sha": hashlib.sha256(raw).hexdigest()[:16]}

    return {k: arr(pl.get(k)) for k in ("k", "v", "ks", "vs")}


def _decode_kv_payload(doc) -> Optional[dict]:
    """Inverse of :func:`_encode_kv_payload`; None for anything malformed
    or checksum-failed (the caller treats that page as missing — shape/
    dtype validation against the local pools happens in the engine)."""
    import base64
    import binascii
    import hashlib

    import numpy as np

    if not isinstance(doc, dict):
        return None

    def arr(enc):
        if enc is None:
            return None
        if not isinstance(enc, dict):
            raise ValueError("bad array encoding")
        raw = base64.b64decode(enc["b64"], validate=True)
        if hashlib.sha256(raw).hexdigest()[:16] != enc.get("sha"):
            raise ValueError("checksum mismatch")
        a = np.frombuffer(raw, dtype=np.dtype(str(enc["dtype"])))
        return a.reshape([int(s) for s in enc["shape"]]).copy()

    try:
        out = {k: arr(doc.get(k)) for k in ("k", "v", "ks", "vs")}
    except (KeyError, ValueError, TypeError, binascii.Error):
        return None
    if out["k"] is None or out["v"] is None:
        return None
    return out


def _deadline_from(request: web.Request, body: dict) -> Optional[float]:
    """Absolute monotonic deadline for this request, or None.

    The router's ``X-LLMK-Deadline-Ms`` header (milliseconds of budget
    REMAINING, already decremented for gateway time) takes precedence over
    the body's OpenAI-style ``timeout`` field (seconds). A malformed header
    means no deadline rather than a 400: deadlines are best-effort shedding,
    not an input-validation surface.
    """
    raw = request.headers.get(DEADLINE_HEADER)
    if raw is not None:
        try:
            return time.monotonic() + float(raw) / 1000.0
        except ValueError:
            return None
    t = body.get("timeout")
    if isinstance(t, (int, float)) and not isinstance(t, bool) and t > 0:
        return time.monotonic() + float(t)
    return None


def _keepalive_interval_s() -> float:
    """SSE keepalive comment period: ``LLMK_SSE_KEEPALIVE_S`` seconds
    (default 15; <= 0 disables). Read per-stream so tests can monkeypatch
    the env without restarting the server."""
    import os

    raw = os.environ.get("LLMK_SSE_KEEPALIVE_S", "")
    try:
        return float(raw) if raw else 15.0
    except ValueError:
        return 15.0


def _adapter_from_model(model) -> Optional[str]:
    """Multi-tenant naming: ``model="base:adapter"`` addresses a LoRA
    adapter of the served base model. A plain model name (no colon) is the
    base model itself — the adapter part is everything after the FIRST
    colon (adapter names themselves cannot contain one)."""
    if isinstance(model, str) and ":" in model:
        return model.split(":", 1)[1]
    return None


class EngineLoop(threading.Thread):
    """Drives Engine.step() whenever there is work; sleeps otherwise.

    ``stop()`` begins a GRACEFUL drain: work already admitted or queued
    keeps stepping to completion (bounded by ``drain_timeout_s``) so
    streaming clients receive their final events during the preStop
    window; the API layer refuses new submissions while draining."""

    # must stay under _stop_loop's 60 s join so shutdown never wedges on
    # a pathological backlog
    drain_timeout_s = 55.0

    def __init__(self, engine: Engine, metrics: Optional[dict] = None,
                 model_name: str = "",
                 flight: Optional[tracing.FlightRecorder] = None,
                 profiles=None):
        super().__init__(daemon=True, name="engine-loop")
        self.engine = engine
        self.metrics = metrics
        self.model_name = model_name
        self.flight = flight
        self.profiles = profiles  # ProfileManager for watchdog captures
        self._wake = threading.Event()
        self._stop_evt = threading.Event()
        self._ttft_seen: set[str] = set()
        self._preempt_seen = 0
        self._moe_seen: collections.Counter = collections.Counter()
        self._path_seen: collections.Counter = collections.Counter()
        self._prefix_skipped_seen: collections.Counter = (
            collections.Counter())
        self._early_exit_seen = 0
        self._first_tokens_seen = {"backpressure": 0, "step": 0}
        self._decode_launches_seen: collections.Counter = (
            collections.Counter())
        self._spec_seen = {"drafted": 0, "accepted": 0}
        self._adapter_seen = {"hits": 0, "misses": 0, "evictions": 0}
        self._host_kv_seen = {"hits": 0, "misses": 0, "evictions": 0}
        self._tenant_admitted_seen: "collections.Counter" = (
            collections.Counter())
        self._shed_total = 0
        # goodput-ledger drain state: cumulative ms already exported
        # (delta-style, matching the other counters above)
        self._led_phase_seen: dict[str, float] = {}
        self._led_tenant_seen: dict[tuple, float] = {}
        self._led_dispatches_seen = 0
        self._led_kind_seen: dict[tuple, float] = {}
        self._led_emit_seen = {"decode": 0.0, "spec": 0.0}
        self._led_idle_seen: dict[str, float] = {}
        self._led_frame_seen = (0.0, 0.0)
        self.auto_profiles = 0

    def _mlabel(self, r) -> str:
        """Per-request model label: ``base:adapter`` for LoRA requests so
        multi-tenant latency series separate per tenant."""
        a = getattr(r, "adapter", None)
        return f"{self.model_name}:{a}" if a else self.model_name

    def submit(self, *args, **kw) -> Request:
        req = self.engine.submit(*args, **kw)
        if self.metrics:
            self.metrics["requests_total"].inc()
            self.metrics["prompt_tokens"].inc(len(req.prompt))
        self._wake.set()
        return req

    def abort(self, req: Request, reason: str = "abort") -> None:
        self.engine.abort(req, reason)
        self._wake.set()

    def stop(self) -> None:
        self._stop_evt.set()
        self._wake.set()

    def run(self) -> None:
        eng = self.engine
        try:
            self._run()
        finally:
            # harvest anything still in flight so streaming clients get
            # their final events instead of hanging on a graceful shutdown
            eng._drain_async()

    def _run(self) -> None:
        eng = self.engine
        drain_deadline = None
        while True:
            if self._stop_evt.is_set():
                if drain_deadline is None:
                    drain_deadline = time.monotonic() + self.drain_timeout_s
                if (not eng.has_work() or getattr(eng, "wedged", False)
                        or time.monotonic() >= drain_deadline):
                    return
            elif not eng.has_work():
                self._wake.wait(timeout=0.05)
                self._wake.clear()
                continue
            dw0 = eng.device_wait_s() if hasattr(eng, "device_wait_s") else 0.0
            t0 = time.monotonic()
            events = eng.step()
            dt = time.monotonic() - t0
            # for the flight recorder: how much of this step's wall time
            # the engine was blocked on reads (not device time: with
            # dispatches in flight the device works while the host does;
            # llm_dispatch_device_seconds_total is the device's). Clamped
            # to [0, dt] — the harvester runs concurrently, so its delta
            # can exceed this step's own wall time.
            device_s = 0.0
            if hasattr(eng, "device_wait_s"):
                device_s = max(0.0, min(eng.device_wait_s() - dw0, dt))
            occupancy = sum(r is not None for r in eng.slots)
            pages_used = eng.config.num_pages - 1 - eng.allocator.num_free_pages
            step_tokens = sum(len(ev.new_tokens) for ev in events)
            step_finished = sum(1 for ev in events if ev.finished)
            self._shed_total += sum(
                1 for ev in events
                if ev.finished and ev.finish_reason in ("timeout", "stalled"))
            led = getattr(eng, "ledger", None)
            led_snap = led.snapshot() if led is not None else None
            if self.metrics:
                m = self.metrics
                if eng.preemptions > self._preempt_seen:
                    m["preemptions"].inc(eng.preemptions - self._preempt_seen)
                    self._preempt_seen = eng.preemptions
                steps_obs = getattr(eng, "steps_obs", None)
                if steps_obs is not None:
                    while steps_obs:
                        m["decode_steps_per_dispatch"].observe(
                            steps_obs.popleft())
                admitted = getattr(eng, "tenant_admitted", None)
                if admitted is not None:
                    for key, v in list(admitted.items()):
                        seen = self._tenant_admitted_seen[key]
                        if v > seen:
                            m["tenant_admitted"].labels(
                                tenant=key[0], priority=key[1]).inc(v - seen)
                            self._tenant_admitted_seen[key] = v
                twobs = getattr(eng, "tenant_wait_obs", None)
                if twobs is not None:
                    while twobs:
                        tenant, wait, _prio = twobs.popleft()
                        m["tenant_queue_wait"].labels(
                            tenant=tenant).observe(wait)
                early_exit = getattr(eng, "early_exit_steps", 0)
                if early_exit > self._early_exit_seen:
                    m["decode_early_exit"].inc(
                        early_exit - self._early_exit_seen)
                    self._early_exit_seen = early_exit
                for where, v in getattr(
                        eng, "first_tokens_handed", {}).items():
                    if v > self._first_tokens_seen[where]:
                        m["first_tokens"].labels(delivered=where).inc(
                            v - self._first_tokens_seen[where])
                        self._first_tokens_seen[where] = v
                for when, v in getattr(eng, "decode_launches", {}).items():
                    if v > self._decode_launches_seen[when]:
                        m["decode_launches"].labels(when=when).inc(
                            v - self._decode_launches_seen[when])
                        self._decode_launches_seen[when] = v
                for kind, stats in getattr(eng, "moe_stats", {}).items():
                    for stat, v in stats.items():
                        seen = self._moe_seen[kind, stat]
                        if v > seen:
                            m["moe_" + stat].labels(kind=kind).inc(
                                v - seen)
                            self._moe_seen[kind, stat] = v
                for why, v in getattr(
                        eng, "prefix_reuse_skipped", {}).items():
                    if v > self._prefix_skipped_seen[why]:
                        m["prefix_reuse_skipped"].labels(why=why).inc(
                            v - self._prefix_skipped_seen[why])
                        self._prefix_skipped_seen[why] = v
                drafted = getattr(eng, "spec_drafted_tokens", 0)
                if drafted > self._spec_seen["drafted"]:
                    m["spec_drafted"].inc(
                        drafted - self._spec_seen["drafted"])
                    self._spec_seen["drafted"] = drafted
                accepted = getattr(eng, "spec_accepted_tokens", 0)
                if accepted > self._spec_seen["accepted"]:
                    m["spec_accepted"].inc(
                        accepted - self._spec_seen["accepted"])
                    self._spec_seen["accepted"] = accepted
                if drafted > 0:
                    m["spec_accept_ratio"].set(accepted / drafted)
                adp = getattr(eng, "adapters", None)
                if adp is not None:
                    for k, seen in self._adapter_seen.items():
                        v = adp.stats[k]
                        if v > seen:
                            m["adapter_cache_" + k].inc(v - seen)
                            self._adapter_seen[k] = v
                    while adp.load_times:
                        m["adapter_load"].observe(adp.load_times.pop(0))
                hk = getattr(eng, "host_kv", None)
                if hk is not None:
                    for k in ("hits", "misses", "evictions"):
                        v = getattr(hk, k)
                        if v > self._host_kv_seen[k]:
                            m["kv_host_cache_" + k].inc(
                                v - self._host_kv_seen[k])
                            self._host_kv_seen[k] = v
                upl = getattr(eng, "kv_upload_obs", None)
                if upl is not None:
                    while upl:
                        m["kv_upload"].observe(upl.popleft())
                cc = getattr(eng, "cache_config", None)
                if cc is not None:
                    m["kv_bytes_per_token"].set(cc.bytes_per_token)
                # tokens by path, whatever the model; the same under the
                # latent model's own name, which its benchmark metric reads
                mla = getattr(eng.model_config, "is_mla", False)
                counts = {"path_tokens": ("path", dict(
                              eng.path_tokens, decode=eng.decode_tokens)),
                          "ssm_positions": ("path", eng.ssm_positions),
                          "attn_window_rows": ("rows", eng.window_rows),
                          "decode_windows": ("sampler", eng.decode_windows)}
                for name, (label, by_value) in counts.items():
                    for path, v in by_value.items():
                        new = v - self._path_seen[name, path]
                        if new > 0:
                            m[name].labels(**{label: path}).inc(new)
                            if mla and name == "path_tokens":
                                m["mla_tokens"].labels(path=path).inc(new)
                            self._path_seen[name, path] = v
                if mla:
                    kp = eng.k_pages.data
                    m["latent_cache_bytes"].set(kp.size * kp.dtype.itemsize)
                m["conv_state_bytes"].set(eng.slot_state_bytes)
                if led_snap is not None:
                    series = dict(led_snap["phase_ms"])
                    series["idle"] = led_snap["idle_ms"]
                    for ph, ms in series.items():
                        seen = self._led_phase_seen.get(ph, 0.0)
                        if ms > seen:
                            m["chip_seconds"].labels(phase=ph).inc(
                                (ms - seen) / 1000.0)
                            self._led_phase_seen[ph] = ms
                    for key, ms in led_snap["tenant_ms"].items():
                        seen = self._led_tenant_seen.get(key, 0.0)
                        if ms > seen:
                            m["tenant_chip_seconds"].labels(
                                tenant=key[0], phase=key[1]).inc(
                                    (ms - seen) / 1000.0)
                            self._led_tenant_seen[key] = ms
                    self._drain_dispatch_totals(led_snap)
                m["batch_occupancy"].set(occupancy)
                m["kv_pages_used"].set(pages_used)
                m["kv_pages_live"].set(eng.allocator.num_live_pages)
                m["waiting"].set(len(eng.waiting))
                m["queue_depth"].labels(
                    model=self.model_name,
                    role=eng.config.role or "both").set(len(eng.waiting))
                m["prefix_hit_tokens"].set(eng.allocator.hit_tokens_total)
                for ev in events:
                    m["tokens_generated"].inc(len(ev.new_tokens))
                    r = ev.request
                    # OpenMetrics exemplar: pin the latency sample to its
                    # W3C trace id so a slow histogram bucket links
                    # straight to the exported waterfall
                    tid = getattr(getattr(r, "trace", None),
                                  "trace_id", None)
                    if ev.finished:
                        m["requests_finished"].inc()
                        m["e2e_latency"].labels(model=self._mlabel(r)).observe(
                            (r.finished_at or time.monotonic())
                            - r.submitted_at, trace_id=tid)
                    if ev.finished and ev.finish_reason == "timeout":
                        # queue = shed before ever being prefilled;
                        # decode = aborted mid-generation at its deadline
                        phase = "queue" if r.admitted_at is None else "decode"
                        m["deadline_exceeded"].labels(phase=phase).inc()
                    if r.first_token_at and r.id not in self._ttft_seen:
                        self._ttft_seen.add(r.id)
                        m["ttft"].labels(model=self._mlabel(r)).observe(
                            r.first_token_at - r.submitted_at, trace_id=tid)
                    if ev.finished:
                        self._ttft_seen.discard(r.id)
            if self.flight is not None:
                # one flight-recorder frame per engine step: enough to
                # reconstruct "what was the engine doing" after a stall
                # or latency spike without a profiler attached
                frame = dict(
                    step_ms=round(dt * 1000.0, 3),
                    device_ms=round(device_s * 1000.0, 3),
                    host_ms=round((dt - device_s) * 1000.0, 3),
                    occupancy=occupancy,
                    kv_pages_used=pages_used,
                    waiting=len(eng.waiting),
                    tokens=step_tokens,
                    tokens_per_s=round(step_tokens / dt, 1) if dt > 0 else 0.0,
                    finished=step_finished,
                    preemptions=eng.preemptions,
                    shed=self._shed_total,
                    wedged=bool(getattr(eng, "wedged", False)),
                )
                if led_snap is not None:
                    attr, waste = (led_snap["attributed_ms"],
                                   led_snap["wasted_ms"])
                    pa, pw = self._led_frame_seen
                    self._led_frame_seen = (attr, waste)
                    frame.update(
                        chip_attr_ms=round(attr - pa, 3),
                        chip_waste_ms=round(waste - pw, 3),
                    )
                self.flight.record(**frame)
            if led is not None and led.take_anomaly():
                self._trigger_auto_profile()

    # ledger snapshot field of a kind -> (counter, units per second)
    _DISPATCH_SERIES = (("dispatches", "dispatches", 1.0),
                        ("device_ms", "dispatch_device_seconds", 1000.0),
                        ("behind_ms", "dispatch_behind_seconds", 1000.0),
                        ("enqueue_ms", "dispatch_enqueue_seconds", 1000.0))

    def _drain_dispatch_totals(self, led_snap: dict) -> None:
        """The dispatch records' own totals into their counters: by kind
        of step, and by what the host was doing in each device gap.
        They move only when a dispatch is booked; the decode windows'
        hand-over lag (Engine.decode_emit_s) when one was collected."""
        m = self.metrics
        for kind, s in getattr(self.engine, "decode_emit_s", {}).items():
            if s > self._led_emit_seen[kind]:
                m["decode_emit_seconds"].labels(kind=kind).inc(
                    s - self._led_emit_seen[kind])
                self._led_emit_seen[kind] = s
        if led_snap["dispatches"] == self._led_dispatches_seen:
            return
        self._led_dispatches_seen = led_snap["dispatches"]
        for kind, tot in led_snap["kinds"].items():
            for field, series, per_s in self._DISPATCH_SERIES:
                seen = self._led_kind_seen.get((kind, field), 0.0)
                if tot[field] > seen:
                    m[series].labels(kind=kind).inc(
                        (tot[field] - seen) / per_s)
                    self._led_kind_seen[(kind, field)] = tot[field]
        for host, ms in led_snap["idle_host_ms"].items():
            seen = self._led_idle_seen.get(host, 0.0)
            if ms > seen:
                m["device_idle_seconds"].labels(host=host).inc(
                    (ms - seen) / 1000.0)
                self._led_idle_seen[host] = ms

    def _trigger_auto_profile(self) -> None:
        """One bounded, rate-limited profiler capture while the step-time
        anomaly is still live (the detector's cooldown is the rate limit;
        a capture already in flight is skipped, not queued)."""
        self.auto_profiles += 1
        if self.metrics:
            self.metrics["auto_profile"].labels(reason="step_anomaly").inc()
        if self.flight is not None:
            self.flight.record(marker="auto_profile", reason="step_anomaly")
        prof = self.profiles
        if prof is None:
            return
        import os
        duration_ms = float(os.environ.get("LLMK_ANOMALY_CAPTURE_MS", "2000"))

        def _cap():
            try:
                prof.capture(duration_ms=duration_ms)
            except RuntimeError:
                pass  # a capture is already running — skip, don't queue
            except Exception:
                pass  # profiling must never take the serving loop down

        threading.Thread(target=_cap, daemon=True,
                         name="auto-profile").start()


def _event_pusher(loop: asyncio.AbstractEventLoop, q: "asyncio.Queue"):
    """Engine-thread -> asyncio delivery without blocking threads: the
    engine calls this with each event; it lands in the request's asyncio
    queue via call_soon_threadsafe. (The old model — one executor thread
    parked in a blocking queue.get per active stream — capped concurrency
    at the thread pool size and collapsed gateway TTFT under load.)"""
    def push(item):
        try:
            loop.call_soon_threadsafe(q.put_nowait, item)
        except RuntimeError:
            pass  # loop already closed (shutdown/disconnect)
    return push


async def _next_event(req: Request) -> tuple[list[int], bool, Optional[str]]:
    """Await the engine thread's next event for this request."""
    q = getattr(req, "_aq", None)
    if q is not None:
        return await q.get()
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(None, req.events.get)


class IncrementalDetokenizer:
    """Emit text deltas from a growing token list, holding back bytes that
    may still change (partial UTF-8 / merged tokens)."""

    def __init__(self, tokenizer: TokenizerLike):
        self.tok = tokenizer
        self.ids: list[int] = []
        self.sent = 0

    def push(self, new_ids: list[int], final: bool = False) -> str:
        self.ids += new_ids
        text = self.tok.decode(self.ids)
        if not final and text and text[-1] == "�":
            # trailing replacement char: likely mid-UTF-8 sequence; hold back
            text = text[:-1]
        delta = text[self.sent:]
        if final:
            delta = self.tok.decode(self.ids)[self.sent:]
        self.sent += len(delta)
        return delta


class StopChecker:
    """Server-side stop-SEQUENCE matching (the OpenAI ``stop`` parameter).

    Stop token ids are handled inside the engine; stop *strings* can span
    token boundaries, so they are matched on the detokenized text stream.
    ``push`` returns (text safe to emit, hit): while streaming, the last
    ``max(len(stop)) - 1`` characters are held back so a stop sequence split
    across deltas is never partially emitted.
    """

    def __init__(self, stops: list[str]):
        self.stops = [s for s in stops if s]
        self.holdback = max((len(s) for s in self.stops), default=1) - 1
        self.text = ""
        self.emitted = 0

    def push(self, delta: str, final: bool = False) -> tuple[str, bool]:
        self.text += delta
        # earliest occurrence IN THE TEXT wins, not list order: with
        # stop=["b", "a"] and text "a...b" output truncates at "a"
        # (OpenAI semantics). Scanning from ``emitted`` (nothing earlier
        # can be truncated anyway) keeps the scan O(holdback + delta) and
        # re-finds matches deferred by the partial-prefix rule below.
        best = -1
        for s in self.stops:
            idx = self.text.find(s, self.emitted)
            if idx != -1 and (best == -1 or idx < best):
                best = idx
        if best != -1 and not final:
            # a LONGER stop that started before ``best`` may still be
            # completing (its remainder arrives in a later delta); firing
            # now would truncate at the later match. Defer: emit up to the
            # earliest such candidate start and wait for the next delta.
            pend = self._pending_start_before(best)
            if pend is not None:
                cut = max(self.emitted, pend)
                out = self.text[self.emitted:cut]
                self.emitted = cut
                return out, False
        if best != -1:
            out = self.text[self.emitted:best]
            self.emitted = best
            return out, True
        cut = len(self.text) if final or not self.stops else max(
            self.emitted, len(self.text) - self.holdback)
        out = self.text[self.emitted:cut]
        self.emitted = cut
        return out, False

    def _pending_start_before(self, limit: int) -> Optional[int]:
        """Earliest position < ``limit`` where some stop has matched a
        proper prefix that runs off the end of the text (i.e. could still
        complete), or None."""
        n = len(self.text)
        earliest = None
        for s in self.stops:
            for i in range(max(self.emitted, n - len(s) + 1), min(limit, n)):
                if s.startswith(self.text[i:]):  # i + len(s) > n by range
                    if earliest is None or i < earliest:
                        earliest = i
                    break
        return earliest


def _parse_stops(body: dict) -> list[str]:
    stop = body.get("stop")
    if isinstance(stop, str):
        return [stop]
    if isinstance(stop, list):
        return [s for s in stop if isinstance(s, str)]
    return []


class OpenAIServer:
    def __init__(
        self,
        engine: Engine,
        tokenizer: TokenizerLike,
        model_name: str,
        registry: Optional[Registry] = None,
    ):
        self.tokenizer = tokenizer
        self.model_name = model_name
        self.registry = registry or Registry()
        self.metrics = engine_metrics(self.registry)
        # startup phases timed before this registry existed (mesh init,
        # checkpoint load, warmup compiles in cli.py) land in the process
        # -wide ColdStartRecorder; flush them into the histogram now so
        # the first /metrics scrape already carries the full cold start
        for phase, seconds in cold_start.drain():
            self.metrics["cold_start"].labels(phase=phase).observe(seconds)
        import jax

        build_info_metrics(
            self.registry, backend=jax.default_backend(),
            role=getattr(getattr(engine, "config", None), "role", None)
            or "both")
        # runtime telemetry (device memory, live buffers, jit compile
        # counters) refreshed at scrape time by the /metrics handler
        self.telemetry = RuntimeTelemetry(self.registry)
        # on-demand bounded profile captures (POST/GET /debug/profile)
        self.profiles = ProfileManager()
        # observability surfaces: recent completed traces (/debug/traces)
        # and the engine flight recorder (/debug/engine)
        import os
        self.traces = tracing.TraceStore(
            int(os.environ.get("LLMK_TRACE_RING", "256")))
        self.flight = tracing.FlightRecorder(
            int(os.environ.get("LLMK_FLIGHT_STEPS", "512")))
        # cross-hop tracing: tail-sampled OTLP export of finished request
        # fragments (dormant without LLMK_OTLP_ENDPOINT — every skipped
        # trace is still counted in llm_trace_dropped_total)
        self.tail_sampler = tracing.TailSampler()
        self.exporter = tracing.exporter_from_env(
            "llmk-engine", self.metrics["trace_spans_exported"],
            self.metrics["trace_dropped"])
        self.loop_thread = EngineLoop(engine, self.metrics,
                                      model_name=model_name,
                                      flight=self.flight,
                                      profiles=self.profiles)
        self.engine = engine
        # readiness lifecycle: loading -> serving -> draining; "wedged" is
        # derived from the engine watchdog and overrides everything.
        # /health (liveness) fails ONLY when wedged — a restart helps
        # there and nowhere else; /ready (readiness) is 200 only while
        # serving, so k8s pulls the pod from endpoints during load and
        # the preStop drain window without killing it.
        self._state = "loading"
        # grammar-constrained decoding (response_format / forced
        # tool_choice): the tokenizer's byte map is derived once on first
        # use; compiled grammars are cached in engine/grammar.py
        self._token_bytes = None
        self._token_bytes_lock = threading.Lock()
        # disaggregated handoff: lazy client session for pulling KV pages
        # from a prefill replica (decode role); closed at shutdown
        self._handoff_session = None
        # gray-failure fault state: >1.0 means this replica decodes at
        # 1/factor speed while probes stay green (degraded_replica fault,
        # claimed in _maybe_claim_degraded at startup or mid-run)
        self._degraded_factor = 1.0
        # cache-aware routing: bloom-filter advertisement of the digests
        # resident in the device prefix cache + host KV tier, rebuilt at
        # most every LLMK_PREFIX_FILTER_INTERVAL_S seconds and piggybacked
        # on /ready for the routers' probe cycle (LLMK_PREFIX_FILTER_BITS=0
        # disables the advertisement entirely)
        self._pf_doc: Optional[dict] = None
        self._pf_built = 0.0
        self._pf_bits = int(os.environ.get("LLMK_PREFIX_FILTER_BITS",
                                           "8192"))
        self._pf_hashes = int(os.environ.get("LLMK_PREFIX_FILTER_HASHES",
                                             "4"))
        self._pf_interval = float(os.environ.get(
            "LLMK_PREFIX_FILTER_INTERVAL_S", "2.0"))

    # ------------------------------------------------------------------

    # request body cap: base64 image_url parts inflate images by 4/3, so
    # aiohttp's 1 MiB default would reject most real photos before the
    # handler even runs (multimodal requests with a few images fit well
    # under this)
    MAX_BODY_BYTES = 32 * 1024 * 1024

    @web.middleware
    async def _request_id_middleware(self, request, handler):
        """Read-or-mint the request id at the edge of this process and echo
        it on every response (Dapper-style propagation: both routers
        forward the inbound header verbatim, so the id a client quotes
        matches the engine's trace). The same reconciliation adopts a
        valid inbound ``traceparent`` (the router mints one per hop) so
        this process's fragment parents under the exact hop that reached
        it — a forged or malformed one is re-minted, never trusted."""
        ctx = tracing.reconcile(
            request.headers.get(tracing.TRACEPARENT_HEADER),
            request.headers.get(tracing.TRACESTATE_HEADER),
            request.headers.get(REQUEST_ID_HEADER))
        rid = ctx["request_id"] or tracing.new_request_id()
        request["llmk_request_id"] = rid
        request["llmk_trace_ctx"] = ctx
        try:
            resp = await handler(request)
        except web.HTTPException as ex:
            ex.headers.setdefault(REQUEST_ID_HEADER, rid)
            raise
        if not resp.prepared:
            # streamed responses set the header themselves before prepare()
            resp.headers.setdefault(REQUEST_ID_HEADER, rid)
        return resp

    def make_app(self) -> web.Application:
        app = web.Application(client_max_size=self.MAX_BODY_BYTES,
                              middlewares=[self._request_id_middleware])
        app.router.add_get("/health", self.health)
        app.router.add_get("/ready", self.ready)
        app.router.add_get("/v1/models", self.models)
        app.router.add_get("/metrics", self.prometheus)
        app.router.add_post("/v1/chat/completions", self.chat_completions)
        app.router.add_post("/v1/completions", self.completions)
        # the vllm-openai image's utility surface (reference
        # vllm-models/helm-chart/templates/model-deployments.yaml:21):
        # /tokenize, /detokenize, /version, and an explicit 501 for
        # /v1/embeddings (this server generates; it does not embed)
        app.router.add_post("/tokenize", self.tokenize)
        app.router.add_post("/detokenize", self.detokenize)
        app.router.add_get("/version", self.version)
        app.router.add_post("/v1/embeddings", self.embeddings)
        # disaggregated handoff: a decode replica pulls the host-tier KV
        # pages a prefill replica spilled (serving-port internal surface,
        # like /debug/*: the deployment keeps these ports cluster-local)
        app.router.add_post("/internal/kv/fetch", self.kv_fetch)
        app.router.add_post("/debug/profile", self.profile_capture)
        app.router.add_get("/debug/profile", self.profile_list)
        app.router.add_get("/debug/profile/{capture_id}",
                           self.profile_download)
        app.router.add_post("/debug/profile/start", self.profile_start)
        app.router.add_post("/debug/profile/stop", self.profile_stop)
        app.router.add_get("/debug/traces", self.debug_traces)
        app.router.add_get("/debug/engine", self.debug_engine)
        app.on_startup.append(self._start_loop)
        app.on_cleanup.append(self._stop_loop)
        return app

    async def _start_loop(self, app) -> None:
        from llms_on_kubernetes_tpu import faults
        # injected fault: startup stalls (compile-cache miss in
        # miniature) — the replica stays "loading"/503 so routers and
        # autoscalers see a realistically slow join
        delay = faults.get_float("slow_cold_start", 2.0)
        if delay is not None and delay > 0:
            await asyncio.sleep(delay)
        if not self.loop_thread.is_alive():
            self.loop_thread.start()
        self._state = "serving"
        # "ready" = process start -> taking traffic; sub-phases
        # (mesh/load/compile) were recorded by cli.py where they ran
        self.metrics["cold_start"].labels(phase="ready").observe(
            cold_start.elapsed())
        # injected fault: a spot-TPU preemption notice lands DELAY
        # seconds from now. One-shot (faults.claim) so a multi-replica
        # process loses exactly one replica; its in-flight streams must
        # finish or fail over, never drop.
        notice = faults.get_float("preempt_replica", 1.0)
        if notice is not None and faults.claim("preempt_replica"):
            t = threading.Timer(
                max(notice, 0.0), self.begin_drain,
                kwargs={"reason": "preempt_replica fault"})
            t.daemon = True
            t.start()
        # injected fault: a prefill-role pod crashes abruptly DELAY
        # seconds from now — no graceful drain, readiness AND liveness go
        # 503, in-flight and new requests are refused. One-shot (claim)
        # and armed only on prefill-role servers: the router must retry
        # surviving prefill replicas or fall back to colocated serving.
        crash = faults.get_float("kill_prefill_replica", 1.0)
        if (crash is not None
                and getattr(self.engine.config, "role", None) == "prefill"
                and faults.claim("kill_prefill_replica")):
            t = threading.Timer(max(crash, 0.0), self._kill_abrupt)
            t.daemon = True
            t.start()
        # injected fault: the canonical GRAY failure — this replica
        # streams at 1/FACTOR speed (event pacing stretched in _drain)
        # while /health and /ready keep answering green, so probe-based
        # ejection never fires. One-shot (claim): a multi-replica process
        # degrades exactly ONE replica; the router's latency outlier
        # detector must quarantine it from in-band TTFT alone.
        self._maybe_claim_degraded()

    def _maybe_claim_degraded(self) -> None:
        """Arm the ``degraded_replica`` gray failure on THIS replica if
        the fault is active and still unclaimed. Checked at startup AND
        at stream-delivery time: real gray failures develop at runtime,
        and chaos_bench sets the env only after its baseline waves, so a
        healthy fleet must be able to grow exactly one live victim."""
        if self._degraded_factor > 1.0:
            return
        from llms_on_kubernetes_tpu import faults
        factor = faults.get_float("degraded_replica", 8.0)
        if (factor is not None and factor > 1.0
                and faults.claim("degraded_replica")):
            self._degraded_factor = float(factor)

    def _kill_abrupt(self) -> None:
        """Simulated prefill-pod crash (``kill_prefill_replica`` fault):
        unlike :meth:`begin_drain` there is no grace — every in-flight
        engine request is aborted, the serving state flips to ``killed``
        (liveness and readiness both 503, new work refused), and the
        engine loop stops. Idempotent."""
        if self._state == "killed":
            return
        self._state = "killed"
        self.metrics["engine_state"].set(self.STATE_CODES["killed"])
        try:
            for r in list(self.engine.waiting) + list(self.engine.slots):
                if r is not None:
                    self.loop_thread.abort(r, "kill_prefill_replica")
        except Exception:
            pass  # a fault hook must never take the process down itself
        self.loop_thread.stop()

    def begin_drain(self, reason: str = "scale-in") -> None:
        """Enter the graceful drain from OUTSIDE the event loop.

        The SIGTERM path (aiohttp cleanup -> ``_stop_loop``) and this
        method converge on the same lifecycle: readiness goes 503 so
        routers eject the replica, admissions are refused, and the
        engine loop keeps stepping until in-flight work completes
        (bounded by ``EngineLoop.drain_timeout_s``). Used by the
        ``preempt_replica`` fault and scale-in hooks; idempotent."""
        if self._state == "draining":
            return
        self._state = "draining"
        self.metrics["engine_state"].set(self.STATE_CODES["draining"])
        # stop() only sets events — safe from any thread; the engine
        # loop drains in its own thread while streams keep flowing
        self.loop_thread.stop()

    async def _stop_loop(self, app) -> None:
        if self._state != "killed":
            self._state = "draining"
        if self._handoff_session is not None:
            await self._handoff_session.close()
            self._handoff_session = None
        if self.exporter is not None:
            self.exporter.close()
        self.loop_thread.stop()
        if self.loop_thread.is_alive():
            # join OFF the event loop so cleanup isn't blocked; the join
            # must complete before cli.py broadcasts MSG_SHUTDOWN, or a
            # follower could receive it interleaved with this thread's
            # in-flight step broadcasts and desert the SPMD program
            import asyncio
            await asyncio.get_running_loop().run_in_executor(
                None, self.loop_thread.join, 60.0)

    # ------------------------------------------------------------------
    # endpoints
    # ------------------------------------------------------------------

    STATE_CODES = {"loading": 0, "serving": 1, "draining": 2, "wedged": 3,
                   "killed": 4}

    @property
    def state(self) -> str:
        """Lifecycle state for probes; wedged (engine watchdog fired)
        overrides the loading/serving/draining progression."""
        if self.engine is not None and getattr(self.engine, "wedged", False):
            return "wedged"
        return self._state

    async def health(self, request: web.Request) -> web.Response:
        # liveness: fail ONLY when a restart would help. Loading and
        # draining are healthy; a wedged device step is not, and neither
        # is a fault-killed replica (a crashed pod fails liveness too).
        if self.state in ("wedged", "killed"):
            return web.json_response(
                {"error": {"message": f"engine {self.state}",
                           "type": "service_unavailable"}},
                status=503)
        return web.Response(text="OK")

    async def ready(self, request: web.Request) -> web.Response:
        # readiness: only "serving" takes traffic. Non-200 while loading,
        # draining (preStop window) or wedged pulls the pod from Service
        # endpoints without restarting it.
        state = self.state
        from llms_on_kubernetes_tpu import faults
        flap = faults.get_float("flappy_replica", 1.0)
        if flap and state == "serving" and int(time.monotonic() / flap) % 2:
            # injected fault: readiness flaps while the engine keeps
            # serving — a replica repeatedly joining/leaving endpoints
            state = "draining"
        self.metrics["engine_state"].set(self.STATE_CODES.get(state, 0))
        if state == "serving":
            doc = {"state": state}
            pf = self._prefix_filter_doc()
            if pf is not None:
                doc["prefix_filter"] = pf
            return web.json_response(doc)
        return web.json_response(
            {"state": state,
             "error": {"message": f"not ready: {state}",
                       "type": "service_unavailable"}},
            status=503)

    def _prefix_filter_doc(self) -> Optional[dict]:
        """Serialized digest-membership filter for /ready piggybacking,
        rebuilt at most every ``_pf_interval`` seconds (the probe cycle is
        much faster than cache contents churn). None when the engine has
        no digest surface (stub engines in tests) or bits=0 disabled it —
        the /ready body then stays byte-identical to PR 17."""
        digests_fn = getattr(self.engine, "prefix_filter_digests", None)
        if digests_fn is None or self._pf_bits <= 0:
            return None
        now = time.monotonic()
        if (self._pf_doc is not None
                and now - self._pf_built < self._pf_interval):
            return self._pf_doc
        from llms_on_kubernetes_tpu.server.affinity import BloomFilter

        f = BloomFilter(self._pf_bits, self._pf_hashes)
        try:
            for d in digests_fn():
                f.add(d)
        except Exception:
            return self._pf_doc  # keep advertising the last good filter
        self._pf_doc = f.serialize()
        self._pf_built = now
        return self._pf_doc

    def _cache_digest_header(self, reqs) -> Optional[str]:
        """Canonical engine digest chain for the first request's prompt
        (n>1 fan-out shares one prompt), hex-joined for the
        ``X-LLMK-Cache-Digests`` response header. Same chain and same
        last-page cap as the handoff ticket — exactly what a returning
        identical prompt can adopt from this replica's caches."""
        fn = getattr(self.engine, "handoff_digests", None)
        alloc = getattr(self.engine, "allocator", None)
        if fn is None or alloc is None or not reqs:
            return None
        prompt = getattr(reqs[0], "prompt", None) or []
        n_pages = max(0, (len(prompt) - 1) // alloc.page_size)
        if n_pages <= 0:
            return None
        digests = fn(prompt[:n_pages * alloc.page_size],
                     salt=getattr(reqs[0], "cache_salt", b"") or b"")
        if not digests:
            return None
        return ",".join(d.hex() for d in digests[:CACHE_DIGESTS_MAX])

    # On-demand bounded profiling (SURVEY §5 tracing gap: the reference
    # exposed no profiling at all). POST /debug/profile captures a trace
    # of fixed duration on the LIVE engine — jax.profiler when it starts,
    # host-stack sampler otherwise — under the operator-configured
    # LLMK_PROFILE_DIR (never a caller-supplied path; the endpoint is on
    # the serving port). GET lists captures; GET /debug/profile/<id>
    # downloads one as .tar.gz for TensorBoard/XProf on a workstation.
    async def profile_capture(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except Exception:
            body = {}
        duration_ms = body.get("duration_ms", 500)
        if not isinstance(duration_ms, (int, float)) or duration_ms <= 0:
            return web.json_response(
                {"error": {"message": "duration_ms must be a positive "
                                      "number"}}, status=400)
        if getattr(self, "_profiling", False):
            return web.json_response(
                {"error": {"message": "manual profiler session running "
                                      "(/debug/profile/stop first)"}},
                status=409)
        try:
            # blocking capture runs off the event loop: streams keep
            # flowing, and that live traffic is what gets profiled
            meta = await asyncio.get_running_loop().run_in_executor(
                None, self.profiles.capture, float(duration_ms))
        except RuntimeError:
            return web.json_response(
                {"error": {"message": "capture already in progress"}},
                status=409)
        return web.json_response(meta)

    async def profile_list(self, request: web.Request) -> web.Response:
        return web.json_response({
            "dir": self.profiles.base_dir,
            "busy": self.profiles.busy or getattr(self, "_profiling", False),
            "captures": self.profiles.list_captures(),
        })

    async def profile_download(self, request: web.Request) -> web.Response:
        cap_id = request.match_info["capture_id"]
        data = self.profiles.open_archive(cap_id)
        if data is None:
            return web.json_response(
                {"error": {"message": f"no such capture: {cap_id}"}},
                status=404)
        return web.Response(
            body=data, content_type="application/gzip",
            headers={"Content-Disposition":
                     f'attachment; filename="{cap_id}.tar.gz"'})

    # Manual start/stop pair for traces that must span exactly the
    # traffic of interest (the bounded POST above is the common path).
    async def profile_start(self, request: web.Request) -> web.Response:
        import os

        import jax

        log_dir = os.environ.get("LLMK_PROFILE_DIR", "/tmp/jax-profile")
        if getattr(self, "_profiling", False) or self.profiles.busy:
            return web.json_response(
                {"error": {"message": "profiler already running"}}, status=409)
        try:
            jax.profiler.start_trace(log_dir)
        except Exception as e:  # profiler availability varies by platform
            return web.json_response(
                {"error": {"message": f"profiler unavailable: {e}"}}, status=501)
        self._profiling = True
        return web.json_response({"status": "profiling", "dir": log_dir})

    async def profile_stop(self, request: web.Request) -> web.Response:
        import jax

        if not getattr(self, "_profiling", False):
            return web.json_response(
                {"error": {"message": "profiler not running"}}, status=409)
        self._profiling = False
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            return web.json_response(
                {"error": {"message": f"stop failed: {e}"}}, status=500)
        return web.json_response({"status": "stopped"})

    @staticmethod
    def _int_query(request: web.Request, key: str, default: int) -> int:
        try:
            return int(request.query.get(key, default))
        except (TypeError, ValueError):
            return default

    async def debug_traces(self, request: web.Request) -> web.Response:
        """Recent completed request traces, newest first.

        ``?id=<request id>`` / ``?model=<name>`` filter; ``?limit=N`` caps
        the answer (default 50). Span times are milliseconds relative to
        the request's arrival at this server.
        """
        traces = self.traces.snapshot(
            request_id=request.query.get("id"),
            model=request.query.get("model"),
            limit=self._int_query(request, "limit", 50))
        return web.json_response({"traces": traces})

    async def debug_engine(self, request: web.Request) -> web.Response:
        """Engine flight recorder: the last N decode steps (step time,
        occupancy, KV pages, shed/preempted counts, token throughput) so a
        wedged or slow engine can be diagnosed post-hoc, and beside them
        the ledger's newest N dispatch records (what each device dispatch
        was, what it queued behind, how long the device held it, what the
        host was doing in the gap before it), and under ``"launch"`` what
        times the next decode step: the lead, the device time each kind
        and shape of dispatch last took, the launches by rule, and under
        ``"experts"`` the rows each expert of each expert layer got in
        the newest dispatch whose tokens were read, and ``"product"``:
        which grouped product ops/moe.py last chose, as its
        ``[attention] op=experts`` log line says it, and under
        ``"attention"`` what every dispatcher of ops/attention.py last
        chose at trace time, ``{op: "impl (why)"}`` (the same lines'
        ``op=prefill|chunk|decode|experts``: which kernel the steps this
        process compiled run, the latent decode kernel among them).
        ``?limit=N`` trims the first two to the most recent N."""
        limit = self._int_query(request, "limit", 0) or None
        snap = self.flight.snapshot(limit=limit)
        led = getattr(self.engine, "ledger", None)
        snap["dispatches"] = (led.dispatches_view(limit or 64)
                              if led is not None else [])
        launch_view = getattr(self.engine, "launch_view", None)
        if launch_view is not None:
            snap["launch"] = launch_view()
        # the newest booked dispatch's rows by expert layer and expert
        # (a decode window: its last live token step); None before one
        snap["experts"] = getattr(self.engine, "moe_last", None)
        from llms_on_kubernetes_tpu.ops import attention

        snap["attention"] = {op: f"{impl} ({why})" for op, (impl, why)
                             in sorted(attention._chosen.items())}
        snap["state"] = self.state
        snap["model"] = self.model_name
        snap["role"] = self.engine.config.role or "both"
        return web.json_response(snap)

    # ----- disaggregated prefill/decode handoff (router-internal) -----

    async def kv_fetch(self, request: web.Request) -> web.Response:
        """KV-page export for the disaggregated handoff: a decode replica
        POSTs ``{"tenant": ..., "digests": [hex, ...]}`` and gets back
        ``{"payloads": [...]}`` — position-matched, ``null`` for any page
        this replica's host tier no longer holds (evicted, never spilled,
        or the tier is off). Pages travel checksummed (see
        :func:`_encode_kv_payload`); the decode side treats a checksum
        mismatch like a missing page. A killed/wedged replica refuses, so
        the puller degrades to full re-prefill instead of hanging."""
        if self.state in ("killed", "wedged"):
            return web.json_response(
                {"error": {"message": f"replica {self.state}",
                           "type": "service_unavailable"}}, status=503)
        try:
            body = await request.json()
        except Exception:
            return web.json_response(
                {"error": {"message": "malformed JSON body"}}, status=400)
        raw = body.get("digests") if isinstance(body, dict) else None
        if (not isinstance(raw, list) or len(raw) > 4096
                or not all(isinstance(d, str) for d in raw)):
            return web.json_response(
                {"error": {"message": "digests must be a list of <= 4096 "
                           "hex strings"}}, status=400)
        try:
            digests = [bytes.fromhex(d) for d in raw]
        except ValueError:
            return web.json_response(
                {"error": {"message": "malformed digest hex"}}, status=400)
        tenant = str(body.get("tenant") or "")
        loop = asyncio.get_running_loop()
        payloads = await loop.run_in_executor(
            None, self.engine.host_kv_export, tenant, digests)
        return web.json_response({"payloads": [
            None if pl is None else _encode_kv_payload(pl)
            for pl in payloads]})

    async def _handoff_session_get(self):
        import aiohttp
        if self._handoff_session is None or self._handoff_session.closed:
            self._handoff_session = aiohttp.ClientSession()
        return self._handoff_session

    async def _handoff_pull(self, request: web.Request,
                            deadline: Optional[float],
                            trace=None) -> int:
        """Decode-side half of the handoff: pull the prefill replica's
        spilled pages (named by the router's digest header) into the local
        host tier and return how many landed. Every failure mode — fault
        injection, network error, source refusing, corrupt payload, chain
        gap — returns a smaller count, never raises: the request then
        re-prefills whatever wasn't adopted, degraded but correct."""
        from llms_on_kubernetes_tpu import faults
        src = request.headers.get(HANDOFF_SOURCE_HEADER, "").strip()
        src = src.rstrip("/")
        raw = request.headers.get(HANDOFF_DIGESTS_HEADER, "")
        try:
            digests = [bytes.fromhex(x.strip())
                       for x in raw.split(",") if x.strip()]
        except ValueError:
            digests = []
        if not src or not digests:
            return 0
        if faults.claim_n("drop_handoff"):
            # injected fault: the pull is skipped entirely — every
            # handed-off page "missing", forcing the counted re-prefill
            return 0
        if getattr(self.engine, "host_kv", None) is None:
            return 0
        import os

        import aiohttp
        budget = float(os.environ.get("LLMK_HANDOFF_PULL_TIMEOUT_S", "10"))
        if deadline is not None:
            budget = max(0.05, min(budget, deadline - time.monotonic()))
        tenant = request.headers.get(HANDOFF_TENANT_HEADER, "")
        # kv pull is a cross-replica hop of its own: carry a freshly
        # minted traceparent (and the distributed request id) so the
        # source replica's fetch fragment stitches under this leg
        hop_headers = {}
        rid = request.get("llmk_request_id")
        if rid:
            hop_headers[REQUEST_ID_HEADER] = rid
        pull_sid = ""
        if trace is not None:
            pull_sid = tracing.new_span_id()
            hop_headers[tracing.TRACEPARENT_HEADER] = \
                tracing.format_traceparent(trace.trace_id, pull_sid,
                                           trace.sampled)
        t_pull0 = time.monotonic()
        try:
            sess = await self._handoff_session_get()
            async with sess.post(
                    src + "/internal/kv/fetch",
                    json={"tenant": tenant,
                          "digests": [d.hex() for d in digests]},
                    headers=hop_headers,
                    timeout=aiohttp.ClientTimeout(total=budget)) as r:
                if r.status != 200:
                    return 0
                doc = await r.json()
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
                ValueError):
            return 0
        if trace is not None:
            trace.add_span("kv_pull", t_pull0, time.monotonic(),
                           span_id=pull_sid,
                           parent_span_id=trace.span_id, source=src)
        encs = doc.get("payloads") if isinstance(doc, dict) else None
        if not isinstance(encs, list):
            return 0
        landed = 0
        loop = asyncio.get_running_loop()
        for digest, enc in zip(digests, encs):
            if enc is None:
                break  # chain gap: pages after it are unreachable anyway
            pl = _decode_kv_payload(enc)
            if pl is None:
                break
            ok = await loop.run_in_executor(
                None, self.engine.host_kv_ingest, tenant, digest, pl)
            if not ok:
                break
            landed += 1
        return landed

    async def _handoff_ticket_response(self, req) -> web.Response:
        """Prefill-hop response: await the single-token prefill request
        and answer with a handoff ticket — the chained page digests plus
        the resolved seed — instead of a stream. The router re-issues the
        original body to a decode replica, which pulls those pages and
        regenerates the stream bit-identically from token zero."""
        reason = None
        try:
            while True:
                _toks, done, reason = await _next_event(req)
                if done:
                    break
        except asyncio.CancelledError:
            self.loop_thread.abort(req)
            raise
        if reason == "timeout" and not req.output:
            self.metrics["deadline_exceeded"].labels(phase="queue").inc()
            return web.json_response(
                {"error": {"message": "deadline expired during prefill",
                           "type": "timeout",
                           "code": "deadline_exceeded"}}, status=504)
        if reason not in ("length", "stop") and not req.output:
            # stalled / aborted / killed mid-prefill: the router retries
            # another prefill replica or falls back to colocated
            return web.json_response(
                {"error": {"message": f"prefill failed: {reason}",
                           "type": "service_unavailable",
                           "code": "handoff_prefill_failed"}},
                status=503, headers={"Retry-After": "1"})
        page = self.engine.allocator.page_size
        n_pages = max(0, (len(req.prompt) - 1) // page)
        digests = []
        if n_pages > 0:
            digests = self.engine.handoff_digests(
                req.prompt[:n_pages * page], salt=req.cache_salt or b"")
        doc = {
            "object": "llmk.handoff_ticket",
            "model": self._resp_model([req]),
            "prompt_tokens": len(req.prompt),
            "tenant": req.tenant,
            "seed": req.seed,
            "digests": [d.hex() for d in digests],
        }
        headers = {HANDOFF_TICKET_HEADER: "1"}
        chip = _chip_ms_total([req])
        if chip:
            doc["chip_ms"] = {ph: round(v, 3) for ph, v in chip.items()}
            headers[CHIP_MS_HEADER] = str(round(sum(chip.values()), 3))
        return web.json_response(doc, headers=headers)

    async def models(self, request: web.Request) -> web.Response:
        created = int(time.time())
        ids = [self.model_name]
        adp = getattr(self.engine, "adapters", None)
        if adp is not None:
            # each served LoRA adapter is addressable as its own model id
            ids += [f"{self.model_name}:{a}" for a in adp.names()]
        return web.json_response({
            "object": "list",
            "data": [{
                "id": mid,
                "object": "model",
                "created": created,
                "owned_by": "llms-on-kubernetes-tpu",
            } for mid in ids],
        })

    async def version(self, request: web.Request) -> web.Response:
        from llms_on_kubernetes_tpu import __version__

        return web.json_response({"version": __version__})

    async def embeddings(self, request: web.Request) -> web.Response:
        # explicit 501 (not a blank 404): the endpoint exists in the
        # OpenAI surface, this server just doesn't serve embedding models
        return web.json_response(
            {"error": {"message": "this server hosts a generative model; "
                       "/v1/embeddings is not supported",
                       "type": "not_implemented"}}, status=501)

    async def tokenize(self, request: web.Request) -> web.Response:
        """vllm-openai's POST /tokenize: {"prompt": str} or
        {"messages": [...]} -> {"tokens", "count", "max_model_len"}."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON"}}, status=400)
        prompt = body.get("prompt")
        messages = body.get("messages")
        try:
            if isinstance(prompt, str):
                ids = self.tokenizer.encode(prompt)
            elif isinstance(messages, list) and messages:
                ids = self.tokenizer.apply_chat_template(messages)
            else:
                return web.json_response(
                    {"error": {"message": "provide prompt (string) or "
                               "messages (list)"}}, status=400)
        except Exception as e:  # bad roles/content shape
            return web.json_response(
                {"error": {"message": f"bad input: {e}"}}, status=400)
        return web.json_response({
            "tokens": list(ids), "count": len(ids),
            "max_model_len": self.engine.config.max_model_len,
        })

    async def detokenize(self, request: web.Request) -> web.Response:
        """vllm-openai's POST /detokenize: {"tokens": [ids]} -> {"prompt"}."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON"}}, status=400)
        toks = body.get("tokens")
        if (not isinstance(toks, list)
                or any(not isinstance(t, int) or isinstance(t, bool)
                       for t in toks)):
            return web.json_response(
                {"error": {"message": "tokens must be a list of token ids"}},
                status=400)
        vocab = self.engine.model_config.vocab_size
        if any(not 0 <= t < vocab for t in toks):
            return web.json_response(
                {"error": {"message": f"token id outside the vocabulary "
                           f"(size {vocab})"}}, status=400)
        return web.json_response({"prompt": self.tokenizer.decode(toks)})

    async def prometheus(self, request: web.Request) -> web.Response:
        self.metrics["engine_state"].set(
            self.STATE_CODES.get(self.state, 0))
        # scrape-time freshness for device memory / live buffers
        self.telemetry.refresh()
        # MFU/MBU over the trailing minute of dispatches: a walk of the
        # ledger's ring, made for the reader and not after every step()
        led = getattr(self.engine, "ledger", None)
        util = led.utilization() if led is not None else None
        if util is not None:
            self.metrics["mfu"].set(util[0])
            self.metrics["mbu"].set(util[1])
        return web.Response(
            text=self.registry.render(),
            content_type="text/plain", charset="utf-8",
        )

    def _sampling_from_body(self, body: dict, *, chat: bool) -> SamplingParams:
        max_tokens = body.get("max_tokens") or body.get("max_completion_tokens") or 256
        eos = tuple(self.tokenizer.eos_ids)
        seed = body.get("seed")
        if seed is not None:
            if not isinstance(seed, int) or isinstance(seed, bool):
                raise ValueError("seed must be an integer")
            seed = seed & 0x7FFFFFFF  # engine seeds are int32
        # logprobs: completions takes an int (top-N per token); chat takes
        # a bool plus top_logprobs (0-20 per OpenAI; we cap at LOGPROB_TOPK)
        from llms_on_kubernetes_tpu.engine.sampling import LOGPROB_TOPK

        if chat:
            want = bool(body.get("logprobs", False))
            nlp = int(body.get("top_logprobs", 0) or 0) if want else 0
            if want and nlp == 0:
                nlp = 1  # chat logprobs:true alone still returns the chosen
        else:
            raw = body.get("logprobs")
            if raw is not None and (not isinstance(raw, int) or isinstance(raw, bool)):
                raise ValueError("logprobs must be an integer")
            if raw is not None and raw < 0:
                raise ValueError("logprobs must be non-negative")
            nlp = int(raw or 0)
            if raw is not None:
                nlp = max(nlp, 1)  # logprobs: 0 still returns token_logprobs
        if nlp < 0:
            raise ValueError("logprobs/top_logprobs must be non-negative")
        if nlp > LOGPROB_TOPK:
            raise ValueError(
                f"logprobs/top_logprobs supports at most {LOGPROB_TOPK} "
                f"alternatives, got {nlp}")
        # logit_bias: {"token_id": bias in [-100, 100]} (OpenAI); applied
        # on device every step. Entry count is bounded by the engine's
        # packed-row budget (LOGIT_BIAS_SLOTS; submit() enforces it).
        bias_items: list = []
        lb = body.get("logit_bias")
        if lb is not None:
            if not isinstance(lb, dict):
                raise ValueError("logit_bias must be an object mapping "
                                 "token ids to bias values")
            for k, v in lb.items():
                try:
                    tid = int(k)
                except (TypeError, ValueError):
                    raise ValueError(f"logit_bias key {k!r} is not a "
                                     f"token id")
                if not isinstance(v, (int, float)) or isinstance(v, bool):
                    raise ValueError(f"logit_bias value for {k} must be a "
                                     f"number")
                if not -100.0 <= float(v) <= 100.0:
                    raise ValueError("logit_bias values must be in "
                                     "[-100, 100]")
                bias_items.append((tid, float(v)))
        return SamplingParams(
            temperature=float(body.get("temperature", 1.0)),
            top_p=float(body.get("top_p", 1.0)),
            top_k=int(body.get("top_k", 0)),
            max_tokens=int(max_tokens),
            stop_token_ids=eos,
            seed=seed,
            presence_penalty=float(body.get("presence_penalty", 0.0)),
            frequency_penalty=float(body.get("frequency_penalty", 0.0)),
            logprobs=nlp,
            logit_bias=tuple(bias_items),
        )

    def _grammar_for_request(self, body: dict, tool_grammar):
        """Compile the request's decoding constraint, or None.

        BLOCKING (runs in an executor): grammar compilation is CPU-bound
        host work (~1s for the generic JSON grammar at a 128K vocab,
        cached per (grammar, vocab) after that — engine/grammar.py).
        ``tool_grammar`` is ``(tools, force_name_or_None)`` when
        tool_choice forces calls; it is exclusive with a JSON
        response_format (one token stream cannot satisfy both).
        Raises GrammarError (mapped to 400)."""
        from llms_on_kubernetes_tpu.engine.grammar import (
            GrammarError, compile_response_format, compile_tool_choice,
            token_bytes_of,
        )

        rf = body.get("response_format")
        rf_active = isinstance(rf, dict) and rf.get("type") not in (
            None, "text")
        if tool_grammar is not None and rf_active:
            raise GrammarError(
                "response_format json_object/json_schema cannot be combined "
                "with a forced tool_choice — the constrained token stream "
                "can only satisfy one")
        if tool_grammar is None and rf is None:
            return None
        with self._token_bytes_lock:
            if self._token_bytes is None:
                self._token_bytes = token_bytes_of(self.tokenizer)
        eos = sorted(self.tokenizer.eos_ids)
        if tool_grammar is not None:
            tools, force = tool_grammar
            return compile_tool_choice(tools, force, self._token_bytes, eos)
        return compile_response_format(rf, self._token_bytes, eos)

    def _decode_data_url(self, url: str, what: str):
        """data: URL -> loaded PIL image (400 on bad bytes)."""
        import base64
        import binascii
        import io

        from PIL import Image

        if not url.startswith("data:"):
            raise ValueError(
                f"{what} must be a data: URL (base64); the server does "
                f"not fetch remote media")
        try:
            img = Image.open(io.BytesIO(base64.b64decode(url.split(",", 1)[-1])))
            img.load()  # force decode NOW: bad bytes -> 400, not a 500 later
        except (OSError, binascii.Error, SyntaxError) as e:
            raise ValueError(f"undecodable {what} data: {e}")
        return img

    def _extract_video(self, part):
        """``video_url`` data URL (animated GIF/WebP/APNG — the formats
        PIL iterates; pre-extracted frames are the deployment contract,
        matching the reference's in-cluster no-egress stance) ->
        (frames [PIL], per-temporal-patch timestamps in seconds).

        Frames are uniformly sampled to LLMK_MAX_VIDEO_FRAMES (default 8
        = 4 temporal patches, the default per-request block budget) and
        trimmed to a temporal_patch_size multiple; timestamps follow the
        HF Qwen3-VL processor (mean of first/last frame time within each
        temporal patch, from the container's frame durations).

        Only the SAMPLED frames are materialized: animated containers
        compress highly, so eagerly retaining every decoded frame would
        let a 32 MB body expand to gigabytes of host RAM before the
        subsampling cap ran (untrusted-input availability risk). The
        frame count and size are checked against a total decoded-pixel
        budget (LLMK_MAX_VIDEO_PIXELS) up front — PIL must still walk
        earlier frames to composite deltas, so the budget bounds decode
        CPU as well as memory. Per-frame durations are clamped to
        [1 ms, 10 s]: they render as '<t seconds>' prompt text, and a
        container with zero/garbage duration metadata must not produce
        nonsensical timestamps."""
        import os

        import numpy as np

        vis = self.engine.model_config.vision
        if vis is None:  # text-only model: a 400, not an AttributeError 500
            raise ValueError(
                f"model {self.model_name!r} does not accept video input")
        tp = vis.temporal_patch_size
        img = self._decode_data_url(
            (part.get("video_url") or {}).get("url", ""), "video_url")
        n = int(getattr(img, "n_frames", 1))
        w, h = img.size
        # independent frame-count cap: the pixel budget alone would admit
        # a ~1M-frame GIF of 1x1 pixels, whose per-frame seek/composite
        # loop below still stalls the event loop for its duration
        max_frames = int(os.environ.get("LLMK_MAX_VIDEO_INPUT_FRAMES",
                                        "4096"))
        if n > max_frames:
            raise ValueError(
                f"video has {n} frames; at most {max_frames} are accepted "
                f"(frames are subsampled anyway — send fewer)")
        budget = int(os.environ.get("LLMK_MAX_VIDEO_PIXELS", str(1 << 28)))
        if n * w * h > budget:
            raise ValueError(
                f"video of {n} frames at {w}x{h} exceeds the decoded-pixel "
                f"budget ({budget}); send fewer/smaller frames")
        cap = max(tp, int(os.environ.get("LLMK_MAX_VIDEO_FRAMES", "8")))
        idx = np.linspace(0, n - 1, min(n, cap)).round().astype(int)
        want = set(idx.tolist())
        by_i, times_all, t = {}, [], 0.0
        for i in range(n):
            try:
                img.seek(i)
            except EOFError:  # container lied about n_frames
                break
            times_all.append(t)
            dur = img.info.get("duration")
            try:
                dur = float(dur) if dur else 1000.0 / 24.0
            except (TypeError, ValueError):
                dur = 1000.0 / 24.0
            t += min(max(dur, 1.0), 10_000.0) / 1000.0
            if i in want:
                by_i[i] = img.convert("RGB")
        idx = idx[idx < len(times_all)]
        frames = [by_i[i] for i in idx]
        times = [times_all[i] for i in idx]
        if not frames:
            raise ValueError("video contains no decodable frames")
        while len(frames) % tp:  # pad to a temporal-patch multiple
            frames.append(frames[-1])
            times.append(times[-1])
        ts = [(times[i] + times[i + tp - 1]) / 2
              for i in range(0, len(frames), tp)]
        return frames, ts

    def _extract_images(self, messages: list) -> tuple[list, list]:
        """OpenAI multimodal content parts -> (template-ready messages,
        decoded media). ``image_url`` / ``video_url`` parts accept data:
        URLs (base64); remote http(s) URLs are rejected — the serving pod
        must not fetch arbitrary URLs. Image parts become
        {"type": "image"} placeholders the model's chat template renders
        as its begin-of-image marker; a video becomes one
        ``<t seconds>`` text + image placeholder PER TEMPORAL PATCH (the
        Qwen3-VL prompt convention: timestamps carry time, every frame
        block behaves as an image) and contributes one ("video", frames)
        entry to the media list."""
        out, images = [], []
        for m in messages:
            content = m.get("content")
            if not isinstance(content, list):
                out.append(m)
                continue
            parts = []
            for part in content:
                ptype = part.get("type") if isinstance(part, dict) else None
                if ptype == "image_url":
                    images.append(self._decode_data_url(
                        (part.get("image_url") or {}).get("url", ""),
                        "image_url"))
                    parts.append({"type": "image"})
                elif ptype == "video_url":
                    frames, ts = self._extract_video(part)
                    for t in ts:
                        parts.append({"type": "text",
                                      "text": f"<{t:.1f} seconds>"})
                        parts.append({"type": "image"})
                    images.append(("video", frames))
                else:
                    parts.append(part)
            out.append({**m, "content": parts})
        return out, images

    def _splice_image_tokens(self, ids: list[int], n_images: int) -> list[int]:
        """Expand each begin-of-image marker into the soft-token run the
        engine substitutes embeddings at: boi -> [boi, soft * N, eoi].
        Placeholder soft tokens or an eoi the template already emitted
        after the marker are consumed (Qwen templates render
        <|vision_start|><|image_pad|><|vision_end|>; gemma templates
        render the begin marker alone)."""
        cfg = self.engine.model_config
        t_img = cfg.vision.mm_tokens_per_image
        out, found, i = [], 0, 0
        while i < len(ids):
            t = ids[i]
            out.append(t)
            i += 1
            if t == cfg.boi_token_id:
                found += 1
                out += [cfg.image_token_id] * t_img
                while i < len(ids) and ids[i] == cfg.image_token_id:
                    i += 1  # template's own placeholder(s): replaced
                if i < len(ids) and ids[i] == cfg.eoi_token_id:
                    i += 1
                if cfg.eoi_token_id is not None:
                    out.append(cfg.eoi_token_id)
        if found != n_images:
            raise ValueError(
                f"chat template produced {found} image markers for "
                f"{n_images} images")
        return out

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": {"message": "invalid JSON"}}, status=400)
        messages = body.get("messages")
        if not isinstance(messages, list) or not messages:
            return web.json_response(
                {"error": {"message": "messages must be a non-empty list"}}, status=400)
        try:
            messages, images = self._extract_images(messages)
        except ValueError as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        if images and self.engine.model_config.vision is None:
            return web.json_response(
                {"error": {"message": f"model {self.model_name!r} does not "
                           f"accept images"}}, status=400)
        # tools / tool_choice (the vllm-openai surface): schemas render
        # through the chat template; output is parsed for tool-call blocks
        from llms_on_kubernetes_tpu.server.tools import (
            inject_tool_messages, validate_tool_choice, validate_tools,
        )

        tools = body.get("tools")
        try:
            if tools is not None:
                tools = validate_tools(tools)
            tool_mode = validate_tool_choice(body.get("tool_choice"), tools)
        except ValueError as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        if tool_mode is not None:
            messages = inject_tool_messages(messages, tool_mode)
        try:
            # pass tools only when active: tools-unaware tokenizer
            # implementations (duck-typed TokenizerLike) keep working
            if tool_mode is not None and tools:
                prompt_ids = self.tokenizer.apply_chat_template(
                    messages, tools=tools)
            else:
                prompt_ids = self.tokenizer.apply_chat_template(messages)
            if images:
                vis = self.engine.model_config.vision
                n_blocks = sum(
                    len(e[1]) // vis.temporal_patch_size
                    if isinstance(e, tuple) and e[0] == "video" else 1
                    for e in images)
                prompt_ids = self._splice_image_tokens(prompt_ids, n_blocks)
        except Exception as e:  # bad roles/content shape
            return web.json_response({"error": {"message": f"bad messages: {e}"}}, status=400)
        pixels = None
        if images:
            import numpy as np

            from llms_on_kubernetes_tpu.models.vision import (
                preprocess_image, preprocess_image_qwen3vl,
            )

            vis = self.engine.model_config.vision
            try:
                pixels = []
                for entry in images:
                    if isinstance(entry, tuple) and entry[0] == "video":
                        if vis.family != "qwen3vl":
                            raise ValueError(
                                f"model {self.model_name!r} does not "
                                f"accept video input")
                        # every frame on the FIRST frame's grid (one
                        # dynamic-resolution choice per video)
                        pixels.append(np.stack([
                            preprocess_image_qwen3vl(f, vis)
                            for f in entry[1]]))
                    elif vis.family == "qwen3vl":
                        # dynamic resolution: aspect-preserving grids
                        pixels.append(preprocess_image_qwen3vl(entry, vis))
                    else:
                        pixels.append(preprocess_image(entry, vis.image_size))
            except ValueError as e:
                return web.json_response(
                    {"error": {"message": str(e)}}, status=400)
            except Exception as e:  # undecodable/degenerate image -> 400
                return web.json_response(
                    {"error": {"message": f"bad image: {e}"}}, status=400)
        # "required" / named-function forcing is grammar-GUARANTEED: the
        # sampled stream cannot be anything but well-formed tool calls
        # (auto mode stays parser-based — the model may answer in text).
        # Whether the request NAMED a function is judged from the body's
        # original shape, not the normalized string — a tool literally
        # called "required" or "auto" must not be mistaken for a mode.
        tool_grammar = None
        named = isinstance(body.get("tool_choice"), dict)
        if tool_mode is not None and (named or tool_mode == "required"):
            tool_grammar = (tools, tool_mode if named else None)
        return await self._serve(request, body, [prompt_ids], chat=True,
                                 images=pixels,
                                 tools_on=tool_mode is not None,
                                 tool_grammar=tool_grammar)

    async def completions(self, request: web.Request) -> web.StreamResponse:
        """Supports every OpenAI ``prompt`` form: a string, a token-id list,
        a list of strings, and a list of token-id lists (one choice each)."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": {"message": "invalid JSON"}}, status=400)
        prompt = body.get("prompt", "")
        if isinstance(prompt, list) and prompt and all(isinstance(t, int) for t in prompt):
            prompts: list[list[int]] = [list(prompt)]
        elif isinstance(prompt, list):
            prompts = []
            for p in prompt:
                if isinstance(p, str):
                    prompts.append(self.tokenizer.encode(p))
                elif isinstance(p, list) and all(isinstance(t, int) for t in p):
                    prompts.append(list(p))
                else:
                    return web.json_response(
                        {"error": {"message": "prompt list items must be strings "
                                   "or token-id lists"}}, status=400)
        elif isinstance(prompt, str):
            prompts = [self.tokenizer.encode(prompt)]
        else:
            return web.json_response(
                {"error": {"message": "prompt must be a string or list"}}, status=400)
        if not prompts or any(not p for p in prompts):
            return web.json_response({"error": {"message": "empty prompt"}}, status=400)
        return await self._serve(request, body, prompts, chat=False)

    # ------------------------------------------------------------------

    async def _serve(self, request, body, prompts, *, chat: bool,
                     images=None, tools_on: bool = False,
                     tool_grammar=None) -> web.StreamResponse:
        """Trace-managed wrapper around the serving path: every request —
        success, client error, or crash — leaves a completed trace in the
        /debug/traces ring and a one-line JSON access log with its id."""
        rid = request.get("llmk_request_id") or tracing.new_request_id()
        adapter = _adapter_from_model(body.get("model"))
        model_label = (f"{self.model_name}:{adapter}" if adapter
                       else self.model_name)
        ctx = request.get("llmk_trace_ctx") or {}
        trace = tracing.Trace(rid, model=model_label,
                              trace_id=ctx.get("trace_id", ""),
                              parent_span_id=ctx.get("parent_span_id", ""),
                              component="api",
                              sampled=bool(ctx.get("sampled", True)))
        trace.engine_reqs = []  # engine Requests serving this HTTP request
        status = "error"
        resp = None
        try:
            resp = await self._serve_inner(
                request, body, prompts, trace, chat=chat, images=images,
                tools_on=tools_on, tool_grammar=tool_grammar)
            status = "ok" if resp.status < 400 else f"http_{resp.status}"
            return resp
        finally:
            self._finalize_trace(trace, status, resp)

    def _finalize_trace(self, trace, status: str, resp) -> None:
        """Derive the request's span timeline from the engine Request
        timestamps (single writer each: submit/admit/first-token/last
        hand-over) and publish it. The phases are disjoint by
        construction, so their durations sum to at most the end-to-end
        latency; the ``prefill`` phase has four children
        (:meth:`_split_first_token`), the ``decode`` phase its parts as
        attributes and one child (:meth:`_split_decode`). ``decode`` ends
        and ``stream`` starts where the event that finishes the request
        left the engine (``last_token_at``; ``finished_at``, the slot's
        release, where there was no such hand-over)."""
        now = time.monotonic()
        many = len(trace.engine_reqs) > 1

        def eng_span(name, start, end, **meta):
            # every engine-phase window is a first-class child of this
            # process's fragment root, so the stitched cross-hop tree can
            # nest queue/prefill/decode under the exact router hop that
            # carried the request here
            trace.add_span(name, start, end, span_id=tracing.new_span_id(),
                           parent_span_id=trace.span_id, **meta)

        for i, req in enumerate(trace.engine_reqs):
            meta = {"choice": i} if many else {}
            sub = req.submitted_at
            adm = req.admitted_at
            ft = req.first_token_at
            fin = req.last_token_at
            if fin is None:
                fin = req.finished_at
            fin = now if fin is None else min(fin, now)
            eng_span("admission", trace.t0, sub, **meta)
            eng_span("queue", sub, adm if adm is not None else fin,
                     **meta)
            if adm is not None:
                pre_kw = dict(meta)
                if req.chip_ms:
                    # goodput-ledger attribution: device time this stream
                    # actually consumed, vs the wall-clock span bounds
                    pre_kw["chip_ms"] = round(
                        req.chip_ms.get("prefill", 0.0), 3)
                pre_id = tracing.new_span_id()
                trace.add_span("prefill", adm, ft if ft is not None else fin,
                               span_id=pre_id, parent_span_id=trace.span_id,
                               **pre_kw)
                self._split_first_token(trace, req, pre_id, meta)
            if ft is not None:
                dec_kw = dict(meta, tokens=len(req.output))
                if req.chip_ms:
                    dec_kw["chip_ms"] = round(
                        req.chip_ms.get("decode", 0.0), 3)
                    waste = (req.chip_ms.get("spec_waste", 0.0)
                             + req.chip_ms.get("early_exit", 0.0))
                    if waste:
                        dec_kw["chip_waste_ms"] = round(waste, 3)
                self._split_decode(trace, req, ft, max(fin, ft), dec_kw,
                                   meta)
            if fin < now:
                # engine finished before the response flushed: the tail is
                # stream/serialization time on the API side
                eng_span("stream", fin, now, **meta)
        trace.finish(status)
        self.traces.add(trace)
        tracing.jlog(
            "request", request_id=trace.request_id, component="api",
            model=trace.model, status=status,
            http_status=getattr(resp, "status", None),
            e2e_ms=round(trace.e2e_ms() or 0.0, 3),
            tokens=sum(len(r.output) for r in trace.engine_reqs))
        tracing.maybe_log_slow(trace, "api")
        self._export_trace(trace)

    @staticmethod
    def _split_first_token(trace, req, parent_id: str, meta: dict) -> None:
        """Four disjoint children of the ``prefill`` span that sum to it
        exactly, from the timestamps the request took off its prefill's
        dispatch record: ``prefill.pack`` (admission to launch: host KV
        commit, packing, enqueue), ``prefill.behind`` (launch to the
        device being free for it: the dispatches ahead), ``prefill.device``
        (to its result being complete on the device: the prefill itself,
        every dispatch of a chunked one) and ``prefill.emit`` (to
        ``first_token_at``, stamped where the token's event is put on the
        request's queue: the read, and the engine thread waking for it).
        Nothing without a ledger, or before the first token."""
        adm, ft = req.admitted_at, req.first_token_at
        launched, read = req.prefill_launched_at, req.prefill_read_at
        if ft is None or launched is None or read is None:
            return
        launched = min(max(launched, adm), ft)
        # not booked yet (a dispatch launched ahead of it is still unread):
        # it then waited behind nothing the ledger has seen
        started = req.prefill_started_at or launched
        read = min(max(read, launched), ft)
        started = min(max(started, launched), read)
        for name, start, end in (("prefill.pack", adm, launched),
                                 ("prefill.behind", launched, started),
                                 ("prefill.device", started, read),
                                 ("prefill.emit", read, ft)):
            trace.add_span(name, start, end, span_id=tracing.new_span_id(),
                           parent_span_id=parent_id, **meta)

    def _split_decode(self, trace, req, ft: float, end: float,
                      dec_kw: dict, meta: dict) -> None:
        """The ``decode`` span, ``first_token_at`` to the last hand-over,
        with where the request's token gap went as attributes in ms
        (GoodputLedger.decode_account: ``ride_ms`` the whole decode
        windows it consumed a token of, ``prefill_ms`` the prefill and
        chunk dispatches run between them, ``other_ms`` the rest up to
        its last window's completion on the device, ``idle_ms`` the idle
        part of that) and one child, ``decode.emit``: from that completion
        to the hand-over. The three and the child are disjoint and sum to
        the span. No attribute and no child without a ledger, or where
        its records no longer tell (none is guessed)."""
        led = getattr(self.engine, "ledger", None)
        acct = led.decode_account(req, ft, end) if led is not None else None
        dec_id = tracing.new_span_id()
        if acct is not None:
            dec_kw.update((part + "_ms", round(acct[part] * 1000.0, 3))
                          for part in ("ride", "prefill", "other", "idle"))
        trace.add_span("decode", ft, end, span_id=dec_id,
                       parent_span_id=trace.span_id, **dec_kw)
        if acct is not None:
            trace.add_span("decode.emit", acct["done"], end,
                           span_id=tracing.new_span_id(),
                           parent_span_id=dec_id, **meta)

    def _export_trace(self, trace) -> None:
        """Tail-sampling + OTLP enqueue for a finished fragment; never
        raises, and a non-exported trace is counted, never silent."""
        try:
            d = trace.to_dict()
            if self.exporter is None:
                self.metrics["trace_dropped"].labels(
                    reason="disabled").inc()
                return
            st = d.get("status") or ""
            error = st == "error" or st.startswith("http_5")
            keep, reason = self.tail_sampler.decide(
                error, d.get("e2e_ms"), tracing.is_multi_hop(d))
            if not keep:
                self.metrics["trace_dropped"].labels(reason=reason).inc()
                return
            self.exporter.export(d)
        except Exception:  # noqa: BLE001 — observability must not fail serving
            pass

    async def _serve_inner(self, request, body, prompts, trace, *,
                           chat: bool, images=None, tools_on: bool = False,
                           tool_grammar=None) -> web.StreamResponse:
        from llms_on_kubernetes_tpu.engine.engine import (
            EngineStallError, QueueFullError, UnknownAdapterError)
        from llms_on_kubernetes_tpu.engine.grammar import GrammarError

        if self.state in ("draining", "killed"):
            # draining: in-flight streams run to completion, NEW work is
            # refused so the client's retry lands on a live replica (the
            # router's probe loop has already seen /ready 503). killed: a
            # fault-injected prefill-pod crash — everything is refused.
            return web.json_response(
                {"error": {"message": f"server is {self.state}; not "
                           "accepting new requests",
                           "type": "service_unavailable",
                           "code": "shutting_down"}},
                status=503, headers={"Retry-After": "5"})
        deadline = _deadline_from(request, body)
        if deadline is not None and deadline <= time.monotonic():
            # expired before we touched the engine: never submitted, so
            # count it as a queue-phase shed (the client gave up already)
            self.metrics["deadline_exceeded"].labels(phase="queue").inc()
            return web.json_response(
                {"error": {"message": "deadline expired before processing",
                           "type": "timeout", "code": "deadline_exceeded"}},
                status=504)
        try:
            params = self._sampling_from_body(body, chat=chat)
        except (ValueError, TypeError) as e:  # bad seed/temperature/... -> 400
            return web.json_response({"error": {"message": str(e)}}, status=400)
        rf = body.get("response_format")
        rf_active = rf is not None and not (
            isinstance(rf, dict) and rf.get("type") in (None, "text"))
        if tool_grammar is not None or rf_active:
            # guided decoding (vllm-openai parity): response_format
            # json_object/json_schema and grammar-guaranteed tool forcing.
            # An explicit {"type": "text"} skips the executor hop (and the
            # first-use vocab byte-map derivation) entirely.
            try:
                grammar = await asyncio.get_running_loop().run_in_executor(
                    None, self._grammar_for_request, body, tool_grammar)
            except GrammarError as e:
                return web.json_response(
                    {"error": {"message": str(e)}}, status=400)
            if grammar is not None:
                params = dataclasses.replace(params, grammar=grammar)
        if not chat and body.get("suffix"):
            return web.json_response(
                {"error": {"message": "suffix (fill-in-middle) is not "
                           "supported by this model server"}}, status=400)
        want_prompt_scores = bool(
            not chat and body.get("echo") and params.logprobs)
        if want_prompt_scores and body.get("stream"):
            # the streamed logprobs protocol has no slot for prompt-token
            # entries; silently omitting them is exactly the partial
            # logprobs block the round-2 advisor rejected
            return web.json_response(
                {"error": {"message": "echo with logprobs cannot be "
                           "streamed; use stream=false"}}, status=400)
        n = body.get("n", 1)
        if not isinstance(n, int) or isinstance(n, bool) or not 1 <= n <= 16:
            return web.json_response(
                {"error": {"message": "n must be an integer in [1, 16]"}},
                status=400)
        # best_of: sample that many candidates per prompt server-side,
        # return the n highest-mean-logprob ones (non-streaming only)
        best_of = body.get("best_of", n) if not chat else n
        if not isinstance(best_of, int) or isinstance(best_of, bool) or best_of < n:
            return web.json_response(
                {"error": {"message": "best_of must be an integer >= n"}},
                status=400)
        if best_of > 16:
            return web.json_response(
                {"error": {"message": "best_of must be <= 16"}}, status=400)
        if best_of > n and body.get("stream"):
            return web.json_response(
                {"error": {"message": "best_of > n cannot be streamed"}},
                status=400)
        raw_resume = request.headers.get(RESUME_TOKENS_HEADER)
        if raw_resume is not None:
            # internal resume replay (router splice): continue a stream a
            # dead replica started. Only single-choice streams are
            # journaled/resumable; the replay is idempotent — the same
            # prefix + seed deterministically yields the same continuation.
            if not body.get("stream") or n != 1 or best_of != 1 \
                    or len(prompts) != 1:
                return web.json_response(
                    {"error": {"message": "stream resume requires a "
                               "single-choice streaming request"}}, status=400)
            try:
                prefix = tuple(int(t) for t in raw_resume.split(",")
                               if t.strip())
            except ValueError:
                return web.json_response(
                    {"error": {"message": f"malformed {RESUME_TOKENS_HEADER} "
                               "header"}}, status=400)
            if prefix:
                params = dataclasses.replace(params, prefix_tokens=prefix)
        stops = _parse_stops(body)
        adapter = _adapter_from_model(body.get("model"))
        # per-tenant QoS identity (mirrors the router's resolution): the
        # body's `user` else the requested model string. The priority
        # header is the router's RESOLVED value (it strips the client's);
        # direct clients may set it too — invalid values fall through to
        # the engine's per-tenant config/default.
        tenant = tenant_of(body, self.model_name)
        raw_prio = request.headers.get(PRIORITY_HEADER)
        priority = (raw_prio.strip().lower()
                    if raw_prio is not None
                    and raw_prio.strip().lower() in PRIORITIES else None)
        # --- disaggregated two-hop serving (router-internal headers) ---
        # Decode hop: the router re-issues the ORIGINAL body here with the
        # prefill replica's resolved seed, so this fresh request samples
        # bit-identically to a colocated one; the pulled pages below make
        # its prefill a host-tier hit instead of recompute.
        raw_hseed = request.headers.get(HANDOFF_SEED_HEADER)
        if raw_hseed is not None and params.seed is None:
            try:
                params = dataclasses.replace(
                    params, seed=int(raw_hseed) & 0x7FFFFFFF)
            except ValueError:
                pass  # malformed internal header: still correct, new seed
        # Prefill hop: answer with a handoff ticket instead of a stream.
        # Ineligible shapes DECLINE by serving normally — the router sent
        # the journal header too, so a declined ticket degrades to an
        # ordinary relayable stream, never an error.
        want_ticket = (
            request.headers.get(HANDOFF_HEADER, "").strip().lower()
            == "ticket"
            and raw_resume is None and len(prompts) == 1
            and n == 1 and best_of == 1
            and getattr(self.engine, "host_kv", None) is not None)
        if want_ticket:
            # prompt ingestion only: one sampled token proves the prefill
            # completed, and submit(handoff=True) drains the spilled pages
            # to the host tier eagerly so the decode pull never races
            params = dataclasses.replace(params, max_tokens=1)
        elif request.headers.get(HANDOFF_SOURCE_HEADER):
            adopted = await self._handoff_pull(request, deadline,
                                               trace=trace)
            request["llmk_handoff_adopted"] = adopted
        # best_of choices per prompt (prompt-major choice order, per
        # OpenAI); usage counts each UNIQUE prompt once, not n times
        loop = asyncio.get_running_loop()
        reqs = []
        try:
            for prompt_ids in prompts:
                for j in range(best_of):
                    p = params
                    if best_of > 1 and params.seed is not None and j > 0:
                        # a fixed seed would make the choices identical —
                        # derive a distinct (still deterministic) seed each
                        p = dataclasses.replace(
                            params, seed=(params.seed + j) & 0x7FFFFFFF)
                    q: asyncio.Queue = asyncio.Queue()
                    # the engine request carries the distributed request id
                    # (suffixed per choice so engine-side ids stay unique)
                    eng_id = (trace.request_id if len(prompts) * best_of == 1
                              else f"{trace.request_id}/{len(reqs)}")
                    req = self.loop_thread.submit(
                        prompt_ids, p, on_event=_event_pusher(loop, q),
                        images=images, deadline=deadline, request_id=eng_id,
                        adapter=adapter, tenant=tenant, priority=priority,
                        handoff=want_ticket)
                    req.trace = trace
                    trace.engine_reqs.append(req)
                    req._aq = q
                    reqs.append(req)
        except UnknownAdapterError as e:
            # 404, not a silent base-model fallback: a typo'd adapter name
            # must never be served the base model's (different) weights
            for r in reqs:
                self.loop_thread.abort(r)
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error",
                           "code": "adapter_not_found"}}, status=404)
        except EngineStallError as e:
            for r in reqs:
                self.loop_thread.abort(r)
            return web.json_response(
                {"error": {"message": str(e), "type": "service_unavailable",
                           "code": "engine_stalled"}},
                status=503, headers={"Retry-After": "30"})
        except QueueFullError as e:
            for r in reqs:
                self.loop_thread.abort(r)
            # Retry-After from the actual backlog — queue depth times the
            # observed step time — so a saturated replica says "come back
            # when the queue has drained" instead of inviting a thundering
            # herd at 1 s intervals. Shares the rate limiter's clamp
            # (server/qos.py retry_after_s) but carries a DISTINCT error
            # code: overloaded = the server's capacity, rate_limited = the
            # tenant's own contract — clients back off differently.
            est = len(self.engine.waiting) * max(self.engine._est_step, 1e-3)
            prio_label = priority or dict(
                self.engine.config.qos_priorities).get(
                    tenant, self.engine.config.qos_default_priority)
            self.metrics["tenant_shed"].labels(
                tenant=tenant, priority=prio_label,
                reason="overloaded").inc()
            return web.json_response(
                {"error": {"message": str(e), "type": "rate_limit_exceeded",
                           "code": "overloaded"}},
                status=429,
                headers={"Retry-After": str(retry_after_s(est))})
        except ValueError as e:
            for r in reqs:
                self.loop_thread.abort(r)
            return web.json_response({"error": {"message": str(e)}}, status=400)

        if want_ticket:
            return await self._handoff_ticket_response(reqs[0])

        rid = ("chatcmpl-" if chat else "cmpl-") + uuid.uuid4().hex[:24]
        created = int(time.time())
        if raw_resume is not None:
            # the spliced continuation must be indistinguishable from the
            # original stream: reuse its SSE id and created stamp
            sid = request.headers.get(RESUME_STREAM_ID_HEADER, "")
            if sid and len(sid) <= 128 and sid.isprintable():
                rid = sid
            raw_created = request.headers.get(RESUME_CREATED_HEADER, "")
            if raw_created.isdigit():
                created = int(raw_created)
        if body.get("stream"):
            include_usage = bool(
                (body.get("stream_options") or {}).get("include_usage"))
            return await self._stream_response(
                request, reqs, rid, created, chat, stops, params.logprobs,
                include_usage, prompts, tools_on=tools_on)
        prompt_scores = None
        if want_prompt_scores:
            # echo+logprobs: per-position PROMPT logprobs (first entry
            # null, OpenAI semantics) via the cache-free scoring forward —
            # runs concurrently with the generation already in flight
            loop = asyncio.get_running_loop()
            try:
                prompt_scores = [
                    await loop.run_in_executor(
                        None, self.engine.score_prompt, p)
                    for p in prompts]
            except ValueError as e:  # e.g. sequence-parallel serving
                for r in reqs:
                    self.loop_thread.abort(r)
                return web.json_response(
                    {"error": {"message": str(e)}}, status=400)
            except BaseException:
                # scoring died some other way (device OOM, cancellation):
                # the generations already submitted must not keep burning
                # decode slots with nobody reading their events
                for r in reqs:
                    self.loop_thread.abort(r)
                raise
        return await self._full_response(
            reqs, rid, created, chat, prompts, stops, params.logprobs,
            n, best_of, echo=bool(body.get("echo")) and not chat,
            tools_on=tools_on, prompt_scores=prompt_scores)

    async def _drain(self, req, stops):
        """Async generator over one request's events: yields
        ``(text_delta, done, finish_reason, tokens_so_far, lp_entries,
        raw_tokens)``.

        Single source of truth for stop-token filtering, incremental
        detokenization, stop-sequence matching, and early abort — consumed
        by both the streaming and non-streaming paths. ``tokens_so_far``
        counts event tokens deterministically (``req.output`` may still be
        growing on the engine thread after an abort). ``lp_entries`` pairs
        each VISIBLE token id with its recorded (logprob, top_ids,
        top_logprobs) tuple. ``raw_tokens`` is the event's UNFILTERED token
        id list (stop tokens included) — what the router's resume journal
        must record.
        """
        detok = IncrementalDetokenizer(self.tokenizer)
        stopper = StopChecker(stops)
        stop_ids = set(req.params.stop_token_ids)
        nlp = req.params.logprobs
        total = 0
        pending: list = []   # entries whose text the stopper still holds back
        released_chars = 0   # emitted chars covered by released entries
        prefix = list(req.params.prefix_tokens or ())
        if prefix:
            # Resume replay: the prefix tokens' text was already delivered
            # to the client by the replica that died. Warm the detokenizer
            # and stop checker with them so continuation deltas splice
            # byte-exactly after what the client has: cumulative emitted
            # chars are a pure function of the cumulative token ids, so
            # ``stopper.emitted`` lands exactly where the dead replica's
            # stream left off (regardless of how it chunked its writes).
            warm_text, warm_hit = stopper.push(
                detok.push(prefix, final=False), final=False)
            del warm_text
            total = len(prefix)
            released_chars = stopper.emitted
            if warm_hit:
                # the prefix itself completes a stop sequence — the
                # original stream was ending anyway; finish cleanly
                self.loop_thread.abort(req)
                yield "", True, "stop", total, [], []
                return
        from llms_on_kubernetes_tpu import faults
        jitter_ms = faults.get_float("net_jitter", 25.0)
        self._maybe_claim_degraded()
        t_last = time.monotonic()
        while True:
            toks, done, reason = await _next_event(req)
            # injected gray-failure faults, applied between the engine
            # event and its delivery so probes/health stay untouched:
            # degraded_replica stretches THIS replica's event pacing by
            # (factor-1)x the real inter-event time (slow HBM/thermal
            # throttle in miniature); net_jitter adds 0..MS ms of random
            # delay on EVERY replica sharing the env (latency noise the
            # outlier detector's floors must not trip on)
            if self._degraded_factor > 1.0:
                await asyncio.sleep((time.monotonic() - t_last)
                                    * (self._degraded_factor - 1.0))
            if jitter_ms is not None and jitter_ms > 0:
                import random
                await asyncio.sleep(random.uniform(0.0, jitter_ms / 1000.0))
            t_last = time.monotonic()
            start = total
            total += len(toks)
            # exclude trailing stop token from visible text (OpenAI behavior)
            raw_entries = [
                (t, req.output_logprobs[start + i]
                 if start + i < len(req.output_logprobs) else None)
                for i, t in enumerate(toks)
                if not (done and reason == "stop" and t in stop_ids)
            ]
            if nlp == 0:
                # no logprobs wanted: one batched detok push per event (the
                # per-token variant below re-decodes the id list per token)
                text, hit = stopper.push(
                    detok.push([t for t, _ in raw_entries], final=done),
                    final=done)
                if hit:
                    self.loop_thread.abort(req)
                    yield text, True, "stop", total, [], toks
                    return
                yield text, done, reason, total, [], toks
                if done:
                    return
                continue
            # logprobs path. Per-token text comes from the detokenizer's
            # ACTUAL emitted deltas (one id pushed at a time), not from
            # decode([tid]) in isolation — a mid-UTF-8/BPE token decodes to
            # a replacement char alone, which would drift the stop-cut and
            # text_offset accounting (round-2 advisor finding). Entries are
            # RELEASED only once the stopper emits their text, so streamed
            # logprobs never outrun a stop truncation that lands later.
            # entries: (token_id, logprob_data, emitted_text_piece)
            delta_parts = []
            for i, (t, lp) in enumerate(raw_entries):
                piece = detok.push([t], final=done and i == len(raw_entries) - 1)
                delta_parts.append(piece)
                pending.append((t, lp, piece))
            if done and not raw_entries:
                delta_parts.append(detok.push([], final=True))
            text, hit = stopper.push("".join(delta_parts), final=done)
            released = []
            while pending:
                t, lp, piece = pending[0]
                if released_chars + len(piece) > stopper.emitted:
                    break  # text still held back (or beyond a stop cut)
                released.append(pending.pop(0))
                released_chars += len(piece)
            if hit and pending and released_chars < stopper.emitted:
                # the stop cut lands MID-token: part of this entry's text
                # is in the final visible output, so its logprob entry is
                # included (truncation rule: every token that contributed
                # visible characters appears in the logprobs; tokens
                # entirely beyond the cut do not) — round-3 advisor finding
                released.append(pending.pop(0))
            if hit:
                self.loop_thread.abort(req)
                yield text, True, "stop", total, released, toks
                return
            yield text, done, reason, total, released, toks
            if done:
                return

    async def _consume(self, req, stops) -> tuple[str, Optional[str], int, list]:
        parts: list[str] = []
        finish_reason, total = None, 0
        entries: list = []
        async for text, done, reason, total, evs, _toks in self._drain(
                req, stops):
            parts.append(text)
            entries += evs
            if done:
                finish_reason = reason
        return "".join(parts), finish_reason, total, entries

    # -- logprob response shaping --------------------------------------

    def _resp_model(self, reqs) -> str:
        """Response ``model`` field: echoes ``base:adapter`` for LoRA
        requests (all choices of one HTTP request share the adapter)."""
        a = getattr(reqs[0], "adapter", None) if reqs else None
        return f"{self.model_name}:{a}" if a else self.model_name

    def _tok_str(self, tid: int) -> str:
        return self.tokenizer.decode([tid])

    def _chat_logprobs(self, entries, nlp: int) -> dict:
        # the chosen token's text is its EMITTED piece (self-consistent
        # with the response text even across multi-byte/BPE merges);
        # alternatives can only be decoded in isolation
        content = []
        for tid, lp, piece in entries:
            if lp is None:
                continue
            chosen_lp, top_ids, top_lps = lp
            content.append({
                "token": piece,
                "logprob": chosen_lp,
                "bytes": list(piece.encode("utf-8")),
                "top_logprobs": [
                    {"token": self._tok_str(i), "logprob": l,
                     "bytes": list(self._tok_str(i).encode("utf-8"))}
                    for i, l in zip(top_ids[:nlp], top_lps[:nlp])
                ],
            })
        return {"content": content}

    def _completion_logprobs(self, entries, nlp: int, base_offset: int) -> dict:
        tokens, token_logprobs, top_logprobs, text_offset = [], [], [], []
        offset = base_offset
        # token strings and text_offset both come from each token's
        # EMITTED piece (the detokenizer's actual delta), so
        # response_text[text_offset[i]:][:len(tokens[i])] == tokens[i]
        # holds exactly, even across multi-byte/BPE merges
        for tid, lp, piece in entries:
            if lp is None:
                offset += len(piece)
                continue
            chosen_lp, top_ids, top_lps = lp
            tokens.append(piece)
            token_logprobs.append(chosen_lp)
            top_logprobs.append(
                {self._tok_str(i): l
                 for i, l in zip(top_ids[:nlp], top_lps[:nlp])})
            text_offset.append(offset)
            offset += len(piece)
        return {"tokens": tokens, "token_logprobs": token_logprobs,
                "top_logprobs": top_logprobs, "text_offset": text_offset}

    def _prompt_logprob_block(self, prompt_ids, score, nlp: int) -> dict:
        """OpenAI prompt-logprobs block for ``echo``: entry i scores
        prompt token i (null for the first token — nothing conditions
        it). Pieces come from the incremental detokenizer so offsets and
        token strings stay self-consistent across BPE merges."""
        lps, top_ids, top_lps = score
        detok = IncrementalDetokenizer(self.tokenizer)
        tokens, token_logprobs, top_logprobs, text_offset = [], [], [], []
        offset = 0
        for i, tid in enumerate(prompt_ids):
            piece = detok.push([tid], final=i == len(prompt_ids) - 1)
            tokens.append(piece)
            if i == 0:
                token_logprobs.append(None)
                top_logprobs.append(None)
            else:
                token_logprobs.append(float(lps[i - 1]))
                top_logprobs.append(
                    {self._tok_str(t): float(l)
                     for t, l in zip(top_ids[i - 1][:nlp],
                                     top_lps[i - 1][:nlp])})
            text_offset.append(offset)
            offset += len(piece)
        return {"tokens": tokens, "token_logprobs": token_logprobs,
                "top_logprobs": top_logprobs, "text_offset": text_offset}

    async def _full_response(self, reqs, rid, created, chat, prompts, stops,
                             nlp: int, n: int, best_of: int,
                             echo: bool, tools_on: bool = False,
                             prompt_scores=None) -> web.Response:
        per_prompt = best_of  # reqs are prompt-major groups of best_of
        results = []
        completion_tokens = 0
        try:
            for i, req in enumerate(reqs):
                text, finish_reason, ntok, entries = await self._consume(req, stops)
                completion_tokens += ntok
                results.append((i // per_prompt, text, finish_reason, entries))
        except asyncio.CancelledError:
            # client went away mid-generation: free slots/pages now
            for r in reqs:
                self.loop_thread.abort(r, "disconnect")
            raise

        if any(r[2] == "stalled" for r in results):
            # the engine watchdog shed this request: the device step it was
            # riding never completed. A non-streaming client gets a clean
            # 503 (a retry may land on a healthy replica) instead of a
            # truncated completion masquerading as success.
            return web.json_response(
                {"error": {"message": "engine stalled while generating; "
                           "request was aborted",
                           "type": "service_unavailable",
                           "code": "engine_stalled"}},
                status=503, headers={"Retry-After": "30"})

        if results and all(r[2] == "timeout" and not r[1] for r in results):
            # every choice hit its end-to-end deadline before producing a
            # single token: there is no useful partial output, so answer
            # with the same 504 the router would have produced. (Any choice
            # WITH partial text falls through to a 200 whose finish_reason
            # is "timeout" — the client sees what was generated in budget.)
            return web.json_response(
                {"error": {"message": "deadline exceeded before any output "
                           "was generated", "type": "timeout",
                           "code": "deadline_exceeded"}},
                status=504)

        if best_of > n:
            # keep the n best candidates per prompt by mean token logprob;
            # a degenerate EMPTY completion must never win (its mean would
            # otherwise score 0.0, beating every real candidate)
            def score(entry_list):
                lps = [lp[0] for _, lp, _ in entry_list if lp is not None]
                return sum(lps) / len(lps) if lps else float("-inf")
            kept = []
            for g in range(len(prompts)):
                group = [r for r in results if r[0] == g]
                group.sort(key=lambda r: score(r[3]), reverse=True)
                kept += group[:n]
            results = kept

        choices = []
        prompt_blocks: dict = {}
        for i, (g, text, finish_reason, entries) in enumerate(results):
            if chat:
                message = {"role": "assistant", "content": text}
                if tools_on:
                    from llms_on_kubernetes_tpu.server.tools import (
                        ToolStreamParser,
                    )

                    parser = ToolStreamParser()
                    content, _ = parser.push(text, final=True)
                    if parser.calls:
                        message["content"] = content or None
                        message["tool_calls"] = parser.calls
                        if finish_reason == "stop":
                            finish_reason = "tool_calls"
                choice = {
                    "index": i,
                    "message": message,
                    "finish_reason": finish_reason,
                }
                if nlp:
                    choice["logprobs"] = self._chat_logprobs(entries, nlp)
            else:
                echo_text = self.tokenizer.decode(prompts[g]) if echo else ""
                choice = {"index": i, "text": echo_text + text,
                          "finish_reason": finish_reason}
                if nlp:
                    lp = self._completion_logprobs(
                        entries, nlp, len(echo_text))
                    if prompt_scores is not None:
                        if g not in prompt_blocks:  # once per prompt, not
                            prompt_blocks[g] = self._prompt_logprob_block(
                                prompts[g], prompt_scores[g], nlp)
                        pb = prompt_blocks[g]       # per n/best_of choice
                        lp = {k: pb[k] + lp[k] for k in lp}
                    choice["logprobs"] = lp
            choices.append(choice)
        prompt_tokens = sum(len(p) for p in prompts)
        usage = {
            "prompt_tokens": prompt_tokens,
            "completion_tokens": completion_tokens,
            "total_tokens": prompt_tokens + completion_tokens,
        }
        chip = _chip_ms_total(reqs)
        if chip:
            usage["chip_ms"] = {ph: round(v, 3) for ph, v in chip.items()}
        resp = web.json_response({
            "id": rid, "object": "chat.completion" if chat else "text_completion",
            "created": created, "model": self._resp_model(reqs),
            "choices": choices, "usage": usage,
        })
        if chip:
            resp.headers[CHIP_MS_HEADER] = str(round(sum(chip.values()), 3))
        cd = self._cache_digest_header(reqs)
        if cd:
            resp.headers[CACHE_DIGESTS_HEADER] = cd
        return resp

    async def _stream_response(self, request, reqs, rid, created, chat, stops,
                               nlp: int = 0, include_usage: bool = False,
                               prompts=None,
                               tools_on: bool = False) -> web.StreamResponse:
        from llms_on_kubernetes_tpu import faults

        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "X-Accel-Buffering": "no",
            },
        )
        rid_header = request.get("llmk_request_id")
        if rid_header:
            # set before prepare(): the middleware cannot add headers to an
            # already-prepared streaming response
            resp.headers[REQUEST_ID_HEADER] = rid_header
        adopted = request.get("llmk_handoff_adopted")
        if adopted is not None:
            # decode hop of a disaggregated request: how many handed-off
            # pages actually landed — the router counts 0-with-digests as
            # a degraded (re-prefill) handoff, never a client error
            resp.headers[HANDOFF_ADOPTED_HEADER] = str(adopted)
        cd = self._cache_digest_header(reqs)
        if cd:
            # set before prepare() like the ids above: the router learns
            # this stream's key→digest chain for cache-aware re-routing
            resp.headers[CACHE_DIGESTS_HEADER] = cd
        await resp.prepare(request)
        obj = "chat.completion.chunk" if chat else "text_completion"
        resp_model = self._resp_model(reqs)
        write_lock = asyncio.Lock()
        completion_tokens = 0
        # router-internal stream-resume protocol (headers documented at the
        # module constants): journal comments only when the router asked,
        # and only single-choice streams are journaled — the router marks
        # anything else non-resumable
        journal_on = (JOURNAL_HEADER in request.headers) and len(reqs) == 1
        resumed = RESUME_TOKENS_HEADER in request.headers
        # LLMK_FAULT=kill_mid_stream[:N]: one-shot (claim) — the first
        # in-process stream to deliver N tokens severs its client socket
        # abruptly, simulating a replica death mid-generation
        kill_after = faults.get_float("kill_mid_stream", 8.0)

        def chunk(index: int, delta_text: Optional[str], reason: Optional[str],
                  role: bool = False, entries=None, base_offset: int = 0,
                  tool_deltas=None) -> bytes:
            if chat:
                delta: dict = {}
                if role:
                    delta["role"] = "assistant"
                if delta_text is not None:
                    delta["content"] = delta_text
                if tool_deltas:
                    delta["tool_calls"] = tool_deltas
                choice = {"index": index, "delta": delta, "finish_reason": reason}
                if nlp and entries:
                    choice["logprobs"] = self._chat_logprobs(entries, nlp)
            else:
                choice = {"index": index, "text": delta_text or "", "finish_reason": reason}
                if nlp and entries:
                    choice["logprobs"] = self._completion_logprobs(
                        entries, nlp, base_offset)
            payload = {
                "id": rid, "object": obj, "created": created,
                "model": resp_model, "choices": [choice],
            }
            return f"data: {json.dumps(payload)}\n\n".encode()

        async def pump(index: int, req) -> None:
            """Relay one request's tokens as SSE chunks (choices interleave
            across requests; the write lock keeps individual events intact)."""
            nonlocal completion_tokens
            if chat and not resumed:
                # a resumed splice continues an existing client stream;
                # the role delta was already delivered by the original
                async with write_lock:
                    await resp.write(chunk(index, None, None, role=True))
            tool_parser = None
            if tools_on and chat:
                from llms_on_kubernetes_tpu.server.tools import ToolStreamParser

                tool_parser = ToolStreamParser()
            n_calls = 0
            total = 0
            tok_chars = 0  # cumulative offsets across the WHOLE stream
            signalled = False  # any chunk written for this choice yet
            async for text, done, reason, total, entries, raw_toks in \
                    self._drain(req, stops):
                tool_deltas = None
                if tool_parser is not None:
                    # tool-call blocks are cut out of the content stream;
                    # each completed block becomes ONE tool_calls delta
                    # carrying the full id/name/arguments (OpenAI clients
                    # accept whole-call deltas; finish_reason flips below)
                    text, new_calls = tool_parser.push(text, final=done)
                    if new_calls:
                        tool_deltas = []
                        for c in new_calls:
                            tool_deltas.append({"index": n_calls, "id": c["id"],
                                                "type": c["type"],
                                                "function": c["function"]})
                            n_calls += 1
                async with write_lock:
                    # a chunk is due when there is text OR logprob entries —
                    # entries for tokens whose text is still held back
                    # (partial UTF-8, stop-sequence window) must not be lost
                    if text or tool_deltas or (nlp and entries):
                        await resp.write(chunk(index, text or None, None,
                                               entries=entries,
                                               base_offset=tok_chars,
                                               tool_deltas=tool_deltas))
                        signalled = True
                        if nlp:
                            tok_chars += sum(len(p) for _, _, p in entries)
                    elif not signalled and not done:
                        # first token arrived but its text is held back
                        # (mid-UTF-8 sequence / stop-sequence window): emit
                        # ONE empty delta so the client's time-to-first-
                        # chunk tracks the engine's first token, not the
                        # holdback's resolution a decode step later
                        await resp.write(chunk(index, "", None))
                        signalled = True
                    if done:
                        if (tool_parser is not None and tool_parser.calls
                                and reason == "stop"):
                            reason = "tool_calls"
                        await resp.write(chunk(index, None, reason))
                    if journal_on and raw_toks:
                        # AFTER the event's data writes — the splice
                        # invariant (see JOURNAL_HEADER): a journaled
                        # token implies its emitted text was delivered
                        await resp.write(
                            (": llmk-tok "
                             + ",".join(str(t) for t in raw_toks)
                             + "\n\n").encode())
                if (kill_after is not None and total >= kill_after
                        and faults.claim("kill_mid_stream")):
                    # simulated replica death mid-generation: sever the
                    # socket abruptly (RST) so the router sees a broken
                    # stream and exercises its journal resume/truncation
                    for r in reqs:
                        self.loop_thread.abort(r, "kill_mid_stream")
                    if request.transport is not None:
                        request.transport.abort()
                    return
            completion_tokens += total

        keepalive_task = None
        keep_s = _keepalive_interval_s()
        if keep_s > 0:
            async def _keepalive() -> None:
                # SSE comment heartbeat: long prefills/queue waits produce
                # no data chunks, and idle-timeout LBs reap quiet streams;
                # clients and the router ignore/relay comments transparently
                while True:
                    await asyncio.sleep(keep_s)
                    async with write_lock:
                        await resp.write(b": ping\n\n")

            keepalive_task = asyncio.get_running_loop().create_task(
                _keepalive())
        try:
            await asyncio.gather(*(pump(i, r) for i, r in enumerate(reqs)))
            if include_usage:
                prompt_tokens = sum(len(p) for p in (prompts or []))
                usage = {"prompt_tokens": prompt_tokens,
                         "completion_tokens": completion_tokens,
                         "total_tokens": prompt_tokens + completion_tokens}
                chip = _chip_ms_total(reqs)
                if chip:
                    usage["chip_ms"] = {
                        ph: round(v, 3) for ph, v in chip.items()}
                await resp.write(
                    f"data: {json.dumps({'id': rid, 'object': obj, 'created': created, 'model': resp_model, 'choices': [], 'usage': usage})}\n\n".encode())
            await resp.write(b"data: [DONE]\n\n")
        except (ConnectionResetError, asyncio.CancelledError):
            # client went away: cancel generation so slots/pages free up now
            for r in reqs:
                self.loop_thread.abort(r, "disconnect")
            raise
        finally:
            if keepalive_task is not None:
                keepalive_task.cancel()
        await resp.write_eof()
        return resp


def run_server(
    engine: Engine,
    tokenizer: TokenizerLike,
    model_name: str,
    host: str = "0.0.0.0",
    port: int = 8080,
) -> None:
    server = OpenAIServer(engine, tokenizer, model_name)
    # handler_cancellation: client disconnects must cancel non-streaming
    # handlers so the abort path frees decode slots (aiohttp defaults False)
    web.run_app(server.make_app(), host=host, port=port, print=None,
                handler_cancellation=True)
