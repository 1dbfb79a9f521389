"""Payload-inspecting multi-model API gateway (router).

Reproduces the routing semantics of the reference's OpenResty/Lua gateway
(reference vllm-models/helm-chart/templates/model-gateway.yaml:29-86,
SURVEY §3.1) with its defects fixed:

- ``GET /v1/models`` is answered AT THE GATEWAY, synthesizing the model list
  from config — no backend is consulted (model-gateway.yaml:29-49).
- ``POST`` bodies are JSON-decoded; ``body["model"]`` is EXACT-matched
  against the configured model names; no/unknown model falls back to the
  default backend (model-gateway.yaml:51-75). Unlike the reference's silent
  fallback, ``strict=True`` turns unknown models into a 404 with an
  OpenAI-style error, and the non-strict fallback is logged + counted
  (``llm_router_unknown_model_fallback_total``) so misrouted traffic is
  visible.
- ``GET /health`` -> 200 "OK" (model-gateway.yaml:84-86).
- Everything else is proxied to the selected backend **streaming**, chunk
  by chunk — the reference's Python gateway buffered entire responses and
  broke SSE (api-gateway.yaml:99); this one never buffers.
- 502 with a JSON error on upstream failure (api-gateway.yaml:100-104).

Fault tolerance (the layer the pulled vLLM image got from its ingress for
free, SURVEY §5 / ISSUE 1 + ISSUE 2):

- each model maps to a **replica set** (one or more upstream base URLs),
  balanced with power-of-two-choices over the healthy members;
- a **per-replica circuit breaker**: after ``breaker_threshold``
  consecutive transport failures the replica is OPEN for
  ``breaker_open_s`` seconds, then one half-open probe decides close vs
  re-open; a request is 503'd only when every replica is open;
- optional active background ``GET /ready`` **health probes**
  (``probe_interval_s``) eject replicas that are unreachable or report
  503 (the engine's ``draining``/``wedged`` states) and re-admit them
  when they recover, exported as ``llm_replica_healthy{model,replica}``;
- per-request **connect/read timeouts** (connect default 5 s, sock-read
  default 120 s between chunks, total default 300 s);
- **bounded retries** with exponential backoff + jitter, only on
  connect-phase failures (no response head received yet — the request
  body is fully buffered, so a resend cannot double-apply). A retry
  prefers a *different* healthy replica (failover, counted in
  ``llm_failover_total``) and fails over immediately; only a retry
  against the same replica backs off. Read-phase failures are never
  resent.
- an **end-to-end deadline**: ``X-LLMK-Deadline-Ms`` (or a ``timeout``
  body field, in seconds) carries the client's remaining budget; the
  router rejects already-expired requests with 504 and forwards the
  decremented budget so the server/engine can shed doomed work;
- consistent OpenAI-style error JSON for every gateway-generated failure.

A native C++ implementation with identical semantics lives in
native/router/ for the OpenResty-equivalent deployment; this Python one is
the local-path/default router and the executable spec both are tested
against.
"""

from __future__ import annotations

import asyncio
import json
import math
import os
import random
import time
from typing import Optional, Union

import aiohttp
from aiohttp import web

from llms_on_kubernetes_tpu import faults
from llms_on_kubernetes_tpu.server import affinity, outlier, tracing
from llms_on_kubernetes_tpu.server.cluster_metrics import (
    SLOTracker, merge_expositions, slo_gauges,
)
from llms_on_kubernetes_tpu.server.metrics import (
    Registry, build_info_metrics, router_metrics,
)
from llms_on_kubernetes_tpu.server.qos import (
    PRIORITY_HEADER, QoSGate, default_token_charge,
)
from llms_on_kubernetes_tpu.server.tracing import REQUEST_ID_HEADER, jlog

DEADLINE_HEADER = "X-LLMK-Deadline-Ms"

# Stream-resume protocol (router <-> API server, internal). The router adds
# JOURNAL_HEADER to streaming completion requests; the API then follows each
# SSE event's data with a ``: llmk-tok <ids>`` comment naming the event's raw
# token ids, which the router journals and strips. When the upstream dies
# mid-stream, the router re-issues the request to another replica with
# RESUME_TOKENS_HEADER carrying the journaled ids (plus the original SSE
# stream id/created stamp) and splices the continuation into the client's
# stream. Comment-AFTER-data ordering is the correctness invariant: a
# journaled token implies all its emitted text was already relayed, so the
# continuation can never skip text the client is missing — at worst it
# replays a little, which the router drops (the echo).
JOURNAL_HEADER = "X-LLMK-Journal"
RESUME_TOKENS_HEADER = "X-LLMK-Resume-Tokens"
RESUME_STREAM_ID_HEADER = "X-LLMK-Resume-Stream-Id"
RESUME_CREATED_HEADER = "X-LLMK-Resume-Created"

# Disaggregated prefill/decode two-hop protocol (router <-> API server,
# internal). The router sends a streaming completion to a prefill-role
# replica with ``X-LLMK-Handoff: ticket``; the replica runs chunked prompt
# ingestion only, spills the prompt's full KV pages to its host tier, and
# answers with a JSON handoff ticket (marked by the response header
# ``X-LLMK-Handoff-Ticket``) carrying the page digests, host-tier tenant
# key, and the resolved sampling seed. The router then re-issues the
# ORIGINAL body to a decode-role replica with the Source/Digests/Tenant/
# Seed headers; that replica pulls the pages from the prefill replica's
# ``/internal/kv/fetch``, lands them in its own host tier, and serves the
# request from scratch — admission adopts the pulled pages, the seed makes
# the sampled stream bit-identical to colocated serving, and the client
# sees one ordinary SSE stream (journal/resume engages normally for any
# later mid-stream death). ``X-LLMK-Handoff-Adopted`` on the decode
# response reports how many pages were adopted (0 with digests offered =
# the counted degraded re-prefill).
HANDOFF_HEADER = "X-LLMK-Handoff"
HANDOFF_SOURCE_HEADER = "X-LLMK-Handoff-Source"
HANDOFF_DIGESTS_HEADER = "X-LLMK-Handoff-Digests"
HANDOFF_TENANT_HEADER = "X-LLMK-Handoff-Tenant"
HANDOFF_SEED_HEADER = "X-LLMK-Handoff-Seed"
HANDOFF_TICKET_HEADER = "X-LLMK-Handoff-Ticket"
HANDOFF_ADOPTED_HEADER = "X-LLMK-Handoff-Adopted"

# Cache-aware routing (router <-> API server, internal): every completion
# response carries the canonical engine digest chain of the prompt's full
# pages on this header. The router caches the chain per affinity key,
# matches it against the digest-membership filters replicas piggyback on
# their /ready bodies, and steers returning sessions to the replica whose
# caches actually hold the chain (server/affinity.py is the executable
# spec; the native router mirrors it on tests/data/affinity_vectors.json).
CACHE_DIGESTS_HEADER = "X-LLMK-Cache-Digests"

HOP_BY_HOP = {
    "connection", "keep-alive", "proxy-authenticate", "proxy-authorization",
    "te", "trailers", "transfer-encoding", "upgrade", "host",
    "content-length",
}

# Connect-phase failures: the upstream never produced a response head, so
# the (fully buffered) request is safe to resend. Read-phase failures after
# the head arrives are NOT in this set — they are relayed/terminated, never
# retried (the upstream may have executed the request).
RETRYABLE_ERRORS = (
    aiohttp.ClientConnectionError,   # incl. ClientConnectorError, ServerDisconnectedError
    ConnectionResetError,
    asyncio.TimeoutError,
)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_json(name: str) -> Optional[dict]:
    """A JSON-object env var (the outlier/budget config blocks ride the
    env as JSON strings, like LLMK_QOS); junk or non-objects are None."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        doc = json.loads(raw)
    except ValueError:
        return None
    return doc if isinstance(doc, dict) else None


def error_body(message: str, type_: str, code: str = "") -> dict:
    body = {"error": {"message": message, "type": type_}}
    if code:
        body["error"]["code"] = code
    return body


class _StreamJournal:
    """Per-stream resume journal for a relayed SSE completion stream.

    Records the token ids the client has effectively received (from the
    API's ``: llmk-tok`` comments, which are stripped before forwarding)
    and the count of content chars actually forwarded. On an upstream
    death the journal is everything needed to splice a continuation:

    - ``tokens``        -> ``X-LLMK-Resume-Tokens`` for the re-issue;
    - ``chars - chars_at_mark`` -> the replayed echo to drop: chars the
      client received for tokens the journal missed (possible because the
      tok comment follows its data). The resumed replica regenerates
      those tokens deterministically and re-emits their text, which
      ``feed`` trims from the continuation (``echo_skip``).

    Bounded: past ``max_tokens`` journaled ids the stream is marked
    non-resumable (a resume needs the COMPLETE prefix, so a dropping ring
    would be useless — overflow just flips the stream back to the
    truncation path). Text itself is never buffered, only counted.
    """

    _TOK = b": llmk-tok"

    def __init__(self, max_tokens: int = 4096):
        self.max_tokens = max_tokens
        self.tokens: list[int] = []
        self.chars = 0           # content chars forwarded to the client
        self.chars_at_mark = 0   # self.chars when the last tok comment landed
        self.saw_data = False    # any data: chunk forwarded yet
        self.done = False        # "data: [DONE]" forwarded: stream complete
        self.finished = False    # a choice carried a finish_reason
        self.overflow = False
        self.not_resumable: Optional[str] = None
        self.stream_id: Optional[str] = None
        self.created: Optional[int] = None
        self.echo_skip = 0       # replayed-echo chars still to drop
        self._buf = b""

    def feed(self, data: bytes) -> bytes:
        """Digest upstream bytes; return what to forward downstream.

        Complete lines only — a trailing partial line is held until its
        newline arrives, so journal state never runs behind forwarded
        text and a spliced continuation never lands mid-line.
        """
        self._buf += data
        out = bytearray()
        while True:
            nl = self._buf.find(b"\n")
            if nl < 0:
                break
            line = self._buf[:nl + 1]
            self._buf = self._buf[nl + 1:]
            kept = self._line(line)
            if kept is not None:
                out += kept
        return bytes(out)

    def _line(self, line: bytes) -> Optional[bytes]:
        s = line.strip()
        if s.startswith(self._TOK):
            try:
                ids = [int(x) for x in s[len(self._TOK):].split(b",")
                       if x.strip()]
            except ValueError:
                ids = []
            self.tokens += ids
            if len(self.tokens) > self.max_tokens:
                self.overflow = True
            self.chars_at_mark = self.chars
            return None  # internal comment: never reaches the client
        if not s.startswith(b"data:"):
            return line  # keepalives, blank lines, "event:" fields, ...
        payload = s[5:].strip()
        if payload == b"[DONE]":
            self.done = True
            return line
        try:
            doc = json.loads(payload)
            if not isinstance(doc, dict):
                raise ValueError("non-object data chunk")
        except (ValueError, UnicodeDecodeError):
            self.not_resumable = "unparseable data chunk"
            self.saw_data = True
            return line
        return self._data(doc, line)

    def _data(self, doc: dict, line: bytes) -> Optional[bytes]:
        self.saw_data = True
        if self.stream_id is None and isinstance(doc.get("id"), str):
            self.stream_id = doc["id"]
            if isinstance(doc.get("created"), int):
                self.created = doc["created"]
        content: Optional[str] = None
        content_key = None
        choices = doc.get("choices")
        for ch in choices if isinstance(choices, list) else []:
            if not isinstance(ch, dict):
                continue
            if ch.get("index", 0) != 0:
                self.not_resumable = "multi-choice stream"
            if ch.get("finish_reason"):
                self.finished = True
            if ch.get("logprobs"):
                # prefix logprob data is unrecoverable on another replica
                self.not_resumable = "logprobs stream"
            delta = ch.get("delta")
            if isinstance(delta, dict):
                if delta.get("tool_calls"):
                    self.not_resumable = "tool-call stream"
                c = delta.get("content")
                key = ("delta", "content")
            else:
                c = ch.get("text")
                key = ("text",)
            if isinstance(c, str) and ch.get("index", 0) == 0:
                content, content_key = c, (ch, key)
        if content:
            if self.echo_skip > 0:
                # a resumed upstream deterministically regenerated tokens
                # the client already has text for: trim the duplicate
                drop = min(self.echo_skip, len(content))
                self.echo_skip -= drop
                content = content[drop:]
                ch, key = content_key
                if len(key) == 2:
                    ch[key[0]][key[1]] = content
                else:
                    ch[key[0]] = content
                line = b"data: " + json.dumps(doc).encode() + b"\n"
            self.chars += len(content)
        return line

    def flush(self) -> bytes:
        """Held-back trailing bytes (a stream that ended without a final
        newline); forward them verbatim once the upstream EOFs cleanly."""
        tail, self._buf = self._buf, b""
        return tail

    def resumable(self) -> tuple[bool, str]:
        """May this stream be spliced onto another replica right now?"""
        if self.done:
            return False, "stream already complete"
        if self.overflow:
            return False, f"journal overflow (> {self.max_tokens} tokens)"
        if self.not_resumable:
            return False, self.not_resumable
        return True, ""


class CircuitBreaker:
    """Per-replica consecutive-failure breaker (closed → open → half-open).

    ``allow()`` gates requests; callers report outcomes via
    ``record_success``/``record_failure``. While OPEN every request is
    rejected until ``open_s`` elapses; then exactly one probe is admitted
    (half-open) and its outcome closes or re-opens the circuit. The clock
    is injectable so tests can drive the state machine deterministically.
    """

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half-open"

    def __init__(self, threshold: int = 5, open_s: float = 10.0,
                 clock=time.monotonic):
        self.threshold = max(1, threshold)
        self.open_s = open_s
        self.clock = clock
        self.state = self.CLOSED
        self.failures = 0
        self.opened_at = 0.0
        self._probe_started: Optional[float] = None

    def blocked(self) -> bool:
        """Non-mutating peek: would ``allow()`` reject right now?

        Used for replica *selection* so that considering a candidate does
        not consume its half-open probe slot.
        """
        now = self.clock()
        if self.state == self.OPEN:
            return now - self.opened_at < self.open_s
        if self.state == self.HALF_OPEN:
            return (self._probe_started is not None
                    and now - self._probe_started < self.open_s)
        return False

    def allow(self) -> bool:
        now = self.clock()
        if self.state == self.OPEN:
            if now - self.opened_at < self.open_s:
                return False
            self.state = self.HALF_OPEN
            self._probe_started = None
        if self.state == self.HALF_OPEN:
            # one probe at a time; a stuck probe frees the slot after open_s
            if (self._probe_started is not None
                    and now - self._probe_started < self.open_s):
                return False
            self._probe_started = now
        return True

    def record_success(self) -> None:
        self.state = self.CLOSED
        self.failures = 0
        self._probe_started = None

    def record_failure(self) -> None:
        self.failures += 1
        if self.state == self.HALF_OPEN or self.failures >= self.threshold:
            self.state = self.OPEN
            self.opened_at = self.clock()
            self._probe_started = None

    def retry_after_s(self) -> float:
        return max(0.0, self.open_s - (self.clock() - self.opened_at))


class Replica:
    """One upstream of a model's replica set, with its routing state."""

    def __init__(self, model: str, url: str, breaker: CircuitBreaker,
                 role: str = "both"):
        self.model = model
        self.url = url                 # base URL, no trailing slash
        self.breaker = breaker
        self.role = role               # prefill | decode | both
        self.healthy = True            # last active-probe verdict
        self.inflight = 0              # requests currently relayed through it

    def __repr__(self) -> str:
        return f"Replica({self.model!r}, {self.url!r})"


def _normalize_backends(
        backends: "dict[str, Union[str, list[str]]]") -> dict[str, list[str]]:
    """Accept both the legacy name→url and the name→[urls] config shapes."""
    out: dict[str, list[str]] = {}
    for name, urls in backends.items():
        if isinstance(urls, str):
            urls = [urls]
        urls = [u.rstrip("/") for u in urls if u]
        if not urls:
            raise ValueError(f"model {name!r} has an empty replica list")
        out[name] = urls
    return out


class Router:
    def __init__(
        self,
        backends: "dict[str, Union[str, list[str]]]",
        default_model: Optional[str] = None,
        strict: bool = False,
        adapters: Optional[dict] = None,
        upstream_timeout: float = 300.0,
        connect_timeout: float = 5.0,
        read_timeout: float = 120.0,
        retry_attempts: int = 3,
        retry_backoff_s: float = 0.2,
        breaker_threshold: int = 5,
        breaker_open_s: float = 10.0,
        probe_interval_s: Optional[float] = None,
        probe_timeout_s: float = 2.0,
        probe_path: str = "/ready",
        stream_resume: Optional[bool] = None,
        resume_attempts: Optional[int] = None,
        hedge_ms: Optional[float] = None,
        journal_max_tokens: int = 4096,
        qos: Optional[dict] = None,
        roles: Optional[dict] = None,
        handoff_retries: Optional[int] = None,
        outlier_ejection: Optional[dict] = None,
        retry_budget: Optional[dict] = None,
        prefix_affinity: Optional[dict] = None,
        tracing_cfg: Optional[dict] = None,
        clock=time.monotonic,
    ):
        """backends: model name -> base URL or list of replica base URLs.

        ``probe_interval_s=None`` disables the active health prober (the
        default for embedded/test use); ``run_router`` enables it.
        """
        if not backends:
            raise ValueError("router needs at least one backend")
        self.backends = _normalize_backends(backends)
        self.default_model = default_model or next(iter(self.backends))
        if self.default_model not in self.backends:
            raise ValueError(f"default model {self.default_model!r} not in backends")
        self.strict = strict
        # model -> LoRA adapter names its replicas serve; requests address
        # them as model="base:adapter" (multi-tenant serving)
        self.adapters: dict[str, list[str]] = {}
        for mname, names in (adapters or {}).items():
            if mname not in self.backends:
                raise ValueError(
                    f"adapters configured for unknown model {mname!r}")
            self.adapters[mname] = sorted({str(a) for a in names})
        self.timeout = aiohttp.ClientTimeout(
            total=upstream_timeout, connect=connect_timeout,
            sock_read=read_timeout,
        )
        self.retry_attempts = max(1, retry_attempts)
        self.retry_backoff_s = retry_backoff_s
        # mid-stream failover (journal + splice): LLMK_STREAM_RESUME
        # (default on), capped at LLMK_RESUME_ATTEMPTS re-issues per
        # stream; hedged first-byte requests via LLMK_HEDGE_MS (default
        # off). Constructor args override the env for embedded/test use.
        if stream_resume is None:
            stream_resume = os.environ.get(
                "LLMK_STREAM_RESUME", "1").strip().lower() not in (
                    "0", "false", "off", "no", "")
        self.stream_resume = bool(stream_resume)
        if resume_attempts is None:
            resume_attempts = _env_int("LLMK_RESUME_ATTEMPTS", 2)
        self.resume_attempts = max(0, resume_attempts)
        if hedge_ms is None:
            hedge_ms = _env_float("LLMK_HEDGE_MS", 0.0)
        self.hedge_ms = max(0.0, hedge_ms)
        # disaggregated serving: replica URL -> serving role. A model with
        # BOTH a prefill and a decode replica gets the two-hop flow for
        # streaming completions; everything else serves colocated.
        self.roles: dict[str, str] = {
            str(u).rstrip("/"): str(r) for u, r in (roles or {}).items()}
        if handoff_retries is None:
            handoff_retries = _env_int("LLMK_HANDOFF_RETRIES", 2)
        self.handoff_retries = max(1, handoff_retries)
        self.journal_max_tokens = max(1, journal_max_tokens)
        self.probe_interval_s = probe_interval_s
        self.probe_timeout_s = probe_timeout_s
        self.probe_path = probe_path
        self.clock = clock
        self.registry = Registry()
        self.metrics = router_metrics(self.registry)
        build_info_metrics(self.registry, backend="python-router",
                           role="router")
        # a labeled counter with no children exports no samples: pre-seed
        # every handoff outcome so rate() and the dashboard panels see an
        # explicit 0 before the first disaggregated request
        for oc in ("ok", "retried", "reprefill", "fallback_colocated"):
            self.metrics["handoff"].labels(outcome=oc)
        # sliding-window SLO over proxied outcomes (llm_slo_* gauges read
        # it at scrape time); objectives from LLMK_SLO_* env vars
        self.slo = SLOTracker()
        slo_gauges(self.registry, self.slo)
        # per-tenant QoS: rate limits + priority resolution + brownout
        # (server/qos.py is the executable spec; the native router
        # mirrors it). An empty/missing config leaves the gate dormant.
        self.qos_gate = QoSGate(qos, clock=clock)
        self.scrape_timeout_s = 5.0
        self.traces = tracing.TraceStore(
            int(os.environ.get("LLMK_TRACE_RING", "256")))
        # cross-hop tracing: tail sampler + OTLP exporter. Config (from
        # router.json "tracing") overrides env; no endpoint anywhere ⇒
        # the exporter stays dormant and drops are counted "disabled".
        tcfg = dict(tracing_cfg or {})
        self.tracing_cfg = tcfg

        def _cfg_float(key):
            v = tcfg.get(key)
            try:
                return float(v) if v is not None else None
            except (TypeError, ValueError):
                return None

        self.tail_sampler = tracing.TailSampler(
            sample=_cfg_float("sample"), slow_ms=_cfg_float("tailSlowMs"))
        endpoint = str(tcfg.get("otlpEndpoint")
                       or os.environ.get(tracing.OTLP_ENDPOINT_ENV,
                                         "")).strip()
        self.exporter: Optional[tracing.OtlpExporter] = None
        if endpoint:
            self.exporter = tracing.OtlpExporter(
                endpoint, service_name="llmk-router",
                exported=self.metrics["trace_spans_exported"],
                dropped=self.metrics["trace_dropped"])
        # per-replica state; breakers indexed by replica URL for inspection
        self.replicas: dict[str, list[Replica]] = {}
        self.breakers: dict[str, CircuitBreaker] = {}
        for name, urls in self.backends.items():
            reps = []
            for url in urls:
                breaker = self.breakers.get(url)
                if breaker is None:
                    breaker = self.breakers[url] = CircuitBreaker(
                        breaker_threshold, breaker_open_s, clock)
                rep = Replica(name, url, breaker,
                              role=self.roles.get(url, "both"))
                reps.append(rep)
                self.metrics["replica_healthy"].labels(
                    model=name, replica=url, role=rep.role).set(1)
            self.replicas[name] = reps
        # models with at least one prefill AND one decode replica use the
        # two-hop handoff flow for streaming completions
        self._disagg: dict[str, bool] = {
            name: {"prefill", "decode"} <= {r.role for r in reps}
            for name, reps in self.replicas.items()}
        # gray-failure layer (server/outlier.py is the executable spec;
        # the native router mirrors it): latency/error outlier quarantine
        # plus the per-model retry budget every retry source draws from.
        # Both stay dormant unless configured.
        self.outlier_cfg = outlier.OutlierConfig(
            outlier_ejection if outlier_ejection is not None
            else _env_json("LLMK_OUTLIER"))
        self.retry_budget_cfg = outlier.RetryBudgetConfig(
            retry_budget if retry_budget is not None
            else _env_json("LLMK_RETRY_BUDGET"))
        self.outliers: dict[str, outlier.OutlierDetector] = {}
        self.retry_budgets: dict[str, outlier.RetryBudget] = {}
        if self.outlier_cfg.enabled:
            for reason in ("latency", "errors"):
                self.metrics["outlier_ejections"].labels(reason=reason)
            for name, reps in self.replicas.items():
                self.outliers[name] = outlier.OutlierDetector(
                    self.outlier_cfg, clock=clock)
                for rep in reps:
                    for reason in ("latency", "errors"):
                        self.metrics["quarantined"].labels(
                            model=name, replica=rep.url,
                            reason=reason).set(0)
        if self.retry_budget_cfg.enabled:
            for name in self.backends:
                self.retry_budgets[name] = outlier.RetryBudget(
                    self.retry_budget_cfg, clock=clock)
        # prefix-affinity + cache-aware routing (server/affinity.py is the
        # executable spec; the native router mirrors it byte-for-byte on
        # tests/data/affinity_vectors.json). Dormant unless configured —
        # pick decisions stay pure P2C and probes ignore filter payloads.
        self.affinity_cfg = affinity.AffinityConfig(
            prefix_affinity if prefix_affinity is not None
            else _env_json("LLMK_AFFINITY"))
        self.affinity_digests = affinity.KeyDigestCache(
            self.affinity_cfg.key_cache)
        # replica URL -> last adopted /ready filter and its clock stamp
        self._filters: dict[str, affinity.BloomFilter] = {}
        self._filter_at: dict[str, float] = {}
        if self.affinity_cfg.enabled:
            for name in self.backends:
                self.metrics["affinity_hits"].labels(model=name)
                for reason in (affinity.FALLBACK_UNHEALTHY,
                               affinity.FALLBACK_QUARANTINED,
                               affinity.FALLBACK_OVERLOADED,
                               affinity.FALLBACK_MISS):
                    self.metrics["affinity_fallback"].labels(
                        model=name, reason=reason)
        self._session: Optional[aiohttp.ClientSession] = None
        self._probe_task: Optional[asyncio.Task] = None

    def make_app(self) -> web.Application:
        app = web.Application()
        app.router.add_get("/health", self.health)
        app.router.add_get("/metrics", self.metrics_endpoint)
        app.router.add_get("/metrics/cluster", self.metrics_cluster)
        app.router.add_get("/debug/traces", self.debug_traces)
        app.router.add_get("/debug/trace/{trace_id}", self.debug_trace)
        app.router.add_get("/debug/replicas", self.debug_replicas)
        app.router.add_get("/v1/models", self.models)
        app.router.add_route("*", "/{path:.*}", self.proxy)
        app.on_startup.append(self._startup)
        app.on_cleanup.append(self._cleanup)
        return app

    async def _startup(self, app) -> None:
        self._session = aiohttp.ClientSession(timeout=self.timeout)
        if self.probe_interval_s:
            self._probe_task = asyncio.get_event_loop().create_task(
                self._probe_loop())

    async def _cleanup(self, app) -> None:
        if self._probe_task:
            self._probe_task.cancel()
            try:
                await self._probe_task
            except asyncio.CancelledError:
                pass
            self._probe_task = None
        if self._session:
            await self._session.close()
        if self.exporter is not None:
            self.exporter.close()

    # ------------------------------------------------------------------
    # active health probing

    async def _probe_loop(self) -> None:
        while True:
            await asyncio.sleep(self.probe_interval_s)
            await self.probe_all()

    async def probe_all(self) -> None:
        """One probe sweep over every replica (also callable from tests)."""
        await asyncio.gather(*(
            self._probe_one(rep)
            for reps in self.replicas.values() for rep in reps
        ), return_exceptions=True)

    async def _probe_one(self, rep: Replica) -> None:
        # A replica is ejected when it is unreachable or its readiness
        # endpoint answers 503 (the engine's loading/draining/wedged
        # states). Any other status — including 404 from upstreams that
        # expose no /ready — counts as reachable, so plain HTTP backends
        # stay routable.
        try:
            async with self._session.get(
                rep.url + self.probe_path,
                timeout=aiohttp.ClientTimeout(total=self.probe_timeout_s),
            ) as resp:
                raw = await resp.read()
                healthy = resp.status != 503
                if self.affinity_cfg.enabled and resp.status == 200:
                    self._refresh_filter(rep, raw)
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            healthy = False
        self._set_health(rep, healthy)

    def _refresh_filter(self, rep: Replica, raw: bytes) -> None:
        """Adopt the digest-membership filter the replica piggybacked on
        its /ready body. Absent or malformed keeps the last good filter —
        the age gauge makes staleness visible, and a stale filter only
        degrades cache-aware placement to pure rendezvous."""
        try:
            doc = json.loads(raw)
        except (ValueError, UnicodeDecodeError):
            return
        pf = doc.get("prefix_filter") if isinstance(doc, dict) else None
        f = affinity.BloomFilter.parse(pf) if isinstance(pf, dict) else None
        if f is None:
            return
        self._filters[rep.url] = f
        self._filter_at[rep.url] = self.clock()

    def _set_health(self, rep: Replica, healthy: bool) -> None:
        if healthy != rep.healthy:
            jlog("replica_health", component="router", model=rep.model,
                 replica=rep.url,
                 verdict="re-admitted" if healthy else "ejected")
        rep.healthy = healthy
        self.metrics["replica_healthy"].labels(
            model=rep.model, replica=rep.url,
            role=rep.role).set(1 if healthy else 0)

    # ------------------------------------------------------------------

    async def health(self, request: web.Request) -> web.Response:
        return web.Response(text="OK")

    async def metrics_endpoint(self, request: web.Request) -> web.Response:
        # breaker state is refreshed at scrape time (it changes on every
        # request outcome; per-transition gauge writes would be hot-path)
        for reps in self.replicas.values():
            for r in reps:
                self.metrics["breaker_open"].labels(
                    model=r.model, replica=r.url, role=r.role).set(
                        0 if r.breaker.state == CircuitBreaker.CLOSED else 1)
                if self.affinity_cfg.enabled:
                    at = self._filter_at.get(r.url)
                    if at is not None:
                        self.metrics["prefix_filter_age"].labels(
                            model=r.model, replica=r.url).set(
                                max(0.0, self.clock() - at))
        return web.Response(text=self.registry.render(),
                            content_type="text/plain")

    async def _scrape_replica(self, url: str) -> Optional[str]:
        """One replica's /metrics text, or None on any failure (counted —
        an unreachable replica must be visible in the cluster view, not
        silently absent from it)."""
        try:
            async with self._session.get(
                url + "/metrics",
                timeout=aiohttp.ClientTimeout(total=self.scrape_timeout_s),
            ) as resp:
                text = await resp.text()
                if resp.status != 200:
                    raise aiohttp.ClientResponseError(
                        resp.request_info, (), status=resp.status)
                return text
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            self.metrics["cluster_scrape_errors"].inc()
            jlog("cluster_scrape_error", component="router", replica=url)
            return None

    async def metrics_cluster(self, request: web.Request) -> web.Response:
        """Merged cluster exposition: every distinct replica's /metrics
        aggregated per the contract in cluster_metrics.merge_expositions
        (counters/histograms summed, gauges per-replica-labeled). The
        router's OWN series stay on /metrics — mixing them here would
        duplicate family headers for names both layers emit
        (llm_build_info et al.)."""
        urls = sorted({rep.url for reps in self.replicas.values()
                       for rep in reps})
        texts = await asyncio.gather(*(self._scrape_replica(u) for u in urls))
        merged = merge_expositions(dict(zip(urls, texts)))
        return web.Response(text=merged, content_type="text/plain")

    async def models(self, request: web.Request) -> web.Response:
        """Synthesized exactly like the reference gateway (no backend hop)."""
        now = int(time.time())
        ids = []
        for name in self.backends:
            ids.append(name)
            ids += [f"{name}:{a}" for a in self.adapters.get(name, ())]
        return web.json_response({
            "object": "list",
            "data": [
                {"id": mid, "object": "model", "created": now,
                 "owned_by": "llms-on-kubernetes-tpu"}
                for mid in ids
            ],
        })

    @staticmethod
    def _json_doc(body: bytes) -> Optional[dict]:
        if not body:
            return None
        try:
            data = json.loads(body)
        except (json.JSONDecodeError, UnicodeDecodeError):
            return None
        return data if isinstance(data, dict) else None

    def select_backend(self, body: bytes) -> tuple[str, Optional[str]]:
        """Exact-match routing on the JSON `model` field.

        Returns (model_name, error); error is set in strict mode and for
        an unknown adapter of a known base (``base:adapter`` naming).
        """
        return self._select(self._json_doc(body))[:2]

    def _select(self, doc: Optional[dict]) \
            -> tuple[str, Optional[str], Optional[str]]:
        model = doc.get("model") if doc else None
        if isinstance(model, str) and model in self.backends:
            return model, None, None
        if isinstance(model, str) and ":" in model:
            # base:adapter multi-tenant naming — resolved BEFORE the
            # unknown-model fallback so an adapter request never silently
            # lands on the base model's (different) weights
            base, adapter = model.split(":", 1)
            if base in self.backends:
                if adapter in self.adapters.get(base, ()):
                    return base, None, None
                # known base, unknown adapter: ALWAYS a 404 (even
                # non-strict; the fallback counter is for unknown BASES)
                return base, (f"adapter {adapter!r} not found for model "
                              f"{base!r}"), "adapter_not_found"
        if model is not None:
            if self.strict:
                return (self.default_model, f"model {model!r} not found",
                        "model_not_found")
            self.metrics["unknown_model_fallback"].inc()
            jlog("unknown_model_fallback", component="router",
                 model=str(model), default=self.default_model)
        return self.default_model, None, None

    def _deadline_from(self, request: web.Request, doc: Optional[dict],
                       now: float) -> Optional[float]:
        """Absolute deadline on ``self.clock``, or None when the client
        set no budget. Header takes precedence over the body field."""
        raw = request.headers.get(DEADLINE_HEADER)
        if raw is not None:
            try:
                return now + float(raw) / 1000.0
            except ValueError:
                return None
        timeout = doc.get("timeout") if doc else None
        if isinstance(timeout, (int, float)) and not isinstance(timeout, bool):
            return now + float(timeout)
        return None

    def _serve_roles(self, model: str) -> Optional[tuple]:
        """Role preference for ordinary (non-two-hop) traffic: when the
        model has prefill-role replicas, prefer both/decode ones — a
        prefill pod serving full generations starves the ticket flow —
        falling back to prefill only when nothing else is routable."""
        if any(r.role == "prefill" for r in self.replicas[model]):
            return ("both", "decode")
        return None

    def _pick(self, model: str, exclude: set,
              roles: Optional[tuple] = None,
              shadow: bool = False) -> Optional[Replica]:
        """Power-of-two-choices over the model's routable replicas.

        Replicas in ``exclude`` (already failed this request) are skipped
        unless nothing else is routable; breaker half-open slots are only
        claimed for the final choice (``blocked()`` peeks first). With
        ``roles``, replicas of those roles are preferred and the rest are
        a last resort (never preferred over an excluded preferred one is
        NOT guaranteed — availability beats affinity).

        Quarantined replicas (outlier detector) are excluded like
        unhealthy ones, with two exceptions: a ``shadow`` pick steers the
        request TO a quarantined member (the 1-in-N trickle that lets it
        earn re-admission), and when nothing non-quarantined is routable
        a quarantined replica still beats a 503.
        """
        det = self.outliers.get(model)
        reps = self.replicas[model]
        pools = [reps]
        if roles:
            pref = [r for r in reps if r.role in roles]
            pools = [pref, reps] if pref and len(pref) < len(reps) \
                else ([pref] if pref else [reps])
        for pool in pools:
            live = [r for r in pool
                    if r.healthy and not r.breaker.blocked()]
            if det is not None and shadow:
                qcands = [r for r in live if r.url not in exclude
                          and det.is_quarantined(r.url)]
                if qcands:
                    choice = random.choice(qcands)
                    return choice if choice.breaker.allow() else None
            cands = [r for r in live if r.url not in exclude
                     and not (det is not None
                              and det.is_quarantined(r.url))]
            if not cands and det is not None:
                # every non-quarantined member is down/excluded: routing
                # to a quarantined replica still beats failing the request
                cands = [r for r in live if r.url not in exclude]
            if not cands and exclude:
                cands = live
            if not cands:
                continue
            if len(cands) == 1:
                choice = cands[0]
            else:
                a, b = random.sample(cands, 2)
                choice = a if a.inflight <= b.inflight else b
            return choice if choice.breaker.allow() else None
        return None

    def _pick_role(self, model: str, exclude: set,
                   role: str) -> Optional[Replica]:
        """Strict single-role pick for the handoff hops (no cross-role
        fallback — that decision belongs to the caller's ladder)."""
        det = self.outliers.get(model)
        live = [r for r in self.replicas[model]
                if r.role == role and r.url not in exclude and r.healthy
                and not r.breaker.blocked()]
        cands = [r for r in live
                 if not (det is not None and det.is_quarantined(r.url))]
        if not cands:
            cands = live  # quarantined-only pool: degrade, don't refuse
        if not cands:
            return None
        if len(cands) == 1:
            choice = cands[0]
        else:
            a, b = random.sample(cands, 2)
            choice = a if a.inflight <= b.inflight else b
        return choice if choice.breaker.allow() else None

    # ------------------------------------------------------------------
    # prefix-affinity + cache-aware placement (server/affinity.py holds
    # the semantics; routing never changes tokens, only placement)

    def _affinity_route(self, model: str, doc: Optional[dict],
                        trace: "tracing.Trace") \
            -> tuple[Optional[str], Optional[str], Optional[str]]:
        """(affinity key, chosen replica URL, kv-pull source URL) for one
        completion request — (key, None, None) when the decision ladder
        fell back to P2C, (None, None, None) when the request has no
        affinity key at all. Counted into the hits/fallback series here,
        at decision time, not at dispatch."""
        cfg = self.affinity_cfg
        text = affinity.canonical_prompt(doc)
        if text is None:
            self.metrics["affinity_fallback"].labels(
                model=model, reason=affinity.FALLBACK_MISS).inc()
            return None, None, None
        key = affinity.affinity_key(
            affinity.request_tenant(doc, model), text, cfg.prefix_chars)
        pool = self.replicas[model]
        if any(r.role == "prefill" for r in pool):
            # mirror _serve_roles: a full generation never pins to a
            # prefill pod (it would starve the disagg ticket flow); the
            # two-hop handoff path has its own KV-aware placement
            pool = [r for r in pool if r.role in ("both", "decode")]
        if not pool:
            self.metrics["affinity_fallback"].labels(
                model=model, reason=affinity.FALLBACK_UNHEALTHY).inc()
            return key, None, None
        det = self.outliers.get(model)
        reps = [{
            "url": r.url,
            "healthy": r.healthy,
            "breaker_open": r.breaker.blocked(),
            "quarantined": bool(det is not None
                                and det.is_quarantined(r.url)),
            "inflight": r.inflight,
            "filter": self._filters.get(r.url),
        } for r in pool]
        digests = self.affinity_digests.get(key)
        url, outcome = affinity.decide(key, reps, digests,
                                       cfg.overload_factor,
                                       cfg.overload_slack)
        if url is None:
            self.metrics["affinity_fallback"].labels(
                model=model, reason=outcome).inc()
            return key, None, None
        self.metrics["affinity_hits"].labels(model=model).inc()
        trace.event("affinity", outcome=outcome, replica=url)
        pull = None
        if cfg.kv_fetch and digests:
            # stretch flag: the chosen replica's filter claims none of
            # the chain but a peer's does — have the chosen replica pull
            # the spilled pages over /internal/kv/fetch (PR-16 substrate)
            # instead of re-prefilling
            chosen = next((x for x in reps if x["url"] == url), None)
            if chosen is not None and affinity.filter_claim(
                    chosen["filter"], digests) == 0:
                best_claim = 0
                for x in reps:
                    if x["url"] == url:
                        continue
                    c = affinity.filter_claim(x["filter"], digests)
                    if c > best_claim:
                        pull, best_claim = x["url"], c
        return key, url, pull

    def _learn_digests(self, key: str, resp_headers) -> None:
        """Fold a completion response's canonical digest chain into the
        per-key cache so the NEXT request with this key can be matched
        against replica filters (router-side keys converge on what the
        engine actually caches)."""
        raw = resp_headers.get(CACHE_DIGESTS_HEADER)
        if raw:
            self.affinity_digests.put(key, affinity.parse_digest_header(
                raw, self.affinity_cfg.max_digests))

    # ------------------------------------------------------------------
    # gray-failure layer plumbing (server/outlier.py holds the semantics)

    def _outlier_group(self, rep: Replica) -> list:
        """Peer population a replica is judged against: same model AND
        same role — a prefill pool's latency profile says nothing about
        a decode pool's."""
        return [r.url for r in self.replicas[rep.model]
                if r.role == rep.role]

    def _observe_replica(self, rep: Replica, ttft_ms: Optional[float],
                         error: bool) -> None:
        """Fold one in-band outcome into the model's outlier detector
        and export any quarantine transition it causes."""
        det = self.outliers.get(rep.model)
        if det is None:
            return
        event = det.record(rep.url, self._outlier_group(rep), ttft_ms,
                           error)
        if not event:
            return
        if event.startswith("quarantine:"):
            reason = event.split(":", 1)[1]
            s = det.get(rep.url)
            self.metrics["quarantined"].labels(
                model=rep.model, replica=rep.url, reason=reason).set(1)
            self.metrics["outlier_ejections"].labels(reason=reason).inc()
            jlog("replica_quarantined", component="router",
                 model=rep.model, replica=rep.url, reason=reason,
                 ewma_ttft_ms=round(s.ewma_ttft_ms or 0.0, 3),
                 ewma_err=round(s.ewma_err or 0.0, 4))
        elif event == "readmit":
            for reason in ("latency", "errors"):
                self.metrics["quarantined"].labels(
                    model=rep.model, replica=rep.url, reason=reason).set(0)
            jlog("replica_readmitted", component="router",
                 model=rep.model, replica=rep.url)
        elif event == "guard_blocked":
            # outlier streak complete but ejecting would pass the
            # max-ejection-fraction guard: common-mode slowdown, degrade
            # instead of self-DoSing (the streak holds and re-tries)
            jlog("quarantine_guard_blocked", component="router",
                 model=rep.model, replica=rep.url)

    def _charge_retry(self, model: str, rid: str, source: str) -> bool:
        """Draw one token from the model's retry budget. False means the
        caller must downgrade (shed / single-attempt / truncate) — never
        dispatch the retry anyway."""
        budget = self.retry_budgets.get(model)
        if budget is None or budget.charge():
            return True
        self.metrics["retry_budget_exhausted"].inc()
        jlog("retry_budget_exhausted", request_id=rid, component="router",
             model=model, source=source)
        return False

    def _refund_retry(self, model: str) -> None:
        """Return a charged token that never became bytes on the wire
        (no replica to send the retry to)."""
        budget = self.retry_budgets.get(model)
        if budget is not None:
            budget.refund()

    def _unroutable_response(self, model: str, rid: str = "") -> web.Response:
        reps = self.replicas[model]
        healthy = [r for r in reps if r.healthy]
        if healthy:
            retry_after = max(1, math.ceil(
                min(r.breaker.retry_after_s() for r in healthy)))
            return web.json_response(
                error_body(
                    f"all {len(healthy)} replica(s) of {model!r} unavailable "
                    f"(circuit open)",
                    "service_unavailable", "upstream_circuit_open"),
                status=503, headers=self._rid_headers(
                    rid, {"Retry-After": str(retry_after)}),
            )
        retry_after = max(1, math.ceil(self.probe_interval_s or 1))
        return web.json_response(
            error_body(
                f"no healthy replicas for {model!r} "
                f"({len(reps)} ejected by health probes)",
                "service_unavailable", "no_healthy_upstream"),
            status=503, headers=self._rid_headers(
                rid, {"Retry-After": str(retry_after)}),
        )

    def _deadline_response(self, rid: str = "") -> web.Response:
        self.metrics["deadline_rejected"].inc()
        return web.json_response(
            error_body("deadline expired before the request could be "
                       "forwarded", "timeout", "deadline_exceeded"),
            status=504, headers=self._rid_headers(rid),
        )

    @staticmethod
    def _rid_headers(rid: str, extra: Optional[dict] = None) -> dict:
        headers = dict(extra) if extra else {}
        if rid:
            headers[REQUEST_ID_HEADER] = rid
        return headers

    async def debug_traces(self, request: web.Request) -> web.Response:
        try:
            limit = int(request.query.get("limit", "50"))
        except ValueError:
            limit = 50
        return web.json_response({"traces": self.traces.snapshot(
            request_id=request.query.get("id"),
            model=request.query.get("model"),
            limit=limit,
        )})

    async def debug_trace(self, request: web.Request) -> web.Response:
        """Hop-stitched waterfall for one trace: this router's local
        fragments plus child fragments pulled on demand from every
        replica's ``/debug/traces?id=``, assembled into one tree
        (tracing.stitch_waterfall) with per-hop durations and retry/
        hedge/redirect annotations."""
        tid = request.match_info["trace_id"]
        fragments = self.traces.snapshot(request_id=tid, limit=32)
        urls = sorted({r.url for reps in self.replicas.values()
                       for r in reps})

        async def pull(base: str) -> list[dict]:
            try:
                async with self._session.get(
                        f"{base}/debug/traces",
                        params={"id": tid, "limit": "8"},
                        timeout=aiohttp.ClientTimeout(
                            total=self.scrape_timeout_s)) as resp:
                    if resp.status != 200:
                        return []
                    doc = await resp.json(content_type=None)
            except (aiohttp.ClientError, TimeoutError, OSError, ValueError):
                return []
            traces = doc.get("traces") if isinstance(doc, dict) else None
            return [t for t in traces or [] if isinstance(t, dict)]

        if self._session is not None and urls:
            for pulled in await asyncio.gather(*(pull(u) for u in urls)):
                fragments.extend(pulled)
        doc = tracing.stitch_waterfall(tid, fragments)
        if not doc["fragments"]:
            return web.json_response(
                error_body(f"no trace fragments for {tid!r} (evicted from "
                           "the ring, or never traced here)", "not_found",
                           "trace_not_found"), status=404)
        return web.json_response(doc)

    async def debug_replicas(self, request: web.Request) -> web.Response:
        """Per-replica routing state: health, breaker, inflight, and —
        when the gray-failure layer is on — the quarantine FSM and the
        model's retry-budget level."""
        models = {}
        for name, reps in self.replicas.items():
            det = self.outliers.get(name)
            entry: dict = {"replicas": []}
            for r in reps:
                d = {
                    "url": r.url,
                    "role": r.role,
                    "healthy": r.healthy,
                    "inflight": r.inflight,
                    "breaker": r.breaker.state,
                }
                if det is not None:
                    d["outlier"] = det.snapshot(r.url)
                if self.affinity_cfg.enabled:
                    f = self._filters.get(r.url)
                    if f is not None:
                        d["prefix_filter"] = {
                            "count": f.count,
                            "age_s": round(max(0.0, self.clock()
                                               - self._filter_at[r.url]), 3),
                        }
                entry["replicas"].append(d)
            budget = self.retry_budgets.get(name)
            if budget is not None:
                entry["retry_budget"] = {
                    "level": budget.level,
                    "burst": budget.config.burst,
                    "ratio": budget.config.ratio,
                    "min_per_s": budget.config.min_per_s,
                }
            models[name] = entry
        return web.json_response({
            "outlier_ejection_enabled": self.outlier_cfg.enabled,
            "retry_budget_enabled": self.retry_budget_cfg.enabled,
            "prefix_affinity_enabled": self.affinity_cfg.enabled,
            "models": models,
        })

    # ------------------------------------------------------------------

    async def proxy(self, request: web.Request) -> web.StreamResponse:
        # canonical reconciliation of client-supplied correlation headers
        # (trace_vectors.json §reconcile): a valid traceparent is adopted,
        # a forged/malformed one is re-minted; same treatment for the
        # request id. The router's fragment is the edge root span unless
        # an outer proxy advertised a parent.
        ctx = tracing.reconcile(
            request.headers.get(tracing.TRACEPARENT_HEADER),
            request.headers.get(tracing.TRACESTATE_HEADER),
            request.headers.get(REQUEST_ID_HEADER))
        rid = ctx["request_id"] or tracing.new_request_id()
        trace = tracing.Trace(rid, clock=self.clock,
                              trace_id=ctx["trace_id"],
                              parent_span_id=ctx["parent_span_id"],
                              component="router", sampled=ctx["sampled"])
        request["llmk_tracestate"] = ctx["tracestate"]
        resp: Optional[web.StreamResponse] = None
        status = "error"
        try:
            resp = await self._proxy_inner(request, trace, rid)
            status = "ok" if resp.status < 400 else f"http_{resp.status}"
            return resp
        finally:
            trace.finish(status)
            self.traces.add(trace)
            # SLO sample: availability from the downstream status (0 =
            # failed before any status), TTFT from the first relayed byte
            self.slo.observe(int(getattr(resp, "status", 0) or 0),
                             request.get("llmk_ttft_ms"))
            jlog("request", request_id=rid, component="router",
                 model=trace.model, status=status,
                 http_status=getattr(resp, "status", None),
                 method=request.method, path=request.path,
                 e2e_ms=round(trace.e2e_ms() or 0.0, 3))
            tracing.maybe_log_slow(trace, "router")
            self._export_trace(trace)

    def _export_trace(self, trace: "tracing.Trace") -> None:
        """Tail-sampling decision + OTLP enqueue for a finished trace.
        Never raises, never blocks; a non-exported trace is always
        counted (dropped by reason), never silently discarded."""
        try:
            d = trace.to_dict()
            if self.exporter is None:
                self.metrics["trace_dropped"].labels(reason="disabled").inc()
                return
            status = d.get("status") or ""
            error = status == "error" or status.startswith("http_5")
            keep, reason = self.tail_sampler.decide(
                error, d.get("e2e_ms"), tracing.is_multi_hop(d))
            if not keep:
                self.metrics["trace_dropped"].labels(reason=reason).inc()
                return
            self.exporter.export(d)
        except Exception:  # noqa: BLE001 — observability must not 500 a proxy
            pass

    @staticmethod
    def _hop_headers(trace: "tracing.Trace", headers: dict) -> tuple:
        """Copy ``headers`` and mint a fresh per-hop ``traceparent``.

        Every upstream leg (connect attempt, hedge secondary, resume
        re-issue, handoff prefill/decode) gets its own span id so the
        receiving process can parent its fragment under the exact hop
        that reached it — that's what lets /debug/trace stitch retries
        and races into one tree instead of a pile of siblings.
        Returns ``(send_headers, hop_span_id)``.
        """
        sid = tracing.new_span_id()
        h = dict(headers)
        h[tracing.TRACEPARENT_HEADER] = tracing.format_traceparent(
            trace.trace_id, sid, trace.sampled)
        return h, sid

    async def _proxy_inner(self, request: web.Request,
                           trace: "tracing.Trace",
                           rid: str) -> web.StreamResponse:
        t0 = trace.t0
        body = await request.read()
        doc = self._json_doc(body)
        model, err, err_code = self._select(doc)
        req_model = doc.get("model") if doc else None
        # the trace label keeps the adapter suffix for RESOLVED
        # base:adapter requests (routing itself is per base model)
        trace.model = (req_model
                       if err is None and isinstance(req_model, str)
                       and req_model.startswith(model + ":") else model)
        trace.add_span("receive", t0, self.clock(), bytes=len(body))
        if err:
            return web.json_response(
                error_body(err, "invalid_request_error", err_code),
                status=404, headers=self._rid_headers(rid),
            )
        # demand signal, counted BEFORE replica selection can fail: a
        # scaled-to-zero model has no healthy replica, and this series'
        # rate is exactly what wakes it (KEDA trigger in manifests.py)
        self.metrics["requests_total"].labels(model=model).inc()
        # every admitted primary request earns the retry budget its
        # fractional token (SRE retry throttling: retries scale WITH
        # traffic, never against a fixed allowance)
        budget = self.retry_budgets.get(model)
        if budget is not None:
            budget.on_primary()

        # --- edge QoS gate: per-tenant rate limits, then the brownout
        # ladder (shed lowest-priority first, degrade before shedding the
        # class above). The resolved priority is forwarded upstream in
        # place of whatever the client sent, so the engine's fair queue
        # and the edge always agree on the request's class.
        tenant, priority = self.qos_gate.resolve(
            doc, model, request.headers.get(PRIORITY_HEADER))
        hedge_ok = True
        if self.qos_gate.enabled:
            self.metrics["tenant_requests"].labels(
                tenant=tenant, priority=priority).inc()
            depth = sum(r.inflight for reps in self.replicas.values()
                        for r in reps)
            burn = self.slo.snapshot()["error_budget_burn_rate"]
            forced = 0
            if faults.is_active("overload_spike"):
                # brownout-ladder fault hook (Python router only; see
                # faults.py): pretend the gateway is at this level
                forced = int(faults.get_float("overload_spike", 2.0) or 0)
            charge = default_token_charge(doc)
            verdict = self.qos_gate.check(
                tenant, priority, charge, float(depth), float(burn), forced)
            if verdict.action == "shed":
                self.metrics["tenant_router_shed"].labels(
                    tenant=tenant, priority=priority,
                    reason=verdict.reason).inc()
                return web.json_response(
                    error_body(verdict.message, "rate_limit_exceeded",
                               verdict.reason),
                    status=429, headers=self._rid_headers(
                        rid, {"Retry-After": str(verdict.retry_after)}))
            if verdict.action == "degrade":
                self.metrics["tenant_degraded"].labels(
                    tenant=tenant, priority=priority).inc()
                hedge_ok = False  # no speculative duplicates under brownout
                clamp = verdict.clamp_max_tokens or 0
                if doc is not None and clamp > 0:
                    mt = doc.get("max_tokens")
                    unset = not (isinstance(mt, (int, float))
                                 and not isinstance(mt, bool) and mt > 0)
                    if unset or mt > clamp:
                        doc = dict(doc)
                        doc["max_tokens"] = clamp
                        body = json.dumps(doc).encode()
                        charge = min(charge, clamp)
            self.metrics["tenant_tokens"].labels(tenant=tenant).inc(charge)
        request["llmk_hedge_ok"] = hedge_ok

        deadline = self._deadline_from(request, doc, t0)
        if deadline is not None and self.clock() >= deadline:
            return self._deadline_response(rid)

        # the inbound deadline header is consumed here; a decremented copy
        # is re-added per attempt below (never the client's raw value).
        # The stream-resume protocol headers are router-internal — a
        # client-supplied copy must never reach an upstream.
        headers = {
            k: v for k, v in request.headers.items()
            if k.lower() not in HOP_BY_HOP
            and k.lower() not in (DEADLINE_HEADER.lower(),
                                  REQUEST_ID_HEADER.lower(),
                                  PRIORITY_HEADER.lower(),
                                  JOURNAL_HEADER.lower(),
                                  RESUME_TOKENS_HEADER.lower(),
                                  RESUME_STREAM_ID_HEADER.lower(),
                                  RESUME_CREATED_HEADER.lower(),
                                  HANDOFF_HEADER.lower(),
                                  HANDOFF_SOURCE_HEADER.lower(),
                                  HANDOFF_DIGESTS_HEADER.lower(),
                                  HANDOFF_TENANT_HEADER.lower(),
                                  HANDOFF_SEED_HEADER.lower(),
                                  tracing.TRACEPARENT_HEADER,
                                  tracing.TRACESTATE_HEADER)
        }
        headers[REQUEST_ID_HEADER] = rid
        # the inbound traceparent was consumed by reconcile() at the edge;
        # every upstream send mints a fresh per-hop traceparent (see
        # _hop_headers) so each leg gets a unique parent pointer. A valid
        # adopted tracestate rides along unchanged; anything else is gone.
        ts = request.get("llmk_tracestate") or ""
        if ts:
            headers[tracing.TRACESTATE_HEADER] = ts
        # RESOLVED priority, never the client's raw header (an invalid or
        # unauthorized value must not leak past the gateway)
        headers[PRIORITY_HEADER] = priority
        peername = request.transport.get_extra_info("peername") if request.transport else None
        client_ip = peername[0] if peername else ""
        headers["X-Real-IP"] = client_ip
        prior = request.headers.get("X-Forwarded-For")
        headers["X-Forwarded-For"] = f"{prior}, {client_ip}" if prior else client_ip
        headers["X-Forwarded-Proto"] = request.scheme

        # streaming completions get the journal/splice relay: the journal
        # is kept even with resume disabled (the truncation error event
        # and counter need it); the upstream only emits tok comments when
        # asked, so the header rides only when resume is on
        journal: Optional[_StreamJournal] = None
        if (request.method == "POST" and doc is not None
                and doc.get("stream") is True
                and request.match_info["path"].rstrip("/").endswith(
                    "completions")):
            journal = _StreamJournal(self.journal_max_tokens)
            if self.stream_resume:
                headers[JOURNAL_HEADER] = "1"

        # --- disaggregated two-hop: streaming completions on a model with
        # separate prefill/decode pools go prefill-ticket -> decode-adopt.
        # Every failure in the ladder falls through to the ordinary
        # colocated path below — the two-hop flow is an optimization, never
        # a new way to fail a request.
        if journal is not None and self._disagg.get(model):
            resp = await self._handoff_flow(
                request, trace, rid, model, headers, body, deadline,
                journal, t0)
            if resp is not None:
                return resp
            self.metrics["handoff"].labels(
                outcome="fallback_colocated").inc()
            trace.event("handoff_fallback_colocated")

        # --- prefix-affinity + cache-aware placement: an affinity-keyed
        # completion prefers its rendezvous-pinned replica, or a peer
        # whose advertised /ready filter claims the prompt's digest
        # chain. The connect loop below uses the choice as its attempt-1
        # target only — every fallback (breaker race, retry, shadow
        # trickle) is the unchanged P2C path, so routing can change
        # placement but never tokens.
        aff_key: Optional[str] = None
        aff_url: Optional[str] = None
        aff_pull: Optional[str] = None
        if (self.affinity_cfg.enabled and request.method == "POST"
                and doc is not None
                and request.match_info["path"].rstrip("/").endswith(
                    "completions")):
            aff_key, aff_url, aff_pull = self._affinity_route(
                model, doc, trace)
            if aff_key:
                request["llmk_affinity_key"] = aff_key

        # --- connect/request phase: bounded retries with backoff+jitter.
        # Only failures BEFORE a response head are retried (the buffered
        # body makes the resend safe); each transport failure feeds the
        # replica's breaker. A retry prefers a different healthy replica
        # (failover, immediate); retrying the same replica backs off.
        upstream: Optional[aiohttp.ClientResponse] = None
        active: Optional[Replica] = None
        prev: Optional[Replica] = None
        last_err: Optional[BaseException] = None
        tried: set = set()
        never_picked = True
        t_connect0 = self.clock()
        attempt = 0
        # shadow trickle: while the model has quarantined replicas, every
        # shadow_every-th request is deliberately steered to one so it can
        # earn re-admission (streaming clients keep resume/failover — the
        # quarantined replica is never their only shot at a response)
        det = self.outliers.get(model)
        shadow = bool(
            det is not None
            and det.quarantined_in(
                [r.url for r in self.replicas[model]]) > 0
            and det.shadow_tick())
        for attempt in range(1, self.retry_attempts + 1):
            if attempt > 1 and not self._charge_retry(model, rid,
                                                      "connect"):
                trace.add_span("connect", t_connect0, self.clock(),
                               error="retry budget exhausted",
                               attempts=attempt - 1)
                return web.json_response(
                    error_body(
                        "retry budget exhausted after upstream error: "
                        f"{last_err}", "service_unavailable",
                        "retry_budget_exhausted"),
                    status=503, headers=self._rid_headers(
                        rid, {"Retry-After": "1"}))
            replica = None
            if aff_url is not None and attempt == 1 and not shadow:
                # affinity target for the first attempt (shadow trickle
                # outranks it: a quarantined replica must still get its
                # 1-in-N chance to earn re-admission); any breaker race
                # since the decision falls through to P2C
                replica = next((r for r in self.replicas[model]
                                if r.url == aff_url), None)
                if replica is not None and not replica.breaker.allow():
                    replica = None
            if replica is None:
                replica = self._pick(model, tried,
                                     roles=self._serve_roles(model),
                                     shadow=shadow and attempt == 1)
            if replica is None:
                if attempt > 1:
                    self._refund_retry(model)
                break
            never_picked = False
            if prev is not None and replica.url != prev.url:
                self.metrics["failover"].inc()
                jlog("failover", request_id=rid, component="router",
                     model=model, src=prev.url, dst=replica.url)
            if deadline is not None:
                remaining = deadline - self.clock()
                if remaining <= 0:
                    return self._deadline_response(rid)
                headers[DEADLINE_HEADER] = str(int(remaining * 1000))
            url = f"{replica.url}/{request.match_info['path']}"
            if request.query_string:
                url += f"?{request.query_string}"
            send_headers, hop_sid = self._hop_headers(trace, headers)
            if aff_pull and attempt == 1 and replica.url == aff_url:
                # kv_fetch stretch: the chosen replica's caches hold none
                # of the chain but a peer's do — name that peer so the
                # replica pulls the spilled pages over /internal/kv/fetch
                # (PR-16 substrate) instead of re-prefilling
                send_headers[HANDOFF_SOURCE_HEADER] = aff_pull
                send_headers[HANDOFF_DIGESTS_HEADER] = ",".join(
                    d.hex() for d in self.affinity_digests.get(aff_key))
                send_headers[HANDOFF_TENANT_HEADER] = tenant
            replica.inflight += 1
            try:
                upstream = await self._session.request(
                    request.method, url, data=body or None,
                    headers=send_headers,
                )
                replica.breaker.record_success()
                active = replica
                trace.add_span("connect", t_connect0, self.clock(),
                               span_id=hop_sid,
                               parent_span_id=trace.span_id,
                               replica=replica.url, attempts=attempt)
                break
            except RETRYABLE_ERRORS as e:
                replica.inflight -= 1
                replica.breaker.record_failure()
                self._observe_replica(replica, None, True)
                last_err = e
                tried.add(replica.url)
                prev = replica
                if attempt >= self.retry_attempts:
                    break
                # back off only when no untried alternate exists (a
                # failover to a different replica is immediate); the
                # shared deadline-aware full-jitter curve keeps both
                # routers' retry waves decorrelated and never sleeps a
                # doomed request past its budget
                alternates = [r for r in self.replicas[model]
                              if r.url not in tried and r.healthy
                              and not r.breaker.blocked()]
                if not alternates:
                    remaining = ((deadline - self.clock())
                                 if deadline is not None else -1.0)
                    await asyncio.sleep(outlier.backoff_s(
                        self.retry_backoff_s, attempt - 1,
                        random.random(), remaining_s=remaining))
            except (aiohttp.ClientError, TimeoutError, OSError) as e:
                replica.inflight -= 1
                replica.breaker.record_failure()
                self._observe_replica(replica, None, True)
                last_err = e
                break
        if upstream is None or active is None:
            if never_picked and last_err is None:
                return self._unroutable_response(model, rid)
            trace.add_span("connect", t_connect0, self.clock(),
                           error=str(last_err), attempts=attempt)
            return web.json_response(
                error_body(f"upstream error: {last_err}", "bad_gateway",
                           "upstream_error"),
                status=502, headers=self._rid_headers(rid),
            )

        if journal is not None:
            return await self._relay_stream(
                request, trace, rid, model, headers, body, deadline,
                upstream, active, tried, t0, journal)

        if aff_key and upstream.status == 200:
            self._learn_digests(aff_key, upstream.headers)

        # --- relay phase (non-journaled): stream the response; never
        # retried (the upstream may have executed the request).
        resp: Optional[web.StreamResponse] = None
        t_head = self.clock()
        t_first: Optional[float] = None
        relayed = 0
        try:
            async with upstream:
                resp = web.StreamResponse(status=upstream.status)
                for k, v in upstream.headers.items():
                    if k.lower() not in HOP_BY_HOP:
                        resp.headers[k] = v
                # echo the id even when the upstream is not LLMK-aware
                resp.headers.setdefault(REQUEST_ID_HEADER, rid)
                await resp.prepare(request)
                # never buffer: relay chunks as they arrive (SSE-safe)
                async for chunk in upstream.content.iter_any():
                    if t_first is None:
                        t_first = self.clock()
                        trace.add_span("first_byte", t_head, t_first)
                        request["llmk_ttft_ms"] = (t_first - t0) * 1000.0
                        self._observe_replica(
                            active, request["llmk_ttft_ms"], False)
                    relayed += len(chunk)
                    await resp.write(chunk)
                await resp.write_eof()
                trace.add_span("stream", t_first if t_first is not None
                               else t_head, self.clock(), bytes=relayed,
                               upstream_status=upstream.status)
                return resp
        except (aiohttp.ClientError, TimeoutError, OSError) as e:
            active.breaker.record_failure()
            self._observe_replica(active, None, True)
            trace.event("relay_error", error=str(e), bytes=relayed)
            if resp is None or not resp.prepared:
                return web.json_response(
                    error_body(f"upstream error: {e}", "bad_gateway",
                               "upstream_error"),
                    status=502, headers=self._rid_headers(rid),
                )
            # Upstream died mid-stream: headers are already on the wire, so a
            # 502 can't be sent. Close the downstream connection so the client
            # sees EOF/reset instead of hanging forever on a half-open stream.
            if request.transport is not None:
                request.transport.close()
            return resp
        finally:
            active.inflight -= 1

    # ------------------------------------------------------------------
    # journaled SSE relay: mid-stream failover splice + hedged requests

    _RELAY_ERRORS = (aiohttp.ClientError, TimeoutError, OSError)

    async def _handoff_flow(self, request: web.Request,
                            trace: "tracing.Trace", rid: str, model: str,
                            headers: dict, body: bytes,
                            deadline: Optional[float],
                            journal: "_StreamJournal",
                            t0: float) -> Optional[web.StreamResponse]:
        """Two-hop disaggregated serving (protocol at the HANDOFF_*
        constants): prefill-hop for a ticket, then re-issue the original
        body to a decode replica that adopts the ticket's pages.

        Returns the relayed response, or None to tell the caller to fall
        back to the ordinary colocated path (prefill pool exhausted, no
        decode replica took the request within ``handoff_retries``
        attempts) — the fallback is degraded capacity, never an error.
        A replica that answers but refuses (draining 503, ineligible
        body) is skipped without feeding its breaker; only transport
        failures do that.
        """
        t_h0 = self.clock()
        path = request.match_info["path"]
        qs = f"?{request.query_string}" if request.query_string else ""

        # --- prefill hop: chunked prompt ingestion, ticket back
        ticket: Optional[dict] = None
        source: Optional[Replica] = None
        tried_p: set = set()
        for p_attempt in range(1, self.retry_attempts + 1):
            # prefill-hop retries are retries like any other: past the
            # first attempt they draw from the model's budget, and an
            # exhausted budget downgrades to the colocated single path
            if p_attempt > 1 and not self._charge_retry(
                    model, rid, "handoff_prefill"):
                return None
            replica = self._pick_role(model, tried_p, "prefill")
            if replica is None:
                if p_attempt > 1:
                    self._refund_retry(model)
                return None
            h, p_sid = self._hop_headers(trace, headers)
            h[HANDOFF_HEADER] = "ticket"
            if deadline is not None:
                remaining = deadline - self.clock()
                if remaining <= 0:
                    return self._deadline_response(rid)
                h[DEADLINE_HEADER] = str(int(remaining * 1000))
            t_p0 = self.clock()
            replica.inflight += 1
            try:
                up = await self._session.request(
                    request.method, f"{replica.url}/{path}{qs}",
                    data=body or None, headers=h)
            except self._RELAY_ERRORS:
                replica.inflight -= 1
                replica.breaker.record_failure()
                self._observe_replica(replica, None, True)
                tried_p.add(replica.url)
                continue
            ctype = up.headers.get("Content-Type", "").lower()
            if up.status == 200 and up.headers.get(HANDOFF_TICKET_HEADER):
                try:
                    doc_t = await up.json(content_type=None)
                except (*self._RELAY_ERRORS, ValueError):
                    replica.inflight -= 1
                    replica.breaker.record_failure()
                    self._observe_replica(replica, None, True)
                    tried_p.add(replica.url)
                    up.close()
                    continue
                replica.inflight -= 1
                replica.breaker.record_success()
                if not isinstance(doc_t, dict):
                    tried_p.add(replica.url)
                    continue
                ticket, source = doc_t, replica
                trace.add_span("handoff_prefill", t_p0, self.clock(),
                               span_id=p_sid,
                               parent_span_id=trace.span_id,
                               replica=replica.url, attempts=p_attempt)
                break
            if up.status == 200 and ctype.startswith("text/event-stream"):
                # the replica DECLINED the ticket (ineligible shape) and
                # is serving the stream itself: relay it like any other —
                # correct, just not disaggregated
                replica.breaker.record_success()
                trace.event("handoff_declined", replica=replica.url)
                return await self._relay_stream(
                    request, trace, rid, model, h, body, deadline, up,
                    replica, tried_p, t0, journal)
            # answered but refused (draining/killed 503, 4xx): not a
            # transport failure — skip it, the colocated fallback will
            # produce the authoritative response if nothing else works
            replica.inflight -= 1
            up.close()
            tried_p.add(replica.url)
        if ticket is None or source is None:
            return None

        digests = [d for d in ticket.get("digests", ())
                   if isinstance(d, str) and d]
        seed = ticket.get("seed")

        # --- decode hop: fresh issue of the ORIGINAL body + adoption
        # headers; the stream regenerates bit-identically from token zero
        h2 = dict(headers)
        if digests:
            h2[HANDOFF_SOURCE_HEADER] = source.url
            h2[HANDOFF_DIGESTS_HEADER] = ",".join(digests)
            h2[HANDOFF_TENANT_HEADER] = str(ticket.get("tenant") or "")
        if isinstance(seed, int) and not isinstance(seed, bool):
            h2[HANDOFF_SEED_HEADER] = str(seed)
        tried_d: set = set()
        for attempt in range(1, self.handoff_retries + 1):
            if attempt > 1 and not self._charge_retry(
                    model, rid, "handoff_decode"):
                break
            replica = self._pick_role(model, tried_d, "decode")
            if replica is None:
                if attempt > 1:
                    self._refund_retry(model)
                break
            # each decode attempt is its own hop: fresh traceparent so a
            # retried adoption shows up as a distinct leg in the waterfall
            d_sid = tracing.new_span_id()
            h2[tracing.TRACEPARENT_HEADER] = tracing.format_traceparent(
                trace.trace_id, d_sid, trace.sampled)
            if deadline is not None:
                remaining = deadline - self.clock()
                if remaining <= 0:
                    return self._deadline_response(rid)
                h2[DEADLINE_HEADER] = str(int(remaining * 1000))
            t_d0 = self.clock()
            replica.inflight += 1
            try:
                up = await self._session.request(
                    request.method, f"{replica.url}/{path}{qs}",
                    data=body or None, headers=h2)
            except self._RELAY_ERRORS:
                replica.inflight -= 1
                replica.breaker.record_failure()
                self._observe_replica(replica, None, True)
                tried_d.add(replica.url)
                continue
            ctype = up.headers.get("Content-Type", "").lower()
            if up.status != 200 or not ctype.startswith("text/event-stream"):
                replica.inflight -= 1
                up.close()
                tried_d.add(replica.url)
                continue
            replica.breaker.record_success()
            try:
                adopted = int(up.headers.get(HANDOFF_ADOPTED_HEADER, "0"))
            except ValueError:
                adopted = 0
            # reprefill = pages were offered but none adopted: the decode
            # replica recomputed the prompt — degraded, counted, correct
            outcome = ("reprefill" if digests and adopted <= 0
                       else ("ok" if attempt == 1 else "retried"))
            self.metrics["handoff"].labels(outcome=outcome).inc()
            self.metrics["handoff_seconds"].observe(self.clock() - t_h0)
            jlog("handoff", request_id=rid, component="router", model=model,
                 prefill=source.url, decode=replica.url, outcome=outcome,
                 pages_offered=len(digests), pages_adopted=adopted)
            trace.event("handoff", outcome=outcome, adopted=adopted,
                        prefill=source.url, decode=replica.url)
            trace.add_span("handoff_decode", t_d0, self.clock(),
                           span_id=d_sid, parent_span_id=trace.span_id,
                           replica=replica.url, attempts=attempt)
            return await self._relay_stream(
                request, trace, rid, model, h2, body, deadline, up,
                replica, tried_d, t0, journal)
        return None

    async def _relay_stream(self, request: web.Request,
                            trace: "tracing.Trace", rid: str, model: str,
                            headers: dict, body: bytes,
                            deadline: Optional[float],
                            upstream: aiohttp.ClientResponse,
                            active: Replica, tried: set, t0: float,
                            journal: _StreamJournal) -> web.StreamResponse:
        """Relay a streaming completion with the resume journal engaged.

        One iteration of the outer loop per upstream segment: the original
        stream, then — on a mid-stream death — each continuation spliced
        from another replica. The client sees a single uninterrupted SSE
        stream; when no continuation is possible the stream ends with an
        explicit error event instead of a silent EOF.
        """
        resp: Optional[web.StreamResponse] = None
        sse = False
        t_head = self.clock()
        t_first: Optional[float] = None
        relayed = 0
        resumes = 0  # re-issues consumed, capped by resume_attempts
        first: Optional[bytes] = None
        try:
            if self.hedge_ms > 0 and request.get("llmk_hedge_ok", True):
                try:
                    upstream, active, first = await self._hedge_race(
                        request, model, headers, body, deadline, upstream,
                        active, tried, trace, rid)
                except self._RELAY_ERRORS as e:
                    # every attempt died before a first byte; the hedge
                    # race already released its replicas, and nothing is
                    # on the wire yet so a plain 502 is still possible
                    active = None
                    trace.event("relay_error", error=str(e), bytes=0)
                    return web.json_response(
                        error_body(f"upstream error: {e}", "bad_gateway",
                                   "upstream_error"),
                        status=502, headers=self._rid_headers(rid))
            akey = request.get("llmk_affinity_key")
            if akey and upstream.status == 200:
                self._learn_digests(akey, upstream.headers)
            while True:  # one iteration per upstream segment
                if resp is None:
                    sse = upstream.headers.get(
                        "Content-Type", "").lower().startswith(
                            "text/event-stream")
                    resp = web.StreamResponse(status=upstream.status)
                    for k, v in upstream.headers.items():
                        if k.lower() not in HOP_BY_HOP:
                            resp.headers[k] = v
                    resp.headers.setdefault(REQUEST_ID_HEADER, rid)
                    await resp.prepare(request)
                lost: Optional[BaseException] = None
                ait = upstream.content.iter_any().__aiter__()
                while True:
                    if first is not None:
                        chunk, first = first, None
                        if not chunk:
                            continue
                    else:
                        try:
                            chunk = await ait.__anext__()
                        except StopAsyncIteration:
                            break
                        except self._RELAY_ERRORS as e:
                            lost = e
                            break
                    if t_first is None:
                        t_first = self.clock()
                        trace.add_span("first_byte", t_head, t_first)
                        request["llmk_ttft_ms"] = (t_first - t0) * 1000.0
                        self._observe_replica(
                            active, request["llmk_ttft_ms"], False)
                    relayed += len(chunk)
                    out = journal.feed(chunk) if sse else chunk
                    if out:
                        # client-side write failures propagate (client
                        # gone) — only UPSTREAM errors trigger a resume
                        await resp.write(out)
                if lost is None:
                    upstream.close()
                    break  # clean upstream EOF: relay complete
                # --- upstream died mid-stream
                active.breaker.record_failure()
                self._observe_replica(active, None, True)
                active.inflight -= 1
                tried.add(active.url)
                dead = active.url
                active = None
                upstream.close()
                trace.event("relay_error", error=str(lost), bytes=relayed,
                            replica=dead)
                if not resp.prepared:
                    return web.json_response(
                        error_body(f"upstream error: {lost}", "bad_gateway",
                                   "upstream_error"),
                        status=502, headers=self._rid_headers(rid))
                if not sse:
                    # a non-SSE upstream body (error JSON relayed verbatim):
                    # the pre-resume close-on-death contract
                    if request.transport is not None:
                        request.transport.close()
                    return resp
                if journal.finished or journal.done:
                    # the stream was semantically complete — at most the
                    # [DONE] terminator was lost; finish it ourselves
                    try:
                        if not journal.done:
                            await resp.write(b"data: [DONE]\n\n")
                        await resp.write_eof()
                    except (ConnectionResetError, OSError):
                        pass
                    return resp
                nxt = await self._resume_upstream(
                    request, model, headers, body, deadline, tried, journal,
                    rid, resumes, trace)
                if nxt is None:
                    return await self._truncate_stream(resp, model, trace)
                upstream, active, used = nxt
                resumes += used
                self.metrics["stream_resume"].labels(outcome="ok").inc()
                journal.echo_skip = journal.chars - journal.chars_at_mark
                jlog("stream_resume", request_id=rid, component="router",
                     model=model, replica=active.url,
                     prefix_tokens=len(journal.tokens),
                     echo_skip=journal.echo_skip)
                trace.event("stream_resume", replica=active.url,
                            tokens=len(journal.tokens))
            tail = journal.flush() if sse else b""
            if tail:
                await resp.write(tail)
            await resp.write_eof()
            trace.add_span("stream", t_first if t_first is not None
                           else t_head, self.clock(), bytes=relayed,
                           upstream_status=upstream.status, resumes=resumes)
            return resp
        finally:
            if active is not None:
                active.inflight -= 1
            # a client that hung up (cancellation, or resp.write raising)
            # leaves through here with the upstream still open: close it,
            # or the replica decodes the abandoned stream to its end
            upstream.close()

    async def _resume_upstream(self, request: web.Request, model: str,
                               headers: dict, body: bytes,
                               deadline: Optional[float], tried: set,
                               journal: _StreamJournal, rid: str,
                               resumes: int,
                               trace: "tracing.Trace"):
        """Re-issue a died stream to another replica with the journaled
        prefix. Returns (upstream, replica, attempts_used) on a spliceable
        200 SSE response, or None to give up (disabled, exhausted,
        non-resumable stream, no replica, or deadline spent)."""
        if not self.stream_resume:
            ok, why = False, "resume disabled"
        elif resumes >= self.resume_attempts:
            ok, why = False, f"attempts exhausted ({self.resume_attempts})"
        else:
            ok, why = journal.resumable()
        if not ok:
            jlog("stream_resume_giveup", request_id=rid, component="router",
                 model=model, reason=why)
            return None
        h = dict(headers)
        if journal.saw_data or journal.tokens:
            # the client has seen part of the stream: replay idempotently
            # with the journaled prefix (possibly empty — e.g. only the
            # role delta was delivered) and the original stream identity
            h[RESUME_TOKENS_HEADER] = ",".join(map(str, journal.tokens))
            if journal.stream_id:
                h[RESUME_STREAM_ID_HEADER] = journal.stream_id
            if journal.created is not None:
                h[RESUME_CREATED_HEADER] = str(journal.created)
        # else: nothing reached the client yet — a clean re-issue
        used = 0
        attempts_left = self.resume_attempts - resumes
        while used < attempts_left:
            if deadline is not None:
                remaining = deadline - self.clock()
                if remaining <= 0:
                    jlog("stream_resume_giveup", request_id=rid,
                         component="router", model=model, reason="deadline")
                    return None
                h[DEADLINE_HEADER] = str(int(remaining * 1000))
            # every re-issue is a retry: it draws from the model budget,
            # and an exhausted budget truncates (explicit error event)
            # instead of piling resume traffic onto a sick pool
            if not self._charge_retry(model, rid, "stream_resume"):
                jlog("stream_resume_giveup", request_id=rid,
                     component="router", model=model,
                     reason="retry budget exhausted")
                return None
            replica = self._pick(model, tried, roles=self._serve_roles(model))
            if replica is None:
                self._refund_retry(model)
                jlog("stream_resume_giveup", request_id=rid,
                     component="router", model=model,
                     reason="no healthy replica")
                return None
            used += 1
            url = f"{replica.url}/{request.match_info['path']}"
            if request.query_string:
                url += f"?{request.query_string}"
            # fresh traceparent per re-issue: the splice leg is its own
            # hop, parented under the router fragment like any other
            r_sid = tracing.new_span_id()
            h[tracing.TRACEPARENT_HEADER] = tracing.format_traceparent(
                trace.trace_id, r_sid, trace.sampled)
            t_r0 = self.clock()
            replica.inflight += 1
            try:
                up = await self._session.request(
                    request.method, url, data=body or None, headers=h)
            except self._RELAY_ERRORS:
                replica.inflight -= 1
                replica.breaker.record_failure()
                self._observe_replica(replica, None, True)
                tried.add(replica.url)
                continue
            ctype = up.headers.get("Content-Type", "").lower()
            if up.status != 200 or not ctype.startswith("text/event-stream"):
                # the replica answered but refused the splice (draining
                # 503, resume rejected 400): not a transport failure
                replica.inflight -= 1
                up.close()
                tried.add(replica.url)
                continue
            replica.breaker.record_success()
            trace.add_span("resume", t_r0, self.clock(), span_id=r_sid,
                           parent_span_id=trace.span_id,
                           replica=replica.url, attempts=used)
            return up, replica, used
        jlog("stream_resume_giveup", request_id=rid, component="router",
             model=model, reason=f"attempts exhausted ({self.resume_attempts})")
        return None

    async def _truncate_stream(self, resp: web.StreamResponse, model: str,
                               trace: "tracing.Trace") -> web.StreamResponse:
        """No continuation possible: end the stream with an explicit SSE
        error event (finish_reason=upstream_lost) instead of the silent
        EOF clients used to get, and count the loss."""
        self.metrics["stream_truncated"].labels(model=model).inc()
        if self.stream_resume:
            self.metrics["stream_resume"].labels(outcome="gave_up").inc()
        trace.event("stream_truncated", model=model)
        payload = {
            "error": {"message": "upstream connection lost mid-stream and "
                      "the stream could not be resumed",
                      "type": "upstream_error", "code": "upstream_lost"},
            "choices": [{"index": 0, "delta": {},
                         "finish_reason": "upstream_lost"}],
        }
        try:
            await resp.write(b"event: error\ndata: "
                             + json.dumps(payload).encode() + b"\n\n")
            await resp.write_eof()
        except (ConnectionResetError, OSError):
            pass
        return resp

    async def _hedge_race(self, request: web.Request, model: str,
                          headers: dict, body: bytes,
                          deadline: Optional[float],
                          upstream: aiohttp.ClientResponse, active: Replica,
                          tried: set, trace: "tracing.Trace", rid: str):
        """Tail-TTFT hedging (LLMK_HEDGE_MS): wait for the primary's first
        body byte; when it is late, race a secondary on a different
        replica and keep whichever streams first. The loser is cancelled
        and its connection closed (the replica aborts the duplicate on
        disconnect), so at most one stream ever reaches the client.
        Returns (upstream, replica, first_chunk) for the winner; raises
        the last transport error if every attempt dies before a first
        byte (both replicas already released)."""

        async def first_of(up: aiohttp.ClientResponse):
            try:
                chunk = await up.content.iter_any().__aiter__().__anext__()
            except StopAsyncIteration:
                chunk = b""
            return up, chunk

        prim = asyncio.ensure_future(first_of(upstream))
        done, _ = await asyncio.wait({prim}, timeout=self.hedge_ms / 1000.0)
        if done:
            try:
                _, chunk = prim.result()
            except self._RELAY_ERRORS:
                active.breaker.record_failure()
                self._observe_replica(active, None, True)
                active.inflight -= 1
                tried.add(active.url)
                raise
            return upstream, active, chunk
        hedge_rep = self._pick(model, tried | {active.url},
                               roles=self._serve_roles(model))
        # a hedge is a speculative retry: it draws from the same budget
        # as every other retry source, and an exhausted budget downgrades
        # to the plain single-attempt path (keep waiting on the primary)
        if hedge_rep is None or not self._charge_retry(model, rid, "hedge"):
            try:
                _, chunk = await prim
            except self._RELAY_ERRORS:
                active.breaker.record_failure()
                self._observe_replica(active, None, True)
                active.inflight -= 1
                tried.add(active.url)
                raise
            return upstream, active, chunk
        h, hedge_sid = self._hop_headers(trace, headers)
        if deadline is not None:
            remaining = deadline - self.clock()
            h[DEADLINE_HEADER] = str(max(1, int(remaining * 1000)))
        url = f"{hedge_rep.url}/{request.match_info['path']}"
        if request.query_string:
            url += f"?{request.query_string}"
        jlog("hedge_launch", request_id=rid, component="router", model=model,
             primary=active.url, hedge=hedge_rep.url)
        trace.event("hedge_launch", primary=active.url, hedge=hedge_rep.url)
        t_hedge0 = self.clock()
        hedge_rep.inflight += 1

        async def hedge_of():
            up2 = await self._session.request(
                request.method, url, data=body or None, headers=h)
            try:
                return await first_of(up2)
            except asyncio.CancelledError:
                up2.close()
                raise

        sec = asyncio.ensure_future(hedge_of())
        live = {prim: active, sec: hedge_rep}
        pending = {prim, sec}
        last_err: Optional[BaseException] = None
        while pending:
            done, pending = await asyncio.wait(
                pending, return_when=asyncio.FIRST_COMPLETED)
            # deterministic preference: the primary when both land together
            for fut in (f for f in (prim, sec) if f in done):
                rep = live[fut]
                if fut.exception() is not None:
                    last_err = fut.exception()
                    rep.breaker.record_failure()
                    self._observe_replica(rep, None, True)
                    rep.inflight -= 1
                    tried.add(rep.url)
                    continue
                up, chunk = fut.result()
                loser = sec if fut is prim else prim
                if loser in pending:
                    loser.cancel()
                    try:
                        await loser
                    except (asyncio.CancelledError, *self._RELAY_ERRORS):
                        pass
                    else:
                        lup, _ = loser.result()
                        lup.close()
                    lrep = live[loser]
                    lrep.inflight -= 1
                    if loser is sec:
                        # the losing hedge leg still reached a replica:
                        # record its hop span so that replica's fragment
                        # has a parent in the stitched waterfall
                        trace.add_span("hedge", t_hedge0, self.clock(),
                                       span_id=hedge_sid,
                                       parent_span_id=trace.span_id,
                                       replica=hedge_rep.url)
                    if loser is prim:
                        upstream.close()
                rep.breaker.record_success()
                outcome = "primary_won" if fut is prim else "hedge_won"
                self.metrics["hedged"].labels(outcome=outcome).inc()
                if fut is not prim:
                    trace.event("hedge_won", replica=rep.url)
                    trace.add_span("hedge", t_hedge0, self.clock(),
                                   span_id=hedge_sid,
                                   parent_span_id=trace.span_id,
                                   replica=rep.url)
                return up, rep, chunk
        assert last_err is not None
        raise last_err


def run_router(
    backends: "dict[str, Union[str, list[str]]]",
    default_model: Optional[str] = None,
    strict: bool = False,
    host: str = "0.0.0.0",
    port: int = 8080,
    probe_interval_s: Optional[float] = 2.0,
    adapters: Optional[dict] = None,
    stream_resume: Optional[bool] = None,
    resume_attempts: Optional[int] = None,
    hedge_ms: Optional[float] = None,
    qos: Optional[dict] = None,
    roles: Optional[dict] = None,
    handoff_retries: Optional[int] = None,
    outlier_ejection: Optional[dict] = None,
    retry_budget: Optional[dict] = None,
    prefix_affinity: Optional[dict] = None,
    tracing_cfg: Optional[dict] = None,
) -> None:
    router = Router(backends, default_model, strict, adapters=adapters,
                    probe_interval_s=probe_interval_s,
                    stream_resume=stream_resume,
                    resume_attempts=resume_attempts, hedge_ms=hedge_ms,
                    qos=qos, roles=roles, handoff_retries=handoff_retries,
                    outlier_ejection=outlier_ejection,
                    retry_budget=retry_budget,
                    prefix_affinity=prefix_affinity,
                    tracing_cfg=tracing_cfg)
    web.run_app(router.make_app(), host=host, port=port, print=None,
                handler_cancellation=True)
