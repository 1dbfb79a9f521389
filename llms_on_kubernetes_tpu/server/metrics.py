"""Minimal Prometheus-text metrics registry.

The reference had NO metrics story: vLLM's /metrics existed in-image but
nothing scraped it, and the Python gateway actively suppressed logs
(reference ramalama-models/helm-chart/templates/api-gateway.yaml:106-108;
SURVEY §5 "Metrics"). This closes that gap with a dependency-free registry
exposing the serving numbers that matter on TPU: TTFT, tokens/s, batch
occupancy, KV-page usage.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

# Stamped once at import: every exposition in this process reports the same
# start time, and uptime is derived from it at scrape time.
_PROCESS_START_WALL = time.time()


def escape_label_value(v: str) -> str:
    """Prometheus exposition escaping for label VALUES: backslash, the
    double quote, and newline must be escaped or the series line is
    unparseable (model names and replica URLs are operator input)."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _label_str(names: tuple, values: tuple) -> str:
    return ",".join(f'{n}="{escape_label_value(v)}"'
                    for n, v in zip(names, values))


class _LabeledValue:
    """One child time series of a labeled Counter/Gauge."""

    def __init__(self) -> None:
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n

    def set(self, v: float) -> None:
        self.value = v


class _Metric:
    """Shared scalar-or-labeled plumbing for Counter and Gauge.

    Without ``label_names`` the metric is a single scalar series (the
    original behavior). With ``label_names`` the parent holds child series
    keyed by label values; ``labels(**kv)`` returns (creating on first use)
    the child, which supports ``inc``/``set``.
    """

    kind = "untyped"

    def __init__(self, name: str, help_: str, registry: "Registry",
                 label_names: tuple[str, ...] = ()):
        self.name, self.help = name, help_
        self.value = 0.0
        self.label_names = tuple(label_names)
        self._children: dict[tuple[str, ...], _LabeledValue] = {}
        registry._add(self)

    def labels(self, **kv: str) -> _LabeledValue:
        key = tuple(str(kv[n]) for n in self.label_names)
        child = self._children.get(key)
        if child is None:
            child = self._children[key] = _LabeledValue()
        return child

    def labeled_value(self, **kv: str) -> Optional[float]:
        """Current value of a child series, or None if never touched."""
        key = tuple(str(kv[n]) for n in self.label_names)
        child = self._children.get(key)
        return child.value if child is not None else None

    def render(self) -> str:
        out = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} {self.kind}",
        ]
        if not self.label_names:
            out.append(f"{self.name} {self.value}")
        else:
            for key in sorted(self._children):
                lbl = _label_str(self.label_names, key)
                out.append(f"{self.name}{{{lbl}}} {self._children[key].value}")
        return "\n".join(out) + "\n"


class Counter(_Metric):
    kind = "counter"

    def inc(self, n: float = 1.0) -> None:
        self.value += n


class Gauge(_Metric):
    kind = "gauge"

    def set(self, v: float) -> None:
        self.value = v


class CallbackGauge(Gauge):
    """Gauge whose value is recomputed by ``fn()`` at every render.

    For quantities that must be fresh at scrape time without a poller:
    process uptime, sliding-window SLO ratios. A callback failure keeps
    the previous value — a scrape must never 500 because a derived
    quantity hiccupped.
    """

    def __init__(self, name: str, help_: str, registry: "Registry",
                 fn: Callable[[], float]):
        super().__init__(name, help_, registry)
        self._fn = fn

    def render(self) -> str:
        try:
            self.value = float(self._fn())
        except Exception:
            pass
        return super().render()


class _HistogramSeries:
    """One histogram time series: the bucket counts + sum + count."""

    def __init__(self, buckets: tuple[float, ...]):
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)
        # last exemplar per bucket: (trace_id, observed value, unix ts).
        # Stored per bucket so the rendered exemplar value is always within
        # its bucket's range, as OpenMetrics requires.
        self.exemplars: list = [None] * (len(buckets) + 1)
        self.total = 0.0
        self.n = 0

    def observe(self, v: float, trace_id: Optional[str] = None) -> None:
        self.total += v
        self.n += 1
        for i, b in enumerate(self.buckets):
            if v <= b:
                self.counts[i] += 1
                if trace_id:
                    self.exemplars[i] = (trace_id, v, time.time())
                return
        self.counts[-1] += 1
        if trace_id:
            self.exemplars[-1] = (trace_id, v, time.time())

    def percentile(self, q: float) -> Optional[float]:
        """Approximate percentile from bucket upper bounds (for bench/tests)."""
        if self.n == 0:
            return None
        target = q * self.n
        acc = 0
        for i, b in enumerate(self.buckets):
            acc += self.counts[i]
            if acc >= target:
                return b
        return float("inf")

    @staticmethod
    def _exemplar_suffix(ex) -> str:
        """OpenMetrics exemplar: `` # {trace_id="..."} value timestamp``.
        Appended to bucket lines only — a trace-id breadcrumb from a
        latency histogram straight to ``GET /debug/trace/<id>``."""
        if ex is None:
            return ""
        tid, v, ts = ex
        return f' # {{trace_id="{escape_label_value(tid)}"}} {v} {round(ts, 3)}'

    def _render_series(self, name: str, labels: str) -> list[str]:
        """Series lines with ``labels`` ('' or 'k="v",...') merged into the
        bucket's le label set."""
        pre = labels + "," if labels else ""
        out = []
        acc = 0
        for i, b in enumerate(self.buckets):
            acc += self.counts[i]
            out.append(f'{name}_bucket{{{pre}le="{b}"}} {acc}'
                       f"{self._exemplar_suffix(self.exemplars[i])}")
        acc += self.counts[-1]
        out.append(f'{name}_bucket{{{pre}le="+Inf"}} {acc}'
                   f"{self._exemplar_suffix(self.exemplars[-1])}")
        suffix = f"{{{labels}}}" if labels else ""
        out.append(f"{name}_sum{suffix} {self.total}")
        out.append(f"{name}_count{suffix} {self.n}")
        return out


class Histogram(_HistogramSeries):
    """Scalar-or-labeled histogram, mirroring _Metric's labels() shape.

    Without ``label_names`` the parent IS the single series (the original
    behavior). With them, ``labels(**kv)`` returns (creating on first use)
    a child series; the parent's own counters stay untouched and are not
    rendered.
    """

    def __init__(self, name: str, help_: str, buckets: tuple[float, ...],
                 registry: "Registry", label_names: tuple[str, ...] = ()):
        super().__init__(tuple(sorted(buckets)))
        self.name, self.help = name, help_
        self.label_names = tuple(label_names)
        self._children: dict[tuple[str, ...], _HistogramSeries] = {}
        registry._add(self)

    def labels(self, **kv: str) -> _HistogramSeries:
        key = tuple(str(kv[n]) for n in self.label_names)
        child = self._children.get(key)
        if child is None:
            child = self._children[key] = _HistogramSeries(self.buckets)
        return child

    def render(self) -> str:
        out = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} histogram",
        ]
        if not self.label_names:
            out += self._render_series(self.name, "")
        else:
            for key in sorted(self._children):
                out += self._children[key]._render_series(
                    self.name, _label_str(self.label_names, key))
        return "\n".join(out) + "\n"


class Registry:
    def __init__(self) -> None:
        self._metrics: list = []
        self._lock = threading.Lock()

    def _add(self, m) -> None:
        with self._lock:
            self._metrics.append(m)

    def render(self) -> str:
        with self._lock:
            return "".join(m.render() for m in self._metrics)


def build_info_metrics(registry: Registry, backend: str = "none",
                       jax_version: Optional[str] = None,
                       role: str = "both") -> dict:
    """Identity + lifetime series every exposition must carry (engine, API
    server, both routers): which build/runtime answered this scrape, when
    the process started, and how long it has been up. ``backend`` is the
    serving backend ("tpu"/"cpu" for engines, "python-router"/
    "native-router" for gateways); ``role`` is the disaggregated serving
    role ("prefill"/"decode"/"both" for engines, "router" for gateways) so
    the cluster view can tell the pools apart; ``jax_version`` defaults to
    the installed jax distribution WITHOUT importing (and thereby
    initializing) jax — routers must stay accelerator-free."""
    from llms_on_kubernetes_tpu import __version__

    if jax_version is None:
        try:
            from importlib import metadata
            jax_version = metadata.version("jax")
        except Exception:
            jax_version = "none"
    info = Gauge(
        "llm_build_info",
        "Build/runtime identity of this process (value is always 1)",
        registry, label_names=("version", "jax", "backend", "role"))
    info.labels(version=__version__, jax=jax_version, backend=backend,
                role=role).set(1)
    start = Gauge(
        "llm_process_start_time_seconds",
        "Unix time this process started", registry)
    start.set(round(_PROCESS_START_WALL, 3))
    uptime = CallbackGauge(
        "llm_process_uptime_seconds",
        "Seconds since process start (recomputed at scrape)", registry,
        lambda: round(time.time() - _PROCESS_START_WALL, 3))
    return {"build_info": info, "start_time": start, "uptime": uptime}


def trace_export_metrics(registry: Registry) -> dict:
    """Tail-sampled OTLP span-export accounting, shared by every process
    that owns a trace exporter (engine/API server and both routers). The
    invariant the names encode: a trace that is not exported is COUNTED
    dropped (by reason), never silently discarded."""
    exported = Counter(
        "llm_trace_spans_exported_total",
        "Spans handed to the OTLP exporter by outcome (ok = accepted by "
        "the collector, error = POST failed after the trace was already "
        "sampled in)", registry, label_names=("outcome",))
    dropped = Counter(
        "llm_trace_dropped_total",
        "Finished traces not exported, by reason (sampled_out = tail "
        "sampler's probabilistic drop of a boring trace, queue_full = "
        "exporter backpressure, disabled = no LLMK_OTLP_ENDPOINT)",
        registry, label_names=("reason",))
    # pre-seed so the rate() panels and the cluster merge see the series
    # before the first drop/export happens
    exported.labels(outcome="ok")
    dropped.labels(reason="sampled_out")
    return {"trace_spans_exported": exported, "trace_dropped": dropped}


def engine_metrics(registry: Registry) -> dict:
    """The standard serving metric set (SURVEY §5 gap list)."""
    m = {
        "requests_total": Counter(
            "llm_requests_total", "Requests received", registry),
        "requests_finished": Counter(
            "llm_requests_finished_total", "Requests finished", registry),
        "tokens_generated": Counter(
            "llm_tokens_generated_total", "Output tokens sampled", registry),
        "prompt_tokens": Counter(
            "llm_prompt_tokens_total", "Prompt tokens prefilled", registry),
        "preemptions": Counter(
            "llm_preemptions_total", "Requests preempted for KV memory", registry),
        "ttft": Histogram(
            "llm_ttft_seconds", "Time to first token",
            (0.01, 0.025, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0, 10.0), registry,
            label_names=("model",)),
        "e2e_latency": Histogram(
            "llm_e2e_latency_seconds",
            "Request latency, submit to finish",
            (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0),
            registry, label_names=("model",)),
        "batch_occupancy": Gauge(
            "llm_decode_batch_occupancy", "Active decode slots", registry),
        "kv_pages_used": Gauge(
            "llm_kv_pages_used", "KV pages allocated (finished requests' "
            "cached prefixes included: it stays near the pool's size)",
            registry),
        "kv_pages_live": Gauge(
            "llm_kv_pages_live", "KV pages held by live sequences: "
            "allocated and neither free nor an evictable cached prefix",
            registry),
        "waiting": Gauge(
            "llm_waiting_requests", "Requests queued for admission", registry),
        # same value as llm_waiting_requests but model-labeled: the
        # autoscaling signal (HPA Pods metric / KEDA prometheus trigger
        # per model) — deploy/manifests.py render_model_autoscaler
        "queue_depth": Gauge(
            "llm_queue_depth",
            "Requests queued for admission, per served model and serving "
            "role (the replica-autoscaling signal; the prefill pool "
            "scales on its own role's series)",
            registry, label_names=("model", "role")),
        "cold_start": Histogram(
            "llm_cold_start_seconds",
            "Startup phase durations: compile=warmup executable builds, "
            "load=checkpoint load + engine init, mesh=distributed init + "
            "device mesh, ready=process start to serving",
            (0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 30.0, 60.0, 120.0, 180.0,
             300.0, 600.0),
            registry, label_names=("phase",)),
        "prefix_hit_tokens": Gauge(
            "llm_prefix_cache_hit_tokens_total",
            "Prompt tokens served from the prefix cache", registry),
        "engine_state": Gauge(
            "llm_engine_state",
            "Serving lifecycle: 0=loading 1=serving 2=draining 3=wedged",
            registry),
        "deadline_exceeded": Counter(
            "llm_deadline_exceeded_total",
            "Requests shed at their end-to-end deadline, by phase: "
            "queue=expired while waiting (never admitted), "
            "decode=aborted in flight",
            registry, label_names=("phase",)),
        "adapter_cache_hits": Counter(
            "llm_adapter_cache_hits_total",
            "LoRA adapter requests that found their adapter already "
            "resident in a device slot", registry),
        "adapter_cache_misses": Counter(
            "llm_adapter_cache_misses_total",
            "LoRA adapter requests that had to load/upload their adapter "
            "into a device slot", registry),
        "adapter_cache_evictions": Counter(
            "llm_adapter_cache_evictions_total",
            "Resident LoRA adapters evicted from a device slot to make "
            "room (sustained high rate = cache thrash; add slots)",
            registry),
        "adapter_load": Histogram(
            "llm_adapter_load_seconds",
            "LoRA adapter load+upload latency on a cache miss "
            "(host-cached reloads are upload-only)",
            (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0),
            registry),
        "decode_steps_per_dispatch": Histogram(
            "llm_decode_steps_per_dispatch",
            "Decode steps consumed per device dispatch (fused multi-step "
            "decode window depth; 1 = the single-step path)",
            (1.0, 2.0, 4.0, 8.0, 16.0, 32.0), registry),
        "decode_early_exit": Counter(
            "llm_decode_early_exit_total",
            "Planned decode row-steps wasted because a request finished "
            "or aborted mid-window (fused multi-step decode early-exit "
            "accounting; a high rate vs llm_tokens_generated_total means "
            "decode_steps is oversized for typical generations)",
            registry),
        "spec_drafted": Counter(
            "llm_spec_drafted_total",
            "Draft tokens proposed into speculative verify windows "
            "(prompt-lookup or draft-model tier; excludes the bonus "
            "token every window commits regardless)", registry),
        "spec_accepted": Counter(
            "llm_spec_accepted_total",
            "Draft tokens accepted by the target model's verify pass "
            "(exact-match under greedy decoding)", registry),
        "spec_accept_ratio": Gauge(
            "llm_spec_accept_ratio",
            "Lifetime accepted/drafted ratio of speculative decoding "
            "(0 when speculation is off or no drafts were proposed; a "
            "low ratio on steady traffic means the drafter does not fit "
            "the workload — the engine demotes drafting adaptively)",
            registry),
        "tenant_admitted": Counter(
            "llm_tenant_admitted_total",
            "Requests admitted into a decode slot, by fair-queue tenant "
            "and priority class (first admissions only; a preemption "
            "round trip is not new throughput)",
            registry, label_names=("tenant", "priority")),
        "tenant_queue_wait": Histogram(
            "llm_tenant_queue_wait_seconds",
            "Submit-to-admission wait per fair-queue tenant — the "
            "fairness signal (one tenant's p99 diverging from the rest "
            "means its weight/priority is starving it)",
            (0.005, 0.025, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
             60.0),
            registry, label_names=("tenant",)),
        "tenant_shed": Counter(
            "llm_tenant_shed_total",
            "Requests refused with 429 by tenant, priority, and reason "
            "(overloaded = queue-depth backpressure / brownout, "
            "rate_limited = the tenant's own token-bucket limits)",
            registry, label_names=("tenant", "priority", "reason")),
        "kv_host_cache_hits": Counter(
            "llm_kv_host_cache_hits_total",
            "KV pages served from the host-RAM offload tier to a "
            "resuming/returning session (each page skips page_size "
            "tokens of re-prefill)", registry),
        "kv_host_cache_misses": Counter(
            "llm_kv_host_cache_misses_total",
            "Admissions whose prefix found no host-tier pages beyond "
            "the device cache", registry),
        "kv_host_cache_evictions": Counter(
            "llm_kv_host_cache_evictions_total",
            "Host-tier KV pages dropped by LRU capacity pressure "
            "(sustained high rate vs hits = thrash; grow "
            "kvHostCacheGB)", registry),
        "kv_upload": Histogram(
            "llm_kv_upload_seconds",
            "Host->device KV page upload latency per resuming "
            "admission (stage + dispatch of the re-upload that replaces "
            "re-prefill)",
            (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 1.0),
            registry),
        "kv_bytes_per_token": Gauge(
            "llm_kv_bytes_per_token",
            "Device KV-cache bytes per cached token across all layers, "
            "both K and V, scales included (int8 pages roughly halve "
            "this vs bf16)", registry),
        "mfu": Gauge(
            "llm_mfu_ratio",
            "Model FLOPs utilization over the trailing minute of "
            "dispatches, computed when /metrics is scraped: achieved "
            "FLOP/s (2 * active params per planned token, wasted rows "
            "included) over the accelerator's nominal dense peak "
            "(PaLM-style MFU; never set on a CPU, which has no peak)",
            registry),
        "mbu": Gauge(
            "llm_mbu_ratio",
            "Memory-bandwidth utilization over the trailing minute: "
            "achieved HBM traffic (weight streaming per fused window + "
            "KV page writes) over the accelerator's nominal peak "
            "bytes/s — the decode-side twin of llm_mfu_ratio", registry),
        "chip_seconds": Counter(
            "llm_chip_seconds_total",
            "Goodput-ledger chip time by outcome: prefill/decode = "
            "attributed to live streams, spec_waste = rejected "
            "speculative tails, early_exit = masked/abandoned fused-"
            "window rows, idle = device gaps between dispatches; the "
            "phases sum to the ledger's wall-clock window "
            "(conservation is CI-gated)",
            registry, label_names=("phase",)),
        "dispatches": Counter(
            "llm_dispatches_total",
            "Device dispatches booked by the ledger, by kind of step "
            "(prefill, chunk = a chunked prefill's chain, decode = one "
            "fused window, spec = a speculative verify)",
            registry, label_names=("kind",)),
        "dispatch_device_seconds": Counter(
            "llm_dispatch_device_seconds_total",
            "Seconds the device held dispatches of each kind: from the "
            "device being free for one (the previous one's completion, "
            "or its own launch) to its read landing, in launch order",
            registry, label_names=("kind",)),
        "dispatch_behind_seconds": Counter(
            "llm_dispatch_behind_seconds_total",
            "Seconds dispatches of each kind sat enqueued behind earlier "
            "ones before the device was free for them",
            registry, label_names=("kind",)),
        "dispatch_enqueue_seconds": Counter(
            "llm_dispatch_enqueue_seconds_total",
            "Host seconds inside the jitted calls that launched "
            "dispatches of each kind (a re-trace shows here)",
            registry, label_names=("kind",)),
        "decode_emit_seconds": Counter(
            "llm_decode_emit_seconds_total",
            "Seconds decode windows of each kind (decode, spec) waited, "
            "complete on the device, for their tokens to be put on the "
            "requests' queues at the end of the scheduler step that "
            "collected them (the engine thread waking, collecting, the "
            "rest of the step); over llm_dispatches_total of the kind it "
            "is the hand-over lag a window, which every token of it pays",
            registry, label_names=("kind",)),
        "device_idle_seconds": Counter(
            "llm_device_idle_seconds_total",
            "Seconds the device sat between dispatches, by what the host "
            "was doing: no_work (nothing active or waiting), compile (the "
            "next dispatch re-traced), scheduling (anything else); sums "
            "to llm_chip_seconds_total{phase=\"idle\"}",
            registry, label_names=("host",)),
        "tenant_chip_seconds": Counter(
            "llm_tenant_chip_seconds_total",
            "Chip time attributed per fair-queue tenant and ledger "
            "phase — the chargeback / capacity-planning series (waste "
            "phases bill the tenant whose speculation or early exit "
            "burned the window)",
            registry, label_names=("tenant", "phase")),
        "first_tokens": Counter(
            "llm_first_tokens_total",
            "First tokens handed to their requests, by who handed them "
            "over: backpressure=the engine thread, from inside its wait at "
            "full pipeline depth, as the token's read landed; step=at the "
            "end of a scheduler step",
            registry, label_names=("delivered",)),
        "decode_launches": Counter(
            "llm_decode_launches_total",
            "Decode steps launched, by the rule that launched each: "
            "timed=a measured lead before the device was estimated to run "
            "out of work, work still on it; late=the device was already "
            "free (the estimate overshot or the host came late; the gap "
            "is llm_device_idle_seconds_total{host=\"scheduling\"}); "
            "admission=directly behind a prefill, whose sampled tokens it "
            "merges; depth=as soon as the pipeline had room (a shape that "
            "never ran, a step no longer than two leads, multihost). "
            "late over all is the miss rate",
            registry, label_names=("when",)),
        "decode_windows": Counter(
            "llm_decode_windows_total",
            "Decode windows launched, by what their rows asked of the "
            "sampler (booked on the host from the window's packed rows, "
            "with the predicate the executable evaluates on them): "
            "shaped=a live row carries a presence or frequency penalty or "
            "a logit_bias entry, so every token step of the window keeps "
            "the penalty counts, applies the penalties and scatters the "
            "biases over [slots, vocab]; plain=no live row does, and the "
            "window's token steps skip all three",
            registry, label_names=("sampler",)),
        # the expert layers (ops/moe.py), booked by the engine where a
        # dispatch's tokens are read (Engine._book_moe); kind is the
        # dispatch's: prefill | chunk | decode. All five are sums over
        # token steps (a prefill or chunk is one) and expert layers
        "moe_experts_touched": Counter(
            "llm_moe_experts_touched_total",
            "Experts that got at least one row, summed over token steps "
            "and expert layers; over llm_moe_expert_slots_total it is the "
            "share of its experts' weights a step reads",
            registry, label_names=("kind",)),
        "moe_expert_slots": Counter(
            "llm_moe_expert_slots_total",
            "Experts there were to touch: token steps x expert layers x "
            "experts HELD here (all of them, unless the model holds one "
            "chip's share; a window step in which no row was live is no "
            "step)",
            registry, label_names=("kind",)),
        "moe_routed_rows": Counter(
            "llm_moe_routed_rows_total",
            "(token, expert) pairs routed, to any expert: live rows x "
            "experts per token x expert layers, summed over token steps",
            registry, label_names=("kind",)),
        "moe_held_rows": Counter(
            "llm_moe_held_rows_total",
            "(token, expert) pairs that fell on experts held here, the rows "
            "the grouped product multiplied; equals "
            "llm_moe_routed_rows_total unless the model holds a share of "
            "its experts (then about held / experts of it at even routing)",
            registry, label_names=("kind",)),
        "moe_fullest_expert_rows": Counter(
            "llm_moe_fullest_expert_rows_total",
            "Rows of the fullest expert of each expert layer, summed; over "
            "llm_moe_mean_expert_rows_total it is the load imbalance "
            "(1 = even)", registry, label_names=("kind",)),
        "moe_mean_expert_rows": Counter(
            "llm_moe_mean_expert_rows_total",
            "Rows of the mean expert of each expert layer (routed rows / "
            "experts), summed", registry, label_names=("kind",)),
        "mla_tokens": Counter(
            "llm_mla_tokens_total",
            "Tokens a latent-attention model (MLA) attended by each path: "
            "prefill=a bucket over its own rows, expanded to heads; "
            "chunk=a chunk over cached latent rows and its own, expanded; "
            "decode=one token a step over cached rows, absorbed. 0 for a "
            "model without latent attention",
            registry, label_names=("path",)),
        "latent_cache_bytes": Gauge(
            "llm_latent_cache_bytes",
            "Device bytes of the latent pool (one latent row a token a "
            "layer, no V side) of a latent-attention model; 0 for a model "
            "with K and V pools", registry),
        "path_tokens": Counter(
            "llm_path_tokens_total",
            "Real tokens the model ran, by the path that ran them, whatever "
            "the model: prefill=a bucket from an empty cache and state; "
            "chunk=a chunk over its slot's cached positions and state; "
            "decode=one step a token inside the decode window",
            registry, label_names=("path",)),
        "ssm_positions": Counter(
            "llm_ssm_positions_total",
            "Positions the Mamba layers' scan or step ran over by each "
            "path, a bucket's padding included, and a decode window's idle "
            "rows where the state-space step is the XLA step over every "
            "slot (the kernel visits the rows live at the launch alone); "
            "over llm_path_tokens_total it is the work done per real "
            "token. 0 for a model without Mamba layers",
            registry, label_names=("path",)),
        "attn_window_rows": Counter(
            "llm_attn_window_rows_total",
            "Rows of cached keys and values in the layers that attend inside "
            "a sliding window, summed over every planned token step of every "
            "live row (counted on the host where a decode window is planned): "
            "cached=rows the pool holds for those layers (layers x length); "
            "reached=rows those layers can still read (layers x min(length, "
            "window)). reached over cached is the share of the window "
            "layers' held rows that any later token can read; 0 for a model "
            "without window layers", registry, label_names=("rows",)),
        "conv_state_bytes": Gauge(
            "llm_conv_state_bytes",
            "Device bytes of the per-slot state that conv layers (their "
            "short-convolution window) or Mamba layers (their convolution "
            "window and float32 state-space state) keep beside the KV pool "
            "(0 for a model without them)", registry),
        "prefix_reuse_skipped": Counter(
            "llm_prefix_reuse_skipped_total",
            "Admissions that adopted no cached prefix though the prefix "
            "cache was on, by reason: recurrent_state=the model has conv or "
            "Mamba layers, and a cached page holds no such state at its end",
            registry, label_names=("why",)),
        "auto_profile": Counter(
            "llm_auto_profile_total",
            "Automatic bounded profiler captures triggered by the "
            "step-time anomaly watchdog (EWMA + z-score over per-"
            "dispatch device time; rate-limited by anomalyProfile "
            "cooldown)",
            registry, label_names=("reason",)),
    }
    m.update(trace_export_metrics(registry))
    # pre-seed the watchdog counter's only known reason at zero: a
    # labeled counter with no children exports no samples, so the
    # dashboard's rate() panel and the router's /metrics/cluster merge
    # would not see the series until the first trigger
    m["auto_profile"].labels(reason="step_anomaly")
    for delivered in ("backpressure", "step"):
        m["first_tokens"].labels(delivered=delivered)
    # likewise the dispatch counters, for every kind and host there is
    from llms_on_kubernetes_tpu.engine.ledger import (
        DECODE_LAUNCH_RULES, IDLE_HOSTS, KINDS, MOE_STATS, SAMPLERS,
    )

    m["prefix_reuse_skipped"].labels(why="recurrent_state")
    for path in ("prefill", "chunk", "decode"):
        m["mla_tokens"].labels(path=path)
        m["path_tokens"].labels(path=path)
        m["ssm_positions"].labels(path=path)
    for rows in ("cached", "reached"):
        m["attn_window_rows"].labels(rows=rows)
    for kind in ("prefill", "chunk", "decode"):
        for stat in MOE_STATS:
            m["moe_" + stat].labels(kind=kind)
    for when in DECODE_LAUNCH_RULES:
        m["decode_launches"].labels(when=when)
    for sampler in SAMPLERS:
        m["decode_windows"].labels(sampler=sampler)
    for kind in KINDS:
        for series in ("dispatches", "dispatch_device_seconds",
                       "dispatch_behind_seconds", "dispatch_enqueue_seconds"):
            m[series].labels(kind=kind)
    for kind in ("decode", "spec"):
        m["decode_emit_seconds"].labels(kind=kind)
    for host in IDLE_HOSTS:
        m["device_idle_seconds"].labels(host=host)
    return m


class ColdStartRecorder:
    """Collects startup-phase durations BEFORE a metrics registry exists.

    The cold-start phases (mesh init, checkpoint load, warmup compile)
    happen in ``cli.py serve`` long before ``OpenAIServer`` builds its
    registry, so the timings park here and are drained into the
    ``llm_cold_start_seconds{phase=...}`` histogram when the server
    constructs. A module-level singleton (``cold_start``) because process
    startup is inherently a singleton; tests reset it via ``reset()``.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self._phases: list[tuple[str, float]] = []

    def reset(self) -> None:
        with self._lock:
            self._t0 = time.monotonic()
            self._phases = []

    def record(self, phase: str, seconds: float) -> None:
        with self._lock:
            self._phases.append((phase, float(seconds)))

    def phase(self, name: str):
        """Context manager timing one startup phase."""
        recorder = self

        class _Phase:
            def __enter__(self):
                self._t = time.monotonic()
                return self

            def __exit__(self, *exc):
                recorder.record(name, time.monotonic() - self._t)
                return False

        return _Phase()

    def elapsed(self) -> float:
        """Seconds since process start (or the last reset)."""
        with self._lock:
            return time.monotonic() - self._t0

    def drain(self) -> list[tuple[str, float]]:
        with self._lock:
            phases, self._phases = self._phases, []
            return phases


cold_start = ColdStartRecorder()


def router_metrics(registry: Registry) -> dict:
    """Gateway-side metric set (replica routing + failover visibility)."""
    return {
        "replica_healthy": Gauge(
            "llm_replica_healthy",
            "Active /ready probe verdict per replica (1=routable), with "
            "its serving role — a wedged prefill pool is visible without "
            "hiding healthy decode replicas",
            registry, label_names=("model", "replica", "role")),
        "breaker_open": Gauge(
            "llm_router_breaker_open",
            "Circuit-breaker verdict per replica (1=open/half-open probe "
            "pending, 0=admitting), per serving role",
            registry, label_names=("model", "replica", "role")),
        "requests_total": Counter(
            "llm_router_requests_total",
            "Requests the router accepted, by resolved model — the "
            "demand signal that wakes a scaled-to-zero model (its "
            "engines emit no llm_queue_depth while no replica runs)",
            registry, label_names=("model",)),
        "failover": Counter(
            "llm_failover_total",
            "Requests retried on a different replica after a "
            "connect-phase failure", registry),
        "unknown_model_fallback": Counter(
            "llm_router_unknown_model_fallback_total",
            "Requests naming an unknown model that were routed to the "
            "default backend (strict=false)", registry),
        "deadline_rejected": Counter(
            "llm_router_deadline_rejected_total",
            "Requests rejected at the gateway with an already-expired "
            "deadline", registry),
        "cluster_scrape_errors": Counter(
            "llm_cluster_scrape_errors_total",
            "Replica /metrics scrapes that failed during /metrics/cluster "
            "aggregation (unreachable replica, bad exposition)", registry),
        "stream_resume": Counter(
            "llm_stream_resume_total",
            "Journaled SSE streams whose upstream died mid-relay, by "
            "outcome: ok=continuation spliced from another replica "
            "(invisible to the client), gave_up=resume disabled, "
            "exhausted, or impossible (stream truncated)",
            registry, label_names=("outcome",)),
        "hedged": Counter(
            "llm_hedged_requests_total",
            "Streams whose first byte outran LLMK_HEDGE_MS so a secondary "
            "was raced on another replica, by which attempt won "
            "(primary_won / hedge_won)",
            registry, label_names=("outcome",)),
        "stream_truncated": Counter(
            "llm_stream_truncated_total",
            "Streams that died mid-relay and could not be resumed: the "
            "client got a final SSE error event "
            "(finish_reason=upstream_lost) and a closed stream",
            registry, label_names=("model",)),
        "handoff": Counter(
            "llm_handoff_total",
            "Disaggregated prefill->decode handoffs by outcome: ok=first "
            "decode replica adopted the pages, retried=a later decode "
            "replica did, reprefill=the decode replica could not adopt "
            "and re-prefilled the prompt (degraded, correct), "
            "fallback_colocated=the two-hop flow fell back to a "
            "colocated replica",
            registry, label_names=("outcome",)),
        "handoff_seconds": Histogram(
            "llm_handoff_seconds",
            "Prefill-hop start to decode-hop response head for "
            "disaggregated two-hop requests (ticket + KV adoption time)",
            (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0),
            registry),
        "tenant_requests": Counter(
            "llm_tenant_requests_total",
            "Proxied requests by QoS tenant and resolved priority class "
            "(counted at the gateway before rate-limit/brownout checks)",
            registry, label_names=("tenant", "priority")),
        "tenant_router_shed": Counter(
            "llm_tenant_router_shed_total",
            "Requests the gateway refused with 429, by tenant, priority, "
            "and reason (rate_limited = the tenant's token buckets, "
            "overloaded = the adaptive brownout ladder)",
            registry, label_names=("tenant", "priority", "reason")),
        "tenant_tokens": Counter(
            "llm_tenant_tokens_total",
            "Generated-token budget charged per tenant at admission "
            "(max_tokens or the default charge — what the "
            "tokens-per-minute bucket meters)",
            registry, label_names=("tenant",)),
        "tenant_degraded": Counter(
            "llm_tenant_degraded_total",
            "Requests admitted in degraded mode under brownout (clamped "
            "max_tokens, hedging disabled), by tenant and priority",
            registry, label_names=("tenant", "priority")),
        "quarantined": Gauge(
            "llm_replica_quarantined",
            "Gray-failure quarantine verdict per replica (1=ejected from "
            "P2C candidate sets, serving only shadow traffic), by the "
            "outlier dimension that tripped it (latency|errors)",
            registry, label_names=("model", "replica", "reason")),
        "outlier_ejections": Counter(
            "llm_outlier_ejections_total",
            "Replicas quarantined by the latency/error outlier detector, "
            "by reason (latency = TTFT EWMA z-score over peers, errors = "
            "error-rate EWMA z-score)",
            registry, label_names=("reason",)),
        "retry_budget_exhausted": Counter(
            "llm_retry_budget_exhausted_total",
            "Retries (connect failover, stream resume, hedges, handoff "
            "retries) refused because the per-model retry budget was "
            "exhausted — the anti-retry-storm throttle", registry),
        "affinity_hits": Counter(
            "llm_affinity_hits_total",
            "Requests the prefix-affinity layer placed on a cache-bearing "
            "replica: the rendezvous-pinned one, or a peer whose "
            "advertised digest filter claimed the request's prefix chain",
            registry, label_names=("model",)),
        "affinity_fallback": Counter(
            "llm_affinity_fallback_total",
            "Affinity-keyed requests that fell back to plain P2C, by "
            "reason: unhealthy = pinned replica down/breaker-open, "
            "quarantined = pinned replica gray-ejected, overloaded = "
            "pinned replica's inflight beyond the brownout guard, miss = "
            "request had no affinity key (no prompt prefix)",
            registry, label_names=("model", "reason")),
        "prefix_filter_age": Gauge(
            "llm_prefix_filter_age_seconds",
            "Seconds since the replica's digest-membership filter was "
            "last refreshed from its /ready advertisement (stale filters "
            "degrade cache-aware placement to pure rendezvous)",
            registry, label_names=("model", "replica")),
        **trace_export_metrics(registry),
    }
