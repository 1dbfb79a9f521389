"""Shipping monitoring artifacts: Prometheus alert rules + Grafana dashboard.

Telemetry that nobody alerts on is a dashboard screenshot, not monitoring.
This module is the single source of truth for the two artifacts the
deployment ships alongside the serving topology:

- ``alert_rules()``: a Prometheus rule file (dict form) with the
  multi-window SLO burn-rate alerts over the router's ``llm_slo_*``
  gauges, a wedged-engine page on ``llm_engine_state``, replica-health
  and cluster-scrape-error tickets;
- ``grafana_dashboard()``: a Grafana dashboard (dict form) for the same
  series plus the runtime telemetry (device memory, compile cache,
  kernel-vs-host step split) added in this PR.

``render_monitoring(spec)`` wraps both in ConfigMaps so
``deploy.manifests.render_manifests`` ships them with everything else.
The Helm charts carry byte-identical copies under
``helm-chart/files/`` (templates/monitoring.yaml mounts them via
``.Files.Get``); ``scripts/check_monitoring.py`` regenerates those
copies and CI fails if they drift from this module.

Every ``llm_*`` name referenced by an alert expression must be a series
this repo actually emits — ``scripts/check_monitoring.py`` cross-checks
them against ``scripts/metrics_lint.known_emitted_names()`` so a renamed
metric can't silently orphan its alert.
"""

from __future__ import annotations

import json
import re
from typing import Any

ALERT_RULES_CONFIGMAP = "llmk-alert-rules"
ALERT_RULES_KEY = "llmk-alerts.yaml"
DASHBOARD_CONFIGMAP = "llmk-grafana-dashboard"
DASHBOARD_KEY = "llmk-dashboard.json"

_METRIC_NAME_RE = re.compile(r"\bllm_[a-z0-9_]+")


def alert_rules() -> dict[str, Any]:
    """Prometheus rule file covering the SLOs and the failure modes the
    fault-tolerance PRs introduced detection for.

    Burn-rate thresholds follow the standard multi-window pairing
    (SRE workbook ch.5): a fast window that pages when the monthly
    budget would be gone in hours, and a slow window that tickets a
    steady leak. The ``llm_slo_*`` gauges are already windowed by the
    router (LLMK_SLO_WINDOW_S), so the rules use plain ``for:`` holds
    rather than recording-rule window math.
    """
    return {
        "groups": [
            {
                "name": "llmk-slo",
                "rules": [
                    {
                        "alert": "LLMKErrorBudgetFastBurn",
                        "expr": "llm_slo_error_budget_burn_rate > 14",
                        "for": "5m",
                        "labels": {"severity": "page"},
                        "annotations": {
                            "summary": "error budget burning >14x",
                            "description": (
                                "Availability error budget on "
                                "{{ $labels.instance }} is burning at "
                                "{{ $value }}x the sustainable rate; at "
                                "14x a 30-day budget is gone in ~2 days."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKErrorBudgetSlowBurn",
                        "expr": "llm_slo_error_budget_burn_rate > 2",
                        "for": "1h",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "error budget burning >2x",
                            "description": (
                                "Sustained burn rate {{ $value }}x on "
                                "{{ $labels.instance }} will exhaust the "
                                "budget well before the window ends."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKTTFTObjectiveMissed",
                        "expr": "llm_slo_ttft_ok_ratio < 0.95",
                        "for": "10m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "TTFT SLO below target",
                            "description": (
                                "Only {{ $value }} of recent requests met "
                                "the TTFT objective (target 0.95) on "
                                "{{ $labels.instance }}."
                            ),
                        },
                    },
                ],
            },
            {
                "name": "llmk-serving",
                "rules": [
                    {
                        "alert": "LLMKEngineWedged",
                        # state enum: 0 idle, 1 active, 2 draining, 3 wedged
                        # (server/metrics.py llm_engine_state)
                        "expr": "llm_engine_state == 3",
                        "for": "1m",
                        "labels": {"severity": "page"},
                        "annotations": {
                            "summary": "engine wedged (watchdog)",
                            "description": (
                                "Engine on {{ $labels.instance }} has "
                                "reported the wedged state for 1m; decode "
                                "progress has stalled past the watchdog "
                                "budget."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKReplicaUnhealthy",
                        "expr": "llm_replica_healthy == 0",
                        "for": "2m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "replica failing health probes",
                            "description": (
                                "Router marks replica "
                                "{{ $labels.replica }} of model "
                                "{{ $labels.model }} unhealthy; traffic "
                                "is failing over to peers."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKClusterScrapeErrors",
                        "expr": (
                            "rate(llm_cluster_scrape_errors_total[5m])"
                            " > 0"
                        ),
                        "for": "10m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "cluster metrics aggregation "
                                       "degraded",
                            "description": (
                                "/metrics/cluster on "
                                "{{ $labels.instance }} has been failing "
                                "to scrape at least one replica for 10m; "
                                "the merged view is incomplete."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKAdapterThrash",
                        "expr": (
                            "rate(llm_adapter_cache_evictions_total[5m])"
                            " > 0.5"
                        ),
                        "for": "10m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "LoRA adapter cache thrashing",
                            "description": (
                                "Engine on {{ $labels.instance }} is "
                                "evicting adapters faster than one every "
                                "two seconds for 10m; the working set of "
                                "adapters exceeds the device slots "
                                "(raise adapterSlots or split tenants)."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKKVHostCacheThrash",
                        # pages churn through the host tier without ever
                        # being re-used: the parked-session working set
                        # exceeds the tier, so spills evict pages faster
                        # than resumes consume them
                        "expr": (
                            "rate(llm_kv_host_cache_evictions_total[10m])"
                            " > 1 and rate("
                            "llm_kv_host_cache_hits_total[10m]) < 0.1 * "
                            "rate(llm_kv_host_cache_evictions_total[10m])"
                        ),
                        "for": "15m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "host KV offload tier thrashing",
                            "description": (
                                "Engine on {{ $labels.instance }} is "
                                "evicting host-tier KV pages much faster "
                                "than resuming sessions re-use them; "
                                "parked sessions age out before they "
                                "return. Raise kvHostCacheGB (and the "
                                "pod memory request) or accept "
                                "re-prefills for long-idle sessions."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKColdStartSlow",
                        # phase="ready" is process start -> taking
                        # traffic; with the persistent compile cache a
                        # warm restart should be far under this
                        "expr": (
                            "histogram_quantile(0.95, rate("
                            'llm_cold_start_seconds_bucket'
                            '{phase="ready"}[30m])) > 180'
                        ),
                        "for": "5m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "replicas starting too slowly "
                                       "to absorb spikes",
                            "description": (
                                "p95 cold start (start to ready) is "
                                "{{ $value }}s over the last 30m; "
                                "scale-out arrives too late to help a "
                                "spike. Check the persistent compile "
                                "cache (JAX_COMPILATION_CACHE_DIR on the "
                                "weight PVC) and weight-load times."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKQueueSaturated",
                        # 2x the default autoscaling queueDepthTarget (8)
                        "expr": "llm_queue_depth > 16",
                        "for": "10m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "admission queue saturated",
                            "description": (
                                "Model {{ $labels.model }} on "
                                "{{ $labels.instance }} has held more "
                                "than twice the autoscaling target of "
                                "queued requests for 10m; the "
                                "autoscaler is at its ceiling, not "
                                "reacting, or scale-out is too slow."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKHandoffDegraded",
                        # disaggregated prefill/decode only: handoffs
                        # that miss the fast path — decode re-prefilling
                        # from scratch or the router falling back to a
                        # colocated replica — still serve correctly, but
                        # burn the chip-time disaggregation was meant to
                        # save. A sustained degraded fraction means the
                        # host tier is evicting pages before adoption or
                        # the decode pool is unreachable.
                        "expr": (
                            "sum(rate(llm_handoff_total{outcome=~"
                            '"reprefill|fallback_colocated"}[10m])) > '
                            "0.2 * sum(rate(llm_handoff_total[10m]))"
                        ),
                        "for": "15m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "KV handoffs degrading to "
                                       "re-prefill / colocated fallback",
                            "description": (
                                "More than 20% of prefill->decode KV "
                                "handoffs have missed the fast path for "
                                "15m (llm_handoff_total outcomes "
                                "reprefill + fallback_colocated). "
                                "Streams still complete, but decode "
                                "replicas are repeating prefill work. "
                                "Check decode-pool health/breakers, "
                                "kvHostCacheGB pressure on the prefill "
                                "pool, and LLMK_HANDOFF_RETRIES."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKStreamLoss",
                        # any truncation at all is a client that watched
                        # its generation die — the journal/resume path
                        # exists precisely so this stays at zero
                        "expr": (
                            "increase(llm_stream_truncated_total[10m]) > 0"
                        ),
                        "for": "10m",
                        "labels": {"severity": "page"},
                        "annotations": {
                            "summary": "client-visible stream truncations",
                            "description": (
                                "Streams for model {{ $labels.model }} "
                                "were truncated mid-generation for 10m "
                                "(upstream died and no resume was "
                                "possible). Check replica churn and "
                                "llm_stream_resume_total{outcome="
                                "\"gave_up\"} — exhausted resume "
                                "attempts, expired deadlines, or "
                                "non-resumable streams (multi-choice/"
                                "logprobs) are the usual causes."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKTenantStarvation",
                        # the fair scheduler's whole contract: no tenant
                        # waits unboundedly while peers are served. p99
                        # admission wait far beyond the starvation-aging
                        # threshold (qos_starvation_s, default 5s) means
                        # weights/priorities are misconfigured or the
                        # fleet is undersized for the admitted mix
                        "expr": (
                            "histogram_quantile(0.99, sum by (le, tenant)"
                            " (rate("
                            "llm_tenant_queue_wait_seconds_bucket[5m])))"
                            " > 30"
                        ),
                        "for": "10m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "tenant starving in the "
                                       "admission queue",
                            "description": (
                                "Tenant {{ $labels.tenant }} has a p99 "
                                "queue wait above 30s for 10m while the "
                                "engine keeps admitting; its fair-share "
                                "weight is too small for its load, or "
                                "higher-priority traffic plus brownout "
                                "shedding is not relieving pressure."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKSpecLowAccept",
                        # advisory: speculation is burning verify FLOPs
                        # without paying for itself. The engine demotes
                        # drafting adaptively, so this is a tuning
                        # signal (switch ngram <-> draft tier, or turn
                        # speculation off for this traffic), never a
                        # correctness problem — greedy outputs are
                        # bit-identical either way.
                        "expr": (
                            "rate(llm_spec_accepted_total[15m]) / "
                            "rate(llm_spec_drafted_total[15m]) < 0.2 "
                            "and rate(llm_spec_drafted_total[15m]) > 1"
                        ),
                        "for": "30m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "speculative decoding accept "
                                       "ratio persistently low",
                            "description": (
                                "Fewer than 20% of drafted tokens on "
                                "{{ $labels.instance }} survive the "
                                "verify pass over 30m. The drafter does "
                                "not fit this traffic; consider the "
                                "draft-model tier (draft:) for free-form "
                                "chat, prompt-lookup (speculation: "
                                "ngram) for RAG/code/summarization, or "
                                "disabling speculation for this model."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKGoodputCollapse",
                        # the goodput ledger's headline failure mode:
                        # chips are busy (or idle) but streams are not
                        # being served — most chip time going to waste
                        # phases or idle gaps WHILE work is queued. The
                        # per-phase breakdown and any auto-profile
                        # capture (llm_auto_profile_total) say where the
                        # time went.
                        "expr": (
                            "sum(rate(llm_chip_seconds_total"
                            '{phase=~"spec_waste|early_exit|idle"}[10m]))'
                            " / sum(rate(llm_chip_seconds_total[10m]))"
                            " > 0.6 and on() sum(llm_queue_depth) > 4"
                        ),
                        "for": "10m",
                        "labels": {"severity": "page"},
                        "annotations": {
                            "summary": "chip time mostly wasted/idle "
                                       "while requests queue",
                            "description": (
                                "Over 60% of ledger chip-seconds are "
                                "speculative rejected tails, early-exit "
                                "rows, or idle gaps for 10m while the "
                                "admission queue is non-empty. Serving "
                                "capacity is being burned without "
                                "producing stream tokens: check the "
                                "spec accept ratio, decode_steps sizing "
                                "vs typical generations, and the "
                                "flight recorder / auto-profile capture "
                                "for the slow phase."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKReplicaQuarantined",
                        # gray failure: the replica answers health probes
                        # but its in-band TTFT/error EWMA is a z-score
                        # outlier vs peers, so the router quarantined it.
                        # Traffic fails over; a ticket because the pod
                        # needs a human look (probes will NOT catch it)
                        "expr": "llm_replica_quarantined == 1",
                        "for": "5m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "replica quarantined as a "
                                       "gray-failure outlier",
                            "description": (
                                "Router quarantined replica "
                                "{{ $labels.replica }} of model "
                                "{{ $labels.model }} as a "
                                "{{ $labels.reason }} outlier vs its "
                                "peers while its health probes stayed "
                                "green. Shadow traffic will readmit it "
                                "if it recovers; a quarantine that "
                                "holds for hours is a degraded pod "
                                "(bad node, throttled NIC, sick HBM) "
                                "that needs replacing."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKRetryBudgetExhausted",
                        # sustained exhaustion = the cluster is in (or
                        # one failover away from) a retry storm: enough
                        # primaries are failing that the budget cannot
                        # cover their retries. Page — this is the
                        # metastable-failure guard actively shedding
                        "expr": (
                            "rate(llm_retry_budget_exhausted_total[5m])"
                            " > 0.1"
                        ),
                        "for": "10m",
                        "labels": {"severity": "page"},
                        "annotations": {
                            "summary": "retry budget exhausted — "
                                       "requests shedding instead of "
                                       "retrying",
                            "description": (
                                "The router on {{ $labels.instance }} "
                                "has been refusing retries (code="
                                "retry_budget_exhausted) for 10m: "
                                "failures are arriving faster than the "
                                "budget refills, the signature of a "
                                "fleet-wide problem a retry storm "
                                "would only amplify. Find the failing "
                                "replicas (llm_replica_quarantined, "
                                "llm_replica_healthy, breaker states) "
                                "instead of raising the budget."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKAffinityDegraded",
                        # cache-aware routing is configured but most
                        # keyed requests are falling back to plain P2C:
                        # pinned replicas unhealthy/quarantined/hot, or
                        # prompts that never produce a key. Prefill is
                        # being re-paid across the fleet — a ticket, not
                        # a page: serving still works, just slower.
                        "expr": (
                            "sum(rate(llm_affinity_fallback_total[15m]))"
                            " / (sum(rate(llm_affinity_hits_total[15m]))"
                            " + sum(rate("
                            "llm_affinity_fallback_total[15m])))"
                            " > 0.5"
                        ),
                        "for": "15m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "prefix-affinity routing mostly "
                                       "falling back to P2C",
                            "description": (
                                "Over half of affinity-keyed requests "
                                "fell back to plain P2C for 15m, so "
                                "prefix caches are going cold and "
                                "prefill is re-paid. Break down "
                                "llm_affinity_fallback_total by reason: "
                                "unhealthy/quarantined pins mean sick "
                                "replicas, overloaded means the pool is "
                                "too hot for pinning, miss means the "
                                "workload's prompts never form a key "
                                "(consider disabling the layer)."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKTraceDropping",
                        # the tail sampler guarantees errors/slow/multi-
                        # hop traces always export; drops beyond the
                        # deliberate reasons (sampled_out, disabled)
                        # mean the exporter queue is overflowing or the
                        # collector is rejecting batches — waterfalls
                        # for exactly the requests being debugged go
                        # missing. Ticket: observability gap, not an
                        # availability problem.
                        "expr": (
                            "sum(rate(llm_trace_dropped_total"
                            "{reason=\"queue_full\"}[10m]))"
                            " + sum(rate(llm_trace_spans_exported_total"
                            "{outcome=\"error\"}[10m])) > 0.1"
                        ),
                        "for": "10m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "trace spans being dropped — "
                                       "waterfalls incomplete",
                            "description": (
                                "Trace export on {{ $labels.instance }} "
                                "is losing spans: the OTLP queue is "
                                "overflowing (reason=queue_full) or the "
                                "collector is rejecting batches "
                                "(outcome=error). Tail-sampled traces "
                                "(errors, slow, multi-hop) are exactly "
                                "the ones an investigation needs. Check "
                                "collector health and the "
                                "tracing.otlpEndpoint value; lower "
                                "tracing.sample if volume is the cause."
                            ),
                        },
                    },
                    {
                        "alert": "LLMKDeadlineExceeded",
                        "expr": (
                            "rate(llm_deadline_exceeded_total[5m]) > 1"
                        ),
                        "for": "5m",
                        "labels": {"severity": "ticket"},
                        "annotations": {
                            "summary": "requests blowing their deadline",
                            "description": (
                                "More than one request per second on "
                                "{{ $labels.instance }} is exceeding its "
                                "end-to-end deadline."
                            ),
                        },
                    },
                ],
            },
        ],
    }


def _panel(panel_id: int, title: str, exprs: list[str],
           x: int, y: int, unit: str = "short") -> dict[str, Any]:
    return {
        "id": panel_id,
        "title": title,
        "type": "timeseries",
        "gridPos": {"h": 8, "w": 12, "x": x, "y": y},
        "fieldConfig": {"defaults": {"unit": unit}},
        "targets": [
            {"expr": expr, "refId": chr(ord("A") + i)}
            for i, expr in enumerate(exprs)
        ],
    }


def grafana_dashboard() -> dict[str, Any]:
    """One dashboard, four rows: SLO health, traffic/latency, engine
    runtime (device memory + compile cache + step split), and fleet
    state. Expressions stick to series validated by check_monitoring."""
    panels = [
        _panel(1, "SLO: availability / TTFT ok ratio",
               ["llm_slo_availability", "llm_slo_ttft_ok_ratio"],
               0, 0, unit="percentunit"),
        _panel(2, "SLO: error budget burn rate",
               ["llm_slo_error_budget_burn_rate"], 12, 0),
        _panel(3, "Request rate",
               ["rate(llm_requests_total[5m])",
                "rate(llm_requests_finished_total[5m])"], 0, 8,
               unit="reqps"),
        _panel(4, "TTFT p50/p95",
               ["histogram_quantile(0.5, "
                "rate(llm_ttft_seconds_bucket[5m]))",
                "histogram_quantile(0.95, "
                "rate(llm_ttft_seconds_bucket[5m]))"], 12, 8,
               unit="s"),
        _panel(5, "Device memory",
               ['llm_device_memory_bytes{kind="bytes_in_use"}',
                'llm_device_memory_bytes{kind="bytes_limit"}',
                "llm_device_live_buffer_bytes"], 0, 16,
               unit="bytes"),
        _panel(6, "JIT compiles vs cache hits",
               ["rate(llm_jit_compiles_total[5m])",
                "rate(llm_jit_cache_hits_total[5m])"], 12, 16),
        _panel(7, "Device time by dispatch kind vs idle by cause",
               ["sum by (kind) "
                "(rate(llm_dispatch_device_seconds_total[5m]))",
                "sum by (host) "
                "(rate(llm_device_idle_seconds_total[5m]))"], 0, 24,
               unit="percentunit"),
        _panel(8, "Fleet: replica health / engine state",
               ["llm_replica_healthy", "llm_engine_state",
                "llm_cluster_replica_up"], 12, 24),
        _panel(9, "Tokens generated",
               ["rate(llm_tokens_generated_total[5m])"], 0, 32),
        _panel(10, "KV pages used / waiting requests",
               ["llm_kv_pages_used", "llm_waiting_requests"], 12, 32),
        _panel(11, "Adapter cache: hits / misses / evictions",
               ["rate(llm_adapter_cache_hits_total[5m])",
                "rate(llm_adapter_cache_misses_total[5m])",
                "rate(llm_adapter_cache_evictions_total[5m])"], 0, 40),
        _panel(12, "Adapter load latency p95",
               ["histogram_quantile(0.95, "
                "rate(llm_adapter_load_seconds_bucket[5m]))"], 12, 40,
               unit="s"),
        _panel(13, "Cold start by phase (p95)",
               ["histogram_quantile(0.95, sum by (le, phase) "
                "(rate(llm_cold_start_seconds_bucket[30m])))"], 0, 48,
               unit="s"),
        _panel(14, "Queue depth per model (autoscaling signal)",
               ["llm_queue_depth",
                "rate(llm_router_requests_total[1m])"], 12, 48),
        _panel(15, "Decode fusion: steps/dispatch p50 / early-exit rate",
               ["histogram_quantile(0.5, "
                "rate(llm_decode_steps_per_dispatch_bucket[5m]))",
                "rate(llm_decode_early_exit_total[5m])"], 0, 56),
        _panel(16, "Stream resilience: resumes / hedges / truncations",
               ["rate(llm_stream_resume_total[5m])",
                "rate(llm_hedged_requests_total[5m])",
                "rate(llm_stream_truncated_total[5m])"], 12, 56),
        _panel(17, "QoS: shed by priority (gateway + engine)",
               ["sum by (priority) "
                "(rate(llm_tenant_router_shed_total[5m]))",
                "sum by (priority) (rate(llm_tenant_shed_total[5m]))",
                "sum by (priority) "
                "(rate(llm_tenant_degraded_total[5m]))"], 0, 64),
        _panel(18, "QoS: per-tenant queue wait p95 / admissions",
               ["histogram_quantile(0.95, sum by (le, tenant) "
                "(rate(llm_tenant_queue_wait_seconds_bucket[5m])))",
                "sum by (tenant) "
                "(rate(llm_tenant_admitted_total[5m]))"], 12, 64,
               unit="s"),
        _panel(19, "Speculative decode: accept ratio",
               ["llm_spec_accept_ratio",
                "rate(llm_spec_accepted_total[5m]) / "
                "rate(llm_spec_drafted_total[5m])"], 0, 72,
               unit="percentunit"),
        _panel(20, "Speculative decode: drafted / accepted rate",
               ["rate(llm_spec_drafted_total[5m])",
                "rate(llm_spec_accepted_total[5m])"], 12, 72),
        _panel(21, "KV host tier: hit ratio / evictions",
               ["rate(llm_kv_host_cache_hits_total[5m]) / "
                "(rate(llm_kv_host_cache_hits_total[5m]) + "
                "rate(llm_kv_host_cache_misses_total[5m]))",
                "rate(llm_kv_host_cache_evictions_total[5m])"], 0, 80),
        _panel(22, "KV: upload p95 / bytes per token",
               ["histogram_quantile(0.95, "
                "rate(llm_kv_upload_seconds_bucket[5m]))",
                "llm_kv_bytes_per_token"], 12, 80),
        _panel(23, "Goodput: chip-seconds by phase",
               ['sum by (phase) (rate(llm_chip_seconds_total[5m]))'],
               0, 88, unit="percentunit"),
        _panel(24, "Hardware utilization: MFU / MBU",
               ["llm_mfu_ratio", "llm_mbu_ratio"], 12, 88,
               unit="percentunit"),
        _panel(25, "Wasted chip fraction (spec tails + early exits)",
               ['sum (rate(llm_chip_seconds_total'
                '{phase=~"spec_waste|early_exit"}[5m])) / '
                'sum (rate(llm_chip_seconds_total[5m]))'],
               0, 96, unit="percentunit"),
        _panel(26, "Per-tenant chip-seconds (chargeback)",
               ["sum by (tenant) "
                "(rate(llm_tenant_chip_seconds_total[5m]))",
                "rate(llm_auto_profile_total[1h])"], 12, 96),
        _panel(27, "KV handoff: outcomes (disaggregated)",
               ["sum by (outcome) (rate(llm_handoff_total[5m]))"],
               0, 104),
        _panel(28, "KV handoff: latency p50 / p95",
               ["histogram_quantile(0.50, "
                "rate(llm_handoff_seconds_bucket[5m]))",
                "histogram_quantile(0.95, "
                "rate(llm_handoff_seconds_bucket[5m]))"], 12, 104,
               unit="s"),
        _panel(29, "Gray failure: quarantined replicas / ejections",
               ["sum by (model, replica, reason) "
                "(llm_replica_quarantined)",
                "sum by (reason) "
                "(rate(llm_outlier_ejections_total[5m]))"], 0, 112),
        _panel(30, "Retry budget: exhaustion rate",
               ["rate(llm_retry_budget_exhausted_total[5m])"], 12, 112),
        _panel(31, "Prefix affinity: cache-aware placements / fallbacks",
               ["sum by (model) (rate(llm_affinity_hits_total[5m]))",
                "sum by (model, reason) "
                "(rate(llm_affinity_fallback_total[5m]))"], 0, 120),
        _panel(32, "Prefix affinity: filter age (stale = blind routing)",
               ["max by (model, replica) "
                "(llm_prefix_filter_age_seconds)"], 12, 120, unit="s"),
        _panel(33, "Tracing: spans exported (by outcome)",
               ["sum by (outcome) "
                "(rate(llm_trace_spans_exported_total[5m]))"], 0, 128),
        _panel(34, "Tracing: traces dropped (by reason)",
               ["sum by (reason) "
                "(rate(llm_trace_dropped_total[5m]))"], 12, 128),
        _panel(35, "Dispatch waits by kind: behind earlier dispatches "
               "(a prefill: about half a decode window) / host enqueue, "
               "seconds per dispatch",
               ["sum by (kind) "
                "(rate(llm_dispatch_behind_seconds_total[5m])) / "
                "sum by (kind) (rate(llm_dispatches_total[5m]))",
                "sum by (kind) "
                "(rate(llm_dispatch_enqueue_seconds_total[5m])) / "
                "sum by (kind) (rate(llm_dispatches_total[5m]))"],
               0, 136, unit="s"),
        _panel(36, "Decode launches by rule (late = the device was "
               "already free: late over all is the miss rate)",
               ["sum by (when) (rate(llm_decode_launches_total[5m]))"],
               12, 136),
        _panel(37, "Experts: share touched by a step, by kind of dispatch "
               "(the share of the experts' weights it reads) / load, "
               "fullest over mean expert (1 = even)",
               ["sum by (kind) (rate(llm_moe_experts_touched_total[5m])) / "
                "sum by (kind) (rate(llm_moe_expert_slots_total[5m]))",
                "sum by (kind) "
                "(rate(llm_moe_fullest_expert_rows_total[5m])) / "
                "sum by (kind) (rate(llm_moe_mean_expert_rows_total[5m]))"],
               0, 144),
        _panel(38, "Conv state bytes / prefix reuse skipped (by reason)",
               ["llm_conv_state_bytes",
                "sum by (why) "
                "(rate(llm_prefix_reuse_skipped_total[5m]))"], 12, 144),
        _panel(39, "Decode hand-over lag: a window complete on the device "
               "to its tokens on the requests' queues, seconds per window "
               "(every token of a window pays it; a request's own is span "
               "decode.emit)",
               ["sum by (kind) "
                "(rate(llm_decode_emit_seconds_total[5m])) / "
                "sum by (kind) (rate(llm_dispatches_total"
                "{kind=~\"decode|spec\"}[5m]))"],
               0, 152, unit="s"),
    ]
    return {
        "title": "LLM serving on TPU — cluster overview",
        "uid": "llmk-overview",
        "tags": ["llmk", "tpu", "slo"],
        "timezone": "utc",
        "schemaVersion": 39,
        "refresh": "30s",
        "time": {"from": "now-1h", "to": "now"},
        "panels": panels,
    }


def alert_rules_yaml() -> str:
    """Rule file as YAML text — the exact bytes shipped in the ConfigMap
    and committed under each chart's files/ directory."""
    import yaml

    return yaml.safe_dump(alert_rules(), sort_keys=False,
                          default_flow_style=False)


def dashboard_json() -> str:
    return json.dumps(grafana_dashboard(), indent=2, sort_keys=True) + "\n"


def referenced_metric_names() -> set[str]:
    """Every llm_* series name referenced by an alert expression or a
    dashboard panel target. check_monitoring verifies this set is a
    subset of what the servers actually emit."""
    names: set[str] = set()
    for group in alert_rules()["groups"]:
        for rule in group["rules"]:
            names.update(_METRIC_NAME_RE.findall(rule["expr"]))
    for panel in grafana_dashboard()["panels"]:
        for target in panel["targets"]:
            names.update(_METRIC_NAME_RE.findall(target["expr"]))
    return names


def render_monitoring(spec) -> list[dict[str, Any]]:
    """The two monitoring ConfigMaps, in the same Manifest dict form as
    the rest of deploy.manifests. The Grafana ConfigMap carries the
    conventional ``grafana_dashboard: "1"`` label that the Grafana
    sidecar provisioner watches for."""
    from llms_on_kubernetes_tpu.deploy.manifests import _meta

    alerts_cm = {
        "apiVersion": "v1",
        "kind": "ConfigMap",
        "metadata": _meta(ALERT_RULES_CONFIGMAP, spec, "monitoring"),
        "data": {ALERT_RULES_KEY: alert_rules_yaml()},
    }
    dash_meta = _meta(DASHBOARD_CONFIGMAP, spec, "monitoring")
    dash_meta["labels"] = dict(dash_meta["labels"])
    dash_meta["labels"]["grafana_dashboard"] = "1"
    dashboard_cm = {
        "apiVersion": "v1",
        "kind": "ConfigMap",
        "metadata": dash_meta,
        "data": {DASHBOARD_KEY: dashboard_json()},
    }
    return [alerts_cm, dashboard_cm]
