"""The ``models[]`` configuration contract, validated.

Extends the reference's schema (reference vllm-models/helm-chart/
values.yaml:1-27: ``huggingfaceId, modelName, gpuRequestCount, replicas,
pvcSize``) the way SURVEY §7.6 prescribes: TPU topology instead of a GPU
count, explicit sharding (tp/ep/dp), per-model engine-arg passthrough (the
reference hardcoded engine flags in its template — SURVEY §5 "Config"), and
schema validation with actionable errors (the reference had none; its dead
``dnsResolver`` value shipped unnoticed, values.yaml:36-39).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

_NAME_RE = re.compile(r"^[a-z0-9]([a-z0-9-]{0,51}[a-z0-9])?$")

# Prometheus the KEDA ScaledObject triggers query (scrapes the router's
# /metrics/cluster); the kube-prometheus-stack default in-cluster address.
DEFAULT_PROMETHEUS_URL = \
    "http://prometheus-server.monitoring.svc.cluster.local:9090"

# chips per host for each accelerator type: a request larger than this
# renders a multi-host slice (LeaderWorkerSet-style pod group).
CHIPS_PER_HOST = {"v5e": 8, "v5p": 4, "v6e": 8}
VALID_TOPOLOGIES = {
    "v5e": {1: "1x1", 4: "2x2", 8: "2x4", 16: "4x4", 32: "4x8", 64: "8x8", 256: "16x16"},
    "v5p": {4: "2x2x1", 8: "2x2x2", 16: "2x2x4", 32: "2x4x4", 64: "4x4x4"},
    "v6e": {1: "1x1", 4: "2x2", 8: "2x4", 16: "4x4", 32: "4x8", 64: "8x8", 256: "16x16"},
}


class SpecError(ValueError):
    pass


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    accelerator: str = "v5e"     # v5e | v5p | v6e
    chips: int = 8
    topology: Optional[str] = None  # derived from chips if omitted
    # explicit pod-group size; None => derived from chips / chips-per-host
    hosts_override: Optional[int] = None

    def resolved_topology(self) -> str:
        if self.topology:
            return self.topology
        table = VALID_TOPOLOGIES[self.accelerator]
        if self.chips not in table:
            raise SpecError(
                f"no default topology for {self.chips} {self.accelerator} chips; "
                f"known: {sorted(table)} (or set tpu.topology explicitly)"
            )
        return table[self.chips]

    @property
    def hosts(self) -> int:
        if self.hosts_override is not None:
            return self.hosts_override
        per = CHIPS_PER_HOST[self.accelerator]
        return max(1, -(-self.chips // per))

    @property
    def chips_per_host(self) -> int:
        # chips % hosts == 0 is enforced at load time (_tpu_from), so this
        # equals the Helm chart's chipsPerHost helper (exact division) and
        # per-pod requests always sum to tpu.chips
        return max(1, self.chips // self.hosts)

    @property
    def multi_host(self) -> bool:
        return self.hosts > 1

    @property
    def gke_accelerator(self) -> str:
        return {
            "v5e": "tpu-v5-lite-podslice",
            "v5p": "tpu-v5p-slice",
            "v6e": "tpu-v6e-slice",
        }[self.accelerator]


@dataclasses.dataclass(frozen=True)
class ShardingSpec:
    tp: int = 0        # 0 => all chips on the tensor axis
    ep: int = 1
    data: int = 1

    def resolve(self, chips: int) -> "ShardingSpec":
        tp = self.tp or chips // (self.ep * self.data)
        if tp * self.ep * self.data != chips:
            raise SpecError(
                f"sharding tp={tp} x ep={self.ep} x data={self.data} != "
                f"{chips} chips"
            )
        return ShardingSpec(tp=tp, ep=self.ep, data=self.data)


@dataclasses.dataclass(frozen=True)
class AutoscalingSpec:
    """Per-model closed-loop autoscaling knobs (``autoscaling:`` block).

    ``min_replicas >= 1`` renders an ``autoscaling/v2`` HPA scaling on
    ``llm_queue_depth`` (served per-pod by prometheus-adapter) plus the
    router's TTFT-SLO attainment (``llm_slo_ttft_miss_ratio`` as an
    Object metric on the api-gateway Service). ``min_replicas == 0``
    renders a KEDA ScaledObject instead — the HPA cannot scale to zero —
    whose prometheus triggers query the same series Prometheus scrapes
    from the router's ``/metrics/cluster``.
    """

    min_replicas: int = 1
    max_replicas: int = 4
    # waiting requests per replica before adding one (llm_queue_depth)
    queue_depth_target: int = 8
    # scale out when the TTFT-SLO ok ratio drops below this attainment
    ttft_ok_ratio_floor: float = 0.95

    def validate(self, model_name: str) -> None:
        if self.min_replicas < 0:
            raise SpecError(
                f"model {model_name}: autoscaling.minReplicas must be >= 0")
        if self.max_replicas < max(1, self.min_replicas):
            raise SpecError(
                f"model {model_name}: autoscaling.maxReplicas="
                f"{self.max_replicas} must be >= max(1, minReplicas="
                f"{self.min_replicas})")
        if self.queue_depth_target < 1:
            raise SpecError(
                f"model {model_name}: autoscaling.queueDepthTarget must "
                f"be >= 1")
        if not (0.0 < self.ttft_ok_ratio_floor <= 1.0):
            raise SpecError(
                f"model {model_name}: autoscaling.ttftOkRatioFloor must be "
                f"in (0, 1], got {self.ttft_ok_ratio_floor}")


@dataclasses.dataclass(frozen=True)
class AnomalyProfileSpec:
    """Step-time anomaly watchdog knobs (``anomalyProfile:`` block).

    The goodput ledger feeds every dispatch's device time to an EWMA +
    z-score detector; a sustained anomaly triggers ONE bounded profiler
    capture (``llm_auto_profile_total{reason="step_anomaly"}``),
    rate-limited by ``cooldownS``. Rendered as LLMK_ANOMALY_* env vars.
    """

    enabled: bool = True
    threshold: float = 4.0      # z-score a dispatch must exceed
    cooldown_s: float = 600.0   # min seconds between automatic captures

    def validate(self, model_name: str) -> None:
        if self.threshold <= 0:
            raise SpecError(
                f"model {model_name}: anomalyProfile.threshold must be "
                f"> 0, got {self.threshold}")
        if self.cooldown_s < 0:
            raise SpecError(
                f"model {model_name}: anomalyProfile.cooldownS must be "
                f">= 0, got {self.cooldown_s}")


_QOS_PRIORITIES = ("interactive", "normal", "batch")


@dataclasses.dataclass(frozen=True)
class TenantQoSSpec:
    """One tenant's QoS contract (an entry under ``qos.tenants``, or the
    ``qos.default`` applied to unlisted tenants). Keys are the router.json
    wire names — the block is passed to both routers verbatim."""

    weight: float = 1.0            # DRR share inside its priority class
    priority: Optional[str] = None  # interactive | normal | batch
    rps: float = 0.0               # requests/s bucket; 0 = unlimited
    burst: float = 0.0             # rps bucket capacity; 0 = derived
    tokens_per_min: float = 0.0    # generated-token budget; 0 = unlimited

    def validate(self, label: str) -> None:
        if self.priority is not None and self.priority not in _QOS_PRIORITIES:
            raise SpecError(
                f"qos {label}: priority must be one of {_QOS_PRIORITIES}, "
                f"got {self.priority!r}")
        if self.weight <= 0:
            raise SpecError(f"qos {label}: weight must be > 0")
        for k in ("rps", "burst", "tokens_per_min"):
            if getattr(self, k) < 0:
                raise SpecError(f"qos {label}: {k} must be >= 0")

    def to_wire(self) -> dict:
        out: dict = {}
        if self.weight != 1.0:
            out["weight"] = self.weight
        if self.priority is not None:
            out["priority"] = self.priority
        for k in ("rps", "burst", "tokens_per_min"):
            v = getattr(self, k)
            if v:
                out[k] = v
        return out


@dataclasses.dataclass(frozen=True)
class QoSSpec:
    """The gateway-level QoS block (``qos:``): per-tenant weighted fair
    shares + token-bucket rate limits, and the adaptive brownout ladder.
    Rendered verbatim into router.json — the python and native routers
    parse identical keys (tests/data/qos_vectors.json is the semantics
    contract between them)."""

    tenants: tuple[tuple[str, TenantQoSSpec], ...] = ()
    default: Optional[TenantQoSSpec] = None
    # brownout signals: 0 disables a signal entirely
    has_brownout: bool = False
    queue_depth_hi: float = 0.0
    burn_rate_hi: float = 0.0
    clamp_max_tokens: int = 64
    # the values.yaml block as given; to_wire() emits it verbatim so the
    # Python renderer and the Go template (`toJson .Values.qos`) produce
    # byte-identical router.json qos blocks (field-level parity tests)
    raw: Optional[dict] = None

    def validate(self) -> None:
        for name, t in self.tenants:
            if not name:
                raise SpecError("qos.tenants: tenant names must be "
                                "non-empty strings")
            t.validate(f"tenant {name!r}")
        if self.default is not None:
            self.default.validate("default")
        if self.queue_depth_hi < 0 or self.burn_rate_hi < 0:
            raise SpecError("qos.brownout thresholds must be >= 0")
        if self.clamp_max_tokens < 1:
            raise SpecError("qos.brownout.clamp_max_tokens must be >= 1")

    def to_wire(self) -> dict:
        if self.raw is not None:
            return self.raw  # callers serialize, never mutate
        out: dict = {}
        if self.tenants:
            out["tenants"] = {n: t.to_wire() for n, t in self.tenants}
        if self.default is not None:
            out["default"] = self.default.to_wire()
        if self.has_brownout:
            b: dict = {}
            if self.queue_depth_hi:
                b["queue_depth_hi"] = self.queue_depth_hi
            if self.burn_rate_hi:
                b["burn_rate_hi"] = self.burn_rate_hi
            if self.clamp_max_tokens != 64:
                b["clamp_max_tokens"] = self.clamp_max_tokens
            out["brownout"] = b
        return out


# gray-failure layer (ISSUE 17): knob names are the router.json wire keys
# — server/outlier.py is the executable spec, tests/data/outlier_vectors.json
# pins both routers to identical semantics
_OUTLIER_KEYS = frozenset({
    "ewma_alpha", "z_threshold", "cv_floor", "err_spread_floor",
    "min_ttft_ms", "err_floor", "min_samples", "streak",
    "max_eject_fraction", "shadow_every", "readmit_successes",
})
_RETRY_BUDGET_KEYS = frozenset({"ratio", "min_per_s", "burst"})
# prefix-affinity + cache-aware routing (ISSUE 18): wire keys of the
# router.json "prefix_affinity" block — server/affinity.py is the
# executable spec, tests/data/affinity_vectors.json pins both routers
_AFFINITY_KEYS = frozenset({
    "enabled", "prefix_chars", "filter_bits", "filter_hashes",
    "overload_factor", "overload_slack", "key_cache", "max_digests",
    "kv_fetch",
})
_AFFINITY_BOOL_KEYS = frozenset({"enabled", "kv_fetch"})

# router.json "tracing" block + engine env — server/tracing.py is the
# executable spec, tests/data/trace_vectors.json pins both routers
_TRACING_KEYS = frozenset({"otlpEndpoint", "sample", "tailSlowMs"})


@dataclasses.dataclass(frozen=True)
class OutlierEjectionSpec:
    """Latency/error outlier ejection config (``outlierEjection:``): the
    gray-failure detector that quarantines a replica whose in-band TTFT or
    error EWMA is a z-score outlier vs same-model-same-role peers while
    its probes stay green. Rendered verbatim into router.json — a
    non-empty block enables the layer in both routers."""

    # the values.yaml block as given; to_wire() emits it verbatim so the
    # Python renderer and the Go template (`toJson .Values.outlierEjection`)
    # produce byte-identical router.json blocks
    raw: dict = dataclasses.field(default_factory=dict)

    def validate(self) -> None:
        unknown = set(self.raw) - _OUTLIER_KEYS
        if unknown:
            raise SpecError(
                f"unknown outlierEjection keys: {sorted(unknown)} "
                f"(known: {sorted(_OUTLIER_KEYS)})")
        for k, v in self.raw.items():
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                raise SpecError(
                    f"outlierEjection.{k} must be a number, got {v!r}")
            if v < 0:
                raise SpecError(
                    f"outlierEjection.{k} must be >= 0, got {v}")
        alpha = self.raw.get("ewma_alpha")
        if alpha is not None and not (0 < alpha <= 1):
            raise SpecError(
                f"outlierEjection.ewma_alpha must be in (0, 1], got {alpha}")
        frac = self.raw.get("max_eject_fraction")
        if frac is not None and frac > 1:
            raise SpecError(
                f"outlierEjection.max_eject_fraction must be <= 1, "
                f"got {frac}")

    def to_wire(self) -> dict:
        return self.raw  # callers serialize, never mutate


@dataclasses.dataclass(frozen=True)
class RetryBudgetSpec:
    """Cluster retry-budget config (``retryBudget:``): one per-model token
    bucket every retry source draws from (connect failover, handoff
    retries, stream resume, hedges) so localized failure cannot amplify
    into a cluster-wide retry storm. Rendered verbatim into router.json —
    a non-empty block enables the budget in both routers."""

    raw: dict = dataclasses.field(default_factory=dict)

    def validate(self) -> None:
        unknown = set(self.raw) - _RETRY_BUDGET_KEYS
        if unknown:
            raise SpecError(
                f"unknown retryBudget keys: {sorted(unknown)} "
                f"(known: {sorted(_RETRY_BUDGET_KEYS)})")
        for k, v in self.raw.items():
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                raise SpecError(
                    f"retryBudget.{k} must be a number, got {v!r}")
            if v < 0:
                raise SpecError(f"retryBudget.{k} must be >= 0, got {v}")

    def to_wire(self) -> dict:
        return self.raw  # callers serialize, never mutate


@dataclasses.dataclass(frozen=True)
class PrefixAffinitySpec:
    """Prefix-affinity + KV-cache-aware routing config
    (``prefixAffinity:``): pins same-prefix sessions to a rendezvous-hashed
    replica and steers to peers whose advertised digest filters claim the
    request's prefix chain. Rendered verbatim into router.json — a
    non-empty block enables the layer in both routers; absent = dormant
    (pure P2C, byte-identical to the layer not existing)."""

    raw: dict = dataclasses.field(default_factory=dict)

    def validate(self) -> None:
        unknown = set(self.raw) - _AFFINITY_KEYS
        if unknown:
            raise SpecError(
                f"unknown prefixAffinity keys: {sorted(unknown)} "
                f"(known: {sorted(_AFFINITY_KEYS)})")
        for k, v in self.raw.items():
            if k in _AFFINITY_BOOL_KEYS:
                if not isinstance(v, bool):
                    raise SpecError(
                        f"prefixAffinity.{k} must be a bool, got {v!r}")
                continue
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                raise SpecError(
                    f"prefixAffinity.{k} must be a number, got {v!r}")
            if v < 0:
                raise SpecError(
                    f"prefixAffinity.{k} must be >= 0, got {v}")
        hashes = self.raw.get("filter_hashes")
        if hashes is not None and not (1 <= hashes <= 4):
            raise SpecError(
                f"prefixAffinity.filter_hashes must be in [1, 4], "
                f"got {hashes}")

    def to_wire(self) -> dict:
        return self.raw  # callers serialize, never mutate


@dataclasses.dataclass(frozen=True)
class TracingSpec:
    """Cross-hop distributed tracing config (``tracing:``): OTLP/HTTP
    endpoint for tail-sampled span export, the head sample rate for
    unremarkable traces, and the slow-trace threshold that forces export.
    Rendered verbatim into router.json and as LLMK_* env on the engine
    containers — absent = dormant (no exporter thread, no headers beyond
    the always-on traceparent propagation, rendering byte-identical)."""

    raw: dict = dataclasses.field(default_factory=dict)

    def validate(self) -> None:
        unknown = set(self.raw) - _TRACING_KEYS
        if unknown:
            raise SpecError(
                f"unknown tracing keys: {sorted(unknown)} "
                f"(known: {sorted(_TRACING_KEYS)})")
        ep = self.raw.get("otlpEndpoint")
        if ep is not None and not isinstance(ep, str):
            raise SpecError(
                f"tracing.otlpEndpoint must be a string, got {ep!r}")
        for k in ("sample", "tailSlowMs"):
            v = self.raw.get(k)
            if v is None:
                continue
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                raise SpecError(f"tracing.{k} must be a number, got {v!r}")
            if v < 0:
                raise SpecError(f"tracing.{k} must be >= 0, got {v}")
        sample = self.raw.get("sample")
        if sample is not None and sample > 1:
            raise SpecError(
                f"tracing.sample must be in [0, 1], got {sample}")

    def to_wire(self) -> dict:
        return self.raw  # callers serialize, never mutate


@dataclasses.dataclass(frozen=True)
class AdapterSpec:
    """One LoRA adapter a model's replicas serve (multi-tenant serving):
    requests address it as ``model: "<modelName>:<name>"``."""

    name: str
    huggingface_id: Optional[str] = None
    path: Optional[str] = None

    @property
    def ref(self) -> str:
        return self.path or self.huggingface_id or ""

    def validate(self, model_name: str) -> None:
        if not _NAME_RE.match(self.name):
            raise SpecError(
                f"model {model_name}: adapter name {self.name!r} must be a "
                f"DNS-1123 label (it becomes part of the model id "
                f"'{model_name}:{self.name}')"
            )
        if not self.ref:
            raise SpecError(
                f"model {model_name}: adapter {self.name!r} needs "
                f"huggingfaceId or path"
            )


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    model_name: str
    huggingface_id: Optional[str] = None
    model_path: Optional[str] = None       # local path (ramalama-equivalent)
    replicas: int = 1
    pvc_size: str = "30Gi"
    pvc_shared: bool = False               # ReadOnlyMany cache (fixes the
                                           # reference's RWO x replicas
                                           # deadlock, SURVEY §5 Checkpoint)
    tpu: Optional[TPUSpec] = dataclasses.field(default_factory=TPUSpec)
    sharding: ShardingSpec = dataclasses.field(default_factory=ShardingSpec)
    quantization: Optional[str] = None     # None | int8 | fp8 | awq
    max_model_len: int = 4096
    engine_args: tuple[str, ...] = ()      # passthrough (reference gap)
    # free-form k8s resources for CPU/local models (the ramalama chart's
    # verbatim `toYaml .resources` passthrough, reference
    # ramalama model-deployments.yaml:36-37); ignored when tpu is set
    resources: Optional[dict] = None
    dtype: Optional[str] = None            # engine --dtype override
    # fused decode window: tokens sampled per device dispatch
    # (LLMK_DECODE_STEPS); None = engine default. Multihost replicas
    # clamp to 1 at engine start (no two-process run has shown K > 1
    # across hosts yet), so the spec accepts it everywhere.
    decode_steps: Optional[int] = None
    # speculative decoding tier (LLMK_SPECULATION): None = off,
    # "ngram" = model-free prompt lookup, "draft" = small draft model.
    # `draft` names the draft checkpoint (registry name or .gguf path,
    # LLMK_DRAFT_MODEL) and implies speculation: draft. Needs
    # decodeSteps >= 2 — drafts ride the fused decode window.
    speculation: Optional[str] = None
    draft: Optional[str] = None
    # KV-cache storage dtype (LLMK_KV_DTYPE): None = full-width (the model
    # compute dtype), "int8" = quantized pages with per-token scales —
    # roughly 2x the resident streams per chip at equal HBM
    kv_dtype: Optional[str] = None
    # host-RAM offload tier capacity in GiB (LLMK_KV_HOST_CACHE_GB):
    # finished/preempted sessions park their KV pages in host memory and
    # a returning session re-uploads instead of re-prefilling. 0 = off.
    kv_host_cache_gb: float = 0.0
    # disaggregated serving role (LLMK_ROLE): "both" (default) keeps the
    # colocated prefill+decode replica; "prefill" replicas run chunked
    # prompt ingestion only and hand finished KV pages off via the host
    # tier (so kvHostCacheGB > 0 is required); "decode" replicas adopt
    # handed-off pages and run the fused K-step loop. Two models[]
    # entries MAY share one modelName iff their roles are exactly
    # {prefill, decode} — they render as separate Deployments that the
    # router composes into one two-hop serving path.
    role: str = "both"
    # goodput ledger (LLMK_LEDGER): per-request chip-time attribution +
    # MFU/MBU accounting. None = engine default (on); False disables the
    # per-dispatch bookkeeping entirely.
    ledger: Optional[bool] = None
    # step-time anomaly watchdog -> automatic profiler capture
    # (LLMK_ANOMALY_PROFILE / _Z / _COOLDOWN_S); None = engine defaults
    anomaly_profile: Optional[AnomalyProfileSpec] = None
    # multi-tenant LoRA: adapters served on this model's replicas, the
    # device slot count (LRU-recycled) and max rank the slots are sized for
    adapters: tuple = ()                   # tuple[AdapterSpec, ...]
    adapter_slots: int = 4
    adapter_rank: int = 16
    # closed-loop replica autoscaling; None = static replica count
    autoscaling: Optional[AutoscalingSpec] = None

    def validate(self) -> None:
        if not _NAME_RE.match(self.model_name):
            raise SpecError(
                f"modelName {self.model_name!r} must be a DNS-1123 label"
            )
        if not (self.huggingface_id or self.model_path):
            raise SpecError(
                f"model {self.model_name}: need huggingfaceId or modelPath"
            )
        scale_to_zero = (self.autoscaling is not None
                         and self.autoscaling.min_replicas == 0)
        if self.replicas < (0 if scale_to_zero else 1):
            raise SpecError(
                f"model {self.model_name}: replicas must be >= 1 "
                f"(0 only with autoscaling.minReplicas: 0 — scale-to-zero)")
        if self.decode_steps is not None and self.decode_steps < 1:
            raise SpecError(
                f"model {self.model_name}: decodeSteps must be >= 1, "
                f"got {self.decode_steps}"
            )
        if self.speculation not in (None, "ngram", "draft"):
            raise SpecError(
                f"model {self.model_name}: speculation must be 'ngram' or "
                f"'draft', got {self.speculation!r}"
            )
        if self.speculation == "draft" and not self.draft:
            raise SpecError(
                f"model {self.model_name}: speculation: draft needs a "
                f"draft: model reference (registry name or .gguf path)"
            )
        if self.draft and self.speculation == "ngram":
            raise SpecError(
                f"model {self.model_name}: draft: {self.draft!r} is unused "
                f"under speculation: ngram — drop one of them"
            )
        if self.speculation is not None and self.decode_steps is not None \
                and self.decode_steps < 2:
            raise SpecError(
                f"model {self.model_name}: speculation needs "
                f"decodeSteps >= 2 (drafts ride the fused decode window), "
                f"got decodeSteps: {self.decode_steps}"
            )
        if self.quantization not in (None, "int8", "fp8", "awq"):
            raise SpecError(
                f"model {self.model_name}: unknown quantization "
                f"{self.quantization!r}"
            )
        if self.kv_dtype not in (None, "int8"):
            raise SpecError(
                f"model {self.model_name}: kvDtype must be 'int8' or "
                f"omitted, got {self.kv_dtype!r}"
            )
        if self.kv_host_cache_gb < 0:
            raise SpecError(
                f"model {self.model_name}: kvHostCacheGB must be >= 0, "
                f"got {self.kv_host_cache_gb}"
            )
        if self.role not in ("prefill", "decode", "both"):
            raise SpecError(
                f"model {self.model_name}: role must be 'prefill', "
                f"'decode', or 'both', got {self.role!r}"
            )
        if self.role != "both" and self.tpu is not None \
                and self.tpu.multi_host:
            raise SpecError(
                f"model {self.model_name}: role: {self.role} is "
                f"unsupported on a multi-host slice (the KV handoff "
                f"rides the coordinator-local host tier, which multihost "
                f"rejects) — drop role: or use a single-host topology"
            )
        if self.role == "prefill" and self.kv_host_cache_gb <= 0:
            raise SpecError(
                f"model {self.model_name}: role: prefill needs "
                f"kvHostCacheGB > 0 — the handoff ticket points decode "
                f"replicas at pages spilled into the host tier, so a "
                f"prefill replica without one has nowhere to put them"
            )
        if (self.kv_host_cache_gb > 0 and self.tpu is not None
                and self.tpu.multi_host):
            raise SpecError(
                f"model {self.model_name}: kvHostCacheGB is unsupported on "
                f"a multi-host slice (page uploads are coordinator-local "
                f"and would desync follower pods) — drop it or use a "
                f"single-host topology"
            )
        if self.anomaly_profile is not None:
            self.anomaly_profile.validate(self.model_name)
            if self.ledger is False and self.anomaly_profile.enabled:
                raise SpecError(
                    f"model {self.model_name}: anomalyProfile needs the "
                    f"goodput ledger (the watchdog reads its per-dispatch "
                    f"times) — drop `ledger: false` or disable the "
                    f"watchdog"
                )
        if self.tpu is not None:
            if self.tpu.accelerator not in CHIPS_PER_HOST:
                raise SpecError(
                    f"model {self.model_name}: unknown accelerator "
                    f"{self.tpu.accelerator!r} (known: {sorted(CHIPS_PER_HOST)})"
                )
            self.tpu.resolved_topology()
            self.sharding.resolve(self.tpu.chips)
        # peak replica count: what the autoscaler may scale up to, not
        # just the static spec — an HPA-driven second replica hits the
        # same RWO volume-attach deadlock as a static replicas: 2
        peak = self.replicas
        if self.autoscaling is not None:
            self.autoscaling.validate(self.model_name)
            peak = max(peak, self.autoscaling.max_replicas)
            if self.tpu is not None and self.tpu.multi_host:
                raise SpecError(
                    f"model {self.model_name}: autoscaling targets the "
                    f"replica count, but a multi-host slice's StatefulSet "
                    f"replicas are the pod GROUP size ({self.tpu.hosts} "
                    f"hosts); autoscaling multi-host models is unsupported"
                )
        if peak > 1 and not self.pvc_shared and self.huggingface_id:
            raise SpecError(
                f"model {self.model_name}: up to {peak} replicas with a "
                f"ReadWriteOnce cache PVC deadlocks on volume attach; set "
                f"pvcShared: true (ReadOnlyMany) or replicas: 1"
            )
        anames = [a.name for a in self.adapters]
        adupes = {n for n in anames if anames.count(n) > 1}
        if adupes:
            raise SpecError(
                f"model {self.model_name}: duplicate adapter name(s): "
                f"{sorted(adupes)}"
            )
        for a in self.adapters:
            a.validate(self.model_name)
        if self.adapters and (self.adapter_slots < 1 or self.adapter_rank < 1):
            raise SpecError(
                f"model {self.model_name}: adapterSlots and adapterRank "
                f"must be >= 1"
            )


@dataclasses.dataclass(frozen=True)
class DeploySpec:
    models: tuple[ModelSpec, ...]
    namespace: str = "tpu-models"
    image: str = "llms-on-kubernetes-tpu:latest"
    image_pull_policy: str = "IfNotPresent"
    storage_class: Optional[str] = None
    default_model: Optional[str] = None    # router fallback; first if None
    strict_routing: bool = False           # 404 unknown models (reference
                                           # silently fell back, SURVEY §3.1)
    native_router: bool = True             # C++ router image vs python
    # router-side active /ready probe period per replica; 0 disables
    probe_interval_s: float = 2.0
    # zero-drop streams (ISSUE 9): mid-stream journal resume on upstream
    # death (on by default), capped re-issues per stream, and hedged
    # first-byte requests (0 = off). Rendered into router.json — both the
    # native C++ router and the python router parse the same keys.
    stream_resume: bool = True
    resume_attempts: int = 2
    hedge_ms: float = 0.0
    # disaggregated serving: attempts across decode replicas to place a
    # prefill handoff ticket before falling back to a colocated replica
    # (LLMK_HANDOFF_RETRIES in both routers)
    handoff_retries: int = 2
    # per-tenant QoS at the gateway (ISSUE 10); None = QoS disabled
    qos: Optional[QoSSpec] = None
    # gray-failure layer (ISSUE 17): latency/error outlier ejection and
    # cluster retry budgets; None = layer disabled (dormant in routers)
    outlier_ejection: Optional[OutlierEjectionSpec] = None
    retry_budget: Optional[RetryBudgetSpec] = None
    # prefix-affinity + cache-aware routing (ISSUE 18); None = dormant
    prefix_affinity: Optional[PrefixAffinitySpec] = None
    # cross-hop distributed tracing (ISSUE 19); None = dormant (no OTLP
    # exporter; traceparent propagation itself is always on)
    tracing: Optional[TracingSpec] = None
    webui_enabled: bool = True
    webui_name: str = "TPU Multi-Model WebUI"
    hf_secret_name: str = "huggingface-token"
    host_model_path: Optional[str] = None  # local path mount (CPU profile)
    # Prometheus address the KEDA ScaledObject triggers query
    prometheus_url: str = DEFAULT_PROMETHEUS_URL

    def validate(self) -> None:
        if not self.models:
            raise SpecError("at least one model is required")
        names = [m.model_name for m in self.models]
        # one entry per name, with one exception: a disaggregated pair —
        # exactly two entries whose roles are {prefill, decode} — shares
        # the modelName so the router serves them as one model
        by_name: dict[str, list[str]] = {}
        for m in self.models:
            by_name.setdefault(m.model_name, []).append(m.role)
        for name, roles in by_name.items():
            if len(roles) == 1:
                continue
            if sorted(roles) != ["decode", "prefill"]:
                raise SpecError(
                    f"duplicate modelName(s): ['{name}'] (two entries may "
                    f"share a modelName only as a disaggregated pair with "
                    f"roles prefill + decode; got roles {sorted(roles)})")
        for m in self.models:
            m.validate()
        if self.default_model is not None and self.default_model not in names:
            raise SpecError(
                f"defaultModel {self.default_model!r} is not in models[] "
                f"({names})"
            )
        if self.resume_attempts < 0:
            raise SpecError(
                f"router.resumeAttempts must be >= 0, got "
                f"{self.resume_attempts}")
        if self.hedge_ms < 0:
            raise SpecError(
                f"router.hedgeMs must be >= 0, got {self.hedge_ms}")
        if self.handoff_retries < 0:
            raise SpecError(
                f"router.handoffRetries must be >= 0, got "
                f"{self.handoff_retries}")
        if self.qos is not None:
            self.qos.validate()
        if self.outlier_ejection is not None:
            self.outlier_ejection.validate()
        if self.retry_budget is not None:
            self.retry_budget.validate()
        if self.prefix_affinity is not None:
            self.prefix_affinity.validate()
        if self.tracing is not None:
            self.tracing.validate()

    @property
    def resolved_default(self) -> str:
        return self.default_model or self.models[0].model_name


# ---------------------------------------------------------------------------
# YAML / dict loading (the values.yaml surface)
# ---------------------------------------------------------------------------

def _tpu_from(d: Optional[dict]) -> Optional[TPUSpec]:
    if d is None:
        return None
    unknown = set(d) - {"accelerator", "chips", "topology", "hosts"}
    if unknown:
        raise SpecError(f"unknown tpu keys: {sorted(unknown)}")
    hosts = d.get("hosts")
    if hosts is not None:
        if int(hosts) < 1:
            raise SpecError(f"tpu.hosts must be >= 1, got {hosts}")
        chips = int(d.get("chips", 8))
        if chips % int(hosts) != 0:
            raise SpecError(
                f"tpu.chips={chips} not divisible by tpu.hosts={hosts} — "
                f"every slice host carries the same chip count"
            )
    return TPUSpec(
        accelerator=d.get("accelerator", "v5e"),
        chips=int(d.get("chips", 8)),
        topology=d.get("topology"),
        hosts_override=int(hosts) if hosts is not None else None,
    )


def _autoscaling_from(d: Optional[dict], model_name: str) \
        -> Optional[AutoscalingSpec]:
    if d is None:
        return None
    if not isinstance(d, dict):
        raise SpecError(
            f"model {model_name}: autoscaling must be a mapping")
    unknown = set(d) - {"minReplicas", "maxReplicas", "queueDepthTarget",
                        "ttftOkRatioFloor"}
    if unknown:
        raise SpecError(
            f"model {model_name}: unknown autoscaling keys: "
            f"{sorted(unknown)}")
    return AutoscalingSpec(
        min_replicas=int(d.get("minReplicas", 1)),
        max_replicas=int(d.get("maxReplicas", 4)),
        queue_depth_target=int(d.get("queueDepthTarget", 8)),
        ttft_ok_ratio_floor=float(d.get("ttftOkRatioFloor", 0.95)),
    )


def _anomaly_from(d: Optional[dict], model_name: str) \
        -> Optional[AnomalyProfileSpec]:
    if d is None:
        return None
    if not isinstance(d, dict):
        raise SpecError(
            f"model {model_name}: anomalyProfile must be a mapping")
    unknown = set(d) - {"enabled", "threshold", "cooldownS"}
    if unknown:
        raise SpecError(
            f"model {model_name}: unknown anomalyProfile keys: "
            f"{sorted(unknown)}")
    return AnomalyProfileSpec(
        enabled=bool(d.get("enabled", True)),
        threshold=float(d.get("threshold", 4.0)),
        cooldown_s=float(d.get("cooldownS", 600.0)),
    )


def _tenant_qos_from(d, label: str) -> TenantQoSSpec:
    if not isinstance(d, dict):
        raise SpecError(f"qos {label}: must be a mapping")
    unknown = set(d) - {"weight", "priority", "rps", "burst",
                        "tokens_per_min"}
    if unknown:
        raise SpecError(f"qos {label}: unknown keys: {sorted(unknown)}")
    return TenantQoSSpec(
        weight=float(d.get("weight", 1.0)),
        priority=d.get("priority"),
        rps=float(d.get("rps", 0.0)),
        burst=float(d.get("burst", 0.0)),
        tokens_per_min=float(d.get("tokens_per_min", 0.0)),
    )


def _qos_from(d: Optional[dict]) -> Optional[QoSSpec]:
    if not d:
        # absent OR empty block = disabled (matches both routers'
        # truthiness: empty tenants/default/brownout do not enable QoS)
        return None
    if not isinstance(d, dict):
        raise SpecError("qos must be a mapping")
    unknown = set(d) - {"tenants", "default", "brownout"}
    if unknown:
        raise SpecError(f"unknown qos keys: {sorted(unknown)}")
    tenants_raw = d.get("tenants") or {}
    if not isinstance(tenants_raw, dict):
        raise SpecError("qos.tenants must be a mapping of tenant -> entry")
    brownout = d.get("brownout")
    if brownout is not None and not isinstance(brownout, dict):
        raise SpecError("qos.brownout must be a mapping")
    if brownout:
        unknown = set(brownout) - {"queue_depth_hi", "burn_rate_hi",
                                   "clamp_max_tokens"}
        if unknown:
            raise SpecError(f"unknown qos.brownout keys: {sorted(unknown)}")
    b = brownout or {}
    return QoSSpec(
        tenants=tuple(sorted(
            (str(n), _tenant_qos_from(t, f"tenant {n!r}"))
            for n, t in tenants_raw.items())),
        default=(_tenant_qos_from(d["default"], "default")
                 if d.get("default") else None),
        has_brownout=bool(brownout),
        queue_depth_hi=float(b.get("queue_depth_hi", 0.0)),
        burn_rate_hi=float(b.get("burn_rate_hi", 0.0)),
        clamp_max_tokens=int(b.get("clamp_max_tokens", 64)),
        raw=d,
    )


def _outlier_from(d: Optional[dict]) -> Optional[OutlierEjectionSpec]:
    if not d:
        # absent OR empty block = disabled (matches both routers'
        # truthiness: enabled iff the wire block is non-empty)
        return None
    if not isinstance(d, dict):
        raise SpecError("outlierEjection must be a mapping")
    return OutlierEjectionSpec(raw=d)


def _retry_budget_from(d: Optional[dict]) -> Optional[RetryBudgetSpec]:
    if not d:
        return None
    if not isinstance(d, dict):
        raise SpecError("retryBudget must be a mapping")
    return RetryBudgetSpec(raw=d)


def _affinity_from(d: Optional[dict]) -> Optional[PrefixAffinitySpec]:
    if not d:
        return None
    if not isinstance(d, dict):
        raise SpecError("prefixAffinity must be a mapping")
    return PrefixAffinitySpec(raw=d)


def _tracing_from(d: Optional[dict]) -> Optional[TracingSpec]:
    if not d:
        return None
    if not isinstance(d, dict):
        raise SpecError("tracing must be a mapping")
    return TracingSpec(raw=d)


def _adapter_from(d: dict, model_name: str) -> AdapterSpec:
    if not isinstance(d, dict):
        raise SpecError(
            f"model {model_name}: adapters[] entries must be mappings")
    unknown = set(d) - {"name", "huggingfaceId", "path"}
    if unknown:
        raise SpecError(
            f"model {model_name}: unknown adapter keys: {sorted(unknown)}")
    return AdapterSpec(
        name=str(d.get("name", "")),
        huggingface_id=d.get("huggingfaceId"),
        path=d.get("path"),
    )


def _model_from(d: dict) -> ModelSpec:
    known = {
        "modelName", "huggingfaceId", "modelPath", "replicas", "pvcSize",
        "pvcShared", "tpu", "sharding", "quantization", "maxModelLen",
        "engineArgs", "resources", "dtype", "decodeSteps",
        "speculation", "draft", "kvDtype", "kvHostCacheGB", "role",
        "ledger", "anomalyProfile",
        "adapters", "adapterSlots", "adapterRank", "autoscaling",
    }
    unknown = set(d) - known
    if unknown:
        raise SpecError(
            f"unknown model keys: {sorted(unknown)} (known: {sorted(known)})"
        )
    sh = d.get("sharding") or {}
    return ModelSpec(
        model_name=d.get("modelName", ""),
        huggingface_id=d.get("huggingfaceId"),
        model_path=d.get("modelPath"),
        replicas=int(d.get("replicas", 1)),
        pvc_size=str(d.get("pvcSize", "30Gi")),
        pvc_shared=bool(d.get("pvcShared", False)),
        # modelPath without an explicit tpu block = the local/CPU profile
        # (the ramalama-equivalent contract has no accelerator at all)
        tpu=(_tpu_from(d["tpu"]) if "tpu" in d
             else (None if d.get("modelPath") else TPUSpec())),
        sharding=ShardingSpec(
            tp=int(sh.get("tp", 0)), ep=int(sh.get("ep", 1)),
            data=int(sh.get("data", 1)),
        ),
        quantization=d.get("quantization"),
        max_model_len=int(d.get("maxModelLen", 4096)),
        engine_args=tuple(d.get("engineArgs", ())),
        resources=d.get("resources"),
        dtype=d.get("dtype"),
        decode_steps=(int(d["decodeSteps"]) if "decodeSteps" in d
                      else None),
        # draft: alone implies speculation: draft (mirrors EngineConfig)
        speculation=(d.get("speculation")
                     or ("draft" if d.get("draft") else None)),
        draft=d.get("draft"),
        kv_dtype=d.get("kvDtype"),
        kv_host_cache_gb=float(d.get("kvHostCacheGB", 0) or 0),
        role=str(d.get("role", "both") or "both"),
        ledger=(bool(d["ledger"]) if "ledger" in d else None),
        anomaly_profile=_anomaly_from(d.get("anomalyProfile"),
                                      d.get("modelName", "")),
        adapters=tuple(_adapter_from(a, d.get("modelName", ""))
                       for a in d.get("adapters", ()) or ()),
        adapter_slots=int(d.get("adapterSlots", 4)),
        adapter_rank=int(d.get("adapterRank", 16)),
        autoscaling=_autoscaling_from(d.get("autoscaling"),
                                      d.get("modelName", "")),
    )


def load_spec(source: "str | dict") -> DeploySpec:
    """Load + validate a DeploySpec from a YAML path/string or a dict."""
    import yaml

    if isinstance(source, str):
        if "\n" not in source and source.endswith((".yaml", ".yml")):
            with open(source) as f:
                data = yaml.safe_load(f)
        else:
            data = yaml.safe_load(source)
    else:
        data = source
    if not isinstance(data, dict):
        raise SpecError("config must be a mapping")

    models = tuple(_model_from(m) for m in data.get("models", ()))
    webui = data.get("webui", {}) or {}
    image = data.get("image", {}) or {}
    if isinstance(image, dict):
        repo = image.get("repository", "llms-on-kubernetes-tpu")
        tag = image.get("tag", "latest")
        image_str = f"{repo}:{tag}"
        pull = image.get("pullPolicy", "IfNotPresent")
    else:
        image_str, pull = str(image), "IfNotPresent"
    spec = DeploySpec(
        models=models,
        namespace=data.get("namespace", "tpu-models"),
        image=image_str,
        image_pull_policy=pull,
        storage_class=(data.get("storage") or {}).get("className"),
        default_model=(data.get("router") or {}).get("defaultModel"),
        strict_routing=bool((data.get("router") or {}).get("strict", False)),
        native_router=bool((data.get("router") or {}).get("native", True)),
        probe_interval_s=float(
            (data.get("router") or {}).get("probeIntervalS", 2.0)),
        stream_resume=bool(
            (data.get("router") or {}).get("streamResume", True)),
        resume_attempts=int(
            (data.get("router") or {}).get("resumeAttempts", 2)),
        hedge_ms=float((data.get("router") or {}).get("hedgeMs", 0.0)),
        handoff_retries=int(
            (data.get("router") or {}).get("handoffRetries", 2)),
        qos=_qos_from(data.get("qos")),
        outlier_ejection=_outlier_from(data.get("outlierEjection")),
        retry_budget=_retry_budget_from(data.get("retryBudget")),
        prefix_affinity=_affinity_from(data.get("prefixAffinity")),
        tracing=_tracing_from(data.get("tracing")),
        webui_enabled=bool(webui.get("enabled", True)),
        webui_name=webui.get("name", "TPU Multi-Model WebUI"),
        hf_secret_name=data.get("hfSecretName", "huggingface-token"),
        host_model_path=data.get("hostModelPath"),
        prometheus_url=str(data.get("prometheusUrl")
                           or DEFAULT_PROMETHEUS_URL),
    )
    spec.validate()
    return spec
