"""Kubernetes manifest renderer: models[] spec → the full serving topology.

The TPU-native equivalent of the reference chart's template fan-out
(reference vllm-models/helm-chart/templates/: model-deployments.yaml,
model-services.yaml, model-pvcs.yaml, model-gateway.yaml,
webui-deployment.yaml, gateway.yaml — SURVEY §3.2 "config fan-out"). Per
model it emits:

- single-host (tpu.hosts == 1): a Deployment requesting
  ``google.com/tpu: <chips>`` with GKE TPU nodeSelectors
  (``cloud.google.com/gke-tpu-accelerator`` / ``gke-tpu-topology``) — the
  analogue of the reference's ``nvidia.com/gpu`` requests + taint
  tolerations (model-deployments.yaml:40-44,75-78);
- multi-host (v5p-16 etc.): a StatefulSet pod group + headless Service for
  stable worker DNS, each pod one slice host, with
  ``jax.distributed``-compatible env (coordinator = pod 0) — the
  capability the reference lacked entirely (SURVEY §2.4);
- a ClusterIP Service per model, an optional HF-cache PVC (ReadOnlyMany
  opt-in to fix the reference's RWO x replicas deadlock, SURVEY §5);
- the router ConfigMap/Deployment/Service with a **config-hash pod
  annotation** so ArgoCD syncing a model-list change rolls the router —
  the reference's gateway silently kept stale routes until manually
  restarted (SURVEY §3.2);
- Istio Gateway + VirtualService (same 4-route shape as the reference:
  exact /v1/models, prefix /v1/, /health, / → webui);
- OpenWebUI Deployment/Service/PVC pointed at the router.
"""

from __future__ import annotations

import hashlib
import json
from typing import Any, Optional

from llms_on_kubernetes_tpu.deploy.spec import DeploySpec, ModelSpec

Manifest = dict[str, Any]

ENGINE_PORT = 8080
ROUTER_PORT = 8080
WEBUI_PORT = 8080

# Drain budget (k8s/tpu-models/README.md "Graceful shutdown / rollout"):
# the preStop sleep holds SIGTERM until endpoint removal has propagated,
# then the server's drain (in-flight generations complete, /ready -> 503)
# must finish inside the grace period or the kubelet SIGKILLs mid-stream.
PRESTOP_SLEEP_S = 5
MODEL_GRACE_S = 330    # worst-case long generation + preStop sleep
ROUTER_GRACE_S = 30    # router only relays; in-flight proxying is short


def _res_name(m: ModelSpec) -> str:
    """Kubernetes resource base name for one models[] entry. A
    disaggregated pair shares its modelName, so role-specific entries get
    a role suffix — separate Deployments/Services per pool, while the
    router still serves them as one model."""
    base = f"model-{m.model_name}"
    return base if m.role == "both" else f"{base}-{m.role}"


def _lifecycle() -> dict[str, Any]:
    return {"lifecycle": {"preStop": {"exec": {
        "command": ["sh", "-c", f"sleep {PRESTOP_SLEEP_S}"]}}}}


def _labels(app: str, component: str) -> dict[str, str]:
    return {
        "app": app,
        "app.kubernetes.io/component": component,
        "app.kubernetes.io/part-of": "llms-on-kubernetes-tpu",
    }


def _meta(name: str, spec: DeploySpec, component: str,
          annotations: Optional[dict] = None) -> Manifest:
    meta: Manifest = {
        "name": name,
        "namespace": spec.namespace,
        "labels": _labels(name, component),
    }
    if annotations:
        meta["annotations"] = annotations
    return meta


def _engine_args(m: ModelSpec, spec: DeploySpec) -> list[str]:
    ref = m.huggingface_id or m.model_path
    args = [
        "serve",
        "--model", str(ref),
        "--served-model-name", m.model_name,
        "--host", "0.0.0.0",
        "--port", str(ENGINE_PORT),
    ]
    if m.tpu is not None:
        sh = m.sharding.resolve(m.tpu.chips)
        args += ["--tensor-parallel-size", str(sh.tp)]
        if sh.ep > 1:
            args += ["--expert-parallel-size", str(sh.ep)]
    if m.quantization:
        args += ["--quantization", m.quantization]
    if m.dtype:
        args += ["--dtype", m.dtype]
    for a in m.adapters:
        args += ["--adapter", f"{a.name}={a.ref}"]
    if m.adapters:
        args += ["--adapter-slots", str(m.adapter_slots),
                 "--adapter-rank", str(m.adapter_rank)]
    args += list(m.engine_args)
    return args


def _probes() -> dict[str, Any]:
    """Same probe budget as the reference's vLLM pods (cold start can include
    an HF download; reference model-deployments.yaml:48-63)."""
    return {
        "readinessProbe": {
            "httpGet": {"path": "/health", "port": ENGINE_PORT},
            "initialDelaySeconds": 120, "periodSeconds": 30,
            "failureThreshold": 10,
        },
        "livenessProbe": {
            "httpGet": {"path": "/health", "port": ENGINE_PORT},
            "initialDelaySeconds": 300, "periodSeconds": 60,
            "failureThreshold": 5,
        },
    }


def _engine_container(m: ModelSpec, spec: DeploySpec) -> Manifest:
    c: Manifest = {
        "name": "engine",
        "image": spec.image,
        "imagePullPolicy": spec.image_pull_policy,
        "command": ["python", "-m", "llms_on_kubernetes_tpu"],
        "args": _engine_args(m, spec),
        "ports": [
            {"containerPort": ENGINE_PORT, "name": "http"},
        ],
        "env": [
            {"name": "HUGGING_FACE_HUB_TOKEN", "valueFrom": {"secretKeyRef": {
                "name": spec.hf_secret_name, "key": "token",
                "optional": True,
            }}},
        ],
        **_probes(),
        **_lifecycle(),
    }
    if m.decode_steps is not None:
        # env (not an engine arg) so the fused-decode window stays out
        # of the argv contract the golden tests pin; the engine clamps
        # to 1 on multihost regardless of what the spec asks for
        c["env"].append({"name": "LLMK_DECODE_STEPS",
                         "value": str(m.decode_steps)})
    if m.speculation is not None:
        # same env convention as the decode window; the engine ignores
        # speculation on multihost after its decode_steps clamp
        c["env"].append({"name": "LLMK_SPECULATION",
                         "value": m.speculation})
    if m.draft is not None:
        c["env"].append({"name": "LLMK_DRAFT_MODEL", "value": m.draft})
    if m.kv_dtype is not None:
        # env, like the decode window: KV storage width is an engine
        # runtime knob, not part of the pinned argv contract
        c["env"].append({"name": "LLMK_KV_DTYPE", "value": m.kv_dtype})
    if m.kv_host_cache_gb > 0:
        c["env"].append({"name": "LLMK_KV_HOST_CACHE_GB",
                         "value": str(m.kv_host_cache_gb)})
    if m.role != "both":
        # disaggregated serving role; the engine validates the combo
        # (prefill needs the host tier, multihost rejects roles)
        c["env"].append({"name": "LLMK_ROLE", "value": m.role})
    if m.ledger is not None:
        # goodput ledger on/off; engine default is on, so only an
        # explicit spec value renders env
        c["env"].append({"name": "LLMK_LEDGER",
                         "value": "1" if m.ledger else "0"})
    if m.anomaly_profile is not None:
        ap = m.anomaly_profile
        c["env"].append({"name": "LLMK_ANOMALY_PROFILE",
                         "value": "1" if ap.enabled else "0"})
        c["env"].append({"name": "LLMK_ANOMALY_Z",
                         "value": str(ap.threshold)})
        c["env"].append({"name": "LLMK_ANOMALY_COOLDOWN_S",
                         "value": str(ap.cooldown_s)})
    if spec.prefix_affinity is not None:
        # cache-aware routing (ISSUE 18): the replica's /ready filter
        # geometry must match what the router config promised, so the
        # spec block's bits/hashes thread through to the API server
        aff = spec.prefix_affinity.to_wire()
        if "filter_bits" in aff:
            c["env"].append({"name": "LLMK_PREFIX_FILTER_BITS",
                             "value": str(int(aff["filter_bits"]))})
        if "filter_hashes" in aff:
            c["env"].append({"name": "LLMK_PREFIX_FILTER_HASHES",
                             "value": str(int(aff["filter_hashes"]))})
    if spec.tracing is not None:
        # cross-hop tracing (ISSUE 19): the engine fragments export to
        # the same OTLP endpoint under the same sampling policy as the
        # router's, so stitched trees are complete on the backend too
        tr = spec.tracing.to_wire()
        if tr.get("otlpEndpoint"):
            c["env"].append({"name": "LLMK_OTLP_ENDPOINT",
                             "value": str(tr["otlpEndpoint"])})
        if "sample" in tr:
            c["env"].append({"name": "LLMK_TRACE_SAMPLE",
                             "value": str(float(tr["sample"]))})
        if "tailSlowMs" in tr:
            c["env"].append({"name": "LLMK_SLOW_REQUEST_MS",
                             "value": str(float(tr["tailSlowMs"]))})
    if m.tpu is None:
        # local/CPU profile: force the XLA-CPU backend (same env the
        # local-models chart sets) so the TPU-enabled image runs on
        # accelerator-less nodes
        c["env"].append({"name": "JAX_PLATFORMS", "value": "cpu"})
    if m.tpu is not None:
        c["resources"] = {
            "requests": {"google.com/tpu": str(m.tpu.chips_per_host)},
            "limits": {"google.com/tpu": str(m.tpu.chips_per_host)},
        }
    elif m.resources:
        # local/CPU profile: verbatim passthrough, like the reference's
        # `toYaml .resources` (ramalama model-deployments.yaml:36-37)
        c["resources"] = m.resources
    if m.huggingface_id:
        c["volumeMounts"] = [{
            "name": "hf-cache", "mountPath": "/root/.cache/huggingface",
        }]
        # XLA's persistent compile cache beside the weights, on the same
        # PVC: a restarted pod loads its executables instead of compiling
        c["env"].append({"name": "JAX_COMPILATION_CACHE_DIR",
                         "value": "/root/.cache/huggingface/xla_cache"})
    elif spec.host_model_path:
        c["volumeMounts"] = [{
            "name": "models", "mountPath": "/mnt/models", "readOnly": True,
        }]
    return c


def _tpu_node_selector(m: ModelSpec) -> dict[str, str]:
    assert m.tpu is not None
    return {
        "cloud.google.com/gke-tpu-accelerator": m.tpu.gke_accelerator,
        "cloud.google.com/gke-tpu-topology": m.tpu.resolved_topology(),
    }


def _volumes(m: ModelSpec, spec: DeploySpec) -> list[Manifest]:
    if m.huggingface_id:
        return [{
            "name": "hf-cache",
            "persistentVolumeClaim": {
                "claimName": f"{_res_name(m)}-cache",
                **({"readOnly": True} if m.pvc_shared else {}),
            },
        }]
    if spec.host_model_path:
        return [{
            "name": "models",
            "hostPath": {"path": spec.host_model_path, "type": "Directory"},
        }]
    return []


def _scrape_annotations(port: int = ENGINE_PORT) -> dict[str, str]:
    """Prometheus scrape hints for a pod's /metrics (SURVEY §5: the
    reference never scraped its engines' metrics endpoints)."""
    return {
        "prometheus.io/scrape": "true",
        "prometheus.io/port": str(port),
        "prometheus.io/path": "/metrics",
    }


def render_model_single_host(m: ModelSpec, spec: DeploySpec) -> list[Manifest]:
    pod_spec: Manifest = {
        "terminationGracePeriodSeconds": MODEL_GRACE_S,
        "containers": [_engine_container(m, spec)],
        "volumes": _volumes(m, spec),
    }
    if m.tpu is not None:
        pod_spec["nodeSelector"] = _tpu_node_selector(m)
    name = _res_name(m)
    dep = {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": _meta(name, spec, "model-server"),
        "spec": {
            "replicas": m.replicas,
            "selector": {"matchLabels": {"app": name}},
            "template": {
                "metadata": {
                    "labels": _labels(name, "model-server"),
                    "annotations": _scrape_annotations(),
                },
                "spec": pod_spec,
            },
        },
    }
    return [dep]


def render_model_multi_host(m: ModelSpec, spec: DeploySpec) -> list[Manifest]:
    """One logical server spanning a pod group (v5p-16 ⇒ 4 pods × 4 chips).

    StatefulSet + headless Service gives each worker a stable DNS name;
    pod 0 is the ``jax.distributed`` coordinator and the only pod the
    model Service routes requests to (workers follow the jit program).
    SURVEY §7 hard-part 3: the reference's single-pod Deployment shape
    could never express this.
    """
    assert m.tpu is not None and m.tpu.multi_host
    name = f"model-{m.model_name}"
    headless = {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": _meta(f"{name}-workers", spec, "model-worker-discovery"),
        "spec": {
            "clusterIP": "None",
            "publishNotReadyAddresses": True,
            "selector": {"app": name},
            "ports": [{"port": ENGINE_PORT, "name": "http"}],
        },
    }
    container = _engine_container(m, spec)
    container["env"] += [
        {"name": "POD_NAME", "valueFrom": {
            "fieldRef": {"fieldPath": "metadata.name"}}},
        {"name": "TPU_WORKER_HOSTNAMES", "value": ",".join(
            f"{name}-{i}.{name}-workers.{spec.namespace}.svc.cluster.local"
            for i in range(m.tpu.hosts)
        )},
        {"name": "JAX_COORDINATOR_ADDRESS", "value":
            f"{name}-0.{name}-workers.{spec.namespace}.svc.cluster.local:8476"},
        {"name": "JAX_NUM_PROCESSES", "value": str(m.tpu.hosts)},
    ]
    sts = {
        "apiVersion": "apps/v1",
        "kind": "StatefulSet",
        "metadata": _meta(name, spec, "model-server"),
        "spec": {
            "serviceName": f"{name}-workers",
            "replicas": m.tpu.hosts,
            "podManagementPolicy": "Parallel",  # gang start: all workers at once
            "selector": {"matchLabels": {"app": name}},
            "template": {
                "metadata": {
                    "labels": _labels(name, "model-server"),
                    "annotations": _scrape_annotations(),
                },
                "spec": {
                    "subdomain": f"{name}-workers",
                    "terminationGracePeriodSeconds": MODEL_GRACE_S,
                    "nodeSelector": _tpu_node_selector(m),
                    "containers": [container],
                    "volumes": _volumes(m, spec),
                },
            },
        },
    }
    return [headless, sts]


def render_model_service(m: ModelSpec, spec: DeploySpec) -> Manifest:
    name = _res_name(m)
    selector: Manifest = {"app": name}
    if m.tpu is not None and m.tpu.multi_host:
        # only the coordinator pod serves HTTP
        selector = {"statefulset.kubernetes.io/pod-name": f"{name}-0"}
    return {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": _meta(name, spec, "model-service"),
        "spec": {
            "type": "ClusterIP",
            "selector": selector,
            "ports": [{"port": ENGINE_PORT, "targetPort": ENGINE_PORT,
                       "name": "http"}],
        },
    }


def _peak_replicas(m: ModelSpec) -> int:
    """Most replicas this model can ever run: the static count, or the
    autoscaler's ceiling when one is configured. Routing topology (the
    headless -replicas Service) keys off this, not the instantaneous
    count — an HPA scaling 1 -> 4 must not change the backend URL."""
    if m.autoscaling is not None:
        return max(m.replicas, m.autoscaling.max_replicas)
    return m.replicas


def render_model_replica_service(m: ModelSpec,
                                 spec: DeploySpec) -> Optional[Manifest]:
    """Headless Service over a replicated single-host model's pods.

    The router targets this for replicas > 1: headless DNS resolves to the
    ready pod IPs directly, so a new connection after a failover retry can
    land on a different replica than the one that just refused (a ClusterIP
    Service would be a single conntrack-balanced VIP hiding the replicas).
    """
    if _peak_replicas(m) <= 1 or (m.tpu is not None and m.tpu.multi_host):
        return None
    name = _res_name(m)
    return {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": _meta(f"{name}-replicas", spec, "model-replicas"),
        "spec": {
            "clusterIP": "None",
            "selector": {"app": name},
            "ports": [{"port": ENGINE_PORT, "name": "http"}],
        },
    }


# Scale-down damping shared by the HPA behavior block and the KEDA
# cooldownPeriod: one replica per minute after a 5-minute quiet window,
# so a burst's tail never mass-SIGTERMs replicas mid-stream (each removal
# still runs the full drain lifecycle: preStop sleep + graceful drain).
SCALE_DOWN_STABILIZATION_S = 300


def _ttft_miss_milli(a) -> int:
    """TTFT miss-ratio threshold in thousandths (k8s quantity millis):
    a 0.95 attainment floor = scale out beyond 50m missed."""
    return int(round((1.0 - a.ttft_ok_ratio_floor) * 1000))


def render_model_autoscaler(m: ModelSpec,
                            spec: DeploySpec) -> Optional[Manifest]:
    """One autoscaler per model with an ``autoscaling:`` block.

    minReplicas >= 1: an ``autoscaling/v2`` HPA. ``llm_queue_depth`` is a
    per-pod series (prometheus-adapter exposes it on the custom-metrics
    API from the pods' own /metrics scrape); TTFT attainment rides along
    as an Object metric on the api-gateway Service — the router emits
    ``llm_slo_ttft_miss_ratio`` over its sliding SLO window, so the
    target is the MISS ratio (HPA scales up when a Value metric exceeds
    its target; the ok-ratio would have the inverted sign).

    minReplicas == 0: a KEDA ScaledObject (the HPA cannot reach zero).
    Its prometheus triggers query the series Prometheus scrapes from the
    router's ``/metrics/cluster``; the queue trigger adds the router-side
    arrival rate (``llm_router_requests_total``) so a fully scaled-to-
    zero model — whose engines emit no queue depth at all — still wakes
    on incoming demand.

    Disaggregated roles scale on the signal their pool actually bounds:
    a ``prefill`` pool on its own queue depth only (tickets waiting for
    prompt ingestion; gateway TTFT conflates both pools), a ``decode``
    pool on the TTFT-attainment Object metric only (the decode fleet is
    what keeps streams flowing; its queue is by construction shallow).
    """
    a = m.autoscaling
    if a is None:
        return None
    name = _res_name(m)
    queue_metric = {"type": "Pods", "pods": {
        "metric": {"name": "llm_queue_depth"},
        "target": {"type": "AverageValue",
                   "averageValue": str(a.queue_depth_target)}}}
    ttft_metric = {"type": "Object", "object": {
        "metric": {"name": "llm_slo_ttft_miss_ratio"},
        "describedObject": {"apiVersion": "v1",
                            "kind": "Service",
                            "name": "api-gateway"},
        "target": {"type": "Value",
                   "value": f"{_ttft_miss_milli(a)}m"}}}
    if m.role == "prefill":
        metrics = [queue_metric]
    elif m.role == "decode":
        metrics = [ttft_metric]
    else:
        metrics = [queue_metric, ttft_metric]
    if a.min_replicas >= 1:
        return {
            "apiVersion": "autoscaling/v2",
            "kind": "HorizontalPodAutoscaler",
            "metadata": _meta(name, spec, "autoscaler"),
            "spec": {
                "scaleTargetRef": {"apiVersion": "apps/v1",
                                   "kind": "Deployment", "name": name},
                "minReplicas": a.min_replicas,
                "maxReplicas": a.max_replicas,
                "metrics": metrics,
                "behavior": {"scaleDown": {
                    "stabilizationWindowSeconds": SCALE_DOWN_STABILIZATION_S,
                    "policies": [{"type": "Pods", "value": 1,
                                  "periodSeconds": 60}],
                }},
            },
        }
    role_sel = "" if m.role == "both" else f',role="{m.role}"'
    queue_query = (
        f'sum(llm_queue_depth{{model="{m.model_name}"{role_sel}}}) + '
        f'sum(rate(llm_router_requests_total{{model="{m.model_name}"}}[1m]))'
    )
    queue_trigger = {"type": "prometheus", "metadata": {
        "serverAddress": spec.prometheus_url,
        "metricName": "llm_queue_depth",
        "query": queue_query,
        "threshold": str(a.queue_depth_target)}}
    # percent integer, not a float ratio: the Helm template
    # must render the identical string without float math
    ttft_trigger = {"type": "prometheus", "metadata": {
        "serverAddress": spec.prometheus_url,
        "metricName": "llm_slo_ttft_miss_ratio",
        "query": "100 * max(llm_slo_ttft_miss_ratio)",
        "threshold":
            str(int(round((1.0 - a.ttft_ok_ratio_floor)
                          * 100)))}}
    if m.role == "prefill":
        triggers = [queue_trigger]
    elif m.role == "decode":
        triggers = [ttft_trigger]
    else:
        triggers = [queue_trigger, ttft_trigger]
    return {
        "apiVersion": "keda.sh/v1alpha1",
        "kind": "ScaledObject",
        "metadata": _meta(name, spec, "autoscaler"),
        "spec": {
            "scaleTargetRef": {"name": name},
            "minReplicaCount": a.min_replicas,
            "maxReplicaCount": a.max_replicas,
            "cooldownPeriod": SCALE_DOWN_STABILIZATION_S,
            "triggers": triggers,
        },
    }


def render_model_pvc(m: ModelSpec, spec: DeploySpec) -> Optional[Manifest]:
    if not m.huggingface_id:
        return None
    access = "ReadOnlyMany" if m.pvc_shared else "ReadWriteOnce"
    pvc: Manifest = {
        "apiVersion": "v1",
        "kind": "PersistentVolumeClaim",
        "metadata": _meta(f"{_res_name(m)}-cache", spec, "weight-cache"),
        "spec": {
            "accessModes": [access],
            "resources": {"requests": {"storage": m.pvc_size}},
        },
    }
    if spec.storage_class:
        pvc["spec"]["storageClassName"] = spec.storage_class
    return pvc


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------

def _backend_urls(m: ModelSpec, spec: DeploySpec) -> list[str]:
    """Replica-set URLs for one models[] entry (always a list)."""
    if _peak_replicas(m) > 1 and not (m.tpu is not None and m.tpu.multi_host):
        # replicated single-host model: route via the headless -replicas
        # Service, whose DNS answers with the READY pod IPs (Deployment
        # pods have no stable per-pod names to enumerate). Explicit
        # multi-URL replica lists remain a config-level capability for
        # out-of-cluster replicas.
        return [f"http://{_res_name(m)}-replicas."
                f"{spec.namespace}.svc.cluster.local:{ENGINE_PORT}"]
    return [f"http://{_res_name(m)}."
            f"{spec.namespace}.svc.cluster.local:{ENGINE_PORT}"]


def router_config(spec: DeploySpec) -> dict[str, Any]:
    """The router's model→replica-set table (consumed by server/router.py
    and by the native C++ router alike)."""
    # a disaggregated pair shares its modelName: both entries' URLs merge
    # into one replica set, and the roles map tells the router which URL
    # serves which hop of the two-hop flow
    backends: dict[str, list[str]] = {}
    roles: dict[str, str] = {}
    for m in spec.models:
        urls = _backend_urls(m, spec)
        backends.setdefault(m.model_name, []).extend(urls)
        if m.role != "both":
            for u in urls:
                roles[u] = m.role
    cfg: dict[str, Any] = {
        "backends": backends,
        "default_model": spec.resolved_default,
        "strict": spec.strict_routing,
        "probe_interval_s": spec.probe_interval_s,
        # zero-drop streams: journal resume + hedging knobs (ISSUE 9),
        # parsed by both router implementations
        "stream_resume": spec.stream_resume,
        "resume_attempts": spec.resume_attempts,
        "hedge_ms": spec.hedge_ms,
    }
    if roles:
        cfg["roles"] = roles
        cfg["handoff_retries"] = spec.handoff_retries
    adapters = {m.model_name: [a.name for a in m.adapters]
                for m in spec.models if m.adapters}
    if adapters:
        # base:adapter requests resolve at the gateway; unknown adapters
        # of a known base 404 instead of falling back to the base model
        cfg["adapters"] = adapters
    if spec.qos is not None:
        # per-tenant QoS (ISSUE 10): fair shares, rate limits, brownout —
        # identical wire keys for both router implementations
        cfg["qos"] = spec.qos.to_wire()
    if spec.outlier_ejection is not None:
        # gray-failure layer (ISSUE 17): latency/error outlier ejection —
        # a non-empty block enables it in both router implementations
        cfg["outlier_ejection"] = spec.outlier_ejection.to_wire()
    if spec.retry_budget is not None:
        cfg["retry_budget"] = spec.retry_budget.to_wire()
    if spec.prefix_affinity is not None:
        # prefix-affinity + cache-aware routing (ISSUE 18): a non-empty
        # block enables the layer in both router implementations
        cfg["prefix_affinity"] = spec.prefix_affinity.to_wire()
    if spec.tracing is not None:
        # cross-hop tracing (ISSUE 19): OTLP export + tail sampling — a
        # non-empty block enables the exporter in both implementations
        # (traceparent propagation itself needs no config)
        cfg["tracing"] = spec.tracing.to_wire()
    return cfg


def config_hash(spec: DeploySpec) -> str:
    blob = json.dumps(router_config(spec), sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def render_router(spec: DeploySpec) -> list[Manifest]:
    cfg = router_config(spec)
    cm = {
        "apiVersion": "v1",
        "kind": "ConfigMap",
        "metadata": _meta("api-gateway-config", spec, "router-config"),
        "data": {"router.json": json.dumps(cfg, indent=2, sort_keys=True)},
    }
    args = ["router", "--config", "/etc/router/router.json"]
    dep = {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": _meta("api-gateway", spec, "router"),
        "spec": {
            "replicas": 2,   # the reference's only redundancy was its python
                             # gateway's 2 replicas (api-gateway.yaml:121)
            "selector": {"matchLabels": {"app": "api-gateway"}},
            "template": {
                "metadata": {
                    "labels": _labels("api-gateway", "router"),
                    # config-hash annotation: rolls the router pods whenever
                    # the models[] list changes (reference gap, SURVEY §3.2)
                    "annotations": {
                        "checksum/router-config": config_hash(spec),
                        **_scrape_annotations(ROUTER_PORT),
                    },
                },
                "spec": {
                    "terminationGracePeriodSeconds": ROUTER_GRACE_S,
                    "containers": [{
                        "name": "router",
                        "image": spec.image,
                        "imagePullPolicy": spec.image_pull_policy,
                        **_lifecycle(),
                        "command": (
                            ["/usr/local/bin/tpu-router"] if spec.native_router
                            else ["python", "-m", "llms_on_kubernetes_tpu"]
                        ),
                        "args": args,
                        "ports": [{"containerPort": ROUTER_PORT, "name": "http"}],
                        "volumeMounts": [{
                            "name": "config", "mountPath": "/etc/router",
                        }],
                        "readinessProbe": {
                            "httpGet": {"path": "/health", "port": ROUTER_PORT},
                            "initialDelaySeconds": 2, "periodSeconds": 5,
                        },
                        "livenessProbe": {
                            "httpGet": {"path": "/health", "port": ROUTER_PORT},
                            "initialDelaySeconds": 10, "periodSeconds": 20,
                        },
                        "resources": {
                            "requests": {"cpu": "100m", "memory": "128Mi"},
                            "limits": {"cpu": "1", "memory": "512Mi"},
                        },
                    }],
                    "volumes": [{
                        "name": "config",
                        "configMap": {"name": "api-gateway-config"},
                    }],
                },
            },
        },
    }
    svc = {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": _meta("api-gateway", spec, "router"),
        "spec": {
            "type": "ClusterIP",
            "selector": {"app": "api-gateway"},
            "ports": [{"port": ROUTER_PORT, "targetPort": ROUTER_PORT,
                       "name": "http"}],
        },
    }
    return [cm, dep, svc]


# ---------------------------------------------------------------------------
# Istio + WebUI
# ---------------------------------------------------------------------------

def render_istio(spec: DeploySpec) -> list[Manifest]:
    gw = {
        "apiVersion": "networking.istio.io/v1beta1",
        "kind": "Gateway",
        "metadata": _meta("tpu-models-gateway", spec, "ingress"),
        "spec": {
            "selector": {"istio": "ingressgateway"},
            "servers": [{
                "port": {"number": 80, "name": "http", "protocol": "HTTP"},
                "hosts": ["*"],
            }],
        },
    }
    gateway_dst = [{"destination": {
        "host": f"api-gateway.{spec.namespace}.svc.cluster.local",
        "port": {"number": ROUTER_PORT}}}]
    routes = [
        {"match": [{"uri": {"exact": "/v1/models"}}], "route": gateway_dst},
        {"match": [{"uri": {"prefix": "/v1/"}}], "route": gateway_dst},
        {"match": [{"uri": {"prefix": "/health"}}], "route": gateway_dst},
    ]
    if spec.webui_enabled:
        routes.append({
            "match": [{"uri": {"prefix": "/"}}],
            "route": [{"destination": {
                "host": f"webui.{spec.namespace}.svc.cluster.local",
                "port": {"number": WEBUI_PORT}}}],
        })
    vs = {
        "apiVersion": "networking.istio.io/v1beta1",
        "kind": "VirtualService",
        "metadata": _meta("tpu-models-routes", spec, "ingress"),
        "spec": {
            "hosts": ["*"],
            "gateways": ["tpu-models-gateway"],
            "http": routes,
        },
    }
    return [gw, vs]


def render_webui(spec: DeploySpec) -> list[Manifest]:
    if not spec.webui_enabled:
        return []
    dep = {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": _meta("webui", spec, "webui"),
        "spec": {
            "replicas": 1,
            "selector": {"matchLabels": {"app": "webui"}},
            "template": {
                "metadata": {
                    "labels": _labels("webui", "webui"),
                    # explicit opt-out: OpenWebUI exposes no Prometheus
                    # endpoint, so the annotation documents the decision
                    # instead of leaving the pod silently unscraped
                    "annotations": {"prometheus.io/scrape": "false"},
                },
                "spec": {
                    "containers": [{
                        "name": "webui",
                        "image": "ghcr.io/open-webui/open-webui:dev-slim",
                        "env": [
                            {"name": "OPENAI_API_BASE_URLS", "value":
                                f"http://api-gateway.{spec.namespace}.svc.cluster.local:{ROUTER_PORT}/v1"},
                            {"name": "WEBUI_NAME", "value": spec.webui_name},
                        ],
                        "ports": [{"containerPort": WEBUI_PORT}],
                        "volumeMounts": [{
                            "name": "data", "mountPath": "/app/backend/data",
                        }],
                        "readinessProbe": {
                            "httpGet": {"path": "/", "port": WEBUI_PORT},
                            "initialDelaySeconds": 15, "periodSeconds": 10,
                        },
                        "resources": {
                            "requests": {"cpu": "200m", "memory": "512Mi"},
                            "limits": {"cpu": "1", "memory": "1Gi"},
                        },
                    }],
                    "volumes": [{
                        "name": "data",
                        "persistentVolumeClaim": {"claimName": "webui-data"},
                    }],
                },
            },
        },
    }
    svc = {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": _meta("webui", spec, "webui"),
        "spec": {
            "type": "ClusterIP",
            "selector": {"app": "webui"},
            "ports": [{"port": WEBUI_PORT, "targetPort": WEBUI_PORT}],
        },
    }
    pvc = {
        "apiVersion": "v1",
        "kind": "PersistentVolumeClaim",
        # chat history survives chart deletion, like the reference's
        # `helm.sh/resource-policy: keep` (webui-pvc.yaml:8-9)
        "metadata": _meta("webui-data", spec, "webui",
                          annotations={"helm.sh/resource-policy": "keep"}),
        "spec": {
            "accessModes": ["ReadWriteOnce"],
            "resources": {"requests": {"storage": "1Gi"}},
        },
    }
    return [dep, svc, pvc]


# ---------------------------------------------------------------------------
# Top level
# ---------------------------------------------------------------------------

def render_manifests(spec: DeploySpec) -> list[Manifest]:
    spec.validate()
    out: list[Manifest] = []
    for m in spec.models:
        if m.tpu is not None and m.tpu.multi_host:
            out += render_model_multi_host(m, spec)
        else:
            out += render_model_single_host(m, spec)
        out.append(render_model_service(m, spec))
        replica_svc = render_model_replica_service(m, spec)
        if replica_svc:
            out.append(replica_svc)
        pvc = render_model_pvc(m, spec)
        if pvc:
            out.append(pvc)
        hpa = render_model_autoscaler(m, spec)
        if hpa:
            out.append(hpa)
    out += render_router(spec)
    out += render_istio(spec)
    out += render_webui(spec)
    out += render_monitoring_manifests(spec)
    return out


def render_monitoring_manifests(spec: DeploySpec) -> list[Manifest]:
    """Alert-rules + Grafana-dashboard ConfigMaps (deploy.monitoring is
    the source of truth; deferred import keeps this module importable
    without pulling the dashboard payloads in)."""
    from llms_on_kubernetes_tpu.deploy.monitoring import render_monitoring

    return render_monitoring(spec)


def to_yaml(manifests: list[Manifest]) -> str:
    import yaml

    return "---\n".join(
        yaml.safe_dump(m, sort_keys=False, default_flow_style=False)
        for m in manifests
    )
