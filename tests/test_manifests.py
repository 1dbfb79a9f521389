"""Deploy layer: spec validation + manifest rendering.

The golden-file tests the reference never had for its Helm fan-out
(SURVEY §4: "manifest golden tests ... the one thing the reference could
have tested"). Covers the reference's per-model resource fan-out semantics
plus the TPU-native extensions (topologies, multi-host pod groups) and the
fixed reference defects (config-hash rollout, RWO x replicas deadlock)."""

import json

import pytest
import yaml

from llms_on_kubernetes_tpu.deploy.manifests import (
    config_hash, render_manifests, router_config, to_yaml,
)
from llms_on_kubernetes_tpu.deploy.spec import (
    DeploySpec, ModelSpec, ShardingSpec, SpecError, TPUSpec, load_spec,
)

BASE_YAML = """
namespace: tpu-models
models:
  - modelName: llama-3-8b
    huggingfaceId: meta-llama/Meta-Llama-3-8B-Instruct
    pvcSize: 40Gi
    tpu: {accelerator: v5e, chips: 8}
  - modelName: mistral-7b
    huggingfaceId: mistralai/Mistral-7B-Instruct-v0.2
    tpu: {accelerator: v5e, chips: 8}
router:
  strict: true
"""


def kinds(manifests, kind):
    return [m for m in manifests if m["kind"] == kind]


def by_name(manifests, kind, name):
    (m,) = [m for m in kinds(manifests, kind)
            if m["metadata"]["name"] == name]
    return m


def test_spec_round_trip_and_fanout():
    spec = load_spec(BASE_YAML)
    ms = render_manifests(spec)
    # reference fan-out: per model Deployment + Service + PVC (SURVEY §3.2)
    assert len(kinds(ms, "Deployment")) == 2 + 1 + 1  # models + router + webui
    assert {s["metadata"]["name"] for s in kinds(ms, "Service")} >= {
        "model-llama-3-8b", "model-mistral-7b", "api-gateway", "webui"}
    assert len(kinds(ms, "PersistentVolumeClaim")) == 3  # 2 caches + webui
    # every manifest lands in the namespace
    assert all(m["metadata"]["namespace"] == "tpu-models" for m in ms)
    # renders to valid multi-doc YAML
    docs = list(yaml.safe_load_all(to_yaml(ms)))
    assert len(docs) == len(ms)


def test_tpu_scheduling_replaces_gpu():
    """google.com/tpu + GKE nodeSelectors stand in for the reference's
    nvidia.com/gpu + taints (model-deployments.yaml:40-44,75-78)."""
    ms = render_manifests(load_spec(BASE_YAML))
    dep = by_name(ms, "Deployment", "model-llama-3-8b")
    pod = dep["spec"]["template"]["spec"]
    assert pod["nodeSelector"] == {
        "cloud.google.com/gke-tpu-accelerator": "tpu-v5-lite-podslice",
        "cloud.google.com/gke-tpu-topology": "2x4",
    }
    res = pod["containers"][0]["resources"]
    assert res["requests"]["google.com/tpu"] == "8"
    assert res["limits"]["google.com/tpu"] == "8"
    args = pod["containers"][0]["args"]
    assert "--tensor-parallel-size" in args
    assert args[args.index("--tensor-parallel-size") + 1] == "8"


def test_multi_host_renders_pod_group():
    """v5p-16 = 4 hosts x 4 chips -> StatefulSet pod group + headless
    Service + jax.distributed env (the capability gap in SURVEY §2.4)."""
    spec = load_spec("""
models:
  - modelName: llama-3-70b
    huggingfaceId: meta-llama/Meta-Llama-3-70B-Instruct
    pvcShared: true
    tpu: {accelerator: v5p, chips: 16}
""")
    ms = render_manifests(spec)
    sts = by_name(ms, "StatefulSet", "model-llama-3-70b")
    assert sts["spec"]["replicas"] == 4
    assert sts["spec"]["podManagementPolicy"] == "Parallel"
    env = {e["name"]: e.get("value") for e in
           sts["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert env["JAX_NUM_PROCESSES"] == "4"
    assert "model-llama-3-70b-0.model-llama-3-70b-workers" in env["JAX_COORDINATOR_ADDRESS"]
    assert len(env["TPU_WORKER_HOSTNAMES"].split(",")) == 4
    headless = by_name(ms, "Service", "model-llama-3-70b-workers")
    assert headless["spec"]["clusterIP"] == "None"
    # the request Service pins to the coordinator pod
    svc = by_name(ms, "Service", "model-llama-3-70b")
    assert svc["spec"]["selector"] == {
        "statefulset.kubernetes.io/pod-name": "model-llama-3-70b-0"}
    # per-host chip count, not whole-slice
    res = sts["spec"]["template"]["spec"]["containers"][0]["resources"]
    assert res["requests"]["google.com/tpu"] == "4"


def test_router_semantics_and_config_hash_rollout():
    spec = load_spec(BASE_YAML)
    ms = render_manifests(spec)
    cm = by_name(ms, "ConfigMap", "api-gateway-config")
    cfg = json.loads(cm["data"]["router.json"])
    assert cfg["default_model"] == "llama-3-8b"  # first model, like reference
    assert cfg["strict"] is True
    assert set(cfg["backends"]) == {"llama-3-8b", "mistral-7b"}
    # backend values are replica LISTS now (failover-capable routing)
    assert cfg["backends"]["mistral-7b"] == [
        "http://model-mistral-7b.tpu-models.svc.cluster.local:8080"]
    # config-hash annotation rolls the router on model changes (SURVEY §3.2
    # gap: the reference's gateway kept stale routes until restarted)
    dep = by_name(ms, "Deployment", "api-gateway")
    h1 = dep["spec"]["template"]["metadata"]["annotations"]["checksum/router-config"]
    assert h1 == config_hash(spec)
    spec2 = load_spec(BASE_YAML.replace("mistral-7b", "qwen3-8b"))
    assert config_hash(spec2) != h1


REPLICAS_YAML = """
namespace: tpu-models
models:
  - modelName: llama-3-8b
    huggingfaceId: meta-llama/Meta-Llama-3-8B-Instruct
    pvcShared: true
    replicas: 2
    tpu: {accelerator: v5e, chips: 8}
  - modelName: mistral-7b
    huggingfaceId: mistralai/Mistral-7B-Instruct-v0.2
    tpu: {accelerator: v5e, chips: 8}
"""


def test_replicated_model_gets_headless_service_and_replica_backends():
    """replicas > 1 adds a headless -replicas Service (DNS answers with the
    ready pod IPs, so a router failover reconnect can land on a different
    pod) and the router.json backend entry routes through it."""
    ms = render_manifests(load_spec(REPLICAS_YAML))
    headless = by_name(ms, "Service", "model-llama-3-8b-replicas")
    assert headless["spec"]["clusterIP"] == "None"
    assert headless["spec"]["selector"] == {"app": "model-llama-3-8b"}
    cfg = json.loads(by_name(ms, "ConfigMap", "api-gateway-config")
                     ["data"]["router.json"])
    assert cfg["backends"]["llama-3-8b"] == [
        "http://model-llama-3-8b-replicas.tpu-models.svc.cluster.local:8080"]
    # single-replica models keep the plain ClusterIP Service, no headless
    assert cfg["backends"]["mistral-7b"] == [
        "http://model-mistral-7b.tpu-models.svc.cluster.local:8080"]
    assert not [s for s in kinds(ms, "Service")
                if s["metadata"]["name"] == "model-mistral-7b-replicas"]
    assert cfg["probe_interval_s"] == 2.0


def test_drain_budget_prestop_and_grace():
    """Every workload ships the drain budget: preStop sleep holds SIGTERM
    until endpoint removal propagates; the grace period covers in-flight
    generations (engine) / relays (router)."""
    ms = render_manifests(load_spec(BASE_YAML))
    model = by_name(ms, "Deployment", "model-llama-3-8b")
    pod = model["spec"]["template"]["spec"]
    assert pod["terminationGracePeriodSeconds"] == 330
    assert pod["containers"][0]["lifecycle"]["preStop"]["exec"]["command"] \
        == ["sh", "-c", "sleep 5"]
    gw = by_name(ms, "Deployment", "api-gateway")
    gw_pod = gw["spec"]["template"]["spec"]
    assert gw_pod["terminationGracePeriodSeconds"] == 30
    assert gw_pod["containers"][0]["lifecycle"]["preStop"]["exec"]["command"] \
        == ["sh", "-c", "sleep 5"]
    # multi-host pod groups get the engine grace too
    spec = load_spec("""
models:
  - modelName: llama-3-70b
    huggingfaceId: meta-llama/Meta-Llama-3-70B-Instruct
    pvcShared: true
    tpu: {accelerator: v5p, chips: 16}
""")
    sts = by_name(render_manifests(spec), "StatefulSet", "model-llama-3-70b")
    assert sts["spec"]["template"]["spec"]["terminationGracePeriodSeconds"] == 330


def test_istio_routes_match_reference_shape():
    ms = render_manifests(load_spec(BASE_YAML))
    vs = by_name(ms, "VirtualService", "tpu-models-routes")
    matches = [r["match"][0]["uri"] for r in vs["spec"]["http"]]
    # 4-route shape of reference gateway.yaml:26-57
    assert matches == [
        {"exact": "/v1/models"}, {"prefix": "/v1/"},
        {"prefix": "/health"}, {"prefix": "/"},
    ]
    webui_dst = vs["spec"]["http"][-1]["route"][0]["destination"]["host"]
    assert webui_dst.startswith("webui.")


def test_webui_points_at_router():
    ms = render_manifests(load_spec(BASE_YAML))
    dep = by_name(ms, "Deployment", "webui")
    env = {e["name"]: e["value"] for e in
           dep["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert env["OPENAI_API_BASE_URLS"].endswith("api-gateway.tpu-models.svc.cluster.local:8080/v1")
    pvc = by_name(ms, "PersistentVolumeClaim", "webui-data")
    assert pvc["metadata"]["annotations"]["helm.sh/resource-policy"] == "keep"


def test_local_cpu_profile_uses_hostpath():
    """The ramalama-equivalent local path: hostPath weights, no TPU, no PVC
    (reference ramalama-models/helm-chart values.yaml:26)."""
    spec = DeploySpec(
        models=(ModelSpec(model_name="tinyllama", model_path="/mnt/models/tiny",
                          tpu=None),),
        host_model_path="/mnt/models", webui_enabled=True,
    )
    ms = render_manifests(spec)
    dep = by_name(ms, "Deployment", "model-tinyllama")
    pod = dep["spec"]["template"]["spec"]
    assert "nodeSelector" not in pod
    assert pod["volumes"][0]["hostPath"]["path"] == "/mnt/models"
    assert "resources" not in pod["containers"][0]
    assert kinds(ms, "PersistentVolumeClaim") == [
        by_name(ms, "PersistentVolumeClaim", "webui-data")]


def test_validation_errors():
    with pytest.raises(SpecError, match="DNS-1123"):
        load_spec("models: [{modelName: 'Bad_Name', huggingfaceId: x}]")
    with pytest.raises(SpecError, match="duplicate"):
        load_spec("""
models:
  - {modelName: a, huggingfaceId: x}
  - {modelName: a, huggingfaceId: y}
""")
    with pytest.raises(SpecError, match="deadlock"):
        load_spec("models: [{modelName: a, huggingfaceId: x, replicas: 2}]")
    # the fix: shared read-only cache allows replicas
    load_spec("models: [{modelName: a, huggingfaceId: x, replicas: 2, pvcShared: true}]")
    with pytest.raises(SpecError, match="unknown model keys"):
        load_spec("models: [{modelName: a, huggingfaceId: x, dnsResolver: z}]")
    with pytest.raises(SpecError, match="sharding"):
        ModelSpec(model_name="a", huggingface_id="x",
                  tpu=TPUSpec(chips=8),
                  sharding=ShardingSpec(tp=3)).validate()
    with pytest.raises(SpecError, match="defaultModel"):
        spec = load_spec(BASE_YAML)
        DeploySpec(models=spec.models, default_model="nope").validate()
    with pytest.raises(SpecError, match="decodeSteps"):
        load_spec("models: [{modelName: a, huggingfaceId: x, decodeSteps: 0}]")


def test_speculation_spec_validation():
    """ISSUE 12: speculation/draft knobs are validated at spec load, not
    at pod start — a typo'd tier or a draft tier with no model fails
    `deploy validate`, not the rollout."""
    with pytest.raises(SpecError, match="speculation"):
        load_spec("models: [{modelName: a, huggingfaceId: x, "
                  "speculation: banana}]")
    with pytest.raises(SpecError, match="draft"):
        load_spec("models: [{modelName: a, huggingfaceId: x, "
                  "speculation: draft}]")
    with pytest.raises(SpecError, match="unused"):
        load_spec("models: [{modelName: a, huggingfaceId: x, "
                  "speculation: ngram, draft: tiny}]")
    with pytest.raises(SpecError, match="decodeSteps >= 2"):
        load_spec("models: [{modelName: a, huggingfaceId: x, "
                  "speculation: ngram, decodeSteps: 1}]")
    # draft: alone implies speculation: draft (mirrors EngineConfig)
    spec = load_spec("models: [{modelName: a, huggingfaceId: x, "
                     "draft: /models/d.gguf}]")
    assert spec.models[0].speculation == "draft"
    load_spec("models: [{modelName: a, huggingfaceId: x, "
              "speculation: ngram, decodeSteps: 4}]")


def test_speculation_threads_to_engine_env():
    """ISSUE 12: speculation/draft ride as LLMK_SPECULATION /
    LLMK_DRAFT_MODEL env, same convention as the decode window."""
    spec = load_spec("""
namespace: tpu-models
models:
  - modelName: llama-3-8b
    huggingfaceId: meta-llama/Meta-Llama-3-8B-Instruct
    decodeSteps: 8
    speculation: ngram
    tpu: {accelerator: v5e, chips: 8}
  - modelName: mistral-7b
    huggingfaceId: mistralai/Mistral-7B-Instruct-v0.2
    draft: /models/draft.gguf
    tpu: {accelerator: v5e, chips: 8}
""")
    ms = render_manifests(spec)
    env = {e["name"]: e.get("value") for e in
           by_name(ms, "Deployment", "model-llama-3-8b")
           ["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert env["LLMK_SPECULATION"] == "ngram"
    assert "LLMK_DRAFT_MODEL" not in env
    env2 = {e["name"]: e.get("value") for e in
            by_name(ms, "Deployment", "model-mistral-7b")
            ["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert env2["LLMK_SPECULATION"] == "draft"
    assert env2["LLMK_DRAFT_MODEL"] == "/models/draft.gguf"


def test_compile_cache_lives_on_the_weight_pvc():
    """Every workload that mounts the weight PVC (Deployment and multi-host
    StatefulSet) points JAX_COMPILATION_CACHE_DIR into it, so a restarted
    pod finds its executables; the path sits under the PVC's mount."""
    spec = load_spec("""
namespace: tpu-models
models:
  - modelName: mistral-7b
    huggingfaceId: mistralai/Mistral-7B-Instruct-v0.2
    tpu: {accelerator: v5e, chips: 8}
  - modelName: llama-3-70b
    huggingfaceId: meta-llama/Meta-Llama-3-70B-Instruct
    tpu: {accelerator: v5e, chips: 16}
""")
    ms = render_manifests(spec)
    for kind, name in (("Deployment", "model-mistral-7b"),
                       ("StatefulSet", "model-llama-3-70b")):
        c = by_name(ms, kind, name)["spec"]["template"]["spec"][
            "containers"][0]
        env = {e["name"]: e.get("value") for e in c["env"]}
        mounts = [m["mountPath"] for m in c["volumeMounts"]
                  if m["name"] == "hf-cache"]
        assert len(mounts) == 1
        assert env["JAX_COMPILATION_CACHE_DIR"].startswith(mounts[0] + "/")


def test_decode_steps_threads_to_engine_env():
    """ISSUE 8: decodeSteps rides as LLMK_DECODE_STEPS env (not an engine
    arg, keeping the argv contract stable); absent by default."""
    spec = load_spec("""
namespace: tpu-models
models:
  - modelName: llama-3-8b
    huggingfaceId: meta-llama/Meta-Llama-3-8B-Instruct
    decodeSteps: 8
    tpu: {accelerator: v5e, chips: 8}
  - modelName: mistral-7b
    huggingfaceId: mistralai/Mistral-7B-Instruct-v0.2
    tpu: {accelerator: v5e, chips: 8}
""")
    assert spec.models[0].decode_steps == 8
    assert spec.models[1].decode_steps is None
    ms = render_manifests(spec)
    env = {e["name"]: e.get("value") for e in
           by_name(ms, "Deployment", "model-llama-3-8b")
           ["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert env["LLMK_DECODE_STEPS"] == "8"
    env2 = {e["name"] for e in
            by_name(ms, "Deployment", "model-mistral-7b")
            ["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert "LLMK_DECODE_STEPS" not in env2


AUTOSCALE_YAML = """
namespace: tpu-models
models:
  - modelName: llama-3-8b
    huggingfaceId: meta-llama/Meta-Llama-3-8B-Instruct
    pvcShared: true
    tpu: {accelerator: v5e, chips: 8}
    autoscaling: {minReplicas: 1, maxReplicas: 4, queueDepthTarget: 8,
                  ttftOkRatioFloor: 0.95}
  - modelName: mistral-7b
    huggingfaceId: mistralai/Mistral-7B-Instruct-v0.2
    pvcShared: true
    replicas: 0
    tpu: {accelerator: v5e, chips: 8}
    autoscaling: {minReplicas: 0, maxReplicas: 2, queueDepthTarget: 4}
"""


def test_autoscaling_hpa_golden():
    """ISSUE 7: minReplicas >= 1 renders an autoscaling/v2 HPA on
    llm_queue_depth (Pods) + TTFT-SLO attainment (Object on the gateway
    Service), with the slow-scale-down behavior that keeps a burst's
    replicas warm for the next one."""
    ms = render_manifests(load_spec(AUTOSCALE_YAML))
    hpa = by_name(ms, "HorizontalPodAutoscaler", "model-llama-3-8b")
    assert hpa["apiVersion"] == "autoscaling/v2"
    assert hpa["spec"]["scaleTargetRef"] == {
        "apiVersion": "apps/v1", "kind": "Deployment",
        "name": "model-llama-3-8b"}
    assert hpa["spec"]["minReplicas"] == 1
    assert hpa["spec"]["maxReplicas"] == 4
    assert hpa["spec"]["metrics"] == [
        {"type": "Pods", "pods": {
            "metric": {"name": "llm_queue_depth"},
            "target": {"type": "AverageValue", "averageValue": "8"}}},
        {"type": "Object", "object": {
            "metric": {"name": "llm_slo_ttft_miss_ratio"},
            "describedObject": {"apiVersion": "v1", "kind": "Service",
                                "name": "api-gateway"},
            # 1 - 0.95 floor, as integer millis (no float-format drift
            # between the Python renderer and the Helm template)
            "target": {"type": "Value", "value": "50m"}}},
    ]
    assert hpa["spec"]["behavior"] == {"scaleDown": {
        "stabilizationWindowSeconds": 300,
        "policies": [{"type": "Pods", "value": 1, "periodSeconds": 60}]}}
    # no ScaledObject for the HPA-managed model
    assert not [m for m in kinds(ms, "ScaledObject")
                if m["metadata"]["name"] == "model-llama-3-8b"]


def test_autoscaling_scaledobject_golden():
    """minReplicas: 0 renders a KEDA ScaledObject instead: Prometheus
    queue-depth trigger with a router arrival-rate term (the wake-from-
    zero signal — at zero replicas there are no pods to report queue
    depth) plus the TTFT trigger as an integer percent."""
    ms = render_manifests(load_spec(AUTOSCALE_YAML))
    so = by_name(ms, "ScaledObject", "model-mistral-7b")
    assert so["apiVersion"] == "keda.sh/v1alpha1"
    assert so["spec"]["scaleTargetRef"] == {"name": "model-mistral-7b"}
    assert so["spec"]["minReplicaCount"] == 0
    assert so["spec"]["maxReplicaCount"] == 2
    assert so["spec"]["cooldownPeriod"] == 300
    prom = "http://prometheus-server.monitoring.svc.cluster.local:9090"
    assert so["spec"]["triggers"] == [
        {"type": "prometheus", "metadata": {
            "serverAddress": prom,
            "metricName": "llm_queue_depth",
            "query": 'sum(llm_queue_depth{model="mistral-7b"}) + '
                     'sum(rate(llm_router_requests_total{model="mistral-7b"}'
                     '[1m]))',
            "threshold": "4"}},
        {"type": "prometheus", "metadata": {
            "serverAddress": prom,
            "metricName": "llm_slo_ttft_miss_ratio",
            "query": "100 * max(llm_slo_ttft_miss_ratio)",
            "threshold": "5"}},
    ]
    # the scaled-to-zero Deployment starts at replicas: 0
    dep = by_name(ms, "Deployment", "model-mistral-7b")
    assert dep["spec"]["replicas"] == 0
    # no HPA for the KEDA-managed model (they would fight over the
    # replica count)
    assert not [m for m in kinds(ms, "HorizontalPodAutoscaler")
                if m["metadata"]["name"] == "model-mistral-7b"]


def test_autoscaling_peak_drives_replica_routing():
    """Routing topology keys off the PEAK replica count (autoscaling
    maxReplicas), not the instantaneous one: a model at replicas: 1 that
    can scale to 4 still needs the headless -replicas Service and the
    router must route through it, or scaled-out pods get no traffic."""
    ms = render_manifests(load_spec(AUTOSCALE_YAML))
    for name in ("model-llama-3-8b", "model-mistral-7b"):
        headless = by_name(ms, "Service", f"{name}-replicas")
        assert headless["spec"]["clusterIP"] == "None"
    cfg = json.loads(by_name(ms, "ConfigMap", "api-gateway-config")
                     ["data"]["router.json"])
    assert cfg["backends"]["llama-3-8b"] == [
        "http://model-llama-3-8b-replicas.tpu-models.svc.cluster.local:8080"]


def test_autoscaling_validation():
    base = "modelName: a, huggingfaceId: x, pvcShared: true"
    with pytest.raises(SpecError, match="maxReplicas"):
        load_spec("models: [{%s, autoscaling: {minReplicas: 3, "
                  "maxReplicas: 2}}]" % base)
    with pytest.raises(SpecError, match="unknown autoscaling keys"):
        load_spec("models: [{%s, autoscaling: {replicas: 2}}]" % base)
    # replicas: 0 is only meaningful under scale-to-zero autoscaling
    with pytest.raises(SpecError, match="scale-to-zero"):
        load_spec("models: [{%s, replicas: 0}]" % base)
    # autoscaling a multi-host pod group is unsupported (replicas are the
    # GROUP size, not a capacity dial)
    with pytest.raises(SpecError, match="multi-host"):
        load_spec("""
models:
  - modelName: big
    huggingfaceId: x
    pvcShared: true
    tpu: {accelerator: v5p, chips: 16}
    autoscaling: {minReplicas: 1, maxReplicas: 2}
""")
    # peak replicas (maxReplicas), not current, drives the RWO deadlock
    # check: replicas: 1 but scalable to 2 still needs pvcShared
    with pytest.raises(SpecError, match="deadlock"):
        load_spec("models: [{modelName: a, huggingfaceId: x, "
                  "autoscaling: {minReplicas: 1, maxReplicas: 2}}]")


def test_sharding_resolution():
    assert ShardingSpec().resolve(8) == ShardingSpec(tp=8, ep=1, data=1)
    assert ShardingSpec(ep=8).resolve(16) == ShardingSpec(tp=2, ep=8, data=1)
    # mixtral EP config from BASELINE.json configs[3]
    spec = load_spec("""
models:
  - modelName: mixtral-8x7b
    huggingfaceId: mistralai/Mixtral-8x7B-Instruct-v0.1
    tpu: {accelerator: v5e, chips: 8}
    sharding: {ep: 8}
""")
    args = render_manifests(spec)[0]["spec"]["template"]["spec"]["containers"][0]["args"]
    assert args[args.index("--expert-parallel-size") + 1] == "8"
    assert args[args.index("--tensor-parallel-size") + 1] == "1"


def test_render_cli(tmp_path, capsys):
    from llms_on_kubernetes_tpu.cli import main

    cfg = tmp_path / "models.yaml"
    cfg.write_text(BASE_YAML)
    assert main(["render", "--config", str(cfg)]) == 0
    docs = list(yaml.safe_load_all(capsys.readouterr().out))
    assert any(d["kind"] == "ConfigMap" for d in docs)


def test_router_config_matches_python_router():
    """The rendered router.json drives server/router.py directly."""
    from llms_on_kubernetes_tpu.server.router import Router

    cfg = router_config(load_spec(BASE_YAML))
    r = Router(cfg["backends"], cfg["default_model"], cfg["strict"])
    assert r.select_backend(b'{"model": "mistral-7b"}')[0] == "mistral-7b"
    name, err = r.select_backend(b'{"model": "nope"}')
    assert err is not None  # strict


def test_router_config_stream_resilience_knobs():
    """ISSUE 9: router.streamResume/resumeAttempts/hedgeMs flow into
    router.json (defaults: resume on, 2 attempts, hedging off) and the
    python Router honors them over the env knobs. Falsy overrides must
    survive — the historical Helm `default`-swallows-false bug is exactly
    what the hasKey template + this test guard against."""
    from llms_on_kubernetes_tpu.server.router import Router

    cfg = router_config(load_spec(BASE_YAML))
    assert cfg["stream_resume"] is True
    assert cfg["resume_attempts"] == 2
    assert cfg["hedge_ms"] == 0.0

    tuned = BASE_YAML.replace(
        "router:",
        "router:\n  streamResume: false\n  resumeAttempts: 0\n"
        "  hedgeMs: 75.5")
    cfg2 = router_config(load_spec(tuned))
    assert cfg2["stream_resume"] is False
    assert cfg2["resume_attempts"] == 0
    assert cfg2["hedge_ms"] == 75.5
    # knob changes roll the router pods via the config-hash annotation
    assert config_hash(load_spec(tuned)) != config_hash(load_spec(BASE_YAML))

    r = Router(cfg2["backends"], cfg2["default_model"], cfg2["strict"],
               stream_resume=cfg2["stream_resume"],
               resume_attempts=cfg2["resume_attempts"],
               hedge_ms=cfg2["hedge_ms"])
    assert r.stream_resume is False
    assert r.resume_attempts == 0
    assert r.hedge_ms == 75.5

    import pytest as _pytest

    from llms_on_kubernetes_tpu.deploy.spec import SpecError
    with _pytest.raises(SpecError):
        load_spec(BASE_YAML.replace("router:", "router:\n  hedgeMs: -1"))
    with _pytest.raises(SpecError):
        load_spec(BASE_YAML.replace("router:",
                                    "router:\n  resumeAttempts: -2"))


def test_monitoring_configmaps_rendered():
    """ISSUE 5: render_manifests ships the alert-rules and Grafana
    dashboard ConfigMaps; payloads are well-formed and land in the
    namespace like everything else."""
    ms = render_manifests(load_spec(BASE_YAML))
    alerts = by_name(ms, "ConfigMap", "llmk-alert-rules")
    rules = yaml.safe_load(alerts["data"]["llmk-alerts.yaml"])
    group_names = [g["name"] for g in rules["groups"]]
    assert "llmk-slo" in group_names and "llmk-serving" in group_names
    all_rules = [r for g in rules["groups"] for r in g["rules"]]
    by_alert = {r["alert"]: r for r in all_rules}
    # the alerts the issue names: SLO burn, wedged engine, replica health
    assert "llm_slo_error_budget_burn_rate" in \
        by_alert["LLMKErrorBudgetFastBurn"]["expr"]
    assert by_alert["LLMKEngineWedged"]["expr"] == "llm_engine_state == 3"
    assert by_alert["LLMKReplicaUnhealthy"]["expr"] == \
        "llm_replica_healthy == 0"
    assert all(r.get("for") and r["labels"]["severity"] in
               ("page", "ticket") for r in all_rules)

    dash = by_name(ms, "ConfigMap", "llmk-grafana-dashboard")
    assert dash["metadata"]["labels"]["grafana_dashboard"] == "1"
    board = json.loads(dash["data"]["llmk-dashboard.json"])
    assert board["uid"] == "llmk-overview"
    assert len(board["panels"]) >= 8
    assert alerts["metadata"]["namespace"] == "tpu-models"


def test_monitoring_alert_exprs_reference_emitted_series():
    """Every llm_* name in an alert expr / dashboard target must be a
    series the servers emit (metrics_lint's constructor-derived
    inventory) — the lockstep check behind scripts/check_monitoring.py."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "scripts"))
    from metrics_lint import known_emitted_names

    from llms_on_kubernetes_tpu.deploy.monitoring import (
        referenced_metric_names,
    )

    missing = referenced_metric_names() - known_emitted_names()
    assert not missing, f"alerts reference non-emitted series: {missing}"


def test_monitoring_chart_files_in_sync():
    """The copies committed under each chart's files/ (mounted via
    .Files.Get) must be byte-identical to what deploy.monitoring renders —
    otherwise helm ships stale alert rules."""
    import pathlib

    from llms_on_kubernetes_tpu.deploy import monitoring

    root = pathlib.Path(__file__).resolve().parent.parent / "k8s"
    payloads = {
        monitoring.ALERT_RULES_KEY: monitoring.alert_rules_yaml(),
        monitoring.DASHBOARD_KEY: monitoring.dashboard_json(),
    }
    for chart in ("tpu-models", "local-models"):
        for fname, want in payloads.items():
            path = root / chart / "helm-chart" / "files" / fname
            assert path.exists(), (
                f"{path} missing — run scripts/check_monitoring.py --write")
            assert path.read_text() == want, (
                f"{path} stale — run scripts/check_monitoring.py --write")


def test_values_schema_validates_chart_defaults():
    """Both charts' values.yaml must validate against their
    values.schema.json (the reference shipped no schema — SURVEY §5 gap),
    and obvious misconfigurations must be rejected."""
    import copy
    import json
    import pathlib

    jsonschema = pytest.importorskip("jsonschema")
    root = pathlib.Path(__file__).resolve().parent.parent / "k8s"
    for chart in ("tpu-models", "local-models"):
        cdir = root / chart / "helm-chart"
        schema = json.loads((cdir / "values.schema.json").read_text())
        values = yaml.safe_load((cdir / "values.yaml").read_text())
        jsonschema.validate(values, schema)

        bad = copy.deepcopy(values)
        bad["models"][0]["modelName"] = "Bad_Name!"  # not DNS-safe
        with pytest.raises(jsonschema.ValidationError):
            jsonschema.validate(bad, schema)
        bad = copy.deepcopy(values)
        bad["models"][0]["unknownKey"] = 1  # dead values rejected
        with pytest.raises(jsonschema.ValidationError):
            jsonschema.validate(bad, schema)


def test_renderer_consumes_chart_values_verbatim():
    """The Python renderer and the Helm charts share one contract: both
    charts' shipped values.yaml must load and render (catches drift like a
    chart key the spec rejects)."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent / "k8s"
    tpu = load_spec(str(root / "tpu-models" / "helm-chart" / "values.yaml"))
    docs = render_manifests(tpu)
    kinds = [d["kind"] for d in docs]
    assert "Deployment" in kinds and "ConfigMap" in kinds
    # tpu profile: every model container requests google.com/tpu
    for d in docs:
        if d["kind"] == "Deployment" and d["metadata"]["name"].startswith("model-"):
            res = d["spec"]["template"]["spec"]["containers"][0]["resources"]
            assert "google.com/tpu" in res["requests"]

    local = load_spec(str(root / "local-models" / "helm-chart" / "values.yaml"))
    docs = render_manifests(local)
    for d in docs:
        if d["kind"] == "Deployment" and d["metadata"]["name"].startswith("model-"):
            res = d["spec"]["template"]["spec"]["containers"][0].get("resources", {})
            assert "google.com/tpu" not in res.get("requests", {})


def test_router_config_qos_block():
    """ISSUE 10: the qos: block flows verbatim into router.json (the
    python and native routers parse identical keys), validates its keys,
    and rolls the router pods via the config hash when tuned."""
    from llms_on_kubernetes_tpu.server.router import Router

    cfg = router_config(load_spec(BASE_YAML))
    assert "qos" not in cfg  # absent block = no key at all

    qos_yaml = BASE_YAML + """
qos:
  tenants:
    frontend: {priority: interactive, weight: 4}
    analytics: {priority: batch, rps: 5, tokens_per_min: 6000}
  default: {rps: 50}
  brownout:
    queue_depth_hi: 32
    burn_rate_hi: 2.0
    clamp_max_tokens: 48
"""
    spec = load_spec(qos_yaml)
    cfg2 = router_config(spec)
    # passed verbatim — field-level parity with the Go template's toJson
    assert cfg2["qos"] == {
        "tenants": {
            "frontend": {"priority": "interactive", "weight": 4},
            "analytics": {"priority": "batch", "rps": 5,
                          "tokens_per_min": 6000},
        },
        "default": {"rps": 50},
        "brownout": {"queue_depth_hi": 32, "burn_rate_hi": 2.0,
                     "clamp_max_tokens": 48},
    }
    assert config_hash(spec) != config_hash(load_spec(BASE_YAML))
    # the python Router accepts the rendered block and enables its gate
    r = Router(cfg2["backends"], cfg2["default_model"], cfg2["strict"],
               qos=cfg2["qos"])
    assert r.qos_gate.enabled
    tenant, prio = r.qos_gate.resolve({"user": "frontend"}, "llama-3-8b",
                                      None)
    assert (tenant, prio) == ("frontend", "interactive")

    # an EMPTY block disables cleanly (matches both routers' truthiness)
    assert "qos" not in router_config(load_spec(BASE_YAML + "\nqos: {}\n"))

    # unknown keys and invalid values are rejected at spec load
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nqos: {tenants: {t: {rate: 5}}}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nqos: {shed: true}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nqos: {tenants: {t: {priority: vip}}}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nqos: {tenants: {t: {weight: 0}}}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nqos: {brownout: {queue_depth_hi: -1}}\n")


def test_router_config_gray_failure_blocks():
    """ISSUE 17: outlierEjection/retryBudget flow verbatim into
    router.json (both routers parse identical wire keys, pinned by
    tests/data/outlier_vectors.json), validate their keys at spec load,
    and roll the router pods via the config hash when tuned."""
    from llms_on_kubernetes_tpu.server.router import Router

    cfg = router_config(load_spec(BASE_YAML))
    assert "outlier_ejection" not in cfg  # absent block = no key at all
    assert "retry_budget" not in cfg

    gray_yaml = BASE_YAML + """
outlierEjection:
  ewma_alpha: 0.5
  z_threshold: 2.5
  min_samples: 4
  streak: 2
  max_eject_fraction: 0.25
retryBudget:
  ratio: 0.1
  min_per_s: 0.5
  burst: 6
"""
    spec = load_spec(gray_yaml)
    cfg2 = router_config(spec)
    # passed verbatim — field-level parity with the Go template's toJson
    assert cfg2["outlier_ejection"] == {
        "ewma_alpha": 0.5, "z_threshold": 2.5, "min_samples": 4,
        "streak": 2, "max_eject_fraction": 0.25,
    }
    assert cfg2["retry_budget"] == {
        "ratio": 0.1, "min_per_s": 0.5, "burst": 6,
    }
    assert config_hash(spec) != config_hash(load_spec(BASE_YAML))
    # the python Router accepts the rendered blocks and arms the layer
    r = Router(cfg2["backends"], cfg2["default_model"], cfg2["strict"],
               outlier_ejection=cfg2["outlier_ejection"],
               retry_budget=cfg2["retry_budget"])
    assert r.outlier_cfg.enabled and r.outlier_cfg.ewma_alpha == 0.5
    assert r.retry_budget_cfg.enabled and r.retry_budget_cfg.burst == 6.0

    # an EMPTY block disables cleanly (matches both routers' truthiness)
    cfg3 = router_config(load_spec(
        BASE_YAML + "\noutlierEjection: {}\nretryBudget: {}\n"))
    assert "outlier_ejection" not in cfg3 and "retry_budget" not in cfg3

    # unknown keys and invalid values are rejected at spec load
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\noutlierEjection: {zscore: 3}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\noutlierEjection: {ewma_alpha: 1.5}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\noutlierEjection: {streak: -1}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML
                  + "\noutlierEjection: {max_eject_fraction: 1.5}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nretryBudget: {percent: 20}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nretryBudget: {ratio: -0.1}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nretryBudget: {burst: nope}\n")


def test_values_schema_gray_failure_parity():
    """Both charts schematize outlierEjection/retryBudget with the wire
    key names (schema drift between the charts and the renderer is the
    failure mode this pins)."""
    import copy
    import json
    import pathlib

    jsonschema = pytest.importorskip("jsonschema")
    root = pathlib.Path(__file__).resolve().parent.parent / "k8s"
    for chart in ("tpu-models", "local-models"):
        cdir = root / chart / "helm-chart"
        schema = json.loads((cdir / "values.schema.json").read_text())
        oprops = schema["properties"]["outlierEjection"]["properties"]
        # schema keys == the spec's accepted wire keys, verbatim
        from llms_on_kubernetes_tpu.deploy.spec import (
            _OUTLIER_KEYS, _RETRY_BUDGET_KEYS)
        assert set(oprops) == set(_OUTLIER_KEYS), chart
        bprops = schema["properties"]["retryBudget"]["properties"]
        assert set(bprops) == set(_RETRY_BUDGET_KEYS), chart

        values = yaml.safe_load((cdir / "values.yaml").read_text())
        assert values.get("outlierEjection"), (
            f"{chart}: shipped values.yaml should demo the gray-failure "
            f"layer")
        jsonschema.validate(values, schema)
        bad = copy.deepcopy(values)
        bad["outlierEjection"]["zscore"] = 3  # unknown knob rejected
        with pytest.raises(jsonschema.ValidationError):
            jsonschema.validate(bad, schema)
        bad = copy.deepcopy(values)
        bad["retryBudget"] = {"ratio": -1}
        with pytest.raises(jsonschema.ValidationError):
            jsonschema.validate(bad, schema)


def test_router_config_prefix_affinity_block():
    """ISSUE 18: prefixAffinity flows verbatim into router.json (both
    routers parse identical wire keys, pinned by
    tests/data/affinity_vectors.json), validates at spec load, arms the
    python Router, and rolls the router pods via the config hash."""
    from llms_on_kubernetes_tpu.server.router import Router

    cfg = router_config(load_spec(BASE_YAML))
    assert "prefix_affinity" not in cfg  # absent block = no key at all

    aff_yaml = BASE_YAML + """
prefixAffinity:
  prefix_chars: 512
  filter_bits: 16384
  filter_hashes: 3
  overload_factor: 1.5
  overload_slack: 4
  key_cache: 2048
  max_digests: 16
  kv_fetch: true
"""
    spec = load_spec(aff_yaml)
    cfg2 = router_config(spec)
    # passed verbatim — field-level parity with the Go template's toJson
    assert cfg2["prefix_affinity"] == {
        "prefix_chars": 512, "filter_bits": 16384, "filter_hashes": 3,
        "overload_factor": 1.5, "overload_slack": 4, "key_cache": 2048,
        "max_digests": 16, "kv_fetch": True,
    }
    assert config_hash(spec) != config_hash(load_spec(BASE_YAML))
    # the python Router accepts the rendered block and arms the layer
    r = Router(cfg2["backends"], cfg2["default_model"], cfg2["strict"],
               prefix_affinity=cfg2["prefix_affinity"])
    assert r.affinity_cfg.enabled
    assert r.affinity_cfg.prefix_chars == 512
    assert r.affinity_cfg.kv_fetch

    # an EMPTY block disables cleanly (matches both routers' truthiness)
    cfg3 = router_config(load_spec(BASE_YAML + "\nprefixAffinity: {}\n"))
    assert "prefix_affinity" not in cfg3

    # explicit enabled:false renders but stays dormant in the Router
    cfg4 = router_config(load_spec(
        BASE_YAML + "\nprefixAffinity: {enabled: false, filter_bits: 64}\n"))
    r4 = Router(cfg4["backends"], cfg4["default_model"], cfg4["strict"],
                prefix_affinity=cfg4["prefix_affinity"])
    assert not r4.affinity_cfg.enabled

    # unknown keys and invalid values are rejected at spec load
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nprefixAffinity: {prefixChars: 128}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nprefixAffinity: {filter_hashes: 9}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nprefixAffinity: {prefix_chars: -1}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nprefixAffinity: {kv_fetch: 1}\n")
    with pytest.raises(SpecError):
        load_spec(BASE_YAML + "\nprefixAffinity: {enabled: yes_please}\n")


def test_values_schema_prefix_affinity_parity():
    """Both charts schematize prefixAffinity with the wire key names
    (schema drift between the charts and the renderer is the failure
    mode this pins), ship a demo block, and reject unknown knobs."""
    import copy
    import json
    import pathlib

    jsonschema = pytest.importorskip("jsonschema")
    from llms_on_kubernetes_tpu.deploy.spec import _AFFINITY_KEYS
    root = pathlib.Path(__file__).resolve().parent.parent / "k8s"
    for chart in ("tpu-models", "local-models"):
        cdir = root / chart / "helm-chart"
        schema = json.loads((cdir / "values.schema.json").read_text())
        aprops = schema["properties"]["prefixAffinity"]["properties"]
        # schema keys == the spec's accepted wire keys, verbatim
        assert set(aprops) == set(_AFFINITY_KEYS), chart

        values = yaml.safe_load((cdir / "values.yaml").read_text())
        assert values.get("prefixAffinity"), (
            f"{chart}: shipped values.yaml should demo cache-aware "
            f"routing")
        jsonschema.validate(values, schema)
        bad = copy.deepcopy(values)
        bad["prefixAffinity"]["prefixChars"] = 128  # unknown knob
        with pytest.raises(jsonschema.ValidationError):
            jsonschema.validate(bad, schema)
        bad = copy.deepcopy(values)
        bad["prefixAffinity"]["filter_hashes"] = 9  # out of [1, 4]
        with pytest.raises(jsonschema.ValidationError):
            jsonschema.validate(bad, schema)


# ---------------------------------------------------------------------------
# ISSUE 16: disaggregated prefill/decode roles
# ---------------------------------------------------------------------------

def _disagg_yaml(pre_scale="{minReplicas: 1, maxReplicas: 4}",
                 dec_scale="{minReplicas: 1, maxReplicas: 8}"):
    return f"""
models:
  - modelName: llama-3-8b
    huggingfaceId: meta-llama/Meta-Llama-3-8B-Instruct
    pvcShared: true
    tpu: {{accelerator: v5e, chips: 8}}
    role: prefill
    kvHostCacheGB: 16
    autoscaling: {pre_scale}
  - modelName: llama-3-8b
    huggingfaceId: meta-llama/Meta-Llama-3-8B-Instruct
    pvcShared: true
    tpu: {{accelerator: v5e, chips: 8}}
    role: decode
    autoscaling: {dec_scale}
router: {{handoffRetries: 3}}
"""


def test_disagg_roles_render_paired_deployments():
    """A prefill/decode pair sharing one modelName renders role-suffixed
    Deployments/Services/PVCs, threads LLMK_ROLE to the engines, and the
    router config merges both pools under the one model with a roles map
    steering the two-hop flow."""
    spec = load_spec(_disagg_yaml())
    ms = render_manifests(spec)
    for role in ("prefill", "decode"):
        dep = by_name(ms, "Deployment", f"model-llama-3-8b-{role}")
        env = {e["name"]: e.get("value") for e in
               dep["spec"]["template"]["spec"]["containers"][0]["env"]}
        assert env["LLMK_ROLE"] == role
        by_name(ms, "Service", f"model-llama-3-8b-{role}")
        by_name(ms, "Service", f"model-llama-3-8b-{role}-replicas")
        if role == "prefill":  # the handoff's spill target
            assert float(env["LLMK_KV_HOST_CACHE_GB"]) == 16.0

    cfg = router_config(spec)
    urls = cfg["backends"]["llama-3-8b"]
    assert len(urls) == 2 and len(set(urls)) == 2
    assert cfg["roles"] == {
        u: ("prefill" if "-prefill-" in u else "decode") for u in urls}
    assert cfg["handoff_retries"] == 3
    # colocated specs stay byte-for-byte free of the new keys (parity
    # with the pre-disagg router.json contract)
    colo = router_config(load_spec(BASE_YAML))
    assert "roles" not in colo and "handoff_retries" not in colo


def test_disagg_autoscaler_signals_split_per_role():
    """Each pool scales on the signal it actually bounds: prefill on its
    own role's queue depth only, decode on TTFT attainment only; a
    colocated model keeps both metrics."""
    ms = render_manifests(load_spec(_disagg_yaml()))
    pre = by_name(ms, "HorizontalPodAutoscaler", "model-llama-3-8b-prefill")
    dec = by_name(ms, "HorizontalPodAutoscaler", "model-llama-3-8b-decode")
    (pm,) = pre["spec"]["metrics"]
    assert pm["pods"]["metric"]["name"] == "llm_queue_depth"
    (dm,) = dec["spec"]["metrics"]
    assert dm["object"]["metric"]["name"] == "llm_slo_ttft_miss_ratio"

    # KEDA scale-to-zero path: the prefill queue query selects its own
    # role's series so the decode pool's depth can't mask a ticket backlog
    ms0 = render_manifests(load_spec(_disagg_yaml(
        pre_scale="{minReplicas: 0, maxReplicas: 4}",
        dec_scale="{minReplicas: 0, maxReplicas: 8}")))
    pre0 = by_name(ms0, "ScaledObject", "model-llama-3-8b-prefill")
    (pt,) = pre0["spec"]["triggers"]
    assert 'role="prefill"' in pt["metadata"]["query"]
    dec0 = by_name(ms0, "ScaledObject", "model-llama-3-8b-decode")
    (dt,) = dec0["spec"]["triggers"]
    assert dt["metadata"]["metricName"] == "llm_slo_ttft_miss_ratio"


def test_disagg_spec_validation():
    base = """
models:
  - modelName: m
    huggingfaceId: org/m
    pvcShared: true
"""
    # roles ride the coordinator-local host tier: multi-host slices reject
    with pytest.raises(SpecError, match="multi-host"):
        load_spec(base + "    tpu: {accelerator: v5p, chips: 16}\n"
                         "    role: decode\n")
    # a prefill pool with no host tier has nowhere to spill the handoff
    with pytest.raises(SpecError, match="kvHostCacheGB"):
        load_spec(base + "    role: prefill\n")
    with pytest.raises(SpecError, match="role"):
        load_spec(base + "    role: ingest\n")
    # shared modelName is legal ONLY as an exact {prefill, decode} pair
    dup = """
models:
  - {modelName: m, huggingfaceId: org/m, pvcShared: true, role: %s%s}
  - {modelName: m, huggingfaceId: org/m, pvcShared: true, role: %s}
"""
    with pytest.raises(SpecError, match="prefill \\+ decode"):
        load_spec(dup % ("decode", "", "decode"))
    with pytest.raises(SpecError, match="prefill \\+ decode"):
        load_spec(dup % ("both", "", "both"))
    with pytest.raises(SpecError):
        load_spec(dup % ("prefill", ", kvHostCacheGB: 8", "decode")
                  + "  - {modelName: m, huggingfaceId: org/m, "
                    "pvcShared: true, role: both}\n")
    with pytest.raises(SpecError, match="handoffRetries"):
        load_spec(base + "\nrouter: {handoffRetries: -1}\n")


def test_values_schema_role_and_handoff_parity():
    """Both charts expose the same disagg contract: models[].role with
    the same enum, router.handoffRetries — and a disaggregated values
    doc validates end to end (schema drift between the charts and the
    Python renderer is the failure mode this pins)."""
    import copy
    import json
    import pathlib

    jsonschema = pytest.importorskip("jsonschema")
    root = pathlib.Path(__file__).resolve().parent.parent / "k8s"
    for chart in ("tpu-models", "local-models"):
        cdir = root / chart / "helm-chart"
        schema = json.loads((cdir / "values.schema.json").read_text())
        mprops = schema["properties"]["models"]["items"]["properties"]
        assert mprops["role"]["enum"] == ["prefill", "decode", "both"]
        rprops = schema["properties"]["router"]["properties"]
        assert rprops["handoffRetries"]["type"] == "integer"

        values = yaml.safe_load((cdir / "values.yaml").read_text())
        good = copy.deepcopy(values)
        pre = copy.deepcopy(good["models"][0])
        dec = copy.deepcopy(good["models"][0])
        pre.update(role="prefill", kvHostCacheGB=8)
        dec.update(role="decode")
        good["models"] = [pre, dec]
        good.setdefault("router", {})["handoffRetries"] = 3
        jsonschema.validate(good, schema)

        bad = copy.deepcopy(good)
        bad["models"][0]["role"] = "ingest"
        with pytest.raises(jsonschema.ValidationError):
            jsonschema.validate(bad, schema)
