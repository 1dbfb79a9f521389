"""Command-line entry points — the container commands the charts run.

The reference's pods ran `vllm serve <hf-id> --served-model-name <name>
--port 8080 ...` (reference model-deployments.yaml:26-39) and an
OpenResty/Python gateway (model-gateway.yaml / api-gateway.yaml). The
TPU-native equivalents:

    python -m llms_on_kubernetes_tpu serve  --model <ref> --served-model-name <name> [--tp N]
    python -m llms_on_kubernetes_tpu router --backend name=url ... [--strict]
"""

from __future__ import annotations

import argparse
import os
import sys


def _add_serve(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser("serve", help="run the OpenAI-compatible engine server")
    p.add_argument("--model", required=True,
                   help="registry name, HF repo id, or checkpoint directory")
    p.add_argument("--served-model-name", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--random-weights", action="store_true",
                   help="skip checkpoint loading (benchmarks/smoke tests)")
    p.add_argument("--max-decode-slots", type=int, default=8)
    p.add_argument("--num-pages", type=int, default=2048)
    p.add_argument("--page-size", type=int, default=64)
    p.add_argument("--pages-per-slot", type=int, default=64)
    p.add_argument("--prefill-buckets", default="256,1024,4096")
    p.add_argument("--tensor-parallel-size", "--tp", type=int, default=0,
                   help="0 = all local devices on the mesh 'model' axis")
    p.add_argument("--expert-parallel-size", "--ep", type=int, default=1)
    p.add_argument("--sequence-parallel-size", "--sp", type=int, default=1,
                   help="context-parallel ring size for long prompts "
                        "(prefill runs ring attention over the 'seq' axis)")
    p.add_argument("--quantization", choices=["int8", "fp8", "awq"],
                   default=None,
                   help="int8: weight-only quantize a bf16 checkpoint; "
                        "fp8/awq: assert the checkpoint is that pre-"
                        "quantized format (auto-detected otherwise)")
    p.add_argument("--no-prefix-caching", dest="prefix_caching",
                   action="store_false", default=True,
                   help="disable page-level reuse of shared prompt prefixes")
    p.add_argument("--kv-cache-dtype", choices=["int8"], default=None,
                   help="store KV quantized (halved decode HBM traffic, "
                        "2x token capacity; ~1/127 per-element error)")
    p.add_argument("--kv-host-cache-gb", type=float, default=None,
                   help="host-RAM KV offload tier capacity in GiB: "
                        "finished/preempted sessions park their pages in "
                        "host memory; a returning session re-uploads and "
                        "skips re-prefill (default: $LLMK_KV_HOST_CACHE_GB "
                        "or off; needs prefix caching, single-host only)")
    p.add_argument("--decode-steps", type=int, default=None,
                   help="decode tokens sampled per fused device dispatch "
                        "(default: $LLMK_DECODE_STEPS or 4; forced to 1 "
                        "on multihost)")
    p.add_argument("--speculation", choices=["ngram", "draft"], default=None,
                   help="speculative decoding riding the fused decode "
                        "window: ngram = model-free prompt lookup, draft = "
                        "small draft model via --draft-model (default: "
                        "$LLMK_SPECULATION or off; greedy outputs are "
                        "bit-identical on/off; dropped on multihost)")
    p.add_argument("--draft-model", default=None,
                   help="draft model for --speculation draft (registry "
                        "name or .gguf path; default: $LLMK_DRAFT_MODEL; "
                        "implies --speculation draft)")
    p.add_argument("--no-ledger", dest="ledger", action="store_false",
                   default=None,
                   help="disable the goodput ledger (per-request chip-time "
                        "attribution, MFU/MBU gauges, per-tenant chip-"
                        "seconds; default: $LLMK_LEDGER or on)")
    p.add_argument("--no-anomaly-profile", dest="anomaly_profile",
                   action="store_false", default=None,
                   help="disable the step-time anomaly watchdog's automatic "
                        "profiler captures (default: $LLMK_ANOMALY_PROFILE "
                        "or on)")
    p.add_argument("--anomaly-z", type=float, default=None,
                   help="z-score a dispatch's device time must exceed to "
                        "count as anomalous (default: $LLMK_ANOMALY_Z or "
                        "4.0)")
    p.add_argument("--anomaly-cooldown-s", type=float, default=None,
                   help="minimum seconds between automatic profiler "
                        "captures — the watchdog's rate limit (default: "
                        "$LLMK_ANOMALY_COOLDOWN_S or 600)")
    def _positive_int(v: str) -> int:
        n = int(v)
        if n < 1:
            raise argparse.ArgumentTypeError(f"must be >= 1, got {n}")
        return n

    p.add_argument("--max-images-per-request", type=_positive_int, default=4,
                   help="image/frame blocks the mm prefill is compiled for "
                        "(a video counts one block per temporal patch); "
                        "requests beyond it get a 400")
    p.add_argument("--adapter", action="append", default=None,
                   metavar="NAME=REF",
                   help="repeatable: serve LoRA adapter NAME from REF (HF "
                        "repo id or local dir); requests address it as "
                        "model=<served-name>:NAME")
    p.add_argument("--adapter-slots", type=_positive_int, default=4,
                   help="on-device adapter slots (LRU-recycled)")
    p.add_argument("--adapter-rank", type=_positive_int, default=16,
                   help="max LoRA rank the device stacks are sized for")
    p.add_argument("--no-warmup", dest="warmup", action="store_false",
                   default=True,
                   help="skip the pre-serving warmup generation (first "
                        "requests then pay the prefill/decode compiles)")


def configure_compilation_cache() -> str:
    """Place XLA's persistent compilation cache; returns the directory.

    One rule for every process that compiles (serve, chip_smoke.py's
    children, bench.py, examples/, scripts/): where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself keeps the cache there
    and nothing here names a directory — the charts set it on the weight
    PVC, so the warmup compiles (the dominant cold-start phase) are paid
    once per (program, jaxlib, topology), not once per pod. Unset, the
    cache is ``<checkout>/.jax_cache``: a fixed path, because the path is
    part of what makes a later process find the entries again. Must run
    before the first compilation.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache small programs too: the CPU-side tests (and debug-tiny
    # configs) compile in well under the default 1 s / 4 KiB floors, and
    # a warm restart must hit for them as well
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def _add_router(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser("router", help="run the multi-model API gateway")
    p.add_argument("--backend", action="append", default=None,
                   metavar="NAME=URL[|URL...]",
                   help="repeatable: model name=replica url(s), |-separated")
    p.add_argument("--config", default=None,
                   help="router.json (from `render`): backends/default/strict")
    p.add_argument("--default-model", default=None)
    p.add_argument("--strict", action="store_true",
                   help="404 on unknown model instead of silent default fallback")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--probe-interval", type=float, default=None,
                   metavar="SECONDS",
                   help="active /ready probe period per replica "
                        "(default 2.0; 0 disables probing)")
    p.add_argument("--adapters", action="append", default=None,
                   metavar="NAME=ADAPTER[|ADAPTER...]",
                   help="repeatable: LoRA adapters a model's replicas "
                        "serve, addressed as model=NAME:ADAPTER")


def _add_render(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "render",
        help="render K8s manifests from a models[] config (helm-free path)")
    p.add_argument("--config", required=True, help="deploy config YAML")
    p.add_argument("-o", "--output", default="-",
                   help="output file (default: stdout)")


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="llms-on-kubernetes-tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)
    _add_serve(sub)
    _add_router(sub)
    _add_render(sub)
    args = parser.parse_args(argv)

    if args.cmd == "render":
        from llms_on_kubernetes_tpu.deploy import load_spec, render_manifests, to_yaml

        text = to_yaml(render_manifests(load_spec(args.config)))
        if args.output == "-":
            sys.stdout.write(text)
        else:
            with open(args.output, "w") as f:
                f.write(text)
        return 0

    if args.cmd == "router":
        import json

        from llms_on_kubernetes_tpu.server.router import run_router

        backends = {}
        adapters = {}
        default_model, strict = args.default_model, args.strict
        probe_interval = args.probe_interval
        # None = let Router fall back to the LLMK_STREAM_RESUME /
        # LLMK_RESUME_ATTEMPTS / LLMK_HEDGE_MS env knobs
        stream_resume = resume_attempts = hedge_ms = None
        qos = roles = handoff_retries = None
        # None = let Router fall back to LLMK_OUTLIER / LLMK_RETRY_BUDGET
        # / LLMK_AFFINITY
        outlier_ejection = retry_budget = prefix_affinity = None
        # None = let Router fall back to LLMK_OTLP_ENDPOINT /
        # LLMK_TRACE_SAMPLE / LLMK_SLOW_REQUEST_MS
        tracing_cfg = None
        if args.config:
            with open(args.config) as f:
                cfg = json.load(f)
            backends.update(cfg.get("backends", {}))
            adapters.update(cfg.get("adapters", {}))
            default_model = default_model or cfg.get("default_model")
            strict = strict or bool(cfg.get("strict", False))
            if probe_interval is None and "probe_interval_s" in cfg:
                probe_interval = float(cfg["probe_interval_s"])
            if "stream_resume" in cfg:
                stream_resume = bool(cfg["stream_resume"])
            if "resume_attempts" in cfg:
                resume_attempts = int(cfg["resume_attempts"])
            if "hedge_ms" in cfg:
                hedge_ms = float(cfg["hedge_ms"])
            if "qos" in cfg:
                qos = cfg["qos"]  # per-tenant QoS block, passed verbatim
            if "roles" in cfg:
                # disaggregated serving: replica URL -> prefill|decode|both
                roles = cfg["roles"]
            if "handoff_retries" in cfg:
                handoff_retries = int(cfg["handoff_retries"])
            if "outlier_ejection" in cfg:
                # gray-failure layer: latency/error outlier quarantine,
                # passed verbatim (non-empty block = enabled)
                outlier_ejection = cfg["outlier_ejection"]
            if "retry_budget" in cfg:
                retry_budget = cfg["retry_budget"]
            if "prefix_affinity" in cfg:
                # prefix-affinity + cache-aware routing, passed verbatim
                # (non-empty block = enabled)
                prefix_affinity = cfg["prefix_affinity"]
            if "tracing" in cfg:
                # cross-hop tracing: OTLP export + tail sampling, passed
                # verbatim (non-empty block = exporter enabled)
                tracing_cfg = cfg["tracing"]
        for spec in args.backend or ():
            name, _, urls = spec.partition("=")
            if not urls:
                parser.error(f"--backend must be NAME=URL[|URL...], got {spec!r}")
            backends[name] = [u for u in urls.split("|") if u]
        for spec in args.adapters or ():
            name, _, names = spec.partition("=")
            if not names:
                parser.error(
                    f"--adapters must be NAME=ADAPTER[|ADAPTER...], got {spec!r}")
            adapters[name] = [a for a in names.split("|") if a]
        if not backends:
            parser.error("router needs --config or at least one --backend")
        if probe_interval is None:
            probe_interval = 2.0
        run_router(backends, default_model, strict,
                   host=args.host, port=args.port,
                   probe_interval_s=probe_interval or None,
                   adapters=adapters or None,
                   stream_resume=stream_resume,
                   resume_attempts=resume_attempts, hedge_ms=hedge_ms,
                   qos=qos, roles=roles, handoff_retries=handoff_retries,
                   outlier_ejection=outlier_ejection,
                   retry_budget=retry_budget,
                   prefix_affinity=prefix_affinity,
                   tracing_cfg=tracing_cfg)
        return 0

    # serve (jax is imported from here on: render/router never pay for it)
    from llms_on_kubernetes_tpu.parallel.distributed import maybe_initialize
    from llms_on_kubernetes_tpu.server.metrics import cold_start

    with cold_start.phase("mesh"):
        multi_host = maybe_initialize()  # join pod group BEFORE backend init

    import jax

    # before any compilation: warm restarts reuse cached executables,
    # and the jit counters see the warmup's compiles and cache hits
    print(f"[serve] persistent compile cache: "
          f"{configure_compilation_cache()}", file=sys.stderr)
    from llms_on_kubernetes_tpu.engine import jit_events
    jit_events.install()
    # which device answered, before the minutes of loading and compiling:
    # a start on the wrong platform should be visible (chip_smoke.py reads
    # this line) long before /ready
    dev = jax.devices()[0]
    print(f"[serve] devices: platform={dev.platform} "
          f"device_kind={dev.device_kind!r} count={len(jax.devices())}",
          file=sys.stderr, flush=True)

    from llms_on_kubernetes_tpu.configs import from_hf_config, get_config
    from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig
    from llms_on_kubernetes_tpu.engine.tokenizer import load_tokenizer
    from llms_on_kubernetes_tpu.parallel.mesh import make_mesh
    from llms_on_kubernetes_tpu.server.openai_api import run_server

    model_dir = None
    model_cfg = None
    gguf_path = None
    # GGUF file path: the local solution's `modelPath` contract (reference
    # ramalama values.yaml modelPath -> llama-server --model <file>.gguf)
    gguf_file = None
    if args.model.endswith(".gguf"):
        if not os.path.isfile(args.model):
            raise SystemExit(f"GGUF file not found: {args.model}")
        gguf_path = args.model
        from llms_on_kubernetes_tpu.engine.gguf import GGUFFile, config_from_gguf

        # parsed ONCE; reused for config, weights, and the embedded tokenizer
        gguf_file = GGUFFile(gguf_path)
        model_cfg = config_from_gguf(gguf_file, name=args.served_model_name)
    else:
        try:
            model_cfg = get_config(args.model)
        except KeyError:
            pass
        if not args.random_weights:
            # Missing weights are a STARTUP FAILURE: exit non-zero so the
            # pod stays unready (the reference's 7-min readiness budget
            # exists exactly for the first-boot download, reference
            # model-deployments.yaml:26-70). Random weights only ever
            # behind the explicit --random-weights flag.
            from llms_on_kubernetes_tpu.engine.hub import ensure_model_dir

            try:
                model_dir = ensure_model_dir(args.model)
            except Exception as e:
                # FileNotFoundError/OSError cover the expected operational
                # failures (no checkpoint, unmounted PVC, Hub HTTP/auth
                # errors — requests' exceptions subclass OSError); anything
                # else is a bug, so keep its traceback in the pod log.
                if not isinstance(e, OSError):
                    import traceback
                    traceback.print_exc()
                raise SystemExit(
                    f"[serve] cannot obtain weights for {args.model!r}: {e}\n"
                    f"[serve] (pass --random-weights explicitly to serve an "
                    f"uninitialized model for smoke tests/benchmarks)"
                )
        if model_cfg is None and model_dir is not None:
            cfg_path = os.path.join(model_dir, "config.json")
            model_cfg = from_hf_config(cfg_path, name=args.model)
    if model_cfg is None:
        raise SystemExit(f"cannot resolve model {args.model!r}")

    # cold-start attack: open/mmap the checkpoint shards (pure host I/O)
    # in the background WHILE the device mesh is built below
    weights_preload = None
    if model_dir is not None and not args.random_weights:
        from llms_on_kubernetes_tpu.engine.weights import WeightsPreload

        weights_preload = WeightsPreload(model_dir)

    n_dev = len(jax.devices())
    ep = args.expert_parallel_size
    sp = args.sequence_parallel_size
    if ep < 1 or sp < 1 or n_dev % (ep * sp) != 0:
        parser.error(f"--ep {ep} x --sp {sp} must divide the local "
                     f"device count ({n_dev})")
    tp = args.tensor_parallel_size or n_dev // (ep * sp)
    if tp < 1 or ep * sp * tp > n_dev:
        parser.error(f"--tp {tp} x --ep {ep} x --sp {sp} exceeds the "
                     f"{n_dev} local devices")
    with cold_start.phase("mesh"):
        mesh = make_mesh(data=1, seq=sp, expert=ep, model=tp)

    adapters = {}
    for spec in args.adapter or ():
        name, _, ref = spec.partition("=")
        if not name or not ref:
            parser.error(f"--adapter must be NAME=REF, got {spec!r}")
        adapters[name] = ref

    # LLMK_QOS: engine-side fair-queue config as JSON, e.g.
    # {"weights": {"alice": 4}, "priorities": {"bulk": "batch"},
    #  "default_priority": "normal", "starvation_s": 5} — env (not a flag)
    # so the chart can feed one ConfigMap value to every model pod
    qos_kw = {}
    raw_qos = os.environ.get("LLMK_QOS", "").strip()
    if raw_qos:
        import json

        try:
            q = json.loads(raw_qos)
        except ValueError as e:
            raise SystemExit(f"[serve] LLMK_QOS is not valid JSON: {e}")
        qos_kw = dict(
            qos_weights=q.get("weights", ()),
            qos_priorities=q.get("priorities", ()),
            qos_default_weight=float(q.get("default_weight", 1.0)),
            qos_default_priority=str(q.get("default_priority", "normal")),
            qos_starvation_s=float(q.get("starvation_s", 5.0)),
        )

    engine_cfg = EngineConfig(
        model=model_cfg.name,
        dtype=args.dtype,
        max_decode_slots=args.max_decode_slots,
        num_pages=args.num_pages,
        page_size=args.page_size,
        pages_per_slot=args.pages_per_slot,
        prefill_buckets=tuple(int(x) for x in args.prefill_buckets.split(",")),
        quantization=args.quantization,
        prefix_caching=args.prefix_caching,
        kv_cache_dtype=args.kv_cache_dtype,
        kv_host_cache_gb=args.kv_host_cache_gb,
        decode_steps=args.decode_steps,
        speculation=args.speculation,
        draft_model=args.draft_model,
        ledger=args.ledger,
        anomaly_profile=args.anomaly_profile,
        anomaly_z=args.anomaly_z,
        anomaly_cooldown_s=args.anomaly_cooldown_s,
        max_images_per_request=args.max_images_per_request,
        adapters=adapters,
        adapter_slots=args.adapter_slots,
        adapter_rank=args.adapter_rank,
        # only the coordinator schedules; its engine broadcasts step inputs
        multihost=multi_host,
        **qos_kw,
    )
    gguf_params = None
    if gguf_file is not None and not args.random_weights:
        from llms_on_kubernetes_tpu.engine.gguf import load_gguf_params

        with cold_start.phase("load"):
            _, gguf_params = load_gguf_params(
                gguf_file, cfg=model_cfg, dtype=args.dtype,
                quantization=args.quantization, mesh=mesh,
            )  # closes the mmap; the parsed metadata dict stays usable
    elif gguf_file is not None:
        gguf_file.close()
    with cold_start.phase("load"):
        engine = Engine(engine_cfg, model_config=model_cfg, mesh=mesh,
                        params=gguf_params,
                        model_dir=None if (args.random_weights
                                           or gguf_params is not None)
                        else model_dir,
                        weights_preload=weights_preload)
    if gguf_file is not None:
        # prefer HF tokenizer files beside the .gguf; else the tokenizer
        # embedded in the GGUF metadata itself (a bare .gguf is the
        # documented modelPath contract — it carries its own vocab)
        from llms_on_kubernetes_tpu.engine.tokenizer import (
            ByteTokenizer, GGUFTokenizer,
        )

        tokenizer = load_tokenizer(os.path.dirname(gguf_path) or ".")
        if (isinstance(tokenizer, ByteTokenizer)
                and "tokenizer.ggml.tokens" in gguf_file.metadata):
            tokenizer = GGUFTokenizer(gguf_file.metadata)
    else:
        tokenizer = load_tokenizer(model_dir)
    served = args.served_model_name or model_cfg.name
    peak = ("off" if engine.ledger is None else
            "none" if engine.ledger.peak_flops is None else
            f"{engine.ledger.peak_flops / 1e12:g}TFLOP/s,"
            f"{engine.ledger.peak_bytes_s / 1e9:g}GB/s")
    print(f"[serve] {served}: mesh={dict(mesh.shape)} dtype={args.dtype} "
          f"max_len={engine_cfg.max_model_len} multi_host={multi_host} "
          f"ledger_peak={peak}", file=sys.stderr, flush=True)
    if multi_host:
        from llms_on_kubernetes_tpu.engine.multihost import follower_loop
        from llms_on_kubernetes_tpu.parallel.distributed import is_coordinator

        if not is_coordinator():
            # followers never serve HTTP: they mirror the coordinator's
            # broadcast step sequence so every process enters the same
            # SPMD programs (engine/multihost.py)
            print("[serve] follower pod: entering SPMD mirror loop",
                  file=sys.stderr)
            follower_loop(engine)
            return 0
    try:
        if args.warmup:
            # build the prefill + decode executables BEFORE taking
            # traffic (or fetch them from the persistent cache): the
            # first real request must not pay a 20-40 s compile. Timed
            # as the "compile" cold-start phase.
            from llms_on_kubernetes_tpu.engine.engine import SamplingParams

            with cold_start.phase("compile"):
                w = engine.submit(
                    [1, 2, 3, 4],
                    SamplingParams(temperature=0.0, max_tokens=2))
                while not w.finished:
                    engine.step()
            print("[serve] warmup complete", file=sys.stderr)
        run_server(engine, tokenizer, served, host=args.host, port=args.port)
    finally:
        engine.stop_followers()  # release follower pods' mirror loops
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
