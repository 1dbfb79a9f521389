"""Minimal engine drive: load a model, generate greedily, print tokens.

Usage (CPU or the real TPU — whichever backend jax selects):

    python examples/generate.py                    # debug-tiny, random weights
    python examples/generate.py --model llama-3-8b --quantization int8
    python examples/generate.py --model /path/to/checkpoint-dir
    python examples/generate.py --model /path/to/model.gguf

This is the smallest end-to-end path through the stack: config resolve →
weight load (HF safetensors via the native reader, or GGUF) → continuous-
batching engine → greedy decode. The OpenAI server (python -m
llms_on_kubernetes_tpu serve) wraps exactly this engine.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="debug-tiny")
    ap.add_argument("--prompt", default="The quick brown fox")
    ap.add_argument("--max-tokens", type=int, default=32)
    ap.add_argument("--quantization", choices=["int8"], default=None)
    ap.add_argument("--dtype", default=None)
    args = ap.parse_args()

    import jax

    from llms_on_kubernetes_tpu.cli import configure_compilation_cache
    configure_compilation_cache()

    from llms_on_kubernetes_tpu.configs import REGISTRY, get_config
    from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig, SamplingParams
    from llms_on_kubernetes_tpu.engine.tokenizer import load_tokenizer

    model_cfg = params = model_dir = None
    if args.model.endswith(".gguf"):
        from llms_on_kubernetes_tpu.engine.gguf import load_gguf_params

        model_cfg, params = load_gguf_params(
            args.model, dtype=args.dtype, quantization=args.quantization)
        tokenizer = load_tokenizer(args.model)
    elif args.model in REGISTRY:
        model_cfg = get_config(args.model)
        tokenizer = load_tokenizer(None)
        print(f"[generate] {args.model}: random weights "
              f"(no checkpoint given)", file=sys.stderr)
    else:
        from llms_on_kubernetes_tpu.configs import from_hf_config
        from llms_on_kubernetes_tpu.engine.weights import resolve_model_dir

        model_dir = resolve_model_dir(args.model)
        model_cfg = from_hf_config(os.path.join(model_dir, "config.json"),
                                   name=args.model)
        tokenizer = load_tokenizer(model_dir)

    ecfg = EngineConfig(
        model=model_cfg.name, dtype=args.dtype or model_cfg.dtype,
        quantization=args.quantization, max_decode_slots=4,
        page_size=16, pages_per_slot=32, num_pages=4 * 32 + 1,
        prefill_buckets=(64, 256),
    )
    print(f"[generate] backend={jax.default_backend()} model={model_cfg.name}",
          file=sys.stderr)
    eng = Engine(ecfg, model_config=model_cfg, params=params,
                 model_dir=model_dir)

    prompt_ids = tokenizer.encode(args.prompt)
    t0 = time.monotonic()
    out = eng.generate(prompt_ids,
                       SamplingParams(temperature=0.0,
                                      max_tokens=args.max_tokens))
    dt = time.monotonic() - t0
    print(f"[generate] {len(out)} tokens in {dt:.2f}s "
          f"({len(out) / dt:.1f} tok/s)", file=sys.stderr)
    print(tokenizer.decode(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
