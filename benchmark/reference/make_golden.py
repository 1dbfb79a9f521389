#!/usr/bin/env python3
"""Write ``benchmark/golden/<config>.json`` from the plain reference.

    python3 benchmark/reference/make_golden.py <config> [--out FILE]

The reference is the module the configuration's file names under
``"reference"`` (``reference/<module>.py`` with ``logits_at``), else
``forward.py``. Imports of the program: its registry entry and the seeded
weight generator of what the configuration is served as (``served_as.
weights``: ``SEEDED_WEIGHTS`` below), nothing else. The weights' seed is
the program's own fixed one, so one golden file serves every ``--seed``.
Run it on the device the configuration fits on (a 7B model: the chip).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

GREEDY = 8
TOP = 20
# what the byte tokenizer (served under --random-weights) makes of one
# chat message: BOS, then the bytes of <user>content</user>
BOS = 256


def _int8(cfg):
    from llms_on_kubernetes_tpu.ops.quant import random_quantized_params
    return random_quantized_params(cfg, 0, dtype="bfloat16")


def _bfloat16(cfg):
    # the call engine/engine.py makes for ``serve --random-weights`` with
    # no ``--quantization``, with EngineConfig's seed (0) and --dtype
    import jax
    from llms_on_kubernetes_tpu.models.decoder import init_params
    return init_params(cfg, jax.random.key(0), dtype="bfloat16")


# served_as.weights -> (the tree ``serve --random-weights`` builds for it,
# what the golden file's "weights" string says of it). The reference
# dequantizes or widens one layer at a time: never a float32 copy of a
# whole layer stack.
SEEDED_WEIGHTS = {
    "int8": (_int8,
             "llms_on_kubernetes_tpu.ops.quant."
             "random_quantized_params(cfg, seed=0, dtype=bfloat16), "
             "int8 matrices dequantized with their scales"),
    "bfloat16": (_bfloat16,
                 "llms_on_kubernetes_tpu.models.decoder.init_params(cfg, "
                 "jax.random.key(0), dtype=bfloat16), widened to float32 "
                 "a layer at a time"),
}


def seeded_weights(config: dict):
    """(params, description) of the seeded weights ``config`` is served
    with; a type nothing generates is an error, not a fallback."""
    from harness import manifest
    from llms_on_kubernetes_tpu.configs import get_config

    kind = config["served_as"]["weights"]
    if kind not in SEEDED_WEIGHTS:
        raise manifest.ManifestError(
            f"no seeded weights for served_as.weights {kind!r}: "
            f"{sorted(SEEDED_WEIGHTS)}")
    make, said = SEEDED_WEIGHTS[kind]
    return make(get_config(config["registry_name"])), said


def chat_token_ids(content: str) -> list:
    return [BOS] + list(f"<user>{content}</user>".encode("utf-8"))


def prompts_for(config: dict) -> list:
    """One prompt per prefill bucket, one longer than the largest bucket
    (the chunk path), and the last single-bucket one again (the second
    time the prefix cache answers and the rest goes through the chunk
    path)."""
    from harness.schedule import random_text

    rng = random.Random("golden")
    buckets = sorted(int(b) for b in config["prefill_buckets"])
    out = [{"name": f"bucket{b}", "content": random_text(rng, int(0.6 * b) - 14)}
           for b in buckets]
    out.append({"name": "chunk",
                "content": random_text(rng, int(1.25 * buckets[-1]) - 14)})
    out.append({"name": f"bucket{buckets[-1]}-again",
                "content": out[len(buckets) - 1]["content"]})
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--out", default=None)
    ap.add_argument("--tolerance", type=float, default=None,
                    help="nats; written into the file with --why")
    ap.add_argument("--why", default="not set yet")
    args = ap.parse_args()

    import jax
    import numpy as np

    from harness import manifest

    config = manifest.load_json("configs", f"{args.config}.json")
    reference = manifest.reference_of(config)
    logits_at = reference.logits_at
    params, weights = seeded_weights(config)
    dev = jax.devices()[0]
    done: dict = {}
    rows = []
    for p in prompts_for(config):
        if p["content"] not in done:
            ids = chat_token_ids(p["content"])
            seq = ids + [0] * GREEDY        # room for the greedy path
            top_lp, top_id, chosen = [], [], []
            for step in range(GREEDY):
                n = len(ids) + step
                logits = logits_at(config, params, seq, [n - 1])[0]
                lp = np.asarray(jax.nn.log_softmax(logits), np.float64)
                order = np.argsort(-lp)[:TOP]
                top_id.append([int(i) for i in order])
                top_lp.append([float(lp[i]) for i in order])
                chosen.append(int(order[0]))
                seq[n] = int(order[0])
            done[p["content"]] = {
                "prompt_tokens": len(ids), "greedy_tokens": GREEDY,
                "greedy_ids": chosen, "top_ids": top_id,
                "top_logprobs": top_lp}
            print(f"{p['name']}: {len(ids)} tokens, first top-1 "
                  f"{top_lp[0][0]:.4f}, margin "
                  f"{top_lp[0][0] - top_lp[0][1]:.4f}", file=sys.stderr,
                  flush=True)
        rows.append(dict(p, **done[p["content"]]))
    doc = {
        "config": args.config,
        "reference": f"benchmark/{reference.__name__.replace('.', '/')}.py: "
                     "float32 jax.numpy, matmul precision highest, no "
                     "cache, no batching",
        "made_on": {"platform": dev.platform, "kind": dev.device_kind},
        "weights": weights,
        "tolerance": {"nats": args.tolerance, "why": args.why},
        "prompts": rows,
    }
    out = args.out or os.path.join(BENCH, "golden", f"{args.config}.json")
    with open(out, "w") as f:
        json.dump(doc, f)
        f.write("\n")
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
