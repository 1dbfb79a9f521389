#!/usr/bin/env python3
"""What a golden file's tolerance has to stay under: the plain reference
computed with every layer matrix cut to the nearest type BELOW the one the
configuration is served in, against the golden file's own numbers.

    python3 benchmark/reference/lower_precision.py <config> [--type T]

For each golden prompt, at the first generated position: the largest
difference in nats, over the reference's best 8 ids (the ids the output
check asks for), between the log-probabilities the coarser reference gives
and the golden file's. A tolerance lies under the smallest of them, so that
such a path comes out as not correct. Where the configuration's reference
has ``router_margins`` (a block that routes), the margins of the routings
at that position are printed too: how many a lower precision could flip.
Run it on the device the configuration fits on; it writes nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

PROBED = 8


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--type", default="float8_e4m3fn",
                    help="the type every layer matrix is rounded to")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import manifest
    from reference.make_golden import chat_token_ids, seeded_weights

    config = manifest.load_json("configs", f"{args.config}.json")
    golden = manifest.load_json("golden", f"{args.config}.json")
    reference = manifest.reference_of(config)
    params, _ = seeded_weights(config)
    lower = jnp.dtype(args.type)
    prompts, seen = [], set()
    for p in golden["prompts"]:
        if p["content"] not in seen:
            seen.add(p["content"])
            prompts.append((p, chat_token_ids(p["content"])))
    margins = getattr(reference, "router_margins", None)
    routed = [margins(config, params, ids, len(ids) - 1) if margins else None
              for _p, ids in prompts]
    # the stacked matrices of the layers, cut in place one at a time (a
    # second copy of a model that fills the chip does not fit beside it);
    # norms, biases and the embedding table keep their type, as a
    # weight-only lower-precision path would
    layers = params["layers"]
    for run in (layers if isinstance(layers, (tuple, list)) else (layers,)):
        for name, w in list(run.items()):
            if getattr(w, "ndim", 0) >= 3:
                run[name] = w.astype(lower).astype(w.dtype)
                w.delete()
    rows = []
    for (p, ids), gaps in zip(prompts, routed):
        lg = reference.logits_at(config, params, ids, [len(ids) - 1])[0]
        lp = np.asarray(jax.nn.log_softmax(lg), np.float64)
        diffs = [abs(float(lp[i]) - ref) for i, ref in zip(
            p["top_ids"][0][:PROBED], p["top_logprobs"][0][:PROBED])]
        row = {"name": p["name"], "prompt_tokens": len(ids),
               "max_abs_diff": max(diffs)}
        if gaps is not None:
            row["router_margins"] = gaps
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"config": args.config, "type": args.type,
                      "smallest_max_abs_diff": min(
                          r["max_abs_diff"] for r in rows),
                      "largest_max_abs_diff": max(
                          r["max_abs_diff"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
