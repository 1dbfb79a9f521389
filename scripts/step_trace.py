#!/usr/bin/env python3
"""One token step of a benchmark configuration traced alone, on the chip.

    chiprun --timeout 1500 -- python scripts/step_trace.py \
        --config jamba2-3b --live 56 --out chiprun_out/step_trace.json

An ``Engine`` with the flags ``benchmark/configs/<config>.json`` serves the
configuration with (``registry_name``, ``serve_flags``, random weights),
``--live`` requests of ``--prompt-tokens`` tokens decoding greedily with no
penalty and no ``logit_bias`` (what every traffic mix of the benchmark
sends; ``--asking N`` gives N of them a frequency penalty and a bias), then
``--windows`` fused decode windows under ``jax.profiler`` and every op's
self time by its HLO line, read with ``benchmark/harness/xplane.py``. The
reduction (JSON, ``--out``):

- ``family_ms_a_step``: ms a token step by op family (``fusion``, a
  kernel's name, ``dynamic_update_slice``, ...);
- ``ops``: for each HLO op, by family and result shape: us a call, calls a
  token step, ms a token step, and one instance's HLO line (its operands
  say which fusion it is);
- ``step_ms``: the ops' sum a token step; ``wall_ms_a_token``: the host's
  clock over the tokens every row got.

Only windows the capture holds whole are counted (the first and the last
``decode_multi`` module event are caught in part and dropped), so calls a
step are whole numbers for an op that runs once a step or once a layer.
``--tree`` takes the package and the reader from another checkout (the
parent commit unpacked under ``.scratch/``), for parent against change in
one call. ``--tiny`` swaps in the configuration's ``debug-*`` sibling at a
few rows: the CPU rehearsal of the control flow, whose times mean nothing.
A tool, not code a cell runs: the benchmark never calls it.
"""
import argparse
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {"jamba2-3b": "debug-jamba", "lfm2-24b-a2b": "debug-lfm2",
        "deepseek-v3": "debug-deepseek", "mellum2-12b": "debug-mellum",
        "mistral-7b": "debug-tiny"}


def engine_config(tree: str, name: str, tiny: bool):
    """(EngineConfig, ModelConfig, K) of a benchmark configuration's
    ``serve`` command line, the smallest prefill bucket only (every prompt
    here fits it; one executable less to compile)."""
    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.engine.engine import EngineConfig

    with open(os.path.join(tree, "benchmark", "configs", name + ".json")) as f:
        conf = json.load(f)
    flags = conf["serve_flags"]
    k = int(conf.get("decode_steps_per_dispatch", 4))
    if tiny:
        model = get_config(TINY[name])
        return EngineConfig(
            model=model.name, dtype="float32", max_decode_slots=8,
            page_size=8, num_pages=8 * 8 + 1, pages_per_slot=8,
            prefill_buckets=(16,), decode_steps=k), model, k
    model = get_config(conf["registry_name"])
    buckets = [int(b) for b in str(flags["--prefill-buckets"]).split(",")]
    return EngineConfig(
        model=model.name, dtype=flags.get("--dtype", "bfloat16"),
        max_decode_slots=int(flags["--max-decode-slots"]),
        page_size=int(flags["--page-size"]),
        num_pages=int(flags["--num-pages"]),
        pages_per_slot=int(flags["--pages-per-slot"]),
        prefill_buckets=(min(buckets),),
        quantization=flags.get("--quantization"),
        kv_cache_dtype=flags.get("--kv-cache-dtype"),
        prefix_caching=not flags.get("--no-prefix-caching", False),
        decode_steps=k, anomaly_profile=False), model, k


def reduce_ops(xplane, lines, k: int) -> dict:
    """The decode windows' ops of a loaded trace, a token step."""
    dev = [(ln, evs) for p, ln, evs in lines if xplane.is_device_plane(p)]
    mods = sorted((s, s + d) for ln, evs in dev if ln == xplane.MODULES_LINE
                  for n, s, d in evs if "decode_multi" in n)
    whole = mods[1:-1] if len(mods) > 2 else mods
    ops = [e for ln, evs in dev if ln == xplane.OPS_LINE for e in evs]
    inside = [e for e in ops
              if any(a <= e[1] and e[1] + e[2] <= b + 1 for a, b in whole)]
    steps = max(len(whole) * k, 1)
    fam, by_op = {}, {}
    for name, ns in xplane.self_times(inside):
        f = xplane.family(name)
        fam[f] = fam.get(f, 0.0) + ns
        head = name.split(" = ", 1)
        shape = (head[1].split("{")[0].split(" ")[0][:60]
                 if len(head) > 1 else "")
        rec = by_op.setdefault((f, shape), [0.0, 0, name[:600]])
        rec[0] += ns
        rec[1] += 1
    return {
        "windows_in_capture": len(mods), "windows_counted": len(whole),
        "token_steps": steps,
        "window_ms": (sum(b - a for a, b in whole) / 1e6 / max(len(whole), 1)),
        "step_ms": round(sum(fam.values()) / 1e6 / steps, 4),
        "family_ms_a_step": {n: round(v / 1e6 / steps, 4) for n, v in
                             sorted(fam.items(), key=lambda kv: -kv[1])},
        "ops": [{"family": f, "result": shape,
                 "us_a_call": round(ns / 1e3 / n, 2),
                 "calls_a_step": round(n / steps, 2),
                 "ms_a_step": round(ns / 1e6 / steps, 4), "hlo": line}
                for (f, shape), (ns, n, line) in
                sorted(by_op.items(), key=lambda kv: -kv[1][0])[:80]],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="jamba2-3b",
                    help="a file's name under benchmark/configs")
    ap.add_argument("--live", type=int, default=56,
                    help="rows decoding while the trace is taken")
    ap.add_argument("--asking", type=int, default=0,
                    help="how many of the live rows carry a frequency "
                         "penalty and a logit_bias entry (0: none, what "
                         "the benchmark's mixes send)")
    ap.add_argument("--prompt-tokens", type=int, default=100)
    ap.add_argument("--windows", type=int, default=10,
                    help="decode windows under the profiler (two are "
                         "caught in part and dropped)")
    ap.add_argument("--tree", default=HERE)
    ap.add_argument("--out", default="chiprun_out/step_trace.json")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    sys.path.insert(0, os.path.join(tree, "benchmark"))

    import jax
    import numpy as np
    from harness import xplane
    from llms_on_kubernetes_tpu.engine.engine import Engine, SamplingParams
    from llms_on_kubernetes_tpu.ops import attention

    cfg, model, k = engine_config(tree, args.config, args.tiny)
    live = min(args.live, cfg.max_decode_slots)
    n_prompt = 5 if args.tiny else args.prompt_tokens
    want = args.windows * k
    print(f"[trace] tree {tree} config {args.config} model {model.name} "
          f"live {live} of {cfg.max_decode_slots}, {args.asking} asking",
          flush=True)
    t0 = time.time()
    eng = Engine(cfg, model_config=model)
    rng = np.random.default_rng(52)
    # answers as long as a slot holds: the rows admitted first are far
    # ahead of the last by the time every row decodes
    room = cfg.pages_per_slot * cfg.page_size - n_prompt - 8
    reqs = [eng.submit(
        rng.integers(0, 255, n_prompt).tolist(),
        SamplingParams(max_tokens=room, temperature=0.0, **(
            dict(frequency_penalty=0.5, logit_bias=((17, 2.0),))
            if i < args.asking else {})))
        for i in range(live)]

    def step():
        eng.step()
        assert not any(r.finished for r in reqs), "a row ran out of room"

    # every prompt in, every row decoding, the decode executable warm
    while any(len(r.output) < 12 for r in reqs):
        step()
    print(f"[trace] warm after {time.time() - t0:.0f} s; chosen "
          f"{ {n: v[0] for n, v in attention._chosen.items()} }", flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    tdir = os.path.abspath(args.out) + ".trace"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    n0 = [len(r.output) for r in reqs]
    t1 = time.time()
    while min(len(r.output) - a for r, a in zip(reqs, n0)) < want:
        step()
    wall = time.time() - t1
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(tdir, "plugins/profile/*/*.xplane.pb"))[0]
    red = {"tree": tree, "config": args.config, "model": model.name,
           "live_rows": live, "asking_rows": min(args.asking, live),
           "slots": cfg.max_decode_slots,
           "vocab": model.vocab_size, "decode_steps": k,
           "device": jax.devices()[0].device_kind,
           "wall_ms_a_token": round(wall / want * 1e3, 3),
           "chosen": {n: list(v) for n, v in attention._chosen.items()}}
    red.update(reduce_ops(xplane, xplane.load(pb), k))
    with open(args.out, "w") as f:
        json.dump(red, f, indent=1)
    shutil.rmtree(tdir, ignore_errors=True)
    print("[trace]", json.dumps({n: red[n] for n in (
        "windows_counted", "step_ms", "wall_ms_a_token")}),
        "windows by sampler", eng.decode_windows
        if hasattr(eng, "decode_windows") else "(no such counter)")
    print("[trace] families", json.dumps(dict(list(
        red["family_ms_a_step"].items())[:14])))
    for op in red["ops"][:24]:
        print("[trace] op", op["family"], op["result"], op["us_a_call"],
              "us x", op["calls_a_step"], "=", op["ms_a_step"], "ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
