"""Decode-step profiler: where does the step time go?

Round-4 verdict: decode sits at ~39% of the v5e HBM roofline and nobody
has published a breakdown. This script measures, on the real chip:

1. PURE DEVICE step time — N decode steps chained on device (each step's
   sampled tokens feed the next through last_toks, exactly like the async
   pipeline), ONE final read, so the read's latency is amortized away.
2. ENGINE-LOOP step time — the same config driven through Engine.step()
   at full batch (what bench.py measures), isolating host/scheduler cost.
3. An op-level breakdown from a jax.profiler trace over the chained
   window (device "X" events summed by op name).
4. A per-step KERNEL / DISPATCH / COLLECTIVE / HARVEST breakdown (PR 3):
   kernel = chained device step, dispatch = host enqueue time, collective
   = trace ops matching the collective families (psum/all-*), harvest =
   the synchronizing read. Plus the host packed-array build time (the
   template-cached fast path). Emitted both as a table and as one
   machine-readable ``PROFILE:{...}`` JSON line (PARITY.md carries the
   table).

Usage (real TPU):  python scripts/profile_decode.py [--steps 40]
Env: BENCH_SLOTS/BENCH_PAGE/BENCH_KV/BENCH_MODEL as bench.py.
"""

import argparse
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def steady_packed(eng, lengths_val: int) -> np.ndarray:
    """A full-batch decode packed array at a fixed context length."""
    from llms_on_kubernetes_tpu.engine.engine import (
        _BIAS_DEC, _BUD_DEC, _DEC_COLS, _FSM_DEC, _STOP_DEC,
        LOGIT_BIAS_SLOTS, STOP_SLOTS,
    )

    B = eng.config.max_decode_slots
    pps = eng.allocator.pages_per_slot
    packed = np.zeros((B, _DEC_COLS + pps), np.int32)
    packed[:, 0] = lengths_val
    packed[:, 1] = 0                                # src: last_toks chain
    packed[:, 4] = np.float32(0.0).view(np.int32)   # greedy
    packed[:, 5] = np.float32(1.0).view(np.int32)
    packed[:, _FSM_DEC] = -1
    packed[:, _BUD_DEC] = 1_000_000                 # never early-exit
    packed[:, _STOP_DEC:_STOP_DEC + STOP_SLOTS] = -1
    packed[:, _BIAS_DEC:_BIAS_DEC + LOGIT_BIAS_SLOTS] = -1
    packed[:, _DEC_COLS:] = eng.allocator.page_tables
    return packed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--ctx", type=int, default=96, help="context length")
    ap.add_argument("--trace", default="/tmp/llmk-prof")
    ap.add_argument("--engine-steps", type=int, default=200)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import build_engine, make_configs, warm_engine
    from llms_on_kubernetes_tpu.cli import configure_compilation_cache
    from llms_on_kubernetes_tpu.engine.engine import SamplingParams

    configure_compilation_cache()

    ecfg, cfg, prompt_len, gen_len = make_configs()
    print(f"platform={jax.devices()[0].platform} model={ecfg.model} "
          f"B={ecfg.max_decode_slots} page={ecfg.page_size} "
          f"kv={ecfg.kv_cache_dtype or ecfg.dtype}", flush=True)
    eng = build_engine(ecfg, cfg)
    rng = np.random.default_rng(0)
    warm_engine(eng, cfg, prompt_len, rng)

    # occupy every slot so page tables are real
    B = ecfg.max_decode_slots
    reqs = [eng.submit(list(rng.integers(1, 100, prompt_len)),
                       SamplingParams(temperature=0.0, max_tokens=gen_len))
            for _ in range(B)]
    for _ in range(200):
        eng.step()
        if all(r is not None for r in eng.slots):
            break
    eng._drain_async()
    # grow allocations to cover the probed context length + fused window
    K = int(eng.config.decode_steps or 1)
    for i in range(B):
        eng.allocator.allocate(i, args.ctx + K + 2)

    packed_np = steady_packed(eng, args.ctx)
    packed = jnp.asarray(packed_np)
    toks = jnp.asarray(np.full((B,), 17, np.int32))

    def chain(n, k=1):
        """Dispatch n decode launches (each a fused k-step window when
        k > 1); returns (enqueue wall, sync wall)."""
        nonlocal toks
        t0 = time.monotonic()
        for _ in range(n):
            if k == 1:
                (_pack, toks, eng.k_pages, eng.v_pages, eng.token_counts,
                 _state) = eng._decode_packed(
                    eng.params, cfg, packed, toks, eng._zeros_1, eng.k_pages,
                    eng.v_pages, eng.token_counts, eng._key, None)
            else:
                (_packs, toks, eng.k_pages, eng.v_pages, eng.token_counts,
                 _state) = eng._decode_multi(
                    eng.params, cfg, k, packed, toks, eng._zeros_1,
                    eng.k_pages, eng.v_pages, eng.token_counts, eng._key,
                    None)
        t1 = time.monotonic()
        np.asarray(toks)  # ONE synchronizing read
        return t1 - t0, time.monotonic() - t1

    chain(4)  # warm this exact shape/chain
    wall = sum(chain(args.steps))
    rtt_probe = sum(chain(1))  # ~dispatch + RTT + 1 step
    per_step = (wall - rtt_probe) / (args.steps - 1)
    print(f"pure-device decode step: {1000 * per_step:.2f} ms "
          f"({args.steps} chained; 1-step probe {1000 * rtt_probe:.1f} ms)",
          flush=True)
    print(f"  => {B / per_step:.0f} tok/s/chip device ceiling at B={B}",
          flush=True)

    # dispatch (host enqueue, overlaps the device on TPU) and harvest
    # (the synchronizing read) measured separately for the breakdown
    enq, har = chain(args.steps)
    dispatch_ms = 1000 * enq / args.steps
    harvest_ms = 1000 * har

    # --- fused K-step window: per-DISPATCH cost + host-share vs K=1 ---
    # host time per dispatch (enqueue + sync read + packed-array build)
    # is roughly constant in K, so fusing K steps into one launch shrinks
    # the host share of each generated token by ~K. Both paths are
    # measured in THIS run so the PROFILE line carries its own baseline.
    kernel_k_ms = per_step * 1000
    dispatch_k_ms, harvest_k_ms = dispatch_ms, harvest_ms
    if K > 1:
        n_k = max(4, args.steps // K)
        chain(2, K)  # warm the fused executable
        wall_k = sum(chain(n_k, K))
        probe_k = sum(chain(1, K))
        kernel_k_ms = 1000 * max(wall_k - probe_k, 1e-9) / max(n_k - 1, 1)
        enq_k, har_k = chain(n_k, K)
        dispatch_k_ms = 1000 * enq_k / n_k
        harvest_k_ms = 1000 * har_k
        print(f"fused window (K={K}): {kernel_k_ms:.2f} ms/dispatch = "
              f"{kernel_k_ms / K:.2f} ms/token-step "
              f"({1000 * per_step:.2f} ms unfused)", flush=True)

    # host packed-array build: the template-cached _dec_template path plus
    # the per-step dynamic columns (what the engine loop pays per step)
    active = [(i, r) for i, r in enumerate(eng.slots) if r is not None]
    host_pack_ms = 0.0
    if active:
        reps = 200
        t0 = time.monotonic()
        for _ in range(reps):
            p = eng._dec_template(active)
            for i, r in active:
                p[i, 0] = int(eng.slot_len[i]) + 1
                p[i, 2] = r.pending_token
        host_pack_ms = 1000 * (time.monotonic() - t0) / reps

    # --- op-level trace over a chained window -------------------------
    os.makedirs(args.trace, exist_ok=True)
    collective_ms = 0.0
    try:
        jax.profiler.start_trace(args.trace)
        chain(10)
        jax.profiler.stop_trace()
    except Exception as e:
        print(f"trace failed: {e}", flush=True)
    else:
        collective_ms = report_trace(args.trace, n_steps=10)

    # host share of a dispatch: the host-BLOCKING work per launch — the
    # synchronizing harvest read + the packed-array build. Enqueue is
    # excluded: it overlaps the device in the async pipeline (and on CPU
    # its wall time is just execution backpressure). These costs are
    # ~constant in K, so fusing K steps divides the per-token host share
    # by ~K. Both paths are measured in THIS run so the PROFILE line
    # carries its own K=1 baseline.
    host_k1 = harvest_ms + host_pack_ms
    host_share_k1 = host_k1 / max(1000 * per_step + host_k1, 1e-9)
    host_k = harvest_k_ms + host_pack_ms
    host_share = host_k / max(kernel_k_ms + host_k, 1e-9)

    breakdown = {
        # per-DISPATCH costs of the fused path (== per-step when K=1)
        "kernel_ms": round(kernel_k_ms, 4),
        "dispatch_ms": round(dispatch_k_ms, 4),
        "collective_ms": round(collective_ms, 4),
        "harvest_ms": round(harvest_k_ms, 4),
        "host_pack_ms": round(host_pack_ms, 4),
        "decode_steps": K,
        "tokens_per_dispatch": K,
        "dispatches_per_token": round(1.0 / K, 4),
        "host_share": round(host_share, 4),
        "host_share_k1": round(host_share_k1, 4),
        "kernel_k1_ms": round(1000 * per_step, 4),
        "batch": B,
        "ctx": args.ctx,
    }
    print(f"-- decode breakdown (ms/DISPATCH; K={K} token-steps fused) --",
          flush=True)
    print(f"  kernel      {breakdown['kernel_ms']:8.3f}  "
          "(fused device window)", flush=True)
    print(f"  dispatch    {breakdown['dispatch_ms']:8.3f}  "
          "(host enqueue; overlaps the device on TPU)", flush=True)
    print(f"  collective  {breakdown['collective_ms']:8.3f}  "
          "(trace: psum/all-* families; 0 on one chip)", flush=True)
    print(f"  harvest     {breakdown['harvest_ms']:8.3f}  "
          "(synchronizing read)", flush=True)
    print(f"  host-pack   {breakdown['host_pack_ms']:8.3f}  "
          "(packed-array build; template-cached)", flush=True)
    print(f"  host share  {breakdown['host_share']:8.3f}  "
          f"(K=1 baseline {breakdown['host_share_k1']:.3f})", flush=True)

    # --- engine-loop comparison ---------------------------------------
    # (the PROFILE line prints after this phase: it carries the loop's
    # speculation counters — drafted/accepted/wasted rows — when
    # LLMK_SPECULATION is on)
    for r in reqs:
        eng.abort(r)
    eng.step()
    eng._drain_async()
    reqs = [eng.submit(list(rng.integers(1, 100, prompt_len)),
                       SamplingParams(temperature=0.0, max_tokens=gen_len))
            for _ in range(B - 1)]
    disp0, tok0 = eng.decode_dispatches, eng.decode_tokens
    drafted0 = getattr(eng, "spec_drafted_tokens", 0)
    accepted0 = getattr(eng, "spec_accepted_tokens", 0)
    wasted0 = getattr(eng, "early_exit_steps", 0)
    t0 = time.monotonic()
    total = 0
    window_start = window_tokens = None
    end_t = end_tok = None
    while any(not r.finished for r in reqs):
        events = eng.step()
        total += sum(len(ev.new_tokens) for ev in events)
        active = sum(r is not None for r in eng.slots)
        now = time.monotonic()
        if events and active >= B - 1:
            if window_start is None:
                window_start, window_tokens = now, total
            end_t, end_tok = now, total
    if window_start is not None and end_t is not None and end_t > window_start:
        tps = (end_tok - window_tokens) / (end_t - window_start)
        print(f"engine-loop steady decode: {tps:.0f} tok/s "
              f"({1000 * (B - 1) / tps:.2f} ms/step at B={B - 1})",
              flush=True)
    disp = eng.decode_dispatches - disp0
    toks_n = eng.decode_tokens - tok0
    if toks_n:
        print(f"engine-loop dispatches/token: {disp / toks_n:.3f} "
              f"({disp} dispatches, {toks_n} tokens, K={K})", flush=True)
    # speculation accounting over the engine-loop window: drafted rows
    # ridden, drafts that survived the verify pass, and row-steps whose
    # launch was wasted (rejected tails + early exits) — the FLOPs
    # speculation risks against the dispatches it saves
    breakdown["spec_drafted"] = getattr(eng, "spec_drafted_tokens",
                                        0) - drafted0
    breakdown["spec_accepted"] = getattr(eng, "spec_accepted_tokens",
                                         0) - accepted0
    breakdown["wasted_rows"] = getattr(eng, "early_exit_steps", 0) - wasted0
    print(f"  spec-drafted  {breakdown['spec_drafted']:6d}  "
          "(draft tokens ridden on decode windows)", flush=True)
    print(f"  spec-accepted {breakdown['spec_accepted']:6d}  "
          "(drafts surviving the verify pass)", flush=True)
    print(f"  wasted-rows   {breakdown['wasted_rows']:6d}  "
          "(row-steps launched then discarded)", flush=True)
    print("PROFILE:" + json.dumps(breakdown), flush=True)
    print(f"total wall {time.monotonic() - t0:.1f}s", flush=True)


def report_trace(trace_dir: str, n_steps: int) -> float:
    """Sum device-track "X" events by op name across the trace; returns
    the collective-op families' total in ms/step (the breakdown's
    'collective' slice)."""
    files = glob.glob(os.path.join(
        trace_dir, "plugins/profile/*/*.trace.json.gz"))
    if not files:
        print("no trace files found", flush=True)
        return 0.0
    path = max(files, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    # device pids: process names containing "TPU" / "/device:"
    pid_names = {e["pid"]: e["args"].get("name", "")
                 for e in events
                 if e.get("ph") == "M" and e.get("name") == "process_name"
                 and "args" in e}
    dev_pids = {p for p, n in pid_names.items()
                if "TPU" in n or "/device" in n.lower() or "Chip" in n}
    import re

    agg: dict = {}
    counts: dict = {}
    parent = 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in dev_pids:
            continue
        name = e.get("name", "?")
        if name.startswith("jit_"):      # whole-module parent span
            parent += e.get("dur", 0.0)
            continue
        # group op instances: strip trailing .N / digits (fusion.324,
        # pallas_paged_attention.77 -> one family each)
        fam = re.sub(r"[.\d]+$", "", name)
        agg[fam] = agg.get(fam, 0.0) + e.get("dur", 0.0)
        counts[fam] = counts.get(fam, 0) + 1
    total = sum(agg.values())
    print(f"-- device op breakdown ({path.split('/')[-1]}, {n_steps} steps; "
          f"module span {parent / 1000 / n_steps:.2f} ms/step, child ops "
          f"{total / 1000 / n_steps:.2f} ms/step) --", flush=True)
    for fam, dur in sorted(agg.items(), key=lambda kv: -kv[1])[:22]:
        print(f"  {dur / 1000 / n_steps:8.3f} ms/step  "
              f"{100 * dur / max(total, 1e-9):5.1f}%  x{counts[fam]:<5d} "
              f"{fam[:80]}", flush=True)
    coll = re.compile(r"all-reduce|all-gather|all-to-all|reduce-scatter"
                      r"|collective|permute|psum")
    coll_us = sum(d for f, d in agg.items() if coll.search(f))
    return coll_us / 1000 / n_steps


if __name__ == "__main__":
    main()
