#!/usr/bin/env python3
"""Find a cell's knee: the highest of a few fixed rates the replica
sustains within the mix's two latency limits.

    python3 benchmark/sweep.py --workload <cell> --rates 3,4,5,6,7,8 \\
        --seconds 30 [--out FILE]

One server process for the whole sweep; each rate gets the mix's preroll, a
window and a drain. A rate passes when the mix's share of the requests SENT
in its window (failed ones miss) met both limits — first token within
``limits.ttft_ms`` of being due, time per output token within
``limits.tpot_ms`` — and the backlog did not grow: at the window's end no
more requests were still waiting for a first token than arrive within the
first-token limit. The record is written beside the cell's file
(``cells/<cell>.sweep.json``); the knee goes into ``cells/<cell>.json`` by
hand, with the record as its evidence. A sweep measures nothing the driver
reads and is not part of a run.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import cell as cellmod  # noqa: E402
from harness import (launcher, lifecycle, manifest,  # noqa: E402
                     setup_steps, stats)


def judge(got: dict, mix: dict, rate: float) -> dict:
    w0, w1 = got["bounds"]["window"]
    window = [r for r in got["records"] if r.part == "window"]
    censor = got["bounds"]["drained"][1]
    lim = mix["limits"]
    ttfts = [stats.ttft_ms(r, censor) for r in window]
    met = stats.met_both_limits(window, lim, censor)
    waiting = sum(1 for r in got["records"] if r.due <= w1
                  and not (r.events and r.events[0][0] <= w1)
                  and not (r.status and r.status != 200))
    tpots = stats.tpots_with_worst(window)
    share = met / len(window)
    backlog_ok = waiting <= rate * lim["ttft_ms"] / 1000.0
    return {
        "rate_rps": rate, "sent": len(window),
        "failed": sum(1 for r in window if not r.ok),
        "met_both_share": share, "waiting_at_end": waiting,
        "backlog_ok": backlog_ok,
        "passes": bool(share >= lim["share_that_must_meet_both"]
                       and backlog_ok),
        "ttft_p50_ms": stats.percentile(ttfts, 50),
        "ttft_p95_ms": stats.percentile(ttfts, 95),
        "tpot_p50_ms": stats.percentile(tpots, 50) if tpots else None,
        "tpot_p95_ms": stats.percentile(tpots, 95) if tpots else None,
        "out_tok_s": stats.tokens_between(got["records"], w0, w1)
        / (w1 - w0),
        "drain_s": censor - w1,
    }


def main(timeline: lifecycle.Timeline) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests/s, ascending")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    rates = [float(x) for x in args.rates.split(",")]
    try:
        cell = manifest.load_cell(args.workload)
        platform = "cpu" if args.rehearse else "tpu"
        if cell.config.get("platform", "tpu") != platform:
            raise launcher.NoResult(
                f"{args.workload!r} is not a {platform} cell")
        rows = []
        with cellmod.served(cell, platform, int(cell.config["chips"]),
                            timeline) as up:
            cellmod.storms(up, cell)
            for i, rate in enumerate(rates):
                before = setup_steps.compiles(up.stack.server_port)
                got = cellmod.offer(up, cell, rate, args.seconds,
                                    args.seed + i)
                row = judge(got, cell.mix, rate)
                # a rate during which the server re-traced a step stalled
                # for seconds and says nothing about capacity
                row["jit_compiles_during"] = setup_steps.compiles(
                    up.stack.server_port) - before
                if args.rehearse:   # counts only: no CPU timing by name
                    row = {k: v for k, v in row.items()
                           if not k.endswith(("_ms", "_s"))}
                rows.append(row)
                print(json.dumps(rows[-1]), flush=True)
                up.stack.check_alive()
            device = up.device
    except (launcher.NoResult, manifest.ManifestError) as e:
        print(f"sweep: no result: {e}", file=sys.stderr)
        return 2
    except launcher.Failed as e:
        print(f"sweep: failed: {e}", file=sys.stderr)
        return 1
    passing = [r["rate_rps"] for r in rows if r["passes"]]
    doc = {"cell": args.workload, "device": device,
           "seconds_per_rate": args.seconds, "seed": args.seed,
           "limits": cell.mix["limits"], "rates": rows,
           "knee_rps": max(passing) if passing else None,
           "rehearsal": bool(args.rehearse)}
    out = args.out or os.path.join(manifest.BENCH_DIR, "cells",
                                   f"{args.workload}.sweep.json")
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(json.dumps({"knee_rps": doc["knee_rps"], "record": out}))
    return 0


if __name__ == "__main__":
    sys.exit(lifecycle.guarded(main, lifecycle.Timeline(T0, "sweep")))
