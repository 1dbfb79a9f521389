#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --rehearse            # debug-tiny on the CPU

Starts ``serve`` and ``router`` as children (this process never imports
JAX), warms up, checks outputs, offers open-loop traffic for --seconds,
drains, and prints the result as the last line of stdout. Without a TPU
that is in the benchmark's peak table, or outside a checkout, it prints no
result and exits non-zero. ``BENCH_RUN`` in the environment is ignored.

However it ends, it leaves no process behind (``harness/lifecycle.py``):
stderr names the children it started ("benchmark: children server=PID
router=PID"), and a run ended by SIGTERM, SIGHUP or SIGINT stops them,
says "benchmark: ended by signal N after S s in phase P", prints no
result and exits 128 + N.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import cell, launcher, lifecycle, manifest  # noqa: E402


def main(timeline: lifecycle.Timeline) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the CPU rehearsal cell: counts, never a timing")
    args = ap.parse_args()
    try:
        bench = manifest.load_benchmark()
        seconds = args.seconds or float(bench["run_seconds"])
        name = args.workload
        if args.rehearse:
            name = name or "debug-tiny.rehearse"
            seconds = args.seconds or 6.0
        if not name:
            ap.error("--workload is required")
        return cell.run(name, args.seed, seconds, bool(args.trace),
                        args.rehearse, timeline)
    except (launcher.NoResult, manifest.ManifestError) as e:
        print(f"benchmark: no result: {e}", file=sys.stderr)
        return 2
    except launcher.Failed as e:
        print(f"benchmark: failed: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(lifecycle.guarded(main, lifecycle.Timeline(T0)))
