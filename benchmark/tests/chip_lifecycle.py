#!/usr/bin/env python3
"""On the chip: end runs of a cell from outside and show that the chip is
free for the next one. Not a test that pytest collects: it needs the chip
and takes a quarter of an hour after the first run has compiled.

    python3 benchmark/tests/chip_lifecycle.py --workload <cell> --seed <n> \\
        --out chiprun_out/lifecycle.json

1. a whole run (it compiles where the checkout's cache is empty);
2. a run ended by SIGKILL in mid-window;
3. a run ended by SIGTERM 40 s into "loading shapes" (the server inside
   XLA); its time to ``/ready`` shows what 2 left;
4. a run ended by SIGTERM in mid-window (the event loop inside the steps
   of some thirty streams); its time to ``/ready`` shows what 3 left;
5. a whole traced run; its time to ``/ready`` shows what 4 left, and its
   wall time is what a traced run has against the 360 s a run may take.

Each step's record holds the children's pids, how long after the signal
the last of them was gone, the run's exit code, its "phase" lines and,
for a whole run, its result line and where its time went. Before chip
time is spent on it, ``--rehearse`` with a CPU cell walks the same steps
here (it shows nothing about the chip).
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import manifest  # noqa: E402
from test_lifecycle import CHILDREN, alive  # noqa: E402

RUN = os.path.join(manifest.BENCH_DIR, "run.py")
SECONDS = float(manifest.load_benchmark()["run_seconds"])
INTO_SHAPES_S = 40.0


def one(args, seed: int, trace: int, end=None) -> dict:
    """One run. ``end`` = (phase, seconds into it, signal) ends it there."""
    with tempfile.TemporaryDirectory() as tmp:
        out_path, err_path = (os.path.join(tmp, n) for n in ("out", "err"))
        t0 = time.monotonic()
        with open(out_path, "wb") as out, open(err_path, "wb") as err:
            run = subprocess.Popen(
                [sys.executable, RUN, "--workload", args.workload, "--seed",
                 str(seed), "--seconds", str(SECONDS), "--trace",
                 str(trace)] + ["--rehearse"] * args.rehearse,
                stdout=out, stderr=err, cwd=manifest.REPO_DIR)
        rec = {"seed": seed, "trace": trace, "run_pid": run.pid}

        def said() -> str:
            with open(err_path, errors="replace") as f:
                return f.read()

        try:
            if end is not None:
                phase, after_s, signum = end
                while f"benchmark: phase {phase} " not in said():
                    if run.poll() is not None:
                        raise SystemExit(f"run ended before {phase}:\n"
                                         f"{said()[-3000:]}")
                    time.sleep(0.2)
                time.sleep(after_s)
                pids = [int(g) for g in CHILDREN.search(said()).groups()]
                rec.update(signal=int(signum), children=pids,
                           children_alive_before=[alive(p) for p in pids],
                           signalled_at_s=time.monotonic() - t0)
                t_sig = time.monotonic()
                run.send_signal(signum)
                rec["exit_code"] = run.wait(timeout=60)
                rec["run_gone_after_s"] = time.monotonic() - t_sig
                while any(alive(p) for p in pids) \
                        and time.monotonic() < t_sig + 30:
                    time.sleep(0.02)
                rec["children_gone_after_s"] = time.monotonic() - t_sig
                rec["children_left"] = [p for p in pids if alive(p)]
            else:
                rec["exit_code"] = run.wait(timeout=1500)
        finally:
            if run.poll() is None:
                run.kill()
                run.wait()
        rec["wall_s"] = time.monotonic() - t0
        err_text = said()
        rec["stderr_benchmark_lines"] = [
            ln for ln in err_text.splitlines() if ln.startswith("benchmark:")]
        with open(out_path) as f:
            lines = f.read().strip().splitlines()
        rec["stdout_lines"] = len(lines)
        if end is None and lines:
            rec["result"] = json.loads(lines[-1])
            infos = [json.loads(ln)["info"] for ln in lines[:-1]]
            rec["where_the_time_went"] = {
                k: infos[0][k] for k in
                ("setup_phases_s", "drain_s", "after_the_window_s",
                 "compiles_in_window", "child_exit_codes", "failed")
                if k in infos[0]}
        elif end is None:
            rec["stderr_tail"] = err_text[-3000:]
    print(json.dumps(rec), flush=True)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="a CPU cell: rehearses this script, shows nothing")
    args = ap.parse_args()
    pre_s = float(manifest.load_cell(args.workload).mix["preroll_s"])
    mid_window = pre_s + SECONDS / 2
    steps = [one(args, args.seed, 0),
             one(args, args.seed + 1, 0,
                 ("preroll", mid_window, signal.SIGKILL)),
             one(args, args.seed + 2, 0,
                 ("shapes", INTO_SHAPES_S, signal.SIGTERM)),
             one(args, args.seed + 3, 0,
                 ("preroll", mid_window, signal.SIGTERM)),
             one(args, args.seed + 4, 1)]
    with open(args.out, "w") as f:
        json.dump(steps, f, indent=1)
        f.write("\n")
    ok = (all(not s.get("children_left") for s in steps)
          and steps[0]["exit_code"] == 0 and steps[-1]["exit_code"] == 0
          and [s["exit_code"] for s in steps[1:4]] == [-9, 143, 143])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
