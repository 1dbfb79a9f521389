"""One run of one cell: start the stack, warm up, check outputs, offer the
open-loop traffic for the window, drain, reduce, print.

Stdout: free-form ``{"info": ...}`` lines first, then — last — the one
result line the driver reads. A rehearsal (``--rehearse``, CPU) prints a
line of counts and never a device metric's name.
"""

from __future__ import annotations

import asyncio
import contextlib
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

from . import client, lifecycle, manifest, schedule, setup_steps, stats
from .launcher import Failed, NoResult, Stack
from .peaks import PEAKS

START_BUDGET_S = 1100.0     # first run of a cell in a checkout compiles
# no new warm-up storm this long after the start: a warm run must end
# within 360 s, a run that had to compile (it shows by being past the warm
# budget before its first storm) within 1200 s
STORM_BUDGET_S = 240.0
COLD_STORM_BUDGET_S = 950.0


class Context:
    """What a layer-metric reader may read. Built once per traced run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _say(**info) -> None:
    print(json.dumps({"info": info}), flush=True)


# --------------------------------------------------------------------------
# pollers of the traced run
# --------------------------------------------------------------------------

def _pollers(stack: Stack, mix: dict, sink: dict):
    """Side tasks of a traced run: over the window, scrape both /metrics
    once a second and keep every finished trace of /debug/traces (a ring
    of 256, so polled and de-duplicated by id); over the tail that a
    traced run offers after its window, take one profiler capture."""
    import aiohttp

    sink.update(polls=[], router_polls=[], spans={}, capture=None,
                capture_at=None)
    trace_s = float(mix["trace"]["capture_s"])
    tail_s = float(mix["trace"]["tail_s"])

    async def poll(t0: float, t_end: float) -> None:
        base = f"http://127.0.0.1:{stack.server_port}"
        rbase = f"http://127.0.0.1:{stack.router_port}"
        async with aiohttp.ClientSession() as s:
            while time.monotonic() < t_end - tail_s:
                now = time.monotonic()
                if now >= t0:
                    try:
                        async with s.get(base + "/metrics") as r:
                            sink["polls"].append(
                                (now, client.parse_metrics(await r.text())))
                        async with s.get(rbase + "/metrics") as r:
                            sink["router_polls"].append(
                                (now, client.parse_metrics(await r.text())))
                        async with s.get(base + "/debug/traces?limit=256") as r:
                            for t in (await r.json()).get("traces", ()):
                                sink["spans"][t.get("id")] = t
                    except (aiohttp.ClientError, ValueError):
                        pass
                await asyncio.sleep(1.0)

    async def capture(t0: float, t_end: float) -> None:
        at = t_end - tail_s + 0.5
        await asyncio.sleep(max(0.0, at - time.monotonic()))
        url = f"http://127.0.0.1:{stack.server_port}/debug/profile"
        async with aiohttp.ClientSession() as s:
            sink["capture_at"] = (time.monotonic(), None)
            async with s.post(url, json={"duration_ms": 1000 * trace_s}) as r:
                sink["capture"] = await r.json()
            sink["capture_at"] = (sink["capture_at"][0], time.monotonic())

    return [poll, capture]


def traced_window_s(asked_s: float, devs: dict) -> float:
    """The length of the traced window: what the capture was asked for,
    or the span of the device's own events where that is longer. The
    server's ``duration_s`` is the time it slept between starting and
    stopping the profiler, and stopping takes a moment more, so a device
    that never idles shows events over slightly more than was asked."""
    return max(float(asked_s), *(d["span_s"] for d in devs.values()))


def _start_reduce(profile_dir: str) -> "subprocess.Popen | None":
    """Start reducing the newest .xplane.pb under the server's profile
    directory, in a child process held to the CPU (the parent never
    imports jax; the child touches no chip, so it may run while the server
    is being stopped). It dies with this process; ``served`` reaps it on
    any way out that ``_finish_reduce`` did not see."""
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "xplane.py")
    return lifecycle.spawn(
        [sys.executable, script, max(files, key=os.path.getmtime)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _finish_reduce(proc) -> "dict | None":
    if proc is None:
        return None
    try:
        out, err = proc.communicate(timeout=300)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise Failed("xplane reduction took over 300 s") from None
    if proc.returncode != 0:
        raise Failed(f"xplane reduction failed: {err[-500:]}")
    return json.loads(out)


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

@contextlib.contextmanager
def served(cell, platform: str, chips: int, timeline: lifecycle.Timeline):
    """The cell's configuration behind a router, ready and warmed up for
    the cell's mix; stopped and cleaned up on the way out: gracefully
    when the block ends by itself, at once (SIGKILL) when an exception or
    a signal ends it, with whatever ``up.reducer`` still runs. Call it
    from the main thread (``launcher.Child``). Yields a ``Context`` with
    the stack, the device, the phases' times and the list of 503s the
    set-up requests waited out."""
    config, mix = cell.config, cell.mix
    workdir = tempfile.mkdtemp(prefix="bench-")
    profile_dir = os.path.join(workdir, "profiles")
    stack = Stack(config, workdir, platform, {
        "LLMK_PROFILE_DIR": profile_dir,
        "LLMK_PROFILE_MAX_S": str(mix["trace"]["capture_s"] + 1),
        # the server then logs every trace-and-compile by name, which is
        # how a stall inside a window is told from a slow request
        "JAX_LOG_COMPILES": "1"})
    waited: list = []
    up = None
    try:
        stack.start()
        print(f"{timeline.who}: children server={stack.server.proc.pid} "
              f"router={stack.router.proc.pid}", file=sys.stderr, flush=True)
        device = stack.wait_device(
            chips, None if platform == "cpu" else PEAKS,
            time.monotonic() + 300.0)
        give_up = timeline.t0 + START_BUDGET_S
        while True:
            stack.check_alive()
            try:
                client.http_json(stack.server_port, "GET", "/ready",
                                 timeout=5)
                break
            except (OSError, Failed):
                pass
            if time.monotonic() > give_up:
                raise Failed("server not ready within the start budget")
            time.sleep(0.25)
        t_ready = time.monotonic()
        timeline.enter("shapes")
        warm = setup_steps.load_shapes(
            stack.router_port, stack.server_port, config["registry_name"],
            config, mix, waited)
        up = Context(stack=stack, device=device, waited=waited, warm=warm,
                     t_ready=t_ready, t_warm=time.monotonic(),
                     profile_dir=profile_dir, timeline=timeline,
                     reducer=None)
        yield up
    except BaseException:
        # nothing is left to read of a run that ends here: no 30 s of
        # grace a child, whoever ends this process waits less than that
        stack.stop(hard=True)
        for c in stack.children:
            print(f"--- last lines of {c.name} ---\n{c.tail()}",
                  file=sys.stderr)
        raise
    finally:
        stack.stop()
        lifecycle.reap(up and up.reducer)
        shutil.rmtree(workdir, ignore_errors=True)


def storms(up, cell) -> dict:
    up.timeline.enter("storms")
    spent = time.monotonic() - up.timeline.t0
    budget = COLD_STORM_BUDGET_S if spent > STORM_BUDGET_S else STORM_BUDGET_S
    return setup_steps.storms(
        up.stack.router_port, up.stack.server_port,
        cell.config["registry_name"], cell.mix,
        float(cell.cell["knee_rps"]), up.timeline.t0 + budget,
        up.waited)


def offer(up, cell, rate: float, seconds: float, seed: int,
          side=(), tail_s: float = 0.0) -> dict:
    """Preroll plus one window of the cell's mix at ``rate``, and for a
    traced run ``tail_s`` more seconds of it for the profiler."""
    mix = cell.mix
    pre_s = float(mix["preroll_s"])
    parts = [("preroll", schedule.plan(mix, rate, pre_s, seed, "preroll"),
              pre_s),
             ("window", schedule.plan(mix, rate, seconds, seed, "window"),
              float(seconds))]
    if tail_s:
        parts.append(("tail", schedule.plan(mix, rate, tail_s, seed, "tail"),
                      tail_s))
    # where the open loop is by the clock, for a run that is ended inside
    # it; a traced run's tail holds the profiler's capture and its drain
    # the profiler's stop
    t_tail = pre_s + float(seconds)
    up.timeline.enter("preroll", window=pre_s, drain=t_tail + tail_s,
                      **({"tail": t_tail} if tail_s else {}))
    got = asyncio.run(client.open_loop(
        up.stack.router_port, cell.config["registry_name"], mix, parts,
        float(mix["drain_limit_s"]), side))
    for s in got["side"]:
        if isinstance(s, BaseException):
            raise Failed(f"a side task of the run failed: {s!r}")
    return got


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        rehearse: bool, timeline: lifecycle.Timeline) -> int:
    t_process_start = timeline.t0
    bench = manifest.load_benchmark()
    cell = manifest.load_cell(cell_name)
    config, mix = cell.config, cell.mix
    declared = {w["name"]: w for w in bench["workloads"]}
    if rehearse:
        if config.get("platform") != "cpu":
            raise NoResult(f"--rehearse runs a CPU configuration; "
                           f"{cell.cell['config']!r} is not one")
        chips = 1
    else:
        if cell_name not in declared:
            raise NoResult(f"{cell_name!r} is not in BENCHMARK.json")
        if config.get("platform", "tpu") != "tpu":
            raise NoResult(f"{cell.cell['config']!r} is a rehearsal "
                           f"configuration; use --rehearse")
        chips = int(declared[cell_name]["chips"])
    model = config["registry_name"]
    with served(cell, "cpu" if rehearse else "tpu", chips,
                timeline) as up:
        stack, device, waited, warm = (up.stack, up.device, up.waited,
                                       up.warm)
        t_ready, t_warm = up.t_ready, up.t_warm
        timeline.enter("check")
        check = setup_steps.check_outputs(
            stack.router_port, model, cell.cell["config"], config, waited)
        t_checked = time.monotonic()
        stormed = storms(up, cell)
        t_stormed = time.monotonic()
        rate = cell.rate_rps
        sink: dict = {}

        tail_s = float(mix["trace"]["tail_s"]) if trace else 0.0

        async def mark(t0: float, t_end: float) -> None:
            # the counters as the window opens and as it closes: what the
            # preroll or a traced run's tail compiled is not the window's
            for key, at in (("at_window_start", t0),
                            ("at_window_end", t_end - tail_s)):
                await asyncio.sleep(max(0.0, at - time.monotonic()))
                sink[key] = await asyncio.to_thread(
                    client.scrape, stack.server_port)

        side = [mark] + (_pollers(stack, mix, sink) if trace else [])
        got = offer(up, cell, rate, seconds, seed, side, tail_s)
        t_offered = time.monotonic()
        after = client.scrape(stack.server_port)
        attention = stack.attention_impl()
        compiled = stack.compile_log()
        stack.check_alive()
        timeline.enter("stop")
        up.reducer = _start_reduce(up.profile_dir) if trace else None
        # everything a run reads of its children is read: end them at once
        # (a graceful stop of the server takes 8-14 s of a run's 360)
        exit_codes = stack.stop(hard=True)
        t_stopped = time.monotonic()
        timeline.enter("reduce")
        reduced = _finish_reduce(up.reducer)
        t_reduced = time.monotonic()

    records = got["records"]
    wall_offset = time.time() - time.monotonic()
    before, at_end = sink["at_window_start"], sink["at_window_end"]
    w0, w1 = got["bounds"]["window"]
    window = [r for r in records if r.part == "window"]
    censor = got["bounds"]["drained"][1]
    failed = [r for r in window if not r.ok]
    late = [1000.0 * (r.sent - r.due) for r in records]
    compiles = (client.metric_sum(at_end, "llm_jit_compiles_total")
                - client.metric_sum(before, "llm_jit_compiles_total"))
    correct = bool(check["correct"])
    ctx = Context(cell=cell, bench=bench, records=records, window=(w0, w1),
                  censor_at=censor, setup_s=w0 - t_process_start)
    # every end-to-end reading that has a file, an entry or not: a
    # statistic that repeats too widely to be held to a bound (a first
    # token's time, since PR 45) is still printed in every run
    e2e = {name: manifest.read_metric("end_to_end", name, ctx)
           for name in manifest.metric_files("end_to_end")}
    met = stats.met_both_limits(window, mix["limits"], censor)
    timings = {} if rehearse else {
        # every file of end_to_end/, also those this cell does not
        # report and that decide nothing here (above the knee the tails
        # swing with the smallest change); never printed by a CPU rehearsal
        "end_to_end_all": e2e,
        "met_both_limits_share": met / len(window),
        "setup_phases_s": {"to_ready": t_ready - t_process_start,
                           "warm_up": t_warm - t_ready,
                           "check": t_checked - t_warm,
                           "storms": t_stormed - t_checked,
                           "preroll": w0 - t_stormed},
        "drain_s": censor - w1,
        # where a run's time goes after its window: a run has 360 s
        "after_the_window_s": {
            "to_drained_and_captured": t_offered - w1,
            "profiler_post": (sink["capture_at"][1] - sink["capture_at"][0]
                              if trace else None),
            "stop": t_stopped - t_offered,
            "reduce_trace_after_stop": t_reduced - t_stopped}}
    _say(cell=cell_name, seed=seed, seconds=seconds, rate_rps=rate,
         attempted=len(window), failed=len(failed),
         statuses=sorted({r.status for r in window}),
         first_errors=[r.error for r in failed if r.error][:3],
         generator_lateness_ms={"p50": stats.percentile(late, 50),
                                "p99": stats.percentile(late, 99),
                                "max": max(late)},
         stopped_early=sum(1 for r in window
                           if r.ok and r.tokens < r.max_tokens),
         prompt_token_mismatch=sum(
             1 for r in window if r.usage
             and r.usage.get("prompt_tokens") != r.prompt_tokens),
         output_tokens=sum(r.tokens for r in window),
         compiles_in_window=compiles,
         compiled_after_the_storms=[
             [round(t - wall_offset - w0, 3), name, diff]
             for t, name, diff in compiled if t - wall_offset >= t_stormed],
         waited_503_in_setup=len(waited), attention=attention,
         warm_up=warm, storms=stormed, check=check, child_exit_codes=exit_codes,
         **timings)

    # what ``correct`` compared, each number beside its limit: the last
    # lines of stderr and the last key of the result line, which is all
    # that is kept of a run that is not correct
    compared = setup_steps.compared(check)
    for name, c in compared.items():
        print(f"{timeline.who}: compared {name} {c['value']} limit "
              f"{c['limit']} ({c['better']} passes)", file=sys.stderr)
    sys.stderr.flush()

    if rehearse:
        # counts only: a CPU timing never appears under a metric's name
        print(json.dumps({
            "rehearsal": True, "correct": correct,
            "attempted": len(window), "failed": len(failed),
            "device": device,
            "counts": {"output_tokens": sum(r.tokens for r in window),
                       "compiles_in_window": compiles,
                       "traced_planes": (reduced or {}).get("planes", [])
                       if trace else None},
            "compared": compared}))
        return 0

    mem = client.metric_values(after, "llm_device_memory_bytes",
                               kind="peak_bytes_in_use")
    dev = dict(device, memory_peak_bytes=max(mem) if mem else 0)
    result = {"correct": correct, "attempted": len(window),
              "failed": len(failed), "metrics": {}, "device": dev}
    if not trace:
        for m in manifest.metrics_of(bench, cell_name, "end_to_end"):
            if e2e[m["name"]] is None:
                raise Failed(f"{m['name']} has no samples in this run")
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    else:
        devs = (reduced or {}).get("devices", {})
        if not devs:
            raise Failed("the traced run saw no operation on the device: "
                         f"planes {(reduced or {}).get('planes')}")
        busy = [d["busy_s"] for d in devs.values()]
        cap0, cap1 = sink["capture_at"]
        ctx.__dict__.update(
            before=before, after=at_end, polls=sink["polls"],
            router_polls=sink["router_polls"], spans=sink["spans"],
            trace=reduced, trace_window=(cap0, cap1),
            peaks=PEAKS[device["kind"]])
        for m in manifest.metrics_of(bench, cell_name, "per_layer"):
            value = manifest.read_metric("per_layer", m["name"], ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        dev["busy_s"] = sum(busy) / len(busy)
        dev["window_s"] = traced_window_s(sink["capture"]["duration_s"], devs)
        first = next(iter(devs.values()))
        result["breakdown"] = {"device_ops": first["device_ops"],
                               "idle_gaps": first["idle_gaps"]}
        _say(trace={p: {"lines": d["lines"], "span_s": d["span_s"],
                        "busy_s": d["busy_s"], "modules": d["modules"]}
                    for p, d in devs.items()},
             spans_kept=len(sink["spans"]), polls=len(sink["polls"]))
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0
