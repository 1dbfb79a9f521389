#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path still starts on
the chip. Not a benchmark: it reports no rate and no latency.

    python chip_smoke.py              # one TPU chip, mistral-7b int8
    python chip_smoke.py --chips 4    # the same server with --tp 4: each
                                      # device must hold its share, no more
    python chip_smoke.py --rehearse   # debug-tiny on the CPU (tier-1)

It starts the programs a deployment starts — ``python -m
llms_on_kubernetes_tpu serve`` with the chart's default gateway ``python -m
llms_on_kubernetes_tpu router`` in front — as child processes, and talks to
them over HTTP with the standard library: client -> router -> server ->
engine. This parent never imports JAX (a process that has touched JAX holds
the chip, and a child that needs it then fails or hangs).

The model is mistral-7b at full depth (32 layers) and published widths,
random int8 weights from a seed, behind a cache a deployment would use:
page 64 x 64 pages = 4096-token slots (also Mistral's window), 12 slots,
769 pages (about 7.5 GB of weights + 6.4 GB of pool on a 16 GB chip).
Everything else is what the CLI defaults to.

Exit 0 only when every step passed on a TPU (or, under --rehearse, on the
CPU, with the TPU-only checks skipped by that flag and nothing else).
Stdout is two lines of JSON: the report (flags, per-step pass/fail,
attention implementations, cold-start phases, bytes in use), then the
verdict, ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": 1}}`` with the device as the server's JAX reported it — a
rehearsal's verdict also carries ``"rehearsal": true``. Without an
accelerator, or outside a checkout, it prints no result and exits non-zero.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1150.0          # the driver allows 1200 s, compilation included
PATIENCE_S = 60.0          # how long a request through the router waits
                           # out 503 + Retry-After before it is a failure

# mistralai/Mistral-7B-v0.1 as published (configs.py "mistral-7b"); only
# used to know how many bytes the server should be holding
MISTRAL_7B = dict(layers=32, hidden=4096, ffn=14336, heads=32, kv_heads=8,
                  head_dim=128, vocab=32000)
CACHE = dict(page_size=64, pages_per_slot=64, slots=12, num_pages=769,
             buckets="256,1024")
V5E_PEAK = "197TFLOP/s,819GB/s"   # engine/ledger.py's row for "TPU v5 lite"


class Failed(Exception):
    """A step's check did not hold."""


class NoResult(Exception):
    """No accelerator / not a checkout: print no result, exit non-zero."""


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One ``python -m llms_on_kubernetes_tpu ...`` process, its output in
    a log file this parent can read while it runs."""

    def __init__(self, name: str, args: list, env: dict, workdir: str):
        self.name = name
        self.log_path = os.path.join(workdir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "llms_on_kubernetes_tpu", *args],
            cwd=HERE, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)

    def log(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def tail(self, n: int = 80) -> str:
        return "\n".join(ln[:300] for ln in self.log().splitlines()[-n:])

    def stop(self, timeout_s: float = 60.0) -> int:
        """SIGTERM, wait, SIGKILL the whole process group if it lingers."""
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        rc = self.proc.wait()
        self._log.close()
        return rc


# --------------------------------------------------------------------------
# HTTP (standard library only)
# --------------------------------------------------------------------------

RETRIED_503: list = []      # one entry per 503 this client waited out


def open_200(port: int, method: str, path: str, body, timeout: float,
             patience_s: float = 0.0):
    """Send one request and return (connection, response) once the status
    is 200. A 503 that carries Retry-After is the router saying "not now"
    (its 2 s health probe can time out against a server busy compiling,
    which ejects the only replica until the next probe): a client waits
    as told and asks again, for at most ``patience_s``; each wait is
    counted in the report."""
    give_up = time.monotonic() + patience_s
    while True:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        try:
            conn.request(method, path,
                         None if body is None else json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status == 200:
                return conn, resp
            raw = resp.read()
            wait = resp.getheader("Retry-After")
        except BaseException:
            conn.close()
            raise
        conn.close()
        if not (resp.status == 503 and wait
                and time.monotonic() + float(wait) < give_up):
            raise Failed(f"{method} {path} -> {resp.status}: {raw[:300]!r}")
        RETRIED_503.append(path)
        time.sleep(float(wait))


def http_json(port: int, method: str, path: str, body=None,
              timeout: float = 60.0, patience_s: float = 0.0):
    conn, resp = open_200(port, method, path, body, timeout, patience_s)
    try:
        return json.loads(resp.read())
    finally:
        conn.close()


def stream_completion(port: int, body: dict, timeout: float,
                      started: "threading.Event | None" = None,
                      hang_up: "threading.Event | None" = None) -> dict:
    """POST a streaming /v1/completions and read the SSE body to its end
    (or until ``hang_up`` is set: a client that disconnects mid-stream).
    Returns the count of data chunks, the finish reasons seen, and whether
    the stream closed with ``data: [DONE]``."""
    out = {"chunks": 0, "finish_reasons": [], "done": 0, "after_done": 0}
    conn, resp = open_200(port, "POST", "/v1/completions",
                          dict(body, stream=True), timeout, PATIENCE_S)
    try:
        for raw in resp:
            if hang_up is not None and hang_up.is_set():
                break
            line = raw.decode("utf-8", "replace").strip()
            if not line.startswith("data:"):
                continue            # comments (": ping"), blank separators
            data = line[5:].strip()
            if data == "[DONE]":
                out["done"] += 1
                continue
            if out["done"]:
                out["after_done"] += 1
            doc = json.loads(data)
            if "error" in doc:
                raise Failed(f"stream error frame: {data[:300]}")
            out["chunks"] += 1
            if started is not None and out["chunks"] >= 2:
                started.set()       # first token came from the prefill
            for ch in doc.get("choices", ()):
                if ch.get("finish_reason"):
                    out["finish_reasons"].append(ch["finish_reason"])
    finally:
        conn.close()
    return out


_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def scrape(port: int) -> list:
    """The server's /metrics as [(name, {label: value}, float)]."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    out = []
    for line in text.splitlines():
        m = None if line.startswith("#") else _SAMPLE.match(line)
        if m:
            out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")),
                        float(m.group(3))))
    return out


def metric(samples: list, name: str, **labels) -> list:
    return [v for n, lab, v in samples if n == name
            and all(lab.get(k) == want for k, want in labels.items())]


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

class Smoke:
    def __init__(self, rehearse: bool, chips: int):
        self.rehearse = rehearse
        self.chips = chips
        self.t0 = time.monotonic()
        self.model = "debug-tiny" if rehearse else "mistral-7b"
        self.workdir = tempfile.mkdtemp(prefix="chip-smoke-")
        self.children: list[Child] = []
        self.server: "Child | None" = None
        self.server_port = self.router_port = 0
        self.steps: dict = {}
        self.summary: dict = {
            "ok": False, "model": self.model, "chips": chips,
            "cache": CACHE, "steps": self.steps,
        }
        if rehearse:
            self.summary["rehearsal"] = True
        else:
            self.summary["widths"] = MISTRAL_7B

    def left(self) -> float:
        return BUDGET_S - (time.monotonic() - self.t0)

    # -- processes -----------------------------------------------------

    def child_env(self) -> dict:
        env = dict(os.environ)
        env["LLMK_PROFILE_DIR"] = os.path.join(self.workdir, "profiles")
        if self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        return env

    def serve_flags(self) -> list:
        return [
            "--model", self.model, "--random-weights",
            "--quantization", "int8",
            "--max-decode-slots", str(CACHE["slots"]),
            "--num-pages", str(CACHE["num_pages"]),
            "--page-size", str(CACHE["page_size"]),
            "--pages-per-slot", str(CACHE["pages_per_slot"]),
            "--prefill-buckets", CACHE["buckets"],
            "--tp", str(self.chips),
        ]

    def start_server(self, name: str) -> dict:
        """Start ``serve``, wait for /ready; returns what its start-up
        lines said. Fails fast when the device it reports is not the one
        this run is for."""
        # a restart takes the port the router already points at
        self.server_port = self.server_port or free_port()
        self.server = Child(
            name, ["serve", *self.serve_flags(), "--host", "127.0.0.1",
                   "--port", str(self.server_port)],
            self.child_env(), self.workdir)
        self.children.append(self.server)
        info: dict = {}
        while True:
            if not info:
                m = re.search(r"\[serve\] devices: platform=(\S+) "
                              r"device_kind='([^']*)' count=(\d+)",
                              self.server.log())
                if m:
                    info = {"platform": m.group(1),
                            "device_kind": m.group(2),
                            "n_devices": int(m.group(3))}
                    want = "cpu" if self.rehearse else "tpu"
                    if info["platform"] != want:
                        raise NoResult(
                            f"JAX found platform={info['platform']!r}, "
                            f"this run needs {want!r}")
            if self.server.proc.poll() is not None:
                if not info:
                    raise NoResult(
                        f"{name} exited {self.server.proc.returncode} "
                        f"before reporting a device")
                raise Failed(f"{name} exited "
                             f"{self.server.proc.returncode} before /ready")
            try:
                http_json(self.server_port, "GET", "/ready", timeout=5)
                break
            except (OSError, Failed):
                pass
            if self.left() <= 0:
                raise Failed(f"{name} not ready within the time budget")
            time.sleep(1.0)
        m = re.search(r"ledger_peak=(\S+)", self.server.log())
        info["ledger_peak"] = m.group(1) if m else None
        return info

    def start_router(self) -> None:
        self.router_port = free_port()
        router = Child(
            "router", ["router", "--backend",
                       f"{self.model}=http://127.0.0.1:{self.server_port}",
                       "--host", "127.0.0.1",
                       "--port", str(self.router_port)],
            self.child_env(), self.workdir)
        self.children.append(router)
        while True:
            if router.proc.poll() is not None:
                raise Failed(f"router exited {router.proc.returncode}")
            try:
                http_json(self.router_port, "GET", "/v1/models", timeout=5)
                return
            except (OSError, Failed):
                pass
            if self.left() <= 0:
                raise Failed("router not up within the time budget")
            time.sleep(0.5)

    def cleanup(self) -> None:
        for c in self.children:
            c.stop(timeout_s=10.0)
        shutil.rmtree(self.workdir, ignore_errors=True)

    # -- requests ------------------------------------------------------

    def completion(self, prompt: str, **kw) -> dict:
        return http_json(
            self.router_port, "POST", "/v1/completions",
            dict(model=self.model, prompt=prompt, temperature=0, **kw),
            timeout=max(30.0, min(280.0, self.left())),
            patience_s=PATIENCE_S)

    def prompt(self, n: int, tag: int) -> str:
        """About n tokens (random weights serve the byte tokenizer: one
        token per character). The tag comes first so that no two prompts
        share a cached prefix page."""
        words = f"{tag:02d} the quick brown fox jumps over the lazy dog "
        return (words * (n // len(words) + 1))[:n]

    # -- steps ---------------------------------------------------------

    def step_models(self) -> None:
        doc = http_json(self.router_port, "GET", "/v1/models")
        ids = [m.get("id") for m in doc.get("data", ())]
        if self.model not in ids:
            raise Failed(f"/v1/models lists {ids}, not {self.model!r}")

    def step_chat(self) -> None:
        doc = http_json(
            self.router_port, "POST", "/v1/chat/completions",
            dict(model=self.model, max_tokens=16, temperature=0,
                 messages=[{"role": "user", "content": "Say hello."}]),
            timeout=max(30.0, min(280.0, self.left())),
            patience_s=PATIENCE_S)
        choice = doc["choices"][0]
        if choice.get("finish_reason") not in ("stop", "length"):
            raise Failed(f"chat finish_reason {choice.get('finish_reason')!r}")
        if not 1 <= doc["usage"]["completion_tokens"] <= 16:
            raise Failed(f"chat usage {doc['usage']}")

    def step_streams(self, lengths: list) -> None:
        """Concurrent streaming completions: every one ends in exactly one
        finish_reason and one [DONE], with nothing after it."""
        results: dict = {}

        def run(i: int, n: int) -> None:
            try:
                results[i] = stream_completion(
                    self.router_port,
                    dict(model=self.model, prompt=self.prompt(n, i),
                         max_tokens=64, temperature=0),
                    timeout=max(30.0, self.left()))
            except Exception as e:  # noqa: BLE001 — reported below
                results[i] = e

        threads = [threading.Thread(target=run, args=(i, n), daemon=True)
                   for i, n in enumerate(lengths)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(1.0, self.left()))
        for i, n in enumerate(lengths):
            r = results.get(i)
            if not isinstance(r, dict):
                raise Failed(f"stream {i} ({n} tokens in): {r!r}")
            if (len(r["finish_reasons"]) != 1 or r["done"] != 1
                    or r["after_done"] or r["chunks"] < 2):
                raise Failed(f"stream {i} ({n} tokens in) ended badly: {r}")

    def step_repeat(self) -> list:
        """The same greedy request twice in a row: identical tokens. The
        byte tokenizer cannot spell most of a 32000-token vocabulary, so
        the per-token logprobs stand in for the token ids — two runs that
        chose the same tokens on the same path produce the same floats."""
        body = dict(max_tokens=32, logprobs=1)
        a = self.completion(self.prompt(20, 90), **body)["choices"][0]
        b = self.completion(self.prompt(20, 90), **body)["choices"][0]
        la = a["logprobs"]["token_logprobs"]
        if (a["text"] != b["text"] or la != b["logprobs"]["token_logprobs"]
                or a["logprobs"]["tokens"] != b["logprobs"]["tokens"]):
            raise Failed(f"greedy repeat differs: {la[:8]} vs "
                         f"{b['logprobs']['token_logprobs'][:8]}")
        if len(la) < 8:
            raise Failed(f"greedy repeat returned {len(la)} tokens")
        return [round(x, 3) for x in la[:8]]

    def step_logprobs(self) -> None:
        lp = self.completion(self.prompt(20, 91), max_tokens=16,
                             logprobs=3)["choices"][0]["logprobs"]
        vals = list(lp["token_logprobs"])
        for alt in lp["top_logprobs"]:
            vals.extend(alt.values())
        if not vals or not all(
                isinstance(v, float) and math.isfinite(v) and v <= 1e-6
                for v in vals):
            raise Failed(f"logprobs not finite log-probabilities: {vals[:8]}")

    def step_profile(self) -> dict:
        """One on-demand capture while a stream is decoding must be a JAX
        profiler trace with a non-empty .xplane.pb — only the process that
        holds the chip can trace it, and the server would otherwise hand
        back a Python sampling profile without complaint."""
        started, hang_up = threading.Event(), threading.Event()
        box: dict = {}

        def run() -> None:
            try:
                # as long as a slot allows; hung up on once the capture
                # is back
                box["r"] = stream_completion(
                    self.router_port,
                    dict(model=self.model, prompt=self.prompt(20, 92),
                         max_tokens=4000, temperature=0),
                    timeout=max(30.0, self.left()), started=started,
                    hang_up=hang_up)
            except Exception as e:  # noqa: BLE001 — reported below
                box["r"] = e

        t = threading.Thread(target=run, daemon=True)
        t.start()
        if not started.wait(timeout=max(1.0, min(120.0, self.left()))):
            raise Failed(f"the stream to profile never started: {box}")
        cap = None
        for _ in range(20):     # 409 while an automatic capture is running
            try:
                cap = http_json(self.server_port, "POST", "/debug/profile",
                                {"duration_ms": 500}, timeout=120)
                break
            except Failed as e:
                if "409" not in str(e):
                    raise
                time.sleep(1.0)
        still_decoding = t.is_alive()
        hang_up.set()
        t.join(timeout=max(1.0, min(60.0, self.left())))
        if cap is None:
            raise Failed("profiler stayed busy")
        planes = [f for f in cap.get("files", ())
                  if f["name"].endswith(".xplane.pb") and f["bytes"] > 0]
        if cap.get("source") != "jax-profiler" or not planes:
            raise Failed(f"capture is not a JAX profiler trace: {cap}")
        if not still_decoding:
            raise Failed("the stream ended before the capture did")
        if not isinstance(box.get("r"), dict):
            raise Failed(f"profiled stream failed: {box.get('r')!r}")
        return {"source": cap["source"],
                "xplane_bytes": sum(f["bytes"] for f in planes)}

    def step_device(self, info: dict) -> dict:
        """Proof it was the chip, from the server's own /metrics."""
        samples = scrape(self.server_port)
        backend = {lab.get("backend") for n, lab, _ in samples
                   if n == "llm_build_info"}
        in_use = {lab["device"]: v for n, lab, v in samples
                  if n == "llm_device_memory_bytes"
                  and lab.get("kind") == "bytes_in_use"}
        out = {"backend": sorted(backend), "bytes_in_use": in_use}
        if self.rehearse:
            if backend != {"cpu"}:
                raise Failed(f"llm_build_info backend {backend}")
            return out
        if backend != {"tpu"}:
            raise Failed(f"llm_build_info backend {backend}, want tpu")
        if len(in_use) != info["n_devices"] or info["n_devices"] != self.chips:
            raise Failed(f"bytes_in_use for {len(in_use)} devices, server "
                         f"has {info['n_devices']}, asked for {self.chips}")
        m = MISTRAL_7B
        kv_tok = 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * 2
        if metric(samples, "llm_kv_bytes_per_token") != [float(kv_tok)]:
            raise Failed("llm_kv_bytes_per_token is not mistral-7b's "
                         f"{kv_tok} at full depth and bf16")
        pool = kv_tok * CACHE["num_pages"] * CACHE["page_size"]
        attn = m["hidden"] * m["head_dim"] * 2 * (m["heads"] + m["kv_heads"])
        weights = (m["layers"] * (attn + 3 * m["hidden"] * m["ffn"])  # int8
                   + 2 * m["vocab"] * m["hidden"] * 2)   # bf16 embed + head
        out["expected_bytes"] = {"weights": weights, "pool": pool}
        # every device holds its shard of both (0.97: norms, scales and
        # the embedding's replication are not worth modelling)
        floor = 0.97 * (weights + pool) / self.chips
        if min(in_use.values()) < floor:
            raise Failed(f"a device holds {min(in_use.values()):.3e} B, "
                         f"less than weights+pool share {floor:.3e}")
        if max(in_use.values()) > 1.25 * min(in_use.values()):
            raise Failed(f"devices unbalanced: {in_use}")
        if (info["device_kind"] == "TPU v5 lite"
                and info["ledger_peak"] != V5E_PEAK):
            raise Failed(f"ledger peak {info['ledger_peak']} on a v5e, "
                         f"want {V5E_PEAK}")
        return out

    def attention_impl(self) -> dict:
        """What the dispatchers in ops/attention.py said they took, from
        the server's log: op -> every distinct "impl (why)" it printed."""
        said: dict = {}
        for m in re.finditer(r"\[attention\] op=(\w+) impl=(\S+) why=(.*)",
                             self.server.log()):
            said.setdefault(m.group(1), [])
            entry = f"{m.group(2)} ({m.group(3).strip()})"
            if entry not in said[m.group(1)]:
                said[m.group(1)].append(entry)
        want = "xla" if self.rehearse else "pallas-compiled"
        for op in ("prefill", "decode"):
            got = said.get(op, [])
            if not got or not all(g.startswith(want + " ") for g in got):
                raise Failed(f"{op} attention traced {got}, want only {want}")
        return said

    def cold_start(self) -> dict:
        samples = scrape(self.server_port)
        return {
            "phase_s": {lab["phase"]: round(v, 1) for n, lab, v in samples
                        if n == "llm_cold_start_seconds_sum"},
            "jit_cache_hits": sum(metric(samples,
                                         "llm_jit_cache_hits_total")),
            "jit_compiles": sum(metric(samples, "llm_jit_compiles_total")),
        }

    def step_restart(self, first: dict) -> dict:
        """SIGTERM the server, start it again with the same flags, send
        one request: the drained server released the chip, a second
        process could take it, and it found the first one's executables
        in the persistent compile cache."""
        rc = self.server.stop(timeout_s=max(10.0, min(120.0, self.left())))
        if rc not in (0, -signal.SIGTERM):
            raise Failed(f"server exited {rc} on SIGTERM")
        self.children.remove(self.server)
        self.start_server("server2")
        # waits out 503s until the router's prober readmits the replica
        self.completion(self.prompt(20, 93), max_tokens=4)
        second = self.cold_start()
        if second["jit_cache_hits"] <= 0:
            raise Failed(f"no persistent-cache hit on the second start: "
                         f"{second}")
        c1 = first["phase_s"].get("compile")
        c2 = second["phase_s"].get("compile")
        # a first start that itself hit a warm cache is no slower
        if first["jit_cache_hits"] == 0 and not (c1 and c2 and c2 < c1):
            raise Failed(f"compile phase {c2}s after {c1}s: not shorter")
        return second

    # -- driver --------------------------------------------------------

    def run(self) -> None:
        do = self.do
        info = do("start", lambda: self.start_server("server"))
        self.summary.update(
            platform=info["platform"], device_kind=info["device_kind"],
            n_devices=info["n_devices"], ledger_peak=info["ledger_peak"],
            flags=" ".join(["serve", *self.serve_flags()]))
        do("router", self.start_router)
        do("models", self.step_models)
        do("chat", self.step_chat)
        if self.chips == 1:
            # both buckets, the chunked path (1500 > the largest bucket)
            # and a multi-row decode batch compile and run
            do("streams", lambda: self.step_streams(
                [20, 600, 1500, 20, 600, 1500, 20, 600]))
        else:
            do("streams", lambda: self.step_streams([20, 20, 20, 20]))
        self.summary["repeat_logprobs_first8"] = do("repeat",
                                                    self.step_repeat)
        do("logprobs", self.step_logprobs)
        self.summary["profile"] = do("profile", self.step_profile)
        self.summary["device_memory"] = do(
            "device", lambda: self.step_device(info))
        self.summary["attention_impl"] = do("attention", self.attention_impl)
        first = self.cold_start()
        self.summary["cold_start"] = {"first": first}
        if self.chips > 1:
            # one restart is proof enough; four chips cost four times
            self.steps["restart"] = "skipped (--chips > 1)"
        else:
            self.summary["cold_start"]["second"] = do(
                "restart", lambda: self.step_restart(first))
        self.summary["ok"] = all(
            v == "pass" or v.startswith("skipped")
            for v in self.steps.values())

    def do(self, name: str, fn):
        try:
            out = fn()
        except (Failed, NoResult):
            self.steps[name] = "fail"
            raise
        except Exception as e:
            self.steps[name] = "fail"
            raise Failed(f"{type(e).__name__}: {e}") from e
        self.steps[name] = "pass"
        return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="debug-tiny on the CPU: same script, TPU-only "
                         "checks skipped; never a chip result")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="serve with --tp N on an N-chip host")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(HERE, "llms_on_kubernetes_tpu")):
        print("chip_smoke: llms_on_kubernetes_tpu/ is not beside this "
              "script; run it from a checkout", file=sys.stderr)
        return 2

    smoke = Smoke(args.rehearse, 1 if args.rehearse else args.chips)
    try:
        smoke.run()
    except NoResult as e:
        print(f"chip_smoke: no result: {e}", file=sys.stderr)
        if smoke.server is not None:
            print(smoke.server.tail(), file=sys.stderr)
        return 2
    except Failed as e:
        smoke.summary["error"] = str(e)[:600]
        for c in smoke.children:
            print(f"--- last lines of {c.name} ---\n{c.tail()}",
                  file=sys.stderr)
    finally:
        smoke.cleanup()
    summary = smoke.summary
    summary["retried_503"] = len(RETRIED_503)
    print(json.dumps(summary))
    if "platform" not in summary:   # it failed before the server was up
        return 1
    verdict = {"ok": summary["ok"],
               "device": {"platform": summary["platform"],
                          "kind": summary["device_kind"],
                          "count": summary["n_devices"]}}
    if args.rehearse:
        # a rehearsal never reads as a chip result, whatever passed
        verdict["rehearsal"] = True
    assert not verdict["ok"] or args.rehearse \
        or verdict["device"]["platform"] == "tpu"
    print(json.dumps(verdict))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
