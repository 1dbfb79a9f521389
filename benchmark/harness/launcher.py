"""Start the programs a deployment starts, as child processes.

``python -m llms_on_kubernetes_tpu serve ...`` with ``python -m
llms_on_kubernetes_tpu router`` in front, the way chip_smoke.py starts
them (copied here, not imported: the benchmark owns its yardstick). The
parent never imports JAX: a process that has touched JAX holds the chip.
"""

from __future__ import annotations

import os
import re
import signal
import socket
import subprocess
import sys
import time

from . import lifecycle, manifest


class NoResult(Exception):
    """No accelerator, the wrong one, or not a checkout: print no result
    and exit non-zero."""


class Failed(Exception):
    """The system under test did not do what a run needs."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One ``python -m llms_on_kubernetes_tpu ...`` process, its output in
    a log file the parent can read while it runs. The kernel kills it when
    the parent dies (``lifecycle.spawn``); that follows the thread that
    forked, so build a ``Child`` from the main thread only. It leads a
    session of its own, so that ``stop`` can end its helpers with it."""

    def __init__(self, name: str, args: list, env: dict, workdir: str):
        self.name = name
        self.log_path = os.path.join(workdir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = lifecycle.spawn(
            [sys.executable, "-m", "llms_on_kubernetes_tpu", *args],
            cwd=manifest.REPO_DIR, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def log(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def tail(self, n: int = 60) -> str:
        return "\n".join(ln[:300] for ln in self.log().splitlines()[-n:])

    def stop(self, timeout_s: float = 30.0, hard: bool = False) -> int:
        """SIGTERM, wait, then SIGKILL the whole group if it lingers;
        ``hard`` goes straight to SIGKILL. A second call only returns the
        exit code (the pid is reaped by then and may belong to someone
        else)."""
        if self._log.closed:
            return self.proc.returncode
        if self.proc.poll() is None and not hard:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        rc = self.proc.wait()
        self._log.close()
        return rc


def serve_args(config: dict, port: int) -> list:
    """The ``serve`` command line, every flag from the configuration's
    file: ``{"--flag": value}`` in order, ``true`` for a bare flag."""
    args = ["serve", "--model", config["registry_name"]]
    for flag, value in config["serve_flags"].items():
        args.append(flag)
        if value is not True:
            args.append(str(value))
    return args + ["--host", "127.0.0.1", "--port", str(port)]


_DEVICE = re.compile(r"\[serve\] devices: platform=(\S+) "
                     r"device_kind='([^']*)' count=(\d+)")
_COMPILE = re.compile(r"^WARNING:(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3}):"
                      r"jax\._src\.interpreters\.pxla:\d+: "
                      r"Compiling jit\((\w+)\)(.*)$", re.M)
_ARGS = re.compile(r"ShapedArray\(([^)]*)\)|PartitionSpec\(([^)]*)\)"
                   r"|(UnspecifiedValue)")
_ATTN = re.compile(r"\[attention\] op=(\w+) impl=(\S+) why=(.*)")


class Stack:
    """One server behind one router."""

    def __init__(self, config: dict, workdir: str, platform: str,
                 env_extra: dict):
        self.config = config
        self.workdir = workdir
        self.platform = platform
        env = dict(os.environ)
        env.update(env_extra)
        if platform == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
        self.env = env
        self.server_port = free_port()
        self.router_port = free_port()
        self.children: list = []
        self.server = None
        self.router = None
        self.device: dict = {}

    def start(self) -> None:
        if not os.path.isdir(os.path.join(manifest.REPO_DIR,
                                          "llms_on_kubernetes_tpu")):
            raise NoResult("llms_on_kubernetes_tpu/ is not beside "
                           "benchmark/: run from a checkout")
        self.server = Child("server", serve_args(self.config,
                                                 self.server_port),
                            self.env, self.workdir)
        self.children.append(self.server)
        name = self.config["registry_name"]
        self.router = Child(
            "router", ["router", "--backend",
                       f"{name}=http://127.0.0.1:{self.server_port}",
                       "--host", "127.0.0.1", "--port",
                       str(self.router_port)], self.env, self.workdir)
        self.children.append(self.router)

    def wait_device(self, chips: int, known_kinds, give_up: float) -> dict:
        """Block until the server says which device it found; refuse any
        but the platform this run is for."""
        while True:
            m = _DEVICE.search(self.server.log())
            if m:
                self.device = {"platform": m.group(1), "kind": m.group(2),
                               "count": int(m.group(3))}
                break
            if self.server.proc.poll() is not None:
                raise NoResult(
                    f"server exited {self.server.proc.returncode} before "
                    f"reporting a device\n{self.server.tail(20)}")
            if time.monotonic() > give_up:
                raise NoResult("server reported no device in time")
            time.sleep(0.2)
        d = self.device
        if d["platform"] != self.platform:
            raise NoResult(f"JAX found platform={d['platform']!r}, this "
                           f"run needs {self.platform!r}")
        if d["count"] < chips:
            raise NoResult(f"{d['count']} chips, the cell needs {chips}")
        if known_kinds is not None and d["kind"] not in known_kinds:
            raise NoResult(f"device_kind {d['kind']!r} is not in the "
                           f"benchmark's peak table {sorted(known_kinds)}")
        return d

    def check_alive(self) -> None:
        for c in self.children:
            if c.proc.poll() is not None:
                raise Failed(f"{c.name} exited {c.proc.returncode}\n"
                             f"{c.tail(30)}")

    def attention_impl(self) -> dict:
        """op -> every distinct "impl (why)" the dispatchers printed."""
        said: dict = {}
        for m in _ATTN.finditer(self.server.log()):
            entry = f"{m.group(2)} ({m.group(3).strip()})"
            if entry not in said.setdefault(m.group(1), []):
                said[m.group(1)].append(entry)
        return said

    def compile_log(self) -> list:
        """[(wall-clock seconds, jitted function, what differs)] for every
        trace-and-compile the server logged (JAX_LOG_COMPILES), small
        helpers included. ``what differs`` lists the argument shapes and
        sharding annotations that are not those of the function's first
        compile: it names the variant a re-trace was for."""
        out, first = [], {}
        for m in _COMPILE.finditer(self.server.log()):
            at = time.mktime(time.strptime(m.group(1), "%Y-%m-%d %H:%M:%S"))
            sig = ["".join(t) for t in _ARGS.findall(m.group(4))]
            base = first.setdefault(m.group(3), sig)
            diff = [f"{i}: {a!r} -> {b!r}" for i, (a, b)
                    in enumerate(zip(base, sig)) if a != b]
            if len(sig) != len(base):
                diff.append(f"{len(base)} -> {len(sig)} annotations")
            out.append((at + int(m.group(2)) / 1000.0, m.group(3), diff))
        return out

    def stop(self, hard: bool = False) -> dict:
        return {c.name: c.stop(hard=hard) for c in reversed(self.children)}
