"""On-demand, bounded-duration profile captures for a serving process.

``POST /debug/profile`` must work against a *live* engine without
restarting it (the whole point: you profile the replica that is
misbehaving under production traffic, not a fresh one). This module owns
the capture lifecycle so the API layer stays a thin HTTP shim:

- One capture at a time per process (the JAX profiler is a process-global
  singleton; concurrent captures corrupt each other) — a second POST
  while one runs gets a 409 from the server.
- Durations are clamped to ``[0.05s, LLMK_PROFILE_MAX_S]`` (default 30s)
  so a fat-fingered ``duration_ms`` can't leave the profiler running for
  an hour on a production replica.
- Captures land in ``LLMK_PROFILE_DIR`` (default ``/tmp/llmk-profile``)
  under an opaque ``cap-<n>-<stamp>`` directory; ``list_captures()``
  enumerates them and ``open_archive()`` streams one back as a .tar.gz
  built with stdlib tarfile (no shelling out on a serving pod).
- When ``jax.profiler`` is unavailable (stripped build, or the trace
  fails to start), a pure-Python sampling profiler over
  ``sys._current_frames()`` captures aggregated host stacks instead —
  strictly worse than an XLA trace but enough to find a host-side stall.
"""

from __future__ import annotations

import collections
import io
import json
import os
import re
import sys
import tarfile
import threading
import time
import traceback

_CAPTURE_ID_RE = re.compile(r"^cap-[0-9]+-[0-9]+$")
_SAMPLE_INTERVAL_S = 0.005


def _base_dir() -> str:
    return os.environ.get("LLMK_PROFILE_DIR", "/tmp/llmk-profile")


def _max_duration_s() -> float:
    try:
        return float(os.environ.get("LLMK_PROFILE_MAX_S", "30"))
    except ValueError:
        return 30.0


def _dir_listing(path: str) -> list[dict]:
    """[{name, bytes}] for every regular file under path (relative names)."""
    out = []
    for root, _dirs, files in os.walk(path):
        for f in sorted(files):
            full = os.path.join(root, f)
            try:
                size = os.path.getsize(full)
            except OSError:
                continue
            out.append({"name": os.path.relpath(full, path), "bytes": size})
    return out


class _SamplingProfiler:
    """Host-stack sampler: periodically snapshots every thread's stack via
    sys._current_frames() and aggregates identical stacks with counts.
    The output (stacks.json) is a flat list sorted by sample count — the
    top entry is where the process was actually spending its time."""

    def __init__(self) -> None:
        self._counts: collections.Counter = collections.Counter()
        self._samples = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="llmk-prof-sampler", daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        me = threading.get_ident()
        while not self._stop.is_set():
            for tid, frame in sys._current_frames().items():
                if tid == me:
                    continue
                stack = tuple(
                    f"{fr.filename}:{fr.lineno}:{fr.name}"
                    for fr in traceback.extract_stack(frame))
                self._counts[stack] += 1
            self._samples += 1
            self._stop.wait(_SAMPLE_INTERVAL_S)

    def stop_and_dump(self, out_dir: str) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
        stacks = [
            {"count": n, "frames": list(stack)}
            for stack, n in self._counts.most_common()
        ]
        payload = {
            "kind": "py-sampling-profile",
            "samples": self._samples,
            "interval_s": _SAMPLE_INTERVAL_S,
            "stacks": stacks,
        }
        with open(os.path.join(out_dir, "stacks.json"), "w") as f:
            json.dump(payload, f, indent=1)


class ProfileManager:
    """Capture lifecycle + capture-directory catalogue for one process."""

    def __init__(self, base_dir: str | None = None):
        self.base_dir = base_dir or _base_dir()
        self._lock = threading.Lock()
        self._busy = False
        self._seq = 0

    @property
    def busy(self) -> bool:
        return self._busy

    def capture(self, duration_ms: float) -> dict:
        """Run one bounded capture, blocking for its duration.

        The API server runs this off the event loop (thread executor) so
        streams keep flowing while the profiler samples them — that
        concurrent traffic is exactly what the capture is for.

        Raises RuntimeError("busy") if a capture is already running.
        """
        with self._lock:
            if self._busy:
                raise RuntimeError("busy")
            self._busy = True
            self._seq += 1
            seq = self._seq
        try:
            duration_s = max(0.05, min(duration_ms / 1000.0,
                                       _max_duration_s()))
            cap_id = f"cap-{seq}-{int(time.time())}"
            out_dir = os.path.join(self.base_dir, cap_id)
            os.makedirs(out_dir, exist_ok=True)
            source = self._run_capture(out_dir, duration_s)
            meta = {
                "id": cap_id,
                "source": source,
                "duration_s": duration_s,
                "created": time.time(),
            }
            with open(os.path.join(out_dir, "capture.json"), "w") as f:
                json.dump(meta, f, indent=1)
            return dict(meta, files=_dir_listing(out_dir))
        finally:
            with self._lock:
                self._busy = False

    def _run_capture(self, out_dir: str, duration_s: float) -> str:
        """jax.profiler trace if it starts, else the sampling fallback.
        Returns the source tag recorded in capture.json."""
        try:
            import jax.profiler as jprof

            # the device's lines and the host's TraceMe events (the
            # engine's llmk.* phases among them), and not the python
            # tracer: nothing reads its frames, and its stop alone took
            # ~50 s of a capture on the CPU. (On the chip the stop is the
            # device's own events — 400k ops a 1.5 s capture of a 7B model
            # — and takes a minute with or without it, or the HLO protos.)
            opts = jprof.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jprof.start_trace(out_dir, profiler_options=opts)
        except Exception:
            sampler = _SamplingProfiler()
            sampler.start()
            time.sleep(duration_s)
            sampler.stop_and_dump(out_dir)
            return "py-sampler"
        try:
            time.sleep(duration_s)
        finally:
            try:
                jprof.stop_trace()
            except Exception:
                pass
        return "jax-profiler"

    # -- catalogue ------------------------------------------------------

    def list_captures(self) -> list[dict]:
        """All completed captures under base_dir, newest first."""
        out = []
        try:
            entries = sorted(os.listdir(self.base_dir))
        except OSError:
            return []
        for name in entries:
            if not _CAPTURE_ID_RE.match(name):
                continue
            path = os.path.join(self.base_dir, name)
            meta_path = os.path.join(path, "capture.json")
            meta = {"id": name}
            try:
                with open(meta_path) as f:
                    meta.update(json.load(f))
            except (OSError, ValueError):
                continue  # in-flight or mangled capture: not listable yet
            files = _dir_listing(path)
            meta["files"] = files
            meta["bytes"] = sum(f["bytes"] for f in files)
            out.append(meta)
        out.sort(key=lambda m: m.get("created", 0), reverse=True)
        return out

    def open_archive(self, capture_id: str) -> bytes | None:
        """The capture directory as .tar.gz bytes, or None if no such
        capture. The id is validated against the strict cap-N-STAMP shape
        (never joined raw into a path) so ../ traversal is impossible."""
        if not _CAPTURE_ID_RE.match(capture_id):
            return None
        path = os.path.join(self.base_dir, capture_id)
        if not os.path.isdir(path):
            return None
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w:gz") as tar:
            tar.add(path, arcname=capture_id)
        return buf.getvalue()
