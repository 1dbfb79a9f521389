"""Cold-start contract: persistent compile cache + opt-in hardware run.

CI-tier (CPU): the persistent XLA compilation cache that ISSUE 7 mounts
on the weight PVC must actually shorten a warm restart — two fresh
processes share one ``JAX_COMPILATION_CACHE_DIR`` and the second's compile
is measurably faster (cache hit instead of recompilation).

Opt-in hardware run: ``LLMK_TEST_COLDSTART=1 pytest tests/test_cold_start.py
-s`` on a machine with the TPU visible (and no other TPU process). It
measures the reference deployment's cold-start contract: process start →
real safetensors checkpoint (TinyLlama-1.1B architecture/size,
synthesized — zero-egress sandbox; scripts/synth_checkpoint.py) loaded
through the native mmap reader → engine compiled → first completion
served, against the charts' probe budget (readiness 120 s + 30 s × 10
failures = 420 s, mirroring the reference's, reference
model-deployments.yaml:48-63).
"""

import http.client
import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

from conftest import free_port

REPO = pathlib.Path(__file__).resolve().parent.parent

hardware_opt_in = pytest.mark.skipif(
    os.environ.get("LLMK_TEST_COLDSTART") != "1",
    reason="opt-in: LLMK_TEST_COLDSTART=1 (needs exclusive TPU access)")

PROBE_BUDGET_S = 420.0  # readinessProbe: 120s initial + 30s x 10 failures


# ---------------------------------------------------------------------------
# persistent compile cache (CPU, runs in CI)
# ---------------------------------------------------------------------------

def test_compilation_cache_rule(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX keeps its cache there and the
    program updates no directory in code. Unset: <checkout>/.jax_cache, a
    fixed path."""
    import jax

    from llms_on_kubernetes_tpu.cli import configure_compilation_cache

    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (updates.append(name), real_update(name, val)))
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compilation_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert configure_compilation_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        real_update("jax_compilation_cache_dir", before)


# compile something expensive enough that a recompile-vs-cache-hit gap
# dominates interpreter startup noise, then report just the compile time
_COMPILE_SNIPPET = """
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from llms_on_kubernetes_tpu.cli import configure_compilation_cache
d = configure_compilation_cache()
import jax
import jax.numpy as jnp
assert d == jax.config.jax_compilation_cache_dir \
    == os.environ["JAX_COMPILATION_CACHE_DIR"], d

@jax.jit
def f(x):
    # unrolled on purpose: a scan body compiles ONCE and stays too cheap
    # for the cache-hit gap to beat timing noise; 32 distinct steps give
    # XLA a big enough HLO graph that recompiling visibly costs
    for i in range(32):
        x = jnp.tanh(x @ x) * (0.1 * i + 1.0) + jnp.sin(x)
    return x

x = jnp.ones((128, 128), jnp.float32)
t0 = time.perf_counter()
f(x).block_until_ready()
print("COMPILE_S", time.perf_counter() - t0)
"""


def _compile_once(cache_dir: str) -> float:
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    # the forced 8-device host platform is irrelevant here; keep the
    # subprocess a plain single-device CPU like a real serving pod
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _COMPILE_SNIPPET], env=env,
                         cwd=str(REPO), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    for line in out.stdout.splitlines():
        if line.startswith("COMPILE_S"):
            return float(line.split()[1])
    raise AssertionError(f"no COMPILE_S line in:\n{out.stdout}")


def test_warm_restart_compiles_faster_than_cold(tmp_path):
    """ISSUE 7 acceptance: with the persistent cache configured, a warm
    restart (second process, same cache dir) must be measurably faster
    than the cold one — the cache actually persists across processes."""
    cache = str(tmp_path / "xla-cache")
    cold_s = _compile_once(cache)
    entries = [p for p in pathlib.Path(cache).rglob("*") if p.is_file()]
    assert entries, "cold run wrote nothing to the compilation cache"
    warm_s = _compile_once(cache)
    # a cache hit skips XLA optimization; "measurably" = at least 40%
    # off (in practice it is >90%), far outside CPU timing jitter
    assert warm_s < cold_s * 0.6, (
        f"warm restart not faster: cold={cold_s:.3f}s warm={warm_s:.3f}s")


def _serve_once(ckpt: str, label: str) -> dict:
    port = free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, "-m", "llms_on_kubernetes_tpu", "serve",
         "--model", ckpt, "--port", str(port), "--host", "127.0.0.1",
         "--max-decode-slots", "8", "--num-pages", "512",
         "--prefill-buckets", "256"],
        env=env, cwd=str(REPO),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    ready_at = first_completion_at = None
    try:
        while time.monotonic() - t0 < PROBE_BUDGET_S:
            if proc.poll() is not None:
                out = proc.stdout.read()
                raise AssertionError(f"server died:\n{out[-3000:]}")
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
                conn.request("GET", "/health")
                if conn.getresponse().status == 200:
                    ready_at = time.monotonic() - t0
                    conn.close()
                    break
            except OSError:
                time.sleep(1.0)
        assert ready_at is not None, "server never became ready in budget"
        # first completion: includes the prefill+decode compiles
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        conn.request("POST", "/v1/completions", json.dumps({
            "model": "m", "prompt": "hello", "max_tokens": 4,
            "temperature": 0}), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()[:500]
        resp.read()
        first_completion_at = time.monotonic() - t0
        conn.close()
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
    stats = {"label": label, "ready_s": round(ready_at, 1),
             "first_completion_s": round(first_completion_at, 1)}
    print(f"\ncold-start [{label}]: {json.dumps(stats)}")
    return stats


@hardware_opt_in
def test_real_checkpoint_cold_start_within_probe_budget(tmp_path):
    sys.path.insert(0, str(REPO / "scripts"))
    from synth_checkpoint import synthesize

    ckpt = os.environ.get("LLMK_COLDSTART_CKPT", "/tmp/tinyllama-synth")
    t0 = time.monotonic()
    synthesize(ckpt)
    print(f"\ncheckpoint ready in {time.monotonic() - t0:.1f}s at {ckpt}")

    cold = _serve_once(ckpt, "cold")
    assert cold["first_completion_s"] < PROBE_BUDGET_S
    # warm restart: OS page cache holds the checkpoint bytes AND the
    # persistent compilation cache (cli.configure_compilation_cache, on
    # the weight PVC in-cluster) skips the XLA compiles
    warm = _serve_once(ckpt, "warm")
    assert warm["first_completion_s"] < PROBE_BUDGET_S
    assert warm["first_completion_s"] <= cold["first_completion_s"]
