"""chip_smoke.py on the CPU: ``--rehearse`` drives the real entry points
(serve + router as child processes, HTTP from outside) with debug-tiny, so
the script that proves the chip is itself proven before chip time is spent
on it — and it can never pass for a chip result."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
SMOKE = str(REPO / "chip_smoke.py")


def _run(args, cwd=REPO, **env):
    e = dict(os.environ, **env)
    e.pop("XLA_FLAGS", None)     # children are plain one-device processes
    e.pop("LLMK_TEST_TPU", None)
    return subprocess.run([sys.executable, SMOKE, *args], cwd=str(cwd),
                          env=e, capture_output=True, text=True, timeout=600)


@pytest.mark.e2e
def test_rehearsal_passes_and_says_it_is_one():
    out = _run(["--rehearse"])
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert len(lines) == 2              # the report, then the verdict
    # the last line is the driver's contract: exactly "ok" and "device"
    # (platform and kind text, count a whole number) — plus, for a
    # rehearsal and nothing else, the key that says it is one
    verdict = json.loads(lines[-1])
    assert verdict == {"ok": True, "rehearsal": True,
                       "device": verdict["device"]}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    doc = json.loads(lines[0])
    assert doc["ok"] is True and doc["rehearsal"] is True
    assert doc["platform"] == "cpu"
    assert {"model", "flags", "steps", "attention_impl", "cold_start",
            "device_memory", "device_kind", "n_devices",
            "retried_503"} <= set(doc)
    assert set(doc["steps"].values()) == {"pass"}
    assert {"start", "router", "models", "chat", "streams", "repeat",
            "logprobs", "profile", "device", "attention",
            "restart"} == set(doc["steps"])
    assert set(doc["cold_start"]) == {"first", "second"}
    assert doc["cold_start"]["second"]["jit_cache_hits"] > 0
    # "ok": true never appears without "rehearsal": true off the chip
    for line in out.stdout.splitlines():
        if '"ok": true' in line:
            assert '"rehearsal": true' in line


@pytest.mark.e2e
def test_without_an_accelerator_there_is_no_result():
    """The chip run on a machine whose JAX finds only the CPU: non-zero,
    and nothing on stdout (least of all an "ok")."""
    out = _run([], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "platform='cpu'" in out.stderr


def test_outside_a_checkout_there_is_no_result(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(pathlib.Path(SMOKE).read_text())
    out = subprocess.run([sys.executable, str(lone)], cwd=str(tmp_path),
                         capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
