"""The whole harness on the CPU: serve + router children, warm-up, the
reference against the served path, the open loop, the traced run's pollers
and reduction. One run serves every assertion (it takes about a minute)."""

import json
import os
import subprocess
import sys

import pytest

from harness import manifest
from test_lifecycle import CHILDREN, still_alive_after

RUN = os.path.join(manifest.BENCH_DIR, "run.py")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


@pytest.fixture(scope="module")
def rehearsal():
    proc = subprocess.run(
        [sys.executable, RUN, "--rehearse", "--seconds", "4", "--seed",
         str(2**31 + 7), "--trace", "1"],
        env=ENV, capture_output=True, text=True, timeout=900,
        cwd=manifest.REPO_DIR)
    assert proc.returncode == 0, proc.stderr[-2000:]
    # a run that ends by itself leaves neither child behind
    pids = [int(g) for g in CHILDREN.search(proc.stderr).groups()]
    assert still_alive_after(pids, 5) == []
    return proc.stdout.strip().splitlines()


def test_the_last_line_is_a_rehearsal_and_prints_no_metric(rehearsal):
    last = json.loads(rehearsal[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["device"]["platform"] == "cpu"
    assert last["failed"] == 0 and last["attempted"] == 32
    bench = manifest.load_benchmark()
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    text = "\n".join(rehearsal)
    for name in names:
        if name != "compiles_in_window":     # a count, and said to be one
            assert name not in text, name
    assert "busy_s" not in text and "memory_peak_bytes" not in text


def test_the_reference_agrees_with_the_served_path_on_debug_tiny(rehearsal):
    check = json.loads(rehearsal[-2])["info"]["check"]
    assert check["golden"] and check["correct"] and check["repeat_identical"]
    assert check["finite"]
    names = [p["name"] for p in check["prompts"]]
    assert names == ["bucket32", "bucket128", "chunk", "bucket128-again"]
    golden = manifest.load_json("golden", "debug-tiny.json")
    tol = golden["tolerance"]["nats"]
    for p, want in zip(check["prompts"], golden["prompts"]):
        assert p["ok"] and p["prompt_tokens_ok"] and len(p["probes"]) == 8
        assert [q[0] for q in p["probes"]] == want["top_ids"][0][:8]
        assert p["max_abs_diff"] <= tol
        # the token another prompt likes best is served lower here than
        # there by far more than the tolerance: the ids are the ids asked
        tid, there, here = p["foreign"]
        assert there - here > 5 * tol
    assert check["max_abs_diff"] <= tol and check["rms_diff"] < tol


def test_the_open_loop_kept_time_and_lost_nothing(rehearsal):
    info = json.loads(rehearsal[-2])["info"]
    assert info["statuses"] == [200] and info["prompt_token_mismatch"] == 0
    assert info["generator_lateness_ms"]["p99"] < 250
    assert info["output_tokens"] > 0 and info["stopped_early"] == 0
    assert json.loads(rehearsal[-1])["counts"]["traced_planes"]


@pytest.mark.parametrize("args", [
    ["--workload", "mistral-7b.chat", "--seed", "1", "--seconds", "1",
     "--trace", "0"],                       # no TPU here
    ["--workload", "no-such.cell", "--seconds", "1"],
    ["--workload", "debug-tiny.rehearse", "--seconds", "1"],  # not a cell
])
def test_no_accelerator_or_no_such_cell_prints_no_result(args):
    proc = subprocess.run([sys.executable, RUN, *args], env=ENV,
                          capture_output=True, text=True, timeout=300,
                          cwd=manifest.REPO_DIR)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no result" in proc.stderr
