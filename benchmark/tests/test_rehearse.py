"""The whole harness on the CPU: serve + router children, warm-up, the
reference against the served path, the open loop, the traced run's pollers
and reduction. One run serves every assertion (it takes about a minute)."""

import functools
import json
import os
import subprocess
import sys

import pytest

from harness import manifest, schedule
from test_lifecycle import CHILDREN, still_alive_after

RUN = os.path.join(manifest.BENCH_DIR, "run.py")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def rehearse(*args):
    """One CPU rehearsal to its end: its stdout lines. A run that ends by
    itself leaves neither child behind."""
    proc = subprocess.run(
        [sys.executable, RUN, "--rehearse", "--seconds", "4", *args],
        env=ENV, capture_output=True, text=True, timeout=900,
        cwd=manifest.REPO_DIR)
    assert proc.returncode == 0, proc.stderr[-2000:]
    pids = [int(g) for g in CHILDREN.search(proc.stderr).groups()]
    assert still_alive_after(pids, 5) == []
    lines = proc.stdout.strip().splitlines()
    # what ``correct`` compared, each number beside its limit: the result
    # line's LAST key, and the last lines of stderr in the same order
    last = json.loads(lines[-1])
    config = manifest.load_cell(
        json.loads(lines[-2])["info"]["cell"]).cell["config"]
    golden = manifest.load_json("golden", f"{config}.json")
    # two numbers a golden prompt, then the two self-consistency checks
    assert list(last)[-1] == "compared"
    assert len(last["compared"]) == 2 * len(golden["prompts"]) + 2
    said = proc.stderr.strip().splitlines()[-len(last["compared"]):]
    for line, (name, c) in zip(said, last["compared"].items()):
        assert line.startswith(
            f"benchmark: compared {name} {c['value']} limit {c['limit']} ")
    return lines


@pytest.fixture(scope="module")
def rehearsal():
    return rehearse("--seed", str(2**31 + 7), "--trace", "1")


# the rehearsals of what a configuration or a mix may bring with new files
# alone: (cell, --trace, requests in a 4 s window at the cell's rate)
OTHERS = {"debug-moe.rehearse": ("1", 32),
          "debug-tiny.rehearse-long": ("0", 16)}
SEED = 2**31 + 11


@functools.lru_cache(maxsize=None)
def other(cell):
    """The one run of ``cell`` that serves every assertion about it."""
    return rehearse("--workload", cell, "--seed", str(SEED), "--trace",
                    OTHERS[cell][0])


@pytest.mark.parametrize("cell", sorted(OTHERS))
def test_another_rehearsal_ends_with_a_correct_result_line(cell):
    lines = other(cell)
    last, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] == OTHERS[cell][1]
    assert info["cell"] == cell and info["statuses"] == [200]
    assert info["prompt_token_mismatch"] == 0 and info["output_tokens"] > 0
    # nothing compiles inside the window, or after the storms at all
    assert last["counts"]["compiles_in_window"] == 0
    assert info["compiled_after_the_storms"] == []
    check = info["check"]
    config = manifest.load_cell(cell).cell["config"]
    golden = manifest.load_json("golden", f"{config}.json")
    assert check["golden"] and check["correct"] and check["repeat_identical"]
    assert [p["name"] for p in check["prompts"]] == [
        p["name"] for p in golden["prompts"]]
    assert 0.0 < check["max_abs_diff"] <= golden["tolerance"]["nats"]


def test_the_sparse_rehearsal_is_held_to_its_own_reference():
    lines = other("debug-moe.rehearse")
    golden = manifest.load_json("golden", "debug-moe.json")
    assert golden["reference"].startswith("benchmark/reference/moe.py")
    dense = manifest.load_json("golden", "debug-tiny.json")
    check = json.loads(lines[-2])["info"]["check"]
    for p, want, other_block in zip(check["prompts"], golden["prompts"],
                                    dense["prompts"]):
        assert [q[0] for q in p["probes"]] == want["top_ids"][0][:8]
        assert want["top_ids"][0][:8] != other_block["top_ids"][0][:8]


def test_the_long_rehearsal_warms_the_chunk_path_before_the_storms():
    cell = "debug-tiny.rehearse-long"
    info = json.loads(other(cell)[-2])["info"]
    warm = info["warm_up"]
    assert warm["shapes"] == [[32, 25], [128, 121]]
    assert warm["chunk_path"] == [400]
    # the output check, whose golden prompts take the chunk path, found
    # its executables compiled: it added nothing to llm_jit_compiles_total
    assert info["storms"]["jit_compiles_after_each_round"][0] == \
        warm["jit_compiles_before_and_after"][1]
    # and the window did send prompts over the largest bucket
    loaded = manifest.load_cell(cell)
    sent = schedule.plan(loaded.mix, loaded.rate_rps, 4.0, SEED, "window")
    assert sum(1 for r in sent if r.prompt_tokens > 128) >= 4
    assert max(r.prompt_tokens for r in sent) > 384


def test_a_mix_inside_its_buckets_is_warmed_as_it_always_was(rehearsal):
    warm = json.loads(rehearsal[-2])["info"]["warm_up"]
    assert warm["shapes"] == [[32, 25], [128, 93]]
    assert warm["chunk_path"] == []


@pytest.mark.parametrize("config", ["debug-tiny", "debug-moe"])
def test_make_golden_reproduces_the_golden_file_byte_for_byte(config,
                                                              tmp_path):
    """Through the loaders: the reference the configuration names, the
    weights it is served as. ``debug-tiny``'s file predates both."""
    kept = os.path.join(manifest.BENCH_DIR, "golden", f"{config}.json")
    tol = manifest.load_json("golden", f"{config}.json")["tolerance"]
    out = str(tmp_path / "golden.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "reference",
                                      "make_golden.py"), config,
         "--out", out, "--tolerance", repr(tol["nats"]), "--why",
         tol["why"]],
        env=ENV, capture_output=True, text=True, timeout=600,
        cwd=manifest.REPO_DIR)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(out, "rb") as a, open(kept, "rb") as b:
        assert a.read() == b.read()


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
