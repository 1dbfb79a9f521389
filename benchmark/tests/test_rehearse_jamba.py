"""The rehearsal of a stack of Mamba and position-free attention layers,
``debug-jamba``: per-slot convolution inputs and a float32 state-space state
beside the KV pool, its own reference (``reference/jamba.py``) and shape
counts (``harness/shapes_jamba.py``), found by name, through the whole
harness on the CPU. One traced run serves every assertion. A rehearsal is
never an entry of BENCHMARK.json, so the cell's two counter metrics are read
here through the reader the benchmark has, from what the run's own pollers
saw."""

import json

import pytest

from harness import manifest, shapes_jamba
from reference import jamba
from test_manifest import BENCH, assert_expected_bytes_and_flags
from test_rehearse import rehearse

CELL = "debug-jamba.rehearse"
SEED = 2**31 + 17
SSM_METRICS = ("ssm_prompt_us_per_token", "ssm_positions_per_token")


@pytest.fixture(scope="module")
def lines():
    return rehearse("--workload", CELL, "--seed", str(SEED), "--trace", "1")


def test_the_harness_finds_its_shapes_and_its_reference_by_name():
    doc = manifest.load_json("configs", "debug-jamba.json")
    assert manifest.shapes_of(doc) is shapes_jamba
    assert manifest.reference_of(doc) is jamba
    assert_expected_bytes_and_flags(doc)
    # keys and values of the ONE attention layer's one head, not of four
    # layers; the state of a sequence is the three Mamba layers'
    assert shapes_jamba.kv_bytes_per_token(doc) == 2 * 1 * 1 * 16 * 2
    assert shapes_jamba.slot_state_bytes(doc) == 3 * 128 * (16 * 4 + 3 * 2)
    assert doc["expected_bytes"]["ssm_state"] == \
        shapes_jamba.ssm_state_bytes(doc, 4)
    assert not any(c["name"] == "debug-jamba" for c in BENCH["configs"])
    assert not any(w["name"] == CELL for w in BENCH["workloads"])


def test_the_benchmarks_configuration_is_the_published_model_uncut():
    doc = manifest.load_json("configs", "jamba2-3b.json")
    entry, = [c for c in BENCH["configs"] if c["name"] == "jamba2-3b"]
    assert entry["reduced"] == doc["reduced"] == []
    assert manifest.shapes_of(doc) is shapes_jamba
    assert (doc["num_hidden_layers"], doc["hidden_size"],
            doc["mamba_d_state"], doc["mamba_dt_rank"]) == (28, 2560, 16, 160)
    assert shapes_jamba.kinds(doc).count("attn") == 2
    total = sum(doc["expected_bytes"][k]
                for k in ("weights", "pool", "ssm_state"))
    assert 0.25 * 16e9 < total < 0.6 * 16e9      # the floor for a new cell


def test_the_rehearsal_ends_with_a_correct_result_line(lines):
    last, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] == 32
    assert info["cell"] == CELL and info["statuses"] == [200]
    assert last["counts"]["compiles_in_window"] == 0
    assert info["compiled_after_the_storms"] == []
    check = info["check"]
    golden = manifest.load_json("golden", "debug-jamba.json")
    assert golden["reference"].startswith("benchmark/reference/jamba.py")
    assert [p["name"] for p in check["prompts"]] == [
        p["name"] for p in golden["prompts"]]
    assert 0.0 < check["max_abs_diff"] <= golden["tolerance"]["nats"]
    for p, want in zip(check["prompts"], golden["prompts"]):
        assert [q[0] for q in p["probes"]] == want["top_ids"][0][:8]


@pytest.mark.parametrize("name", SSM_METRICS)
def test_the_cells_counter_metrics_have_something_to_read(name):
    """The two metrics of the Mamba layers name series the server exports
    and the reader they are read through: the files are held to the
    program, since no rehearsal reports a metric under its name."""
    from llms_on_kubernetes_tpu.server import metrics

    exported = {m.name for m in metrics.engine_metrics(
        metrics.Registry()).values()}
    spec = manifest.load_json("layer_metrics", f"{name}.json")
    assert spec["reader"] == "counter_ratio"
    for side in ("num", "den"):
        for series in spec["args"][side]:
            assert series["metric"] in exported
            assert list(series["labels"].values())[0] in ("prefill", "chunk")
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["unit"] == spec["unit"] and entry["layer"] == spec["layer"]
    assert entry["moves"] == spec["moves"] == "tpot_p95_ms"
    assert entry["workloads"] == ["jamba2-3b.long-answers"]
