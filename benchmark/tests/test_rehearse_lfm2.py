"""The rehearsal of a stack of several kinds of layer, ``debug-lfm2``:
conv state beside the KV pool, sigmoid-routed experts through the grouped
product, its own reference (``reference/lfm2_moe.py``) and shape counts
(``harness/shapes_lfm2_moe.py``), found by name, through the whole harness
on the CPU. One traced run serves every assertion. A rehearsal is never an
entry of BENCHMARK.json, so the new counter metrics are read here through
the readers the benchmark has, from what the run's own pollers saw."""

import json

import pytest

from harness import manifest, shapes_lfm2_moe
from reference import lfm2_moe
from test_manifest import BENCH, assert_expected_bytes_and_flags
from test_rehearse import rehearse

CELL = "debug-lfm2.rehearse"
SEED = 2**31 + 13


@pytest.fixture(scope="module")
def lines():
    return rehearse("--workload", CELL, "--seed", str(SEED), "--trace", "1")


def test_the_harness_finds_its_shapes_and_its_reference_by_name():
    doc = manifest.load_json("configs", "debug-lfm2.json")
    assert manifest.shapes_of(doc) is shapes_lfm2_moe
    assert manifest.reference_of(doc) is lfm2_moe
    assert_expected_bytes_and_flags(doc)
    # keys and values of the ONE attention layer, not of three layers
    assert shapes_lfm2_moe.kv_bytes_per_token(doc) == 2 * 1 * 2 * 16 * 2
    assert not any(c["name"] == "debug-lfm2" for c in BENCH["configs"])
    assert not any(w["name"] == CELL for w in BENCH["workloads"])


def test_the_rehearsal_ends_with_a_correct_result_line(lines):
    last, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] == 32
    assert info["cell"] == CELL and info["statuses"] == [200]
    assert last["counts"]["compiles_in_window"] == 0
    assert info["compiled_after_the_storms"] == []
    check = info["check"]
    golden = manifest.load_json("golden", "debug-lfm2.json")
    assert golden["reference"].startswith("benchmark/reference/lfm2_moe.py")
    assert [p["name"] for p in check["prompts"]] == [
        p["name"] for p in golden["prompts"]]
    assert 0.0 < check["max_abs_diff"] <= golden["tolerance"]["nats"]
    for p, want in zip(check["prompts"], golden["prompts"]):
        assert [q[0] for q in p["probes"]] == want["top_ids"][0][:8]


def test_the_cells_counter_metrics_have_something_to_read(lines):
    """The three metrics of the expert layers name series the server
    exports and the reader they are read through: the files are held to
    the program, since no rehearsal reports a metric under its name."""
    from llms_on_kubernetes_tpu.server import metrics

    exported = {m.name for m in metrics.engine_metrics(
        metrics.Registry()).values()}
    for name in ("moe_experts_touched_share", "moe_load_max_over_mean",
                 "moe_rows_per_decode_step"):
        spec = manifest.load_json("layer_metrics", f"{name}.json")
        assert spec["reader"] == "counter_ratio" and spec["layer"] == "Experts"
        for side in ("num", "den"):
            assert spec["args"][side]["metric"] in exported
            assert spec["args"][side]["labels"]["kind"] in (
                "prefill", "decode")
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["layer"] == "Experts" and entry["unit"] == spec["unit"]
        assert entry["moves"] == spec["moves"]
