"""The rehearsal of latent attention over a latent pool, ``debug-deepseek``
cut by name to a share (layers 0 and 2-3, experts 0-3 of 8, 260 vocabulary
rows): all three attention paths (buckets, chunks over cached latent rows,
absorbed decode), group-limited routing, a shared expert, its own reference
(``reference/deepseek_v3.py``) and shape counts
(``harness/shapes_deepseek_v3.py``), found by name, through the whole harness
on the CPU. One traced run serves every assertion. A rehearsal is never an
entry of BENCHMARK.json, so the two counter metrics this configuration's
cell brings are read here through the reader the benchmark has, from what
the run's own pollers saw."""

import json

import pytest

from harness import manifest, shapes_deepseek_v3
from reference import deepseek_v3
from test_manifest import BENCH, assert_expected_bytes_and_flags
from test_rehearse import rehearse

CELL = "debug-deepseek.rehearse-long"
SEED = 2**31 + 17


@pytest.fixture(scope="module")
def lines():
    return rehearse("--workload", CELL, "--seed", str(SEED), "--trace", "1")


def test_the_harness_finds_its_shapes_and_its_reference_by_name():
    doc = manifest.load_json("configs", "debug-deepseek.json")
    assert manifest.shapes_of(doc) is shapes_deepseek_v3
    assert manifest.reference_of(doc) is deepseek_v3
    assert_expected_bytes_and_flags(doc)
    # one latent row a layer (16 + 8 values), no V: what the algorithm
    # needs; the pool's rows are padded to 128 lanes
    assert shapes_deepseek_v3.kv_bytes_per_token(doc) == 3 * 24 * 2
    assert shapes_deepseek_v3.pool_bytes(doc) == 64 * 64 * 3 * 128 * 2
    assert doc["share"]["routed_experts"] == 8 and doc["n_routed_experts"] == 4
    assert not any(c["name"] == "debug-deepseek" for c in BENCH["configs"])
    assert not any(w["name"] == CELL for w in BENCH["workloads"])


def test_the_cut_is_spelled_in_the_registry_name():
    """``get_config`` of the one name gives the cut the file describes."""
    from llms_on_kubernetes_tpu.configs import get_config

    doc = manifest.load_json("configs", "debug-deepseek.json")
    cfg = get_config(doc["registry_name"])
    assert (cfg.num_layers, cfg.num_dense_layers) == (
        doc["num_hidden_layers"], doc["first_k_dense_replace"])
    assert (cfg.num_held_experts, cfg.first_expert, cfg.num_experts) == (
        doc["n_routed_experts"], doc["share"]["first_expert"],
        doc["share"]["routed_experts"])
    assert cfg.vocab_size == doc["vocab_size"]
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "n_group", "topk_group",
                "n_shared_experts"):
        assert getattr(cfg, key) == doc[key], key


def test_the_rehearsal_ends_with_a_correct_result_line(lines):
    last, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] == 16
    assert info["cell"] == CELL and info["statuses"] == [200]
    assert last["counts"]["compiles_in_window"] == 0
    assert info["compiled_after_the_storms"] == []
    check = info["check"]
    golden = manifest.load_json("golden", "debug-deepseek.json")
    assert golden["reference"].startswith(
        "benchmark/reference/deepseek_v3.py")
    assert [p["name"] for p in check["prompts"]] == [
        p["name"] for p in golden["prompts"]]
    assert 0.0 < check["max_abs_diff"] <= golden["tolerance"]["nats"]
    for p, want in zip(check["prompts"], golden["prompts"]):
        assert [q[0] for q in p["probes"]] == want["top_ids"][0][:8]


def test_all_three_latent_paths_ran_and_said_so(lines):
    said = json.loads(lines[-2])["info"]["attention"]
    assert "expanded" in said["prefill"][0] and "expanded" in said["chunk"][0]
    assert "absorbed" in said["decode"][0]


def test_the_cells_new_counter_metrics_have_something_to_read():
    """The two metrics the benchmark's cell brings name series the server
    exports and the reader they are read through: the files are held to
    the program, since no rehearsal reports a metric under its name."""
    from llms_on_kubernetes_tpu.server import metrics

    exported = {m.name for m in metrics.engine_metrics(
        metrics.Registry()).values()}
    for name, layer in (("moe_held_rows_share", "Experts"),
                        ("mla_chunk_tokens_share", "Kernels")):
        spec = manifest.load_json("layer_metrics", f"{name}.json")
        assert spec["reader"] == "counter_ratio" and spec["layer"] == layer
        sides = [side for key in ("num", "den") for side in (
            spec["args"][key] if isinstance(spec["args"][key], list)
            else [spec["args"][key]])]
        assert all(side["metric"] in exported for side in sides)
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["layer"] == layer and entry["unit"] == spec["unit"] == "%"
        assert entry["moves"] == spec["moves"] == "tpot_mean_ms"
        assert entry["better"] == "lower"
