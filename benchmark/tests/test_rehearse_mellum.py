"""The rehearsal of a stack of window and full attention layers,
``debug-mellum``: a window of 8 under prompts of up to 400 tokens, so a
bucket's mask, the chunk path's history and the paged decode kernel's rows
all lie past the window's edge; YaRN on the full layers alone;
softmax-routed experts in every layer; its own reference
(``reference/mellum.py``) and shape counts (``harness/shapes_mellum.py``),
found by name, through the whole harness on the CPU. One traced run serves
every assertion. A rehearsal is never an entry of BENCHMARK.json, so the
cell's counter metric is read here through the reader the benchmark has,
from what the run's own pollers saw."""

import json

import pytest

from harness import manifest, shapes_mellum
from reference import mellum
from test_manifest import BENCH, assert_expected_bytes_and_flags
from test_rehearse import rehearse

CELL = "debug-mellum.rehearse-long"
REAL = "mellum2-12b"
SEED = 2**31 + 19


@pytest.fixture(scope="module")
def lines():
    return rehearse("--workload", CELL, "--seed", str(SEED), "--trace", "1")


def test_the_harness_finds_its_shapes_and_its_reference_by_name():
    doc = manifest.load_json("configs", "debug-mellum.json")
    assert manifest.shapes_of(doc) is shapes_mellum
    assert manifest.reference_of(doc) is mellum
    assert_expected_bytes_and_flags(doc)
    # every layer keeps every token: 8 layers x 2 KV heads x 16 x K and V
    assert shapes_mellum.kv_bytes_per_token(doc) == 8 * 2 * 2 * 16 * 2
    assert shapes_mellum.layer_counts(doc) == (6, 2)
    # a row 40 tokens in: six layers read 8 of them, two read all
    assert shapes_mellum.keys_read(doc, 40) == 6 * 8 + 2 * 40
    assert not any(c["name"] == "debug-mellum" for c in BENCH["configs"])
    assert not any(w["name"] == CELL for w in BENCH["workloads"])
    loaded = manifest.load_cell(CELL)
    assert loaded.mix["prompt_tokens"]["max"] > max(doc["prefill_buckets"])
    assert loaded.mix["prompt_tokens"]["max"] > 10 * doc["sliding_window"]


def test_the_benchmarks_configuration_is_three_periods_at_every_width():
    doc = manifest.load_json("configs", f"{REAL}.json")
    entry, = [c for c in BENCH["configs"] if c["name"] == REAL]
    assert entry["reduced"] == doc["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types"]
    assert manifest.shapes_of(doc) is shapes_mellum
    assert manifest.reference_of(doc) is mellum
    assert doc["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 3
    assert (doc["hidden_size"], doc["num_attention_heads"],
            doc["num_key_value_heads"], doc["head_dim"],
            doc["moe_intermediate_size"], doc["num_experts"],
            doc["num_experts_per_tok"], doc["vocab_size"],
            doc["sliding_window"]) == (2304, 32, 4, 128, 896, 64, 8, 98304,
                                       1024)
    assert doc["published"]["num_hidden_layers"] == 28
    total = doc["expected_bytes"]["weights"] + doc["expected_bytes"]["pool"]
    assert 0.25 * 16e9 < total < 16e9            # the floor for a new cell
    cell, = [w for w in BENCH["workloads"] if w["config"] == REAL]
    assert cell["name"] == f"{REAL}.long-prompts" and cell["chips"] == 1
    listed = {m["name"] for m in BENCH["per_layer"]
              if cell["name"] in m.get("workloads", ())}
    assert "attn_window_reach_share" in listed
    # the token step's share of its roofline counts the experts the
    # program says it read: the even-routing expectation is not listed
    assert "decode_hbm_share_routed.tpot_mean" in listed
    assert "decode_hbm_share.tpot_mean" not in listed
    assert not {m for m in listed if m.startswith("mla_")}
    assert "moe_held_rows_share" not in listed


def test_the_rehearsal_ends_with_a_correct_result_line(lines):
    last, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] == 16
    assert info["cell"] == CELL and info["statuses"] == [200]
    assert last["counts"]["compiles_in_window"] == 0
    assert info["compiled_after_the_storms"] == []
    check = info["check"]
    golden = manifest.load_json("golden", "debug-mellum.json")
    assert golden["reference"].startswith("benchmark/reference/mellum.py")
    assert [p["name"] for p in check["prompts"]] == [
        p["name"] for p in golden["prompts"]]
    assert 0.0 < check["max_abs_diff"] <= golden["tolerance"]["nats"]
    for p, want in zip(check["prompts"], golden["prompts"]):
        assert [q[0] for q in p["probes"]] == want["top_ids"][0][:8]


def test_every_path_ran_for_both_kinds_of_layer_and_said_so(lines):
    said = json.loads(lines[-2])["info"]["attention"]
    for op in ("prefill", "chunk", "decode"):
        for kind in ("sliding", "full"):
            assert f"{op}_{kind}" in said, sorted(said)
        assert op not in said


def test_the_cells_counter_metric_has_something_to_read():
    """``attn_window_reach_share`` names a series the server exports and
    the reader it is read through: the file is held to the program, since
    no rehearsal reports a metric under its name."""
    from llms_on_kubernetes_tpu.server import metrics

    exported = {m.name for m in metrics.engine_metrics(
        metrics.Registry()).values()}
    name = "attn_window_reach_share"
    spec = manifest.load_json("layer_metrics", f"{name}.json")
    assert spec["reader"] == "counter_ratio"
    for side, rows in (("num", "reached"), ("den", "cached")):
        assert spec["args"][side]["metric"] in exported
        assert spec["args"][side]["labels"] == {"rows": rows}
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["unit"] == spec["unit"] == "%"
    assert entry["layer"] == spec["layer"] == "Cache"
    assert entry["moves"] == spec["moves"] == "tpot_mean_ms"
    assert entry["better"] == "higher"
    assert entry["workloads"] == [f"{REAL}.long-prompts"]


ROUTED = "decode_hbm_share_routed.tpot_mean"


def routed_context(touched, slots=1200.0, post_s=40.0, **kw):
    """Ten streams of 3,000 tokens decoding from before the capture until
    5 s after it was posted, 12 ms a token step (K = 4: 48 ms a
    dispatch); the profiler's post returns after ``post_s``."""
    from test_readers import context, rec

    cell = manifest.load_cell(f"{REAL}.long-prompts")
    at = 1000.0
    records = [rec(i, at - 9.0, [(at - 8.0, 2999), (at - 2.0, 1),
                                 (at + 5.0, 1)], part="tail")
               for i in range(10)]
    for r in records:
        r.prompt_tokens = 0
    counters = [[(f"llm_moe_{name}_total", {"kind": "decode"}, v)
                 for name, v in (("experts_touched", t),
                                 ("expert_slots", s))]
                for t, s in ((100.0, 200.0),
                             (100.0 + touched, 200.0 + slots))]
    base = dict(cell=cell, records=records, before=counters[0],
                after=counters[1], trace_window=(at, at + post_s),
                trace={"devices": {"/device:TPU:0": {"modules": {
                    "jit__decode_multi_packed_step": {
                        "count": 25, "total_s": 25 * 0.048}}}}})
    base.update(kw)
    return context(**base)


def test_the_routed_share_counts_the_experts_the_program_says_it_read():
    doc = manifest.load_json("configs", f"{REAL}.json")
    got = manifest.read_metric("per_layer", ROUTED, routed_context(780.0))
    need = shapes_mellum.decode_step_bytes(
        doc, 10, 10 * 3000, experts_read_share=0.65)
    assert got == pytest.approx(100.0 * need / 819e9 / 0.012, rel=1e-9)
    assert 70 < got < 85      # 7.4 GB a step in 12 ms
    # half the experts fewer: their bytes fewer, nothing else
    less = manifest.read_metric("per_layer", ROUTED, routed_context(390.0))
    assert got - less == pytest.approx(
        100.0 * 12 * 0.325 * 64 * shapes_mellum.expert_params(doc) * 2
        / 819e9 / 0.012, rel=1e-9)
    # and never the even-routing expectation, which reads higher here
    even = shapes_mellum.decode_step_bytes(doc, 10, 10 * 3000)
    assert need < even


def test_the_routed_share_samples_the_seconds_the_capture_was_asked_for():
    """The streams end 5 s after the capture was posted; a post that
    returns after 40 s or after 2 s reads the same ten rows, where
    ``decode_hbm_share`` averages over the drain."""
    a = manifest.read_metric("per_layer", ROUTED, routed_context(780.0))
    b = manifest.read_metric("per_layer", ROUTED,
                             routed_context(780.0, post_s=2.0))
    assert a == b


@pytest.mark.parametrize("empty", [
    dict(trace=None), dict(trace_window=(1000.0, None)), dict(records=[]),
    dict(before=[], after=[]),            # a program without the counters
    dict(touched=0.0, slots=0.0),         # no token step in the window
    dict(cell=manifest.load_cell("deepseek-v3.long-prompts")),
])
def test_the_routed_share_with_nothing_to_read_returns_nothing(empty):
    touched, slots = empty.pop("touched", 780.0), empty.pop("slots", 1200.0)
    assert manifest.read_metric(
        "per_layer", ROUTED, routed_context(touched, slots, **empty)) is None
