"""A lint of BENCHMARK.json and of the files its names point at."""

import os
import re

import pytest

from harness import manifest, setup_steps, shapes, shapes_moe

BENCH = manifest.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
# cells and configurations of the CPU rehearsals: files beside the
# benchmark's own, never entries of BENCHMARK.json
REHEARSALS = ["debug-tiny.rehearse", "debug-tiny.rehearse-long",
              "debug-moe.rehearse"]
REHEARSAL_CONFIGS = {"debug-tiny": shapes, "debug-moe": shapes_moe}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmark"]
    assert os.path.getsize(os.path.join(manifest.REPO_DIR,
                                        "BENCHMARK.json")) <= 64 * 1024
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_names_units_and_keys(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= allowed | {"bound"}
    else:
        assert set(m) <= allowed | {"layer", "moves"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for cell in m.get("workloads", ()):
        assert cell in CELLS


def test_names_are_unique_and_setup_s_is_everywhere():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_and_reports_enough(cell):
    loaded = manifest.load_cell(cell)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert cell == f"{entry['config']}.{entry['traffic']}"
    assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
    assert len(entry["why"]) <= 200 and entry["chips"] in (1, 4)
    assert loaded.rate_rps > 0
    e2e = [m["name"] for m in manifest.metrics_of(BENCH, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in manifest.metrics_of(BENCH, cell, "end_to_end"):
        spec = manifest.load_json("end_to_end", f"{m['name']}.json")
        assert spec["unit"] == m["unit"]
        assert callable(manifest.load_reader("end_to_end",
                                             spec["reader"]).read)
    layer = manifest.metrics_of(BENCH, cell, "per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], "moves", m["moves"])
        spec = manifest.load_json("layer_metrics", f"{m['name']}.json")
        for key in ("layer", "unit", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert callable(manifest.load_reader("per_layer", spec["reader"]).read)
    assert_fits_a_slot(loaded)


def assert_fits_a_slot(loaded):
    """The longest prompt plus the longest answer fits a slot. (A prompt
    may be longer than the largest prefill bucket: it takes the chunk
    path, which set-up warms; ``setup_steps.chunk_path_lengths``.)"""
    flags = loaded.config["serve_flags"]
    slot = int(flags["--page-size"]) * int(flags["--pages-per-slot"])
    mix = loaded.mix
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= slot


@pytest.mark.parametrize("cell", REHEARSALS)
def test_a_rehearsal_cell_finds_its_files_and_is_no_entry(cell):
    loaded = manifest.load_cell(cell)
    assert cell not in CELLS and loaded.cell["config"] not in {
        c["name"] for c in BENCH["configs"]}
    assert loaded.config["platform"] == "cpu" and loaded.rate_rps > 0
    assert_fits_a_slot(loaded)
    manifest.shapes_of(loaded.config)
    manifest.reference_of(loaded.config)
    assert manifest.load_json("golden", f"{loaded.cell['config']}.json")[
        "config"] == loaded.cell["config"]


def test_the_long_rehearsal_outgrows_the_largest_bucket():
    loaded = manifest.load_cell("debug-tiny.rehearse-long")
    top = max(loaded.config["prefill_buckets"])
    assert loaded.mix["prompt_tokens"]["max"] > top
    # one lone prompt for each executable of the chunk path: 400 = 3 x 128
    # + 16 runs full chunks of 128 and ends in the bucket of 32; 384 = 3 x
    # 128 would run the full chunk's executable again and is not sent
    assert setup_steps.chunk_path_lengths(loaded.config, loaded.mix) == [400]
    assert [b for b, _ in setup_steps.reachable_buckets(
        loaded.config, loaded.mix)] == [32, 128]


@pytest.mark.parametrize("cell", CELLS + ["debug-tiny.rehearse",
                                          "debug-moe.rehearse"])
def test_a_mix_inside_its_buckets_warms_no_chunk_path(cell):
    loaded = manifest.load_cell(cell)
    assert setup_steps.chunk_path_lengths(loaded.config, loaded.mix) == []


@pytest.mark.parametrize("lo,hi,want", [
    (512, 3584, [3328]),         # 3 x 1024 + 256; 3584 ends in a full chunk
    (1100, 1200, [1200]),        # every last chunk lands in the bucket of 256
    (32, 1025, [1025]),          # one token over: a last chunk of one
    (2048, 2048, [2048]),        # full chunks only: the longest warms them
    (32, 1024, []),
])
def test_chunk_path_lengths_one_for_each_last_chunk_bucket(lo, hi, want):
    config = {"prefill_buckets": [256, 1024]}
    mix = {"prompt_tokens": {"min": lo, "max": hi}}
    assert setup_steps.chunk_path_lengths(config, mix) == want


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configurations(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"] == f"benchmark/configs/{cfg['name']}.json"
    doc = manifest.load_json("configs", f"{cfg['name']}.json")
    assert doc["source"] == cfg["source"] and doc["reduced"] == cfg["reduced"]
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    assert_expected_bytes_and_flags(doc)


def assert_expected_bytes_and_flags(doc):
    # the bytes the file expects are the bytes ITS shape counts give: the
    # module it names under "shapes", harness/shapes.py where it names none
    counts = manifest.shapes_of(doc)
    assert counts.weight_bytes(doc) == doc["expected_bytes"]["weights"]
    assert counts.pool_bytes(doc) == doc["expected_bytes"]["pool"]
    flags = " ".join(doc["serve_flags"])
    assert ",".join(map(str, doc["prefill_buckets"])) == \
        doc["serve_flags"]["--prefill-buckets"] and "--model" not in flags


@pytest.mark.parametrize("name", sorted(REHEARSAL_CONFIGS))
def test_rehearsal_configurations(name):
    doc = manifest.load_json("configs", f"{name}.json")
    assert manifest.shapes_of(doc) is REHEARSAL_CONFIGS[name]
    assert_expected_bytes_and_flags(doc)
    if name == "debug-moe":
        # the dense count sees one feed-forward network and no router: a
        # file held to it could only write down bytes that are false
        assert doc["shapes"] == "shapes_moe" and doc["reference"] == "moe"
        assert shapes.weight_bytes(doc) == 126976
        assert doc["expected_bytes"]["weights"] == 238592
    else:
        assert "shapes" not in doc and "reference" not in doc


def test_the_default_modules_are_the_ones_the_benchmark_has():
    doc = manifest.load_json("configs", "mistral-7b.json")
    assert "shapes" not in doc and "reference" not in doc
    assert manifest.shapes_of(doc) is shapes
    from reference import forward
    assert manifest.reference_of(doc) is forward


@pytest.mark.parametrize("key,named", [
    ("shapes", "no_such_module"), ("shapes", "../shapes"), ("shapes", ""),
    ("shapes", 7), ("shapes", "peaks"),          # a file, not shape counts
    ("reference", "no_such_module"), ("reference", "../forward"),
    ("reference", "make_golden"),                # a file, no ``logits_at``
])
def test_a_module_that_is_not_there_is_a_manifest_error(key, named):
    """Not an ImportError, and not a module of another kind."""
    load = {"shapes": manifest.shapes_of,
            "reference": manifest.reference_of}[key]
    with pytest.raises(manifest.ManifestError):
        load({key: named})


def test_the_harness_names_no_cell_configuration_or_mix():
    words = set(CELLS) | {c["name"] for c in BENCH["configs"]} | {
        w["traffic"] for w in BENCH["workloads"]} - {"chat"}
    hdir = os.path.join(manifest.BENCH_DIR, "harness")
    for f in os.listdir(hdir):
        if f.endswith(".py"):
            text = open(os.path.join(hdir, f)).read()
            for w in words:
                assert w not in text, (f, w)
            assert "/chat" not in text.replace("/v1/chat/completions", "")
