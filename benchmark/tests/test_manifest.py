"""A lint of BENCHMARK.json and of the files its names point at."""

import os
import re

import pytest

from harness import manifest, shapes

BENCH = manifest.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
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
    mix = loaded.mix
    # the longest prompt plus the longest answer fits a slot
    flags = loaded.config["serve_flags"]
    slot = int(flags["--page-size"]) * int(flags["--pages-per-slot"])
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= slot
    assert mix["prompt_tokens"]["max"] <= max(loaded.config["prefill_buckets"])


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configurations(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"] == f"benchmark/configs/{cfg['name']}.json"
    doc = manifest.load_json("configs", f"{cfg['name']}.json")
    assert doc["source"] == cfg["source"] and doc["reduced"] == cfg["reduced"]
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    # the bytes the file expects are the bytes its shapes give
    assert shapes.weight_bytes(doc) == doc["expected_bytes"]["weights"]
    assert shapes.pool_bytes(doc) == doc["expected_bytes"]["pool"]
    flags = " ".join(doc["serve_flags"])
    assert ",".join(map(str, doc["prefill_buckets"])) == \
        doc["serve_flags"]["--prefill-buckets"] and "--model" not in flags


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
