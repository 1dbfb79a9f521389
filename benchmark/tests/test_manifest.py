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


def reported(cell: str) -> set:
    """The names of the end-to-end metrics ``cell`` reports."""
    return {m["name"] for m in manifest.metrics_of(BENCH, cell, "end_to_end")}


def quantity(name: str) -> str:
    """What an end-to-end metric is a statistic OF: the ``what`` its
    percentile reader is given, or the ``quantity`` its file names."""
    spec = manifest.load_json("end_to_end", f"{name}.json")
    return spec.get("quantity") or spec["args"]["what"]


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_cell_of_a_layer_metric_reports_what_it_moves(m):
    """``moves`` names one end-to-end metric, and every cell the metric
    lists reports it. A cell that reports the same quantity by another
    statistic lists the metric's twin."""
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    cells = m.get("workloads", CELLS)
    assert cells
    for cell in cells:
        assert m["moves"] in reported(cell), (m["name"], cell, m["moves"])


TWINS = [m for m in BENCH["per_layer"] if "twin_of" in manifest.load_json(
    "layer_metrics", f"{m['name']}.json")]


@pytest.mark.parametrize("m", TWINS, ids=lambda m: m["name"])
def test_a_twin_reads_what_its_original_reads(m):
    """A quantity whose cells report different end-to-end metrics is
    split: the twin's file names the reader and the arguments of the
    original's, letter for letter, and moves another statistic of the
    same quantity; no cell lists both."""
    spec = manifest.load_json("layer_metrics", f"{m['name']}.json")
    first = manifest.load_json("layer_metrics", f"{spec['twin_of']}.json")
    assert m["name"].startswith(spec["twin_of"] + ".")
    for key in ("layer", "module", "unit", "source", "reader", "args"):
        assert spec[key] == first[key], (m["name"], key)
    assert spec["moves"] != first["moves"]
    assert quantity(spec["moves"]) == quantity(first["moves"])
    entry, = [e for e in BENCH["per_layer"] if e["name"] == spec["twin_of"]]
    assert entry["better"] == m["better"]
    assert not set(entry["workloads"]) & set(m["workloads"])


def test_there_are_twins_and_each_listed_file_is_an_entry():
    assert TWINS
    names = {m["name"] for m in BENCH["per_layer"]}
    ldir = os.path.join(manifest.BENCH_DIR, "layer_metrics")
    for f in os.listdir(ldir):
        if f.endswith(".json"):
            assert f[:-len(".json")] in names, f


def test_the_windows_mean_time_per_token_is_the_short_windows_metric():
    """``tpot_mean_ms``: the time per output token as ``tpot_p95_ms``
    has it, pooled over the window's time and not a percentile over its
    requests; reported by the one cell whose window holds too few
    requests for a tail, which does not report the tail."""
    spec = manifest.load_json("end_to_end", "tpot_mean_ms.json")
    tail = manifest.load_json("end_to_end", "tpot_p95_ms.json")
    assert spec["reader"] == "latency_pooled_mean" and spec["args"] == {}
    assert tail["reader"] == "latency_percentile"
    assert tail["args"] == {"what": "tpot", "pct": 95}
    assert quantity("tpot_mean_ms") == quantity("tpot_p95_ms") == "tpot"
    assert spec["unit"] == tail["unit"] == "ms"
    entry, = [m for m in BENCH["end_to_end"] if m["name"] == "tpot_mean_ms"]
    assert entry["workloads"] == ["deepseek-v3.long-prompts"]
    assert entry["bound"] == 0.1 and entry["better"] == "lower"
    for cell in CELLS:
        e2e = reported(cell)
        assert len(e2e & {"tpot_mean_ms", "tpot_p95_ms"}) == 1, cell
        # a 95th percentile wants ten samples beyond it, 200 requests a
        # window: the cell that reports the mean holds fewer
        if "tpot_mean_ms" in e2e:
            rate = manifest.load_cell(cell).rate_rps
            assert rate * BENCH["run_seconds"] < 200


def test_a_first_tokens_time_is_printed_and_read_but_holds_no_bound():
    """Since PR 45 (the driver's check read ``ttft_p50_ms`` spreading by
    more than half of the largest bound, and the builder's sets
    ``ttft_p95_ms``) no cell reports a first-token time end to end. The
    files stay, so every run prints them in ``end_to_end_all``
    (``manifest.metric_files``), and ``mistral-7b.chat`` lists them per
    layer under names of their own."""
    entries = {m["name"] for m in BENCH["end_to_end"]}
    files = manifest.metric_files("end_to_end")
    assert entries <= set(files) and files == sorted(files)
    for pct in (50, 95):
        old, new = f"ttft_p{pct}_ms", f"first_token_p{pct}_ms"
        assert old in files and old not in entries
        spec = manifest.load_json("end_to_end", f"{old}.json")
        assert spec["args"] == {"what": "ttft", "pct": pct}
        layer = manifest.load_json("layer_metrics", f"{new}.json")
        assert layer["reader"] == "first_token_percentile"
        assert layer["args"] == {"pct": pct}
        entry, = [m for m in BENCH["per_layer"] if m["name"] == new]
        assert entry["workloads"] == ["mistral-7b.chat"]
        assert entry["moves"] in reported("mistral-7b.chat")
    for m in BENCH["per_layer"]:
        assert not m["moves"].startswith("ttft_"), m["name"]


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
    """And one whose longest prompt outgrows the largest bucket warms
    that path: which of the two a cell is, its own files say."""
    loaded = manifest.load_cell(cell)
    top = max(loaded.config["prefill_buckets"])
    longest = loaded.mix["prompt_tokens"]["max"]
    lengths = setup_steps.chunk_path_lengths(loaded.config, loaded.mix)
    if longest <= top:
        assert lengths == []
    else:
        assert lengths and all(top < n <= longest for n in lengths)


def test_the_cell_of_long_prompts_warms_the_one_length_it_needs():
    # buckets 512 and 2048, prompts to 8192 = 4 x 2048: full chunks alone
    # need no request of their own; 6656 = 3 x 2048 + 512 ends in the
    # bucket of 512
    loaded = manifest.load_cell("deepseek-v3.long-prompts")
    assert setup_steps.chunk_path_lengths(loaded.config,
                                          loaded.mix) == [6656]


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
