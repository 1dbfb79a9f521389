"""Every per-layer metric's reader on a hand-made context: what it reads,
and that it returns nothing (not zero) where there is nothing to read."""

import os
import time

import pytest

from harness import manifest, shapes, shapes_moe, xplane
from harness.cell import Context, traced_window_s
from harness.client import Record
from harness.peaks import PEAKS

BENCH = manifest.load_benchmark()
CELL = manifest.load_cell("mistral-7b.chat")


def rec(i, due, events, status=200, part="window"):
    r = Record(i, part, due, 100, 50)
    r.status, r.done, r.finish_reason = status, True, "length"
    r.events = events
    return r


def context(**kw):
    now = time.monotonic()
    w0 = now - 60.0
    wall = time.time() - time.monotonic()
    base = dict(
        cell=CELL, bench=BENCH, window=(w0, w0 + 50.0),
        records=[rec(0, w0 + 1, [(w0 + 1.3, 1), (w0 + 1.36, 1),
                                 (w0 + 1.36, 1), (w0 + 1.8, 1)]),
                 rec(1, w0 + 2, [], status=503),
                 rec(2, w0 + 51, [(w0 + 51.2, 1), (w0 + 53.0, 1)],
                     part="tail")],
        before=[("llm_jit_compiles_total", {}, 36.0)],
        after=[("llm_jit_compiles_total", {}, 38.0)],
        polls=[(w0 + 1, [("llm_decode_batch_occupancy", {}, 16.0)]),
               (w0 + 2, [("llm_decode_batch_occupancy", {}, 32.0)]),
               (w0 + 55, [("llm_decode_batch_occupancy", {}, 0.0)])],
        router_polls=[(w0 + 1, [("llm_replica_healthy", {}, 1.0)]),
                      (w0 + 2, [("llm_replica_healthy", {}, 0.0)]),
                      (w0 + 3, [("llm_replica_healthy", {}, 1.0)])],
        spans={"a": {"started": wall + w0 + 5, "spans": [
                   {"name": "queue", "duration_ms": 10.0},
                   {"name": "prefill", "duration_ms": 60.0}]},
               "b": {"started": wall + w0 + 6, "spans": [
                   {"name": "queue", "duration_ms": 30.0}]},
               "late": {"started": wall + w0 + 52, "spans": [
                   {"name": "queue", "duration_ms": 9000.0}]}},
        trace={"devices": {"/device:TPU:0": {"modules": {
            "jit__decode_multi_packed_step": {"count": 10, "total_s": 0.64},
            "jit__prefill_packed_step": {"count": 4, "total_s": 0.2}}}}},
        trace_window=(w0 + 51.0, w0 + 53.0), peaks=PEAKS["TPU v5 lite"],
        censor_at=w0 + 80.0, setup_s=200.0)
    base.update(kw)
    return Context(**base)


def read(name, ctx, kind="per_layer"):
    return manifest.read_metric(kind, name, ctx)


@pytest.mark.parametrize("name,want", [
    ("compiles_in_window", 2.0),
    ("decode_occupancy", 75.0),                 # mean of 16, 32 of 32 slots
    ("gap_p99_ms", 432.4),                      # gaps 60, 0, 440 ms
    ("router_503.count", 3.0),                  # one 503 + two flips
    ("queue_wait_p95_ms", 29.0),                # 10 and 30; "late" is out
    ("prefill_span_p50_ms", 60.0),
    ("decode_step_ms.lat", 16.0),               # 64 ms a dispatch / K = 4
    ("first_token_p50_ms", 39150.0),  # 300 ms and a refused request's 78 s
    ("first_token_p95_ms", 74115.0),
])
def test_reader_values(name, want):
    assert read(name, context()) == pytest.approx(want, rel=1e-3)


@pytest.mark.parametrize("pct", [50, 95])
def test_the_first_tokens_time_reads_the_same_per_layer_as_in_the_info_line(
        pct):
    """Since PR 45 no cell is held to a first-token time: the per-layer
    ``first_token_p<pct>_ms`` and the file ``end_to_end/ttft_p<pct>_ms``
    (printed in every run's ``end_to_end_all``) read one number, and
    nothing where no request was due in the window."""
    ctx = context()
    assert read(f"first_token_p{pct}_ms", ctx) == read(
        f"ttft_p{pct}_ms", ctx, "end_to_end")
    assert read(f"first_token_p{pct}_ms", context(records=[])) is None


@pytest.mark.parametrize("name,want", [
    ("ttft_p50_ms", 39150.0),     # 300 ms and a refused request's 78 s
    ("ttft_p95_ms", 74115.0),
    ("tpot_p95_ms", 166.667),     # 500 ms over the 3 tokens after the first
    # 0.5 s of the one stream and the refused request's 48 s to the
    # window's end, over the stream's 3 tokens after the first
    ("tpot_mean_ms", 1000.0 * (0.5 + 48.0) / 3),
    ("setup_s", 200.0),
])
def test_end_to_end_reader_values(name, want):
    assert read(name, context(), "end_to_end") == pytest.approx(want,
                                                                rel=1e-3)


def stream(i, start, ms_a_token, groups=2, part="window", status=200):
    """A first token at ``start``, then ``groups`` groups of four."""
    step = 0.004 * ms_a_token
    return rec(i, start - 0.1, [(start, 1)] + [
        (start + step * (k + 1), 4) for k in range(groups)],
        part=part, status=status)


def test_the_windows_mean_time_per_token_counts_its_own_time_and_work():
    """``tpot_mean_ms``: every stream's seconds between its first and
    its last token INSIDE the window, over the tokens delivered inside
    it after the first groups. The preroll's streams count, what drains
    after the window does not, and a failed request stays a stream that
    delivers nothing until the run stopped looking."""
    w0 = time.monotonic() - 60.0
    at = dict(window=(w0, w0 + 50.0), censor_at=w0 + 80.0)
    inside = stream(0, w0 + 10, 10.0)               # 0.08 s, 8 tokens
    # first token 1 s before the window, a group every 0.4 s: the groups
    # at +0.2 and +0.6 are the window's, 0.6 s of it are the stream's
    before = stream(1, w0 - 1.0, 100.0, groups=4, part="preroll")
    # a group every 2 s from 49 s: the one at 51 s drains outside, and
    # the stream's time is cut at the window's end: 1 s, no token
    across = stream(2, w0 + 49.0, 500.0)
    later = stream(3, w0 + 51.0, 7.0, part="tail")
    ctx = context(records=[inside, before, across, later], **at)
    assert read("tpot_mean_ms", ctx, "end_to_end") == pytest.approx(
        1000.0 * (0.08 + 0.6 + 1.0) / (8 + 8))
    # one that stalls after its first token, and one refused outright:
    # streams until the window's end (the run looked until 80 s)
    stalled = stream(4, w0 + 40.0, 10.0, groups=0, status=500)
    refused = rec(5, w0 + 45.0, [], status=503)
    ctx = context(records=[inside, stalled, refused], **at)
    assert read("tpot_mean_ms", ctx, "end_to_end") == pytest.approx(
        1000.0 * (0.08 + 10.0 + 5.0) / 8)
    # nothing delivered inside the window: nothing to read, not zero
    for nothing in ([later], [refused]):
        assert read("tpot_mean_ms", context(records=nothing, **at),
                    "end_to_end") is None


def test_decode_hbm_share_is_needed_bytes_over_peak_over_step_time():
    # one stream decoding through the whole traced window at ~101 tokens
    got = read("decode_hbm_share", context())
    cfg = CELL.config
    need = shapes.decode_step_bytes(cfg, 1, 101) / 819e9
    assert got == pytest.approx(100 * need / 0.016, rel=0.02)
    assert 50 < got < 65        # 7.5 GB of weights alone are 9.2 ms


def on_the_recorded_trace(cell_name, batch):
    """``decode_hbm_share`` of ``cell_name`` over tests/data/tiny.xplane.pb
    (recorded on the chip: three dispatches of ``jit_tiny_matmul_step``,
    13.434 us together, read as this configuration's fused decode step)
    with ``batch`` streams of 101 tokens decoding through the capture.
    Every time is a constant, so the reading is the same digits each run."""
    trace = xplane.reduce(xplane.load(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data",
        "tiny.xplane.pb")))
    records = [rec(i, 1000.0, [(1000.5, 1), (1004.0, 1)], part="tail")
               for i in range(batch)]
    ctx = context(cell=manifest.load_cell(cell_name), records=records,
                  trace=trace, trace_window=(1001.0, 1003.0))
    return manifest.load_reader("per_layer", "decode_hbm_share").read(
        ctx, "tiny_matmul_step")


@pytest.mark.parametrize("batch,parent_read", [(1, 791247.217573256),
                                               (8, 801360.4503580385)])
def test_decode_hbm_share_of_the_dense_default_is_what_the_parent_read(
        batch, parent_read):
    """To the last digit: the numbers are those of the reader as it stood
    before configurations could name their shape counts (PR 27's tree, the
    same context), when it imported ``harness/shapes.py`` itself."""
    assert "shapes" not in CELL.config
    assert on_the_recorded_trace("mistral-7b.chat", batch) == parent_read


@pytest.mark.parametrize("batch,layer_bytes,step_bytes", [
    # one layer: attention 64 x 16 x (2 x 4 + 2 x 2) = 12288 int8 bytes,
    # router 64 x 4 bf16 = 512, an expert 3 x 64 x 96 = 18432 int8 bytes;
    # a step of 1 row touches 4 (1 - 1/2) = 2 experts of 4, one of 8 rows
    # 4 (1 - 1/2^8) = 3.984375. A step: 2 layers, the head 256 x 64 bf16 =
    # 32768, 128 an embedding row, 256 a cached token (2 x 2 x 2 x 16 bf16)
    (1, 12288 + 512 + 2 * 18432,
     2 * 49664 + 32768 + 128 + 101 * 256),
    (8, 12288 + 512 + 3.984375 * 18432,
     2 * 86240 + 32768 + 8 * 128 + 8 * 101 * 256),
])
def test_decode_hbm_share_follows_the_configurations_own_shapes(
        batch, layer_bytes, step_bytes):
    cfg = manifest.load_cell("debug-moe.rehearse").config
    assert cfg["shapes"] == "shapes_moe"
    assert shapes_moe._layer_bytes(
        cfg, shapes_moe.experts_touched(cfg, batch)) == layer_bytes
    assert shapes_moe.decode_step_bytes(cfg, batch, 101 * batch) == step_bytes
    assert step_bytes == {1: 158080, 8: 413120}[batch]
    step_s = 13.434e-6 / 3 / 4          # three dispatches of K = 4 steps
    got = on_the_recorded_trace("debug-moe.rehearse", batch)
    assert got == pytest.approx(100 * step_bytes / 819e9 / step_s, rel=1e-12)
    # and not what the dense count would have given the same file
    dense = shapes.decode_step_bytes(cfg, batch, 101 * batch)
    assert dense < step_bytes
    assert got != pytest.approx(100 * dense / 819e9 / step_s, rel=0.1)


def test_moe_shapes_count_held_routed_and_touched_experts():
    cfg = manifest.load_json("configs", "debug-moe.json")
    assert shapes_moe.weight_bytes(cfg) == 2 * (12288 + 512 + 4 * 18432) \
        + 2 * 32768 == 238592
    assert shapes_moe.kv_bytes_per_token(cfg) == 256
    assert shapes_moe.pool_bytes(cfg) == 256 * 64 * 64
    assert shapes_moe.experts_touched(cfg, 0) == 0.0
    assert shapes_moe.experts_touched(cfg, 1) == 2.0       # k of them
    assert 3.99 < shapes_moe.experts_touched(cfg, 32) < 4.0
    # a token computes with its k = 2 experts, whatever the batch
    per_token = 2 * (12288 + 256 + 2 * 18432) + 256 * 64
    assert shapes_moe.decode_step_flops(cfg, 8, 0) == 2.0 * per_token * 8
    assert shapes_moe.prefill_flops(cfg, 1) == 2.0 * (
        per_token + 2 * 4 * 16 * 2 * 1)


@pytest.mark.parametrize("name,empty", [
    ("compiles_in_window", dict(after=[], before=[])),
    ("decode_occupancy", dict(polls=[])),
    ("gap_p99_ms", dict(records=[])),
    ("router_503.count", dict(router_polls=[])),
    ("queue_wait_p95_ms", dict(spans={})),
    ("prefill_span_p50_ms", dict(spans={})),
    ("decode_step_ms.lat", dict(trace={"devices": {}})),
    ("decode_hbm_share", dict(trace=None)),
])
def test_a_reader_with_nothing_to_read_returns_nothing(name, empty):
    assert read(name, context(**empty)) is None


def test_shapes_give_the_bytes_the_server_reports():
    cfg = manifest.load_json("configs", "mistral-7b.json")
    assert shapes.kv_bytes_per_token(cfg) == 131072
    assert shapes.weight_bytes(cfg) == 7503609856     # PR 21's expected
    assert shapes.pool_bytes(cfg) == 6450839552
    # and through the loader, which gives this file the dense counts
    assert manifest.shapes_of(cfg) is shapes
    # a window clips what a long context reads
    assert shapes.attended(cfg, 5000) == 4096
    assert shapes.prefill_flops(cfg, 1024) > 2 * 7e9 * 1024


@pytest.mark.parametrize("asked, spans, want", [
    (1.5, [1.42], 1.5),             # idle at the edges is the window's
    (1.5, [1.500823604], 1.500823604),  # a chip that never idles: stopping
    (1.5, [1.2, 1.5004, 1.49], 1.5004),  # the profiler took a moment more
])
def test_the_traced_window_is_never_shorter_than_what_the_device_showed(
        asked, spans, want):
    devs = {f"/device:TPU:{i}": {"span_s": s, "busy_s": s}
            for i, s in enumerate(spans)}
    window = traced_window_s(asked, devs)
    assert window == want
    busy = [d["busy_s"] for d in devs.values()]
    assert 0 < sum(busy) / len(busy) <= window
