"""Every per-layer metric's reader on a hand-made context: what it reads,
and that it returns nothing (not zero) where there is nothing to read."""

import time

import pytest

from harness import manifest, shapes
from harness.cell import Context
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
])
def test_reader_values(name, want):
    assert read(name, context()) == pytest.approx(want, rel=1e-3)


@pytest.mark.parametrize("name,want", [
    ("ttft_p50_ms", 39150.0),     # 300 ms and a refused request's 78 s
    ("ttft_p95_ms", 74115.0),
    ("tpot_p95_ms", 166.667),     # 500 ms over the 3 tokens after the first
    ("setup_s", 200.0),
])
def test_end_to_end_reader_values(name, want):
    assert read(name, context(), "end_to_end") == pytest.approx(want,
                                                                rel=1e-3)


def test_decode_hbm_share_is_needed_bytes_over_peak_over_step_time():
    # one stream decoding through the whole traced window at ~101 tokens
    got = read("decode_hbm_share", context())
    cfg = CELL.config
    need = shapes.decode_step_bytes(cfg, 1, 101) / 819e9
    assert got == pytest.approx(100 * need / 0.016, rel=0.02)
    assert 50 < got < 65        # 7.5 GB of weights alone are 9.2 ms


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
    # a window clips what a long context reads
    assert shapes.attended(cfg, 5000) == 4096
    assert shapes.prefill_flops(cfg, 1024) > 2 * 7e9 * 1024
