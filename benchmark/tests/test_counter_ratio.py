"""The ``counter_ratio`` reader and the eight per-layer metrics of the
dispatch timeline, on hand-made samples: what each reads, the window as
a denominator, and nothing (not zero) where the program exports no such
series or span, as the program before these metrics does not."""

import time

import pytest

from harness import manifest
from harness.cell import Context

BENCH = manifest.load_benchmark()
CELL = manifest.load_cell("mistral-7b.chat")
RATIO = manifest.load_reader("per_layer", "counter_ratio")

# the eight, BY NAME: where later metrics stand in ``per_layer`` is not
# this file's business
EIGHT = ["first_token_behind_p50_ms", "first_token_device_p50_ms",
         "first_token_emit_p50_ms", "prefill_dispatch_ms",
         "decode_dispatch_ms", "prefill_chip_share", "window_idle_share",
         "kv_pages_live_share"]
# what ``per_layer`` began with, before them
FIRST = ["router_503.count", "queue_wait_p95_ms", "gap_p99_ms",
         "decode_occupancy", "prefill_span_p50_ms", "decode_step_ms.lat",
         "decode_hbm_share", "compiles_in_window"]


def series(dispatches, device_s, idle_s):
    """/metrics samples of the dispatch counters: by kind, then by host."""
    out = []
    for kind, n in dispatches.items():
        out.append(("llm_dispatches_total", {"kind": kind}, float(n)))
    for kind, s in device_s.items():
        out.append(("llm_dispatch_device_seconds_total", {"kind": kind}, s))
    for host, s in idle_s.items():
        out.append(("llm_device_idle_seconds_total", {"host": host}, s))
    return out


def context(**kw):
    w0 = time.monotonic() - 60.0
    wall = time.time() - time.monotonic()
    base = dict(
        cell=CELL, bench=BENCH, window=(w0, w0 + 50.0), records=[],
        # the counters run from the server's start: only what the window
        # added counts
        before=series({"prefill": 100, "decode": 1000, "chunk": 3, "spec": 0},
                      {"prefill": 7.0, "decode": 64.0, "chunk": 1.0,
                       "spec": 0.0},
                      {"no_work": 30.0, "compile": 9.0, "scheduling": 0.5}),
        after=series({"prefill": 300, "decode": 1700, "chunk": 3, "spec": 0},
                     {"prefill": 17.0, "decode": 104.0, "chunk": 1.0,
                      "spec": 0.0},
                     {"no_work": 30.0, "compile": 9.0, "scheduling": 1.0}),
        polls=[(w0 + 1, [("llm_kv_pages_live", {}, 100.0)]),
               (w0 + 2, [("llm_kv_pages_live", {}, 284.5)]),
               (w0 + 55, [("llm_kv_pages_live", {}, 769.0)])],
        router_polls=[],
        spans={"a": {"started": wall + w0 + 5, "spans": [
                   {"name": "prefill", "duration_ms": 300.0},
                   {"name": "prefill.pack", "duration_ms": 2.0},
                   {"name": "prefill.behind", "duration_ms": 120.0},
                   {"name": "prefill.device", "duration_ms": 150.0},
                   {"name": "prefill.emit", "duration_ms": 28.0}]},
               "b": {"started": wall + w0 + 6, "spans": [
                   {"name": "prefill", "duration_ms": 100.0},
                   {"name": "prefill.pack", "duration_ms": 1.0},
                   {"name": "prefill.behind", "duration_ms": 20.0},
                   {"name": "prefill.device", "duration_ms": 50.0},
                   {"name": "prefill.emit", "duration_ms": 29.0}]},
               "late": {"started": wall + w0 + 52, "spans": [
                   {"name": "prefill.behind", "duration_ms": 9000.0}]}},
        trace=None, trace_window=(None, None), peaks={}, censor_at=w0 + 80.0,
        setup_s=200.0)
    base.update(kw)
    return Context(**base)


@pytest.mark.parametrize("name,want", [
    ("first_token_behind_p50_ms", 70.0),    # 20 and 120; "late" is out
    ("first_token_device_p50_ms", 100.0),
    ("first_token_emit_p50_ms", 28.5),
    ("prefill_dispatch_ms", 50.0),          # 10 s over 200 dispatches
    ("decode_dispatch_ms", 14.2857),        # 40 s over 700, over K = 4
    ("prefill_chip_share", 20.0),           # 10 s of 50 s busy
    ("window_idle_share", 1.0),             # 0.5 s of a 50 s window
    ("kv_pages_live_share", 25.0),          # mean 192.25 of 769 pages
])
def test_the_eight_metrics(name, want):
    got = manifest.read_metric("per_layer", name, context())
    assert got == pytest.approx(want, rel=1e-4)


@pytest.mark.parametrize("name", EIGHT)
def test_nothing_to_read_in_an_older_program(name):
    """The program before this change exports none of the counters, the
    gauge or the spans: every reader returns None and does not raise, and
    the line leaves the metric out."""
    old = context(before=[("llm_jit_compiles_total", {}, 36.0)],
                  after=[("llm_jit_compiles_total", {}, 38.0)],
                  polls=[(time.monotonic() - 59.0,
                          [("llm_kv_pages_used", {}, 768.0)])],
                  spans={"a": {"started": time.time() - 55.0, "spans": [
                      {"name": "prefill", "duration_ms": 300.0}]}})
    assert manifest.read_metric("per_layer", name, old) is None


def test_the_eight_are_entries_appended_after_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:8] == FIRST
    assert set(EIGHT) <= set(names[8:])
    for m in BENCH["per_layer"]:
        if m["name"] in EIGHT:
            assert "mistral-7b.chat" in m["workloads"]


def test_ratio_of_two_counters_and_of_summed_series():
    ctx = context()
    dev = {"metric": "llm_dispatch_device_seconds_total"}
    n = {"metric": "llm_dispatches_total"}
    # no labels: every series of the name
    assert RATIO.read(ctx, dev, n, scale=1000.0) == pytest.approx(
        1000.0 * 50.0 / 900.0)
    # a list on either side is summed
    pre = dict(dev, labels={"kind": "prefill"})
    chunk = dict(dev, labels={"kind": "chunk"})
    dec = dict(dev, labels={"kind": "decode"})
    assert RATIO.read(ctx, [pre, chunk], [pre, chunk, dec]) == \
        pytest.approx(0.2)
    assert RATIO.read(ctx, pre, dict(n, labels={"kind": "prefill"}),
                      per_decode_step=True) == pytest.approx(0.05 / 4)


def test_the_window_as_denominator():
    ctx = context(window=(1000.0, 1025.0))
    idle = {"metric": "llm_device_idle_seconds_total"}
    assert RATIO.read(ctx, idle, "window_s", scale=100.0) == \
        pytest.approx(2.0)
    assert RATIO.read(ctx, dict(idle, labels={"host": "no_work"}),
                      "window_s") == 0.0


def test_none_when_a_counter_is_missing_or_the_denominator_stood_still():
    ctx = context()
    dev = {"metric": "llm_dispatch_device_seconds_total"}
    assert RATIO.read(ctx, {"metric": "llm_no_such_total"}, dev) is None
    assert RATIO.read(ctx, dev, {"metric": "llm_no_such_total"}) is None
    assert RATIO.read(ctx, [dev, {"metric": "llm_no_such_total"}],
                      "window_s") is None
    # no chunk dispatch in the window: nothing to divide by, not a zero
    chunk = {"metric": "llm_dispatches_total", "labels": {"kind": "chunk"}}
    assert RATIO.read(ctx, dev, chunk) is None
    # the series is there but was absent before the window: it grew from 0
    ctx = context(before=[])
    assert RATIO.read(ctx, dev, "window_s") == pytest.approx(122.0 / 50.0)
