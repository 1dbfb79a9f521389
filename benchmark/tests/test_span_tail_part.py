"""The ``span_tail_part`` reader and the six per-layer metrics that say
where a slow request's token gap goes, on hand-made traces: which
requests are the tail, the division by ``tokens`` - 1, a child span as a
part, the window's bounds, and nothing (not zero) where the program
carries no such attribute, span or series, as the program before these
metrics does not."""

import time

import pytest

from harness import manifest
from harness.cell import Context

BENCH = manifest.load_benchmark()
CELL = manifest.load_cell("mistral-7b.chat")
TAIL = manifest.load_reader("per_layer", "span_tail_part")

PARTS = ["tpot_tail_ride_ms", "tpot_tail_prefill_ms", "tpot_tail_other_ms",
         "tpot_tail_emit_ms"]
SIX = ["decode_emit_ms", "tpot_tail_span_ms"] + PARTS


def trace(started, tokens, ride, prefill, other, emit, parts=True,
          sid="d0"):
    """One finished request: a ``decode`` span of ``tokens`` whose four
    parts (ms) sum to it, ``decode.emit`` as its child; beside them a
    ``prefill`` span and a child of THAT with the same name, which is
    nobody's part."""
    dec = {"name": "decode", "span_id": sid, "parent_span_id": "root",
           "tokens": tokens, "chip_ms": 1.0,
           "duration_ms": ride + prefill + other + emit}
    spans = [{"name": "prefill", "span_id": "p0", "duration_ms": 70.0},
             {"name": "decode.emit", "parent_span_id": "p0",
              "duration_ms": 999.0},
             dec]
    if parts:
        dec.update(ride_ms=ride, prefill_ms=prefill, other_ms=other,
                   idle_ms=other / 2)
        spans.append({"name": "decode.emit", "parent_span_id": sid,
                      "duration_ms": emit})
    return {"started": started, "spans": spans}


def context(parts=True, emit_series=True, **kw):
    w0 = time.monotonic() - 60.0
    wall = time.time() - time.monotonic()
    # twenty requests of 11 tokens at 12 ms a token, all of it ridden; the
    # two slow ones (the tail from the 90th percentile: 20.0 and 30.0 ms a
    # token) also waited for prefills, a window they did not ride, the
    # hand-over. One request of a single token has no gap to divide, one
    # that finished after the window is nobody's
    spans = {f"r{i}": trace(wall + w0 + 1 + i, 11, 120.0, 0.0, 0.0, 0.0,
                            parts) for i in range(18)}
    spans["slow"] = trace(wall + w0 + 30, 11, 130.0, 50.0, 15.0, 5.0, parts)
    spans["slower"] = trace(wall + w0 + 31, 5, 60.0, 40.0, 12.0, 8.0, parts)
    spans["one"] = trace(wall + w0 + 32, 1, 0.0, 0.0, 0.0, 900.0, parts)
    spans["late"] = trace(wall + w0 + 52, 3, 0.0, 9000.0, 0.0, 0.0, parts)
    counters = [("llm_dispatches_total", {"kind": "decode"}, 1000.0),
                ("llm_dispatches_total", {"kind": "prefill"}, 100.0)]
    after = [("llm_dispatches_total", {"kind": "decode"}, 1500.0),
             ("llm_dispatches_total", {"kind": "prefill"}, 300.0)]
    if emit_series:
        counters += [("llm_decode_emit_seconds_total", {"kind": "decode"},
                      2.0),
                     ("llm_decode_emit_seconds_total", {"kind": "spec"}, 0.0)]
        after += [("llm_decode_emit_seconds_total", {"kind": "decode"}, 2.6),
                  ("llm_decode_emit_seconds_total", {"kind": "spec"}, 0.0)]
    base = dict(cell=CELL, bench=BENCH, window=(w0, w0 + 50.0), records=[],
                before=counters, after=after, polls=[], router_polls=[],
                spans=spans)
    base.update(kw)
    return Context(**base)


def read(name, ctx):
    return manifest.read_metric("per_layer", name, ctx)


@pytest.mark.parametrize("name,want", [
    # (200 / 10 + 120 / 4) / 2
    ("tpot_tail_span_ms", 25.0),
    ("tpot_tail_ride_ms", (13.0 + 15.0) / 2),
    ("tpot_tail_prefill_ms", (5.0 + 10.0) / 2),
    ("tpot_tail_other_ms", (1.5 + 3.0) / 2),
    # the decode span's own child, not the prefill span's of that name
    ("tpot_tail_emit_ms", (0.5 + 2.0) / 2),
    # 0.6 s over the window's 500 decode windows
    ("decode_emit_ms", 1.2),
])
def test_each_metric_reads_its_part_of_the_slow_tail(name, want):
    assert read(name, context()) == pytest.approx(want, rel=1e-9)


def test_the_four_parts_add_up_to_the_span():
    ctx = context()
    assert sum(read(n, ctx) for n in PARTS) == pytest.approx(
        read("tpot_tail_span_ms", ctx), rel=1e-9)


@pytest.mark.parametrize("from_pct,want", [
    (0, (18 * 12.0 + 20.0 + 30.0) / 20),    # every request with a gap
    (95, 30.0),                             # the slowest alone
    (100, 30.0),
])
def test_the_tail_starts_at_the_percentile_it_is_given(from_pct, want):
    got = TAIL.read(context(), span="decode", part="duration_ms",
                    from_pct=from_pct)
    assert got == pytest.approx(want, rel=1e-9)


def test_a_request_of_one_token_and_one_outside_the_window_are_left_out():
    ctx = context()
    alone = {k: ctx.spans[k] for k in ("one", "late")}
    assert TAIL.read(context(spans=alone), span="decode",
                     part="duration_ms", from_pct=90) is None
    # and the window's bounds are span_percentile's: started, half open
    first = manifest.load_reader("per_layer", "span_percentile")
    assert first.read(context(spans=alone), span="decode", pct=50) == 900.0


@pytest.mark.parametrize("name", PARTS + ["decode_emit_ms"])
def test_the_program_before_them_reads_nothing_not_zero(name):
    """The parent's traces carry ``tokens`` and ``chip_ms`` on a
    ``decode`` span and nothing more; its exposition has no such series.
    The span's own length it has (under the older end)."""
    old = context(parts=False, emit_series=False)
    assert read(name, old) is None
    assert read("tpot_tail_span_ms", old) == pytest.approx(25.0)


def test_no_trace_in_the_window_reads_nothing():
    assert all(read(n, context(spans={})) is None for n in SIX[1:])


@pytest.mark.parametrize("name", SIX)
def test_each_file_loads_and_is_listed_for_both_cells(name):
    spec = manifest.load_json("layer_metrics", f"{name}.json")
    assert spec["moves"] == "tpot_p95_ms" and spec["unit"] == "ms"
    manifest.load_reader("per_layer", spec["reader"])
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["moves"] == spec["moves"] and entry["layer"] == spec["layer"]
    assert entry["source"] == spec["source"]
    assert {"mistral-7b.chat",
            "lfm2-24b-a2b.long-answers"} <= set(entry["workloads"])
    for cell in entry["workloads"]:
        assert entry in manifest.metrics_of(BENCH, cell, "per_layer")
