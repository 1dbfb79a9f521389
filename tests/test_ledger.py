"""Goodput ledger (PR 15): per-request chip-time attribution, MFU/MBU
accounting, and the anomaly-triggered auto-profiler.

- unit level: the conservation identity (attributed + wasted + idle ==
  ledger window) under fused K=4 windows, speculative rejected tails,
  early-exit rows, and all-rows-dropped dispatches; per-request shares
  are weighted by planned window tokens and per-tenant sums equal
  per-request sums; FLOPs count planned (wasted included) tokens;
- detector level: the EWMA + z-score watchdog never fires on steady
  load, fires after ``sustain`` consecutive anomalous samples, honors
  its cooldown as the capture rate limit, and keeps its baseline
  unpoisoned by the anomaly it is measuring;
- engine level: a mixed multi-tenant LoRA batch attributes every
  dispatch (tenant sums == request sums), speculative rejected tails
  book as ``spec_waste``, and greedy streams are bit-identical with the
  ledger on or off;
- server level: usage.chip_ms + the X-LLMK-Chip-Ms header, trace spans
  and flight frames carrying chip time, the /metrics series, and an
  injected ``slow_step`` fault producing exactly ONE rate-limited
  profiler capture (``llm_auto_profile_total{reason="step_anomaly"}``).
"""

import asyncio
import itertools
import threading
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llms_on_kubernetes_tpu.configs import get_config
from llms_on_kubernetes_tpu.engine.engine import (
    Engine, EngineConfig, SamplingParams, _Harvester,
)
from llms_on_kubernetes_tpu.engine.ledger import (
    IDLE_HOSTS, MAX_OPEN, PHASES, GoodputLedger, StepAnomalyDetector,
    detect_peak,
)
from test_adapters import write_peft


# ---------------------------------------------------------------------------
# unit: attribution math + conservation identity
# ---------------------------------------------------------------------------

class _Req:
    """Duck-typed stand-in for engine.Request in ledger unit tests."""

    def __init__(self, tenant=""):
        self.tenant = tenant
        self.chip_ms = {}


def _ledger(**kw):
    kw.setdefault("peak_flops", 1e12)
    kw.setdefault("peak_bytes_s", 1e11)
    return GoodputLedger(get_config("debug-tiny"), **kw)


_seqs = itertools.count()


def _open(led, t_launch, kind="decode", rows=None, enqueue_s=0.0,
          retraced=False, after_no_work=False):
    """A dispatch launched at ``t_launch`` and not yet read; its seq."""
    seq = next(_seqs)
    rec = led.open(seq, kind, f"_{kind}_step", "1x1", t_launch - enqueue_s,
                   rows=rows, after_no_work=after_no_work)
    led.launched(rec, t_launch, retraced=retraced)
    return seq


def _record(led, t_launch, t_done, rows, window=1, kind="decode"):
    """One dispatch, read as soon as it is launched (launch order and
    read order agree)."""
    led.close(_open(led, t_launch, kind), t_done, rows, window)


def _booked(led):
    """seq -> the booked record's /debug/engine view."""
    return {d["seq"]: d for d in led.dispatches_view(2048)}


def _conserves(snap):
    total = snap["attributed_ms"] + snap["wasted_ms"] + snap["idle_ms"]
    assert total == pytest.approx(snap["window_ms"], rel=1e-9, abs=1e-6)
    assert snap["busy_ms"] == pytest.approx(
        snap["attributed_ms"] + snap["wasted_ms"], rel=1e-9, abs=1e-6)


def test_fused_window_attribution_and_conservation():
    """K=4 windows: overlapping dispatches segment on completion spacing,
    gaps book as idle, and each segment splits across rows weighted by
    planned window tokens."""
    led = _ledger()
    a, b = _Req("t-a"), _Req("t-b")
    # dispatch 1: launch 0.0, done 0.1 -> 100 ms busy
    _record(led, 0.0, 0.1, [(a, "decode", 4), (b, "decode", 4)], window=4)
    # dispatch 2 launched while 1 was in flight: its busy segment is
    # 0.1 -> 0.2 (the device runs dispatches serially), never 0.05 -> 0.2
    _record(led, 0.05, 0.2, [(a, "decode", 4), (b, "decode", 4)], window=4)
    # 100 ms gap, then a window where `a` early-exits after 2 of 4 rows
    _record(led, 0.3, 0.35,
               [(a, "decode", 2), (a, "early_exit", 2), (b, "decode", 4)],
               window=4)

    snap = led.snapshot()
    assert snap["window_ms"] == pytest.approx(350.0)
    assert snap["idle_ms"] == pytest.approx(100.0)
    assert snap["busy_ms"] == pytest.approx(250.0)
    _conserves(snap)
    # dispatch 3: 50 ms over 8 planned tokens = 6.25 ms/token
    assert a.chip_ms["decode"] == pytest.approx(50 + 50 + 12.5)
    assert a.chip_ms["early_exit"] == pytest.approx(12.5)
    assert b.chip_ms["decode"] == pytest.approx(50 + 50 + 25)
    # per-tenant sums == per-request sums, phase by phase
    assert snap["tenant_ms"][("t-a", "decode")] == pytest.approx(112.5)
    assert snap["tenant_ms"][("t-a", "early_exit")] == pytest.approx(12.5)
    assert snap["tenant_ms"][("t-b", "decode")] == pytest.approx(125.0)
    assert snap["decode_tokens"] == 4 + 4 + 4 + 4 + 2 + 4
    assert snap["dispatches"] == 3


def test_spec_rejected_tail_books_waste_but_keeps_flops():
    """A rejected speculative tail is wasted chip time billed to the
    stream that speculated — but its FLOPs were really executed, so the
    MFU numerator keeps them."""
    led = _ledger()
    r = _Req("spec-tenant")
    _record(led, 0.0, 0.08, [(r, "decode", 2), (r, "spec_waste", 2)], window=4)
    snap = led.snapshot()
    _conserves(snap)
    assert snap["phase_ms"]["decode"] == pytest.approx(40.0)
    assert snap["phase_ms"]["spec_waste"] == pytest.approx(40.0)
    assert snap["wasted_ms"] == pytest.approx(40.0)
    assert r.chip_ms["spec_waste"] == pytest.approx(40.0)
    # only consumed tokens count as goodput...
    assert snap["decode_tokens"] == 2
    # ...but all 4 planned rows were computed
    assert snap["flops"] == pytest.approx(led.flops_per_token * 4)
    assert snap["hbm_bytes"] == pytest.approx(
        led.param_bytes * 4 + led.kv_bytes_per_token * 4)


def test_zero_row_dispatch_still_conserves():
    """Every slot finished mid-flight: the dispatch still burned chip
    time, which must book as waste — not leak out of the identity."""
    led = _ledger()
    _record(led, 0.0, 0.05, [])
    snap = led.snapshot()
    _conserves(snap)
    assert snap["phase_ms"]["early_exit"] == pytest.approx(50.0)
    assert snap["flops"] == 0.0  # nothing was planned, nothing computed
    assert snap["tenant_ms"][("", "early_exit")] == pytest.approx(50.0)


def test_attribution_fuzz_conservation():
    """Property: for ANY sequence of dispatches (overlapping launches,
    mixed phases, random weights) the identity holds exactly."""
    rng = np.random.default_rng(7)
    led = _ledger()
    reqs = [_Req(f"t{i}") for i in range(5)]
    t = 0.0
    for _ in range(200):
        t_launch = t - rng.uniform(0.0, 0.02)  # launched while busy
        t = t + rng.uniform(0.0, 0.01)         # completion spacing
        rows = [(reqs[rng.integers(5)], PHASES[rng.integers(4)],
                 int(rng.integers(0, 5)))
                for _ in range(int(rng.integers(1, 4)))]
        _record(led, t_launch, t, rows, window=int(rng.integers(1, 5)))
    snap = led.snapshot()
    _conserves(snap)
    # per-request sums == per-tenant sums == phase totals
    req_total = sum(v for r in reqs for v in r.chip_ms.values())
    ten_total = sum(v for (ten, _ph), v in snap["tenant_ms"].items() if ten)
    assert req_total == pytest.approx(ten_total, rel=1e-9)


def test_prefill_read_before_the_decode_launched_ahead_of_it():
    """The device runs dispatches in LAUNCH order; reads are collected in
    another: a first token's priority read is booked before the decode
    step launched ahead of it. Each keeps its own segment: the decode
    step its 60 ms, the prefill the 40 ms after it (booking in the order
    of the calls gave the prefill 100 ms and the decode step about 0)."""
    led = _ledger()
    a, b = _Req("a"), _Req("b")
    _record(led, 0.0, 0.10, [(a, "decode", 4)], window=4)
    dec = _open(led, 0.05)                                  # queued behind #1
    pre = _open(led, 0.06, "prefill", rows=[(b, "prefill", 16)])
    led.close(pre, 0.20)                                    # read first...
    assert led.snapshot()["dispatches"] == 1                # ...booked later
    led.close(dec, 0.21, [(a, "decode", 4)], window=4)     # a late, batched stamp
    booked = _booked(led)
    # the decode step cannot have ended after the prefill behind it was read
    assert booked[dec]["device_ms"] == pytest.approx(100.0)
    assert booked[dec]["behind_ms"] == pytest.approx(50.0)
    assert booked[pre]["device_ms"] == pytest.approx(0.0, abs=1e-6)
    snap = led.snapshot()
    _conserves(snap)
    assert snap["window_ms"] == pytest.approx(200.0)
    # with the decode step's own read known to be earlier, both get theirs
    led2 = _ledger()
    b = _Req("b")
    _record(led2, 0.0, 0.10, [(a, "decode", 4)], window=4)
    dec = _open(led2, 0.05)
    pre = _open(led2, 0.06, "prefill", rows=[(b, "prefill", 16)])
    led2.close(pre, 0.20)
    led2.close(dec, 0.16, [(a, "decode", 4)], window=4)
    booked = _booked(led2)
    assert booked[dec]["device_ms"] == pytest.approx(60.0)
    assert booked[pre]["device_ms"] == pytest.approx(40.0)
    assert booked[pre]["behind_ms"] == pytest.approx(100.0)  # 0.06 -> 0.16
    assert b.chip_ms["prefill"] == pytest.approx(40.0)
    # the request's timestamps come off its prefill's record
    assert b.prefill_launched_at == pytest.approx(0.06)
    assert b.prefill_started_at == pytest.approx(0.16)
    assert b.prefill_read_at == pytest.approx(0.20)
    snap = led2.snapshot()
    _conserves(snap)
    assert snap["kinds"]["prefill"] == {
        "dispatches": 1, "device_ms": pytest.approx(40.0),
        "behind_ms": pytest.approx(100.0), "enqueue_ms": 0.0}
    assert snap["kinds"]["decode"]["dispatches"] == 2


class _SlowResult:
    """A device result whose host copy lands ``delay`` seconds after it is
    asked for (``jax.device_get`` calls ``__array__``)."""

    def __init__(self, delay):
        self.delay = delay

    def copy_to_host_async(self):
        pass

    def __array__(self, *a, **kw):
        time.sleep(self.delay)
        return np.zeros((1,), np.int32)


class _OnDevice(_SlowResult):
    """Complete on the device ``delay`` s after the one before it (the
    watcher waits on them in launch order); its host copy is instant."""

    def block_until_ready(self):
        time.sleep(self.delay)
        return self

    def __array__(self, *a, **kw):
        return np.zeros((1,), np.int32)


def test_three_queued_steps_yield_three_segments():
    """The harvester waits on results one at a time in launch order and
    stamps each when ITS result is complete: three steps queued behind a
    slow read still book three segments (read as one batch, with one
    stamp, the first step took all of it and the others nothing)."""
    hv = _Harvester()
    gate = threading.Event()

    class _Gate(_SlowResult):
        def __array__(self, *a, **kw):
            gate.wait(5.0)      # hold the reader until all three are queued
            return super().__array__()

    hv.start()
    try:
        hv.push(-100, _Gate(0.0))
        led = _ledger()
        r = _Req()
        seqs = [_open(led, time.monotonic()) for _ in range(3)]
        for i in range(3):
            hv.push(i, _OnDevice(0.03))
        time.sleep(0.05)
        assert not hv.is_done(0)
        gate.set()
        hv.wait_done(2, timeout_s=10.0)
        stamps = [hv.done_time(i) for i in range(3)]
        assert stamps[0] < stamps[1] < stamps[2]
        for seq, t in zip(seqs, stamps):
            led.close(seq, t, [(r, "decode", 4)], window=4)
    finally:
        hv.stop()
    booked = _booked(led)
    assert all(booked[s]["device_ms"] >= 25.0 for s in seqs[1:]), booked
    _conserves(led.snapshot())


def test_a_slow_read_does_not_move_a_dispatch_s_end():
    """A dispatch's end is when its result was complete on the device, not
    when its host copy landed: a decode step whose read takes 100 ms
    keeps its own 40 ms segment, and the prefill launched behind it the
    next 40 — by the reads' landing the step took both and more."""
    hv = _Harvester()

    class _SlowCopy(_OnDevice):
        def __array__(self, *a, **kw):
            time.sleep(0.1)
            return super().__array__()

    hv.start()
    try:
        led = _ledger()
        a, b = _Req("a"), _Req("b")
        t0 = time.monotonic()
        dec = _open(led, t0)
        pre = _open(led, t0, "prefill", rows=[(b, "prefill", 16)])
        hv.push(0, _SlowCopy(0.04))                     # the decode step
        hv.push(-1 - pre, _OnDevice(0.04))              # the prefill
        hv.wait_key(-1 - pre, timeout_s=10.0)
        assert hv.is_done(0)
        t_read = time.monotonic()
        t_dec, t_pre = hv.done_time(0), hv.done_time(-1 - pre)
        assert t0 + 0.03 < t_dec < t0 + 0.09 < t_read
        assert t_dec + 0.1 < t_pre <= t_read
        led.close(pre, t_pre)                           # collected first
        led.close(dec, t_dec, [(a, "decode", 4)], window=4)
        hv.discard_key(-1 - pre)
        hv.discard_upto(0)
        assert not hv._done
    finally:
        hv.stop()
    booked = _booked(led)
    assert booked[dec]["device_ms"] == pytest.approx(40.0, abs=12.0)
    assert "end_clamped" not in booked[dec]
    _conserves(led.snapshot())


def test_identity_holds_with_out_of_order_closes():
    """Property: whatever order the reads are collected in, unread
    dispatches among them, every record is booked once, in launch order,
    and the identity holds exactly."""
    rng = np.random.default_rng(11)
    led = _ledger()
    reqs = [_Req(f"t{i}") for i in range(4)]
    t = 0.0
    pending, n = [], 0
    for _ in range(300):
        t += rng.uniform(0.0, 0.01)
        kind = ("prefill", "decode", "chunk")[int(rng.integers(3))]
        seq = _open(led, t, kind)
        n += 1
        if rng.uniform() < 0.15:
            led.close(seq, None, [(reqs[0], "prefill", 8)])   # nobody reads it
        else:
            pending.append(seq)
        rng.shuffle(pending)
        while pending and rng.uniform() < 0.6:
            t += rng.uniform(0.0, 0.01)
            rows = [(reqs[int(rng.integers(4))], PHASES[int(rng.integers(4))],
                     int(rng.integers(0, 5)))
                    for _ in range(int(rng.integers(1, 4)))]
            led.close(pending.pop(), t, rows, window=int(rng.integers(1, 5)))
    for seq in pending:
        t += 0.001
        led.close(seq, t, [(reqs[1], "decode", 1)])
    snap = led.snapshot()
    assert snap["dispatches"] == n and not led._open
    assert sum(k["dispatches"] for k in snap["kinds"].values()) == n
    _conserves(snap)
    assert sum(k["device_ms"] for k in snap["kinds"].values()) == \
        pytest.approx(snap["busy_ms"])
    assert sum(snap["idle_host_ms"].values()) == pytest.approx(
        snap["idle_ms"])
    booked = led.dispatches_view(2048)
    assert [d["seq"] for d in booked] == sorted(d["seq"] for d in booked)
    assert all(d["device_ms"] >= 0.0 and d["behind_ms"] >= 0.0
               for d in booked)


def test_a_clamped_end_is_marked_on_the_record():
    """A decode step whose own read lands after the read of the prefill
    launched behind it takes both dispatches' time and the prefill 0: the
    record says so (``end_clamped``), so a reader of /debug/engine can
    count how often the split between the two is not known."""
    led = _ledger()
    a, b = _Req("a"), _Req("b")
    _record(led, 0.0, 0.10, [(a, "decode", 4)], window=4)
    dec = _open(led, 0.05)
    pre = _open(led, 0.06, "prefill", rows=[(b, "prefill", 16)])
    unread = _open(led, 0.07, "prefill", rows=[(a, "prefill", 8)])
    led.close(pre, 0.20)
    led.close(unread, None)                 # a re-prefill nobody reads
    led.close(dec, 0.21, [(a, "decode", 4)], window=4)
    nxt = _open(led, 0.08)
    led.close(nxt, 0.30, [(a, "decode", 4)], window=4)
    booked = _booked(led)
    assert booked[dec].get("end_clamped") is True
    assert booked[dec]["device_ms"] == pytest.approx(100.0)
    assert "end_clamped" not in booked[pre]
    assert booked[pre]["device_ms"] == pytest.approx(0.0, abs=1e-6)
    assert booked[unread].get("end_clamped") is True    # the next read's time
    assert "end_clamped" not in booked[nxt]
    _conserves(led.snapshot())


def test_a_head_whose_read_never_comes_is_dropped_not_waited_for():
    """A record that is never closed (its read raised on the way) would
    hold every later record unbooked and let the open list grow without
    bound. With MAX_OPEN records launched behind it, it is dropped; its
    time falls to the next segment or to idle, and the identity holds."""
    led = _ledger()
    r = _Req()
    _record(led, 0.0, 0.01, [(r, "decode", 1)])
    lost = _open(led, 0.01)                         # never closed
    t = 0.02
    for i in range(3 * MAX_OPEN):
        seq = _open(led, t)
        led.close(seq, t + 0.005, [(r, "decode", 1)])
        t += 0.01
        assert len(led._open) <= MAX_OPEN
    snap = led.snapshot()
    assert snap["lost"] == 1 and lost not in _booked(led)
    assert snap["dispatches"] == 1 + 3 * MAX_OPEN and not led._open
    _conserves(snap)


def test_abandoning_the_head_books_what_waited_behind_it():
    """A launch that raised is taken out by seq; records closed behind it
    are booked at once, not at the next close."""
    led = _ledger()
    r = _Req()
    head = _open(led, 0.0)
    nxt = _open(led, 0.01)
    led.close(nxt, 0.05, [(r, "decode", 1)])
    assert led.snapshot()["dispatches"] == 0
    led.abandon(head)
    assert led.snapshot()["dispatches"] == 1 and not led._open
    led.abandon()                                   # a wedged device: all
    assert not led._open


def test_jit_events_are_the_process_s_and_no_server_moves_them():
    """The engine reads the count of compiles and cache hits around each
    dispatch from engine/jit_events.py, which knows no server; a server's
    telemetry COPIES the same totals at each scrape. So building a server
    in mid-dispatch cannot move the count (it used to: the early counts
    were folded into the newest server's counters in two steps) and the
    engine module does not import the server's."""
    import inspect

    import jax
    import jax.numpy as jnp

    from llms_on_kubernetes_tpu.engine import engine as engine_mod
    from llms_on_kubernetes_tpu.engine import jit_events
    from llms_on_kubernetes_tpu.server.metrics import Registry
    from llms_on_kubernetes_tpu.server.runtime_telemetry import (
        RuntimeTelemetry,
    )

    assert "runtime_telemetry" not in inspect.getsource(engine_mod)
    jit_events.install()
    jit_events.install()                            # once per process
    before = jit_events.count()
    jax.jit(lambda x: x * 3 + before)(jnp.ones((3,))).block_until_ready()
    moved = jit_events.count()
    assert moved > before                           # a compile or a cache hit
    first = RuntimeTelemetry(Registry())
    second = RuntimeTelemetry(Registry())           # tests build many servers
    assert jit_events.count() == moved
    compiles, seconds, hits = jit_events.totals()
    for tel in (first, second):
        tel.refresh()
        assert tel.metrics["jit_compiles"].value == compiles
        assert tel.metrics["jit_cache_hits"].value == hits
        assert tel.metrics["jit_compile_seconds"].value == pytest.approx(
            seconds)
    jax.jit(lambda x: x * 5 - moved)(jnp.ones((3,))).block_until_ready()
    first.refresh()
    assert (first.metrics["jit_compiles"].value
            + first.metrics["jit_cache_hits"].value) == jit_events.count()
    assert jit_events.count() > moved


def test_idle_host_takes_each_of_its_three_values():
    """What the host was doing in the device's gap before a dispatch:
    it had no work, it was re-tracing the step, or anything else."""
    led = _ledger()
    r = _Req()
    _record(led, 0.0, 0.1, [(r, "decode", 4)])
    # the engine had run out of work before this launch
    led.close(_open(led, 0.3, after_no_work=True), 0.4, [(r, "decode", 4)])
    # the jitted call compiled for 150 ms before it enqueued anything
    led.close(_open(led, 0.6, enqueue_s=0.15, retraced=True), 0.7,
              [(r, "decode", 4)])
    # neither: the host was slow to launch
    led.close(_open(led, 0.75), 0.8, [(r, "decode", 4)])
    # launched while the device was busy: no gap, no label
    led.close(_open(led, 0.78), 0.9, [(r, "decode", 4)])
    hosts = [(d.get("idle_host"), d["idle_before_ms"])
             for d in led.dispatches_view()]
    assert hosts == [(None, 0.0), ("no_work", pytest.approx(200.0)),
                     ("compile", pytest.approx(200.0)),
                     ("scheduling", pytest.approx(50.0)), (None, 0.0)]
    snap = led.snapshot()
    assert set(snap["idle_host_ms"]) == set(IDLE_HOSTS)
    assert snap["idle_host_ms"] == {"no_work": pytest.approx(200.0),
                                    "compile": pytest.approx(200.0),
                                    "scheduling": pytest.approx(50.0)}
    assert led.dispatches_view()[2]["enqueue_ms"] == pytest.approx(150.0)
    assert led.dispatches_view()[2]["retraced"] is True
    _conserves(snap)


@pytest.mark.parametrize("with_ledger", [True, False])
def test_estimates_follow_the_device_from_below(with_ledger):
    """The timeline (the ledger's, or the bare one an engine keeps with
    the ledger off) holds per (kind, shape) the device time a dispatch
    last took: it falls to a shorter sample at once and rises to a longer
    one by 2 % a sample; a record whose end was clamped to a later read,
    and the one booked at 0 behind it, teach nothing."""
    from llms_on_kubernetes_tpu.engine.ledger import DispatchTimeline

    tl = _ledger() if with_ledger else DispatchTimeline()
    assert tl.estimate("decode", "1x1") is None

    def run(t_launch, t_done, kind="decode"):
        tl.close(_open(tl, t_launch, kind), t_done, None)

    run(0.0, 0.070)
    assert tl.estimate("decode", "1x1") == pytest.approx(0.070)
    run(0.0, 0.130)                         # 60 ms: down at once
    assert tl.estimate("decode", "1x1") == pytest.approx(0.060)
    run(0.0, 0.230)                         # 100 ms: up by 2 %
    assert tl.estimate("decode", "1x1") == pytest.approx(0.0612)
    # an unread dispatch takes the time up to the next read (clamped);
    # the next one books at 0: neither moves an estimate
    unread = _open(tl, 0.23, "prefill")
    tl.close(unread, None, None)
    run(0.23, 0.33)
    assert tl.estimate("prefill", "1x1") is None
    assert tl.estimate("decode", "1x1") == pytest.approx(0.0612)
    assert tl.estimates_view() == {"decode 1x1": 61.2}
    tl.reset()                              # what was learned survives
    assert tl.estimate("decode", "1x1") == pytest.approx(0.0612)


def test_a_late_stamp_does_not_shorten_the_next_estimate():
    """A completion stamped late makes its own segment long and the next
    one short by as much; the short one is given back what the one
    before it ran over its estimate, so a minimum does not keep it (on
    the chip a 67 ms window was booked at 1.9 ms behind a late stamp)."""
    from llms_on_kubernetes_tpu.engine.ledger import DispatchTimeline

    tl = DispatchTimeline()

    def run(t_launch, t_done):
        tl.close(_open(tl, t_launch), t_done, None)

    run(0.0, 0.067)
    run(0.0, 0.134)
    assert tl.estimate("decode", "1x1") == pytest.approx(0.067)
    run(0.0, 0.266)         # done at 0.201, stamped 65 ms late
    run(0.0, 0.268)         # on time: 2 ms after the late stamp
    assert tl.estimate("decode", "1x1") == pytest.approx(0.067)
    # a dispatch launched onto an idle device started on its own launch,
    # not on the stamp before it: nothing is given back
    run(0.400, 0.460)
    assert tl.estimate("decode", "1x1") == pytest.approx(0.060)
    # and a device that really got slower is followed by the drift
    # alone: its longer segments are not added to each other
    for k in range(5):
        run(0.0, 0.560 + 0.1 * k)
    assert tl.estimate("decode", "1x1") == pytest.approx(0.060 * 1.02 ** 5)


def test_free_at_adds_estimates_to_the_newest_completion():
    """free_at: the newest completion known (booked, closed, or seen by
    the harvester and passed in) plus the estimates of what was launched
    after it; the dispatch on the device now cannot end before now; None
    while a shape that never ran is still ahead."""
    from llms_on_kubernetes_tpu.engine.ledger import DispatchTimeline

    tl = DispatchTimeline()
    assert tl.free_at(5.0, {}) == (5.0, False)      # nothing ever launched
    first = _open(tl, 0.0)
    assert tl.free_at(0.01, {}) is None             # never ran
    tl.close(first, 0.067, None)
    assert tl.free_at(0.1, {}) == (0.067, False)    # free since then
    a, b = _open(tl, 0.07), _open(tl, 0.08)
    assert tl.free_at(0.09, {}) == (pytest.approx(0.07 + 2 * 0.067), True)
    # a overruns its estimate: it ends now at the earliest
    assert tl.free_at(0.15, {}) == (pytest.approx(0.15 + 0.067), True)
    # the harvester has seen a complete, the engine has not collected it
    assert tl.free_at(0.15, {a: 0.14}) == (pytest.approx(0.14 + 0.067), True)
    assert tl.free_at(0.25, {a: 0.14, b: 0.21}) == (0.21, False)
    unknown = _open(tl, 0.22, kind="prefill")
    assert tl.free_at(0.25, {a: 0.14, b: 0.21}) is None
    assert tl.free_at(0.3, {a: 0.14, b: 0.21, unknown: 0.29}) == (0.29, False)


def test_utilization_bounded():
    led = _ledger(peak_flops=1.0, peak_bytes_s=1.0)  # absurdly low peak
    r = _Req()
    _record(led, 0.0, 0.1, [(r, "decode", 4)], window=4)
    mfu, mbu = led.utilization()
    assert mfu == 1.0 and mbu == 1.0  # clamped, never a >100% ratio
    led2 = _ledger(peak_flops=1e18, peak_bytes_s=1e18)
    _record(led2, 0.0, 0.1, [(r, "decode", 4)], window=4)
    mfu2, mbu2 = led2.utilization()
    assert 0.0 < mfu2 < 1e-3 and 0.0 < mbu2 < 1e-3


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def _with_device(monkeypatch, platform, kind):
    import jax
    monkeypatch.delenv("LLMK_PEAK_TFLOPS", raising=False)
    monkeypatch.delenv("LLMK_PEAK_GBPS", raising=False)
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev(platform, kind)])


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", (197e12, 819e9)),     # what a v5e chip reports
    ("TPU v5e", (197e12, 819e9)),
    ("TPU v5p", (459e12, 2765e9)),
    ("TPU v6 lite", (918e12, 1640e9)),
    ("TPU v4", (275e12, 1228e9)),
])
def test_detect_peak_by_reported_device_kind(monkeypatch, kind, peak):
    _with_device(monkeypatch, "tpu", kind)
    assert detect_peak() == peak


def test_detect_peak_unknown_accelerator_is_an_error(monkeypatch):
    """An accelerator the table has never heard of fails start-up with
    the string it reports; the CPU platform has no peak at all."""
    _with_device(monkeypatch, "tpu", "TPU v9 mega")
    with pytest.raises(RuntimeError, match="TPU v9 mega"):
        detect_peak()
    _with_device(monkeypatch, "gpu", "NVIDIA H100")
    with pytest.raises(RuntimeError, match="NVIDIA H100"):
        detect_peak()
    _with_device(monkeypatch, "cpu", "cpu")
    assert detect_peak() is None
    # ... so a CPU's ledger books chip time and reports no MFU/MBU
    led = GoodputLedger(get_config("debug-tiny"))
    assert led.peak_flops is None and led.peak_bytes_s is None
    _record(led, 0.0, 0.1, [(_Req(), "decode", 4)], window=4)
    assert led.utilization() is None
    assert led.snapshot()["busy_ms"] == pytest.approx(100.0)


def test_detect_peak_env_override(monkeypatch):
    _with_device(monkeypatch, "tpu", "TPU v9 mega")
    monkeypatch.setenv("LLMK_PEAK_TFLOPS", "918")
    monkeypatch.setenv("LLMK_PEAK_GBPS", "1640")
    assert detect_peak() == (918e12, 1640e9)
    monkeypatch.setenv("LLMK_PEAK_TFLOPS", "not-a-number")
    with pytest.raises(ValueError):
        detect_peak()


def test_reset_zeroes_accounting():
    led = _ledger()
    _record(led, 0.0, 0.1, [(_Req("x"), "decode", 4)], window=4)
    led.reset()
    snap = led.snapshot()
    assert snap["dispatches"] == 0 and snap["window_ms"] == 0.0
    assert snap["busy_ms"] == 0.0 and snap["tenant_ms"] == {}
    # accounting restarts cleanly after the reset
    _record(led, 5.0, 5.1, [(_Req("x"), "decode", 4)], window=4)
    _conserves(led.snapshot())


# ---------------------------------------------------------------------------
# unit: EWMA + z-score step-time watchdog
# ---------------------------------------------------------------------------

def test_detector_steady_load_never_triggers():
    det = StepAnomalyDetector(threshold=4.0, sustain=3, cooldown_s=10.0,
                              warmup=5)
    for i in range(300):
        # ±2% jitter around 10 ms: well inside the 5%-of-mean variance floor
        assert not det.observe(0.010 * (1.02 if i % 2 else 0.98), now=float(i))
    assert det.triggers == 0


def test_detector_warmup_suppresses_triggers():
    det = StepAnomalyDetector(threshold=4.0, sustain=1, cooldown_s=0.0,
                              warmup=10)
    # wildly bimodal samples during warmup: baseline-building, no triggers
    for i in range(9):
        assert not det.observe(0.001 if i % 2 else 1.0, now=float(i))
    assert det.triggers == 0


def test_detector_trigger_sustain_cooldown_rate_limit():
    det = StepAnomalyDetector(threshold=4.0, sustain=3, cooldown_s=100.0,
                              warmup=5)
    now = 0.0
    for _ in range(20):  # steady baseline: 10 ms steps
        now += 1.0
        assert not det.observe(0.010, now=now)
    baseline = det._mean

    # a sustained 5x slowdown: samples 1 and 2 build the streak, 3 fires
    fired_at = None
    for i in range(10):
        now += 1.0
        if det.observe(0.050, now=now):
            assert fired_at is None, "second trigger inside cooldown"
            fired_at = i
    assert fired_at == 2  # exactly at the sustain count
    assert det.triggers == 1
    # anomalous samples must NOT teach the baseline to accept the slowdown
    assert det._mean == pytest.approx(baseline)

    # still slow past the cooldown: the rate limit re-opens, one more fires
    now += 200.0
    assert det.observe(0.050, now=now)
    assert det.triggers == 2


def test_detector_brief_spike_below_sustain_is_ignored():
    det = StepAnomalyDetector(threshold=4.0, sustain=3, cooldown_s=0.0,
                              warmup=5)
    now = 0.0
    for _ in range(20):
        now += 1.0
        det.observe(0.010, now=now)
    # two-sample spike (below sustain=3), then back to normal
    for dur in (0.050, 0.050, 0.010, 0.010):
        now += 1.0
        assert not det.observe(dur, now=now)
    assert det.triggers == 0


# ---------------------------------------------------------------------------
# engine: attribution through real dispatches
# ---------------------------------------------------------------------------

def _run(eng, reqs):
    steps = 0
    while any(not r.finished for r in reqs):
        eng.step()
        steps += 1
        assert steps < 10_000
    eng._drain_async()
    return reqs


@pytest.fixture(scope="module")
def adapter_dirs(tmp_path_factory):
    root = tmp_path_factory.mktemp("ledger_adapters")
    return {f"ad{i}": str(write_peft(root / f"ad{i}", rank=2, alpha=16,
                                     seed=40 + i))
            for i in range(2)}


@pytest.mark.e2e
def test_engine_multitenant_lora_batch_attribution(adapter_dirs):
    """A mixed batch (two tenants, LoRA + base rows, fused K=4): the
    conservation identity holds on real dispatch timings, per-tenant
    sums equal per-request sums, and every stream got billed for both
    its prefill and its decode."""
    eng = Engine(EngineConfig(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=8, num_pages=64, pages_per_slot=8,
        prefill_buckets=(16, 32), async_scheduling=True, async_depth=2,
        decode_steps=4, adapters=adapter_dirs, adapter_slots=2,
        adapter_rank=4, ledger=True,
    ))
    assert eng.ledger is not None
    rng = np.random.default_rng(3)
    specs = [("acme", "ad0"), ("acme", None), ("beta", "ad1"), ("beta", None)]
    reqs = [eng.submit(list(rng.integers(1, 255, 8)),
                       SamplingParams(temperature=0.0, max_tokens=10),
                       adapter=ad, tenant=ten)
            for ten, ad in specs]
    _run(eng, reqs)

    snap = eng.ledger.snapshot()
    assert snap["dispatches"] > 0
    total = snap["attributed_ms"] + snap["wasted_ms"] + snap["idle_ms"]
    assert total == pytest.approx(snap["window_ms"], rel=1e-6, abs=1e-3)
    # every stream was billed for prefill AND decode device time
    for r in reqs:
        assert r.chip_ms.get("prefill", 0.0) > 0.0
        assert r.chip_ms.get("decode", 0.0) > 0.0
    # per-tenant chargeback reconciles against per-request attribution
    # exactly (fallback rows for request-less dispatches land on "")
    for tenant in ("acme", "beta"):
        by_tenant = sum(v for (ten, _ph), v in snap["tenant_ms"].items()
                        if ten == tenant)
        by_req = sum(sum(r.chip_ms.values())
                     for r, (ten, _ad) in zip(reqs, specs) if ten == tenant)
        assert by_tenant == pytest.approx(by_req, rel=1e-9)
    assert snap["prefill_tokens"] > 0 and snap["decode_tokens"] > 0


@pytest.mark.e2e
def test_engine_spec_rejected_tails_book_spec_waste():
    """ngram speculation against random-weights continuations: drafts
    get rejected mid-window, and the rejected tails must book as
    spec_waste (billed, never counted as goodput)."""
    eng = Engine(EngineConfig(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=8, num_pages=64, pages_per_slot=8,
        prefill_buckets=(16, 32), async_scheduling=True, async_depth=2,
        decode_steps=4, speculation="ngram", ledger=True,
    ))
    # streams held to six tokens: the drafter always has an n-gram to
    # offer, the random-weights model agrees with part of it => rejections
    # happen (why a lookup-friendly prompt alone does not do it is said at
    # SMALL_VOCAB)
    from test_speculation import REPETITIVE, SMALL_VOCAB

    p = SamplingParams(temperature=0.0, max_tokens=16, logit_bias=SMALL_VOCAB)
    reqs = [eng.submit(REPETITIVE, p), eng.submit([4, 5, 6, 7, 8], p)]
    _run(eng, reqs)
    assert 0 < eng.spec_accepted_tokens < eng.spec_drafted_tokens
    snap = eng.ledger.snapshot()
    total = snap["attributed_ms"] + snap["wasted_ms"] + snap["idle_ms"]
    assert total == pytest.approx(snap["window_ms"], rel=1e-6, abs=1e-3)
    assert snap["phase_ms"]["spec_waste"] > 0.0, \
        "rejected drafted tails never booked as spec_waste"
    # waste is attributed to the streams that speculated
    assert sum(r.chip_ms.get("spec_waste", 0.0) for r in reqs) > 0.0


@pytest.mark.e2e
def test_greedy_bit_identical_ledger_on_off():
    """The ledger is accounting, not scheduling: greedy streams must be
    bit-identical with it on or off."""
    def mk(ledger):
        return Engine(EngineConfig(
            model="debug-tiny", dtype="float32", max_decode_slots=4,
            page_size=8, num_pages=64, pages_per_slot=8,
            prefill_buckets=(16, 32), async_scheduling=True, async_depth=2,
            decode_steps=4, ledger=ledger,
        ))
    prompts = [[1, 2, 3], [9, 10], [11, 12, 13, 14]]
    outs = {}
    for ledger in (True, False):
        eng = mk(ledger)
        assert (eng.ledger is not None) == ledger
        reqs = [eng.submit(p, SamplingParams(temperature=0.0, max_tokens=12))
                for p in prompts]
        _run(eng, reqs)
        outs[ledger] = [list(r.output) for r in reqs]
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# server: usage/header/traces/flight/metrics + the slow_step auto-profile
# ---------------------------------------------------------------------------

def _mk_server(monkeypatch=None, **ecfg_kw):
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer
    base = dict(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=4, num_pages=256, pages_per_slot=32,
        prefill_buckets=(32, 64), async_scheduling=True, async_depth=2,
        decode_steps=4,
    )
    base.update(ecfg_kw)
    return OpenAIServer(Engine(EngineConfig(**base)), ByteTokenizer(),
                        "debug-tiny")


@pytest.mark.e2e
def test_usage_header_spans_flight_and_metrics_carry_chip_time():
    srv = _mk_server()

    async def go():
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            r = await client.post(
                "/v1/completions",
                json={"prompt": "abcdef", "max_tokens": 8, "temperature": 0},
                headers={"X-LLMK-Request-Id": "chip-trace-1"})
            assert r.status == 200
            data = await r.json()
            # usage carries the per-phase attribution...
            chip = data["usage"]["chip_ms"]
            assert chip.get("prefill", 0.0) > 0.0
            assert chip.get("decode", 0.0) > 0.0
            # ...and the header carries the all-phase total
            hdr = float(r.headers["X-LLMK-Chip-Ms"])
            assert hdr == pytest.approx(sum(chip.values()), abs=0.01)

            # trace spans carry chip_ms (device time inside the wall span)
            r = await client.get("/debug/traces",
                                 params={"id": "chip-trace-1"})
            spans = {s["name"]: s
                     for s in (await r.json())["traces"][0]["spans"]}
            assert spans["prefill"]["chip_ms"] == pytest.approx(
                chip["prefill"], abs=0.01)
            assert spans["decode"]["chip_ms"] == pytest.approx(
                chip["decode"], abs=0.01)

            # flight frames gained the per-frame ledger keys
            snap = await (await client.get("/debug/engine")).json()
            keyed = [s for s in snap["steps"] if "chip_attr_ms" in s]
            assert keyed, "no flight frame carries ledger keys"
            assert sum(s["chip_attr_ms"] for s in keyed) > 0.0
            # a CPU has no peak: no frame carries an MFU
            assert not any("mfu" in s for s in keyed)
            # beside the frames, the ledger's newest dispatch records
            kinds = {d["kind"] for d in snap["dispatches"]}
            assert {"prefill", "decode"} <= kinds
            assert all(d["device_ms"] >= 0.0 and d["name"].endswith("_step")
                       for d in snap["dispatches"])

            # /metrics: goodput series present and nonzero
            text = await (await client.get("/metrics")).text()
            assert 'llm_chip_seconds_total{phase="prefill"}' in text
            assert 'llm_chip_seconds_total{phase="decode"}' in text
            assert "llm_mfu_ratio" in text and "llm_mbu_ratio" in text
            assert 'llm_tenant_chip_seconds_total{' in text
            assert 'llm_auto_profile_total' in text
            # the dispatch counters, drained beside them: what the device
            # held of every kind is the chip time of every phase
            import re

            def series(name):
                return {lab: float(v) for lab, v in re.findall(
                    rf'^{name}{{\w+="(\w+)"}} (\S+)$', text, re.M)}
            n = series("llm_dispatches_total")
            assert n["prefill"] >= 1 and n["decode"] >= 1 and n["spec"] == 0
            dev = series("llm_dispatch_device_seconds_total")
            chip = series("llm_chip_seconds_total")
            assert sum(dev.values()) == pytest.approx(
                sum(v for ph, v in chip.items() if ph != "idle"), abs=1e-6)
            assert sum(series("llm_device_idle_seconds_total").values()) \
                == pytest.approx(chip.get("idle", 0.0), abs=1e-6)
            assert set(series("llm_dispatch_behind_seconds_total")) == set(n)
            assert set(series("llm_dispatch_enqueue_seconds_total")) == set(n)
        finally:
            await client.close()
    asyncio.run(go())


class _StubProfiles:
    """Records capture() calls; raising busy on overlap like the real one."""

    def __init__(self):
        self.calls = []

    def capture(self, duration_ms=None, **kw):
        self.calls.append(duration_ms)
        return {"ok": True}


@pytest.mark.e2e
def test_slow_step_triggers_exactly_one_rate_limited_capture(monkeypatch):
    """Acceptance: an injected slow_step fault produces exactly one
    automatic profiler capture — the detector's cooldown is the rate
    limit, so the continuing slowness cannot trigger a second one."""
    # small warmup/sustain so the CPU test converges in a few requests;
    # a cooldown far longer than the test pins "exactly one"
    monkeypatch.setenv("LLMK_ANOMALY_WARMUP", "4")
    monkeypatch.setenv("LLMK_ANOMALY_SUSTAIN", "2")
    srv = _mk_server(anomaly_z=6.0, anomaly_cooldown_s=3600.0, ledger=True)
    stub = _StubProfiles()
    srv.loop_thread.profiles = stub

    async def go():
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            async def gen(n):
                for _ in range(n):
                    r = await client.post("/v1/completions", json={
                        "prompt": "abcd", "max_tokens": 6, "temperature": 0})
                    assert r.status == 200

            await gen(3)  # steady baseline past the detector warmup
            assert srv.loop_thread.auto_profiles == 0

            # every harvester read now takes an extra 120 ms: a sustained
            # slowdown the z-score test must catch
            monkeypatch.setenv("LLMK_FAULT", "slow_step:0.12")
            await gen(2)
            monkeypatch.delenv("LLMK_FAULT")

            deadline = time.monotonic() + 10.0
            while (srv.loop_thread.auto_profiles < 1
                   and time.monotonic() < deadline):
                await asyncio.sleep(0.05)
            assert srv.loop_thread.auto_profiles == 1

            # more traffic inside the cooldown: still exactly one
            await gen(2)
            assert srv.loop_thread.auto_profiles == 1

            # the capture ran (background thread) against the ProfileManager
            deadline = time.monotonic() + 5.0
            while not stub.calls and time.monotonic() < deadline:
                await asyncio.sleep(0.05)
            assert len(stub.calls) == 1

            text = await (await client.get("/metrics")).text()
            assert ('llm_auto_profile_total{reason="step_anomaly"} 1.0'
                    in text)
            # the flight recorder carries the capture marker for /debug
            snap = await (await client.get("/debug/engine")).json()
            assert any(s.get("marker") == "auto_profile"
                       for s in snap["steps"])
        finally:
            await client.close()
    asyncio.run(go())
