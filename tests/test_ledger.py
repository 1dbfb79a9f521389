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
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llms_on_kubernetes_tpu.configs import get_config
from llms_on_kubernetes_tpu.engine.engine import (
    Engine, EngineConfig, SamplingParams,
)
from llms_on_kubernetes_tpu.engine.ledger import (
    PHASES, GoodputLedger, StepAnomalyDetector, detect_peak,
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
    led.record(0.0, 0.1, [(a, "decode", 4), (b, "decode", 4)], window=4)
    # dispatch 2 launched while 1 was in flight: its busy segment is
    # 0.1 -> 0.2 (the device runs dispatches serially), never 0.05 -> 0.2
    led.record(0.05, 0.2, [(a, "decode", 4), (b, "decode", 4)], window=4)
    # 100 ms gap, then a window where `a` early-exits after 2 of 4 rows
    led.record(0.3, 0.35,
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
    led.record(0.0, 0.08, [(r, "decode", 2), (r, "spec_waste", 2)], window=4)
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
    led.record(0.0, 0.05, [])
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
        led.record(t_launch, t, rows, window=int(rng.integers(1, 5)))
    snap = led.snapshot()
    _conserves(snap)
    # per-request sums == per-tenant sums == phase totals
    req_total = sum(v for r in reqs for v in r.chip_ms.values())
    ten_total = sum(v for (ten, _ph), v in snap["tenant_ms"].items() if ten)
    assert req_total == pytest.approx(ten_total, rel=1e-9)


def test_utilization_bounded():
    led = _ledger(peak_flops=1.0, peak_bytes_s=1.0)  # absurdly low peak
    r = _Req()
    led.record(0.0, 0.1, [(r, "decode", 4)], window=4)
    mfu, mbu = led.utilization()
    assert mfu == 1.0 and mbu == 1.0  # clamped, never a >100% ratio
    led2 = _ledger(peak_flops=1e18, peak_bytes_s=1e18)
    led2.record(0.0, 0.1, [(r, "decode", 4)], window=4)
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
    the string it reports; only the CPU platform gets a nominal peak."""
    _with_device(monkeypatch, "tpu", "TPU v9 mega")
    with pytest.raises(RuntimeError, match="TPU v9 mega"):
        detect_peak()
    _with_device(monkeypatch, "gpu", "NVIDIA H100")
    with pytest.raises(RuntimeError, match="NVIDIA H100"):
        detect_peak()
    _with_device(monkeypatch, "cpu", "cpu")
    assert detect_peak() == (5e11, 5e10)


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
    led.record(0.0, 0.1, [(_Req("x"), "decode", 4)], window=4)
    led.reset()
    snap = led.snapshot()
    assert snap["dispatches"] == 0 and snap["window_ms"] == 0.0
    assert snap["busy_ms"] == 0.0 and snap["tenant_ms"] == {}
    # accounting restarts cleanly after the reset
    led.record(5.0, 5.1, [(_Req("x"), "decode", 4)], window=4)
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
    # lookup-friendly prompt: the drafter always has an n-gram to offer,
    # the random-weights model rarely agrees => rejections happen
    rep = [5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6]
    reqs = [eng.submit(rep, SamplingParams(temperature=0.0, max_tokens=16)),
            eng.submit([4, 5, 6, 7, 8],
                       SamplingParams(temperature=0.0, max_tokens=16))]
    _run(eng, reqs)
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
            assert all("mfu" in s for s in keyed)

            # /metrics: goodput series present and nonzero
            text = await (await client.get("/metrics")).text()
            assert 'llm_chip_seconds_total{phase="prefill"}' in text
            assert 'llm_chip_seconds_total{phase="decode"}' in text
            assert "llm_mfu_ratio" in text and "llm_mbu_ratio" in text
            assert 'llm_tenant_chip_seconds_total{' in text
            assert 'llm_auto_profile_total' in text
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
