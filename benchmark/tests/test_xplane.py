import os

import pytest

from harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps_and_keeps_gaps():
    assert xplane.union_s([(0, 10), (5, 10), (30, 5)]) == pytest.approx(20e-9)
    assert xplane.union_s([]) == 0.0


def test_names():
    assert xplane.family("fusion.324") == "fusion"
    assert xplane.family("pallas_paged.7") == "pallas_paged"
    assert xplane.module_name("jit__decode_multi_packed_step(123)") == \
        "jit__decode_multi_packed_step"
    assert xplane.is_device_plane("/device:TPU:0")
    assert not xplane.is_device_plane("/host:CPU")


def test_reduce_on_hand_made_lines():
    ms = 1_000_000
    lines = [
        ("/host:CPU", "python", [("x", 0, 5 * ms)]),
        ("/device:TPU:0", xplane.MODULES_LINE, [
            ("jit_prefill(1)", 0, 10 * ms), ("jit_decode(2)", 12 * ms, 4 * ms),
            ("jit_decode(2)", 20 * ms, 4 * ms)]),
        ("/device:TPU:0", xplane.OPS_LINE, [
            ("fusion.1", 0, 6 * ms), ("fusion.2", 6 * ms, 4 * ms),
            ("copy.3", 12 * ms, 4 * ms), ("fusion.1", 20 * ms, 4 * ms)]),
    ]
    out = xplane.reduce(lines)
    dev = out["devices"]["/device:TPU:0"]
    assert out["planes"] == ["/device:TPU:0", "/host:CPU"]
    assert dev["span_s"] == pytest.approx(0.024)
    assert dev["busy_s"] == pytest.approx(0.018)
    assert dev["modules"]["jit_decode"] == {
        "count": 2, "total_s": pytest.approx(0.008)}
    assert dev["device_ops"][0] == ["fusion", pytest.approx(0.014)]
    gaps = dict(dev["idle_gaps"])
    assert gaps["jit_prefill->jit_decode"] == pytest.approx(0.002)
    assert gaps["jit_decode->jit_decode"] == pytest.approx(0.004)


def test_a_trace_with_no_device_plane_reduces_to_no_device():
    out = xplane.reduce([("/host:CPU", "python", [("x", 0, 5)])])
    assert out["devices"] == {}


def test_reduce_the_trace_recorded_on_the_chip():
    """tests/data/tiny.xplane.pb: three rounds of two jitted steps with a
    20 ms pause, recorded by record_tiny_trace.py on a TPU v5e."""
    path = os.path.join(DATA, "tiny.xplane.pb")
    if not os.path.isfile(path):
        pytest.skip("no recorded trace kept with the tests")
    out = xplane.reduce(xplane.load(path))
    assert len(out["devices"]) == 1
    dev = next(iter(out["devices"].values()))
    mods = dev["modules"]
    assert mods["jit_tiny_matmul_step"]["count"] == 3
    assert mods["jit_tiny_reduce_step"]["count"] == 3
    assert 0.0 < dev["busy_s"] < dev["span_s"]
    # the pauses between rounds are idle time between reduce and matmul
    gaps = dict(dev["idle_gaps"])
    assert gaps["jit_tiny_reduce_step->jit_tiny_matmul_step"] > 0.03
    assert dev["span_s"] - dev["busy_s"] > 0.03
