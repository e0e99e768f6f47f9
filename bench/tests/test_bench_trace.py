"""Trace reduction, on a recorded TPU v5e trace of five HAN forwards and on
hand-made timelines."""
import pytest

from bench import trace
from bench.tests.conftest import FIXTURES


def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert trace.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_hand_timeline():
    raw = {
        "devices": {"/device:TPU:0": [
            ("%fusion.1 = f32[8] fusion(...)", 10.0, 40.0),
            ("%copy.2 = f32[8] copy(...)", 30.0, 50.0),
            ("%fusion.1 = f32[8] fusion(...)", 70.0, 90.0)]},
        "spans": [("bench.window", 0.0, 100.0),
                  ("bench.serve_call", 0.0, 100.0),
                  ("bench.sample", 50.0, 70.0)],
    }
    r = trace.reduce(raw)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(60e-9)  # [10, 50] + [70, 90]
    assert r["idle_share"] == pytest.approx(0.4)
    assert r["device_ops"][0] == ("fusion.1", pytest.approx(50e-9))
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.sample"] == pytest.approx(20e-9)  # innermost span
    assert gaps["bench.serve_call"] == pytest.approx(20e-9)  # 0-10, 90-100


def test_reduce_needs_a_window_and_a_device():
    assert trace.reduce({"devices": {}, "spans": []}) is None
    assert trace.reduce({"devices": {}, "spans": [
        ("bench.window", 0.0, 1.0)]}) is None


def test_recorded_chip_trace():
    raw = trace.load(str(FIXTURES / "han_full.xplane.pb"))
    assert list(raw["devices"]) == ["/device:TPU:0"]
    fwd = [(s, e) for n, s, e in raw["spans"] if n == "bench.forward"]
    fetch = [(s, e) for n, s, e in raw["spans"] if n == "bench.fetch"]
    assert len(fwd) == len(fetch) == 5
    # the device ran 1.3 ms ahead of the host's clock in this recording
    assert 1.2e6 < raw["skew_ns"] < 1.4e6
    r = trace.reduce(raw, window=(fwd[0][0], fetch[-1][1]))
    # five forwards of 11.38 ms of device time each on one TPU v5e
    assert r["busy_s"] / 5 == pytest.approx(11.38e-3, rel=0.01)
    assert 0.05 < r["idle_share"] < 0.2
    assert r["device_ops"][0][0] == "copy.65"
    assert dict(r["idle_gaps"]).get("bench.fetch", 0.0) > 0.0
