"""The trace reduction, on hand-made intervals checked by brute force."""
from pathlib import Path

import numpy as np
import pytest

import trace as tm


def brute_busy(intervals, lo, hi, step=1):
    """Covered length of [lo, hi] by marking integer time points."""
    t = np.arange(lo, hi, step)
    hit = np.zeros(t.size, dtype=bool)
    for s, e in intervals:
        hit |= (t >= s) & (t < e)
    return hit.sum() * step


def test_union_covered_and_gaps_agree_with_brute_force():
    rng = np.random.default_rng(0)
    for _ in range(50):
        s = rng.integers(0, 1000, 30)
        e = s + rng.integers(0, 80, 30)
        ms, me = tm.union(s, e)
        assert np.all(ms[1:] > me[:-1])
        lo, hi = sorted(rng.integers(0, 1100, 2))
        want = brute_busy(zip(s, e), lo, hi)
        assert tm.covered(ms, me, lo, hi) == pytest.approx(want)
        gs, ge = tm.gaps(ms, me, lo, hi)
        assert np.sum(ge - gs) == pytest.approx((hi - lo) - want)


def test_reduce_by_hand():
    ns = 1e9   # seconds -> nanoseconds
    spans = [("bench.window", 0, 10 * ns),
             ("bench.step", 0, 5 * ns), ("bench.factorize", 0, 2 * ns),
             ("bench.solve", 2 * ns, 5 * ns),
             ("bench.step", 5 * ns, 10 * ns)]
    devices = {
        "/device:TPU:0": [("fusion.1", 0.5 * ns, 1.5 * ns),
                          ("fusion.2", 1.0 * ns, 2.0 * ns),
                          ("while.3", 3 * ns, 4 * ns),
                          ("fusion.1", 6 * ns, 7 * ns)],
        "/device:TPU:1": [("fusion.1", 0 * ns, 1 * ns)],
    }
    r = tm.reduce(spans, devices)
    assert r["window_s"] == pytest.approx(10)
    assert r["busy_s_by_device"] == {"/device:TPU:0": pytest.approx(3.5),
                                     "/device:TPU:1": pytest.approx(1.0)}
    assert r["busy_s"] == pytest.approx(2.25)
    assert r["span_count"] == {"bench.step": 2, "bench.factorize": 1,
                               "bench.solve": 1}
    assert r["span_device_s"]["bench.factorize"] == pytest.approx(
        (1.5 + 1.0) / 2)
    assert r["span_device_s"]["bench.solve"] == pytest.approx(0.5)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(1.5)]
    # idle gaps of the first device, longest first, named by the span open
    assert r["idle_gaps"][0] == ["bench.step", pytest.approx(3.0)]
    assert r["idle_gaps"][1] == ["bench.solve", pytest.approx(2.0)]
    assert r["idle_gaps"][2] == ["bench.solve", pytest.approx(1.0)]



RECORDED = Path(__file__).parent / "data" / "newton_tpu.xplane.pb"


def test_reduction_of_a_recorded_tpu_trace():
    """Two Newton steps of a 64-node ladder traced on a TPU v5e
    (``record_trace.py``): the reduction finds the chip's ``XLA Ops``, the
    benchmark's spans, and a busy time equal to the union of the ops."""
    from jax.profiler import ProfileData

    planes = {p.name: {ln.name for ln in p.lines}
              for p in ProfileData.from_file(str(RECORDED)).planes}
    assert tm.OP_LINE in planes["/device:TPU:0"]
    spans, devices = tm.read(str(RECORDED), [0])
    assert list(devices) == ["/device:TPU:0"]
    with pytest.raises(ValueError, match="no device plane"):
        tm.read(str(RECORDED), [0, 9])
    r = tm.reduce(spans, devices)
    assert r["span_count"] == {"bench.step": 2, "bench.factorize": 2,
                               "bench.solve": 2}
    (_, lo, hi), = [s for s in spans if s[0] == tm.WINDOW]
    ops = [(s, e) for _, s, e in devices["/device:TPU:0"] if e > lo and s < hi]
    assert ops
    # union by sweeping the sorted intervals one by one
    busy, reach = 0.0, lo
    for s, e in sorted(ops):
        s, e = max(s, reach), min(e, hi)
        if e > s:
            busy += e - s
            reach = e
    assert r["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["span_device_s"]["bench.factorize"] > 0
    assert r["span_device_s"]["bench.solve"] > 0
    assert (r["span_device_s"]["bench.factorize"]
            + r["span_device_s"]["bench.solve"]) <= r["busy_s"] * (1 + 1e-12)
    names = {g[0] for g in r["idle_gaps"]}
    assert names <= {tm.WINDOW, "bench.step", "bench.factorize",
                     "bench.solve"}
