"""The reduction from a profiler trace to busy time, idle share and the
breakdown, on synthetic traces with known answers."""
import pytest

import trace_reduce as tr

MS = 1e6   # nanoseconds


def _trace():
    # device 0: ops 0-10, 5-15 (overlapping), 30-40, 60-70 ms;
    # device 1: 0-50 ms. Window: the harness span, 0-100 ms.
    dev0 = [("fusion", 0, 10 * MS), ("fusion", 5 * MS, 15 * MS),
            ("gemm", 30 * MS, 40 * MS), ("gemm", 60 * MS, 70 * MS)]
    dev1 = [("gemm", 0, 50 * MS)]
    host = [(tr.WINDOW_SPAN, 0, 100 * MS),
            ("engine.device_pass", 10 * MS, 100 * MS),
            ("engine.token_sync", 15 * MS, 29 * MS),
            ("jit.tick", 40 * MS, 60 * MS),
            ("dispatch.execute", 41 * MS, 44 * MS)]
    return tr.Trace([tr.Device(dev0, []), tr.Device(dev1, [])], host)


def test_union_merges_overlaps():
    assert tr.union([(5, 15), (0, 10), (30, 40), (40, 41)]) \
        == [[0, 15], [30, 41]]


def test_busy_idle_and_breakdown():
    t = _trace()
    win = tr.window_of(t)
    assert win == (0, 100 * MS)
    out = tr.reduce(t, win)
    assert out["window_s"] == pytest.approx(0.1)
    # device 0 busy 15 + 10 + 10 = 35 ms, device 1 50 ms: mean 42.5 ms
    assert out["busy_s"] == pytest.approx(0.0425)
    ops = dict(out["device_ops"])
    assert ops["gemm"] == pytest.approx(0.07)
    assert ops["fusion"] == pytest.approx(0.02)
    # device 0 gaps: 15-30 (token sync covers 14 of 15 ms), 40-60 (the
    # tick covers all of it; dispatch.execute only 3 ms), 70-100 (only the
    # device pass covers it)
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx({"engine.token_sync": 0.015,
                                  "jit.tick": 0.020,
                                  "engine.device_pass": 0.030})
    assert out["n_gaps"] == 3


def test_events_outside_the_window_are_clipped():
    t = _trace()
    out = tr.reduce(t, (5 * MS, 35 * MS))
    # device 0 busy 5-15 and 30-35: 15 of 30 ms; device 1: all 30 ms
    assert out["busy_s"] == pytest.approx((0.015 + 0.030) / 2)


def test_no_device_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace([], []), (0, 1))


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: a jitted bf16 1024x1024 matmul and
    tanh, run four times inside the harness's window span, each call in a
    host span of its own."""
    import json
    from pathlib import Path
    planes = json.loads((Path(__file__).resolve().parent / "data"
                         / "recorded_trace.json").read_text())
    t = tr.from_planes((p["name"], [(ln["name"], ln["events"])
                                    for ln in p["lines"]]) for p in planes)
    assert len(t.devices) == 1            # the TPU, not the custom planes
    lo, hi = tr.window_of(t)
    out = tr.reduce(t, (lo, hi))
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["busy_s"] == pytest.approx(
        sum(t - s for s, t in tr.union(
            [(s, e) for _, s, e in t.devices[0].ops if lo < e and s < hi])
            ) * 1e-9, rel=1e-6)
    assert [n for n, _ in out["device_ops"]] == ["jit__lambda"]
    idle = dict(out["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    assert max(idle, key=idle.get) == "host.step"
