"""The trace reduction: busy time as the union of op intervals, idle gaps
labelled by the benchmark span they fall in, device and host time inside
spans."""

import gzip
from pathlib import Path

import numpy as np
import pytest

from harness import trace


def _events():
    ops = {
        # overlapping ops count once
        "TPU:0": (["fusion.1", "fusion.2", "all-gather.3", "while.4"],
                  np.array([10.0, 15.0, 60.0, 62.0]),
                  np.array([20.0, 30.0, 65.0, 80.0])),
        "TPU:1": (["fusion.1"], np.array([10.0]), np.array([12.0])),
    }
    spans = [("window", 0.0, 100.0), ("route_call", 5.0, 45.0),
             ("copy_back", 35.0, 45.0), ("route_call", 50.0, 95.0)]
    return trace.Events(ops=ops, spans=spans)


def test_union_and_gaps():
    u = trace.union(np.array([5.0, 1.0, 2.0, 10.0]),
                    np.array([6.0, 3.0, 4.0, 12.0]))
    np.testing.assert_array_equal(u, [[1, 4], [5, 6], [10, 12]])
    assert trace.covered(u, 2.0, 11.0) == 2 + 1 + 1
    np.testing.assert_array_equal(trace.gaps(u, 0.0, 13.0),
                                  [[0, 1], [4, 5], [6, 10], [12, 13]])


def test_reduction_of_a_small_trace():
    r = trace.Reduced(_events(), ["TPU:0", "TPU:1"])
    assert r.busiest == "TPU:0"
    assert r.busy_s("TPU:0") == pytest.approx(40e-9)  # 10..30 and 60..80
    assert r.window_s == pytest.approx(100e-9)
    assert r.mean_busy_s() == pytest.approx(21e-9)
    assert r.device_in("route_call") == pytest.approx([20e-9, 20e-9])
    assert r.host_in("route_call") == pytest.approx([20e-9, 25e-9])
    # idle 0..10, 30..60 (midpoint 45: inside the copy-back) and 80..100
    assert r.idle_gaps(2) == [["copy_back", pytest.approx(30e-9)],
                              ["route_call", pytest.approx(20e-9)]]
    top = dict(r.top_ops())
    assert top["while.4"] == pytest.approx(18e-9)


def test_events_round_trip_and_op_labels():
    ev = _events()
    back = trace.Events.from_json(ev.to_json())
    assert back.spans == ev.spans
    np.testing.assert_array_equal(back.ops["TPU:0"][1], ev.ops["TPU:0"][1])
    assert trace.op_label(
        "%fusion.155 = s32[1000000]{0:T(1024)} fusion(s32[1000000,12]"
        "{0,1:T(8,128)} %bitcast.111)") == "fusion.155 s32[1000000]"
    assert trace.op_label("%while.45 = (pred[]{:T(512)}, f32[3]) while(x)"
                          ) == "while.45 tuple"


RECORDED = Path(__file__).parent / "data" / "trace_dense4_serve_call.json.gz"


def _busy_brute(starts, ends, a, b):
    """Busy time by walking the intervals one at a time."""
    total, run_s, run_e = 0.0, None, None
    for s, e in sorted(zip(starts, ends)):
        s, e = max(s, a), min(e, b)
        if e <= s:
            continue
        if run_e is None or s > run_e:
            if run_e is not None:
                total += run_e - run_s
            run_s, run_e = s, e
        else:
            run_e = max(run_e, e)
    return total + (0.0 if run_e is None else run_e - run_s)


def test_reduction_of_a_recorded_chip_trace():
    """One serve_stream day traced on a TPU v5e (one call of dense4.serve:
    24 step spans, 17k device ops)."""
    with gzip.open(RECORDED, "rt") as f:
        ev = trace.Events.from_json(f.read())
    r = trace.Reduced(ev, ["TPU:0"])
    names, s, e = ev.ops["TPU:0"]
    busy = r.busy_s("TPU:0")
    assert busy == pytest.approx(_busy_brute(s, e, r.t0, r.t1) * 1e-9)
    assert 0 < busy < r.window_s
    steps = r.spans("serve_step")
    assert len(steps) == 24
    for host, dev, (_, a, b) in zip(r.host_in("serve_step"),
                                    r.device_in("serve_step"), steps):
        assert host + dev == pytest.approx((b - a) * 1e-9)
    idle = trace.gaps(r.cover["TPU:0"], r.t0, r.t1)
    assert busy + (idle[:, 1] - idle[:, 0]).sum() * 1e-9 == pytest.approx(
        r.window_s)
    gaps = r.idle_gaps()
    assert len(gaps) == 10 and all(g[0] == "serve_step" for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    top = r.top_ops()
    assert len(top) == 10
    assert top[0][1] >= top[-1][1] > 0
