"""The reduction from a profiler trace to busy time, idle gaps and host time."""
import gzip
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402
from bench.readers import device_idle_pct, host_ms_per_tick  # noqa: E402
from bench.record import RunRecord  # noqa: E402


def _meta(pid, tid, kind, name):
    ev = {"ph": "M", "pid": pid, "name": kind, "args": {"name": name}}
    if tid is not None:
        ev["tid"] = tid
    return ev


def _x(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur}


def synthetic_trace():
    """One device with overlapping ops, a module line that must not count,
    and the harness's host spans (times in microseconds)."""
    return [
        _meta(1, None, "process_name", "/device:TPU:0"),
        _meta(1, 10, "thread_name", "XLA Ops"),
        _meta(1, 11, "thread_name", "XLA Modules"),
        _meta(2, None, "process_name", "/host:CPU"),
        _meta(2, 20, "thread_name", "python3"),
        _x(1, 11, "jit_step", 0.0, 50.0),
        _x(1, 10, "fusion.1", 0.0, 10.0),
        _x(1, 10, "pallas_kernel", 5.0, 10.0),
        _x(1, 10, "fusion.1", 30.0, 10.0),
        _x(1, 10, "fusion.1", 120.0, 10.0),      # after the window
        _x(2, 20, "bench.window", 0.0, 100.0),
        _x(2, 20, "bench.step", 0.0, 20.0),
        _x(2, 20, "bench.step", 25.0, 20.0),
        _x(2, 20, "bench.wait_arrival", 45.0, 55.0),
    ]


def test_busy_union_merges_overlapping_intervals():
    assert trace_reduce.busy_union([(5, 15), (0, 10), (30, 40), (40, 41)]) \
        == [(0, 15), (30, 41)]


def test_reduce_synthetic_trace():
    r = trace_reduce.reduce_trace(synthetic_trace())
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["chips"] == 1
    # [0, 15) and [30, 40): the module span and the op after the window
    # do not count.
    assert r["busy_s"] == pytest.approx(25e-6)
    assert r["steps"] == [pytest.approx([20e-6, 15e-6]),
                          pytest.approx([20e-6, 10e-6])]
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(20e-6)
    assert ops["pallas_kernel"] == pytest.approx(10e-6)
    gaps = dict(r["idle_gaps"])
    # [15, 30) lies under the two steps, [40, 100) mostly in the wait.
    assert gaps == {"bench.step": pytest.approx(15e-6),
                    "bench.wait_arrival": pytest.approx(60e-6)}
    rec = RunRecord(cfg={}, traffic={}, chips=1, seconds=1e-4, clips=[],
                    ticks=[], horizon=1e-4, peak={}, trace=r)
    assert device_idle_pct(rec) == pytest.approx(75.0)
    assert host_ms_per_tick(rec) == pytest.approx((5e-6 + 10e-6) / 2 * 1e3)


def test_two_devices_average_busy_and_gaps(tmp_path):
    events = synthetic_trace() + [
        _meta(3, None, "process_name", "/device:TPU:1"),
        _meta(3, 30, "thread_name", "XLA Ops"),
        _x(3, 30, "fusion.2", 50.0, 50.0),
    ]
    path = tmp_path / "t.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    r = trace_reduce.reduce_trace(trace_reduce.load_events(path))
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((25e-6 + 50e-6) / 2)
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        (75e-6 + 50e-6) / 2)


def test_reduce_a_trace_recorded_on_the_chip():
    """``gesture-trace.json.gz``: a 0.36 s ``gesture-poisson`` window traced
    on a TPU v5 lite, cut to the process and thread names, the device's
    op lines and the harness's spans."""
    events = trace_reduce.load_events(DATA / "gesture-trace.json.gz")
    r = trace_reduce.reduce_trace(events)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.358982214)
    assert r["busy_s"] == pytest.approx(0.106307000, rel=1e-6)
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert len(r["steps"]) == 4
    assert all(0 < busy < wall for wall, busy in r["steps"])
    names = [n for n, _ in r["device_ops"]]
    assert names[0].startswith("while")          # the chunk step's loop
    assert any(n.startswith("_fused_int") for n in names)  # the kernel

    # The busy union, counted again on a 1 us grid over the window.
    lo, hi = next((e["ts"], e["ts"] + e["dur"]) for e in events
                  if e.get("name") == trace_reduce.WINDOW_SPAN)
    ops_tid = next((e["pid"], e["tid"]) for e in events
                   if e.get("ph") == "M" and e.get("name") == "thread_name"
                   and e["args"]["name"] == trace_reduce.OPS_LINE)
    grid = np.zeros(int(hi - lo) + 1, bool)
    for e in events:
        if e.get("ph") == "X" and (e["pid"], e["tid"]) == ops_tid:
            s, t = max(e["ts"], lo) - lo, min(e["ts"] + e["dur"], hi) - lo
            if t > s:
                grid[int(round(s)):int(round(t))] = True
    assert grid.sum() * 1e-6 == pytest.approx(r["busy_s"], rel=1e-3)


def test_a_trace_without_the_window_span_is_refused():
    events = [e for e in synthetic_trace() if e.get("name") != "bench.window"]
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_trace(events)
