"""The split of a traced window by the program's spans and device scopes."""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

from bench import span_reduce, trace_reduce  # noqa: E402

CHIP_TRACE = DATA / "gesture-spans-trace.json.gz"


def _meta(pid, tid, kind, name):
    ev = {"ph": "M", "pid": pid, "name": kind, "args": {"name": name}}
    if tid is not None:
        ev["tid"] = tid
    return ev


def _x(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur}


def _op(pid, tid, name, ts, dur, scope=""):
    """A device op whose name stack (``tf_op``) holds ``scope``."""
    ev = _x(pid, tid, name, ts, dur)
    ev["args"] = {"long_name": f"%{name} = fusion()",
                  "tf_op": f"jit(_lambda)/while/body/{scope}/op"}
    return ev


def spans_trace():
    """One device whose chunk step holds scoped ops nested in a ``while``
    loop, and the harness's and the program's nested host spans (times in
    microseconds).  Busy: [0, 60) and [80, 85); idle: [60, 80) and
    [85, 100)."""
    return [
        _meta(1, None, "process_name", "/device:TPU:0"),
        _meta(1, 10, "thread_name", "XLA Ops"),
        _meta(1, 11, "thread_name", "XLA Modules"),
        _meta(2, None, "process_name", "/host:CPU"),
        _meta(2, 20, "thread_name", "python3"),
        _x(1, 11, "jit_step", 0.0, 90.0),         # not an op: not counted
        _op(1, 10, "while.2", 0.0, 60.0),
        _op(1, 10, "pad.1", 5.0, 10.0, "spidr.L0.patches"),
        _op(1, 10, "fusion.4", 7.0, 2.0, "spidr.L0.counts"),
        _op(1, 10, "_fused_int_scalar.36", 15.0, 20.0, "spidr.L0.kernel"),
        _op(1, 10, "reduce-window.1", 40.0, 10.0, "spidr.pool0"),
        _op(1, 10, "scatter.1", 80.0, 5.0),
        _op(1, 10, "fusion.9", 120.0, 10.0),      # after the window
        _x(2, 20, "bench.window", 0.0, 100.0),
        _x(2, 20, "bench.step", 0.0, 90.0),
        _x(2, 20, "fleet.step", 1.0, 87.0),
        _x(2, 20, "fleet.place", 1.0, 1.0),
        _x(2, 20, "worker.mark", 2.0, 6.0),
        _x(2, 20, "serve.tick", 8.0, 79.0),
        _x(2, 20, "run_chunk", 8.0, 62.0),
        _x(2, 20, "session.frame", 8.0, 1.0),
        _x(2, 20, "session.upload", 9.0, 1.0),
        _x(2, 20, "session.dispatch", 10.0, 1.0),
        _x(2, 20, "session.fetch", 11.0, 54.0),
        _x(2, 20, "session.price", 65.0, 5.0),
        _x(2, 20, "session.close", 78.0, 4.0),
        _x(2, 20, "bench.submit", 92.0, 8.0),
        _x(2, 20, "fleet.step", 95.0, 10.0),      # not whole in the window
    ]


def test_idle_splits_by_the_deepest_open_span():
    r = span_reduce.reduce_spans(spans_trace())
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(65e-6)
    assert r["chips"] == 1 and r["ticks"] == 1
    idle = dict(r["idle_by_span"])
    want = {"session.fetch": 5, "session.price": 5, "serve.tick": 10,
            "session.close": 2, "fleet.step": 6, "bench.step": 2,
            "bench.submit": 3, "other": 2}
    for name, us in want.items():
        assert idle.pop(name) == pytest.approx(us * 1e-6, abs=1e-12), name
    # Spans with no idle time under them are named, at zero.
    assert set(idle) == {"fleet.place", "worker.mark", "run_chunk",
                         "session.frame", "session.upload",
                         "session.dispatch"}
    assert not any(idle.values())
    assert sum(s for _, s in r["idle_by_span"]) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=1e-12)


def test_busy_splits_by_the_deepest_op_scope():
    r = span_reduce.reduce_spans(spans_trace())
    scopes = dict(r["device_scopes"])
    # The while loop counts only where none of its ops runs; the counts op
    # inside the patch fusion takes its own 2 us.
    assert scopes == {"L0.patches": pytest.approx(8e-6),
                      "L0.counts": pytest.approx(2e-6),
                      "L0.kernel": pytest.approx(20e-6),
                      "pool0": pytest.approx(10e-6),
                      "other": pytest.approx(25e-6)}
    assert sum(scopes.values()) == pytest.approx(r["busy_s"], abs=1e-12)
    # The reduction of the harness reads the same window and busy time.
    base = trace_reduce.reduce_trace(spans_trace())
    assert base["busy_s"] == pytest.approx(r["busy_s"], abs=1e-12)
    assert base["window_s"] == pytest.approx(r["window_s"], abs=1e-12)


def test_a_trace_without_program_spans_splits_idle_by_harness_span():
    # A program that writes no spans and names no scope.
    events = [dict(e, args={}) if "tf_op" in e.get("args", {}) else e
              for e in spans_trace()
              if not e["name"].startswith(span_reduce.PROGRAM_SPANS)]
    r = span_reduce.reduce_spans(events)
    assert r["ticks"] == 0
    assert dict(r["device_scopes"]) == {"other": pytest.approx(r["busy_s"])}
    assert dict(r["idle_by_span"]) == {"bench.step": pytest.approx(25e-6),
                                       "bench.submit": pytest.approx(8e-6),
                                       "other": pytest.approx(2e-6)}


def test_without_the_window_span_the_device_ops_bound_the_window():
    events = [e for e in spans_trace()
              if e.get("name") != trace_reduce.WINDOW_SPAN]
    r = span_reduce.reduce_spans(events)
    assert r["window_s"] == pytest.approx(130e-6)
    assert r["busy_s"] == pytest.approx(75e-6)
    assert r["ticks"] == 2
    assert sum(s for _, s in r["idle_by_span"]) == pytest.approx(55e-6)


def test_idle_and_scopes_average_over_devices():
    events = spans_trace()
    # A second device that runs one L1 kernel for the whole window.
    events += [_meta(3, None, "process_name", "/device:TPU:1"),
               _meta(3, 10, "thread_name", "XLA Ops"),
               _op(3, 10, "_fused_int_scalar.37", 0.0, 100.0,
                   "spidr.L1.kernel")]
    r = span_reduce.reduce_spans(events)
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((65e-6 + 100e-6) / 2)
    assert dict(r["device_scopes"])["L1.kernel"] == pytest.approx(50e-6)
    assert dict(r["idle_by_span"])["serve.tick"] == pytest.approx(5e-6)
    assert sum(s for _, s in r["idle_by_span"]) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=1e-12)


def test_a_trace_without_device_operations_is_refused():
    events = [e for e in spans_trace() if e.get("pid") != 1]
    with pytest.raises(ValueError, match="device operations"):
        span_reduce.reduce_spans(events)


@pytest.mark.parametrize("intervals,pieces", [
    ([], []),
    ([(0, 10, "a")], [(0, 10, "a")]),
    # The inner interval wins where it is open; the outer one resumes.
    ([(0, 10, "a"), (2, 4, "b")], [(0, 2, "a"), (2, 4, "b"), (4, 10, "a")]),
    # Same start: the shorter one is the inner one.
    ([(0, 10, "a"), (0, 3, "b")], [(0, 3, "b"), (3, 10, "a")]),
    # Disjoint intervals leave the gap between them out.
    ([(0, 2, "a"), (5, 6, "a")], [(0, 2, "a"), (5, 6, "a")]),
    # Neighbours with one label merge into one piece.
    ([(0, 2, "a"), (2, 6, "a")], [(0, 6, "a")]),
])
def test_deepest_labels_each_instant_by_the_innermost_interval(intervals,
                                                               pieces):
    assert span_reduce._deepest(intervals) == pieces


@pytest.mark.parametrize("stack,scope", [
    ("jit(_lambda)/while/body/closed_call/spidr.L0.kernel/"
     "jit(_fused_int_scalar)/pallas_call", "L0.kernel"),
    ("jit(_lambda)/while/body/spidr.L12.patches/reshape", "L12.patches"),
    ("jit(_lambda)/spidr.readout/add", "readout"),
    ("jit(_lambda)/spidr.pool1/reduce_window_max", "pool1"),
    ("jit(_lambda)/while/body/copy", "other"),
])
def test_scope_reads_the_innermost_spidr_scope(stack, scope):
    assert span_reduce._scope({"tf_op": stack}) == scope


def test_reduce_a_spans_trace_recorded_on_the_chip():
    """``gesture-spans-trace.json.gz``: four ticks (0.50 s) of a
    ``gesture-poisson`` window traced on a TPU v5 lite with the program's
    tracer on, cut to the process and thread names, the device's op lines
    (each op keeping its ``tf_op`` where that names a ``spidr.*`` scope)
    and the harness's and the program's spans."""
    events = trace_reduce.load_events(CHIP_TRACE)
    r = span_reduce.reduce_spans(events)
    base = trace_reduce.reduce_trace(events)
    assert r["chips"] == 1 and r["ticks"] == len(base["steps"]) == 4
    assert r["busy_s"] == pytest.approx(base["busy_s"], abs=1e-9)
    idle = dict(r["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=1e-6)
    assert {"worker.mark", "session.fetch", "session.price",
            "session.close", "fleet.step"} <= set(idle)
    # The host split lands under the program's spans, not the harness's.
    assert idle["bench.step"] < 0.01 * idle["worker.mark"]
    scopes = dict(r["device_scopes"])
    assert sum(scopes.values()) == pytest.approx(r["busy_s"], abs=1e-9)
    assert {f"L{i}.kernel" for i in range(5)} <= set(scopes)
    assert scopes["other"] < 0.2 * r["busy_s"]

    # The deepest op at each instant, found again on a 1 us grid: ops
    # painted in the order they began, so a later (inner) one wins.
    lo, hi = next((e["ts"], e["ts"] + e["dur"]) for e in events
                  if e.get("name") == trace_reduce.WINDOW_SPAN)
    ops_tid = next((e["pid"], e["tid"]) for e in events
                   if e.get("ph") == "M" and e.get("name") == "thread_name"
                   and e["args"]["name"] == trace_reduce.OPS_LINE)
    ops = sorted((e for e in events if e.get("ph") == "X"
                  and (e["pid"], e["tid"]) == ops_tid),
                 key=lambda e: (e["ts"], -e["dur"]))
    names = sorted(scopes)
    grid = np.full(int(hi - lo) + 1, -1)
    for e in ops:
        s, t = max(e["ts"], lo) - lo, min(e["ts"] + e["dur"], hi) - lo
        if t > s:
            scope = span_reduce._scope(e.get("args", {}))
            grid[int(round(s)):int(round(t))] = names.index(scope)
    for i, name in enumerate(names):
        assert (grid == i).sum() * 1e-6 == pytest.approx(
            scopes[name], rel=0.02, abs=2e-5), name


def test_the_command_line_prints_the_split():
    out = subprocess.run(
        [sys.executable, "-m", "bench.span_reduce", str(CHIP_TRACE)],
        cwd=ROOT, capture_output=True, text=True, check=True).stdout
    printed = json.loads(out.strip().splitlines()[-1])
    want = span_reduce.reduce_spans(trace_reduce.load_events(CHIP_TRACE))
    assert printed == json.loads(json.dumps(want))
