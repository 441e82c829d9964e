"""From a profiler trace to device busy time, idle gaps and host time.

Reads the Perfetto JSON that ``jax.profiler`` writes with
``create_perfetto_trace=True`` (``perfetto_trace.json.gz``) using only
``gzip`` and ``json``, so the reduction loads no accelerator library.

* Device busy: per device, the union of the intervals in which an
  operation ran (the events of its ``XLA Ops`` line), clipped to the
  traced window, which is the extent of the harness span
  ``bench.window``.
* Idle gaps: the rest of the window on each device, each gap attributed
  to the harness span the host was in for most of it (``bench.step``,
  ``bench.submit``, ``bench.wait_arrival``; ``other`` outside them).
* Host time per tick: each ``bench.step`` span's length minus the device
  busy time (the union over all devices) inside it.
"""
from __future__ import annotations

import bisect
import collections
import gzip
import json

__all__ = ["HOST_SPANS", "busy_union", "load_events", "reduce_trace"]

HOST_SPANS = ("bench.step", "bench.submit", "bench.wait_arrival")
WINDOW_SPAN = "bench.window"
DEVICE_MARK = "/device:TPU:"
OPS_LINE = "XLA Ops"


def load_events(path) -> list:
    """The trace's events from a ``.json.gz`` (or plain ``.json``) file."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    return data["traceEvents"] if isinstance(data, dict) else data


def busy_union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _overlap(union: list, starts: list, lo: float, hi: float) -> float:
    """Length of ``union`` (disjoint, sorted; ``starts`` its starts)
    inside [lo, hi]."""
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    total = 0.0
    while i < len(union) and union[i][0] < hi:
        s, e = union[i]
        total += max(0.0, min(e, hi) - max(s, lo))
        i += 1
    return total


def _device_lines(events) -> dict:
    """{device name: [(start, end, op name)]} in microseconds."""
    procs, threads = {}, {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            procs[ev["pid"]] = ev.get("args", {}).get("name", "")
        elif ev.get("name") == "thread_name":
            threads[(ev["pid"], ev.get("tid"))] = ev.get("args", {}).get(
                "name", "")
    devices = {pid: name for pid, name in procs.items() if DEVICE_MARK in name}
    ops_lines = {key for key, name in threads.items()
                 if key[0] in devices and name == OPS_LINE}
    out: dict = {name: [] for name in devices.values()}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in devices:
            continue
        if (ev["pid"], ev.get("tid")) not in ops_lines:
            continue
        s = float(ev["ts"])
        out[devices[ev["pid"]]].append((s, s + float(ev.get("dur", 0.0)),
                                        ev.get("name", "")))
    return {name: ops for name, ops in out.items() if ops}


def _host_spans(events, names) -> list:
    """[(start, end, name)] of the harness's spans, in microseconds."""
    return sorted((float(ev["ts"]), float(ev["ts"]) + float(ev.get("dur", 0)),
                   ev["name"]) for ev in events
                  if ev.get("ph") == "X" and ev.get("name") in names)


def reduce_trace(events, top: int = 10) -> dict:
    """Reduce one traced window; see the module docstring.

    Returns ``window_s``, ``chips``, ``busy_s`` (mean over devices),
    ``steps`` (a list of ``[wall_s, device_busy_s]`` per ``bench.step``
    inside the window; a step with no device time in it means the
    profiler dropped events),
    ``device_ops`` and ``idle_gaps`` (at most ``top`` ``[name, seconds]``
    pairs each, largest first, seconds summed over devices for ops and
    averaged over devices for gaps).
    """
    windows = _host_spans(events, (WINDOW_SPAN,))
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0][0], windows[-1][1]
    lines = _device_lines(events)
    if not lines:
        raise ValueError("the trace holds no device operations")
    spans = _host_spans(events, HOST_SPANS)
    per_device, op_time = [], collections.Counter()
    all_busy = []
    for ops in lines.values():
        for s, e, name in ops:
            cs, ce = max(s, lo), min(e, hi)
            if ce > cs:
                op_time[name] += (ce - cs) * 1e-6
        union = _clip(busy_union((s, e) for s, e, _ in ops), lo, hi)
        per_device.append(union)
        all_busy.extend(union)
    merged = busy_union(all_busy)
    merged_starts = [s for s, _ in merged]

    gaps = collections.Counter()
    span_starts = [s for s, _, _ in spans]
    for union in per_device:
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            best, name = 0.0, "other"
            j = max(0, bisect.bisect_right(span_starts, gs) - 1)
            while j < len(spans) and spans[j][0] < ge:
                ov = min(ge, spans[j][1]) - max(gs, spans[j][0])
                if ov > best:
                    best, name = ov, spans[j][2]
                j += 1
            gaps[name] += (ge - gs) * 1e-6 / len(per_device)

    steps = [[(e - s) * 1e-6, _overlap(merged, merged_starts, s, e) * 1e-6]
             for s, e, name in spans if name == "bench.step"
             and s >= lo and e <= hi]
    if not steps:
        raise ValueError("the traced window holds no whole bench.step span")
    return {
        "window_s": (hi - lo) * 1e-6,
        "chips": len(per_device),
        "busy_s": sum(_length(u) for u in per_device) * 1e-6
        / len(per_device),
        "steps": steps,
        "device_ops": [[n, s] for n, s in op_time.most_common(top)],
        "idle_gaps": [[n, s] for n, s in gaps.most_common(top)],
    }
