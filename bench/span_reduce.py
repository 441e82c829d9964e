"""Where a traced window's time went, by the program's spans and scopes.

Reads the Perfetto JSON that ``jax.profiler`` writes (as
``bench/trace_reduce.py`` does) from a capture in which the program's
tracer was enabled, so that its spans sit on the profiler's host line on
the device's clock (``docs/observability.md`` shows such a capture):

* Idle by span: each device's idle time in the window split exactly by the
  deepest span open on the host at each instant, the harness's
  (``bench.step``, ``bench.submit``, ``bench.wait_arrival``) or the
  program's (``fleet.*``, ``worker.*``, ``serve.*``, ``run_chunk``,
  ``session.*``); ``other`` outside all of them.  The parts sum to the
  window less the device's busy time.
* Device scopes: each device's busy time split by the ``spidr.*`` scope
  of the deepest operation running at each instant (``L{i}.patches``,
  ``L{i}.kernel``, ``L{i}.counts``, ``pool{j}``, ``readout``; ``other``
  for operations under none), read from the name stack in the operation's
  ``tf_op`` argument.  An operation that contains others, as the chunk
  step's ``while`` loop does, counts only where none of them runs.  The
  parts sum to the busy time.

The window is the harness span ``bench.window`` where the trace has one,
else the extent of the device's operations.  Seconds are averaged over
devices.  Run as::

    python3 -m bench.span_reduce perfetto_trace.json.gz

to print the split as one JSON object.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import heapq
import json
import re

from .trace_reduce import (DEVICE_MARK, HOST_SPANS, OPS_LINE, WINDOW_SPAN,
                           busy_union, load_events)

__all__ = ["PROGRAM_SPANS", "TICK_SPAN", "reduce_spans"]

PROGRAM_SPANS = ("fleet.", "worker.", "serve.", "session.", "run_chunk")
TICK_SPAN = "fleet.step"
NAME_STACK = "tf_op"
SCOPE = re.compile(r"spidr\.(L\d+\.[a-z]+|pool\d+|readout)")


def _scope(args: dict) -> str:
    """The innermost ``spidr.*`` scope in an operation's name stack, which
    the profiler gives as its ``tf_op`` argument (``jit(<lambda>)/while/
    body/closed_call/spidr.L0.kernel/jit(_fused_int_scalar)/pallas_call``)."""
    found = SCOPE.findall(args.get(NAME_STACK, ""))
    return found[-1] if found else "other"


def _deepest(intervals) -> list:
    """Split the union of nested ``(start, end, label)`` intervals into
    disjoint sorted pieces, each labelled by the innermost interval open
    there: the one that began last (the shorter one on a tie)."""
    by_start = sorted(intervals, key=lambda iv: (iv[0], -iv[1]))
    points = sorted({x for s, e, _ in by_start for x in (s, e)})
    open_, pieces, k = [], [], 0
    for lo, hi in zip(points, points[1:]):
        while k < len(by_start) and by_start[k][0] <= lo:
            s, e, label = by_start[k]
            heapq.heappush(open_, (-s, e, k, label))
            k += 1
        while open_ and open_[0][1] <= lo:
            heapq.heappop(open_)
        if not open_:
            continue
        label = open_[0][3]
        if pieces and pieces[-1][1] == lo and pieces[-1][2] == label:
            pieces[-1][1] = hi
        else:
            pieces.append([lo, hi, label])
    return [tuple(p) for p in pieces]


def _attribute(intervals, pieces) -> tuple:
    """How much of the disjoint sorted ``intervals`` each label of the
    disjoint sorted ``pieces`` covers: ``(Counter, uncovered length)``."""
    out, uncovered = collections.Counter(), 0.0
    starts = [p[0] for p in pieces]
    for s, e in intervals:
        covered = 0.0
        j = max(0, bisect.bisect_right(starts, s) - 1)
        while j < len(pieces) and pieces[j][0] < e:
            ov = min(e, pieces[j][1]) - max(s, pieces[j][0])
            if ov > 0:
                out[pieces[j][2]] += ov
                covered += ov
            j += 1
        uncovered += (e - s) - covered
    return out, uncovered


def _device_ops(events) -> list:
    """Per device, ``[(start, end, scope)]`` of its ``XLA Ops`` line in
    microseconds."""
    procs, ops_lines = {}, set()
    for ev in events:
        if ev.get("ph") != "M":
            continue
        name = ev.get("args", {}).get("name", "")
        if ev.get("name") == "process_name" and DEVICE_MARK in name:
            procs[ev["pid"]] = []
        elif ev.get("name") == "thread_name" and name == OPS_LINE:
            ops_lines.add((ev["pid"], ev.get("tid")))
    for ev in events:
        if (ev.get("ph") == "X" and ev.get("pid") in procs
                and (ev["pid"], ev.get("tid")) in ops_lines):
            s = float(ev["ts"])
            procs[ev["pid"]].append((s, s + float(ev.get("dur", 0.0)),
                                     _scope(ev.get("args", {}))))
    return [ops for ops in procs.values() if ops]


def _spans(events) -> list:
    """[(start, end, name)] of the harness's and the program's spans."""
    out = []
    for ev in events:
        name = ev.get("name", "")
        if ev.get("ph") == "X" and (name in HOST_SPANS
                                    or name.startswith(PROGRAM_SPANS)):
            s = float(ev["ts"])
            out.append((s, s + float(ev.get("dur", 0.0)), name))
    return out


def reduce_spans(events) -> dict:
    """Reduce one capture; see the module docstring.

    Returns ``window_s``, ``chips``, ``busy_s`` (mean over devices),
    ``ticks`` (the ``fleet.step`` spans wholly inside the window),
    ``idle_by_span`` (every span in the window as a ``[name, seconds]``
    pair, largest first, at 0.0 where no idle time falls under it) and
    ``device_scopes`` (``[scope, seconds]`` pairs, largest first).
    """
    devices = _device_ops(events)
    if not devices:
        raise ValueError("the trace holds no device operations")
    windows = [(float(ev["ts"]), float(ev["ts"]) + float(ev.get("dur", 0.0)))
               for ev in events
               if ev.get("ph") == "X" and ev.get("name") == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for ops in devices for s, _, _ in ops)
        hi = max(e for ops in devices for _, e, _ in ops)
    spans = [iv for iv in _spans(events) if iv[1] > lo and iv[0] < hi]
    pieces = _deepest(spans)
    by_span = collections.Counter({name: 0.0 for _, _, name in spans})
    scopes = collections.Counter()
    busy = 0.0
    for ops in devices:
        inside = [(max(s, lo), min(e, hi), scope) for s, e, scope in ops
                  if e > lo and s < hi]
        union = busy_union((s, e) for s, e, _ in inside)
        busy += sum(e - s for s, e in union)
        for scope, us in _attribute([(lo, hi)], _deepest(inside))[0].items():
            scopes[scope] += us * 1e-6 / len(devices)
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        under, outside = _attribute(idle, pieces)
        under["other"] += outside
        for name, us in under.items():
            by_span[name] += us * 1e-6 / len(devices)
    return {
        "window_s": (hi - lo) * 1e-6,
        "chips": len(devices),
        "busy_s": busy * 1e-6 / len(devices),
        "ticks": sum(1 for s, e, name in spans
                     if name == TICK_SPAN and s >= lo and e <= hi),
        "idle_by_span": [[n, s] for n, s in by_span.most_common()],
        "device_scopes": [[n, s] for n, s in scopes.most_common()],
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="perfetto_trace.json(.gz) of a capture")
    args = ap.parse_args(argv)
    print(json.dumps(reduce_spans(load_events(args.trace))))


if __name__ == "__main__":
    main()
