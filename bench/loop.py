"""The harness's drive loops over a sync-mode fleet.

Both loops drive only ``fleet.submit(events, rid=...)`` and
``fleet.step()`` and read the handles' request stamps
(``first_reply_at``, ``done_at``, ``cursor``), which the program takes on
the same monotonic clock as ``clock``.

* ``open_loop``: clips are due on a fixed schedule whatever the fleet
  does.  Before each step every clip whose due time has passed is
  submitted; latency runs from the due time, so a stalled tick delays
  every clip queued behind it.
* ``closed_loop``: at saturation, the loop keeps ``backlog`` clips queued
  before every step, so every tick runs every slot; only whole ticks
  inside the window count.
"""
from __future__ import annotations

import contextlib
import time

from .record import Clip, Tick

__all__ = ["closed_loop", "no_span", "open_loop", "warm_up", "whole_ticks"]


def no_span(name):
    """A span that records nothing (runs without the profiler)."""
    return contextlib.nullcontext()


class _Ledger:
    """Clips in flight and the frames each tick returned."""

    def __init__(self, fleet, clock):
        self.fleet, self.clock = fleet, clock
        self.live: list = []
        self._cursor: dict = {}

    def add(self, clip: Clip) -> None:
        self.live.append(clip)
        self._cursor[clip.rid] = 0

    def step(self, span) -> Tick:
        start = self.clock()
        with span("bench.step"):
            self.fleet.step()
        end = self.clock()
        frames = slots = 0
        still = []
        for c in self.live:
            req = c.handle.request
            moved = int(req.cursor) - self._cursor[c.rid]
            if moved > 0:
                frames += moved
                slots += 1
                self._cursor[c.rid] = int(req.cursor)
            if req.done_at is not None:
                c.first_reply, c.done = req.first_reply_at, req.done_at
                del self._cursor[c.rid]
            else:
                still.append(c)
        self.live = still
        return Tick(start, end, frames, slots)


def _submit(fleet, clip: Clip, events, clock, overloaded) -> bool:
    clip.submitted = clock()
    try:
        clip.handle = fleet.submit(events, rid=clip.rid)
    except overloaded:
        clip.shed = True
        return False
    return True


def _at(mark, now):
    """Run ``mark = (seconds, fn)`` once ``now`` has reached its time;
    returns what is left of it."""
    if mark is not None and now >= mark[0]:
        mark[1]()
        return None
    return mark


def open_loop(fleet, due, pool_index, clips, seconds: float, drain_s: float,
              overloaded, clock=time.monotonic, sleep=time.sleep,
              span=no_span, on_window_end=None, rid_base: int = 0,
              mark=None):
    """Offer clip ``pool_index[i]`` at ``due[i]`` seconds after the start.

    Returns ``(clips, ticks, horizon, t0)``: every offered clip with its
    stamps relative to ``t0``, the ticks until the last offered clip was
    due, and when the loop stopped waiting for the rest (at most
    ``drain_s`` after the window).  ``mark = (seconds, fn)`` runs ``fn``
    once between ticks when that many seconds have passed;
    ``on_window_end`` runs once when the schedule is exhausted, before
    the drain.
    """
    ledger = _Ledger(fleet, clock)
    offered, ticks = [], []
    t0 = clock()
    i, n = 0, len(due)
    while True:
        now = clock() - t0
        mark = _at(mark, now)
        with span("bench.submit"):
            while i < n and due[i] <= now:
                clip = Clip(rid=rid_base + i, pool_index=int(pool_index[i]),
                            due=float(due[i]))
                if _submit(fleet, clip, clips[clip.pool_index], clock,
                           overloaded):
                    ledger.add(clip)
                offered.append(clip)
                i += 1
        if i == n:
            break
        if ledger.live:
            ticks.append(ledger.step(span))
        else:
            with span("bench.wait_arrival"):
                sleep(max(0.0, due[i] - (clock() - t0)))
    if on_window_end is not None:
        on_window_end()
    while ledger.live and clock() - t0 < seconds + drain_s:
        ledger.step(span)
    horizon = clock() - t0
    return _relative(offered, t0), _relative_ticks(ticks, t0), horizon, t0


def closed_loop(fleet, pool_index, clips, seconds: float, drain_s: float,
                backlog: int, overloaded, clock=time.monotonic,
                span=no_span, on_window_end=None, rid_base: int = 0,
                mark=None):
    """Keep ``backlog`` clips queued before every step for ``seconds``.

    Returns ``(clips, ticks, horizon, t0)``: the clips that held a slot by
    the window's end (each drained to its end, at most ``drain_s``
    after the window) and every tick of the window; ``whole_ticks`` keeps
    those that ended inside it.  ``mark`` and ``on_window_end`` as for
    ``open_loop``.
    """
    ledger = _Ledger(fleet, clock)
    ticks, queued = [], []
    t0 = clock()
    i = 0
    while clock() - t0 < seconds:
        mark = _at(mark, clock() - t0)
        with span("bench.submit"):
            while fleet.queue_depth < backlog:
                clip = Clip(rid=rid_base + i, pool_index=int(pool_index[i]),
                            due=clock() - t0)
                if not _submit(fleet, clip, clips[clip.pool_index], clock,
                               overloaded):
                    raise RuntimeError(
                        f"the fleet shed clip {i} with {fleet.queue_depth} "
                        f"queued: max_queue is below the backlog {backlog}")
                ledger.add(clip)
                queued.append(clip)
                i += 1
        ticks.append(ledger.step(span))
    if on_window_end is not None:
        on_window_end()
    started = [c for c in queued if c.handle.status != "queued"]
    ids = {c.rid for c in started}
    ledger.live = [c for c in ledger.live if c.rid in ids]
    while ledger.live and clock() - t0 < seconds + drain_s:
        ledger.step(span)
    horizon = clock() - t0
    return _relative(started, t0), _relative_ticks(ticks, t0), horizon, t0


def whole_ticks(ticks, seconds: float) -> list:
    """The ticks that started and ended inside ``[0, seconds]``."""
    return [t for t in ticks if t.start >= 0.0 and t.end <= seconds]


def warm_up(fleet, warm_clips, replicas: int, overloaded) -> None:
    """Run two short clips per replica through admit, step, close and a
    reused slot, so that the chunk step and the slot reset compile before
    the window.  ``warm_clips`` are chunk-long clips of the cell's own
    shape; their rids are negative, apart from the window's."""
    rid = -1
    for _ in range(2):
        for _ in range(replicas):
            fleet.submit(warm_clips[(-rid) % len(warm_clips)], rid=rid)
            rid -= 1
        while fleet.step():
            pass


def _relative(clips, t0):
    for c in clips:
        for name in ("submitted", "first_reply", "done"):
            v = getattr(c, name)
            if v is not None:
                setattr(c, name, v - t0)
    return clips


def _relative_ticks(ticks, t0):
    return [Tick(t.start - t0, t.end - t0, t.frames, t.slot_chunks)
            for t in ticks]
