"""What one run leaves for the metric readers in ``bench/metrics/``.

A reader is a file ``bench/metrics/<metric name>.py`` with one function,
``read(run: RunRecord) -> float | None``.  It returns None where the run
holds nothing for it to read (no trace, no clips); the harness then leaves
the metric out of the result line.  Times are seconds on the host's
monotonic clock, relative to the start of the measured window.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

__all__ = ["Clip", "RunRecord", "Tick", "percentile"]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of an empty sample")
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


@dataclasses.dataclass
class Clip:
    """One clip the window offered."""

    rid: int
    pool_index: int
    due: float                       # when it was due to be sent
    submitted: Optional[float] = None
    shed: bool = False               # refused at admission
    first_reply: Optional[float] = None
    done: Optional[float] = None     # final readout on the host
    handle: object = None            # the program's handle while served

    @property
    def failed(self) -> bool:
        return self.shed or self.done is None


@dataclasses.dataclass
class Tick:
    """One ``fleet.step()`` in the window."""

    start: float
    end: float
    frames: int          # stream-timesteps returned to clients
    slot_chunks: int     # active slots that advanced


@dataclasses.dataclass
class RunRecord:
    cfg: dict                        # the configuration file
    traffic: dict                    # the traffic file
    chips: int
    seconds: float                   # the window's length
    clips: list                      # Clip, in the window
    ticks: list                      # Tick, whole ticks in the window
    horizon: float                   # when the run stopped waiting
    peak: dict                       # bench/peaks.json entry
    trace: Optional[dict] = None     # bench.trace_reduce.reduce_trace()
    trace_from: Optional[float] = None   # when the traced part began

    def traced_ticks(self) -> list:
        """The window's ticks that ran under the profiler (they start at
        or after ``trace_from``; the trace begins between two ticks)."""
        if self.trace_from is None:
            return []
        return [t for t in self.ticks if t.start >= self.trace_from]

    def latencies(self, stamp: str) -> list:
        """Seconds from due time to ``stamp`` ("first_reply" or "done")
        for every clip due in the window.  A clip that was shed or never
        reached the stamp reads the horizon itself, which no clip that
        reached it can exceed (due times are at or after 0)."""
        out = []
        for c in self.clips:
            t = None if c.shed else getattr(c, stamp)
            out.append(self.horizon if t is None else t - c.due)
        return out
