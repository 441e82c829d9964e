"""Reductions that more than one metric reader in ``bench/metrics/`` uses."""
from __future__ import annotations

from . import work
from .record import RunRecord, percentile

__all__ = ["device_idle_pct", "device_roofline_pct", "host_ms_per_tick",
           "latency_ms", "mfu_pct"]


def latency_ms(run: RunRecord, stamp: str, q: float):
    """Percentile ``q`` of due time to ``stamp`` over the window's clips."""
    if not run.clips:
        return None
    return percentile(run.latencies(stamp), q) * 1e3


def host_ms_per_tick(run: RunRecord):
    """Mean over the traced ticks of tick wall minus device busy in it."""
    steps = run.trace["steps"] if run.trace else []
    if not steps:
        return None
    return sum(wall - busy for wall, busy in steps) / len(steps) * 1e3


def device_idle_pct(run: RunRecord):
    """Share of the traced window with no operation on the device,
    averaged over the chips."""
    if not run.trace or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def device_roofline_pct(run: RunRecord):
    """Least time for the frames served in the traced window over the
    device busy time summed over the chips."""
    ticks = run.traced_ticks()
    if not run.trace or run.trace["busy_s"] <= 0:
        return None
    frames = sum(t.frames for t in ticks)
    if frames == 0:
        return None
    least, _ = work.least_seconds(
        run.cfg, run.peak, frames, sum(t.slot_chunks for t in ticks),
        len(ticks) * run.chips)
    return 100.0 * least / (run.trace["busy_s"] * run.trace["chips"])


def mfu_pct(run: RunRecord):
    """Dense int8 operations of the frames served over the fleet steps'
    wall time times the int8 peak of the chips."""
    wall = sum(t.end - t.start for t in run.ticks)
    frames = sum(t.frames for t in run.ticks)
    if wall <= 0 or frames == 0:
        return None
    ops = 2 * frames * work.macs_per_frame(run.cfg)
    return 100.0 * ops / (wall * run.peak["int8_ops_per_s"] * run.chips)
