#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell to find its knee.

    python3 bench/sweep.py --workload gesture-poisson --seed 7 \\
        --seconds 8 --rates 50,100,200,400 [--capacity 128]

Sets the cell up once, as ``bench/run.py`` does, then serves one
open-loop window per rate on the same warm fleet, lowest rate first, and
prints one JSON line per rate: clips offered, shed, done by the window's
end, the completion rate inside the window, the backlog at its end and
the latency median and 95th percentile from due time (ms).  The knee is
the highest rate whose completion rate keeps up with the offered rate
with no backlog left at the window's end; the cell's traffic file then
offers 0.8 of it.  ``--capacity`` serves the cell with another number of
session slots per chip (a sweep of capacity against latency).  The
benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.record import percentile  # noqa: E402
from bench.run import NoChip, cell_plan, drive, load_benchmark, \
    prepare  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, clips per second")
    ap.add_argument("--capacity", type=int, default=None,
                    help="session slots per chip instead of the config's")
    args = ap.parse_args(argv)
    plan = cell_plan(load_benchmark(ROOT), ROOT, args.workload)
    if args.capacity is not None:
        plan["cfg"]["deploy"] = dict(plan["cfg"]["deploy"],
                                     stream_capacity=args.capacity)
    try:
        served = prepare(plan, ROOT, args.seed)
    except NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    rid_base = 0
    for rate in sorted(float(r) for r in args.rates.split(",")):
        traffic = dict(plan["traffic"], rate_clips_per_s=rate)
        clips, ticks, horizon, _ = drive(served, traffic, args.seconds,
                                         args.seed, rid_base=rid_base)
        rid_base += len(clips)
        done_in = [c for c in clips
                   if c.done is not None and c.done <= args.seconds]
        lat = [c.done - c.due for c in clips if c.done is not None]
        line = {
            "capacity": served.capacity, "rate": rate, "offered": len(clips),
            "shed": sum(c.shed for c in clips),
            "done_in_window": len(done_in),
            "completion_rate": len(done_in) / args.seconds,
            "backlog_at_end": len(clips) - len(done_in),
            "ticks": len(ticks),
            "tick_ms_mean": (sum(t.end - t.start for t in ticks)
                             / max(1, len(ticks)) * 1e3),
            "latency_p50_ms": percentile(lat, 50) * 1e3 if lat else None,
            "latency_p95_ms": percentile(lat, 95) * 1e3 if lat else None,
        }
        served.say("sweep " + json.dumps(line))
    served.fleet.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
