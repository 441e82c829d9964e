"""The harness's pure parts: schedules, drive loops, windows, discovery."""
import collections
import json
import pathlib
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generator, loop, run  # noqa: E402
from bench.record import RunRecord, Tick, percentile  # noqa: E402


class Overloaded(RuntimeError):
    pass


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, d):
        self.t += max(0.0, d)


class FakeReq:
    def __init__(self, length):
        self.length = length
        self.cursor = 0
        self.first_reply_at = None
        self.done_at = None


class FakeHandle:
    def __init__(self, req):
        self.request = req
        self.status = "queued"


class FakeFleet:
    """A sync fleet whose ticks take ``tick_s`` on a fake clock; tick
    number ``stall_at`` takes ``stall_s`` instead."""

    def __init__(self, clock, capacity=4, length=2, chunk=2, tick_s=0.01,
                 max_queue=100, stall_at=None, stall_s=0.0):
        self.clock, self.capacity, self.length, self.chunk = (
            clock, capacity, length, chunk)
        self.tick_s, self.max_queue = tick_s, max_queue
        self.stall_at, self.stall_s = stall_at, stall_s
        self.queue = collections.deque()
        self.slots = []
        self.ticks = 0

    @property
    def queue_depth(self):
        return len(self.queue)

    def submit(self, events, rid):
        if len(self.queue) >= self.max_queue:
            raise Overloaded()
        h = FakeHandle(FakeReq(self.length))
        self.queue.append(h)
        return h

    def step(self):
        while self.queue and len(self.slots) < self.capacity:
            h = self.queue.popleft()
            h.status = "running"
            self.slots.append(h)
        self.ticks += 1
        self.clock.t += self.stall_s if self.ticks == self.stall_at \
            else self.tick_s
        for h in list(self.slots):
            r = h.request
            r.cursor = min(r.length, r.cursor + self.chunk)
            if r.first_reply_at is None:
                r.first_reply_at = self.clock.t
            if r.cursor >= r.length:
                r.done_at = self.clock.t
                h.status = "done"
                self.slots.remove(h)
        return bool(self.slots or self.queue)


def _open(fleet, clock, due, seconds=1.0):
    clips = np.zeros((4, 2, 1, 1, 1), np.uint8)
    order = np.zeros(len(due), np.int64)
    return loop.open_loop(fleet, due, order, clips, seconds, 5.0,
                          Overloaded, clock=clock, sleep=clock.sleep)


def test_poisson_schedule_is_fixed_by_the_seed():
    a = generator.arrival_schedule(50.0, 10.0, 2**33 + 5)
    b = generator.arrival_schedule(50.0, 10.0, 2**33 + 5)
    c = generator.arrival_schedule(50.0, 10.0, 5)
    assert np.array_equal(a, b)
    assert len(a) == len(c) == 500 and not np.array_equal(a, c)
    # Same gaps in another order; the last clip is due at the window's end.
    assert np.allclose(np.sort(np.diff(a, prepend=0.0)),
                       np.sort(np.diff(c, prepend=0.0)))
    assert a[-1] == pytest.approx(10.0) and np.all(np.diff(a) > 0)
    assert np.mean(np.diff(a)) == pytest.approx(1 / 50.0, rel=0.05)


def test_pool_and_order_are_fixed_by_the_seed():
    traffic = json.loads((ROOT / "bench/traffic/poisson-clips.json").read_text())
    traffic["pool"]["clips"] = 6
    cfg = json.loads((ROOT / "bench/configs/gesture-w4v7.json").read_text())
    cfg["input_hw"], cfg["timesteps"] = [16, 16], 4
    p1 = generator.clip_pool(traffic, cfg, 2**40 + 1)
    p2 = generator.clip_pool(traffic, cfg, 2**40 + 1)
    p3 = generator.clip_pool(traffic, cfg, 1)
    assert p1.shape == (6, 4, 16, 16, 2) and p1.dtype == np.uint8
    assert np.array_equal(p1, p2) and not np.array_equal(p1, p3)
    order = generator.pool_order(6, 14, 9)
    assert np.array_equal(order, generator.pool_order(6, 14, 9))
    assert sorted(order[:6]) == list(range(6))


def test_latency_from_due_time_grows_behind_a_stalled_tick():
    clock = FakeClock()
    due = np.arange(1, 101) * 0.01          # a clip every 10 ms for 1 s
    fleet = FakeFleet(clock, capacity=64, tick_s=0.005, stall_at=40,
                      stall_s=0.3)
    clips, ticks, horizon, t0 = _open(fleet, clock, due)
    lat = {c.rid: c.done - c.due for c in clips}
    stall = next(t for t in ticks if t.end - t.start > 0.2)
    before = [lat[c.rid] for c in clips if c.due < stall.start - 0.01]
    behind = [c for c in clips if stall.start < c.due < stall.end]
    assert behind and max(before) < 0.03
    for c in behind:
        # Each waited out the rest of the stall, from its own due time.
        assert lat[c.rid] >= stall.end - c.due
        assert lat[c.rid] > max(before)
    assert sorted(lat[c.rid] for c in behind) == \
        [lat[c.rid] for c in sorted(behind, key=lambda c: -c.due)]


def test_a_shed_clip_counts_as_failed_and_beyond_every_percentile():
    clock = FakeClock()
    due = np.arange(1, 41) * 0.005           # 200 clips/s into 1 slot
    fleet = FakeFleet(clock, capacity=1, tick_s=0.02, max_queue=2)
    clips, ticks, horizon, _ = _open(fleet, clock, due, seconds=0.2)
    shed = [c for c in clips if c.shed]
    assert shed and all(c.failed and c.handle is None for c in shed)
    rec = RunRecord(cfg={}, traffic={}, chips=1, seconds=0.2, clips=clips,
                    ticks=ticks, horizon=horizon, peak={})
    done = [c.done - c.due for c in clips if not c.shed]
    assert sum(c.failed for c in clips) == len(shed)
    assert max(done) < horizon
    assert percentile(rec.latencies("done"), 100) == horizon


def test_the_saturated_window_counts_whole_ticks_only():
    clock = FakeClock()
    fleet = FakeFleet(clock, capacity=4, length=10, tick_s=0.3)
    clips_ = np.zeros((3, 10, 1, 1, 1), np.uint8)
    order = np.arange(10_000) % 3
    clips, ticks, horizon, _ = loop.closed_loop(
        fleet, order, clips_, 1.0, 5.0, backlog=4, overloaded=Overloaded,
        clock=clock)
    # Ticks end at 0.3, 0.6, 0.9 and 1.2 s: the last overran the window.
    assert [round(t.end, 6) for t in ticks] == [0.3, 0.6, 0.9, 1.2]
    whole = loop.whole_ticks(ticks, 1.0)
    assert len(whole) == 3 and all(t.frames == 8 for t in whole)
    read = run.metric_reader(ROOT, "frames_per_s")
    rec = RunRecord(cfg={}, traffic={}, chips=1, seconds=1.0, clips=clips,
                    ticks=whole, horizon=horizon, peak={})
    assert read(rec) == pytest.approx(24 / 0.9)
    # Every slot ran every tick, and the clips in flight were drained.
    assert len(clips) == 4 and all(c.done is not None for c in clips)


@pytest.mark.parametrize("kind", ["open", "closed"])
def test_the_mark_runs_once_between_ticks(kind):
    clock = FakeClock()
    fleet = FakeFleet(clock, capacity=4, length=10, tick_s=0.3)
    fired = []
    mark = (0.5, lambda: fired.append(clock.t))
    # The first clip is due at 0.05 s in the open loop and at once in the
    # closed one; ticks take 0.3 s, so the mark runs after the second.
    after = {"open": 0.65, "closed": 0.6}[kind]
    if kind == "open":
        due = np.arange(1, 21) * 0.05
        loop.open_loop(fleet, due, np.zeros(20, np.int64),
                       np.zeros((1, 10, 1, 1, 1), np.uint8), 1.0, 5.0,
                       Overloaded, clock=clock, sleep=clock.sleep, mark=mark)
    else:
        loop.closed_loop(fleet, np.arange(100) % 3,
                         np.zeros((3, 10, 1, 1, 1), np.uint8), 1.0, 5.0,
                         backlog=4, overloaded=Overloaded, clock=clock,
                         mark=mark)
    assert fired == [pytest.approx(after)]


def test_whole_ticks_drops_partial_ticks_at_both_ends():
    ticks = [Tick(-0.1, 0.2, 2, 1), Tick(0.2, 0.5, 2, 1),
             Tick(0.5, 0.9, 2, 1), Tick(0.9, 1.1, 2, 1)]
    assert loop.whole_ticks(ticks, 1.0) == ticks[1:3]


def _copy_benchmark(tmp):
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return json.loads((tmp / "BENCHMARK.json").read_text())


def test_new_files_are_found_by_name(tmp_path):
    bench = _copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((tmp_path / "bench/configs/gesture-w4v7.json").read_text())
    cfg["name"] = "gesture-w4v7-wide"
    (tmp_path / "bench/configs/gesture-w4v7-wide.json").write_text(
        json.dumps(cfg))
    traffic = json.loads(
        (tmp_path / "bench/traffic/poisson-clips.json").read_text())
    traffic["rate_clips_per_s"] = 7.0
    (tmp_path / "bench/traffic/slow-clips.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/metrics/clips_offered.new.py").write_text(
        "def read(run):\n    return float(len(run.clips))\n")
    bench["configs"].append({"name": "gesture-w4v7-wide", "source": "x",
                             "file": "bench/configs/gesture-w4v7-wide.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "gesture-slow",
                               "config": "gesture-w4v7-wide",
                               "traffic": "slow-clips", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "clips_offered.new", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "latency_p95_ms",
                               "workloads": ["gesture-slow"]})
    for m in bench["end_to_end"]:
        if m["name"] == "latency_p95_ms":
            m["workloads"].append("gesture-slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    plan = run.cell_plan(run.load_benchmark(tmp_path), tmp_path,
                         "gesture-slow")
    assert plan["cfg"]["name"] == "gesture-w4v7-wide"
    assert plan["traffic"]["rate_clips_per_s"] == 7.0
    assert [m["name"] for m in plan["per_layer"]] == ["clips_offered.new"]
    assert [m["name"] for m in plan["end_to_end"]] == ["latency_p95_ms",
                                                       "setup_s"]
    rec = RunRecord(cfg={}, traffic={}, chips=1, seconds=1.0, clips=[1, 2],
                    ticks=[], horizon=1.0, peak={})
    assert run.metric_reader(tmp_path, "clips_offered.new")(rec) == 2.0
    after = {p: p.read_bytes() for p in before}
    assert after == before   # no file that was there was edited


def test_every_cell_names_files_that_exist():
    bench = run.load_benchmark(ROOT)
    for cell in bench["workloads"]:
        plan = run.cell_plan(bench, ROOT, cell["name"])
        names = [m["name"] for m in plan["end_to_end"] + plan["per_layer"]]
        assert "setup_s" in names and len(plan["per_layer"]) >= 1
        for name in names:
            if name != "setup_s":
                assert callable(run.metric_reader(ROOT, name))


class WallClock:
    """The fake fleet's clock on the host's monotonic clock, for loops
    that a policy drives on the real clock."""

    @property
    def t(self):
        return time.monotonic()

    @t.setter
    def t(self, value):
        pass


_CHECKER = """import numpy as np


def make(rng, n, timesteps, hw, p):
    h, w = hw
    on = (np.add.outer(np.arange(h), np.arange(w)) // p["cell_px"]) % 2
    out = np.zeros((n, timesteps, h, w, 2), np.uint8)
    for i in range(n):
        out[i, :, :, :, rng.integers(2)] = on
    return out
"""

_BURSTS = """import numpy as np

from bench import generator, loop


def drive(fleet, pool, traffic, seconds, seed, *, slots, drain_s,
          overloaded, **kw):
    starts = np.arange(traffic["bursts"]) * seconds / traffic["bursts"]
    due = np.repeat(starts, traffic["burst_clips"]) + 1e-3
    order = generator.pool_order(len(pool), len(due), seed)
    return loop.open_loop(fleet, due, order, pool, seconds, drain_s,
                          overloaded, **kw)
"""


def test_a_new_pattern_and_arrival_policy_run_from_their_files(tmp_path):
    bench = _copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    (tmp_path / "bench/patterns/checker.py").write_text(_CHECKER)
    (tmp_path / "bench/arrivals/bursts.py").write_text(_BURSTS)
    traffic = {"arrivals": "bursts", "bursts": 3, "burst_clips": 4,
               "max_queue_per_slot": 4, "trace_seconds": 0.1,
               "pool": {"pattern": "checker", "clips": 5, "cell_px": 2}}
    (tmp_path / "bench/traffic/checker-bursts.json").write_text(
        json.dumps(traffic))
    bench["workloads"].append({"name": "gesture-bursts",
                               "config": "gesture-w4v7",
                               "traffic": "checker-bursts", "chips": 1,
                               "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    plan = run.cell_plan(run.load_benchmark(tmp_path), tmp_path,
                         "gesture-bursts")
    cfg = dict(plan["cfg"], input_hw=[4, 6], timesteps=2)
    pool = generator.clip_pool(plan["traffic"], cfg, 2**33 + 1, tmp_path)
    assert pool.shape == (5, 2, 4, 6, 2)
    assert np.array_equal(pool.sum(axis=-1)[0, 0],
                          (np.add.outer(np.arange(4), np.arange(6)) // 2) % 2)

    fleet = FakeFleet(WallClock(), capacity=2, length=2, tick_s=0.0)
    served = run.Served(root=tmp_path, devices=[None], peak={}, pool=pool,
                        weights=[], fleet=fleet, overloaded=Overloaded,
                        capacity=2, say=print)
    clips, ticks, horizon, t0 = run.drive(served, plan["traffic"], 0.3,
                                          2**33 + 1)
    assert len(clips) == 12 and all(c.done is not None for c in clips)
    assert sorted(collections.Counter(round(c.due, 6)
                                      for c in clips).values()) == [4, 4, 4]
    assert ticks
    after = {p: p.read_bytes() for p in before}
    assert after == before   # no file that was there was edited
