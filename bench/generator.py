"""The one traffic generator: clip pools and arrival schedules from a seed.

A traffic mix is a data file, ``bench/traffic/<name>.json``, read here.  It
names a clip pattern and its parameters (``pool``), an arrival policy
with its parameters (``arrivals``, e.g. ``"poisson"`` at
``rate_clips_per_s`` or ``"saturated"`` with ``backlog_per_slot``) and the
admission bound (``max_queue_per_slot``).  Patterns and policies are found
by name: ``bench/patterns/<pattern>.py`` holds ``make(rng, n, timesteps,
hw, params)`` and ``bench/arrivals/<arrivals>.py`` holds ``drive(...)``
(see ``bench/run.py``), so a new mix adds files and edits none.  The
patterns are seeded numpy copies of the repository's synthetic DVS
streams, so that the traffic stays fixed while the program changes.

Every seed gets the same multiset of clip classes and inter-arrival gaps
in another order, so seeds change which clip lands where, not how much
work a run offers.
"""
from __future__ import annotations

import pathlib

import numpy as np

from . import named

__all__ = ["arrival_schedule", "clip_pool", "pool_order"]


ROOT = pathlib.Path(__file__).resolve().parents[1]


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


def clip_pool(traffic: dict, cfg: dict, seed: int,
              root: pathlib.Path = ROOT) -> np.ndarray:
    """The run's clip pool, (N, T, H, W, C) uint8 events, from ``seed``.

    Clips are as long as the configuration's ``timesteps`` and as large as
    its input plane; ``root`` is the tree whose patterns are read.
    """
    pool = traffic["pool"]
    make = named.load(root, "patterns", pool["pattern"]).make
    return make(_rng(seed, 0), pool["clips"], cfg["timesteps"],
                tuple(cfg["input_hw"]), pool)


def pool_order(n_pool: int, n: int, seed: int) -> np.ndarray:
    """Which pool clip each of ``n`` submissions sends: passes over the
    pool, each in a fresh seeded order."""
    rng = _rng(seed, 1)
    passes = -(-n // n_pool)
    return np.concatenate([rng.permutation(n_pool)
                           for _ in range(passes)])[:n]


def arrival_schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of a Poisson stream.

    ``round(rate * seconds)`` arrivals whose gaps are the exponential
    distribution's quantiles at the midpoints ``(i + 0.5) / n``, shuffled
    by the seed and scaled so the last one is due at ``seconds``; the gaps'
    mean is ``1 / rate`` as for Poisson arrivals, and every seed offers
    the same gaps in another order.
    """
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = _rng(seed, 2).permutation(gaps)
    due = np.cumsum(gaps)
    return due * (seconds / due[-1])
