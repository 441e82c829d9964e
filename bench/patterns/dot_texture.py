"""Optical-flow clips: a static random dot texture translating at a per-clip
velocity, with events where the shifted texture changes (a seeded copy of
the repository's synthetic DVS flow stream)."""
import numpy as np


def make(rng, n: int, timesteps: int, hw, p: dict) -> np.ndarray:
    h, w = hw
    vmax = p["velocity_px_per_step"]
    out = np.empty((n, timesteps, h, w, 2), np.uint8)
    for i in range(n):
        tex = (rng.random((h, w)) < p["density"]).astype(np.int8)
        vel = rng.uniform(-vmax, vmax, 2)

        def shifted(t):
            dx, dy = np.round(vel * t).astype(np.int64)
            return np.roll(np.roll(tex, dy, axis=0), dx, axis=1)

        prev = shifted(-1)
        for t in range(timesteps):
            cur = shifted(t)
            out[i, t, ..., 0] = cur > prev
            out[i, t, ..., 1] = prev > cur
            prev = cur
    return out
