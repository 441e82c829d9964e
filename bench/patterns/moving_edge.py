"""Gesture clips: a 3-pixel band sweeping at a class-coded angle and speed,
ON events on its leading side, OFF on its trailing side, plus Bernoulli
noise events per pixel and polarity (a seeded copy of the repository's
synthetic DVS gesture stream)."""
import numpy as np


def make(rng, n: int, timesteps: int, hw, p: dict) -> np.ndarray:
    h, w = hw
    classes = p["classes"]
    labels = rng.permutation(np.arange(n) % classes)
    speeds = np.asarray(p["speeds_px_per_step"], np.float64)
    phases = rng.uniform(0.0, p["phase_max_px"], n)
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    t = np.arange(timesteps, dtype=np.float64)[:, None, None]
    out = np.empty((n, timesteps, h, w, 2), np.uint8)
    for i in range(n):
        angle = 2.0 * np.pi * labels[i] / classes
        speed = speeds[labels[i] % len(speeds)]
        pos = (t * speed + phases[i]) % (h + w)
        d = np.cos(angle) * xx + np.sin(angle) * yy - pos
        band = np.abs(d) < p["band_half_width_px"]
        noise = rng.random((2, timesteps, h, w)) < p["noise_per_pixel"]
        out[i, ..., 0] = (band & (d >= 0)) | noise[0]
        out[i, ..., 1] = (band & (d < 0)) | noise[1]
    return out
