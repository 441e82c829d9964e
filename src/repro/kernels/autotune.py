"""Kernel autotuner: pick (block_m, block_n, block_k, T_blk) per layer.

The fused T_blk kernel has a small configuration space whose optimum
depends on the layer's GEMM shape and the precision pair — a 32-wide fc
head wants small output tiles, a 16k-row conv im2col wants the full MXU
block, and the profitable T_blk grows with how much weight traffic a
timestep amortizes.  Rather than hard-coding heuristics, the autotuner
*measures*: it runs each candidate config on synthetic spikes at a
representative sparsity and keeps the fastest.

Results are cached keyed by ``(rows, fan_in, channels, W_b, V_b)`` — the
shape+precision signature that determines kernel behavior — so a network
with repeated layer shapes tunes each shape once, and a JSON disk cache
(``SPIDR_AUTOTUNE_CACHE`` or an explicit path) persists winners across
processes.  ``spidr.compile(..., DeployTarget(autotune=True))`` consults
this module per weight layer and bakes the winner into the engine as
``EngineLayer.kcfg``.

The sweep is deliberately small (a few block shapes x a few T_blk values):
every candidate is bit-exact — the tuner only chooses among equivalent
schedules, so a bad pick costs time, never correctness.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .fused_lif_gemm import DEFAULT_BLOCK, fused_lif_gemm_int_tblk

__all__ = [
    "KernelConfig",
    "autotune_layer",
    "cache_key",
    "clear_cache",
    "load_cache",
    "save_cache",
]

CACHE_ENV = "SPIDR_AUTOTUNE_CACHE"

# Process-wide winner cache: key -> KernelConfig.
_MEMORY_CACHE: dict = {}


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One point of the tuning space: GEMM block + timestep tile."""

    block_m: int = DEFAULT_BLOCK[0]
    block_n: int = DEFAULT_BLOCK[1]
    block_k: int = DEFAULT_BLOCK[2]
    t_block: int = 1

    @property
    def block(self) -> tuple:
        return (self.block_m, self.block_n, self.block_k)

    @property
    def kcfg(self) -> tuple:
        """The ``EngineLayer.kcfg`` tuple form."""
        return (self.block_m, self.block_n, self.block_k, self.t_block)


def cache_key(rows: int, fan_in: int, channels: int,
              weight_bits: int, vmem_bits: int) -> str:
    """Shape+precision signature a tuned config is valid for."""
    return f"r{rows}_f{fan_in}_c{channels}_w{weight_bits}_v{vmem_bits}"


def clear_cache() -> None:
    _MEMORY_CACHE.clear()


def load_cache(path) -> dict:
    """Load a JSON winner cache into the in-memory cache (merging)."""
    data = json.loads(pathlib.Path(path).read_text())
    loaded = {k: KernelConfig(*v) for k, v in data.items()}
    _MEMORY_CACHE.update(loaded)
    return loaded


def save_cache(path) -> None:
    """Persist the in-memory winner cache as JSON."""
    data = {k: list(v.kcfg) for k, v in sorted(_MEMORY_CACHE.items())}
    pathlib.Path(path).write_text(json.dumps(data, indent=2) + "\n")


def _default_candidates(rows: int, fan_in: int, channels: int,
                        timesteps: int) -> list:
    """A small, shape-clipped sweep.

    Block sizes above the (padded) dimension only waste padding work, so
    candidates clip to the next power-of-two cover of each dimension; the
    T_blk axis sweeps 1 (the scan-equivalent schedule) up to the full
    sample depth.

    Only tilings the TPU compiler accepts are offered.  ``bn`` and ``bk``
    are lane dimensions (of the weights/Vmem and of the spikes): each must
    be a multiple of 128 or cover the whole dimension in one block.
    ``bm`` and ``bk`` are also int8 sublane dimensions, which every option
    here satisfies as a multiple of 32.
    """
    def cover(dim, opts, lane=False):
        legal = [o for o in opts if not lane or o % 128 == 0 or o >= dim]
        return [o for o in legal if o < 2 * dim] or legal[:1]

    blocks = []
    for bm in cover(rows, (32, 128)):
        for bn in cover(channels, (32, 128), lane=True):
            for bk in cover(fan_in, (32, 128), lane=True):
                blocks.append((bm, bn, bk))
    tbs = sorted({1, 2, min(4, timesteps), timesteps})
    return [KernelConfig(bm, bn, bk, tb)
            for (bm, bn, bk) in blocks for tb in tbs if tb >= 1]


def _time_candidate(cand: KernelConfig, spikes, weights, v0, threshold,
                    vmem_bits: int, interpret: bool, skip_empty: bool,
                    repeats: int) -> float:
    """Median wall seconds for one chunk under ``cand``'s schedule."""
    t = spikes.shape[0]

    def run():
        v = v0
        outs = []
        for t0 in range(0, t, cand.t_block):
            v_traj, s = fused_lif_gemm_int_tblk(
                spikes[t0:t0 + cand.t_block], weights, v,
                threshold=threshold, vmem_bits=vmem_bits,
                block=cand.block, interpret=interpret,
                skip_empty=skip_empty,
            )
            v = v_traj[-1]
            outs.append(s)
        return v, outs[-1]

    v, s = run()   # warmup: compile/trace outside the timed region
    jax.block_until_ready((v, s))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def autotune_layer(
    rows: int,
    fan_in: int,
    channels: int,
    weight_bits: int,
    vmem_bits: int,
    *,
    timesteps: int = 8,
    sparsity: float = 0.9,
    interpret: bool = True,
    skip_empty: bool = True,
    candidates: Optional[list] = None,
    cache_path=None,
    repeats: int = 1,
    seed: int = 0,
) -> KernelConfig:
    """Measure and cache the fastest kernel config for one layer shape.

    ``rows``/``fan_in``/``channels`` are the layer's GEMM dimensions
    (M/K/N); ``timesteps`` and ``sparsity`` shape the synthetic sample the
    candidates race on.  Returns the cached winner when the
    shape+precision key was tuned before (in this process, or in the JSON
    cache at ``cache_path`` / ``$SPIDR_AUTOTUNE_CACHE``).
    """
    key = cache_key(rows, fan_in, channels, weight_bits, vmem_bits)
    if key in _MEMORY_CACHE:
        return _MEMORY_CACHE[key]
    if cache_path is None:
        cache_path = os.environ.get(CACHE_ENV)
    if cache_path and pathlib.Path(cache_path).exists():
        load_cache(cache_path)
        if key in _MEMORY_CACHE:
            return _MEMORY_CACHE[key]

    rng = np.random.default_rng(seed)
    spikes = jnp.asarray(
        (rng.random((timesteps, rows, fan_in)) > sparsity).astype(np.int8))
    w_max = (1 << (weight_bits - 1)) - 1
    weights = jnp.asarray(
        rng.integers(-w_max - 1, w_max + 1, (fan_in, channels)), jnp.int8)
    v0 = jnp.zeros((rows, channels), jnp.int32)
    threshold = max(1, (1 << (vmem_bits - 2)))

    if candidates is None:
        candidates = _default_candidates(rows, fan_in, channels, timesteps)
    best, best_t = None, float("inf")
    for cand in candidates:
        dt = _time_candidate(cand, spikes, weights, v0, threshold,
                             vmem_bits, interpret, skip_empty, repeats)
        if dt < best_t:
            best, best_t = cand, dt
    _MEMORY_CACHE[key] = best
    if cache_path:
        save_cache(cache_path)
    return best
