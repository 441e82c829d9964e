"""Plain reference of the served network and of its chip-cost accounting.

Written from the configuration file alone (``bench/configs/<name>.json``)
and the paper's datapath; how the layers connect is the network
description's (``bench/networks/<topology>.py``, found by
``bench/named.py``; ``chain.py`` for the paper's networks).  A 3x3
spiking convolution is an integer convolution of the binary input plane
with the layer's signed weights, saturated to the Vmem width; the neuron
adds it to its (leaked) Vmem, saturates, fires at the integer threshold
and resets (to 0, or by the threshold).  Pools take the max of binary
planes.  The readout is the summed last-layer spikes (``"rate"``) or the
last layer's Vmem (``"vmem"``).  It imports nothing of the program under
test.

Sums of 0/1 spikes times weights of at most 8 in magnitude stay far below
2**24, so a float32 convolution at ``HIGHEST`` precision is exact; the
reference runs it timestep by timestep with ``lax.conv_general_dilated``,
not through patch matrices or kernels.

``chip_cost`` copies the chip model's arithmetic (Sec II-E/F mapping, the
asynchronous-handshake pipeline and the calibrated chunk energy) so that a
served stream's cycles and energy can be checked to the last bit from the
reference's own spike counts.
"""
from __future__ import annotations

import math

import numpy as np

from . import named, work

__all__ = ["chip_cost", "reference_run", "weight_layers"]


def weight_layers(cfg: dict) -> list:
    """The configuration's weight layers, in order: each with its
    ``fan_in``, ``c_out``, ``thr_int``, ``shape`` and ``spatial``, as its
    network description gives them."""
    return named.network(cfg).weight_layers(cfg)


def _layer_geometry(cfg: dict) -> list:
    """(spatial, fan_in, out_channels, out_positions) per weight layer."""
    return [(layer["spatial"], f, k, p) for layer, (p, f, k)
            in zip(weight_layers(cfg), work.layer_work(cfg))]


def reference_run(cfg: dict, weights: list, clips: np.ndarray,
                  vmem_bits: int | None = None, budget_bytes: float = 1.5e9):
    """Serve ``clips`` (N, T, H, W, C) whole through the plain reference
    of the configuration's network description.

    ``weights`` are the configuration's integer weights, one (F, K) array
    per weight layer with the fan-in in the order of the layer's
    ``shape`` (kh, kw, c_in for a conv).  Returns ``(readouts,
    input_counts)``: readouts (N, classes) or (N, H, W, K) int32, and the
    per-timestep per-layer input-spike counts (N, T, L).  ``vmem_bits``
    defaults to the configuration's; a smaller value gives the
    lower-precision control.  Clips run in blocks whose state and
    activations fit in ``budget_bytes``.
    """
    return named.network(cfg).reference_run(cfg, weights, clips, vmem_bits,
                                            budget_bytes)


# ---------------------------------------------------------------------------
# The chip model's cycle and energy arithmetic (paper Sec II-E/F, Table I,
# Fig 13/14), priced chunk by chunk as a served stream is.
# ---------------------------------------------------------------------------
_CM_ROWS = 128          # weight rows per compute macro
_N_CM = 9               # compute macros per core
_N_NU = 3               # neuron macros per core
_IFSPAD_COLS = 16       # logical Vmem pairs per macro
_NEURON_CYCLES = 66     # 2 * 32 Vmem rows + 2
_TRANSFER = 64
_RESET = 32
_FILL = 2
# Calibrated chunk energy (Table I / Fig 14).
_CHUNK_POSITIONS = 128 * 16
_OH_CYCLES = 32 + 2 * 64 + 66 + 4 + 15.8
_C_EFF_F = 120.98e-12
_V_REF, _F_REF, _S_REF = 0.9, 50e6, 0.95
_SHARES = {"cim_macros": 0.62, "s2a": 0.08, "input_loader": 0.10,
           "control_clock": 0.14, "data_movement": 0.06}


def _mapping(spatial: bool, fan_in: int, k: int, positions: int,
             weight_bits: int):
    """(active macros, channel tiles, weight-stationary passes).  A
    spatial layer's weights slide over its output positions, which the
    macros take 16 (the IFspad columns) to a pass; any other layer's
    positions take a pass each."""
    if fan_in <= _CM_ROWS * 3:
        pipelines, macros, cap = _N_NU, 3, _CM_ROWS * 3
    else:
        pipelines, macros, cap = 1, _N_CM, _CM_ROWS * _N_CM
    fan_in_tiles = math.ceil(fan_in / cap)
    parallel = pipelines * (48 // weight_bits)
    channel_tiles = math.ceil(k / parallel)
    position_tiles = math.ceil(positions / (_IFSPAD_COLS if spatial else 1))
    return (pipelines * macros, channel_tiles,
            channel_tiles * position_tiles * fan_in_tiles)


def _cycles_per_chunk(sparsity: float) -> float:
    return 2.0 * (_CHUNK_POSITIONS * (1.0 - sparsity)) + _OH_CYCLES


def _chunk_energy_nj(sparsity: float) -> float:
    power_mw = _C_EFF_F * _V_REF ** 2 * _F_REF * 1e3
    e_ref = power_mw * 1e-3 * (_cycles_per_chunk(_S_REF) / _F_REF) * 1e9
    act_ref, act = 1.0 - _S_REF, 1.0 - sparsity
    cyc_ratio = _cycles_per_chunk(sparsity) / _cycles_per_chunk(_S_REF)
    parts = [
        e_ref * _SHARES["cim_macros"] * (act / act_ref) * 1.0,
        e_ref * _SHARES["s2a"] * (0.7 * act / act_ref + 0.3),
        e_ref * _SHARES["input_loader"],
        e_ref * _SHARES["control_clock"] * cyc_ratio,
        e_ref * _SHARES["data_movement"],
    ]
    return float(sum(p * 1.0 for p in parts))


def _pipeline(compute: np.ndarray, clocks):
    """Asynchronous handshake over the compute-macro chain and the neuron
    macro (Fig 13); ``clocks`` carries (cm_free, recv_ready, nu_free)."""
    cm_free, recv_ready, nu_free = clocks
    finish = 0
    for t in range(compute.shape[0]):
        upstream = 0
        for i in range(_N_CM):
            start = max(cm_free[i], recv_ready[i])
            end_compute = start + _RESET + int(compute[t, i]) + _FILL
            end_send = max(end_compute, upstream) + _TRANSFER
            cm_free[i] = end_send
            if i + 1 < _N_CM:
                recv_ready[i + 1] = end_send
            upstream = end_send
        nu_free = max(nu_free, upstream) + _NEURON_CYCLES
        finish = nu_free
    return finish, (cm_free, recv_ready, nu_free)


def chip_cost(cfg: dict, input_counts: np.ndarray, chunk_T: int):
    """Cumulative (cycles, energy_uj) of one stream priced chunk by chunk.

    ``input_counts`` is the stream's (T, L) per-layer input-spike counts.
    """
    geometry = _layer_geometry(cfg)
    maps = [_mapping(spatial, f, k, p, cfg["weight_bits"])
            for spatial, f, k, p in geometry]
    positions = np.array([f * p for _, f, _, p in geometry], np.float64)
    passes = sum(m[2] for m in maps)
    clocks = (np.zeros(_N_CM, np.int64), np.zeros(_N_CM, np.int64), 0)
    cycles, energy = 0, 0.0
    counts_all = np.asarray(input_counts, np.float64)
    for lo in range(0, counts_all.shape[0], chunk_T):
        counts = counts_all[lo:lo + chunk_T]
        t = counts.shape[0]
        compute = np.zeros((t, _N_CM), np.int64)
        for li, (active, channel_tiles, _) in enumerate(maps):
            per_macro = 2.0 * counts[:, li] * channel_tiles / active
            compute[:, :active] += np.ceil(per_macro)[:, None].astype(
                np.int64)
        cycles, clocks = _pipeline(compute, clocks)
        density = counts.sum() / (positions.sum() * t)
        sparsity = float(np.clip(1.0 - density, 0.0, 1.0))
        energy += float(passes * t * _chunk_energy_nj(sparsity) / 1e3)
    return int(cycles), energy
