"""The yardstick's operation and byte counts, and the table of peaks.

Counts come from the configuration's layer shapes, as its network
description (``bench/networks/<topology>.py``) walks them, and stated bit
widths only, never from the engine's tiling, padding or the tiles it
skipped, so a change to how the work is done cannot move them:

* operations: 2 x P x F x K per weight layer per frame (P output
  positions, F fan-in, K output channels), every frame of an active slot,
  dense;
* bytes per slot and chunk: the chunk's events in at one byte per
  element, each layer's Vmem read and written once at
  ceil(vmem_bits / 8) bytes, any other state the network description
  says a slot keeps (``extra_state_bytes``) read and written once, and
  the readout out at the same width;
* bytes per tick and replica: every weight once at
  ceil(weight_bits / 8) bytes.

The least time for a tick is the larger of its operations over the int8
peak and its bytes over the HBM bandwidth.
"""
from __future__ import annotations

import json
import math
import pathlib

from . import named

__all__ = ["bytes_per_slot_chunk", "layer_work", "least_seconds",
           "macs_per_frame", "peak_for", "weight_bytes"]

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


def layer_work(cfg: dict) -> list:
    """(P, F, K) per weight layer, as the network description counts it."""
    return named.network(cfg).layer_work(cfg)


def macs_per_frame(cfg: dict) -> int:
    return sum(p * f * k for p, f, k in layer_work(cfg))


def _readout_elements(cfg: dict) -> int:
    p, _, k = layer_work(cfg)[-1]
    return k if cfg["readout"] == "rate" else p * k


def bytes_per_slot_chunk(cfg: dict, chunk_T: int) -> int:
    """Least bytes one active slot moves in one chunk of ``chunk_T``."""
    h, w = cfg["input_hw"]
    vb = math.ceil(cfg["vmem_bits"] / 8)
    events = chunk_T * h * w * cfg["in_channels"]
    vmem = sum(2 * p * k * vb for p, _, k in layer_work(cfg))
    extra = getattr(named.network(cfg), "extra_state_bytes", None)
    kept = 2 * extra(cfg) if extra is not None else 0
    return events + vmem + kept + _readout_elements(cfg) * vb


def weight_bytes(cfg: dict) -> int:
    wb = math.ceil(cfg["weight_bits"] / 8)
    return sum(f * k * wb for _, f, k in layer_work(cfg))


def peak_for(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise ValueError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}"
            f" (known: {sorted(table)})")
    return table[device_kind]


def least_seconds(cfg: dict, peak: dict, frames: int, slot_chunks: int,
                  replica_ticks: int) -> tuple:
    """(least seconds, bound) for work of ``frames`` frames in
    ``slot_chunks`` active slot-chunks over ``replica_ticks`` replica
    ticks; the bound is ``"compute"`` or ``"memory"``."""
    ops = 2 * frames * macs_per_frame(cfg)
    chunk_T = cfg["deploy"]["chunk_T"]
    nbytes = (slot_chunks * bytes_per_slot_chunk(cfg, chunk_T)
              + replica_ticks * weight_bytes(cfg))
    compute = ops / peak["int8_ops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
