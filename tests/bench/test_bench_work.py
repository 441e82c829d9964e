"""The benchmark's yardstick: operation and byte counts, and the peaks."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402


def _cfg(name):
    """The configuration file with the tree its network description is
    read from, as ``bench.run.cell_plan`` reads it."""
    return dict(json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                           .read_text()), bench_root=str(ROOT))


def test_optical_flow_macs_per_frame_by_hand():
    p = 288 * 384
    first = p * (3 * 3 * 2) * 32          # 63.7 M
    middle = p * (3 * 3 * 32) * 32        # 1.019 G, six of them
    last = p * (3 * 3 * 32) * 2           # 63.7 M
    assert first == 63_700_992 and middle == 1_019_215_872
    assert work.macs_per_frame(_cfg("optflow-w4v7")) == first + 6 * middle + last
    assert work.macs_per_frame(_cfg("optflow-w4v7")) == 6_242_697_216


def test_gesture_macs_per_frame_by_hand():
    full, half = 64 * 64, 32 * 32
    convs = (full * 18 * 16 + 2 * full * 144 * 16 + 2 * half * 144 * 16)
    fc = 64 * 11
    assert work.macs_per_frame(_cfg("gesture-w4v7")) == convs + fc == 24_773_312


def test_least_bytes_per_slot_chunk():
    # events in (1 B per element), every layer's Vmem read and written once
    # (7-bit Vmem: 1 B), the readout out at the Vmem width.
    p = 288 * 384
    flow = 2 * p * 2 + 2 * p * (7 * 32 + 2) + p * 2
    assert work.bytes_per_slot_chunk(_cfg("optflow-w4v7"), 2) == flow == 50_651_136
    gesture = (2 * 64 * 64 * 2
               + 2 * (3 * 64 * 64 * 16 + 2 * 32 * 32 * 16 + 11) + 11)
    assert work.bytes_per_slot_chunk(_cfg("gesture-w4v7"), 2) == gesture == 475_169


def test_weight_bytes_one_byte_per_4_bit_weight():
    assert work.weight_bytes(_cfg("gesture-w4v7")) == 18 * 16 + 4 * 144 * 16 + 64 * 11


def test_least_time_of_a_full_tick():
    peak = work.peak_for("TPU v5 lite")
    flow = _cfg("optflow-w4v7")
    # 16 slots x 2 frames: ~1.02 ms of int8 compute, ~0.99 ms of bytes.
    t, bound = work.least_seconds(flow, peak, frames=32, slot_chunks=16,
                                  replica_ticks=1)
    assert bound == "compute" and t == pytest.approx(1.016e-3, rel=1e-3)
    gesture = _cfg("gesture-w4v7")
    t, bound = work.least_seconds(gesture, peak, frames=128, slot_chunks=64,
                                  replica_ticks=1)
    assert bound == "memory" and t == pytest.approx(37.1e-6, rel=1e-2)


def test_least_time_ignores_how_the_engine_tiles():
    cfg = _cfg("gesture-w4v7")
    tiled = dict(cfg, deploy=dict(cfg["deploy"], block=[8, 128, 128],
                                  t_block=4, skip_empty=False))
    peak = work.peak_for("TPU v5 lite")
    assert work.least_seconds(cfg, peak, 40, 20, 1) == \
        work.least_seconds(tiled, peak, 40, 20, 1)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no peaks"):
        work.peak_for("TPU v99 imaginary")
