"""Network descriptions: every per-network fact the harness uses comes
from ``bench/networks/<topology>.py``, named by the configuration.

The chain, which both paper networks are, gives the numbers the harness
gave before the descriptions existed, to the last bit.  A network that is
not a chain is added as a description and a configuration that names it,
and runs through the harness with no file of the harness edited.
"""
import hashlib
import json
import pathlib
import shutil
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

from bench import generator, reference, run, work  # noqa: E402

PEAK = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def _cfg(path: pathlib.Path) -> dict:
    """A configuration file read as ``run.cell_plan`` reads it, with the
    tree its network description is read from."""
    return dict(json.loads(path.read_text()), bench_root=str(ROOT))


def _sha(arrays) -> str:
    return hashlib.sha256(b"".join(np.asarray(a).tobytes()
                                   for a in arrays)).hexdigest()


# Taken by running the parent tree's ``bench/work.py`` and ``run.make_weights``
# (before the network descriptions) on the same configuration files.
_WORK = {
    "gesture-w4v7": dict(
        macs=24_773_312, bytes2=475_169, weight_bytes=10_208,
        least=(6.4193931623931625e-06, "memory"),
        q_sha="d240d8f10d6300c6fd9edfa531a9421d9d0096122864d8b439a7ee9a88df947e",
        p_sha="5a8646b9f74d678e13bbc1c0a43b541fba5271b5bdd845519a7314a50741fcc0",
        pools=[3, 6, 7]),
    "optflow-w4v7": dict(
        macs=6_242_697_216, bytes2=50_651_136, weight_bytes=56_448,
        least=(0.0011754697047938931, "compute"),
        q_sha="f7b1fd31c2c93ba9c21f242b9b5bf56aa435986c674d79b358ce608e34b2913e",
        p_sha="dfe73abbe6d4f6352544ce1663f77fcd2ba60dfccdbf8ab286dd12310bb657bc",
        pools=[]),
}


@pytest.mark.parametrize("name", sorted(_WORK))
def test_the_chain_counts_the_real_configurations_as_before(name):
    cfg = _cfg(ROOT / "bench" / "configs" / f"{name}.json")
    assert "topology" not in cfg
    want = _WORK[name]
    assert work.macs_per_frame(cfg) == want["macs"]
    assert work.bytes_per_slot_chunk(cfg, 2) == want["bytes2"]
    assert work.weight_bytes(cfg) == want["weight_bytes"]
    assert work.least_seconds(cfg, work.peak_for("TPU v5 lite"), 37, 11,
                              3) == want["least"]
    qs, params = run.make_weights(cfg)
    assert _sha(qs) == want["q_sha"]
    assert _sha(p for p in params if p is not None) == want["p_sha"]
    assert [i for i, p in enumerate(params) if p is None] == want["pools"]


# Taken by running the parent tree's ``bench/reference.py`` on a 4-clip
# pool of seed 2**33 + 7 with the weights of ``run.make_weights``.
_REFERENCE = {
    "tiny-gesture": dict(
        traffic="poisson-clips", shape=(4, 11),
        readout_sha="63f880e41eecdf1dbec3a9a016a77546"
                    "784a116c60511a86c669fb83568c15a5",
        counts_sha="cac3c8b7be8512e74bab23202d878798"
                   "dc3b0deed27d187688397fcabe6cd072",
        counts=[[5, 110, 152, 208, 306, 122],
                [108, 1057, 1132, 806, 927, 192],
                [208, 2037, 1963, 1253, 1414, 195],
                [193, 1709, 1464, 763, 1163, 193]],
        cycles=[2244, 5332, 6125, 5311],
        energy=[2.9684848252462466, 4.244623662583066, 5.339665364521987,
                4.730240821834771]),
    "tiny-optflow": dict(
        traffic="saturated-clips", shape=(4, 12, 16, 2),
        readout_sha="5046a6f2cfaf05cc7661ad6ac097dec5"
                    "e27bab99914972bf8604c7406f49049e",
        counts_sha="b5984c2543a1b4fb61f423ad0884b375"
                   "3ea66261bfb7f254b51cd4e93793a4c8",
        counts=[[84, 3197, 3918, 6604, 8817, 8032, 7770, 6652],
                [28, 989, 1465, 2878, 4899, 5781, 6078, 5739],
                [104, 3917, 4841, 7620, 9545, 8197, 7597, 6693],
                [70, 2749, 3355, 6098, 8658, 7996, 7621, 6606]],
        cycles=[32832, 23415, 35381, 32374],
        energy=[11.652152239548649, 8.887686225827528, 12.204499517657544,
                11.343704820581454]),
}


@pytest.mark.parametrize("name", sorted(_REFERENCE))
def test_the_chain_reference_reads_as_before(name):
    want = _REFERENCE[name]
    cfg = _cfg(DATA / f"{name}.json")
    traffic = json.loads((ROOT / "bench" / "traffic"
                          / f"{want['traffic']}.json").read_text())
    traffic["pool"]["clips"] = 4
    pool = generator.clip_pool(traffic, cfg, 2**33 + 7)
    qs, _ = run.make_weights(cfg)
    readouts, counts = reference.reference_run(cfg, [np.asarray(q)
                                                     for q in qs], pool)
    assert readouts.shape == want["shape"] and readouts.dtype == np.int32
    assert _sha([readouts]) == want["readout_sha"]
    assert counts.dtype == np.int64 and _sha([counts]) == want["counts_sha"]
    assert counts.sum(axis=1).tolist() == want["counts"]
    costs = [reference.chip_cost(cfg, counts[i], 2) for i in range(4)]
    assert [c for c, _ in costs] == want["cycles"]
    assert [e for _, e in costs] == want["energy"]


def _skip_tree(tmp, extra=""):
    """A copy of the benchmark with the test's skip network added as a
    description, a configuration and a cell; returns the files that were
    there before, with their bytes."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp / "bench").rglob("*")
              if p.is_file()}
    (tmp / "bench" / "networks" / "skip.py").write_text(
        (DATA / "skip.py").read_text() + extra)
    shutil.copy(DATA / "tiny-skip.json", tmp / "bench" / "configs")
    traffic = json.loads((ROOT / "bench" / "traffic" / "poisson-clips.json")
                         .read_text())
    traffic["pool"]["clips"] = 4
    (tmp / "bench" / "traffic" / "skip-clips.json").write_text(
        json.dumps(traffic))
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-skip", "source": "test",
                             "file": "bench/configs/tiny-skip.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "skip-poisson", "config": "tiny-skip",
                               "traffic": "skip-clips", "chips": 1,
                               "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return before


def _conv3x3(x, w):
    """(H, W, C) binary plane * (3, 3, C, K) weights, padding 1, by loops."""
    h, wd, _ = x.shape
    xp = np.pad(x, ((1, 1), (1, 1), (0, 0))).astype(np.int64)
    out = np.zeros((h, wd, w.shape[-1]), np.int64)
    for i in range(h):
        for j in range(wd):
            for di in range(3):
                for dj in range(3):
                    out[i, j] += xp[i + di, j + dj] @ w[di, dj]
    return out


def _lif_hard(acc, v, thr):
    """7-bit Vmem, leak V - (V >> 3), hard reset, as the config states."""
    v = v - (v >> 3)
    v = np.clip(v + np.clip(acc, -64, 63), -64, 63)
    s = (v >= thr).astype(np.int64)
    return v * (1 - s), s


def test_a_non_chain_network_runs_from_its_own_files(tmp_path):
    before = _skip_tree(tmp_path)
    plan = run.cell_plan(run.load_benchmark(tmp_path), tmp_path,
                         "skip-poisson")
    cfg = plan["cfg"]
    assert cfg["bench_root"] == str(tmp_path)

    qs, params = run.make_weights(cfg)
    assert [q.shape for q in qs] == [(18, 4), (36, 4)]
    assert all(int(q[0, 0]) == 7 for q in qs)
    assert [p.shape for p in params] == [(18, 4), (36, 4)]
    weights = [np.asarray(q) for q in qs]

    pool = generator.clip_pool(plan["traffic"], cfg, 2**33 + 9, tmp_path)
    assert pool.shape == (4, 3, 6, 6, 2)
    readouts, counts = reference.reference_run(cfg, weights, pool)
    assert readouts.shape == (4, 6, 6, 4) and counts.shape == (4, 3, 2)

    # The first clip by hand: the skip adds layer 1's spikes to layer 2's
    # current and is counted among layer 2's inputs.
    w1 = weights[0].astype(np.int64).reshape(3, 3, 2, 4)
    w2 = weights[1].astype(np.int64).reshape(3, 3, 4, 4)
    v1 = np.zeros((6, 6, 4), np.int64)
    v2 = np.zeros((6, 6, 4), np.int64)
    fired = 0
    for t in range(3):
        x = pool[0, t]
        v1, s1 = _lif_hard(_conv3x3(x, w1), v1, 3)
        v2, _ = _lif_hard(_conv3x3(s1, w2) + s1, v2, 4)
        assert counts[0, t, 0] == np.count_nonzero(x)
        assert counts[0, t, 1] == 2 * s1.sum()
        fired += s1.sum()
    assert fired > 0 and np.any(v2 != 0)
    assert np.array_equal(readouts[0], v2)

    # The chip model's geometry and the yardstick come from the
    # description: the skip is one more input of each layer-2 channel, and
    # both layers slide their weights over the 6 x 6 positions.
    geometry = reference._layer_geometry(cfg)
    assert geometry == [(True, 18, 4, 36), (True, 37, 4, 36)]
    # By hand, each layer: fan-in <= 384, so 3 pipelines of 3 macros (9
    # active) with 3 x 48 / 4 = 36 channels in parallel: 1 channel tile;
    # 36 positions at 16 a pass: 3 position tiles; 1 fan-in tile; 3 passes.
    assert [reference._mapping(*g, 4) for g in geometry] == [(9, 1, 3),
                                                             (9, 1, 3)]
    cycles, energy = reference.chip_cost(cfg, counts[0], 2)
    assert isinstance(cycles, int) and cycles > 0
    # Energy: 6 passes a timestep, each a chunk at that chunk's density of
    # inputs over the 36 x 18 + 36 x 37 weighted positions.
    by_hand = 0.0
    for lo in (0, 2):
        chunk = counts[0, lo:lo + 2]
        t = chunk.shape[0]
        sparsity = 1.0 - chunk.sum() / (36 * (18 + 37) * t)
        by_hand += 6 * t * reference._chunk_energy_nj(sparsity) / 1e3
    assert energy == by_hand
    macs = 36 * 18 * 4 + 36 * 37 * 4
    assert work.macs_per_frame(cfg) == macs
    slot_bytes = 2 * 36 * 2 + 2 * 2 * 36 * 4 + 36 * 4
    assert work.bytes_per_slot_chunk(cfg, 2) == slot_bytes
    assert work.weight_bytes(cfg) == 18 * 4 + 37 * 4
    assert work.least_seconds(cfg, PEAK, 10, 5, 2) == (
        (5 * slot_bytes + 2 * (18 * 4 + 37 * 4)) / 819e9, "memory")
    assert work.least_seconds(cfg, PEAK, 10**6, 5, 2) == (
        2 * 10**6 * macs / 393e12, "compute")

    after = {p: p.read_bytes() for p in before}
    assert after == before   # no file that was there was edited


def test_state_a_description_keeps_is_counted_read_and_written(tmp_path):
    _skip_tree(tmp_path, extra="\n\ndef extra_state_bytes(cfg):\n"
                               "    return 1000\n")
    cfg = run.cell_plan(run.load_benchmark(tmp_path), tmp_path,
                        "skip-poisson")["cfg"]
    assert work.bytes_per_slot_chunk(cfg, 2) == \
        2 * 36 * 2 + 2 * 2 * 36 * 4 + 36 * 4 + 2 * 1000


def test_an_unknown_network_names_the_missing_file():
    cfg = _cfg(ROOT / "bench" / "configs" / "gesture-w4v7.json")
    cfg["topology"] = "no-such-network"
    with pytest.raises(KeyError, match=r"networks/no-such-network\.py"):
        reference.weight_layers(cfg)


def test_a_configuration_not_read_by_cell_plan_has_no_network():
    cfg = json.loads((ROOT / "bench" / "configs" / "gesture-w4v7.json")
                     .read_text())
    with pytest.raises(KeyError, match="bench_root"):
        work.layer_work(cfg)
    plan = run.cell_plan(run.load_benchmark(ROOT), ROOT, "gesture-poisson")
    assert plan["cfg"]["bench_root"] == str(ROOT)
    assert work.layer_work(plan["cfg"]) == work.layer_work(
        dict(cfg, bench_root=str(ROOT)))
