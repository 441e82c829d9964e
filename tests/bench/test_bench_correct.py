"""The comparison that decides ``correct``, proven to fail.

A whole run of the harness is driven on the CPU at a test size (the look
for a chip is skipped), once as it is and once with each fault a served
cell can have planted in the timed path underneath it: a chunk step that
returns its state unchanged, half of the slots left out of the step, and
an answer altered where it is produced; and, for four replicas, half
of them left out of the fleet's tick.  (The cells have no exchange
between chips: four-chip replicas never talk to each other.)  The
control, the reference itself at one bit less of Vmem, has to differ
from the reference on the same clips.
"""
import json
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import control, generator, reference, run  # noqa: E402
from bench.record import Clip  # noqa: E402


@pytest.fixture
def jax_config():
    """Keep the harness's compile-cache settings out of other tests."""
    import jax

    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


@pytest.fixture
def tiny_root(tmp_path):
    """A benchmark tree with CPU-sized cells and the real readers: both
    one-chip cells, and four gesture replicas (on one CPU device)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    for kind in ("metrics", "patterns", "arrivals", "networks"):
        (tmp_path / "bench" / kind).symlink_to(ROOT / "bench" / kind)
    (tmp_path / "src").symlink_to(ROOT / "src")
    cells = {"tiny-poisson": ("tiny-gesture", "poisson-clips", 16, 1),
             "tiny-saturated": ("tiny-optflow", "saturated-clips", 4, 1),
             "tiny-poisson-x4": ("tiny-gesture", "poisson-clips-x4", 16, 4)}
    bench["configs"] = []
    bench["workloads"] = []
    for cell, (config, traffic, clips, chips) in cells.items():
        if not (tmp_path / f"{config}.json").exists():
            bench["configs"].append({"name": config, "source": "test",
                                     "file": f"{config}.json",
                                     "reduced": [], "why": "test"})
            (tmp_path / f"{config}.json").write_text(
                (DATA / f"{config}.json").read_text())
        t = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                       .read_text())
        t["pool"]["clips"] = clips
        t["rate_clips_per_s"] = 60.0
        (tmp_path / "bench" / "traffic" / f"{cell}.json").write_text(
            json.dumps(t))
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": cell, "chips": chips,
                                   "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            open_ = any("poisson" in w for w in m["workloads"])
            m["workloads"] = [c for c in cells if ("poisson" in c) == open_]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def _run(root, cell, seed=2**35 + 11, weights_seed=None):
    return run.run_cell(run.load_benchmark(root), root, cell, seed, 0.5,
                        False, require_tpu=False, weights_seed=weights_seed)


@pytest.mark.parametrize("cell,weights_seed", [
    ("tiny-poisson", None), ("tiny-saturated", None),
    ("tiny-poisson", 2**33 + 5), ("tiny-poisson-x4", None)])
def test_a_sound_run_is_correct(tiny_root, jax_config, cell, weights_seed):
    result = _run(tiny_root, cell, weights_seed=weights_seed)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert all(c["value"] == 0 for c in result["checks"].values())


def _stale_state(monkeypatch):
    import repro.engine.streaming as streaming

    real = streaming.run_chunk

    def stale(engine, state, events, **kw):
        _, out = real(engine, state, events, **kw)
        return state, out

    monkeypatch.setattr(streaming, "run_chunk", stale)


def _half_the_slots(monkeypatch):
    import repro.engine.streaming as streaming

    real = streaming.run_chunk

    def half(engine, state, events, **kw):
        keep = events.shape[1] // 2
        return real(engine, state, events.at[:, keep:].set(0), **kw)

    monkeypatch.setattr(streaming, "run_chunk", half)


def _altered_answer(monkeypatch):
    import repro.engine.streaming as streaming

    real = streaming.StreamSessionManager.step

    def altered(self, chunks):
        updates = real(self, chunks)
        for up in updates.values():
            up.readout = np.asarray(up.readout) + 1
            break
        return updates

    monkeypatch.setattr(streaming.StreamSessionManager, "step", altered)


@pytest.mark.parametrize("cell", ["tiny-poisson", "tiny-saturated",
                                  "tiny-poisson-x4"])
@pytest.mark.parametrize("fault", [_stale_state, _half_the_slots,
                                   _altered_answer])
def test_a_broken_timed_path_is_not_correct(tiny_root, jax_config,
                                            monkeypatch, fault, cell):
    fault(monkeypatch)
    result = _run(tiny_root, cell)
    assert not result["correct"]
    assert result["checks"]["readout_mismatch"]["value"] > 0


def test_replicas_left_out_of_the_tick_are_not_correct(tiny_root,
                                                       jax_config,
                                                       monkeypatch):
    """Half of the four replicas never tick: the clips placed on them are
    never answered."""
    from repro.serving import fleet as fleet_mod

    real = fleet_mod.Fleet.step

    def half(self):
        workers = self.workers
        self.workers = workers[: len(workers) // 2]
        try:
            return real(self)
        finally:
            self.workers = workers

    monkeypatch.setattr(fleet_mod.Fleet, "step", half)
    monkeypatch.setattr(run, "DRAIN_S", 2.0)
    result = _run(tiny_root, "tiny-poisson-x4")
    assert not result["correct"]
    assert result["checks"]["unanswered"]["value"] > 0


@pytest.mark.parametrize("config,traffic", [
    ("tiny-gesture", "poisson-clips"), ("tiny-optflow", "saturated-clips")])
def test_the_control_at_one_bit_less_of_vmem_fails(config, traffic):
    cfg = dict(json.loads((DATA / f"{config}.json").read_text()),
               bench_root=str(ROOT))
    t = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                   .read_text())
    t["pool"]["clips"] = 8
    clips = generator.clip_pool(t, cfg, 123)
    rng = np.random.default_rng(0)
    weights = []
    for layer in reference.weight_layers(cfg):
        f = (layer["kh"] * layer["kw"] * layer["c_in"]
             if layer["kind"] == "conv" else layer["c_in"])
        weights.append(rng.integers(-7, 8, (f, layer["c_out"]), np.int8))
    # The control's answers, judged in the program's place by the
    # comparison that decides ``correct`` in a run.
    result = control.control_result(cfg, weights, clips, 2)
    assert result["correct"] is False
    checks = result["checks"]
    assert checks["readout_mismatch"]["value"] > 0
    assert checks["cycles_mismatch"]["value"] + \
        checks["energy_mismatch"]["value"] > 0
    assert checks["unanswered"]["value"] == 0
    assert checks["compared_none"]["value"] == 0
    # The reference in the same place reads correct.
    want, counts = reference.reference_run(cfg, weights, clips)
    sound = []
    for i, c in enumerate(want):
        cycles, energy = reference.chip_cost(cfg, counts[i], 2)
        sound.append(Clip(rid=i, pool_index=i, due=0.0, done=0.0,
                          handle=types.SimpleNamespace(
                              request=types.SimpleNamespace(
                                  readout=c, cycles=cycles,
                                  energy_uj=energy))))
    assert all(v <= lim for v, lim in
               run.check_clips(cfg, weights, clips, sound, 2).values())


def test_without_a_tpu_the_run_prints_no_result(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path)}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gesture-poisson",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert "{" not in proc.stdout
    assert "needs 1 TPU chip" in proc.stderr
