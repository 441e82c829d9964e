"""LIF-FireNet in the benchmark: its network description
(``bench/networks/firenet.py``) against the program, at test size and at
the cell's full size.

A CPU run of the harness serves a tiny LIF-FireNet (the same eight layers
at 4 channels on 8 x 10 frames) through ``spidr.compile`` ->
``spidr.serve`` and compares every answered clip's readout, cycles and
energy with the description's reference, exactly; the reference's input
counts and output spikes are held to the program's; the same run with
the timed path broken (state unchanged, half the slots dropped, an answer
altered, the previous-spike planes zeroed after each chunk) and the
control at one bit less of Vmem fail.
"""
import dataclasses
import json
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import control, generator, named, reference, run, work  # noqa: E402
from bench.record import Clip  # noqa: E402

FULL = dict(json.loads((ROOT / "bench" / "configs"
                        / "lif-firenet-w4v7.json").read_text()),
            bench_root=str(ROOT))


def _tiny() -> dict:
    return dict(json.loads((DATA / "tiny-firenet.json").read_text()),
                bench_root=str(ROOT))


def _pool(cfg, clips=4, seed=2**33 + 7):
    t = json.loads((ROOT / "bench" / "traffic" / "saturated-clips.json")
                   .read_text())
    t["pool"]["clips"] = clips
    return generator.clip_pool(t, cfg, seed)


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
    """A benchmark tree whose one cell serves the tiny LIF-FireNet under
    the saturated traffic, with the real readers and descriptions."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    for kind in ("metrics", "patterns", "arrivals", "networks"):
        (tmp_path / "bench" / kind).symlink_to(ROOT / "bench" / kind)
    (tmp_path / "src").symlink_to(ROOT / "src")
    (tmp_path / "tiny-firenet.json").write_text(
        (DATA / "tiny-firenet.json").read_text())
    t = json.loads((ROOT / "bench" / "traffic" / "saturated-clips.json")
                   .read_text())
    t["pool"]["clips"] = 4
    (tmp_path / "bench" / "traffic" / "tiny-sat.json").write_text(
        json.dumps(t))
    bench["configs"] = [{"name": "tiny-firenet", "source": "test",
                         "file": "tiny-firenet.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny-firenet-sat",
                           "config": "tiny-firenet", "traffic": "tiny-sat",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-firenet-sat"] if "firenet-saturated"
                              in m["workloads"] else [])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_a_served_run_matches_the_reference_exactly(tiny_root, jax_config):
    result = run.run_cell(run.load_benchmark(tiny_root), tiny_root,
                          "tiny-firenet-sat", 2**35 + 11, 0.5, False,
                          require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert {"frames_per_s", "setup_s"} <= set(result["metrics"])


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


def _previous_spikes_dropped(monkeypatch):
    """The recurrent layers' previous spikes lost at every chunk boundary:
    each chunk's first timestep joins zeros."""
    import jax.numpy as jnp

    import repro.engine.streaming as streaming

    real = streaming.run_chunk

    def dropped(engine, state, events, **kw):
        new, out = real(engine, state, events, **kw)
        return dataclasses.replace(new, spikes=tuple(
            jnp.zeros_like(z) for z in new.spikes)), out

    monkeypatch.setattr(streaming, "run_chunk", dropped)


@pytest.mark.parametrize("fault", [_stale_state, _half_the_slots,
                                   _altered_answer,
                                   _previous_spikes_dropped])
def test_a_broken_timed_path_is_not_correct(tiny_root, jax_config,
                                            monkeypatch, fault):
    fault(monkeypatch)
    result = run.run_cell(run.load_benchmark(tiny_root), tiny_root,
                          "tiny-firenet-sat", 2**35 + 11, 0.5, False,
                          require_tpu=False)
    assert not result["correct"]
    assert result["checks"]["readout_mismatch"]["value"] > 0


def test_the_reference_counts_what_the_program_counts():
    """Per-layer input counts of every clip, and the output spikes and
    readout of the whole pool, the reference's against the program's."""
    import jax.numpy as jnp

    from repro import spidr

    cfg = _tiny()
    pool = _pool(cfg)
    qs, params = run.make_weights(cfg)
    readouts, counts = reference.reference_run(
        cfg, [np.asarray(q) for q in qs], pool)
    assert readouts.shape == (4, 8, 10, 2) and counts.shape == (4, 4, 8)
    compiled = spidr.compile(run.program_spec(cfg), params,
                             spidr.DeployTarget(**cfg["deploy"]))
    for i in range(len(pool)):
        out = compiled.run(jnp.asarray(pool[i][:, None], jnp.float32))
        np.testing.assert_array_equal(np.asarray(out.readout)[0],
                                      readouts[i])
        np.testing.assert_array_equal(np.asarray(out.input_counts),
                                      counts[i])
        # Output spikes against the next layer's input count: equal along
        # the chain; a recurrent layer adds its own spikes of t - 1.
        z = np.asarray(out.spike_counts)
        c = counts[i]
        before = np.vstack([np.zeros((1, 8), np.int64), z[:-1]])
        for li in (1, 2, 4, 5, 6):
            np.testing.assert_array_equal(c[:, li + 1], z[:, li])
        np.testing.assert_array_equal(c[:, 1], z[:, 0] + before[:, 1])
        np.testing.assert_array_equal(c[:, 4], z[:, 3] + before[:, 4])
        assert (z[:, 7] == 0).all()      # the head has no neuron
    costs = [reference.chip_cost(cfg, counts[i], 2) for i in range(4)]
    assert all(c > 0 and e > 0 for c, e in costs)


def test_the_description_at_full_size():
    layers = reference.weight_layers(FULL)
    assert [la["fan_in"] for la in layers] == [18, 576, 288, 288, 576, 288,
                                               288, 32]
    assert [la["thr_int"] for la in layers] == [5, 28, 20, 20, 28, 20, 20, 7]
    assert [la["shape"] for la in layers][1] == (3, 3, 64, 32)
    assert all(la["spatial"] for la in layers)
    p = 260 * 346
    assert work.layer_work(FULL) == [(p, la["fan_in"], la["c_out"])
                                     for la in layers]
    macs_per_pixel = sum(f * k for _, f, k in work.layer_work(FULL))
    recurrent = sum(f * k for _, f, k in work.layer_work(FULL) if f == 576)
    assert (macs_per_pixel, recurrent) == (74_368, 36_864)
    assert work.macs_per_frame(FULL) == 6_690_145_280
    network = named.network(FULL)
    assert network.extra_state_bytes(FULL) == 2 * p * 32 == 5_757_440
    # Per slot and 2-step chunk at one byte an element: the events in,
    # every layer's Vmem (seven of 32 channels, the head's 2) and the two
    # previous-spike planes read and written once, the readout out.
    events, vmem = 2 * p * 2, 2 * p * (7 * 32 + 2)
    kept, readout = 2 * 2 * p * 32, p * 2
    assert work.bytes_per_slot_chunk(FULL, 2) == \
        events + vmem + kept + readout
    assert network.check_program(run.program_spec(FULL), FULL) == []


def test_a_wrong_program_is_named():
    import dataclasses

    from repro.configs import lif_firenet

    spec = dataclasses.replace(lif_firenet.CONFIG, layers=tuple(
        dataclasses.replace(la, recurrent=False)
        for la in lif_firenet.CONFIG.layers))
    mismatch = named.network(FULL).check_program(spec, FULL)
    assert len(mismatch) == 2 and mismatch[0].startswith("layer 1:")


def test_the_control_at_one_bit_less_of_vmem_fails():
    cfg = _tiny()
    pool = _pool(cfg, clips=32, seed=123)
    qs, _ = run.make_weights(cfg)
    weights = [np.asarray(q) for q in qs]
    result = control.control_result(cfg, weights, pool, 2)
    assert result["correct"] is False
    checks = result["checks"]
    assert checks["readout_mismatch"]["value"] > 0
    assert checks["unanswered"]["value"] == 0
    # The reference in the same place reads correct.
    want, counts = reference.reference_run(cfg, weights, pool)
    sound = []
    for i, c in enumerate(want):
        cycles, energy = reference.chip_cost(cfg, counts[i], 2)
        sound.append(Clip(rid=i, pool_index=i, due=0.0, done=0.0,
                          handle=types.SimpleNamespace(
                              request=types.SimpleNamespace(
                                  readout=c, cycles=cycles,
                                  energy_uj=energy))))
    assert all(v <= lim for v, lim in
               run.check_clips(cfg, weights, pool, sound, 2).values())
