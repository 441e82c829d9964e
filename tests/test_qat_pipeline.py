"""Train->deploy QAT pipeline: deploy-exact QAT mode, integer export,
checkpoint round trips, bit-exact parity between the post-STE training
graph and the compiled integer engine (1 and 4 cores, chunk_T in {1, T}),
and the benchmark regression gate."""
import dataclasses
import importlib.util
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import Checkpointer
from repro.core.network import gesture_net, init_params, optical_flow_net, run_snn
from repro.core.quant import QuantSpec, po2_quantize, requantize_threshold
from repro.engine import (
    EngineConfig,
    init_state,
    run_chunk,
    run_engine,
)
from repro.snn.export import (
    deploy,
    dequantize_readout,
    export_network,
    load_exported,
    save_exported,
    verify_roundtrip,
)
from repro.snn.train import (
    TrainConfig,
    effective_spec,
    fit,
    precision_sweep,
)


def reduced_gesture(hw=(16, 16), timesteps=4):
    return dataclasses.replace(gesture_net(), input_hw=hw, timesteps=timesteps)


def reduced_flow(hw=(16, 16), timesteps=3):
    return dataclasses.replace(optical_flow_net(), input_hw=hw,
                               timesteps=timesteps)


def events_for(spec, batch=2, seed=1, density=0.1):
    shape = (spec.timesteps, batch) + spec.input_hw + (2,)
    u = jax.random.uniform(jax.random.PRNGKey(seed), shape)
    return (u < density).astype(jnp.float32)


class TestPo2Quantization:
    @pytest.mark.parametrize("bits", [4, 6, 8])
    def test_scales_are_powers_of_two(self, bits):
        spec = QuantSpec(bits)
        w = jax.random.normal(jax.random.PRNGKey(0), (18, 16)) * 0.3
        q, scale = po2_quantize(w, spec, axis=0)
        exps = np.log2(np.asarray(scale))
        np.testing.assert_array_equal(exps, np.round(exps))
        assert int(np.asarray(q).min()) >= spec.w_min
        assert int(np.asarray(q).max()) <= spec.w_max

    def test_grid_covers_amax(self):
        spec = QuantSpec(4)
        w = jnp.array([[0.9, -1.7, 0.0]])
        q, scale = po2_quantize(w, spec, axis=0)
        deq = np.asarray(q, np.float32) * np.asarray(scale)
        # Quantization error bounded by half a step per channel.
        assert np.all(np.abs(deq - np.asarray(w)) <= np.asarray(scale)[0] / 2)
        # All-zero channel gets the neutral scale 1.0.
        assert float(np.asarray(scale)[0, 2]) == 1.0

    def test_threshold_requantization_exact(self):
        spec = QuantSpec(6)
        scale = jnp.asarray([0.25, 0.015625])  # powers of two
        thr_int, thr_scaled = requantize_threshold(0.5, scale, spec)
        np.testing.assert_array_equal(np.asarray(thr_int), [2, 32])
        np.testing.assert_array_equal(np.asarray(thr_scaled),
                                      np.asarray(thr_int) * np.asarray(scale))


class TestDeployExactParity:
    """run_snn(mode="qat") must equal the deployed integer engine exactly."""

    @pytest.mark.parametrize("bits", [4, 6, 8])
    def test_gesture_parity_1_and_4_cores(self, bits):
        spec = reduced_gesture()
        params = init_params(jax.random.PRNGKey(bits), spec)
        ev = events_for(spec)
        exported = export_network(params, spec, QuantSpec(bits))
        for n_cores in (1, 4):
            engine = deploy(exported, spec, n_cores=n_cores)
            rt = verify_roundtrip(params, spec, engine, ev, exported)
            assert rt.exact, (bits, n_cores, rt)

    def test_flow_parity_soft_reset_if(self):
        spec = reduced_flow()
        params = init_params(jax.random.PRNGKey(0), spec)
        ev = events_for(spec, density=0.15)
        exported = export_network(params, spec, QuantSpec(4))
        rt = verify_roundtrip(params, spec, deploy(exported, spec), ev,
                              exported)
        assert rt.exact, rt

    def test_vmem_readout_dequantizes_exactly(self):
        spec = reduced_flow()
        params = init_params(jax.random.PRNGKey(2), spec)
        ev = events_for(spec, density=0.15)
        qspec = QuantSpec(6)
        exported = export_network(params, spec, qspec)
        out = run_engine(deploy(exported, spec), ev)
        qat_out, _ = run_snn(params, ev, spec, qspec, mode="qat")
        deq = dequantize_readout(exported, spec, out.readout)
        np.testing.assert_array_equal(np.asarray(deq), np.asarray(qat_out))

    def test_fused_backend_matches_jnp(self):
        spec = reduced_gesture(timesteps=2)
        params = init_params(jax.random.PRNGKey(3), spec)
        ev = events_for(spec, batch=1)
        exported = export_network(params, spec, QuantSpec(4))
        a = run_engine(deploy(exported, spec), ev)
        fused_cfg = EngineConfig(QuantSpec(4), backend="fused",
                                 interpret=True, block=(32, 32, 32))
        b = run_engine(deploy(exported, spec, cfg=fused_cfg), ev)
        np.testing.assert_array_equal(np.asarray(a.readout),
                                      np.asarray(b.readout))
        np.testing.assert_array_equal(np.asarray(a.spike_counts),
                                      np.asarray(b.spike_counts))

    def test_qat_mode_gradients_flow(self):
        spec = reduced_gesture(timesteps=2)
        params = init_params(jax.random.PRNGKey(4), spec)
        ev = events_for(spec)

        def loss(p):
            out, _ = run_snn(p, ev, spec, QuantSpec(4), mode="qat")
            return jnp.sum(out)

        grads = jax.grad(loss)(params)
        for g, l in zip(grads, spec.layers):
            if l.kind in ("conv", "fc"):
                assert g is not None and bool(jnp.any(g != 0)), l.kind


class TestTrainedExportDeploy:
    """Acceptance: train (smoke budget) -> export -> checkpoint -> reload ->
    deploy on 1 and 4 cores, bit-exact vs the training graph, chunked."""

    @pytest.mark.parametrize("bits", [4, 6, 8])
    def test_full_pipeline(self, bits, tmp_path):
        spec0 = gesture_net()
        cfg = TrainConfig(weight_bits=bits, lr=2e-3, steps=3, warmup=0,
                          batch=2, hw=(16, 16), timesteps=3, seed=bits)
        state, history = fit(spec0, cfg, log_every=0)
        assert all(np.isfinite(history["loss"]))
        spec = effective_spec(spec0, cfg)
        qspec = QuantSpec(bits)

        exported = export_network(state.params, spec, qspec)
        ckpt = Checkpointer(str(tmp_path / "exported"))
        save_exported(ckpt, step=cfg.steps, exported=exported)
        reloaded = load_exported(ckpt, spec)
        assert reloaded.weight_bits == bits
        for ex, re_ in zip(exported.layers, reloaded.layers):
            if ex is None:
                assert re_ is None
                continue
            np.testing.assert_array_equal(ex.w_q, re_.w_q)
            np.testing.assert_array_equal(ex.scale, re_.scale)
            np.testing.assert_array_equal(ex.thr_int, re_.thr_int)

        ev = events_for(spec, batch=2, seed=7)
        qat_out, qat_counts = run_snn(state.params, ev, spec, qspec,
                                      mode="qat", record_spikes=True)
        for n_cores in (1, 4):
            engine = deploy(reloaded, spec, n_cores=n_cores)
            rt = verify_roundtrip(state.params, spec, engine, ev, reloaded)
            assert rt.exact, (bits, n_cores, rt)
            # chunk_T = T (one whole-stream chunk) and chunk_T = 1.
            whole = run_engine(engine, ev)
            st = init_state(engine, ev.shape[1])
            for t in range(ev.shape[0]):
                st, out = run_chunk(engine, st, ev[t:t + 1])
            np.testing.assert_array_equal(np.asarray(out.readout),
                                          np.asarray(whole.readout))
            np.testing.assert_array_equal(np.asarray(whole.readout),
                                          np.asarray(qat_out).astype(np.int64))
            np.testing.assert_array_equal(
                np.asarray(whole.spike_counts),
                np.asarray(qat_counts).astype(np.int64))

    def test_precision_sweep_driver(self):
        cfg = TrainConfig(steps=2, warmup=0, batch=2, hw=(16, 16),
                          timesteps=2, lr=2e-3, eval_batch=4, eval_batches=1)
        out = precision_sweep("gesture", bits=(4, 8), cfg=cfg)
        assert set(out) == {4, 8}
        for b, res in out.items():
            assert res["exported"].weight_bits == b
            assert np.isfinite(res["metric"])


class TestExportCheckpointFailures:
    def _exported(self, bits=4):
        spec = reduced_gesture(timesteps=2)
        params = init_params(jax.random.PRNGKey(0), spec)
        return spec, export_network(params, spec, QuantSpec(bits))

    def test_load_latest_and_explicit_step(self, tmp_path):
        spec, exported = self._exported()
        ckpt = Checkpointer(str(tmp_path))
        save_exported(ckpt, 5, exported)
        save_exported(ckpt, 9, exported)
        assert load_exported(ckpt, spec).weight_bits == 4
        assert load_exported(ckpt, spec, step=5).weight_bits == 4

    def test_load_empty_dir(self, tmp_path):
        spec, _ = self._exported()
        with pytest.raises(FileNotFoundError, match="no checkpoint steps"):
            load_exported(Checkpointer(str(tmp_path)), spec)

    def test_load_non_export_checkpoint(self, tmp_path):
        spec, _ = self._exported()
        ckpt = Checkpointer(str(tmp_path))
        ckpt.save(1, init_params(jax.random.PRNGKey(0), spec))
        with pytest.raises(ValueError, match="no 'exported_snn' metadata"):
            load_exported(ckpt, spec)

    def test_load_missing_meta_field(self, tmp_path):
        spec, exported = self._exported()
        ckpt = Checkpointer(str(tmp_path))
        save_exported(ckpt, 1, exported)
        meta_path = tmp_path / "step_000000001" / "meta.json"
        meta = json.loads(meta_path.read_text())
        del meta["exported_snn"]["weight_bits"]
        meta_path.write_text(json.dumps(meta))
        with pytest.raises(ValueError, match="weight_bits.*missing"):
            load_exported(ckpt, spec)

    def test_load_corrupt_weight_bits(self, tmp_path):
        spec, exported = self._exported()
        ckpt = Checkpointer(str(tmp_path))
        save_exported(ckpt, 1, exported)
        meta_path = tmp_path / "step_000000001" / "meta.json"
        meta = json.loads(meta_path.read_text())
        meta["exported_snn"]["weight_bits"] = 5
        meta_path.write_text(json.dumps(meta))
        with pytest.raises(ValueError, match="not a supported precision"):
            load_exported(ckpt, spec)

    def test_load_corrupt_leaf_shape(self, tmp_path):
        spec, exported = self._exported()
        ckpt = Checkpointer(str(tmp_path))
        save_exported(ckpt, 1, exported)
        # Right leaf count, wrong shape/dtype: must fail validation loudly
        # instead of deploying a silently cast/truncated tensor.  The
        # checkpoint manifest catches this before the export layer's own
        # shape validation even runs.
        np.save(tmp_path / "step_000000001" / "0.npy",
                np.zeros((3, 3), np.float64))
        with pytest.raises(ValueError, match="manifest|corrupted: layer"):
            load_exported(ckpt, spec)

    def test_load_missing_leaf_file(self, tmp_path):
        spec, exported = self._exported()
        ckpt = Checkpointer(str(tmp_path))
        save_exported(ckpt, 1, exported)
        step_dir = tmp_path / "step_000000001"
        os.remove(step_dir / "0.npy")
        with pytest.raises(FileNotFoundError):
            load_exported(ckpt, spec)

    def test_load_structure_mismatch(self, tmp_path):
        spec, exported = self._exported()
        ckpt = Checkpointer(str(tmp_path))
        save_exported(ckpt, 1, exported)
        other = reduced_flow()
        with pytest.raises(ValueError, match="does not match"):
            load_exported(ckpt, other)

    def test_deploy_precision_mismatch(self):
        spec, exported = self._exported(bits=4)
        with pytest.raises(ValueError, match="exported at 4-bit"):
            deploy(exported, spec, cfg=EngineConfig(QuantSpec(8), backend="jnp"))


# ---------------------------------------------------------------------------
# tools/check_bench.py — the CI regression gate.
# ---------------------------------------------------------------------------
def _load_check_bench():
    path = pathlib.Path(__file__).resolve().parent.parent / "tools" / "check_bench.py"
    ispec = importlib.util.spec_from_file_location("check_bench", path)
    mod = importlib.util.module_from_spec(ispec)
    ispec.loader.exec_module(mod)
    return mod


def _write_bench(path, records):
    path.write_text(json.dumps(
        {"schema": 1, "suite": "spidr-benchmarks", "results": records}))


BASE_RECORDS = [
    {"name": "a_1core", "cycles": 1000, "energy_uj": 4.0, "exact": True,
     "metric": "accuracy", "metric_value": 0.8, "wall_us": 10.0},
    {"name": "a_4core", "cycles": 400, "energy_uj": 4.4, "exact": True,
     "metric": "accuracy", "metric_value": 0.8, "wall_us": 99.0},
    {"name": "flow_1core", "cycles": 2000, "energy_uj": 9.0, "exact": True,
     "metric": "aee", "metric_value": 1.5},
]


class TestCheckBench:
    @pytest.fixture()
    def cb(self):
        return _load_check_bench()

    def _run(self, cb, tmp_path, fresh_records, extra=()):
        base = tmp_path / "baseline.json"
        fresh = tmp_path / "fresh.json"
        _write_bench(base, BASE_RECORDS)
        _write_bench(fresh, fresh_records)
        return cb.main([str(fresh), "--baseline", str(base), *extra])

    def test_identical_passes(self, cb, tmp_path):
        assert self._run(cb, tmp_path, BASE_RECORDS) == 0

    def test_wall_time_ignored(self, cb, tmp_path):
        fresh = [dict(r, wall_us=123456.0) for r in BASE_RECORDS]
        assert self._run(cb, tmp_path, fresh) == 0

    def test_cycle_regression_fails(self, cb, tmp_path, capsys):
        fresh = [dict(r) for r in BASE_RECORDS]
        fresh[0]["cycles"] = 1400  # +40% > default 25% tolerance
        assert self._run(cb, tmp_path, fresh) == 1
        out = capsys.readouterr().out
        assert "cycles regressed" in out
        assert "refresh" in out.lower()
        assert "benchmarks/run.py --smoke" in out

    def test_tolerance_is_configurable(self, cb, tmp_path):
        fresh = [dict(r) for r in BASE_RECORDS]
        fresh[0]["cycles"] = 1400
        assert self._run(cb, tmp_path, fresh, extra=["--tol", "0.5"]) == 0

    def test_improvement_passes(self, cb, tmp_path):
        fresh = [dict(r) for r in BASE_RECORDS]
        fresh[0]["cycles"] = 100
        fresh[1]["energy_uj"] = 0.5
        assert self._run(cb, tmp_path, fresh) == 0

    def test_missing_record_fails(self, cb, tmp_path, capsys):
        assert self._run(cb, tmp_path, BASE_RECORDS[:-1]) == 1
        assert "missing from the fresh run" in capsys.readouterr().out

    def test_exactness_regression_fails(self, cb, tmp_path, capsys):
        fresh = [dict(r) for r in BASE_RECORDS]
        fresh[1]["exact"] = False
        assert self._run(cb, tmp_path, fresh) == 1
        assert "was True in the baseline" in capsys.readouterr().out

    def test_accuracy_drop_fails_aee_rise_fails(self, cb, tmp_path):
        fresh = [dict(r) for r in BASE_RECORDS]
        fresh[0]["metric_value"] = 0.6  # accuracy down 0.2 > 0.05
        assert self._run(cb, tmp_path, fresh) == 1
        fresh = [dict(r) for r in BASE_RECORDS]
        fresh[2]["metric_value"] = 2.5  # aee up 1.0 > 0.05
        assert self._run(cb, tmp_path, fresh) == 1
        # The right directions pass: accuracy up, aee down.
        fresh = [dict(r) for r in BASE_RECORDS]
        fresh[0]["metric_value"] = 0.95
        fresh[2]["metric_value"] = 0.5
        assert self._run(cb, tmp_path, fresh) == 0

    def test_subset_mode(self, cb, tmp_path):
        assert self._run(cb, tmp_path, BASE_RECORDS[:1],
                         extra=["--subset"]) == 0
        assert self._run(cb, tmp_path, BASE_RECORDS[:1]) == 1

    # -- the wall_us/bound_us roofline-ratio gate ---------------------------
    # BASE_RECORDS stay bound_us-free on purpose: test_wall_time_ignored
    # above pins the contract that wall_us ALONE is never gated.  The ratio
    # gate engages only for records whose baseline commits both fields.
    ROOFLINE_RECORDS = [
        {"name": "kernel_s95_tblk", "wall_us": 1000.0, "bound_us": 10.0,
         "exact": True, "sparsity": 0.95},
        {"name": "kernel_s95_per_t", "wall_us": 1500.0, "bound_us": 12.0,
         "exact": True, "sparsity": 0.95},
    ]

    def _run_vs(self, cb, tmp_path, base_records, fresh_records, extra=()):
        base = tmp_path / "baseline.json"
        fresh = tmp_path / "fresh.json"
        _write_bench(base, base_records)
        _write_bench(fresh, fresh_records)
        return cb.main([str(fresh), "--baseline", str(base), *extra])

    def test_roofline_identical_passes(self, cb, tmp_path):
        assert self._run_vs(cb, tmp_path, self.ROOFLINE_RECORDS,
                            self.ROOFLINE_RECORDS) == 0

    def test_roofline_ratio_regression_fails(self, cb, tmp_path, capsys):
        fresh = [dict(r) for r in self.ROOFLINE_RECORDS]
        fresh[0]["wall_us"] = 5000.0  # ratio 100 -> 500 > 4x limit
        assert self._run_vs(cb, tmp_path, self.ROOFLINE_RECORDS, fresh) == 1
        out = capsys.readouterr().out
        assert "wall/roofline ratio regressed" in out
        assert "refresh" in out.lower()

    def test_roofline_tolerance_edges(self, cb, tmp_path):
        # Exactly AT the limit (ratio x (1 + tol)) passes; just past fails.
        fresh = [dict(r) for r in self.ROOFLINE_RECORDS]
        fresh[0]["wall_us"] = 4000.0  # ratio 400 == 100 * (1 + 3.0)
        assert self._run_vs(cb, tmp_path, self.ROOFLINE_RECORDS, fresh) == 0
        fresh[0]["wall_us"] = 4100.0
        assert self._run_vs(cb, tmp_path, self.ROOFLINE_RECORDS, fresh) == 1

    def test_roofline_tolerance_is_configurable(self, cb, tmp_path):
        fresh = [dict(r) for r in self.ROOFLINE_RECORDS]
        fresh[0]["wall_us"] = 5000.0
        assert self._run_vs(cb, tmp_path, self.ROOFLINE_RECORDS, fresh,
                            extra=["--tol-roofline", "9.0"]) == 0

    def test_roofline_improvement_passes(self, cb, tmp_path):
        # A faster kernel OR a tighter bound both shrink the ratio: pass.
        fresh = [dict(r) for r in self.ROOFLINE_RECORDS]
        fresh[0]["wall_us"] = 200.0
        fresh[1]["bound_us"] = 50.0
        assert self._run_vs(cb, tmp_path, self.ROOFLINE_RECORDS, fresh) == 0

    def test_missing_bound_key_fails(self, cb, tmp_path, capsys):
        # bound_us vanishing from the fresh run means the ablation stopped
        # pricing its roofline — the field-disappeared path reports it.
        fresh = [dict(r) for r in self.ROOFLINE_RECORDS]
        del fresh[0]["bound_us"]
        assert self._run_vs(cb, tmp_path, self.ROOFLINE_RECORDS, fresh) == 1
        assert "'bound_us' disappeared" in capsys.readouterr().out

    def test_bound_appearing_fresh_is_not_gated(self, cb, tmp_path):
        # Baseline without bound_us keeps the wall_us-ignored contract even
        # when the fresh run starts reporting a bound.
        base = [{"name": "kernel_s95_tblk", "wall_us": 10.0, "exact": True}]
        fresh = [{"name": "kernel_s95_tblk", "wall_us": 999999.0,
                  "bound_us": 1.0, "exact": True}]
        assert self._run_vs(cb, tmp_path, base, fresh) == 0

    def test_roofline_subset_mode(self, cb, tmp_path):
        # The CI perf-gate job runs --perf --smoke: kernel records only.
        assert self._run_vs(cb, tmp_path,
                            BASE_RECORDS + self.ROOFLINE_RECORDS,
                            self.ROOFLINE_RECORDS, extra=["--subset"]) == 0

    def test_committed_baseline_is_current(self):
        """The committed baseline must carry the QAT sweep records the CI
        gate relies on, all bit-exact."""
        base = json.loads(
            (pathlib.Path(__file__).resolve().parent.parent / "benchmarks" /
             "baseline.json").read_text())
        names = {r["name"] for r in base["results"]}
        for bits in (4, 6, 8):
            assert f"qat_gesture_{bits}b_1core" in names
            assert f"qat_gesture_{bits}b_4core" in names
        assert all(r.get("exact", True) for r in base["results"])
        # The perf gate needs a committed measured-vs-bound ratio for the
        # block-sparse kernel ablation.
        by_name = {r["name"]: r for r in base["results"]}
        assert "kernel_s95_per_t" in names
        assert by_name["kernel_s95_per_t"]["wall_us"] > 0
        assert by_name["kernel_s95_per_t"]["bound_us"] > 0


@pytest.mark.slow
class TestFullSizeParity:
    def test_paper_gesture_shapes_roundtrip(self):
        """Full 64x64x20-timestep gesture net: train graph == engine."""
        spec = gesture_net()
        params = init_params(jax.random.PRNGKey(0), spec)
        ev = events_for(spec, batch=1, density=0.05)
        exported = export_network(params, spec, QuantSpec(4))
        rt = verify_roundtrip(params, spec, deploy(exported, spec), ev,
                              exported)
        assert rt.exact, rt
