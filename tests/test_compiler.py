"""Tests: multi-core compiler (IR -> partition -> select -> schedule) and
the engine's compiled execution + per-core cost attribution.

The load-bearing contract is the ISSUE-3 acceptance criterion: compiling
the gesture network onto 4 cores must produce a schedule whose engine
outputs are bit-exact with the single-core path — spike counts and final
Vmem — under whole-stream and chunked (chunk_T in {1, 3}) execution, with
per-core cycle sums matching the single-core total within the modeled
spike-routing/duplication overhead.
"""
import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler import (
    CoreGrid,
    build_graph,
    compile_network,
    partition_graph,
    select_layer,
)
from repro.configs import spidr_gesture
from repro.core.network import gesture_net, init_params, optical_flow_net
from repro.core.quant import QuantSpec
from repro.engine import (
    EngineConfig,
    StreamSessionManager,
    build_engine,
    compile_engine,
    estimate_cost,
    estimate_multicore_cost,
    init_state,
    run_chunk,
    run_engine,
)


def _events(spec, batch=2, seed=0, sparsity=0.9):
    rng = np.random.default_rng(seed)
    shape = (spec.timesteps, batch) + tuple(spec.input_hw) + (2,)
    return jnp.asarray((rng.random(shape) > sparsity).astype(np.float32))


@pytest.fixture(scope="module")
def gesture_setup():
    spec = spidr_gesture.reduced(hw=(16, 16), timesteps=6)
    params = init_params(jax.random.PRNGKey(0), spec)
    qspec = QuantSpec(4)
    eng = build_engine(spec, params, EngineConfig(qspec, backend="jnp"))
    schedule = compile_network(spec, n_cores=4, qspec=qspec)
    meng = compile_engine(eng, schedule)
    return spec, eng, schedule, meng


# ---------------------------------------------------------------------------
# IR
# ---------------------------------------------------------------------------
class TestIR:
    def test_graph_structure(self):
        spec = gesture_net()
        g = build_graph(spec)
        assert len(g.nodes) == len(spec.layers)
        kinds = [n.kind for n in g.nodes]
        assert kinds == [l.kind for l in spec.layers]
        # Chain: node i consumes node i-1.
        for i, n in enumerate(g.nodes):
            assert n.inputs == ((i - 1,) if i else ())
        assert len(g.weight_nodes) == len(spec.layer_shapes())

    def test_routing_volumes(self):
        g = build_graph(gesture_net())
        # First conv consumes the 64x64x2 event plane.
        assert g.nodes[0].in_positions == 64 * 64 * 2
        # FC consumes the adaptive-pooled 2*2*16 = 64 plane.
        fc = g.weight_nodes[-1]
        assert fc.kind == "fc" and fc.in_positions == 64

    def test_producer_skips_pools(self):
        g = build_graph(gesture_net())
        fc = g.weight_nodes[-1]
        prod = g.producer_of(fc)
        # Nearest weight ancestor of the FC is the last conv (idx 5),
        # through both pool nodes.
        assert prod is not None and prod.idx == 5 and prod.kind == "conv"


# ---------------------------------------------------------------------------
# Partitioner
# ---------------------------------------------------------------------------
class TestPartition:
    def test_gesture_4b_is_pure_pipeline(self):
        """Every gesture layer fits one core at 4-bit: whole-layer
        placement only, spread over all cores."""
        g = build_graph(gesture_net())
        parts = partition_graph(g, CoreGrid(4), QuantSpec(4))
        assert all(not p.split and len(p.slices) == 1 for p in parts)
        used = {p.slices[0].core for p in parts}
        assert used == {0, 1, 2, 3}  # greedy balance touches every core

    def test_flow_8b_channel_splits(self):
        """32-channel convs at 8-bit need 2 channel tiles -> split."""
        g = build_graph(optical_flow_net())
        parts = partition_graph(g, CoreGrid(4), QuantSpec(8))
        split = [p for p in parts if p.split]
        assert split, "expected channel-split layers at 8-bit"
        for p in split:
            assert len(p.slices) >= 2

    def test_slices_contiguous_cover(self):
        for spec, bits in ((gesture_net(), 4), (optical_flow_net(), 8)):
            g = build_graph(spec)
            parts = partition_graph(g, CoreGrid(4), QuantSpec(bits))
            for node, p in zip(g.weight_nodes, parts):
                lo = 0
                for s in sorted(p.slices, key=lambda s: s.lo):
                    assert s.lo == lo and s.width >= 1
                    lo = s.hi
                assert lo == node.shape.out_channels

    def test_single_core_grid(self):
        g = build_graph(gesture_net())
        parts = partition_graph(g, CoreGrid(1), QuantSpec(4))
        assert all(p.slices[0].core == 0 for p in parts)


# ---------------------------------------------------------------------------
# Selector
# ---------------------------------------------------------------------------
class TestSelect:
    def test_conv_weight_stationary_fc_vmem(self):
        g = build_graph(gesture_net())
        nodes = g.weight_nodes
        deep_conv = nodes[1]          # conv(16->16): real position reuse
        plan = select_layer(deep_conv, deep_conv.shape, (QuantSpec(4),))
        assert plan.stationarity == "weight"
        fc = nodes[-1]
        plan = select_layer(fc, fc.shape, (QuantSpec(4),))
        assert plan.stationarity == "vmem"

    def test_mode_matches_fig12_for_paper_layers(self):
        """Where Mode 1's 3x channel parallelism is actually used
        (out_channels > 48/W_b) the cost model rediscovers the Fig 12
        fan-in rule.  Narrow layers (gesture's FC(64,11), flow's final
        conv to 2 channels) legitimately flip to Mode 2: with channel
        tiles == 1 either way, compute is identical and Mode 2 stores the
        fan-in across all 9 macros instead of replicating it per pipeline
        — less weight-load traffic."""
        from repro.core.modes import CM_WEIGHT_ROWS

        qspec = QuantSpec(4)
        for spec in (gesture_net(), optical_flow_net()):
            g = build_graph(spec)
            for node in g.weight_nodes:
                plan = select_layer(node, node.shape, (qspec,))
                if node.shape.out_channels > qspec.neurons_per_row:
                    want = 1 if node.shape.fan_in <= CM_WEIGHT_ROWS * 3 else 2
                    assert plan.mode == want, (spec.name, node.idx)
                else:
                    assert plan.mode == 2, (spec.name, node.idx)

    def test_precision_pinned_by_default(self):
        sch = compile_network(gesture_net(), n_cores=2, qspec=QuantSpec(6))
        assert all(l.plan.spec == QuantSpec(6) for l in sch.layers)

    def test_precision_exploration_rejected_by_engine(self):
        spec = spidr_gesture.reduced(hw=(16, 16), timesteps=2)
        params = init_params(jax.random.PRNGKey(0), spec)
        eng = build_engine(spec, params,
                           EngineConfig(QuantSpec(8), backend="jnp"))
        sch = compile_network(
            spec, n_cores=2, qspec=QuantSpec(8),
            allowed_specs=(QuantSpec(4), QuantSpec(6), QuantSpec(8)))
        if any(l.plan.spec != QuantSpec(8) for l in sch.layers):
            with pytest.raises(ValueError, match="cost analysis"):
                compile_engine(eng, sch)
        else:  # pragma: no cover - selector kept 8-bit everywhere
            pytest.skip("selector picked the engine precision anyway")


# ---------------------------------------------------------------------------
# Schedule
# ---------------------------------------------------------------------------
class TestSchedule:
    def test_leafless_pytree(self, gesture_setup):
        _, _, schedule, _ = gesture_setup
        leaves, treedef = jax.tree_util.tree_flatten(schedule)
        assert leaves == []
        assert jax.tree_util.tree_unflatten(treedef, leaves) is schedule

    def test_describe(self, gesture_setup):
        _, _, schedule, _ = gesture_setup
        text = schedule.describe()
        assert "4 cores" in text and "mode=" in text and "core" in text

    def test_route_factors(self, gesture_setup):
        _, _, schedule, _ = gesture_setup
        first = schedule.layers[0]
        # Sensor feed to a single consumer core is free.
        assert first.route_factor == 0.0
        # Consecutive whole layers on different cores route every spike once.
        for prev, cur in zip(schedule.layers, schedule.layers[1:]):
            if prev.slices[0].core != cur.slices[0].core:
                assert cur.route_factor == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Acceptance: bit-exact multi-core execution.
# ---------------------------------------------------------------------------
class TestMulticoreExecution:
    def test_whole_stream_bit_exact(self, gesture_setup):
        spec, eng, _, meng = gesture_setup
        ev = _events(spec)
        a, b = run_engine(eng, ev), run_engine(meng, ev)
        np.testing.assert_array_equal(np.asarray(a.readout),
                                      np.asarray(b.readout))
        np.testing.assert_array_equal(np.asarray(a.spike_counts),
                                      np.asarray(b.spike_counts))
        np.testing.assert_array_equal(np.asarray(a.input_counts),
                                      np.asarray(b.input_counts))

    @pytest.mark.parametrize("chunk_T", [1, 3])
    def test_chunked_bit_exact_with_final_vmem(self, gesture_setup, chunk_T):
        spec, eng, _, meng = gesture_setup
        ev = _events(spec)
        ref_state = init_state(eng, ev.shape[1])
        ref_state, ref_out = run_chunk(eng, ref_state, ev)
        st = init_state(meng, ev.shape[1])
        for t0 in range(0, ev.shape[0], chunk_T):
            st, out = run_chunk(meng, st, ev[t0:t0 + chunk_T])
        np.testing.assert_array_equal(np.asarray(ref_out.readout),
                                      np.asarray(out.readout))
        for v_ref, v in zip(ref_state.vmem, st.vmem):
            if v_ref is None:
                assert v is None
            else:
                np.testing.assert_array_equal(np.asarray(v_ref),
                                              np.asarray(v))

    def test_split_layers_bit_exact(self):
        """Channel-split placement (8-bit flow-style convs) stays exact."""
        spec = dataclasses.replace(
            optical_flow_net(), input_hw=(16, 16), timesteps=3)
        params = init_params(jax.random.PRNGKey(1), spec)
        qspec = QuantSpec(8)
        eng = build_engine(spec, params, EngineConfig(qspec, backend="jnp"))
        sch = compile_network(spec, n_cores=4, qspec=qspec)
        assert sch.n_split_layers > 0
        meng = compile_engine(eng, sch)
        ev = _events(spec, batch=1, seed=2)
        a, b = run_engine(eng, ev), run_engine(meng, ev)
        np.testing.assert_array_equal(np.asarray(a.readout),
                                      np.asarray(b.readout))
        np.testing.assert_array_equal(np.asarray(a.spike_counts),
                                      np.asarray(b.spike_counts))

    def test_fused_backend_multicore(self):
        """A 2-core plan on the Pallas fused backend (interpret)."""
        spec = spidr_gesture.reduced(hw=(16, 16), timesteps=2)
        params = init_params(jax.random.PRNGKey(0), spec)
        qspec = QuantSpec(4)
        cfg = EngineConfig(qspec, backend="fused", interpret=True,
                           block=(128, 128, 128))
        eng = build_engine(spec, params, cfg)
        meng = compile_engine(eng, compile_network(spec, n_cores=2,
                                                   qspec=qspec))
        ev = _events(spec, batch=1)[:2]
        a, b = run_engine(eng, ev), run_engine(meng, ev)
        np.testing.assert_array_equal(np.asarray(a.readout),
                                      np.asarray(b.readout))

    def test_double_compile_rejected(self, gesture_setup):
        _, _, schedule, meng = gesture_setup
        with pytest.raises(AssertionError):
            compile_engine(meng, schedule)

    @pytest.mark.parametrize("backend", ["jnp", "fused"])
    def test_four_cores_run_one_walk(self, backend):
        """On a host with 4 devices (forced, in a subprocess so the device
        count doesn't leak into this process's jax), a 4-core target's
        chunk step lowers to the 1-core program; its readouts and counts
        are one core's, and a stream's per-core cycles are
        ``estimate_multicore_cost``'s for its counts."""
        code = f"""
import numpy as np, jax, jax.numpy as jnp
from repro import spidr
from repro.configs import spidr_gesture
from repro.core.network import init_params
from repro.engine import estimate_multicore_cost, init_state, run_chunk
assert len(jax.devices()) == 4
spec = spidr_gesture.reduced(hw=(16, 16), timesteps=4)
params = init_params(jax.random.PRNGKey(0), spec)
one, four = (spidr.compile(spec, params, spidr.DeployTarget(
    backend="{backend}", n_cores=n)) for n in (1, 4))
assert one.schedule is None and four.schedule.n_cores == 4
rng = np.random.default_rng(0)
ev = jnp.asarray((rng.random((4, 2, 16, 16, 2)) > 0.9).astype(np.float32))

def step_text(compiled):
    engine = compiled.engine
    return jax.jit(lambda st, e: run_chunk(engine, st, e)).lower(
        init_state(engine, 2), ev).as_text()

assert step_text(four) == step_text(one)
a, b = one.run(ev), four.run(ev)
for f in ("readout", "spike_counts", "input_counts"):
    assert (np.asarray(getattr(a, f)) == np.asarray(getattr(b, f))).all(), f
sess = four.open_stream(capacity=2, chunk_T=2)
slot = sess.open()
for t0 in (0, 2):
    up = sess.step({{slot: np.asarray(ev)[t0:t0 + 2, 0]}})[slot]
mc = estimate_multicore_cost(spec, four.schedule,
                             np.asarray(one.run(ev[:, :1]).input_counts))
want = np.array([pc.makespan_cycles for pc in mc.per_core]) \\
    + mc.routing_cycles
assert (np.asarray(up.per_core_cycles) == want).all()
assert up.cycles == int(want.max())
np.testing.assert_array_equal(up.readout, np.asarray(a.readout)[0])
print("ONE_WALK_OK")
"""
        import os

        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=4")
        env["JAX_PLATFORMS"] = "cpu"
        res = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=560)
        assert res.returncode == 0, res.stderr[-2000:]
        assert "ONE_WALK_OK" in res.stdout


# ---------------------------------------------------------------------------
# Per-core cost attribution.
# ---------------------------------------------------------------------------
class TestMulticoreCost:
    def test_cycle_sums_match_single_core(self, gesture_setup):
        """Acceptance: per-core cycle sums == single-core total within the
        modeled overheads (routing + split duplication + ceil rounding)."""
        spec, eng, schedule, meng = gesture_setup
        ev = _events(spec)
        counts = np.asarray(run_engine(meng, ev).input_counts)
        mc = estimate_multicore_cost(spec, schedule, counts)
        # Exact accounting identity of the model:
        assert (int(mc.compute_cycles.sum())
                == mc.single_core_compute_cycles + mc.duplication_cycles)
        # No split layers in this plan: duplication is ceil rounding only,
        # bounded by T * sum of active macros per layer.
        T = counts.shape[0]
        slack = T * sum(
            l.plan.mapping.pipelines * l.plan.mapping.macros_per_pipeline
            for l in schedule.layers)
        assert 0 <= mc.duplication_cycles <= slack
        # Routing overhead is the only other modeled gap vs single core.
        assert (mc.compute_cycles.sum()
                <= mc.single_core_compute_cycles + slack
                + mc.routing_cycles.sum())

    def test_single_core_model_agrees_with_estimate_cost(self, gesture_setup):
        """The multicore model's single-core baseline is a total-busy sum
        over all 9 macros of the same row-op rule estimate_cost feeds its
        pipeline sim, so it must fit inside 9x the simulated makespan (no
        macro can be busier than the wall clock)."""
        spec, eng, schedule, _ = gesture_setup
        ev = _events(spec)
        counts = np.asarray(run_engine(eng, ev).input_counts)
        mc = estimate_multicore_cost(spec, schedule, counts)
        sc = estimate_cost(spec, QuantSpec(4), counts)
        assert 0 < mc.single_core_compute_cycles <= 9 * sc.makespan_cycles

    def test_idle_chunk_imbalance_invariant(self, gesture_setup):
        """A zero-spike chunk (quiet DVS window) is perfectly balanced:
        load_imbalance keeps its >= 1.0 invariant instead of reporting 0."""
        spec, _, schedule, _ = gesture_setup
        counts = np.zeros((3, len(schedule.layers)))
        mc = estimate_multicore_cost(spec, schedule, counts)
        assert mc.load_imbalance == 1.0
        assert mc.routing_cycles.sum() == 0

    def test_route_fractions_single_source(self, gesture_setup):
        """route_factor is derived from the per-core fractions the cost
        model consumes — one routing model, two views."""
        _, _, schedule, _ = gesture_setup
        for ls in schedule.layers:
            assert ls.route_factor == pytest.approx(sum(ls.route_fractions))
            for c, f in enumerate(ls.route_fractions):
                if f > 0:
                    assert c in ls.consumer_cores

    def test_imbalance_and_energy(self, gesture_setup):
        spec, _, schedule, meng = gesture_setup
        ev = _events(spec)
        counts = np.asarray(run_engine(meng, ev).input_counts)
        mc = estimate_multicore_cost(spec, schedule, counts)
        assert mc.load_imbalance >= 1.0
        assert mc.energy_uj > mc.routing_energy_uj >= 0.0
        assert len(mc.per_core) == 4
        assert sum(pc.energy_uj for pc in mc.per_core) == pytest.approx(
            mc.energy_uj - mc.routing_energy_uj)

    def test_chunked_pricing_invariant(self, gesture_setup):
        """Per-core clocks resume across chunks: pricing chunk by chunk
        equals pricing the whole stream (any chunking)."""
        spec, _, schedule, meng = gesture_setup
        ev = _events(spec)
        counts = np.asarray(run_engine(meng, ev).input_counts)
        whole = estimate_multicore_cost(spec, schedule, counts)
        states, routing = None, np.zeros(4, np.int64)
        for t0 in range(0, counts.shape[0], 2):
            mc = estimate_multicore_cost(spec, schedule,
                                         counts[t0:t0 + 2],
                                         pipeline_states=states)
            states = mc.pipeline_states
            routing += mc.routing_cycles
        final = np.array([pc.makespan_cycles for pc in mc.per_core])
        whole_final = np.array([pc.makespan_cycles for pc in whole.per_core])
        np.testing.assert_array_equal(final, whole_final)
        np.testing.assert_array_equal(routing, whole.routing_cycles)


# ---------------------------------------------------------------------------
# Streaming on a compiled plan.
# ---------------------------------------------------------------------------
class TestMulticoreStreaming:
    def test_sessions_bit_exact_and_attributed(self, gesture_setup):
        spec, eng, schedule, meng = gesture_setup
        ev = _events(spec)
        evn = np.asarray(ev)
        whole = run_engine(eng, ev)

        mgr = StreamSessionManager(meng, capacity=2, chunk_T=3)
        s0, s1 = mgr.open(), mgr.open()
        for t0 in range(0, spec.timesteps, 3):
            ups = mgr.step({s0: evn[t0:t0 + 3, 0], s1: evn[t0:t0 + 3, 1]})
        np.testing.assert_array_equal(
            ups[s0].readout, np.asarray(whole.readout)[0])
        np.testing.assert_array_equal(
            ups[s1].readout, np.asarray(whole.readout)[1])
        # Per-core attribution present and consistent with whole-stream
        # pricing of this slot's own spikes.
        st = init_state(meng, 1)
        _, out = run_chunk(meng, st, ev[:, 0:1])
        mc = estimate_multicore_cost(
            spec, schedule, np.asarray(out.slot_input_counts)[:, :, 0])
        expect = (np.array([pc.makespan_cycles for pc in mc.per_core])
                  + mc.routing_cycles)
        np.testing.assert_array_equal(ups[s0].per_core_cycles, expect)
        assert ups[s0].cycles == int(expect.max())
        assert ups[s0].load_imbalance >= 1.0

    def test_slot_reuse(self, gesture_setup):
        spec, eng, _, meng = gesture_setup
        ev = _events(spec, batch=1, seed=7)
        evn = np.asarray(ev)
        whole = run_engine(eng, ev)
        mgr = StreamSessionManager(meng, capacity=2, chunk_T=2)
        slot = mgr.open()
        for t0 in range(0, spec.timesteps, 2):
            ups = mgr.step({slot: evn[t0:t0 + 2, 0]})
        mgr.close(slot)
        slot2 = mgr.open()
        for t0 in range(0, spec.timesteps, 2):
            ups = mgr.step({slot2: evn[t0:t0 + 2, 0]})
        np.testing.assert_array_equal(ups[slot2].readout,
                                      np.asarray(whole.readout)[0])
