"""LIF-FireNet through the program: recurrent convs and a stateless head,
on the integer engine, the session manager and the fleet.

The network (``configs/lif_firenet.py``) at a CPU size: the eight layers
and their connections at 4 channels on 8 x 10 frames.  One clip is worked
by hand in numpy from the layer equations; everything else is held to an
uninterrupted run of the same stream, bit for bit.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spidr
from repro.analysis import certify_overflow
from repro.compiler import build_graph
from repro.configs import lif_firenet, spidr_gesture, spidr_optflow
from repro.core.layers import SpikingConvParams
from repro.core.network import init_params
from repro.core.quant import QuantSpec
from repro.engine.inference import init_state, reset_slot, run_chunk
from repro.serving import ServeConfig, StreamRequest, StreamWorker

HW, T = (8, 10), 6
SPEC = lif_firenet.reduced(hw=HW, timesteps=T)


def _compiled(backend="jnp", **target):
    params = init_params(jax.random.PRNGKey(3), SPEC)
    return spidr.compile(SPEC, params, spidr.DeployTarget(
        backend=backend, **target))


@functools.lru_cache(maxsize=None)
def _jnp():
    return _compiled()


def _clip(seed=0, t=T, density=0.3):
    rng = np.random.default_rng(seed)
    return (rng.random((t,) + HW + (2,)) < density).astype(np.float32)


# ---------------------------------------------------------------------------
# One clip by hand.
# ---------------------------------------------------------------------------
def _conv(x, w):
    """(H, W, C) plane * (kh, kw, C, K) integer weights, "same" padding,
    by loops over the taps."""
    kh, kw = w.shape[:2]
    ph, pw = kh // 2, kw // 2
    h, wd, _ = x.shape
    xp = np.pad(x, ((ph, ph), (pw, pw), (0, 0))).astype(np.int64)
    out = np.zeros((h, wd, w.shape[-1]), np.int64)
    for di in range(kh):
        for dj in range(kw):
            out += xp[di:di + h, dj:dj + wd] @ w[di, dj]
    return out


def _by_hand(engine, clip):
    """The clip through the layer table, from the engine's integers:
    (head current at the last timestep, per-timestep input counts and
    output counts)."""
    layers = engine.layers
    w = []
    for el, sl in zip(layers, SPEC.layers):
        c = sl.fan_in // (el.kh * el.kw)
        w.append(np.asarray(el.w_q, np.int64).reshape(el.kh, el.kw, c, -1))
    thr = [int(el.thr_int) for el in layers]

    def lif(acc, v, th):   # 7-bit Vmem, leak v - (v >> 1), hard reset
        v = v - (v >> 1)
        v = np.clip(v + np.clip(acc, -64, 63), -64, 63)
        s = (v >= th).astype(np.int64)
        return v * (1 - s), s

    k = SPEC.layers[0].c_out
    v = [np.zeros(HW + (k,), np.int64) for _ in range(7)]
    z1 = np.zeros(HW + (k,), np.int64)       # G1's z(t-1): zero at t = 0
    z4 = np.zeros(HW + (k,), np.int64)
    ins, outs = [], []
    for x in clip.astype(np.int64):
        v[0], z0 = lif(_conv(x, w[0]), v[0], thr[0])
        g1_in = np.concatenate([z0, z1], axis=-1)
        v[1], z1 = lif(_conv(g1_in, w[1]), v[1], thr[1])
        v[2], z2 = lif(_conv(z1, w[2]), v[2], thr[2])
        v[3], z3 = lif(_conv(z2, w[3]), v[3], thr[3])
        g2_in = np.concatenate([z3, z4], axis=-1)
        v[4], z4 = lif(_conv(g2_in, w[4]), v[4], thr[4])
        v[5], z5 = lif(_conv(z4, w[5]), v[5], thr[5])
        v[6], z6 = lif(_conv(z5, w[6]), v[6], thr[6])
        flow = np.clip(_conv(z6, w[7]), -64, 63)
        # Input counts: a recurrent layer counts its own previous spikes.
        ins.append([x.sum(), g1_in.sum(), z1.sum(), z2.sum(), g2_in.sum(),
                    z4.sum(), z5.sum(), z6.sum()])
        outs.append([z.sum() for z in (z0, z1, z2, z3, z4, z5, z6)] + [0])
    return flow, np.array(ins), np.array(outs)


@pytest.mark.parametrize("backend", ["jnp", "fused"])
def test_one_clip_matches_the_equations_worked_by_hand(backend):
    compiled = _jnp() if backend == "jnp" else _compiled("fused")
    clip = _clip(seed=11)
    flow, ins, outs = _by_hand(compiled.engine, clip)
    assert ins[0, 1] == outs[0, 0]        # t = 0: G1 joins zeros
    assert ins[1, 1] == outs[1, 0] + outs[0, 1]   # t = 1: z0 + z1(t-1)
    assert ins[1, 4] == outs[1, 3] + outs[0, 4]   # t = 1: z3 + z4(t-1)
    assert (outs[:, :7].sum(axis=0) > 0).all()    # every LIF layer fires
    out = compiled.run(jnp.asarray(clip[:, None]))
    np.testing.assert_array_equal(np.asarray(out.readout)[0], flow)
    np.testing.assert_array_equal(np.asarray(out.input_counts), ins)
    np.testing.assert_array_equal(np.asarray(out.spike_counts), outs)


# ---------------------------------------------------------------------------
# State: chunking, rewind, snapshot, migration, slot reset.
# ---------------------------------------------------------------------------
def _key(up):
    return (up.timesteps, np.asarray(up.readout).tolist(), up.spikes,
            up.cycles, up.energy_uj)


def _whole(compiled, clip):
    sess = compiled.open_stream(capacity=2, chunk_T=T)
    slot = sess.open()
    return _key(sess.step({slot: clip})[slot])


@pytest.mark.parametrize("chunk_T", [1, 2, 4])
def test_a_stream_split_at_every_chunk_boundary_is_one_run(chunk_T):
    """Readout, spikes and cycles do not depend on where a stream is cut
    into chunks (energy is priced chunk by chunk, at each chunk's
    sparsity, so it does)."""
    clip = _clip(seed=5)
    sess = _jnp().open_stream(capacity=3, chunk_T=chunk_T)
    other, slot = sess.open(), sess.open()   # a neighbour shares the batch
    for t0 in range(0, T, chunk_T):
        ups = sess.step({other: _clip(seed=6)[t0:t0 + chunk_T],
                         slot: clip[t0:t0 + chunk_T]})
    assert _key(ups[slot])[:-1] == _whole(_jnp(), clip)[:-1]


def test_a_failed_tick_rewinds_and_replays_bit_exactly():
    rng = np.random.default_rng(9)
    reqs = [(rid, (rng.random((t,) + HW + (2,)) < 0.3).astype(np.float32))
            for rid, t in enumerate((6, 4, 5))]

    def serve(worker):
        for rid, ev in reqs:
            worker.submit(StreamRequest(rid=rid, events=ev))
        while worker.step():
            pass
        return {r.rid: (np.asarray(r.readout).tolist(), r.cycles,
                        r.energy_uj) for r in worker.done}

    ref = serve(StreamWorker(_jnp(), 2, 2))
    worker = StreamWorker(_jnp(), 2, 2, fail_at_tick=2)
    assert serve(worker) == ref
    assert worker.restarts == 1


def test_a_snapshot_restores_the_previous_spikes(tmp_path):
    clip = _clip(seed=7)
    sess = _jnp().open_stream(capacity=2, chunk_T=2)
    slot = sess.open()
    sess.step({slot: clip[:2]})
    snap = sess.state_dict()
    assert [z.shape for z in snap["engine_state"]["spikes"]] == \
        [(2,) + HW + (4,)] * 2
    assert any(z.any() for z in snap["engine_state"]["spikes"])
    _jnp().snapshot(str(tmp_path), sessions=[sess])
    rest = [sess.step({slot: clip[t0:t0 + 2]})[slot] for t0 in (2, 4)]
    assert _key(rest[-1])[:-1] == _whole(_jnp(), clip)[:-1]

    twin = _jnp().open_stream(capacity=2, chunk_T=2)
    twin.load_state_dict(snap)
    again = [twin.step({slot: clip[t0:t0 + 2]})[slot] for t0 in (2, 4)]
    assert [_key(u) for u in again] == [_key(u) for u in rest]

    restored = spidr.restore(str(tmp_path), spec=SPEC)
    resumed = restored.sessions[0]
    again = [resumed.step({slot: clip[t0:t0 + 2]})[slot] for t0 in (2, 4)]
    assert [_key(u) for u in again] == [_key(u) for u in rest]


def test_a_snapshot_without_the_previous_spikes_is_refused():
    sess = _jnp().open_stream(capacity=2, chunk_T=2)
    snap = sess.state_dict()
    del snap["engine_state"]["spikes"]
    twin = _jnp().open_stream(capacity=2, chunk_T=2)
    with pytest.raises(ValueError, match="previous-spike planes"):
        twin.load_state_dict(snap)


def test_a_stream_migrated_mid_clip_is_one_run():
    clips = [_clip(seed=s) for s in (1, 2, 3)]
    ref_fleet = spidr.serve(_jnp(), ServeConfig(n_replicas=1, capacity=3,
                                              chunk_T=2))
    hs = [ref_fleet.submit(c, rid=i) for i, c in enumerate(clips)]
    ref_fleet.drain()
    ref = {h.rid: (np.asarray(h.readout).copy(), h.cycles, h.energy_uj)
           for h in hs}
    ref_fleet.shutdown()

    fleet = spidr.serve(_jnp(), ServeConfig(n_replicas=2, capacity=3,
                                          chunk_T=2))
    hs = [fleet.submit(c, rid=i) for i, c in enumerate(clips)]
    fleet.step()
    moved = next(h for h in hs if h.status == "running")
    fleet.migrate(moved.rid)
    assert moved.migrations == 1
    fleet.drain()
    for h in hs:
        r, cycles, energy = ref[h.rid]
        np.testing.assert_array_equal(np.asarray(h.readout), r)
        assert (h.cycles, h.energy_uj) == (cycles, energy)
    fleet.shutdown()


def test_reset_slot_clears_the_previous_spikes():
    engine = _jnp().engine
    state = init_state(engine, 2)
    events = jnp.asarray(np.stack([_clip(seed=4)[:2]] * 2, axis=1))
    state, _ = run_chunk(engine, state, events)
    assert all(np.asarray(z[0]).any() and np.asarray(z[1]).any()
               for z in state.spikes)
    cleared = reset_slot(state, 1)
    assert all(not np.asarray(z[1]).any() for z in cleared.spikes)
    for z, was in zip(cleared.spikes, state.spikes):
        np.testing.assert_array_equal(np.asarray(z[0]), np.asarray(was[0]))


def test_the_rewind_point_pins_the_previous_spikes():
    from repro import obs

    planes = 2 * 2 * HW[0] * HW[1] * 4    # two int8 planes of 2 slots
    vmem = 7 * 2 * HW[0] * HW[1] * 4 * 4
    readout = 2 * HW[0] * HW[1] * 2 * 4
    counts = 2 * 8 * 2 * 4
    tracer = obs.Tracer()
    previous = obs.default_tracer()
    obs.set_default_tracer(tracer)
    try:
        worker = StreamWorker(_jnp(), 2, 2)    # marks its first rewind point
    finally:
        obs.set_default_tracer(previous)
    assert worker.sessions.state_nbytes == vmem + readout + counts + planes
    marks = [e for e in tracer.events if e["name"] == "worker.mark"]
    assert [m["args"]["pinned"] for m in marks] == [
        vmem + readout + counts + planes]


@pytest.mark.parametrize("spec", [spidr_gesture.reduced(hw=(16, 16)),
                                  spidr_optflow.reduced()],
                         ids=["gesture", "optflow"])
def test_a_chain_keeps_no_previous_spikes(spec):
    compiled = spidr.compile(spec, init_params(jax.random.PRNGKey(0), spec),
                             spidr.DeployTarget(backend="jnp"))
    sess = compiled.open_stream(capacity=2, chunk_T=2)
    slot = sess.open()
    assert init_state(compiled.engine, 2).spikes == ()
    assert set(sess.state_dict()["engine_state"]) == {
        "vmem", "readout_acc", "out_counts", "in_counts"}
    assert "spikes" not in sess.export_slot(slot)


# ---------------------------------------------------------------------------
# What runs chains only refuses, naming the layer.
# ---------------------------------------------------------------------------
def test_the_t_block_walk_refuses_a_recurrent_layer():
    compiled = _compiled("fused", t_block=2)
    with pytest.raises(ValueError, match=r"t_block .* layer 1 is recurrent"):
        compiled.run(jnp.asarray(_clip()[:, None]))


def test_the_multi_core_path_refuses_a_recurrent_layer():
    with pytest.raises(ValueError, match=r"n_cores > 1.* layer 1 is "
                                         r"recurrent"):
        _compiled(n_cores=4)


def test_training_and_export_refuse_a_non_chain_network():
    from repro.snn.export import export_network
    from repro.snn.train import TrainConfig, fit

    params = init_params(jax.random.PRNGKey(0), SPEC)
    with pytest.raises(ValueError, match="snn.export .* layer 1 is "
                                         "recurrent"):
        export_network(params, SPEC, QuantSpec(4))
    with pytest.raises(ValueError, match="snn.train .* layer 1 is "
                                         "recurrent"):
        fit(SPEC, TrainConfig(steps=1))


@pytest.mark.parametrize("layer,change,error", [
    (3, dict(recurrent=True, conv=SpikingConvParams(3, 3, 2, 1)),
     "layer 3: a recurrent conv must keep its input's 8x10 plane"),
    (3, dict(stateless=True), "layer 3: only the last layer"),
])
def test_a_spec_that_cannot_run_is_refused(layer, change, error):
    layers = list(SPEC.layers)
    layers[layer] = dataclasses.replace(layers[layer], **change)
    with pytest.raises(ValueError, match=error):
        dataclasses.replace(SPEC, layers=tuple(layers))


# ---------------------------------------------------------------------------
# Compiler graph, range certificate and chip-model pricing.
# ---------------------------------------------------------------------------
def test_the_graph_counts_the_recurrent_plane():
    """A chain of edges; a recurrent layer's volume adds its own plane."""
    g = build_graph(SPEC)
    p = HW[0] * HW[1] * 4
    assert [n.inputs for n in g.nodes] == [
        (), (0,), (1,), (2,), (3,), (4,), (5,), (6,)]
    assert [n.in_positions for n in g.nodes] == [
        HW[0] * HW[1] * 2, 2 * p, p, p, 2 * p, p, p, p]


def test_the_certificate_prices_the_recurrent_fan_in():
    cert = certify_overflow(lif_firenet.CONFIG, QuantSpec(4)) \
        .certificates["overflow"]
    g1 = cert["layers"][1]
    assert g1["fan_in"] == 576
    assert (g1["acc_lo"], g1["acc_hi"]) == (576 * -8, 576 * 7)
    assert cert["ok"]
    # On a 13-bit accumulator (4,095) only the recurrent layers can wrap:
    # 576 x 8 = 4,608, where a fan-in of 288 reaches 2,304.
    narrow = certify_overflow(lif_firenet.CONFIG, QuantSpec(4),
                              acc_bits=13).certificates["overflow"]
    assert [la["gemm_ok"] for la in narrow["layers"]] == [
        True, False, True, True, False, True, True, True]
    assert narrow["layers"][1]["min_violating_active_inputs"] == \
        4095 // 8 + 1


def test_layer_shapes_price_the_recurrent_fan_in():
    shapes = lif_firenet.CONFIG.layer_shapes()
    assert [s.fan_in for s in shapes] == [18, 576, 288, 288, 576, 288, 288,
                                          32]
    assert sum(s.fan_in * s.out_channels for s in shapes) == 74_368
