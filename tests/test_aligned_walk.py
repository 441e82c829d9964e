"""The scan walk at a tile-aligned width.

Where the event width is not a multiple of 32, ``run_chunk`` carries every
plane padded with zero columns up to one and cuts it back at the chunk's
edge.  The real columns must be those of the unpadded walk bit for bit:
readouts, per-slot counts, Vmem and previous spikes, under any chunking.
Where the width is a multiple of 32 the chunk step must hold no padding.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, spidr
from repro.configs import lif_firenet, spidr_gesture, spidr_optflow
from repro.core.layers import SpikingConvParams, SpikingDenseParams
from repro.core.network import SNNLayer, SNNSpec, gesture_net, init_params
from repro.core.neuron import NeuronConfig
from repro.core.quant import QuantSpec
from repro.engine import inference
from repro.engine.inference import init_state, run_chunk, run_reference
from repro.kernels import autotune

_N = NeuronConfig(model="if", reset="soft", threshold=0.5)


def _conv(c_in, c_out, stride=1):
    return SNNLayer("conv", c_in, c_out,
                    conv=SpikingConvParams(3, 3, stride, 1, _N))


# A chain that pools adaptively, convolves the pooled plane and ends in an
# fc: carried 32 wide, the 10-wide pooled plane is 16 wide, where a
# 5-wide window would fit three times.
ADAPTIVE_FC = SNNSpec(
    name="adaptive_fc", input_hw=(20, 20), in_channels=2, timesteps=5,
    layers=(_conv(2, 4), SNNLayer("pool"),
            SNNLayer("adaptive_pool", target_hw=2), _conv(4, 4),
            SNNLayer("fc", 16, 3, fc=SpikingDenseParams(_N))),
    readout="rate")

# A chain whose readout is the Vmem of a strided conv over a pooled plane:
# the pooled plane is 11 wide, so the last real output of the stride-2
# conv reads the zero column past it.
STRIDED_VMEM = SNNSpec(
    name="strided_vmem", input_hw=(9, 23), in_channels=2, timesteps=5,
    layers=(_conv(2, 4), SNNLayer("pool"), _conv(4, 3, stride=2)),
    readout="vmem")

UNALIGNED = {
    "firenet_8x10": lif_firenet.reduced(hw=(8, 10), timesteps=5),
    "firenet_9x13": lif_firenet.reduced(hw=(9, 13), timesteps=5),
    "gesture_24x24": spidr_gesture.reduced(hw=(24, 24), timesteps=5),
    "adaptive_fc_20x20": ADAPTIVE_FC,
    "strided_vmem_9x23": STRIDED_VMEM,
}


def _engine(spec, backend):
    params = init_params(jax.random.PRNGKey(1), spec)
    return spidr.compile(spec, params,
                         spidr.DeployTarget(backend=backend)).engine


def _events(spec, batch=3, seed=0, density=0.3):
    rng = np.random.default_rng(seed)
    shape = (spec.timesteps, batch) + tuple(spec.input_hw) + (2,)
    return jnp.asarray((rng.random(shape) < density).astype(np.float32))


def _unpadded_walk(engine, events):
    """The per-timestep walk at the network's own width (the walk
    ``run_reference`` takes): final Vmem and previous spikes, the
    cumulative readout of every timestep, per-slot counts."""
    spec = engine.spec
    ref = dataclasses.replace(
        engine, cfg=dataclasses.replace(engine.cfg, backend="jnp"))
    fresh = init_state(engine, events.shape[1])
    vmem, spikes, acc = list(fresh.vmem), fresh.spikes, fresh.readout_acc
    readouts, outs, ins = [], [], []
    for x_t in events:
        vmem, spikes, (v, s), c_out, c_in = inference._forward_t(
            ref, vmem, x_t, spikes)
        acc = acc + s if spec.readout == "rate" else v
        readouts.append(acc)
        outs.append(c_out)
        ins.append(c_in)
    return vmem, spikes, jnp.stack(readouts), jnp.stack(outs), jnp.stack(ins)


def _chunked(engine, events, chunk_T):
    state = init_state(engine, events.shape[1])
    readouts, outs, ins = [], [], []
    for t0 in range(0, events.shape[0], chunk_T):
        state, out = run_chunk(engine, state, events[t0:t0 + chunk_T],
                               collect_readouts=True)
        readouts.append(out.readouts)
        outs.append(out.slot_spike_counts)
        ins.append(out.slot_input_counts)
    return state, (jnp.concatenate(readouts), jnp.concatenate(outs),
                   jnp.concatenate(ins))


def _equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("backend", ["jnp", "fused"])
@pytest.mark.parametrize("net", sorted(UNALIGNED))
def test_the_aligned_walk_is_the_unpadded_walk(net, backend):
    """Chunks of 2 timesteps (the last one short) against one call, the
    walk at the network's own width and ``run_reference``: every readout,
    per-slot count, Vmem and previous spike plane, bit for bit, in the
    shapes of ``init_state``."""
    spec = UNALIGNED[net]
    assert spec.input_hw[1] % 32
    engine = _engine(spec, backend)
    events = _events(spec)
    chunked = _chunked(engine, events, 2)
    _equal(chunked, _chunked(engine, events, spec.timesteps))
    state, (readouts, outs, ins) = chunked
    vmem, spikes, want_readouts, want_outs, want_ins = _unpadded_walk(
        engine, events)
    assert int(want_outs.sum()) > 0
    _equal((readouts, outs, ins), (want_readouts, want_outs, want_ins))
    _equal((state.vmem, state.spikes, state.readout_acc,
            state.out_counts, state.in_counts),
           (tuple(vmem), spikes, want_readouts[-1], want_outs.sum(0),
            want_ins.sum(0)))
    ref = run_reference(engine, events)
    _equal((state.readout_acc, outs.sum(2), ins.sum(2)),
           (ref.readout, ref.spike_counts, ref.input_counts))


def _chunk_step_text(engine):
    step = jax.jit(lambda st, ev: run_chunk(
        engine, st, ev, collect_counts=True, collect_readouts=True))
    return step.lower(init_state(engine, 2), jnp.zeros(
        (2, 2) + tuple(engine.spec.input_hw) + (2,))).as_text(
            debug_info=True)


@pytest.mark.parametrize("spec,aligned", [
    (spidr_gesture.reduced(hw=(32, 32), timesteps=2), True),
    (spidr_optflow.reduced(hw=(8, 64), timesteps=2), True),
    (lif_firenet.reduced(hw=(8, 10), timesteps=2), False),
], ids=["gesture", "optflow", "firenet"])
def test_only_an_unaligned_width_is_padded(spec, aligned):
    text = _chunk_step_text(_engine(spec, "jnp"))
    assert "spidr.L0.patches" in text
    assert ("spidr.align" not in text) == aligned


@pytest.mark.parametrize("spec,t_block,pad_w", [
    (gesture_net(), 1, 0),
    (spidr_optflow.CONFIG, 1, 0),
    (lif_firenet.CONFIG, 1, 6),
    (lif_firenet.TINY, 1, 22),
    (spidr_gesture.reduced(hw=(24, 24)), 1, 8),
    (spidr_gesture.reduced(hw=(24, 24)), 2, 0),   # the t_block walk
], ids=["gesture", "optflow", "firenet", "firenet_tiny", "chain_24",
        "chain_24_tblk"])
def test_the_build_span_reads_the_columns_added(spec, t_block, pad_w):
    builds = _build_pads(lambda: inference.build_engine(
        spec, init_params(jax.random.PRNGKey(0), spec),
        inference.EngineConfig(QuantSpec(4), t_block=t_block)))
    assert builds == [pad_w]


def _build_pads(build):
    """``pad_w`` of every ``engine.build`` span that ``build()`` records."""
    tracer = obs.Tracer()
    previous = obs.default_tracer()
    obs.set_default_tracer(tracer)
    try:
        build()
    finally:
        obs.set_default_tracer(previous)
    return [e["args"]["pad_w"] for e in tracer.events
            if e["name"] == "engine.build"]


@pytest.mark.parametrize("t_block,pad_w", [(1, 8), (2, 0)],
                         ids=["scan", "tblk"])
def test_the_autotuned_engine_decides_the_pad(monkeypatch, t_block, pad_w):
    """Autotune may hand a layer a ``t_block`` over 1, which sends every
    chunk to the unpadded tiled walk: the span reads the pad of the engine
    ``spidr.compile`` returns, and its chunk step pads just as the span
    reads."""
    monkeypatch.setattr(autotune, "autotune_layer", lambda *a, **k:
                        autotune.KernelConfig(t_block=t_block))
    spec = spidr_gesture.reduced(hw=(24, 24), timesteps=2)
    params = init_params(jax.random.PRNGKey(0), spec)
    compiled = []
    builds = _build_pads(lambda: compiled.append(spidr.compile(
        spec, params, spidr.DeployTarget(backend="fused", autotune=True))))
    assert builds == [pad_w]
    engine = compiled[0].engine
    assert {el.kcfg[3] for el in engine.layers
            if el.kind in ("conv", "fc")} == {t_block}
    assert ("spidr.align" in _chunk_step_text(engine)) == bool(pad_w)
