"""LIF-FireNet: spiking convolutions with recurrent layers and a stateless
1x1 head.

Hagenaars, Paredes-Vallés and de Croon, NeurIPS 2021 (arXiv:2106.01862;
code github.com/tudelft/event_flow, ``LIFFireNet``, which sets
``residual = False``).  The configuration's ``layers`` are walked in
order, each fed by the spikes of the one before it.  Every layer keeps the
input's H x W (stride 1, "same" padding):

* ``conv``: a spiking convolution, ``z = LIF(W * input)``.  With
  ``"recurrent": true`` its input is the channel concatenation of the plane
  it is fed and its own spikes of the previous timestep (zero at t = 0),
  so its weights are (kh, kw, c_in + c_out, c_out), fed channels first;
* ``head``: a convolution with no neuron and no state; its current,
  saturated to the Vmem width, is the readout at the clip's last
  timestep.

The neuron is ``bench.networks.chain.fire_fn``'s: the input current
saturated to the Vmem width, added to the leaked Vmem ``v - (v >> k)``,
saturated, fire at the integer threshold, hard reset to 0.  A layer's
input count is the number of spikes it convolves: a recurrent layer counts
its own previous spikes too.

Departures from event_flow, each stated in the configuration's
``assumed``: binary event frames in place of per-polarity counts; one
integer leak shift and one integer threshold per layer in place of the
trained per-channel leaks and thresholds; no ``tanh`` on the head (the
readout is its integer pre-activation); integer weights and a 7-bit Vmem
in place of float32.  Nothing of the program is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.networks import chain

def _channels_in(layer: dict) -> int:
    return layer["c_in"] + (layer["c_out"] if layer.get("recurrent") else 0)


def weight_layers(cfg: dict) -> list:
    """Every layer has weights: (kh, kw, c_in [+ c_out], c_out), sliding
    over the output positions."""
    out = []
    for layer in cfg["layers"]:
        shape = (layer["kh"], layer["kw"], _channels_in(layer),
                 layer["c_out"])
        out.append(dict(layer, fan_in=shape[0] * shape[1] * shape[2],
                        shape=shape, spatial=True))
    return out


def layer_work(cfg: dict) -> list:
    """(P, F, K) per layer: every layer keeps the input's H x W."""
    h, w = cfg["input_hw"]
    return [(h * w, layer["fan_in"], layer["c_out"])
            for layer in weight_layers(cfg)]


def extra_state_bytes(cfg: dict) -> int:
    """Each recurrent layer's previous spike plane, one byte a position."""
    h, w = cfg["input_hw"]
    return sum(h * w * layer["c_out"] for layer in cfg["layers"]
               if layer.get("recurrent"))


def check_program(spec, cfg: dict) -> list:
    """The program's layers against the file's, layer by layer."""
    mismatch = []
    if spec.in_channels != cfg["in_channels"] or spec.readout != "vmem" \
            or len(spec.layers) != len(cfg["layers"]):
        mismatch.append("input channels, readout or depth")
    n = cfg["neuron"]
    for i, (sl, fl) in enumerate(zip(spec.layers, cfg["layers"])):
        if sl.kind != "conv":
            mismatch.append(f"layer {i}: program {sl.kind}, file conv")
            continue
        got = {"kind": "head" if sl.stateless else "conv", "c_in": sl.c_in,
               "c_out": sl.c_out, "kh": sl.conv.kh, "kw": sl.conv.kw,
               "stride": sl.conv.stride, "padding": sl.conv.padding,
               "recurrent": sl.recurrent}
        want = {k: fl.get(k) for k in got}
        want["recurrent"] = bool(fl.get("recurrent", False))
        if got != want:
            mismatch.append(f"layer {i}: program {got}, file {want}")
        neuron = sl.conv.neuron
        if not sl.stateless and (
                neuron.model, neuron.reset, neuron.threshold,
                neuron.leak_shift) != (n["model"], n["reset"],
                                       n["threshold"], n["leak_shift"]):
            mismatch.append(f"layer {i} neuron")
    return mismatch


def program_params(cfg: dict, weights: list) -> list:
    """One weight per spec layer: every layer has weights."""
    return list(weights)


def _state_shapes(cfg: dict, batch: int) -> list:
    """Each spiking layer's Vmem, then each recurrent layer's previous
    spikes."""
    h, w = cfg["input_hw"]
    layers = cfg["layers"]
    return ([(batch, h, w, layer["c_out"]) for layer in layers
             if layer["kind"] == "conv"]
            + [(batch, h, w, layer["c_out"]) for layer in layers
               if layer.get("recurrent")])


def _timestep_fn(cfg: dict, vmem_bits: int):
    """One timestep through every layer for a batch of clips."""
    fire = chain.fire_fn(cfg, vmem_bits)
    v_min, v_max = -(1 << (vmem_bits - 1)), (1 << (vmem_bits - 1)) - 1
    layers = cfg["layers"]
    n_spiking = sum(layer["kind"] == "conv" for layer in layers)

    def step(weights, state, x):
        vmem = iter(state[:n_spiking])
        prev = iter(state[n_spiking:])
        new_vmem, new_prev, counts = [], [], []
        act = x
        for layer, wt in zip(layers, weights):
            if layer.get("recurrent"):
                act = jnp.concatenate(
                    [act, next(prev).astype(jnp.float32)], axis=-1)
            counts.append(jnp.sum(act, axis=(1, 2, 3)).astype(jnp.int32))
            acc = chain.conv(act, wt, layer)
            if layer["kind"] == "head":
                v = jnp.clip(acc.astype(jnp.int32), v_min, v_max)
                s = jnp.zeros_like(v)
                continue
            v, s = fire(acc, next(vmem), layer["thr_int"])
            new_vmem.append(v)
            if layer.get("recurrent"):
                new_prev.append(s)
            act = s.astype(jnp.float32)
        return new_vmem + new_prev, s, v, jnp.stack(counts, axis=1)

    return jax.jit(step)


def reference_run(cfg: dict, weights: list, clips, vmem_bits=None,
                  budget_bytes: float = 1.5e9):
    """Serve ``clips`` (N, T, H, W, C) whole through the plain reference:
    ``(readouts, input_counts)``, the head's saturated current at each
    clip's last timestep (N, H, W, K) and the per-timestep per-layer input
    counts (N, T, L)."""
    vmem_bits = cfg["vmem_bits"] if vmem_bits is None else vmem_bits
    return chain.run_blocks(
        cfg, _timestep_fn(cfg, vmem_bits),
        chain.shaped_weights(weight_layers(cfg), weights), clips,
        lambda b: _state_shapes(cfg, b), budget_bytes)
