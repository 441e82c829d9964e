"""Spiking networks as layer lists (``SNNSpec``), and the paper's two.

  Optical flow estimation : input 288x384x2, 10 timesteps,
      Conv(2,32) + 6x Conv(32,32) + Conv(32,2)      (3x3, stride 1, pad 1)
  Gesture recognition     : input 64x64x2, 20 timesteps,
      Conv(2,16) + 4x Conv(16,16) + FC(64,11),
      2x2 stride-2 maxpool after every two intermediate conv layers,
      adaptive 2x2 pool before the FC so N_in = 16ch * 2 * 2 = 64.

Kernel sizes are not given in the paper; 3x3/stride-1/pad-1 is assumed
(standard for both reference tasks) — recorded in DESIGN.md §7.

A spec is a list of layers, each fed by the plane the one before it passes
on.  Two conv-layer flags reach beyond a chain (LIF-FireNet,
``configs/lif_firenet.py``, uses both):

  * ``recurrent``: the layer convolves the channel concatenation of its
    input and its own spikes of the previous timestep (zero at t = 0);
    its weights are ``(kh, kw, c_in + c_out, c_out)``, input channels first;
  * ``stateless`` (the last layer only): no neuron and no Vmem; its
    saturated integer current is what a ``"vmem"`` readout reads (a
    regression head).

The paper's chain networks run in float-QAT training mode and in the
bit-exact integer engine; a spec with any of the flags runs in the integer
engine only (training and export refuse it).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .layers import (
    SpikingConvParams,
    SpikingDenseParams,
    init_conv,
    init_dense,
    maxpool2d,
    spiking_conv,
    spiking_dense,
)
from .modes import LayerShape
from .neuron import NeuronConfig
from .quant import QuantSpec

__all__ = ["SNNSpec", "gesture_net", "optical_flow_net", "init_params", "run_snn"]


@dataclasses.dataclass(frozen=True)
class SNNLayer:
    kind: str          # "conv" | "fc" | "pool" | "adaptive_pool"
    c_in: int = 0
    c_out: int = 0
    conv: SpikingConvParams | None = None
    fc: SpikingDenseParams | None = None
    target_hw: int = 0  # adaptive pool target
    # Beyond a chain (conv layers only; see the module docstring).
    recurrent: bool = False       # input joined to own previous spikes
    stateless: bool = False       # no neuron: the readout is its current

    @property
    def fan_in(self) -> int:
        """Weight rows: kh*kw*(c_in [+ c_out if recurrent]) or N_in."""
        if self.kind != "conv":
            return self.c_in
        c = self.c_in + (self.c_out if self.recurrent else 0)
        return self.conv.kh * self.conv.kw * c

    @property
    def extension(self) -> str | None:
        """What makes this layer more than a chain layer, or None."""
        parts = (["recurrent"] if self.recurrent else []) + (
            ["stateless"] if self.stateless else [])
        return ", ".join(parts) or None


@dataclasses.dataclass(frozen=True)
class SNNSpec:
    name: str
    input_hw: tuple
    in_channels: int
    timesteps: int
    layers: tuple
    readout: str  # "rate" (classification) or "vmem" (regression/flow)

    def __post_init__(self):
        planes = self.planes()
        for i, l in enumerate(self.layers):
            if l.extension is None:
                continue
            if l.kind != "conv":
                raise ValueError(f"layer {i} ({l.kind}): only conv layers "
                                 f"can be {l.extension}")
            if l.stateless and i != len(self.layers) - 1:
                raise ValueError(f"layer {i}: only the last layer can be "
                                 "stateless")
            h, w = planes[i - 1][:2] if i else self.input_hw
            if l.recurrent and planes[i][:2] != (h, w):
                raise ValueError(
                    f"layer {i}: a recurrent conv must keep its input's "
                    f"{h}x{w} plane to join its own spikes, not "
                    f"{planes[i][0]}x{planes[i][1]}")

    def planes(self) -> list:
        """The shape of the plane each layer passes on: (H, W, C) after a
        conv or pool, (N,) after an fc."""
        h, w = self.input_hw
        c = self.in_channels
        out = []
        for l in self.layers:
            if l.kind == "conv":
                p = l.conv
                h = (h + 2 * p.padding - p.kh) // p.stride + 1
                w = (w + 2 * p.padding - p.kw) // p.stride + 1
                c = l.c_out
            elif l.kind == "pool":
                h, w = h // 2, w // 2
            elif l.kind == "adaptive_pool":
                h = w = l.target_hw
            if l.kind == "fc":
                out.append((l.c_out,))
            else:
                out.append((h, w, c))
        return out

    def require_chain(self, what: str) -> None:
        """Raise a clear error naming the first layer that is not a chain
        layer: ``what`` (training, export, ...) runs chains only."""
        for i, l in enumerate(self.layers):
            if l.extension is not None:
                raise ValueError(
                    f"{what} supports chain networks only; {self.name} "
                    f"layer {i} is {l.extension}")

    def readout_shape(self, batch: int) -> tuple:
        """The readout: summed last-layer spikes ``(B, classes)``
        ("rate"), or the last weight layer's output plane ("vmem": its
        Vmem, or a stateless head's current)."""
        last = next(i for i in reversed(range(len(self.layers)))
                    if self.layers[i].kind in ("conv", "fc"))
        if self.readout == "rate":
            return (batch, self.layers[last].c_out)
        return (batch,) + self.planes()[last]

    def layer_shapes(self) -> list:
        """Accelerator-view shapes per weight layer (for modes/energy)."""
        out = []
        for l, plane in zip(self.layers, self.planes()):
            if l.kind == "conv":
                out.append(LayerShape("conv", l.fan_in, l.c_out,
                                      plane[0] * plane[1]))
            elif l.kind == "fc":
                out.append(LayerShape.fc(l.c_in, l.c_out))
        return out


def _conv(c_in, c_out, neuron=None):
    return SNNLayer(
        "conv",
        c_in,
        c_out,
        conv=SpikingConvParams(3, 3, 1, 1, neuron or NeuronConfig()),
    )


def gesture_net(neuron: NeuronConfig | None = None) -> SNNSpec:
    # Threshold/width tuned for event-camera input sparsity: low threshold +
    # wide triangle surrogate keeps early layers alive and gradients flowing.
    n = neuron or NeuronConfig(
        model="lif", reset="hard", threshold=0.5, leak=0.95, surrogate_width=2.0
    )
    return SNNSpec(
        name="gesture",
        input_hw=(64, 64),
        in_channels=2,
        timesteps=20,
        layers=(
            _conv(2, 16, n),
            _conv(16, 16, n),
            _conv(16, 16, n),
            SNNLayer("pool"),
            _conv(16, 16, n),
            _conv(16, 16, n),
            SNNLayer("pool"),
            SNNLayer("adaptive_pool", target_hw=2),
            SNNLayer("fc", 64, 11, fc=SpikingDenseParams(n)),
        ),
        readout="rate",
    )


def optical_flow_net(neuron: NeuronConfig | None = None) -> SNNSpec:
    n = neuron or NeuronConfig(
        model="if", reset="soft", threshold=0.5, surrogate_width=2.0
    )
    layers = [_conv(2, 32, n)]
    layers += [_conv(32, 32, n) for _ in range(6)]
    layers += [_conv(32, 2, n)]
    return SNNSpec(
        name="optical_flow",
        input_hw=(288, 384),
        in_channels=2,
        timesteps=10,
        layers=tuple(layers),
        readout="vmem",
    )


def init_params(key: jax.Array, spec: SNNSpec) -> list:
    params = []
    for l in spec.layers:
        if l.kind == "conv":
            key, k = jax.random.split(key)
            params.append(init_conv(k, l.conv.kh, l.conv.kw,
                                    l.fan_in // (l.conv.kh * l.conv.kw),
                                    l.c_out))
        elif l.kind == "fc":
            key, k = jax.random.split(key)
            params.append(init_dense(k, l.c_in, l.c_out))
        else:
            params.append(None)
    return params


def _init_state(spec: SNNSpec, batch: int):
    """Vmem carries for every stateful layer (None for pools and
    stateless layers)."""
    return [jnp.zeros((batch,) + plane)
            if l.kind in ("conv", "fc") and not l.stateless else None
            for l, plane in zip(spec.layers, spec.planes())]


def _init_spikes(spec: SNNSpec, batch: int) -> tuple:
    """Each recurrent layer's previous spike plane, int8 zeros, in layer
    order (empty for a chain)."""
    return tuple(jnp.zeros((batch,) + plane, jnp.int8)
                 for l, plane in zip(spec.layers, spec.planes())
                 if l.recurrent)


def _forward_t(
    params, state, x_t, spec: SNNSpec, qspec: QuantSpec, mode: str, record_spikes=False
):
    """One timestep through all layers. Returns (state', out, spike_counts)."""
    act = x_t
    new_state = []
    spike_counts = []
    out = None
    for i, l in enumerate(spec.layers):
        if l.kind == "conv":
            v, s = spiking_conv(act, params[i], state[i], l.conv, qspec, mode)
            new_state.append(v)
            if record_spikes:
                spike_counts.append(jnp.sum(s))
            act, out = s, (v, s)
        elif l.kind == "fc":
            flat = act.reshape(act.shape[0], -1)
            v, s = spiking_dense(flat, params[i], state[i], l.fc, qspec, mode)
            new_state.append(v)
            if record_spikes:
                spike_counts.append(jnp.sum(s))
            act, out = s, (v, s)
        elif l.kind == "pool":
            act = maxpool2d(act)
            new_state.append(None)
        elif l.kind == "adaptive_pool":
            hw = act.shape[1]
            k = hw // l.target_hw
            act = maxpool2d(act, window=k, stride=k)
            new_state.append(None)
    return new_state, out, spike_counts


def run_snn(
    params,
    inputs: jax.Array,  # (T, B, H, W, C) binary event frames
    spec: SNNSpec,
    qspec: QuantSpec,
    mode: str = "train",
    record_spikes: bool = False,
):
    """Run all timesteps via lax.scan.

    ``mode`` selects the execution contract per layer (see ``core.layers``):
    ``"train"`` (float QAT, per-tensor STE), ``"qat"`` (deploy-exact QAT —
    the forward spike train is bit-identical to the exported integer
    engine) or ``"int"`` (quantized integer datapath).

    Returns the readout:
      * "rate": (B, n_classes) summed output spikes (rate code)
      * "vmem": (B, H, W, 2) final-layer Vmem (flow regression)
    plus per-layer total spike counts if ``record_spikes`` (for the
    sparsity profile of Fig 5 and the energy model).
    """
    spec.require_chain("float / QAT simulation (run_snn)")
    batch = inputs.shape[1]
    state0 = _init_state(spec, batch)

    def step(carry, x_t):
        state, acc = carry
        state, (v, s), counts = _forward_t(
            params, state, x_t, spec, qspec, mode, record_spikes
        )
        acc = acc + s if spec.readout == "rate" else v
        counts = jnp.stack(counts) if record_spikes else jnp.zeros((1,))
        return (state, acc), counts

    n_out = spec.layers[-1].c_out
    if spec.readout == "rate":
        acc0 = jnp.zeros((batch, n_out))
    else:
        # Flow: Vmem of the last conv layer.
        h, w = spec.input_hw
        acc0 = jnp.zeros((batch, h, w, n_out))
    (state, acc), counts = jax.lax.scan(step, (state0, acc0), inputs)
    return acc, counts
