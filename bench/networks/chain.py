"""The chain: weight layers and pools, each fed by the one before it.

Layer kinds: ``conv`` (kh x kw, stride, padding), ``fc``, ``pool`` (2 x 2
max) and ``adaptive_pool`` (max to ``target_hw`` square).  Both paper
networks are chains.  A configuration without ``topology`` is one.

A network description module gives the harness every fact that depends
on how a network's layers connect; ``bench/named.py`` finds it by the
configuration's ``topology``.  It exports

* ``weight_layers(cfg)``: the weight layers in order, each a dict with
  ``fan_in``, ``c_out``, ``thr_int``, ``shape``, the shape its (fan_in,
  c_out) integer weights take in the reference, and ``spatial``, whether
  its weights slide over its output positions (a convolution), which the
  chip model maps 16 positions to a weight pass;
* ``layer_work(cfg)``: (P output positions, F fan-in, K channels) per
  weight layer: the yardstick's work and the chip model's geometry;
* ``reference_run(cfg, weights, clips, vmem_bits=None,
  budget_bytes=1.5e9)``: ``(readouts, input_counts)``, see
  ``bench.reference.reference_run``;
* ``check_program(spec, cfg)``: how the program's network differs from
  the file, one string per difference;
* ``program_params(cfg, weights)``: the per-spec-layer parameters the
  program is given, from the float weights of ``weight_layers``;
* optionally ``extra_state_bytes(cfg)``: the bytes one slot keeps besides
  each layer's Vmem (a recurrent layer's previous spikes), which
  ``bench.work`` counts read and written once per chunk; without it, 0.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
_WEIGHT_KINDS = ("conv", "fc")


def _walk(cfg: dict) -> list:
    """Each weight layer with the (h, w) of its output plane (1 x 1 for
    ``fc``), walking the configuration's shapes through the pools."""
    h, w = cfg["input_hw"]
    out = []
    for layer in cfg["layers"]:
        kind = layer["kind"]
        if kind == "conv":
            p, s = layer["padding"], layer["stride"]
            h = (h + 2 * p - layer["kh"]) // s + 1
            w = (w + 2 * p - layer["kw"]) // s + 1
            out.append((layer, h, w))
        elif kind == "fc":
            out.append((layer, 1, 1))
        elif kind == "pool":
            h, w = h // 2, w // 2
        elif kind == "adaptive_pool":
            h = w = layer["target_hw"]
    return out


def weight_layers(cfg: dict) -> list:
    """The ``conv`` and ``fc`` layers in order, with fan-in and shape."""
    out = []
    for layer, _, _ in _walk(cfg):
        spatial = layer["kind"] == "conv"
        if spatial:
            shape = (layer["kh"], layer["kw"], layer["c_in"], layer["c_out"])
        else:
            shape = (layer["c_in"], layer["c_out"])
        out.append(dict(layer, fan_in=math.prod(shape[:-1]), shape=shape,
                        spatial=spatial))
    return out


def layer_work(cfg: dict) -> list:
    """(P, F, K) per weight layer, walking the configuration's shapes."""
    return [(h * w, layer["fan_in"], layer["c_out"]) for layer, (_, h, w)
            in zip(weight_layers(cfg), _walk(cfg))]


def check_program(spec, cfg: dict) -> list:
    """The program's layers against the file's, layer by layer."""
    mismatch = []
    if spec.in_channels != cfg["in_channels"] or \
            spec.readout != cfg["readout"] or \
            len(spec.layers) != len(cfg["layers"]):
        mismatch.append("input channels, readout or depth")
    n = cfg["neuron"]
    for i, (sl, fl) in enumerate(zip(spec.layers, cfg["layers"])):
        got = {"kind": sl.kind}
        if sl.kind in _WEIGHT_KINDS:
            got.update(c_in=sl.c_in, c_out=sl.c_out)
            neuron = sl.conv.neuron if sl.kind == "conv" else sl.fc.neuron
            if (neuron.model, neuron.reset, neuron.threshold,
                    neuron.leak_shift) != (n["model"], n["reset"],
                                           n["threshold"], n["leak_shift"]):
                mismatch.append(f"layer {i} neuron")
        if sl.kind == "conv":
            got.update(kh=sl.conv.kh, kw=sl.conv.kw, stride=sl.conv.stride,
                       padding=sl.conv.padding)
        if sl.kind == "adaptive_pool":
            got.update(target_hw=sl.target_hw)
        want = {k: v for k, v in fl.items() if k != "thr_int"}
        if got != want:
            mismatch.append(f"layer {i}: program {got}, file {want}")
    return mismatch


def program_params(cfg: dict, weights: list) -> list:
    """One weight per spec layer, None for pools."""
    it = iter(weights)
    return [next(it) if layer["kind"] in _WEIGHT_KINDS else None
            for layer in cfg["layers"]]


def _state_shapes(cfg: dict, batch: int) -> list:
    return [(batch, h, w, layer["c_out"]) if layer["kind"] == "conv"
            else (batch, layer["c_out"]) for layer, h, w in _walk(cfg)]


def _max_pool(x, k: int):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, k, k, 1), (1, k, k, 1), "VALID")


def fire_fn(cfg: dict, vmem_bits: int):
    """``fire(acc, v, thr) -> (v, s)``: the neuron of the paper's datapath.

    The input current is saturated to the Vmem width, added to the
    (leaked) Vmem, saturated again; the neuron fires at the integer
    threshold and resets to 0 (hard) or by the threshold (soft)."""
    v_min, v_max = -(1 << (vmem_bits - 1)), (1 << (vmem_bits - 1)) - 1
    neuron = cfg["neuron"]
    leak = neuron["leak_shift"] if neuron["model"] == "lif" else 0
    hard = neuron["reset"] == "hard"

    def fire(acc, v, thr):
        partial = jnp.clip(acc.astype(jnp.int32), v_min, v_max)
        if leak > 0:
            v = v - (v >> leak)
        v = jnp.clip(v + partial, v_min, v_max)
        s = (v >= thr).astype(jnp.int32)
        v = v * (1 - s) if hard else jnp.clip(v - s * thr, v_min, v_max)
        return v, s

    return fire


def conv(act, w, layer: dict):
    """The integer convolution of a binary NHWC plane, exact in float32."""
    p, st = layer["padding"], layer["stride"]
    return jax.lax.conv_general_dilated(
        act, w, (st, st), ((p, p), (p, p)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HIGHEST)


def _timestep_fn(cfg: dict, vmem_bits: int):
    """One timestep through every layer for a batch of clips."""
    fire = fire_fn(cfg, vmem_bits)

    def step(weights, vmem, x):
        act, wi, new_vmem, counts = x, 0, [], []
        for layer in cfg["layers"]:
            kind = layer["kind"]
            if kind in _WEIGHT_KINDS:
                w, v = weights[wi], vmem[wi]
                if kind == "conv":
                    counts.append(jnp.sum(act != 0, axis=(1, 2, 3)))
                    acc = conv(act, w, layer)
                else:
                    act = act.reshape(act.shape[0], -1)
                    counts.append(jnp.sum(act != 0, axis=1))
                    acc = jnp.dot(act, w, precision=_HIGHEST)
                v, s = fire(acc, v, layer["thr_int"])
                new_vmem.append(v)
                act = s.astype(jnp.float32)
                wi += 1
            elif kind == "pool":
                act = _max_pool(act, 2)
            elif kind == "adaptive_pool":
                act = _max_pool(act, act.shape[1] // layer["target_hw"])
        return new_vmem, s, v, jnp.stack(counts, axis=1)

    return jax.jit(step)


def shaped_weights(layers: list, weights: list) -> list:
    """Each (F, K) integer weight as float32 in its layer's ``shape``."""
    return [jnp.asarray(np.asarray(w), jnp.float32).reshape(layer["shape"])
            for layer, w in zip(layers, weights)]


def run_blocks(cfg: dict, step, ws: list, clips: np.ndarray, state_shapes,
               budget_bytes: float):
    """Serve ``clips`` whole through ``step(ws, state, x) -> (state, s, v,
    counts)`` from a zero state of ``state_shapes(batch)``, in blocks of
    clips whose state and activations, about three int32 copies of the
    state per clip, fit in ``budget_bytes``.  The readout sums the last
    spikes ``s`` over time (``"rate"``) or is the last ``v``.  Returns
    ``(readouts, input_counts)``."""
    n, t_len = clips.shape[:2]
    per_clip = 3 * 4 * sum(int(np.prod(s)) for s in state_shapes(1))
    block = int(max(1, min(n, budget_bytes // per_clip)))
    readouts, counts = [], []
    for lo in range(0, n, block):
        x = clips[lo:lo + block]
        b = x.shape[0]
        vmem = [jnp.zeros(s, jnp.int32) for s in state_shapes(b)]
        acc = None
        per_t = []
        for t in range(t_len):
            vmem, s, v, c = step(ws, vmem, jnp.asarray(x[:, t], jnp.float32))
            if cfg["readout"] == "rate":
                acc = s if acc is None else acc + s
            else:
                acc = v
            per_t.append(np.asarray(c))
        readouts.append(np.asarray(acc, np.int32))
        counts.append(np.stack(per_t, axis=1))
    return np.concatenate(readouts), np.concatenate(counts).astype(np.int64)


def reference_run(cfg: dict, weights: list, clips: np.ndarray,
                  vmem_bits: int | None = None, budget_bytes: float = 1.5e9):
    """Serve ``clips`` (N, T, H, W, C) whole through the plain reference."""
    vmem_bits = cfg["vmem_bits"] if vmem_bits is None else vmem_bits
    return run_blocks(cfg, _timestep_fn(cfg, vmem_bits),
                      shaped_weights(weight_layers(cfg), weights), clips,
                      lambda b: _state_shapes(cfg, b), budget_bytes)
