"""Bit-exact export: fold QAT-trained float params into the integer engine.

SpiDR's training story (Table III, "Modified Training: No") is that networks
are trained offline with standard surrogate-gradient BPTT + QAT and then
deployed *unchanged* on the digital CIM datapath.  This module is that
train->deploy seam:

  * ``export_network``  — fold the trainer's float weights into the engine's
    signed-integer format: per-output-channel power-of-two scales
    (``core.quant.po2_quantize``, the exact quantizer the QAT forward uses),
    int8 weight matrices, and per-channel integer thresholds requantized
    onto each layer's Vmem grid (``B_vmem = 2*B_w - 1`` saturation contract).
  * ``deploy``          — build an executable :class:`SNNEngine` from the
    exported integers, optionally compiled across ``n_cores`` SpiDR cores
    through ``compiler.compile_network``.
  * ``save_exported`` / ``load_exported`` — persist the integer artifact via
    ``checkpoint.Checkpointer`` (atomic, validated on reload).
  * ``verify_roundtrip`` — the proof obligation: run the *training graph*
    (``run_snn(mode="qat")``, post-STE) and the deployed integer engine on
    the same event streams and require identical spike trains and readouts.

Why this is exact rather than approximate: the QAT forward fake-quantizes
with power-of-two per-channel scales, so every float intermediate is
``scale * <integer>`` with the integer far below 2**24 — representable
exactly in float32.  Saturation bounds, the digital leak shift and the
requantized threshold all commute with that scaling, so the float training
graph *is* the integer datapath, viewed through a power-of-two lens.  The
exported integers are produced by the same ``po2_quantize`` call the
training forward used: nothing is re-derived at deploy time.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..checkpoint.checkpoint import Checkpointer
from ..compiler import compile_network
from ..core.network import SNNSpec, run_snn
from ..core.quant import (
    PRECISION_PAIRS,
    QuantSpec,
    po2_quantize,
    requantize_threshold,
)
from ..engine.inference import (
    EngineConfig,
    EngineLayer,
    SNNEngine,
    compile_engine,
    run_engine,
)

__all__ = [
    "ExportedLayer",
    "ExportedNetwork",
    "RoundTrip",
    "deploy",
    "dequantize_readout",
    "export_network",
    "load_exported",
    "read_export_meta",
    "save_exported",
    "verify_roundtrip",
]


@dataclasses.dataclass(frozen=True)
class ExportedLayer:
    """One weight layer in deployable integer form."""

    w_q: np.ndarray      # (F, K) int8 signed weights
    scale: np.ndarray    # (K,) float32 power-of-two per-channel scales
    thr_int: np.ndarray  # (K,) int32 thresholds on the layer's Vmem grid


@dataclasses.dataclass(frozen=True)
class ExportedNetwork:
    """A trained network folded into SpiDR's integer weight format.

    ``layers`` is aligned with ``spec.layers`` / the trainer's params list:
    an :class:`ExportedLayer` per weight layer, ``None`` per pool layer.
    """

    name: str
    weight_bits: int
    layers: tuple

    @property
    def qspec(self) -> QuantSpec:
        return QuantSpec(self.weight_bits)


def export_network(params, spec: SNNSpec, qspec: QuantSpec) -> ExportedNetwork:
    """Fold trained float params into the engine's signed-integer format.

    Per weight layer: symmetric per-output-channel power-of-two quantization
    of the weights (the same ``po2_quantize`` the QAT forward ran, so the
    integers are identical to what training saw through the STE), and the
    float firing threshold requantized onto the layer's integer Vmem grid.
    Chain networks only: a spec with a recurrent or stateless
    layer raises ``ValueError`` naming the layer.
    """
    spec.require_chain("snn.export")
    layers = []
    for layer, p in zip(spec.layers, params):
        if layer.kind not in ("conv", "fc"):
            layers.append(None)
            continue
        neuron = layer.conv.neuron if layer.kind == "conv" else layer.fc.neuron
        q, scale = po2_quantize(jnp.asarray(p), qspec, axis=0)
        scale_k = scale[0]  # (1, K) -> (K,)
        thr_int, _ = requantize_threshold(neuron.threshold, scale_k, qspec)
        layers.append(ExportedLayer(
            w_q=np.asarray(q),
            scale=np.asarray(scale_k, np.float32),
            thr_int=np.asarray(thr_int, np.int32),
        ))
    return ExportedNetwork(name=spec.name, weight_bits=qspec.weight_bits,
                           layers=tuple(layers))


def deploy(
    exported: ExportedNetwork,
    spec: SNNSpec,
    cfg: Optional[EngineConfig] = None,
    n_cores: int = 1,
) -> SNNEngine:
    """Build an executable integer engine from an exported network.

    ``n_cores > 1`` compiles the network across a SpiDR core grid
    (``compiler.compile_network`` -> ``engine.compile_engine``), whose
    plan prices per-core cycles and energy; outputs are the one-core
    engine's.  ``cfg``
    defaults to the pure-jnp backend at the exported precision.
    """
    spec.require_chain("snn.export.deploy")
    cfg = cfg or EngineConfig(exported.qspec, backend="jnp")
    if cfg.qspec.weight_bits != exported.weight_bits:
        raise ValueError(
            f"engine executes {cfg.qspec} but the checkpoint was exported "
            f"at {exported.weight_bits}-bit weights; re-export or change "
            "the EngineConfig")
    layers = []
    for layer, ex in zip(spec.layers, exported.layers):
        if layer.kind == "conv":
            layers.append(EngineLayer(
                kind="conv", neuron=layer.conv.neuron,
                w_q=jnp.asarray(ex.w_q), w_scale=ex.scale,
                thr_int=jnp.asarray(ex.thr_int),
                kh=layer.conv.kh, kw=layer.conv.kw,
                stride=layer.conv.stride, padding=layer.conv.padding,
            ))
        elif layer.kind == "fc":
            layers.append(EngineLayer(
                kind="fc", neuron=layer.fc.neuron,
                w_q=jnp.asarray(ex.w_q), w_scale=ex.scale,
                thr_int=jnp.asarray(ex.thr_int),
            ))
        elif layer.kind == "pool":
            layers.append(EngineLayer(kind="pool"))
        elif layer.kind == "adaptive_pool":
            layers.append(EngineLayer(kind="adaptive_pool",
                                      target_hw=layer.target_hw))
        else:  # pragma: no cover - spec validated upstream
            raise ValueError(layer.kind)
    engine = SNNEngine(spec=spec, cfg=cfg, layers=tuple(layers))
    if n_cores > 1:
        schedule = compile_network(spec, n_cores=n_cores, qspec=cfg.qspec)
        engine = compile_engine(engine, schedule)
    return engine


def dequantize_readout(exported: ExportedNetwork, spec: SNNSpec, readout):
    """Map an integer engine readout back onto the training graph's scale.

    ``"rate"`` readouts are plain spike counts (scale-free); ``"vmem"``
    readouts are integers on the last weight layer's grid and dequantize by
    its per-channel power-of-two scale — exactly, so the result equals the
    QAT graph's float readout bit for bit.
    """
    if spec.readout == "rate":
        return jnp.asarray(readout, jnp.float32)
    last = next(ex for ex in reversed(exported.layers) if ex is not None)
    return jnp.asarray(readout, jnp.float32) * jnp.asarray(last.scale)


# ---------------------------------------------------------------------------
# Persistence: one Checkpointer step per exported artifact.
# ---------------------------------------------------------------------------
_EXPORT_META_KEY = "exported_snn"


def _as_tree(exported: ExportedNetwork):
    return [
        None if ex is None
        else {"w_q": ex.w_q, "scale": ex.scale, "thr_int": ex.thr_int}
        for ex in exported.layers
    ]


def read_export_meta(ckpt: Checkpointer, step: int) -> dict:
    """The ``exported_snn`` metadata of one checkpoint step ({} if absent).

    The single parser for the export artifact's metadata — the facade's
    ``spidr.load`` and :func:`load_exported` both read through it, so the
    key and layout cannot drift between them.
    """
    import json
    import os

    path = os.path.join(ckpt.directory, f"step_{step:09d}", "meta.json")
    with open(path) as f:
        meta = json.load(f)
    return meta.get(_EXPORT_META_KEY) or {}


def save_exported(ckpt: Checkpointer, step: int, exported: ExportedNetwork,
                  spec: Optional[SNNSpec] = None) -> None:
    """Persist an exported network (atomic, one ``step_*`` directory).

    Pass the ``spec`` the network was trained/exported at to record its
    event geometry (``input_hw``/``timesteps``) in the metadata —
    ``spidr.load`` then rebuilds the deployment at that geometry instead
    of the paper network's full-size default when no spec is given.
    """
    info = {
        "name": exported.name,
        "weight_bits": exported.weight_bits,
    }
    if spec is not None:
        info["input_hw"] = list(spec.input_hw)
        info["timesteps"] = int(spec.timesteps)
    ckpt.save(step, _as_tree(exported), extra_meta={_EXPORT_META_KEY: info})


def load_exported(ckpt: Checkpointer, spec: SNNSpec,
                  step: Optional[int] = None) -> ExportedNetwork:
    """Reload an exported network, validating the artifact.

    Raises ``ValueError`` on a checkpoint that was not written by
    ``save_exported``, lacks the export metadata fields, or does not match
    ``spec``'s layer structure; missing leaf files surface as
    ``FileNotFoundError`` from the checkpointer.
    """
    spec.require_chain("snn.export.load_exported")
    if step is None:
        step = ckpt.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint steps under {ckpt.directory}")
    info = read_export_meta(ckpt, step)
    if not info:
        raise ValueError(
            f"checkpoint step {step} in {ckpt.directory} carries no "
            f"'{_EXPORT_META_KEY}' metadata — not an exported network "
            "(was it written by save_exported?)")
    for field in ("name", "weight_bits"):
        if field not in info:
            raise ValueError(
                f"exported checkpoint step {step} is corrupted: metadata "
                f"field '{field}' is missing")
    if info["weight_bits"] not in {w for w, _ in PRECISION_PAIRS}:
        raise ValueError(
            f"exported checkpoint step {step} is corrupted: weight_bits="
            f"{info['weight_bits']!r} is not a supported precision")

    # Template with the layer shapes ``spec`` dictates; restore() re-checks
    # the leaf count so a structure mismatch fails loudly instead of
    # deploying weights into the wrong layer.
    like = []
    for layer in spec.layers:
        if layer.kind == "conv":
            f, k = layer.conv.kh * layer.conv.kw * layer.c_in, layer.c_out
        elif layer.kind == "fc":
            f, k = layer.c_in, layer.c_out
        else:
            like.append(None)
            continue
        like.append({
            "w_q": np.zeros((f, k), np.int8),
            "scale": np.zeros((k,), np.float32),
            "thr_int": np.zeros((k,), np.int32),
        })
    try:
        tree = ckpt.restore(step, like)
    except AssertionError as e:
        raise ValueError(
            f"exported checkpoint step {step} does not match the "
            f"'{spec.name}' layer structure: {e}") from e
    layers = []
    for idx, (template, d) in enumerate(zip(like, tree)):
        if d is None:
            layers.append(None)
            continue
        # restore() only checks the leaf count; validate shapes/dtypes
        # against the spec-derived template so a truncated or regenerated
        # leaf fails here instead of deploying corrupted weights.
        for field, want in template.items():
            got = np.asarray(d[field])
            if got.shape != want.shape or got.dtype != want.dtype:
                raise ValueError(
                    f"exported checkpoint step {step} is corrupted: layer "
                    f"{idx} field '{field}' is {got.dtype}{got.shape}, "
                    f"expected {want.dtype}{want.shape} for '{spec.name}'")
        layers.append(ExportedLayer(
            w_q=np.asarray(d["w_q"], np.int8),
            scale=np.asarray(d["scale"], np.float32),
            thr_int=np.asarray(d["thr_int"], np.int32),
        ))
    return ExportedNetwork(name=info["name"], weight_bits=info["weight_bits"],
                           layers=tuple(layers))


# ---------------------------------------------------------------------------
# The round-trip proof.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RoundTrip:
    """Result of comparing the QAT training graph with the deployed engine."""

    exact: bool
    readout_mismatch: float      # max |qat - dequantized engine readout|
    spike_mismatch: int          # max |per-timestep per-layer spike counts|

    def __bool__(self) -> bool:  # pragma: no cover - convenience
        return self.exact


def verify_roundtrip(
    params,
    spec: SNNSpec,
    engine: SNNEngine,
    events,
    exported: Optional[ExportedNetwork] = None,
    engine_out=None,
) -> RoundTrip:
    """Prove train->deploy bit-exactness on ``events``.

    Runs the post-STE training graph (``run_snn(mode="qat")`` on the float
    ``params``) and the deployed integer ``engine`` on the same
    ``(T, B, H, W, C)`` event streams, and compares the full per-timestep
    per-layer output spike counts plus the readout (engine readout
    dequantized through the exported scales first).  Exact means equal —
    not close.  ``engine_out`` accepts a precomputed
    ``run_engine(engine, events)`` result so callers that already ran the
    engine don't pay for the inference twice.
    """
    exported = exported or export_network(params, spec, engine.cfg.qspec)
    qat_out, qat_counts = run_snn(params, events, spec, engine.cfg.qspec,
                                  mode="qat", record_spikes=True)
    eng = engine_out if engine_out is not None else run_engine(engine, events)
    eng_out = dequantize_readout(exported, spec, eng.readout)
    readout_mismatch = float(
        np.max(np.abs(np.asarray(qat_out) - np.asarray(eng_out))))
    spike_mismatch = int(np.max(np.abs(
        np.asarray(qat_counts).astype(np.int64)
        - np.asarray(eng.spike_counts).astype(np.int64))))
    return RoundTrip(
        exact=(readout_mismatch == 0.0 and spike_mismatch == 0),
        readout_mismatch=readout_mismatch,
        spike_mismatch=spike_mismatch,
    )
