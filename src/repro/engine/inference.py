"""Batched, multi-timestep SNN inference engine (fused timestep loop).

This is the path from a DVS event tensor to output spike counts that the
chip actually takes: every timestep, every layer, weight->Vmem accumulation
fused with the neuron update, state carried across timesteps.  The seed repo
modeled one macro drain / one GEMM at a time; the engine closes the loop:

  events (T, B, H, W, C) --scan over T--> per-timestep layer sweep:
      conv : im2col (input loader, C5) -> (B*P, F) spike matrix
             fused_lif_gemm_int         -> Vmem' and output spikes
      fc   : flatten -> fused_lif_gemm_int
      pool : maxpool on the spike plane (binary in, binary out)
  readout: summed output spikes ("rate") or final-layer Vmem ("vmem")

Beyond a chain (``core.network``'s layer flags): a recurrent conv joins
its own previous spikes (carried in ``EngineState.spikes``) to its input
before im2col, so one GEMM of fan-in ``kh*kw*(c_in + c_out)`` takes both;
a stateless head runs the same update from a zero Vmem with a threshold
it cannot reach, so its output is its saturated current and it carries
no state.

Execution modes:
  * backend="fused" — the Pallas ``fused_lif_gemm_int`` kernel with
    tile-level zero-skipping (``interpret=True`` on CPU).
  * backend="jnp"   — pure-jnp composition of ``saturate`` +
    ``neuron_step_int``; the bit-exact oracle the fused path must match.

Chunked API (streaming): the engine's neuron state is first-class.
``init_state(engine, batch)`` returns an :class:`EngineState` (per-layer
integer Vmem carries, the readout accumulator, cumulative per-sample spike
statistics) and ``run_chunk(engine, state, events_chunk)`` advances it by
any number of timesteps, returning the new state plus a
:class:`ChunkOutput`.  Chunking is *exact*: for any partition of a stream
into chunks (including one timestep at a time) the final state and readout
are bit-identical to a single whole-stream call — the chip analogue is Vmem
staying resident in the CIM macro while events handshake in asynchronously.
``run_engine`` itself is just ``init_state`` + one ``run_chunk``.

Multi-core plans: ``compile_engine(engine, schedule)`` attaches a
``repro.compiler`` :class:`CoreSchedule` to the engine.  The plan is the
modelled chip's: ``engine.cost.estimate_multicore_cost`` prices its
per-core cycles, energy and routing from the schedule and the per-layer
spike counts.  Execution does not split: the integer GEMM and the neuron
update are column-independent, so an ``n_cores`` plan's spikes, Vmem and
counts are the one-core walk's, and the engine runs that walk.

Batch handling: the batch dimension is *folded into the GEMM rows*
(B output positions x P patches share one weight-stationary pass —
the TPU analogue of the macro's Vmem-pair weight reuse), or vmapped
per-sample with ``batch_mode="vmap"``.  Both produce identical spikes;
tests assert it.  Sharding the folded batch over a mesh data axis is a
``jax.device_put`` on ``events`` before calling — the engine is pure.

Everything is integer once weights are quantized: per-layer ``QuantSpec``
precision (W_b-bit weights, (2W-1)-bit Vmem), integer thresholds derived
from the float threshold and the layer's quantization scale.
``build_engine`` quantizes with per-tensor scales (scalar thresholds);
trained networks arrive through ``snn.export.deploy`` with per-channel
power-of-two scales and per-channel integer threshold vectors — both
execute on the same layer update, and the exported form is bit-identical
to the QAT training graph (``run_snn(mode="qat")``).

Memory: all readout/count accumulators are threaded through the scan
*carry* (O(1) in T), never recomputed from stacked per-timestep outputs —
a requirement for long-running streams (see the T=512 smoke test).  The
optional per-timestep count stacks in :class:`ChunkOutput` are O(chunk_T),
and can be disabled entirely with ``collect_counts=False``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..compiler.schedule import CoreSchedule
from ..core.layers import im2col, maxpool2d
from ..core.network import SNNSpec, _init_spikes, _init_state
from ..core.neuron import NeuronConfig, neuron_step_int
from ..core.quant import QuantSpec, quantize, saturate
from ..kernels.fused_lif_gemm import DEFAULT_BLOCK, fused_lif_gemm_int
from ..obs import trace as obs_trace

__all__ = [
    "ChunkOutput",
    "EngineConfig",
    "EngineOutput",
    "EngineState",
    "SNNEngine",
    "build_engine",
    "compile_engine",
    "init_state",
    "reset_slot",
    "run_chunk",
    "run_engine",
    "run_reference",
]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """How to execute the fused timestep loop."""

    qspec: QuantSpec
    backend: str = "fused"        # "fused" (Pallas) | "jnp" (oracle)
    interpret: bool = False       # Pallas interpret mode (CPU)
    skip_empty: bool = True       # tile-level zero-skipping
    block: tuple = DEFAULT_BLOCK

    def __post_init__(self):
        assert self.backend in ("fused", "jnp"), self.backend


@dataclasses.dataclass(frozen=True)
class EngineLayer:
    """One weight layer compiled for the integer datapath."""

    kind: str                     # "conv" | "fc" | "pool" | "adaptive_pool"
    neuron: Optional[NeuronConfig] = None
    w_q: Optional[jax.Array] = None       # int8 quantized weights
    w_scale: Optional[object] = None      # scale (w ~= w_q * scale): float
                                          # (per-tensor) or (K,) array
                                          # (per-channel exported networks)
    thr_int: object = 0                   # integer threshold at this scale:
                                          # int, or (K,) int32 per-channel
    kh: int = 0
    kw: int = 0
    stride: int = 1
    padding: int = 0
    target_hw: int = 0                    # adaptive pool target


@dataclasses.dataclass(frozen=True)
class SNNEngine:
    spec: SNNSpec
    cfg: EngineConfig
    layers: tuple  # of EngineLayer
    # The modelled chip's multi-core plan (None = single-core), attached
    # by ``compile_engine`` for pricing; it does not change execution.
    schedule: Optional[CoreSchedule] = None


@dataclasses.dataclass
class EngineOutput:
    readout: jax.Array       # (B, classes) int32 rate counts or (B,H,W,C) Vmem
    spike_counts: jax.Array  # (T, n_weight_layers) output spikes per layer
    input_counts: jax.Array  # (T, n_weight_layers) input spikes per layer


@dataclasses.dataclass
class EngineState:
    """Persistent neuron state between chunks of one event stream batch.

    The streaming analogue of the chip keeping Vmem resident in the CIM
    macro across timesteps: everything a stream needs to resume exactly
    where it left off, and nothing that grows with the stream length.

    ``vmem``        per-layer int32 Vmem carries (None for pool layers),
                    batch-leading shapes ``(B, H, W, C)`` / ``(B, N)``.
    ``readout_acc`` cumulative readout: summed output spikes ("rate") or
                    the last weight layer's Vmem ("vmem").
    ``out_counts``  ``(n_weight_layers, B)`` cumulative output spikes.
    ``in_counts``   ``(n_weight_layers, B)`` cumulative input spikes.
    ``spikes``      each recurrent layer's int8 spike plane of the last
                    timestep, ``(B, H, W, C)``, in layer order: the input
                    it joins to its next timestep's (empty for a chain).

    All accumulators are int32, like the rest of the integer datapath: a
    persistent "rate" stream wraps once any output unit or counter passes
    2^31 cumulative spikes.  At DVS-like rates that is hours of continuous
    streaming on one session — rotate (close/reopen) streams well before
    then; Vmem itself saturates at (2W−1) bits and never wraps.
    """

    vmem: tuple
    readout_acc: jax.Array
    out_counts: jax.Array
    in_counts: jax.Array
    spikes: tuple = ()


@dataclasses.dataclass
class ChunkOutput:
    """What one ``run_chunk`` call reports (alongside the new state).

    ``readout`` is the *cumulative* readout after the chunk (identical to
    ``state.readout_acc``).  The count fields are per-timestep stacks for
    this chunk only — ``(chunk_T, L)`` batch-summed and ``(chunk_T, L, B)``
    per-sample — or None under ``collect_counts=False``.  ``readouts`` is
    the per-timestep cumulative readout ``(chunk_T, B, ...)``, populated
    only under ``collect_readouts=True`` (the session manager uses it to
    read out a stream that ends mid-chunk).
    """

    readout: jax.Array
    spike_counts: Optional[jax.Array] = None
    input_counts: Optional[jax.Array] = None
    slot_spike_counts: Optional[jax.Array] = None
    slot_input_counts: Optional[jax.Array] = None
    readouts: Optional[jax.Array] = None


def build_engine(spec: SNNSpec, params, cfg: EngineConfig) -> SNNEngine:
    """Quantize float params into the integer engine (per-tensor scales).

    The ``engine.build`` span's ``pad_w`` is the number of zero columns
    the engine's chunk walk adds to every plane (:func:`_walk_pad`)."""
    with obs_trace.default_tracer().span(
            "engine.build", cat="compile", network=spec.name,
            backend=cfg.backend) as args:
        engine = _build_engine(spec, params, cfg)
        args["pad_w"] = _walk_pad(spec)
    return engine


def _build_engine(spec: SNNSpec, params, cfg: EngineConfig) -> SNNEngine:
    layers = []
    for layer, p in zip(spec.layers, params):
        if layer.kind == "conv":
            w_q, scale = quantize(p, cfg.qspec)
            scale_f = float(scale)
            layers.append(EngineLayer(
                kind="conv",
                neuron=layer.conv.neuron,
                w_q=w_q,
                w_scale=scale_f,
                thr_int=int(round(layer.conv.neuron.threshold / scale_f)),
                kh=layer.conv.kh, kw=layer.conv.kw,
                stride=layer.conv.stride, padding=layer.conv.padding,
            ))
        elif layer.kind == "fc":
            w_q, scale = quantize(p, cfg.qspec)
            scale_f = float(scale)
            layers.append(EngineLayer(
                kind="fc",
                neuron=layer.fc.neuron,
                w_q=w_q,
                w_scale=scale_f,
                thr_int=int(round(layer.fc.neuron.threshold / scale_f)),
            ))
        elif layer.kind == "pool":
            layers.append(EngineLayer(kind="pool"))
        elif layer.kind == "adaptive_pool":
            layers.append(EngineLayer(kind="adaptive_pool",
                                      target_hw=layer.target_hw))
        else:  # pragma: no cover - spec is validated upstream
            raise ValueError(layer.kind)
    return SNNEngine(spec=spec, cfg=cfg, layers=tuple(layers))


# ---------------------------------------------------------------------------
# One fused layer-timestep.
# ---------------------------------------------------------------------------
def _fused_update(el: EngineLayer, s2: jax.Array, v2: jax.Array,
                  cfg: EngineConfig, thr=None):
    """(rows, F) spikes x (F, K) weights + (rows, K) Vmem -> (v', s).

    ``thr`` overrides the layer's integer threshold (a stateless head runs
    at one it cannot reach).
    """
    n = el.neuron
    thr = el.thr_int if thr is None else thr
    if cfg.backend == "fused":
        return fused_lif_gemm_int(
            s2, el.w_q, v2,
            threshold=thr,
            leak_shift=n.leak_shift if n.model == "lif" else 0,
            soft_reset=(n.reset == "soft"),
            vmem_bits=cfg.qspec.vmem_bits,
            block=cfg.block,
            interpret=cfg.interpret,
            skip_empty=cfg.skip_empty,
        )
    acc = jnp.dot(
        s2.astype(jnp.int32), el.w_q.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )
    partial = saturate(acc, cfg.qspec)
    # leak_shift=0 means "no leak" (the kernels' convention); neuron_step_int
    # would compute v - (v >> 0) = 0, so route that case through IF dynamics.
    if n.model == "lif" and n.leak_shift == 0:
        n = dataclasses.replace(n, model="if")
    return neuron_step_int(v2, partial, n, cfg.qspec, thr)


def compile_engine(engine: SNNEngine, schedule: CoreSchedule) -> SNNEngine:
    """Attach a compiler :class:`CoreSchedule` to an executable engine.

    The schedule is the modelled chip's plan: per-core cycles, energy and
    routing come from ``estimate_multicore_cost`` over it.  The engine's
    walk is unchanged — integer GEMM and neuron update are column-
    independent, so the plan's outputs are the one-core outputs — and so
    is everything ``run_chunk`` and the session manager compute.
    """
    with obs_trace.default_tracer().span("engine.compile_schedule",
                                         cat="compile",
                                         network=engine.spec.name,
                                         n_cores=schedule.n_cores):
        assert engine.schedule is None, "engine already carries a schedule"
        engine.spec.require_chain("the multi-core path (n_cores > 1)")
        for ls in schedule.layers:
            if ls.plan.spec != engine.cfg.qspec:
                raise ValueError(
                    f"schedule selected {ls.plan.spec} for layer {ls.node} "
                    f"but the engine executes {engine.cfg.qspec}; precision-"
                    "exploring schedules (allowed_specs) are for cost "
                    "analysis, not execution")
        return dataclasses.replace(engine, schedule=schedule)


def _layer_scopes(engine: SNNEngine) -> list:
    """The ``jax.named_scope`` of each engine layer, as device traces name
    it: ``spidr.L{i}`` for the i-th weight layer (its ops under
    ``.patches``, ``.kernel`` and ``.counts``) and ``spidr.pool{j}`` for
    the j-th pool; the readout accumulates under ``spidr.readout``."""
    names, weight, pool = [], 0, 0
    for el in engine.layers:
        if el.kind in ("conv", "fc"):
            names.append(f"spidr.L{weight}")
            weight += 1
        else:
            names.append(f"spidr.pool{pool}")
            pool += 1
    return names


# ---------------------------------------------------------------------------
# Tile-aligned planes.  XLA copies a (B, H, W, C) plane reshaped to the
# kernel's (B*H*W, C) rows, and back, through relayout loops when W is not
# a multiple of the int8 sublane tile; so the scan walk carries every plane
# at the input width rounded up to one (346 -> 352 for a DAVIS346 sensor)
# and cuts it back at the chunk's edge.  The added columns' Vmem and spikes
# may hold anything: no real column reads them.
# ---------------------------------------------------------------------------
_ALIGN_W = 32


def _walk_pad(spec: SNNSpec) -> int:
    """Zero columns ``run_chunk`` adds to every plane: those that bring
    the input width to a multiple of ``_ALIGN_W``."""
    return -spec.input_hw[1] % _ALIGN_W


def _to_width(x: jax.Array, width: Optional[int], axis: int) -> jax.Array:
    """``x`` cut, or zero-padded, to ``width`` along ``axis`` (``x`` itself
    where it has that width, or ``width`` is None)."""
    if width is None or x.shape[axis] == width:
        return x
    if x.shape[axis] > width:
        return jax.lax.slice_in_dim(x, 0, width, axis=axis)
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, width - x.shape[axis])
    return jnp.pad(x, pad)


def _planes_at(spec: SNNSpec, hw: tuple) -> list:
    """``spec.planes()`` of the network walked from an ``hw`` input."""
    if tuple(hw) == tuple(spec.input_hw):
        return spec.planes()
    return dataclasses.replace(spec, input_hw=tuple(hw)).planes()


def _plane_width(plane: tuple) -> Optional[int]:
    """W of an (H, W, C) plane; None for an fc's (N,)."""
    return plane[1] if len(plane) == 3 else None


def _fit_state(spec: SNNSpec, state: EngineState, planes: list):
    """``state`` with every plane cut or zero-padded to ``planes``' widths:
    Vmem, previous spikes and a plane-shaped readout."""
    last = max(i for i, l in enumerate(spec.layers)
               if l.kind in ("conv", "fc"))
    fit = [_plane_width(p) for p in planes]
    return EngineState(
        vmem=tuple(None if v is None else _to_width(v, w, 2)
                   for v, w in zip(state.vmem, fit)),
        readout_acc=(_to_width(state.readout_acc, fit[last], 2)
                     if spec.readout == "vmem" else state.readout_acc),
        out_counts=state.out_counts,
        in_counts=state.in_counts,
        spikes=tuple(_to_width(z, w, 2) for z, w in zip(
            state.spikes, [w for l, w in zip(spec.layers, fit)
                           if l.recurrent])),
    )


def _forward_t(engine: SNNEngine, state, x_t, spikes=()):
    """One timestep through every layer.

    ``spikes`` are the recurrent layers' previous spike planes
    (``EngineState.spikes``).  Returns ``(state', spikes', out,
    counts_out, counts_in)`` with *per-sample* counts of shape
    ``(n_weight_layers, B)`` — the batch axis is kept so a streaming
    session can attribute spikes (and therefore chip cost) to the
    individual stream occupying each batch slot.

    ``x_t`` may be wider than the network's input (``run_chunk`` pads it
    to a tile-aligned width): every plane is then carried at the width of
    the walk from ``x_t``, and a layer reads only its input's real columns
    (``spec.planes()``), so the real columns are those of the unpadded
    walk, bit for bit, and counts count them alone.
    """
    spec = engine.spec
    shapes = _planes_at(spec, x_t.shape[1:3])
    real = [spec.input_hw[1]] + [_plane_width(p) for p in spec.planes()]
    act = x_t  # float {0,1} spike plane (im2col needs float)
    prev = iter(spikes)
    new_state, new_spikes, counts_out, counts_in, out = [], [], [], [], None
    for i, (el, layer, v, scope) in enumerate(zip(
            engine.layers, spec.layers, state, _layer_scopes(engine))):
        if el.kind == "conv":
            b, wide = act.shape[0], act.shape[2]
            if layer.recurrent:
                with jax.named_scope(f"{scope}.recur"):
                    act = jnp.concatenate(
                        [act, next(prev).astype(jnp.float32)], axis=-1)
            with jax.named_scope(f"{scope}.counts"):
                counts_in.append(jnp.sum(_to_width(act, real[i], 2) != 0,
                                         axis=(1, 2, 3)))
            with jax.named_scope(f"{scope}.patches"):
                # The real columns, zero beyond them as in the unpadded
                # plane, in one pad with im2col's own.
                p = el.padding
                x = jnp.pad(_to_width(act, real[i], 2),
                            ((0, 0), (p, p), (p, p + wide - real[i]),
                             (0, 0)))
                cols = im2col(x, el.kh, el.kw, el.stride, 0)
                rows, f = b * cols.shape[1], cols.shape[2]   # (B*P, F)
                k = el.w_q.shape[1]
                s2 = cols.reshape(rows, f).astype(jnp.int8)
            plane = (b,) + shapes[i]
            with jax.named_scope(f"{scope}.kernel"):
                if layer.stateless:
                    v_next, s = _fused_update(
                        el, s2, jnp.zeros((rows, k), jnp.int32), engine.cfg,
                        thr=engine.cfg.qspec.v_max + 1)
                else:
                    v_next, s = _fused_update(el, s2, v.reshape(rows, k),
                                              engine.cfg)
                v_next = v_next.reshape(plane)
                s = s.reshape(plane)
            new_state.append(None if layer.stateless else v_next)
            with jax.named_scope(f"{scope}.counts"):
                counts_out.append(jnp.sum(_to_width(s, real[i + 1], 2),
                                          axis=(1, 2, 3)))
            if layer.recurrent:
                new_spikes.append(s.astype(jnp.int8))
            act, out = s.astype(jnp.float32), (v_next, s)
        elif el.kind == "fc":
            with jax.named_scope(f"{scope}.patches"):
                flat = _to_width(act, real[i], 2).reshape(act.shape[0], -1)
                s2 = flat.astype(jnp.int8)
            with jax.named_scope(f"{scope}.counts"):
                counts_in.append(jnp.sum(flat != 0, axis=1))
            with jax.named_scope(f"{scope}.kernel"):
                v_next, s = _fused_update(el, s2, v, engine.cfg)
            new_state.append(v_next)
            with jax.named_scope(f"{scope}.counts"):
                counts_out.append(jnp.sum(s, axis=1))
            act, out = s.astype(jnp.float32), (v_next, s)
        elif el.kind == "pool":
            # A real output column reads two real input columns only.
            with jax.named_scope(scope):
                act = maxpool2d(act)
            new_state.append(None)
        elif el.kind == "adaptive_pool":
            hw = act.shape[1]
            kk = hw // el.target_hw
            with jax.named_scope(scope):
                act = maxpool2d(_to_width(act, real[i], 2),
                                window=kk, stride=kk)
            new_state.append(None)
    return (new_state, tuple(new_spikes), out, jnp.stack(counts_out),
            jnp.stack(counts_in))


def _init_vmem(engine: SNNEngine, batch: int):
    """Integer Vmem carries (network's float shape walk, cast to int32)."""
    return [
        None if s is None else s.astype(jnp.int32)
        for s in _init_state(engine.spec, batch)
    ]


def _n_weight_layers(engine: SNNEngine) -> int:
    return sum(1 for el in engine.layers if el.kind in ("conv", "fc"))


def init_state(engine: SNNEngine, batch: int) -> EngineState:
    """Fresh (all-zero) persistent state for ``batch`` concurrent streams."""
    spec = engine.spec
    n_l = _n_weight_layers(engine)
    return EngineState(
        vmem=tuple(_init_vmem(engine, batch)),
        # The last weight layer's plane (any pooling/striding along the
        # way) for a "vmem" readout, its classes for "rate".
        readout_acc=jnp.zeros(spec.readout_shape(batch), jnp.int32),
        out_counts=jnp.zeros((n_l, batch), jnp.int32),
        in_counts=jnp.zeros((n_l, batch), jnp.int32),
        spikes=_init_spikes(spec, batch),
    )


def reset_slot(state: EngineState, slot) -> EngineState:
    """Zero one batch slot's state, leaving every other slot untouched.

    This is slot retirement for continuous batching: the retired stream's
    Vmem, previous spikes, readout and counters are cleared so the next
    stream admitted into the slot starts from ``init_state`` conditions,
    and so the slot's all-zero spike planes feed the zero-skip path until
    then.  ``slot`` may
    be a traced int32 — the update is a pure scatter, safe under ``jit``.
    """
    return EngineState(
        vmem=tuple(None if v is None else v.at[slot].set(0)
                   for v in state.vmem),
        readout_acc=state.readout_acc.at[slot].set(0),
        out_counts=state.out_counts.at[:, slot].set(0),
        in_counts=state.in_counts.at[:, slot].set(0),
        spikes=tuple(z.at[slot].set(0) for z in state.spikes),
    )


def run_chunk(
    engine: SNNEngine,
    state: EngineState,
    events: jax.Array,           # (chunk_T, B, H, W, C) binary
    collect_counts: bool = True,
    collect_readouts: bool = False,
) -> tuple:
    """Advance ``state`` by one chunk of timesteps; returns ``(state', out)``.

    Bit-exact under any chunking: ``run_chunk`` over consecutive chunks of
    a stream produces the same final state/readout as one call over the
    concatenated stream.  All accumulators live in the scan *carry* — O(1)
    memory in the total stream length; the optional per-timestep stacks in
    the returned :class:`ChunkOutput` are O(chunk_T) and can be switched
    off for long whole-stream runs (``collect_counts=False``).

    Where the event width is not a multiple of ``_ALIGN_W`` the scan walks
    planes padded with zero columns up to one (``spidr.align`` pads the
    events and the state on entry and cuts the state and readouts back on
    exit), so ``state'`` keeps the shapes of ``init_state``.
    """
    assert events.ndim == 5, "expected (chunk_T, B, H, W, C)"
    spec = engine.spec
    pad = _walk_pad(spec)
    if pad:
        with jax.named_scope("spidr.align"):
            events = _to_width(events, events.shape[3] + pad, 3)
            state = _fit_state(spec, state,
                               _planes_at(spec, events.shape[2:4]))

    def step(carry, x_t):
        vmem, spikes, acc, oc, ic = carry
        vmem, spikes, (v, s), c_out, c_in = _forward_t(
            engine, list(vmem), x_t, spikes)
        with jax.named_scope("spidr.readout"):
            acc = acc + s if spec.readout == "rate" else v
        carry = (tuple(vmem), spikes, acc, oc + c_out, ic + c_in)
        ys = (
            (c_out, c_in) if collect_counts else None,
            acc if collect_readouts else None,
        )
        return carry, ys

    carry0 = (state.vmem, state.spikes, state.readout_acc,
              state.out_counts, state.in_counts)
    (vmem, spikes, acc, oc, ic), (counts, accs) = jax.lax.scan(
        step, carry0, events)
    new_state = EngineState(vmem=vmem, readout_acc=acc,
                            out_counts=oc, in_counts=ic, spikes=spikes)
    if pad:
        with jax.named_scope("spidr.align"):
            new_state = _fit_state(spec, new_state, spec.planes())
            acc = new_state.readout_acc
            if accs is not None and acc.ndim == 4:
                accs = _to_width(accs, acc.shape[2], 3)
    slot_out = slot_in = None
    sum_out = sum_in = None
    if collect_counts:
        slot_out, slot_in = counts            # (chunk_T, L, B)
        sum_out = jnp.sum(slot_out, axis=2)   # (chunk_T, L)
        sum_in = jnp.sum(slot_in, axis=2)
    return new_state, ChunkOutput(
        readout=acc,
        spike_counts=sum_out,
        input_counts=sum_in,
        slot_spike_counts=slot_out,
        slot_input_counts=slot_in,
        readouts=accs,
    )


def _run_folded(engine: SNNEngine, events: jax.Array) -> EngineOutput:
    state = init_state(engine, events.shape[1])
    _, out = run_chunk(engine, state, events)
    return EngineOutput(readout=out.readout, spike_counts=out.spike_counts,
                        input_counts=out.input_counts)


def run_engine(engine: SNNEngine, events: jax.Array,
               batch_mode: str = "fold") -> EngineOutput:
    """Run a whole (T, B, H, W, C) binary event stream through the engine.

    ``batch_mode="fold"`` folds B into the GEMM row dimension (one big
    weight-stationary pass per layer-timestep); ``"vmap"`` maps a
    single-sample engine over the batch axis.  Identical results.

    Implemented as ``init_state`` + one whole-stream ``run_chunk`` — the
    chunked/streaming path and the batch path are the same code.
    """
    assert events.ndim == 5, "expected (T, B, H, W, C)"
    if batch_mode == "fold":
        return _run_folded(engine, events)
    if batch_mode == "vmap":
        out = jax.vmap(
            lambda ev: _run_folded(engine, ev[:, None]),
            in_axes=1,
        )(events)
        return EngineOutput(
            readout=out.readout[:, 0],
            spike_counts=jnp.sum(out.spike_counts, axis=0),
            input_counts=jnp.sum(out.input_counts, axis=0),
        )
    raise ValueError(f"unknown batch_mode {batch_mode!r}")


jax.tree_util.register_pytree_node(
    EngineOutput,
    lambda o: ((o.readout, o.spike_counts, o.input_counts), None),
    lambda _, leaves: EngineOutput(*leaves),
)

jax.tree_util.register_pytree_node(
    EngineState,
    lambda st: ((st.vmem, st.readout_acc, st.out_counts, st.in_counts,
                 st.spikes), None),
    lambda _, leaves: EngineState(*leaves),
)

jax.tree_util.register_pytree_node(
    ChunkOutput,
    lambda o: ((o.readout, o.spike_counts, o.input_counts,
                o.slot_spike_counts, o.slot_input_counts, o.readouts), None),
    lambda _, leaves: ChunkOutput(*leaves),
)


# ---------------------------------------------------------------------------
# Pure-jnp per-timestep reference (no scan, no Pallas): the ground truth the
# engine must reproduce spike-for-spike.
# ---------------------------------------------------------------------------
def run_reference(engine: SNNEngine, events) -> EngineOutput:
    """Python-loop integer reference over the same quantized parameters."""
    spec = engine.spec
    cfg = dataclasses.replace(engine.cfg, backend="jnp")
    ref_engine = dataclasses.replace(engine, cfg=cfg)
    batch = events.shape[1]
    state = _init_vmem(ref_engine, batch)
    spikes = _init_spikes(spec, batch)
    acc = None
    all_out, all_in = [], []
    for t in range(events.shape[0]):
        state, spikes, (v, s), c_out, c_in = _forward_t(
            ref_engine, state, events[t], spikes)
        if spec.readout == "rate":
            acc = s if acc is None else acc + s
        else:
            acc = v
        all_out.append(jnp.sum(c_out, axis=1))
        all_in.append(jnp.sum(c_in, axis=1))
    return EngineOutput(
        readout=acc,
        spike_counts=jnp.stack(all_out),
        input_counts=jnp.stack(all_in),
    )
