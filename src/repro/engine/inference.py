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

Beyond a chain (``core.network``'s layer flags; only this scan walk runs
them): a recurrent conv joins its own previous spikes (carried in
``EngineState.spikes``) to its input before im2col, so one GEMM of fan-in
``kh*kw*(c_in + c_out)`` takes both; a stateless head runs the same
update from a zero Vmem with a threshold it cannot reach, so its output is
its saturated current and it carries no state.

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

Multi-core execution: ``compile_engine(engine, schedule)`` bakes a
``repro.compiler`` :class:`CoreSchedule` into the engine — every weight
layer's output channels become stacked per-core slices executed over a
``cores`` axis (``shard_map`` on a real device mesh, lockstep ``vmap``
emulation on one device) and reassembled by concatenation.  Because the
integer GEMM + neuron update are column-independent, the multi-core path
is bit-exact with the single-core path under any chunking, so the chunked
API below (and the streaming session manager on top of it) work unchanged
on a compiled plan.

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
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..compiler.schedule import CoreSchedule
from ..core.layers import im2col, maxpool2d
from ..core.network import SNNSpec, _init_spikes, _init_state
from ..core.neuron import NeuronConfig, neuron_step_int
from ..core.quant import QuantSpec, quantize, saturate
from ..kernels.fused_lif_gemm import (
    DEFAULT_BLOCK,
    fused_lif_gemm_int,
    fused_lif_gemm_int_tblk,
)
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
    # Vmem-stationary timestep tiling: >1 routes fused-backend chunks
    # through the layer-outer T_blk path (``fused_lif_gemm_int_tblk``) —
    # each weight block is touched once per ``t_block`` timesteps instead
    # of once per timestep.  Bit-exact with the scan path for any value.
    t_block: int = 1

    def __post_init__(self):
        assert self.backend in ("fused", "jnp"), self.backend
        assert isinstance(self.t_block, int) and self.t_block >= 1, \
            self.t_block


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
    # Multi-core placement (set by ``compile_engine`` from a CoreSchedule):
    # stacked per-core channel slices of ``w_q``, zero-padded to the widest
    # slice, plus each core's (lo, hi) channel range ((0, 0) = idle core).
    w_cores: Optional[jax.Array] = None   # (n_cores, F, Kc) int8
    core_slices: tuple = ()               # per-core (lo, hi), len n_cores
    # Per-core slices of a per-channel ``thr_int`` (padding gets v_max+1 so
    # padded channels never spike); None when ``thr_int`` is a scalar.
    thr_cores: Optional[jax.Array] = None  # (n_cores, Kc) int32
    # Autotuned kernel config override: (block_m, block_n, block_k, t_blk).
    # None falls back to the engine-wide ``cfg.block`` / ``cfg.t_block``.
    kcfg: Optional[tuple] = None


@dataclasses.dataclass(frozen=True)
class SNNEngine:
    spec: SNNSpec
    cfg: EngineConfig
    layers: tuple  # of EngineLayer
    # Multi-core plan (None = single-core).  ``compile_engine`` sets both;
    # ``device_parallel`` selects shard_map over a "cores" mesh axis (real
    # devices) vs lockstep vmap emulation (single device).
    schedule: Optional[CoreSchedule] = None
    device_parallel: bool = False


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


def build_engine(spec: SNNSpec, params, cfg: EngineConfig,
                 tune=None) -> SNNEngine:
    """Quantize float params into the integer engine (per-tensor scales).

    ``tune``, where given, maps the quantized engine to the one served
    (``spidr.compile``'s autotune), inside the ``engine.build`` span, whose
    ``pad_w`` is then the number of zero columns the returned engine's
    chunk walk adds to every plane (:func:`_walk_pad`)."""
    with obs_trace.default_tracer().span(
            "engine.build", cat="compile", network=spec.name,
            backend=cfg.backend) as args:
        engine = _build_engine(spec, params, cfg)
        if tune is not None:
            engine = tune(engine)
        args["pad_w"] = _walk_pad(engine)
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
                  cfg: EngineConfig, w_q: Optional[jax.Array] = None,
                  thr=None):
    """(rows, F) spikes x (F, K) weights + (rows, K) Vmem -> (v', s).

    ``w_q``/``thr`` override the layer's weights and integer threshold —
    the multi-core path maps this function over per-core channel slices of
    the weight matrix (and, for per-channel-quantized layers, of the
    threshold vector).
    """
    n = el.neuron
    w = el.w_q if w_q is None else w_q
    thr = el.thr_int if thr is None else thr
    if cfg.backend == "fused":
        return fused_lif_gemm_int(
            s2, w, v2,
            threshold=thr,
            leak_shift=n.leak_shift if n.model == "lif" else 0,
            soft_reset=(n.reset == "soft"),
            vmem_bits=cfg.qspec.vmem_bits,
            block=cfg.block,
            interpret=cfg.interpret,
            skip_empty=cfg.skip_empty,
        )
    acc = jnp.dot(
        s2.astype(jnp.int32), w.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )
    partial = saturate(acc, cfg.qspec)
    # leak_shift=0 means "no leak" (the kernels' convention); neuron_step_int
    # would compute v - (v >> 0) = 0, so route that case through IF dynamics.
    if n.model == "lif" and n.leak_shift == 0:
        n = dataclasses.replace(n, model="if")
    return neuron_step_int(v2, partial, n, cfg.qspec, thr)


# ---------------------------------------------------------------------------
# Multi-core execution (compiled CoreSchedule): each weight layer's output
# channels live as per-core slices.  Every core scans the full input spike
# plane into its own slice's weights (the spike-routing the cost model
# charges), so per-channel results are identical to the single-core GEMM —
# integer GEMM + neuron update are column-independent, which is what makes
# the reassembled output bit-exact.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _cores_mesh(n_cores: int) -> Mesh:
    """The ``cores`` device mesh axis (first ``n_cores`` local devices)."""
    return Mesh(np.array(jax.devices()[:n_cores]), ("cores",))


def _multicore_apply(el: EngineLayer, s2: jax.Array, v2: jax.Array,
                     cfg: EngineConfig, device_parallel: bool, core_update):
    """Run one layer's per-core channel slices and reassemble the output.

    ``el.w_cores`` is ``(C, F, Kc)``; core ``c`` computes channels
    ``[lo_c, hi_c)`` against the *same* spike matrix (replicated — the
    engine analogue of routing the input spikes to every consumer core).
    Idle cores carry zero-width slices padded with zero weights; their
    results are discarded at reassembly.

    ``core_update(sp, blocks)`` runs one core's slice, ``blocks`` =
    ``(w, [thr,] v)``, and returns a ``(v-like, s-like)`` pair whose
    *last* axis is the channel axis — the single-timestep update returns
    ``(rows, Kc)`` pairs, the T_blk update ``(T, rows, Kc)`` stacks; the
    slicing/reassembly below is rank-agnostic.
    """
    n_cores, _, kc = el.w_cores.shape

    def pad_slice(lo, hi):
        vc = v2[:, lo:hi]
        if hi - lo < kc:
            vc = jnp.pad(vc, ((0, 0), (0, kc - (hi - lo))))
        return vc

    # Per-core operands mapped over the ``cores`` axis: the weight slices,
    # plus (for per-channel-quantized layers) the threshold slices.  A
    # scalar threshold stays baked into the kernel via ``el.thr_int``.
    per_core_ops = [el.w_cores]
    if el.thr_cores is not None:
        per_core_ops.append(el.thr_cores)

    if device_parallel and n_cores > 1:
        # Full (n_cores, ...) stack: shard_map needs one uniform block per
        # mesh device, so idle cores ride along with zero weights (they are
        # idle silicon either way).
        v_cores = jnp.stack([pad_slice(lo, hi) for lo, hi in el.core_slices])
        fn = jax.shard_map(
            lambda sp, *blocks: jax.vmap(
                lambda *bs: core_update(sp, bs))(*blocks),
            mesh=_cores_mesh(n_cores),
            in_specs=(P(),) + (P("cores"),) * (len(per_core_ops) + 1),
            out_specs=(P("cores"), P("cores")),
            check_vma=False,
        )
        v_next, s = fn(s2, *per_core_ops, v_cores)
        row = {c: c for c in range(n_cores)}
    else:
        # Lockstep vmapped emulation on one device: only the cores that
        # actually hold a slice compute — a whole layer placed on one core
        # must not cost n_cores zero-weight GEMMs.
        active = tuple(c for c in range(n_cores)
                       if el.core_slices[c][1] > el.core_slices[c][0])
        idx = np.asarray(active)
        v_cores = jnp.stack([pad_slice(*el.core_slices[c]) for c in active])
        v_next, s = jax.vmap(lambda *bs: core_update(s2, bs))(
            *[op[idx] for op in per_core_ops], v_cores)
        row = {c: i for i, c in enumerate(active)}

    # Reassemble output channels in slice order (slices are contiguous and
    # cover [0, K), so concatenation restores the single-core layout).
    # ``[..., :width]`` / ``axis=-1`` keep this correct for both the 2-D
    # single-timestep outputs and the 3-D T_blk trajectory stacks.
    order = sorted(
        (c for c in row if el.core_slices[c][1] > el.core_slices[c][0]),
        key=lambda c: el.core_slices[c][0],
    )
    v_out = jnp.concatenate(
        [v_next[row[c]][..., : el.core_slices[c][1] - el.core_slices[c][0]]
         for c in order], axis=-1)
    s_out = jnp.concatenate(
        [s[row[c]][..., : el.core_slices[c][1] - el.core_slices[c][0]]
         for c in order], axis=-1)
    return v_out, s_out


def _multicore_update(el: EngineLayer, s2: jax.Array, v2: jax.Array,
                      cfg: EngineConfig, device_parallel: bool):
    """Single-timestep multi-core layer update (the original path)."""
    def core_update(sp, blocks):
        w, *thr, v = blocks
        return _fused_update(el, sp, v, cfg, w_q=w,
                             thr=thr[0] if thr else None)

    return _multicore_apply(el, s2, v2, cfg, device_parallel, core_update)


def _layer_update(engine: SNNEngine, el: EngineLayer, s2: jax.Array,
                  v2: jax.Array):
    if el.w_cores is not None:
        return _multicore_update(el, s2, v2, engine.cfg,
                                 engine.device_parallel)
    return _fused_update(el, s2, v2, engine.cfg)


# ---------------------------------------------------------------------------
# Vmem-stationary T_blk tiling: the layer-outer chunk path.  Instead of
# scanning timesteps with a full layer sweep per step, each weight layer
# consumes the whole chunk as (T, rows, F) spike stacks in T_blk-sized
# slabs — one ``fused_lif_gemm_int_tblk`` call per slab touches every
# weight block once for the slab's timesteps (the chip's Vmem-stationary
# mode 2 reuse, Sec II-E).  Bit-exact with the scan path because integer
# accumulation is exact and the per-slab neuron program is sequential in t.
# ---------------------------------------------------------------------------
def _layer_kcfg(el: EngineLayer, cfg: EngineConfig):
    """(gemm block, t_blk) for one layer: autotuned override or config."""
    if el.kcfg is not None:
        bm, bn, bk, tb = el.kcfg
        return (bm, bn, bk), tb
    return cfg.block, cfg.t_block


def _tblk_update(el: EngineLayer, s_slab: jax.Array, v2: jax.Array,
                 cfg: EngineConfig, block: tuple,
                 w_q: Optional[jax.Array] = None, thr=None):
    """One T_blk slab: (T, rows, F) spikes -> (T, rows, K) v-traj + spikes."""
    n = el.neuron
    w = el.w_q if w_q is None else w_q
    thr = el.thr_int if thr is None else thr
    return fused_lif_gemm_int_tblk(
        s_slab, w, v2,
        threshold=thr,
        leak_shift=n.leak_shift if n.model == "lif" else 0,
        soft_reset=(n.reset == "soft"),
        vmem_bits=cfg.qspec.vmem_bits,
        block=block,
        interpret=cfg.interpret,
        skip_empty=cfg.skip_empty,
    )


def _layer_update_tblk(engine: SNNEngine, el: EngineLayer,
                       s_stack: jax.Array, v2: jax.Array):
    """Walk a (T, rows, F) spike stack through one layer in T_blk slabs.

    ``chunk_T`` need not divide ``t_blk``: the remainder slab is simply a
    second (static-shape) kernel specialization.  The Vmem carry threads
    through the slabs, so the result is bit-exact under any slab geometry.
    """
    cfg = engine.cfg
    block, tb = _layer_kcfg(el, cfg)
    t = s_stack.shape[0]

    def slab_update(slab, v_in):
        if el.w_cores is None:
            return _tblk_update(el, slab, v_in, cfg, block)

        def core_update(sp, blocks):
            w, *thr, v = blocks
            return _tblk_update(el, sp, v, cfg, block, w_q=w,
                                thr=thr[0] if thr else None)

        return _multicore_apply(el, slab, v_in, cfg,
                                engine.device_parallel, core_update)

    v_parts, s_parts = [], []
    for t0 in range(0, t, tb):
        v_traj, s = slab_update(s_stack[t0:t0 + tb], v2)
        v_parts.append(v_traj)
        s_parts.append(s)
        v2 = v_traj[-1]
    if len(v_parts) == 1:
        return v_parts[0], s_parts[0]
    return jnp.concatenate(v_parts), jnp.concatenate(s_parts)


def _tblk_active(engine: SNNEngine) -> bool:
    """Route chunks through the layer-outer tiled path?"""
    if engine.cfg.backend != "fused":
        return False
    if engine.cfg.t_block > 1:
        return True
    return any(el.kcfg is not None and el.kcfg[3] > 1
               for el in engine.layers if el.kind in ("conv", "fc"))


def _pool_stack(act: jax.Array, window: int, stride: int) -> jax.Array:
    """maxpool2d over a (T, B, H, W, C) stack via T*B folding."""
    t, b = act.shape[:2]
    out = maxpool2d(act.reshape((t * b,) + act.shape[2:]),
                    window=window, stride=stride)
    return out.reshape((t, b) + out.shape[1:])


def _run_chunk_tiled(engine: SNNEngine, state: EngineState,
                     events: jax.Array, collect_counts: bool,
                     collect_readouts: bool):
    """Layer-outer twin of ``run_chunk``'s scan: same state, same outputs.

    Memory note: this path materializes (chunk_T, ...) activation stacks
    per layer — O(chunk_T), like ``collect_counts`` — so streams should
    keep ``chunk_T`` at a small multiple of ``t_block`` (the scan path
    remains the right tool for huge single-chunk runs).
    """
    spec = engine.spec
    spec.require_chain("the layer-outer t_block walk (t_block > 1)")
    t, b = events.shape[:2]
    act = events.astype(jnp.float32)
    new_vmem, counts_out, counts_in = [], [], []
    last = None  # (v_traj, s_stack) of the last weight layer
    for el, v, scope in zip(engine.layers, state.vmem, _layer_scopes(engine)):
        if el.kind == "conv":
            with jax.named_scope(f"{scope}.counts"):
                counts_in.append(jnp.sum(act != 0, axis=(2, 3, 4)))
            with jax.named_scope(f"{scope}.patches"):
                flat = act.reshape((t * b,) + act.shape[2:])
                cols = im2col(flat, el.kh, el.kw, el.stride, el.padding)
                p, f = cols.shape[1], cols.shape[2]
                k = el.w_q.shape[1]
                s_stack = cols.reshape(t, b * p, f).astype(jnp.int8)
            with jax.named_scope(f"{scope}.kernel"):
                v_traj, s = _layer_update_tblk(engine, el, s_stack,
                                               v.reshape(b * p, k))
                v_traj = v_traj.reshape((t,) + v.shape)
                s = s.reshape((t,) + v.shape)
            new_vmem.append(v_traj[-1])
            with jax.named_scope(f"{scope}.counts"):
                counts_out.append(jnp.sum(s, axis=(2, 3, 4)))
            act, last = s.astype(jnp.float32), (v_traj, s)
        elif el.kind == "fc":
            with jax.named_scope(f"{scope}.patches"):
                flat = act.reshape(t, b, -1)
                s_stack = flat.astype(jnp.int8)
            with jax.named_scope(f"{scope}.counts"):
                counts_in.append(jnp.sum(flat != 0, axis=2))
            with jax.named_scope(f"{scope}.kernel"):
                v_traj, s = _layer_update_tblk(engine, el, s_stack, v)
            new_vmem.append(v_traj[-1])
            with jax.named_scope(f"{scope}.counts"):
                counts_out.append(jnp.sum(s, axis=2))
            act, last = s.astype(jnp.float32), (v_traj, s)
        elif el.kind == "pool":
            with jax.named_scope(scope):
                act = _pool_stack(act, 2, 2)
            new_vmem.append(None)
        elif el.kind == "adaptive_pool":
            kk = act.shape[2] // el.target_hw
            with jax.named_scope(scope):
                act = _pool_stack(act, kk, kk)
            new_vmem.append(None)
    v_traj, s_last = last
    with jax.named_scope("spidr.readout"):
        if spec.readout == "rate":
            accs = state.readout_acc[None] + jnp.cumsum(s_last, axis=0)
        else:
            accs = v_traj
    slot_out = jnp.stack(counts_out, axis=1)   # (chunk_T, L, B)
    slot_in = jnp.stack(counts_in, axis=1)
    new_state = EngineState(
        vmem=tuple(new_vmem),
        readout_acc=accs[-1],
        out_counts=state.out_counts + jnp.sum(slot_out, axis=0),
        in_counts=state.in_counts + jnp.sum(slot_in, axis=0),
    )
    return new_state, ChunkOutput(
        readout=accs[-1],
        spike_counts=jnp.sum(slot_out, axis=2) if collect_counts else None,
        input_counts=jnp.sum(slot_in, axis=2) if collect_counts else None,
        slot_spike_counts=slot_out if collect_counts else None,
        slot_input_counts=slot_in if collect_counts else None,
        readouts=accs if collect_readouts else None,
    )


def compile_engine(engine: SNNEngine, schedule: CoreSchedule,
                   device_parallel: Optional[bool] = None) -> SNNEngine:
    """Bake a compiler :class:`CoreSchedule` into an executable engine.

    Splits every weight layer's quantized weights into the schedule's
    per-core channel slices (stacked, zero-padded to the widest slice) and
    returns an engine whose ``run_chunk``/``run_engine`` execute the
    multi-core plan — bit-exactly with the single-core engine, under any
    chunking, so the streaming session manager works unchanged.

    ``device_parallel=None`` auto-selects: ``shard_map`` over a ``cores``
    mesh axis when the host has at least ``n_cores`` devices, lockstep
    ``vmap`` emulation otherwise.
    """
    with obs_trace.default_tracer().span("engine.compile_schedule",
                                         cat="compile",
                                         network=engine.spec.name,
                                         n_cores=schedule.n_cores):
        return _compile_engine(engine, schedule, device_parallel)


def _compile_engine(engine: SNNEngine, schedule: CoreSchedule,
                    device_parallel: Optional[bool] = None) -> SNNEngine:
    assert engine.schedule is None, "engine already carries a schedule"
    engine.spec.require_chain("the multi-core path (n_cores > 1)")
    for ls in schedule.layers:
        if ls.plan.spec != engine.cfg.qspec:
            raise ValueError(
                f"schedule selected {ls.plan.spec} for layer {ls.node} but "
                f"the engine executes {engine.cfg.qspec}; precision-"
                "exploring schedules (allowed_specs) are for cost analysis, "
                "not execution")
    n_cores = schedule.n_cores
    by_node = {ls.node: ls for ls in schedule.layers}
    new_layers = []
    for idx, el in enumerate(engine.layers):
        if el.kind not in ("conv", "fc"):
            new_layers.append(el)
            continue
        ls = by_node[idx]
        k = el.w_q.shape[1]
        assert k == ls.out_channels, (k, ls.out_channels)
        kc = max(s.width for s in ls.slices)
        w_cores = np.zeros((n_cores, el.w_q.shape[0], kc), np.int8)
        core_slices = [(0, 0)] * n_cores
        w_np = np.asarray(el.w_q)
        # Per-channel-quantized layers carry their threshold vector along
        # the same channel slices; padding gets v_max+1 (never fires).
        per_channel = np.ndim(el.thr_int) > 0
        thr_cores = np.full((n_cores, kc), engine.cfg.qspec.v_max + 1,
                            np.int32) if per_channel else None
        for s in ls.slices:
            w_cores[s.core, :, : s.width] = w_np[:, s.lo:s.hi]
            core_slices[s.core] = (s.lo, s.hi)
            if per_channel:
                thr_cores[s.core, : s.width] = np.asarray(
                    el.thr_int)[s.lo:s.hi]
        new_layers.append(dataclasses.replace(
            el, w_cores=jnp.asarray(w_cores), core_slices=tuple(core_slices),
            thr_cores=None if thr_cores is None else jnp.asarray(thr_cores)))
    if device_parallel is None:
        device_parallel = 1 < n_cores <= len(jax.devices())
    if device_parallel:
        assert n_cores <= len(jax.devices()), (
            f"device_parallel needs {n_cores} devices, "
            f"host has {len(jax.devices())}")
    return dataclasses.replace(engine, layers=tuple(new_layers),
                               schedule=schedule,
                               device_parallel=bool(device_parallel))


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


def _pad_columns(width: int) -> int:
    """Zero columns that bring ``width`` to a multiple of ``_ALIGN_W``."""
    return -width % _ALIGN_W


def _walk_pad(engine: SNNEngine) -> int:
    """Zero columns ``run_chunk`` adds to every plane: those that align
    the input width on the scan walk, none on the ``t_block > 1`` walk."""
    if _tblk_active(engine):
        return 0
    return _pad_columns(engine.spec.input_hw[1])


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
                    v_next, s = _layer_update(engine, el, s2,
                                              v.reshape(rows, k))
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
                v_next, s = _layer_update(engine, el, s2, v)
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
    if _tblk_active(engine):
        return _run_chunk_tiled(engine, state, events,
                                collect_counts, collect_readouts)
    pad = _walk_pad(engine)
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
