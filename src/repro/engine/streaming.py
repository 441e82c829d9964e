"""Streaming stateful serving: persistent-Vmem sessions over one batched step.

SpiDR's defining behavior is that a layer's membrane potentials never leave
the CIM macro between timesteps — events handshake in asynchronously and
accumulate into *resident* state.  This module is the serving-system
analogue: a :class:`StreamSessionManager` keeps an :class:`EngineState`
whose batch axis is a bank of ``capacity`` *slots*, each slot holding the
persistent Vmem of one live event stream, and multiplexes every live
stream's next chunk of timesteps into **one fixed-shape batched
``run_chunk``** per tick (shapes never change, so the jitted step never
recompiles — the SNN analogue of the continuous-batching decode loop in
``launch/serve.py``).

Slot lifecycle (continuous batching over neuron state instead of KV cache):

  open()   -> allocate a free slot, zero its state (``reset_slot``)
  step()   -> pack each live stream's chunk into (chunk_T, capacity, H, W, C)
              — slots without a stream (or whose stream ended) contribute
              all-zero event planes, which the kernels' tile-level zero-skip
              eliminates — then advance every slot in one ``run_chunk``
  close()  -> retire the slot: zero its state so it is inert until reuse

Per-slot accounting rides on the engine's per-sample spike counters: each
tick, every *active* slot's ``(chunk_T, n_layers)`` input-spike counts are
priced with ``engine/cost.py`` (async-pipeline cycles + calibrated energy)
and accumulated on the slot.  Inactive slots are never charged — their
event planes are all zero, they contribute no spikes, and their cumulative
cycle/energy stays exactly 0.

Exactness contract (tested): because batch slots never interact inside the
engine (GEMM rows are independent, pooling is per-sample), a stream served
through the manager — whatever the chunk size, whatever else shares the
batch, however often slots around it are retired and reused — produces
spikes and readouts bit-identical to a single whole-stream ``run_engine``
call on that stream alone.

Multi-core plans ride through unchanged: an engine compiled with a
``repro.compiler`` CoreSchedule (``engine.compile_engine``) has the same
``run_chunk`` signature and bit-exact outputs, so the session mechanics
above don't change at all — only the pricing switches to
``estimate_multicore_cost`` (one resumable handshake clock set per core
per slot, additive routing cycles), and each ``SlotUpdate`` additionally
carries the stream's cumulative per-core cycles and load imbalance.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.pipeline import PipelineState
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from .cost import estimate_cost, estimate_multicore_cost
from .inference import (EngineState, SNNEngine, init_state, reset_slot,
                        run_chunk)

__all__ = ["SESSION_SCHEMA_VERSION", "SessionMark", "SlotUpdate",
           "StreamSessionManager"]

# Serialized-session schema version (see ``StreamSessionManager.state_dict``).
# Bump when the snapshot layout changes; ``load_state_dict`` refuses newer
# schemas with a clean error instead of misreading them.
SESSION_SCHEMA_VERSION = 1


@dataclasses.dataclass
class SlotUpdate:
    """Incremental reply for one stream after one session tick."""

    slot: int
    timesteps: int               # cumulative timesteps consumed by the stream
    readout: np.ndarray          # cumulative readout at ``timesteps``
    chunk_spikes: int            # output spikes this chunk (all layers)
    cycles: int                  # cumulative async-pipeline makespan cycles
    energy_uj: float             # cumulative calibrated energy
    spikes: int = 0              # cumulative output spikes (all layers)
    # Multi-core plans only (engine compiled with a CoreSchedule): the
    # stream's cumulative per-core cycle attribution and the current load
    # imbalance (max/mean busy) of its placement.  None/0 on single core.
    per_core_cycles: Optional[np.ndarray] = None
    load_imbalance: float = 0.0
    # This chunk's (t, n_layers) input-spike counts — populated only when
    # the manager was built with ``collect_chunk_counts=True`` (used by
    # ``launch/serve.py --trace-out`` to re-price finished streams with
    # ``collect_timeline=True`` for the per-stream pipeline timeline).
    input_counts: Optional[np.ndarray] = None


@dataclasses.dataclass(frozen=True)
class SessionMark:
    """An in-process rewind point of a session (:meth:`StreamSessionManager.
    mark`): the resident :class:`EngineState` held by reference, and a host
    copy of the session table.

    The device state is never copied: JAX arrays are immutable and neither
    the chunk step nor ``reset_slot`` donates its input, so the state a
    mark holds stays valid and unchanged however the session advances.
    The handshake clocks are held by reference too: a slot's
    ``PipelineState`` (or per-core list of them) is only ever replaced,
    never mutated.  Unlike :meth:`StreamSessionManager.
    state_dict` a mark is not durable: it lives as long as the process and
    its device.
    """

    state: EngineState       # the session's resident state, by reference
    active: tuple
    ended: tuple
    counters: tuple          # copies of the per-slot cumulative arrays
    pipe_state: tuple
    ticks: int


class StreamSessionManager:
    """Multiplex up to ``capacity`` live event streams onto one engine.

    ``step(chunks)`` takes ``{slot: events}`` with ``events`` of shape
    ``(t, H, W, C)``, ``t <= chunk_T`` (a shorter *final* chunk is
    zero-padded and the readout is snapshotted at the true last timestep),
    and returns ``{slot: SlotUpdate}``.

    The bit-exactness contract is *enforced*, not advisory: every open slot
    must deliver a chunk on every tick (a slot idling through a tick would
    silently advance its resident Vmem through zero-input timesteps — leak
    decay the whole-stream run never saw), and a slot that delivered a
    short chunk has ended its stream and must be ``close()``d before the
    next tick.  Violations raise immediately instead of corrupting state.
    """

    def __init__(self, engine: SNNEngine, capacity: int = 4,
                 chunk_T: int = 2, *, metrics=None, tracer=None,
                 collect_chunk_counts: bool = False, device=None):
        assert capacity >= 1 and chunk_T >= 1
        self.engine = engine
        self.capacity = capacity
        self.chunk_T = chunk_T
        self.device = device
        spec = engine.spec
        self._frame_shape = tuple(spec.input_hw) + (spec.in_channels,)
        # Telemetry (repro.obs).  ``None`` binds the process-wide defaults
        # (disabled unless ``obs.enable_metrics()``/``enable_tracing()`` is
        # called — enabling is retroactive since the objects are shared);
        # ``False`` pins telemetry hard-off for this session regardless of
        # the globals.  Every record site is guarded by one truthiness
        # check, so the disabled path stays within the <1% dispatch budget
        # gated by the ``telemetry_overhead`` benchmark.
        self._metrics = (obs_metrics.default_registry() if metrics is None
                         else (metrics or obs_metrics.MetricsRegistry(False)))
        self._tracer = (obs_trace.default_tracer() if tracer is None
                        else (tracer or obs_trace.Tracer(enabled=False)))
        self._collect_chunk_counts = bool(collect_chunk_counts)
        self._m = None  # lazily bound metric handles (first enabled tick)
        # Position-weighted input-plane size per timestep — the sparsity
        # denominator, identical to the cost model's definition.
        self._positions_per_t = float(
            sum(s.fan_in * s.out_positions for s in spec.layer_shapes()))
        self.state = init_state(engine, capacity)
        if device is not None:
            # Replica device placement: commit the session's resident state
            # to one host device so a fleet of sessions ticks on distinct
            # devices (the jitted step follows its committed operands).
            self.state = jax.device_put(self.state, device)
        # Bytes of the resident state (what ``state_dict`` copies to the
        # host and a ``mark`` pins on the device), of the per-slot arrays a
        # ``mark`` copies, and of one tick's event array: span arguments,
        # fixed for the session's lifetime.
        self.state_nbytes = sum(leaf.nbytes
                                for leaf in jax.tree.leaves(self.state))
        self._frame_bytes = 4 * chunk_T * capacity * int(
            np.prod(self._frame_shape))
        self.active = [False] * capacity
        self.ended = [False] * capacity   # delivered a short (final) chunk
        # Per-slot cumulative accounting (host side, O(capacity)).
        self.slot_timesteps = np.zeros(capacity, np.int64)
        self.slot_spikes = np.zeros(capacity, np.int64)
        self.slot_cycles = np.zeros(capacity, np.int64)
        self.slot_energy_uj = np.zeros(capacity, np.float64)
        # Resumable async-handshake clocks per slot: pricing chunk by chunk
        # with carried state gives the same cumulative makespan as pricing
        # the whole stream at once (chunking-invariant cycle accounting).
        # Multi-core plans keep one clock set per core (a list per slot)
        # plus cumulative per-core routing cycles (additive across chunks).
        self._pipe_state = [None] * capacity
        self._schedule = engine.schedule
        n_cores = engine.schedule.n_cores if engine.schedule else 1
        self._slot_route_cycles = np.zeros((capacity, n_cores), np.int64)
        self.slot_core_cycles = np.zeros((capacity, n_cores), np.int64)
        self.slot_imbalance = np.ones(capacity, np.float64)
        self.table_nbytes = 2 * capacity + sum(
            a.nbytes for a in self._counters())
        self.ticks = 0
        # One jitted step for the session's lifetime: fixed (chunk_T,
        # capacity, H, W, C) event shape, fixed state shapes.  A ``mark``
        # aliases the state passed to this step and to ``_reset``: neither
        # may donate it (``tests/test_streaming_durability.py`` fails if
        # one does).
        self._step = jax.jit(
            lambda st, ev: run_chunk(engine, st, ev, collect_counts=True,
                                     collect_readouts=True)
        )
        self._reset = jax.jit(reset_slot)

    # -- lifecycle ---------------------------------------------------------
    def open(self) -> Optional[int]:
        """Allocate a slot for a new stream; None if the session is full.

        The slot's device state needs no reset here: ``init_state`` zeroed
        every slot at construction and ``close()`` re-zeroes on retirement,
        so an inactive slot is already all-zero — admission is free.
        """
        for i in range(self.capacity):
            if not self.active[i]:
                self.active[i] = True
                self.ended[i] = False
                self.slot_timesteps[i] = 0
                self.slot_spikes[i] = 0
                self.slot_cycles[i] = 0
                self.slot_energy_uj[i] = 0.0
                self._pipe_state[i] = None
                self._slot_route_cycles[i] = 0
                self.slot_core_cycles[i] = 0
                self.slot_imbalance[i] = 1.0
                return i
        return None

    def close(self, slot: int) -> None:
        """Retire a stream: zero the slot so it is inert until reused."""
        assert self.active[slot], f"slot {slot} is not active"
        self.active[slot] = False
        self.ended[slot] = False
        # Traced under the tick that delivered the stream's last chunk.
        with self._tracer.span("session.close", cat="session",
                               tick=self.ticks - 1, slot=slot):
            self.state = self._reset(self.state, jnp.int32(slot))

    @property
    def occupancy(self) -> int:
        return sum(self.active)

    # -- telemetry ---------------------------------------------------------
    def _metric_handles(self):
        """Bind (and cache) the session's metric objects on first use."""
        if self._m is None:
            reg = self._metrics
            self._m = {
                "ticks": reg.counter(
                    "spidr_session_ticks_total", "Session step() calls"),
                "timesteps": reg.counter(
                    "spidr_stream_timesteps_total",
                    "Timesteps consumed across all streams"),
                "in_spikes": reg.counter(
                    "spidr_stream_input_spikes_total",
                    "Layer-input spikes across all streams"),
                "out_spikes": reg.counter(
                    "spidr_stream_output_spikes_total",
                    "Layer-output spikes across all streams"),
                "cycles": reg.counter(
                    "spidr_stream_cycles_total",
                    "Async-pipeline makespan cycle increments"),
                "energy": reg.counter(
                    "spidr_stream_energy_uj_total",
                    "Calibrated energy across all streams (uJ)"),
                "occupancy": reg.gauge(
                    "spidr_session_occupancy",
                    "Open slots at the last tick"),
                "sparsity": reg.histogram(
                    "spidr_chunk_sparsity",
                    "Per-slot per-chunk input sparsity",
                    edges=obs_metrics.FRACTION_BUCKETS),
                "tile_frac": reg.histogram(
                    "spidr_chunk_nonzero_tile_frac",
                    "Per-slot per-chunk nonzero event-tile fraction "
                    "(zero-skip opportunity)",
                    edges=obs_metrics.FRACTION_BUCKETS),
            }
        return self._m

    def _nonzero_tile_frac(self, chunk: np.ndarray) -> float:
        """Fraction of ``block_k``-wide event tiles holding any spike.

        The engine's zero-skip kernels drop all-zero GEMM tiles; this is
        the host-side view of how much of the input plane they get to
        skip, tiled along the flattened (H*W*C) axis with the engine's
        ``block_k``.
        """
        t = chunk.shape[0]
        flat = chunk.reshape(t, -1)
        bk = int(self.engine.cfg.block[2])
        k = flat.shape[1]
        n_tiles = -(-k // bk)
        pad = n_tiles * bk - k
        if pad:
            flat = np.pad(flat, ((0, 0), (0, pad)))
        nz = (flat.reshape(t, n_tiles, bk) != 0).any(axis=2)
        return float(nz.sum() / nz.size)

    # -- the batched tick --------------------------------------------------
    def step(self, chunks: Dict[int, np.ndarray]) -> Dict[int, SlotUpdate]:
        """Advance every slot by ``chunk_T`` timesteps in one fused call.

        Traced as ``run_chunk`` over its host phases: ``session.frame``
        (the dense event array), ``session.upload``, ``session.dispatch``
        (the jitted call, asynchronous), ``session.fetch`` (waiting for
        the device and copying its outputs back) and ``session.price``.
        """
        missing = [i for i in range(self.capacity)
                   if self.active[i] and i not in chunks]
        assert not missing, (
            f"open slots {missing} delivered no chunk this tick; an idle "
            "open slot would advance its Vmem through zero-input timesteps "
            "and diverge from the whole-stream result — deliver every tick "
            "or close() the slot")
        tracer, tick = self._tracer, self.ticks
        with tracer.span("run_chunk", cat="session", tick=tick,
                         slots=len(chunks)):
            with tracer.span("session.frame", cat="session", tick=tick,
                             bytes=self._frame_bytes):
                ev, valid = self._frame(chunks)

            # Telemetry pre-capture: cumulative counters only ever
            # accumulate *deltas*, so totals are chunking-invariant (tested).
            telemetry = bool(self._metrics)
            if telemetry:
                prev_cycles = self.slot_cycles.copy()
                prev_energy = self.slot_energy_uj.copy()

            with tracer.span("session.upload", cat="session", tick=tick):
                # Straight to the session's device (None: the default one).
                ev_dev = jax.device_put(ev, self.device)
            with tracer.span("session.dispatch", cat="session", tick=tick):
                self.state, out = self._step(self.state, ev_dev)
            self.ticks += 1

            fetched = (out.readouts,            # (chunk_T, capacity, ...)
                       out.slot_spike_counts,   # (chunk_T, L, capacity)
                       out.slot_input_counts)
            with tracer.span("session.fetch", cat="session", tick=tick,
                             bytes=sum(a.nbytes for a in fetched)):
                readouts, slot_out, slot_in = (np.asarray(a) for a in fetched)
            with tracer.span("session.price", cat="session", tick=tick):
                updates = self._price(valid, readouts, slot_out, slot_in)
            if telemetry:
                self._record_tick(chunks, valid, slot_in, updates,
                                  prev_cycles, prev_energy)
        return updates

    def _frame(self, chunks: Dict[int, np.ndarray]):
        """The tick's dense ``(chunk_T, capacity, H, W, C)`` event array
        and each delivering slot's valid timesteps."""
        ev = np.zeros((self.chunk_T, self.capacity) + self._frame_shape,
                      np.float32)
        valid = {}
        for slot, chunk in chunks.items():
            assert self.active[slot], f"slot {slot} is not active"
            assert not self.ended[slot], (
                f"slot {slot} already delivered a short (final) chunk; "
                "close() it before the next tick")
            chunk = np.asarray(chunk)
            assert chunk.shape[1:] == self._frame_shape, chunk.shape
            t = chunk.shape[0]
            assert 1 <= t <= self.chunk_T, (t, self.chunk_T)
            if t < self.chunk_T:
                self.ended[slot] = True
            ev[:t, slot] = chunk
            valid[slot] = t
        return ev, valid

    def _price(self, valid, readouts, slot_out, slot_in
               ) -> Dict[int, SlotUpdate]:
        """Price each delivering slot's chunk and build its reply."""
        updates = {}
        for slot, t in valid.items():
            # Price only this stream's own spikes: its per-slot input counts
            # over the chunk's valid timesteps, through the async-pipeline +
            # calibrated-energy models.  Idle slots are never charged.
            counts = slot_in[:t, :, slot]
            per_core_cycles, imbalance = None, 0.0
            if self._schedule is not None:
                cost = estimate_multicore_cost(
                    self.engine.spec, self._schedule, counts,
                    pipeline_states=self._pipe_state[slot])
                self._pipe_state[slot] = cost.pipeline_states
                # Per-core pipeline clocks resume across chunks; routing
                # cycles are additive — cumulative attribution stays
                # chunking-invariant, like the single-core path.
                self._slot_route_cycles[slot] += cost.routing_cycles
                makespans = np.array(
                    [pc.makespan_cycles for pc in cost.per_core], np.int64)
                per_core_cycles = makespans + self._slot_route_cycles[slot]
                self.slot_core_cycles[slot] = per_core_cycles
                self.slot_cycles[slot] = int(per_core_cycles.max())
                self.slot_imbalance[slot] = imbalance = cost.load_imbalance
                self.slot_energy_uj[slot] += float(cost.energy_uj)
            else:
                cost = estimate_cost(self.engine.spec, self.engine.cfg.qspec,
                                     counts,
                                     pipeline_state=self._pipe_state[slot])
                self._pipe_state[slot] = cost.pipeline_state
                # Resumed clocks make the makespan cumulative since the
                # stream began — identical to a whole-stream estimate, any
                # chunking.
                self.slot_cycles[slot] = int(cost.makespan_cycles)
                self.slot_energy_uj[slot] += float(cost.energy_uj)
            chunk_spikes = int(slot_out[:t, :, slot].sum())
            self.slot_timesteps[slot] += t
            self.slot_spikes[slot] += chunk_spikes
            updates[slot] = SlotUpdate(
                slot=slot,
                timesteps=int(self.slot_timesteps[slot]),
                # Snapshot at the stream's true last timestep: zero-padded
                # tail steps never leak into a short final chunk's readout.
                readout=readouts[t - 1, slot],
                chunk_spikes=chunk_spikes,
                cycles=int(self.slot_cycles[slot]),
                energy_uj=float(self.slot_energy_uj[slot]),
                spikes=int(self.slot_spikes[slot]),
                per_core_cycles=per_core_cycles,
                load_imbalance=imbalance,
                input_counts=(counts.copy()
                              if self._collect_chunk_counts else None),
            )
        return updates

    def _record_tick(self, chunks, valid, slot_in, updates,
                     prev_cycles, prev_energy) -> None:
        """Fold one tick into the metrics registry (enabled path only)."""
        m = self._metric_handles()
        m["ticks"].inc()
        m["occupancy"].set(self.occupancy)
        for slot, t in valid.items():
            up = updates[slot]
            in_spikes = float(slot_in[:t, :, slot].sum())
            m["timesteps"].inc(t)
            m["in_spikes"].inc(in_spikes)
            m["out_spikes"].inc(up.chunk_spikes)
            # Cumulative makespan is monotone per stream; exporting the
            # per-tick *increment* keeps the counter chunking-invariant.
            m["cycles"].inc(float(self.slot_cycles[slot] - prev_cycles[slot]))
            m["energy"].inc(
                float(self.slot_energy_uj[slot] - prev_energy[slot]))
            density = in_spikes / (self._positions_per_t * t)
            m["sparsity"].observe(float(np.clip(1.0 - density, 0.0, 1.0)))
            m["tile_frac"].observe(
                self._nonzero_tile_frac(np.asarray(chunks[slot])))

    # -- in-process rewind point -------------------------------------------
    def _counters(self) -> tuple:
        """The per-slot cumulative arrays, which a tick updates in place."""
        return (self.slot_timesteps, self.slot_spikes, self.slot_cycles,
                self.slot_energy_uj, self._slot_route_cycles,
                self.slot_core_cycles, self.slot_imbalance)

    def mark(self) -> SessionMark:
        """A rewind point at the current tick, for :meth:`rewind`.

        Holds the resident device state by reference and copies only the
        host-side session table (``table_nbytes``): no device-to-host
        transfer.  For a durable snapshot use :meth:`state_dict`.
        """
        return SessionMark(
            state=self.state,
            active=tuple(self.active),
            ended=tuple(self.ended),
            counters=tuple(a.copy() for a in self._counters()),
            pipe_state=tuple(self._pipe_state),
            ticks=self.ticks,
        )

    def rewind(self, mark: SessionMark) -> None:
        """Return the session to ``mark``, bit-exactly, with no transfer.

        The mark stays valid: the same mark may be rewound to again.
        """
        self.state = mark.state
        self.active = list(mark.active)
        self.ended = list(mark.ended)
        (self.slot_timesteps, self.slot_spikes, self.slot_cycles,
         self.slot_energy_uj, self._slot_route_cycles, self.slot_core_cycles,
         self.slot_imbalance) = (a.copy() for a in mark.counters)
        self._pipe_state = list(mark.pipe_state)
        self.ticks = mark.ticks

    # -- durability: serializable session state ----------------------------
    @property
    def n_cores(self) -> int:
        return self._schedule.n_cores if self._schedule is not None else 1

    def _pipe_dicts(self, slot: int) -> list:
        """Per-core clock dicts for one slot, ``None`` normalized to zeros.

        A never-stepped slot's ``None`` clock is bit-equivalent to
        :meth:`PipelineState.zero` (``simulate_pipeline`` zero-initializes
        when no state is given), so the serialized structure is identical
        for every slot — a requirement for restoring through the fixed-
        structure checkpoint format.
        """
        ps = self._pipe_state[slot]
        if ps is None:
            per_core = [PipelineState.zero() for _ in range(self.n_cores)]
        elif isinstance(ps, list):
            per_core = ps
        else:
            per_core = [ps]
        assert len(per_core) == self.n_cores, (len(per_core), self.n_cores)
        return [p.to_dict() for p in per_core]

    def state_dict(self) -> dict:
        """The session's full durable state as a deterministic pure-numpy
        tree: every live slot's integer :class:`EngineState` leaves, the
        session table (open/ended flags, cumulative per-slot accounting),
        and the resumable async-handshake clocks.

        Every array is a fresh host copy — nothing aliases the manager's
        live buffers, so ``state_dict`` at tick k is immutable evidence of
        tick k no matter how the session advances afterwards, and outlives
        the process and the device.  (The in-process rewind point is
        :meth:`mark`, which copies no device state.)  The schema
        is pinned by ``tests/test_streaming_durability.py``; round-tripping
        through :meth:`load_state_dict` is bit-exact (tested for any
        snapshot boundary, chunking and slot open/close interleaving).
        """
        st = self.state
        engine_state = {
            "vmem": [None if v is None else np.asarray(v).copy()
                     for v in st.vmem],
            "readout_acc": np.asarray(st.readout_acc).copy(),
            "out_counts": np.asarray(st.out_counts).copy(),
            "in_counts": np.asarray(st.in_counts).copy(),
        }
        if st.spikes:   # recurrent layers only: a chain's tree is unchanged
            engine_state["spikes"] = [np.asarray(z).copy()
                                      for z in st.spikes]
        return {
            "schema": np.int64(SESSION_SCHEMA_VERSION),
            "engine_state": engine_state,
            "table": {
                "active": np.asarray(self.active, np.bool_),
                "ended": np.asarray(self.ended, np.bool_),
                "timesteps": self.slot_timesteps.copy(),
                "spikes": self.slot_spikes.copy(),
                "cycles": self.slot_cycles.copy(),
                "energy_uj": self.slot_energy_uj.copy(),
                "route_cycles": self._slot_route_cycles.copy(),
                "core_cycles": self.slot_core_cycles.copy(),
                "imbalance": self.slot_imbalance.copy(),
                "ticks": np.int64(self.ticks),
            },
            "clocks": [self._pipe_dicts(s) for s in range(self.capacity)],
        }

    def load_state_dict(self, d: dict) -> None:
        """Restore the session to a :meth:`state_dict` snapshot, bit-exactly.

        The manager must have been constructed over the same engine
        geometry (capacity, chunk size, core count, layer shapes); a
        mismatched snapshot raises ``ValueError`` before any state is
        touched.  After the load, every subsequent ``step`` emits spikes,
        readouts and cumulative cycle/energy attribution identical to a
        session that was never interrupted.
        """
        schema = int(d["schema"])
        if schema > SESSION_SCHEMA_VERSION:
            raise ValueError(
                f"session snapshot schema {schema} is newer than this "
                f"build's {SESSION_SCHEMA_VERSION} — upgrade the code or "
                "re-snapshot")
        es, table, clocks = d["engine_state"], d["table"], d["clocks"]
        if len(table["active"]) != self.capacity:
            raise ValueError(
                f"snapshot holds {len(table['active'])} slots but this "
                f"session has capacity {self.capacity} — restore onto a "
                "session opened with the snapshot's geometry")
        if len(clocks) != self.capacity \
                or any(len(c) != self.n_cores for c in clocks):
            raise ValueError(
                f"snapshot clock layout {len(clocks)}x"
                f"{len(clocks[0]) if clocks else 0} does not match this "
                f"session's {self.capacity}x{self.n_cores} (capacity x "
                "cores) — was it taken on a different compiled plan?")
        vmem = []
        for cur, new in zip(self.state.vmem, es["vmem"]):
            if (cur is None) != (new is None) or (
                    cur is not None and cur.shape != np.shape(new)):
                raise ValueError(
                    "snapshot Vmem shapes do not match this engine's "
                    "layers — restore onto the same network/spec")
            vmem.append(None if new is None
                        else jnp.asarray(new, jnp.int32))
        spikes = self._spikes_of(es)
        self.state = dataclasses.replace(
            self.state,
            vmem=tuple(vmem),
            spikes=tuple(jnp.asarray(z, jnp.int8) for z in spikes),
            readout_acc=jnp.asarray(es["readout_acc"],
                                    self.state.readout_acc.dtype),
            out_counts=jnp.asarray(es["out_counts"], jnp.int32),
            in_counts=jnp.asarray(es["in_counts"], jnp.int32),
        )
        if self.device is not None:
            self.state = jax.device_put(self.state, self.device)
        self.active = [bool(a) for a in np.asarray(table["active"])]
        self.ended = [bool(e) for e in np.asarray(table["ended"])]
        self.slot_timesteps = np.asarray(table["timesteps"], np.int64).copy()
        self.slot_spikes = np.asarray(table["spikes"], np.int64).copy()
        self.slot_cycles = np.asarray(table["cycles"], np.int64).copy()
        self.slot_energy_uj = np.asarray(table["energy_uj"],
                                         np.float64).copy()
        self._slot_route_cycles = np.asarray(table["route_cycles"],
                                             np.int64).copy()
        self.slot_core_cycles = np.asarray(table["core_cycles"],
                                           np.int64).copy()
        self.slot_imbalance = np.asarray(table["imbalance"],
                                         np.float64).copy()
        self.ticks = int(table["ticks"])
        pipe = []
        for per_core in clocks:
            states = [PipelineState.from_dict(p) for p in per_core]
            pipe.append(states if self._schedule is not None else states[0])
        self._pipe_state = pipe

    def _spikes_of(self, tree: dict, per_slot: bool = False) -> list:
        """The previous-spike planes a snapshot or slot payload carries,
        checked against this engine's recurrent layers (none, and no key,
        for a chain)."""
        got = list(tree.get("spikes", []))
        want = [z.shape[1:] if per_slot else z.shape
                for z in self.state.spikes]
        if [np.shape(z) for z in got] != want:
            raise ValueError(
                f"previous-spike planes {[np.shape(z) for z in got]} do not "
                f"match this engine's recurrent layers {want} — restore onto "
                "the same network/spec")
        return got

    # -- live migration: one slot's durable state --------------------------
    def export_slot(self, slot: int) -> dict:
        """One live stream's complete durable state as a pure-numpy tree.

        The per-slot slice of :meth:`state_dict` — resident Vmem, readout
        accumulator, spike counters, the session table's cumulative
        accounting, and the resumable handshake clocks.  Fresh host copies,
        nothing aliases live buffers.  Because batch slots never interact
        inside the engine, ``export_slot`` on manager A followed by
        :meth:`import_slot` on manager B (same engine geometry) continues
        the stream bit-exactly: identical spikes, readouts and cumulative
        cycle/energy attribution to a never-migrated run.
        """
        if not self.active[slot]:
            raise ValueError(
                f"slot {slot} is not active — only a live stream's state "
                "can be exported for migration")
        st = self.state
        payload = {
            "schema": np.int64(SESSION_SCHEMA_VERSION),
            "vmem": [None if v is None else np.asarray(v[slot]).copy()
                     for v in st.vmem],
            "readout_acc": np.asarray(st.readout_acc[slot]).copy(),
            "out_counts": np.asarray(st.out_counts[:, slot]).copy(),
            "in_counts": np.asarray(st.in_counts[:, slot]).copy(),
            "table": {
                "ended": bool(self.ended[slot]),
                "timesteps": int(self.slot_timesteps[slot]),
                "spikes": int(self.slot_spikes[slot]),
                "cycles": int(self.slot_cycles[slot]),
                "energy_uj": float(self.slot_energy_uj[slot]),
                "route_cycles": self._slot_route_cycles[slot].copy(),
                "core_cycles": self.slot_core_cycles[slot].copy(),
                "imbalance": float(self.slot_imbalance[slot]),
            },
            "clocks": self._pipe_dicts(slot),
        }
        if st.spikes:   # recurrent layers only: a chain's payload is unchanged
            payload["spikes"] = [np.asarray(z[slot]).copy()
                                 for z in st.spikes]
        return payload

    def import_slot(self, payload: dict, slot: Optional[int] = None) -> int:
        """Install an :meth:`export_slot` payload into a free slot.

        ``slot`` picks the destination explicitly (must be free); the
        default takes the first free slot, like :meth:`open`.  The payload
        must come from a session over the same engine geometry (layer
        shapes, core count) — mismatches raise ``ValueError`` before any
        state is touched.  Returns the destination slot, now active and
        continuing the stream bit-exactly.
        """
        schema = int(payload["schema"])
        if schema > SESSION_SCHEMA_VERSION:
            raise ValueError(
                f"slot payload schema {schema} is newer than this build's "
                f"{SESSION_SCHEMA_VERSION} — upgrade the code or re-export")
        if slot is None:
            slot = next((i for i in range(self.capacity)
                         if not self.active[i]), None)
            if slot is None:
                raise ValueError(
                    "no free slot to import into — close a stream or "
                    "migrate to a session with free capacity")
        elif self.active[slot]:
            raise ValueError(
                f"slot {slot} already holds a live stream — import into a "
                "free slot")
        if len(payload["clocks"]) != self.n_cores:
            raise ValueError(
                f"slot payload carries {len(payload['clocks'])} core "
                f"clock(s) but this session runs {self.n_cores} — was it "
                "exported from a different compiled plan?")
        st = self.state
        for cur, new in zip(st.vmem, payload["vmem"]):
            if (cur is None) != (new is None) or (
                    cur is not None and cur.shape[1:] != np.shape(new)):
                raise ValueError(
                    "slot payload Vmem shapes do not match this engine's "
                    "layers — migrate between replicas of the same "
                    "network/spec")
        spikes = self._spikes_of(payload, per_slot=True)
        vmem = tuple(
            cur if cur is None
            else cur.at[slot].set(jnp.asarray(new, jnp.int32))
            for cur, new in zip(st.vmem, payload["vmem"]))
        self.state = dataclasses.replace(
            st,
            vmem=vmem,
            spikes=tuple(cur.at[slot].set(jnp.asarray(new, jnp.int8))
                         for cur, new in zip(st.spikes, spikes)),
            readout_acc=st.readout_acc.at[slot].set(
                jnp.asarray(payload["readout_acc"],
                            st.readout_acc.dtype)),
            out_counts=st.out_counts.at[:, slot].set(
                jnp.asarray(payload["out_counts"], jnp.int32)),
            in_counts=st.in_counts.at[:, slot].set(
                jnp.asarray(payload["in_counts"], jnp.int32)),
        )
        table = payload["table"]
        self.active[slot] = True
        self.ended[slot] = bool(table["ended"])
        self.slot_timesteps[slot] = int(table["timesteps"])
        self.slot_spikes[slot] = int(table["spikes"])
        self.slot_cycles[slot] = int(table["cycles"])
        self.slot_energy_uj[slot] = float(table["energy_uj"])
        self._slot_route_cycles[slot] = np.asarray(table["route_cycles"],
                                                   np.int64)
        self.slot_core_cycles[slot] = np.asarray(table["core_cycles"],
                                                 np.int64)
        self.slot_imbalance[slot] = float(table["imbalance"])
        states = [PipelineState.from_dict(p) for p in payload["clocks"]]
        self._pipe_state[slot] = (states if self._schedule is not None
                                  else states[0])
        return slot
