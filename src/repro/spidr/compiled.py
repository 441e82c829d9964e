"""``spidr.compile(network, params, target) -> CompiledSNN``: the facade.

One entry point from a network to a deployed SpiDR instance.  Internally it
routes through the existing layers — ``engine`` (fused timestep loop),
``compiler`` (multi-core partition/place/schedule), ``snn.export``
(train->deploy integer folding) and ``engine.streaming`` (persistent-Vmem
sessions) — which are documented internals; every launcher, benchmark,
example and doc constructs deployments through this module instead.

Two input forms, matching the two legacy build chains bit-for-bit:

  * ``compile(spec, float_params, target)`` quantizes with per-tensor
    scales (the legacy ``build_engine`` chain — untrained/ad-hoc params);
  * ``compile(exported, spec, target)`` deploys a trained
    :class:`~repro.snn.export.ExportedNetwork` (per-channel power-of-two
    scales, the legacy ``snn.export.deploy`` chain) — bit-identical to the
    QAT training graph.

``target.n_cores > 1`` additionally routes through
``compiler.compile_network`` + ``engine.compile_engine``; the compiled
plan is bit-exact with single-core execution under any chunking, so every
:class:`CompiledSNN` method behaves identically at any core count.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
import warnings
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.checkpoint import Checkpointer
from ..compiler import compile_network
from ..core.network import SNNSpec
from ..core.pipeline import PipelineState
from ..core.quant import QuantSpec
from ..engine.cost import estimate_cost, estimate_multicore_cost
from ..engine.inference import (
    EngineConfig,
    EngineLayer,
    EngineOutput,
    SNNEngine,
    build_engine,
    compile_engine,
    run_engine,
    run_reference,
)
from ..engine.streaming import (
    SESSION_SCHEMA_VERSION,
    SlotUpdate,
    StreamSessionManager,
)
from ..obs import metrics as obs_metrics
from ..obs import timeline as obs_timeline
from ..obs import trace as obs_trace
from ..snn.export import (
    ExportedLayer,
    ExportedNetwork,
    RoundTrip,
    deploy,
    save_exported,
    load_exported,
    verify_roundtrip,
)
from .target import DeployTarget, _require_positive_int

if TYPE_CHECKING:  # pragma: no cover - annotation-only import
    from ..analysis import AnalysisReport

#: ``spidr.compile(..., check=...)`` modes for the static-analysis gate.
CHECK_MODES = ("strict", "warn", "off")

__all__ = [
    "CompiledSNN",
    "SlotUpdate",
    "StreamSession",
    "VerifyReport",
    "compile",
    "load",
    "read_snapshot_meta",
    "restore",
]

# Live-session snapshot artifact: one Checkpointer step whose metadata
# carries this key.  Distinct from the ``snn.export`` weight artifact
# (``CompiledSNN.save``) — a snapshot additionally serializes every open
# session's slot state, table and handshake clocks, so ``spidr.restore``
# resumes serving bit-exactly in a fresh process.
_SNAPSHOT_META_KEY = "spidr_session_snapshot"
SNAPSHOT_VERSION = 1


def _engine_config(target: DeployTarget) -> EngineConfig:
    """Lower a :class:`DeployTarget` onto the engine's execution config."""
    interpret = target.interpret
    if interpret is None:
        # The fused kernels' revisited-accumulator grid is only sequential
        # on TPU hardware; everywhere else they run interpreted.
        interpret = jax.default_backend() != "tpu"
    return EngineConfig(
        QuantSpec(target.weight_bits),
        # "reference" executes the jnp datapath through the unjitted
        # python-loop oracle (see CompiledSNN.run).
        backend="fused" if target.backend == "fused" else "jnp",
        interpret=bool(interpret),
        skip_empty=target.skip_empty,
        block=tuple(target.block),
        t_block=target.t_block,
    )


def _autotune_engine(base: SNNEngine, spec: SNNSpec, target: DeployTarget,
                     cfg: EngineConfig) -> SNNEngine:
    """Bake measured per-layer kernel configs into ``base``.

    Consults :func:`repro.kernels.autotune.autotune_layer` per weight
    layer (cached by shape+precision, optionally persisted via
    ``$SPIDR_AUTOTUNE_CACHE``) and attaches the winner as
    ``EngineLayer.kcfg``.  Every candidate is bit-exact, so tuning
    changes wall time only, never results.
    """
    from ..kernels.autotune import autotune_layer

    tracer = obs_trace.default_tracer()
    reg = obs_metrics.default_registry()
    t_sweep = time.perf_counter()
    shapes = iter(spec.layer_shapes())
    new_layers = []
    with tracer.span("autotune", cat="compile", network=spec.name):
        for li, el in enumerate(base.layers):
            if el.kind not in ("conv", "fc"):
                new_layers.append(el)
                continue
            sh = next(shapes)
            rows = sh.out_positions if el.kind == "conv" else 1
            with tracer.span("autotune.layer", cat="compile", layer=li,
                             kind=el.kind, rows=rows,
                             channels=sh.out_channels):
                winner = autotune_layer(
                    rows, sh.fan_in, sh.out_channels,
                    target.weight_bits, target.vmem_bits,
                    timesteps=min(spec.timesteps, 8),
                    sparsity=target.assumed_sparsity,
                    interpret=cfg.interpret, skip_empty=cfg.skip_empty)
            if reg:
                # Info-gauge: the chosen KernelConfig rides in the labels
                # (value is a constant 1, Prometheus "info" idiom).
                bm, bn, bk, tb = winner.kcfg
                reg.gauge(
                    "spidr_autotune_kcfg_info",
                    "Chosen per-layer kernel config (info gauge)",
                    labels={"network": spec.name, "layer": li,
                            "kind": el.kind, "block_m": bm, "block_n": bn,
                            "block_k": bk, "t_block": tb}).set(1.0)
            new_layers.append(dataclasses.replace(el, kcfg=winner.kcfg))
    if reg:
        reg.counter(
            "spidr_autotune_seconds_total",
            "Wall seconds spent in autotune sweeps").inc(
                time.perf_counter() - t_sweep)
        reg.counter(
            "spidr_autotune_layers_total",
            "Weight layers autotuned").inc(
                sum(1 for el in base.layers if el.kind in ("conv", "fc")))
    return dataclasses.replace(base, layers=tuple(new_layers))


@dataclasses.dataclass(frozen=True)
class VerifyReport:
    """Result of :meth:`CompiledSNN.verify`: the deployment's proof chain.

    ``reference_exact``    engine output == the unjitted pure-jnp
                           python-loop oracle on the same integers.
    ``single_core_exact``  compiled multi-core plan == the single-core
                           engine (None when the target is single-core).
    ``roundtrip``          QAT training-graph parity
                           (:class:`~repro.snn.export.RoundTrip`; None
                           when no float params are available).
    """

    exact: bool
    reference_exact: bool
    single_core_exact: Optional[bool] = None
    roundtrip: Optional[RoundTrip] = None

    def __bool__(self) -> bool:  # pragma: no cover - convenience
        return self.exact


class StreamSession:
    """Session handle over a bank of persistent-Vmem stream slots.

    Wraps an ``engine.streaming.StreamSessionManager``: ``capacity`` slots
    multiplexed into one fixed-shape jitted chunk step per tick.  The
    delivery contract is the manager's (every open slot delivers a chunk
    every tick; a short chunk ends its stream) — violations raise with the
    manager's diagnostics instead of corrupting state.

    Lifecycle contract (tested in ``tests/test_fleet.py``): the session is
    a context manager; :meth:`close` is idempotent — closing an already
    closed slot (or the whole session twice) is a no-op — while
    :meth:`open`/:meth:`step` on a closed session raise ``RuntimeError``.
    """

    def __init__(self, engine: SNNEngine, capacity: int, chunk_T: int,
                 collect_chunk_counts: bool = False, metrics=None,
                 tracer=None, device=None):
        self._manager = StreamSessionManager(
            engine, capacity=capacity, chunk_T=chunk_T, metrics=metrics,
            tracer=tracer, collect_chunk_counts=collect_chunk_counts,
            device=device)
        self._closed = False

    @property
    def capacity(self) -> int:
        return self._manager.capacity

    @property
    def chunk_T(self) -> int:
        return self._manager.chunk_T

    @property
    def occupancy(self) -> int:
        return self._manager.occupancy

    @property
    def active(self) -> tuple:
        """Per-slot open flags (index = slot id)."""
        return tuple(self._manager.active)

    @property
    def devices(self) -> frozenset:
        """The devices holding the session's resident neuron state — after
        a tick, the output of the jitted chunk step."""
        return frozenset(d for leaf in jax.tree.leaves(self._manager.state)
                         for d in leaf.devices())

    @property
    def state_nbytes(self) -> int:
        """Bytes of the resident neuron state: what :meth:`state_dict`
        copies to the host and a :meth:`mark` pins on the device."""
        return self._manager.state_nbytes

    @property
    def table_nbytes(self) -> int:
        """Bytes of the session table a :meth:`mark` copies on the host."""
        return self._manager.table_nbytes

    def mark(self):
        """An in-process rewind point (see ``StreamSessionManager.mark``):
        the resident device state by reference, the session table copied.
        No device-to-host transfer; not durable."""
        return self._manager.mark()

    def rewind(self, mark) -> None:
        """Return the session to a :meth:`mark`, bit-exactly."""
        self._manager.rewind(mark)

    def state_dict(self) -> dict:
        """The session's full durable state as a deterministic pure-numpy
        tree (see ``StreamSessionManager.state_dict``): every slot's
        integer engine state, the session table, and the resumable
        handshake clocks.  Fresh host copies — never aliases live state."""
        return self._manager.state_dict()

    def load_state_dict(self, d: dict) -> None:
        """Restore the session to a :meth:`state_dict` snapshot bit-exactly
        (the session must have matching capacity/engine geometry)."""
        self._manager.load_state_dict(d)

    @property
    def closed(self) -> bool:
        """True once the whole session was retired via no-arg :meth:`close`
        (or by leaving its ``with`` block)."""
        return self._closed

    def _require_open(self, what: str) -> None:
        if self._closed:
            raise RuntimeError(
                f"cannot {what} on a closed StreamSession — open a new "
                "session with CompiledSNN.open_stream()")

    def open(self) -> Optional[int]:
        """Allocate a slot for a new stream; None if the session is full."""
        self._require_open("open a stream")
        return self._manager.open()

    def step(self, chunks: dict) -> dict:
        """Advance every open slot by one chunk: ``{slot: (t, H, W, C)}``
        events in, ``{slot: SlotUpdate}`` incremental replies out."""
        self._require_open("step")
        return self._manager.step(chunks)

    def close(self, slot: Optional[int] = None) -> None:
        """Retire one stream slot — or, with no argument, the whole session.

        Idempotent by contract: closing a slot that is not open, or
        closing an already closed session, is a no-op (the double-close
        of a shared handle is not an error worth crashing a server for).
        A no-arg close retires every open slot and marks the session
        closed; subsequent :meth:`open`/:meth:`step` raise
        ``RuntimeError``.
        """
        if slot is None:
            for s, active in enumerate(self._manager.active):
                if active:
                    self._manager.close(s)
            self._closed = True
            return
        if self._closed or not self._manager.active[slot]:
            return
        self._manager.close(slot)

    def export_slot(self, slot: int) -> dict:
        """One live stream's durable state as a pure-numpy tree — feed to
        another session's :meth:`import_slot` to migrate the stream
        bit-exactly (see ``StreamSessionManager.export_slot``)."""
        self._require_open("export a slot")
        return self._manager.export_slot(slot)

    def import_slot(self, payload: dict, slot: Optional[int] = None) -> int:
        """Install a migrated stream's :meth:`export_slot` payload into a
        free slot (first free by default); returns the destination slot."""
        self._require_open("import a slot")
        return self._manager.import_slot(payload, slot)

    def iter_chunks(self, events, slot: Optional[int] = None):
        """Serve one whole stream through this session, yielding each
        chunk's :class:`SlotUpdate`.

        ``events`` is one stream's ``(T, H, W, C)`` frames; they are
        delivered ``chunk_T`` timesteps per tick.  With no ``slot`` the
        helper opens one (raising ``RuntimeError`` when the session is
        full) and closes it when the stream ends — including on early
        ``break``/error, since generator cleanup runs the ``finally``.
        Other live slots must keep delivering through their own ``step``
        calls as usual; this helper is the one-stream convenience path.
        """
        self._require_open("iterate a stream")
        events = np.asarray(events)
        own = slot is None
        if own:
            slot = self._manager.open()
            if slot is None:
                raise RuntimeError(
                    f"session is full ({self.capacity} slots live) — "
                    "close a stream or open a larger session")
        try:
            for lo in range(0, events.shape[0], self.chunk_T):
                yield self._manager.step(
                    {slot: events[lo:lo + self.chunk_T]})[slot]
        finally:
            if own and not self._closed and self._manager.active[slot]:
                self._manager.close(slot)

    def __enter__(self) -> "StreamSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class CompiledSNN:
    """A deployed SpiDR network: engine + schedule behind one lifecycle.

    Built by :func:`compile` / :func:`load`; owns the executable
    :class:`~repro.engine.SNNEngine` (single- or multi-core) and exposes
    the whole deployment lifecycle:

      ``run(events)``      whole-tensor inference over ``(T, B, H, W, C)``
      ``open_stream()``    persistent-Vmem streaming session
      ``cost(result)``     calibrated chip cycles/energy for a run
      ``save(path)``       persist the integer artifact (``spidr.load``
                           rebuilds the deployment from it)
      ``verify()``         round-trip parity proof

    Everything is bit-exact with the internal layers it fronts: the same
    spike trains, costs and checkpoints as hand-wiring ``build_engine`` /
    ``compile_network`` / ``compile_engine`` / ``run_chunk`` /
    ``StreamSessionManager`` / ``snn.export`` directly.
    """

    def __init__(self, spec: SNNSpec, target: DeployTarget,
                 engine: SNNEngine, base_engine: SNNEngine,
                 exported: Optional[ExportedNetwork] = None,
                 params=None):
        self.spec = spec
        self.target = target
        self.engine = engine
        self.exported = exported
        self.params = params
        self._base_engine = base_engine  # single-core engine (oracle)
        self._jit_run = None
        self._sessions: list = []       # every StreamSession opened here
        self._analysis: Optional["AnalysisReport"] = None

    # -- introspection -----------------------------------------------------
    @property
    def schedule(self):
        """The compiler's :class:`CoreSchedule` (None on single core)."""
        return self.engine.schedule

    @property
    def n_cores(self) -> int:
        return self.target.n_cores

    def report(self) -> "AnalysisReport":
        """The deployment's static-analysis report (``repro.analysis``).

        Overflow certificates plus schedule verification for *this*
        network at *this* precision and core count.  Populated by
        :func:`compile` unless it ran with ``check="off"``; computed
        lazily here otherwise — so the certificate is always available,
        the ``check`` mode only decides whether findings gate the build.
        """
        if self._analysis is None:
            from .. import analysis

            self._analysis = analysis.analyze_deployment(
                self.spec, self.target.qspec, self.schedule)
        return self._analysis

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (f"CompiledSNN({self.spec.name!r}, "
                f"{self.target.weight_bits}/{self.target.vmem_bits}-bit, "
                f"{self.target.n_cores} core(s), "
                f"backend={self.target.backend!r}, "
                f"{'exported' if self.exported is not None else 'per-tensor'}"
                " weights)")

    # -- whole-tensor inference --------------------------------------------
    def run(self, events) -> EngineOutput:
        """Run a whole ``(T, B, H, W, C)`` binary event stream.

        Returns the engine's :class:`~repro.engine.EngineOutput` (readout +
        per-timestep spike statistics) — pass it to :meth:`cost` to price
        the run on the calibrated chip models.
        """
        # Hot path: a facade dispatch must cost nothing next to the engine
        # (benchmarks/run.py facade_overhead gates it at <1% wall time).
        run_fn = self._jit_run
        if run_fn is not None and isinstance(events, jax.Array) \
                and events.ndim == 5:
            return run_fn(events)
        events = jnp.asarray(events)
        if events.ndim != 5:
            raise ValueError(
                f"expected events of shape (T, B, H, W, C); got "
                f"{events.shape} — a single stream needs a batch axis "
                "(events[:, None])")
        if self.target.backend == "reference":
            return run_reference(self.engine, events)
        if self._jit_run is None:
            self._jit_run = jax.jit(functools.partial(run_engine, self.engine))
        return self._jit_run(events)

    # -- streaming ---------------------------------------------------------
    def open_stream(self, capacity: Optional[int] = None,
                    chunk_T: Optional[int] = None,
                    collect_chunk_counts: bool = False, metrics=None,
                    tracer=None, device=None) -> StreamSession:
        """Open a persistent-Vmem streaming session.

        ``capacity`` / ``chunk_T`` default to the target's
        ``stream_capacity`` / ``chunk_T``.  A stream served through the
        session is bit-identical to a whole-stream :meth:`run` on that
        stream alone, whatever shares the batch.  (A ``"reference"``
        target streams through the jitted jnp datapath — same integers,
        same spikes.)

        ``collect_chunk_counts=True`` makes every ``SlotUpdate`` carry its
        chunk's per-layer input-spike counts, so a server can re-price a
        finished stream with ``collect_timeline=True`` and export its
        per-core pipeline timeline (``launch/serve.py --trace-out``).

        ``metrics`` / ``tracer``: session telemetry (``repro.obs``).
        ``None`` uses the process-wide defaults (disabled unless
        ``obs.enable_metrics()``/``enable_tracing()`` ran); pass a private
        ``MetricsRegistry``/``Tracer`` to isolate, or ``False`` to pin
        telemetry hard off for this session.

        ``device`` commits the session's resident state to one host
        device, so a fleet of sessions over the same deployment ticks on
        distinct devices (``spidr.serve`` replica placement).
        """
        capacity = self.target.stream_capacity if capacity is None \
            else capacity
        chunk_T = self.target.chunk_T if chunk_T is None else chunk_T
        _require_positive_int("capacity", capacity,
                              hint="concurrent persistent-Vmem stream slots")
        _require_positive_int("chunk_T", chunk_T,
                              hint="timesteps delivered per streaming tick")
        session = StreamSession(self.engine, capacity=capacity,
                                chunk_T=chunk_T, metrics=metrics,
                                tracer=tracer,
                                collect_chunk_counts=collect_chunk_counts,
                                device=device)
        self._sessions.append(session)
        return session

    @property
    def sessions(self) -> tuple:
        """Every :class:`StreamSession` opened on this deployment, in
        :meth:`open_stream` order — the set :meth:`snapshot` serializes."""
        return tuple(self._sessions)

    # -- chip cost ---------------------------------------------------------
    def cost(self, result=None, input_counts=None):
        """Price a run on the calibrated chip models.

        Pass the :class:`~repro.engine.EngineOutput` from :meth:`run` (or
        any object with per-timestep ``input_counts``), or a raw
        ``(T, n_weight_layers)`` array via ``input_counts``.  Returns an
        ``EngineCost`` (single core) or ``MulticoreCost`` (compiled plan,
        with per-core attribution and routing overhead).
        """
        counts = self._counts_of(result, input_counts)
        if self.schedule is not None:
            return estimate_multicore_cost(self.spec, self.schedule, counts)
        return estimate_cost(self.spec, self.target.qspec, counts)

    @staticmethod
    def _counts_of(result, input_counts) -> np.ndarray:
        if input_counts is None:
            if result is None or getattr(result, "input_counts", None) is None:
                raise ValueError(
                    "cost() needs spike statistics: pass the EngineOutput "
                    "from run() (with collect_counts on), or a raw "
                    "(T, n_weight_layers) array via input_counts=")
            input_counts = result.input_counts
        return np.asarray(input_counts)

    # -- telemetry ---------------------------------------------------------
    def metrics(self, fmt: str = "prometheus"):
        """Export the process-wide metrics registry (``repro.obs``).

        ``fmt="prometheus"`` returns the text exposition format,
        ``fmt="json"`` the JSON-friendly dict.  Empty unless metrics were
        enabled (``obs.enable_metrics()`` or ``serve.py --metrics-out``)
        before the instrumented paths ran.
        """
        reg = obs_metrics.default_registry()
        if fmt in ("prometheus", "prom", "text"):
            return reg.to_prometheus()
        if fmt == "json":
            return reg.to_dict()
        raise ValueError(
            f"unknown metrics format {fmt!r} — use 'prometheus' or 'json'")

    def pipeline_trace(self, result=None, input_counts=None, path=None,
                       label: str = "run", pid: int = 1) -> list:
        """Chrome-trace pipeline timeline of a run on the compiled plan.

        Prices the run's spike statistics through
        ``estimate_multicore_cost(..., collect_timeline=True)`` and
        renders the simulated per-core async-pipeline clocks (busy /
        AER-routing / idle intervals, one track per core) as Chrome-trace
        events — summed busy+routing durations equal
        ``MulticoreCost.busy_cycles`` exactly.  Returns the event list;
        ``path`` additionally writes a Perfetto-loadable JSON file.
        Multi-core targets only.
        """
        if self.schedule is None:
            raise ValueError(
                "pipeline_trace() renders the multi-core pipeline clocks — "
                "this deployment is single-core (target.n_cores == 1)")
        counts = self._counts_of(result, input_counts)
        cost = estimate_multicore_cost(self.spec, self.schedule, counts,
                                       collect_timeline=True)
        events = obs_timeline.multicore_timeline(cost, label=label, pid=pid)
        if path is not None:
            obs_timeline.write_chrome_trace(events, path)
        return events

    # -- performance model -------------------------------------------------
    def roofline(self, batch: int = 1, timesteps: Optional[int] = None,
                 nonzero_tile_fracs=None) -> dict:
        """Predicted wall-time bound for one chunk on this deployment.

        Prices the compiled engine's actual tiling (per-layer autotuned
        ``kcfg`` when present, else the target's ``block``/``t_block``)
        through :class:`repro.roofline.PerfModel`: bytes-moved + MACs-at-
        sparsity per weight layer, ``bound_us`` = summed max(compute,
        memory) bound.  ``nonzero_tile_fracs`` is a per-weight-layer list
        of nonzero spike-tile fractions (measure with
        ``kernels.spike_tile_bitmap``); default prices dense spikes.
        """
        from ..roofline.analysis import PerfModel

        kcfgs = [el.kcfg for el in self._base_engine.layers
                 if el.kind in ("conv", "fc")]
        cfg = self._base_engine.cfg
        return PerfModel().network_bound(
            self.spec, batch=batch, timesteps=timesteps,
            t_block=cfg.t_block, block=cfg.block,
            nonzero_tile_fracs=nonzero_tile_fracs,
            layer_kcfgs=kcfgs)

    # -- persistence -------------------------------------------------------
    def save(self, path, step: int = 0) -> None:
        """Persist the deployment's integer artifact under ``path``.

        Writes the standard ``snn.export`` checkpoint (atomic, validated
        on reload); ``spidr.load(path)`` rebuilds an equivalent
        :class:`CompiledSNN` from it, bit-exactly, at any target.
        """
        if self.exported is None:
            raise ValueError(
                "this CompiledSNN was compiled from float params with "
                "per-tensor scales, which the export checkpoint format "
                "does not represent — train/export first (snn.train.fit, "
                "then compile(exported, spec, target)) or deploy an "
                "ExportedNetwork to make save()/load() available")
        save_exported(Checkpointer(str(path)), step, self.exported,
                      spec=self.spec)

    def _layer_arrays(self) -> list:
        """The deployment's integer weights as plain numpy, one
        ``{"w_q", "w_scale", "thr_int"}`` per weight layer (None per pool).

        ``w_scale`` is widened to float64 so both provenances serialize
        losslessly: a per-tensor scale is a python float, a per-channel
        exported scale is float32 — either round-trips exactly.
        """
        out = []
        for el in self._base_engine.layers:
            if el.kind not in ("conv", "fc"):
                out.append(None)
                continue
            out.append({
                "w_q": np.asarray(el.w_q, np.int8),
                "w_scale": np.asarray(el.w_scale, np.float64),
                "thr_int": np.asarray(el.thr_int, np.int32),
            })
        return out

    def snapshot(self, path, step: int = 0, sessions=None,
                 extra: Optional[dict] = None) -> None:
        """Persist the complete live serving state under ``path``.

        One atomic, checksummed checkpoint step holding the deployment's
        integer weights plus every open streaming session's durable state
        (slot Vmems, session table, resumable handshake clocks — see
        ``StreamSessionManager.state_dict``).  ``spidr.restore(path)``
        rebuilds the deployment in a fresh process and resumes every
        stream bit-exactly: the same spikes, readouts and cumulative
        cycle/energy attribution as if serving was never interrupted.

        ``sessions`` defaults to every session opened via
        :meth:`open_stream`; ``extra`` is JSON-serializable caller
        bookkeeping (e.g. a server's stream-id/cursor table), returned by
        :func:`read_snapshot_meta`.
        """
        sessions = self.sessions if sessions is None else tuple(sessions)
        t0 = time.perf_counter()
        with obs_trace.default_tracer().span(
                "snapshot.save", cat="durability", path=str(path),
                sessions=len(sessions)):
            target_info = dataclasses.asdict(self.target)
            target_info["block"] = list(target_info["block"])
            info = {
                "version": SNAPSHOT_VERSION,
                "session_schema": SESSION_SCHEMA_VERSION,
                "provenance": ("exported" if self.exported is not None
                               else "per_tensor"),
                "target": target_info,
                "spec": _spec_info(self.spec),
                "sessions": [{"capacity": s.capacity, "chunk_T": s.chunk_T}
                             for s in sessions],
                "extra": extra or {},
            }
            tree = {"layers": self._layer_arrays(),
                    "sessions": [s.state_dict() for s in sessions]}
            Checkpointer(str(path)).save(
                step, tree, extra_meta={_SNAPSHOT_META_KEY: info})
        reg = obs_metrics.default_registry()
        if reg:
            reg.histogram(
                "spidr_snapshot_seconds",
                "CompiledSNN.snapshot wall duration",
                edges=obs_metrics.LATENCY_BUCKETS_S,
            ).observe(time.perf_counter() - t0)

    # -- the proof ---------------------------------------------------------
    def verify(self, events=None, params=None, batch: int = 2,
               seed: int = 0) -> VerifyReport:
        """Prove the deployment's round-trip parity on ``events``.

        Checks, all bit-exact (equal, not close): the engine against the
        unjitted pure-jnp python-loop oracle; a compiled multi-core plan
        against the single-core engine; and — when float params are
        available (``params`` here, or retained from :func:`compile`) —
        the deployed integers against the QAT training graph
        (``snn.export.verify_roundtrip``).  ``events`` defaults to a
        synthetic DVS batch matching the spec's head.
        """
        if events is None:
            from ..snn.data import make_flow_batch, make_gesture_batch

            make = (make_gesture_batch if self.spec.readout == "rate"
                    else make_flow_batch)
            events, _ = make(jax.random.PRNGKey(seed), batch=batch,
                             timesteps=self.spec.timesteps,
                             hw=self.spec.input_hw)
        events = jnp.asarray(events)
        out = self.run(events)
        ref = run_reference(self._base_engine, events)
        reference_exact = bool(
            (np.asarray(out.readout) == np.asarray(ref.readout)).all()
            and (np.asarray(out.spike_counts)
                 == np.asarray(ref.spike_counts)).all())
        single_core_exact = None
        if self.schedule is not None:
            single = run_engine(self._base_engine, events)
            single_core_exact = bool(
                (np.asarray(out.readout) == np.asarray(single.readout)).all()
                and (np.asarray(out.spike_counts)
                     == np.asarray(single.spike_counts)).all())
        roundtrip = None
        params = params if params is not None else self.params
        if self.exported is not None and params is not None:
            roundtrip = verify_roundtrip(params, self.spec, self.engine,
                                         events, self.exported,
                                         engine_out=out)
        exact = reference_exact \
            and single_core_exact is not False \
            and (roundtrip is None or roundtrip.exact)
        return VerifyReport(exact=exact, reference_exact=reference_exact,
                            single_core_exact=single_core_exact,
                            roundtrip=roundtrip)


def _apply_schedule(base: SNNEngine, spec: SNNSpec, target: DeployTarget,
                    cfg: EngineConfig) -> SNNEngine:
    """Bake the target's multi-core plan into ``base`` (identity on 1 core).

    Deterministic in (spec, target): the compiler's partition/place/
    schedule has no randomness, so a freshly compiled replica gets the
    same plan — a precondition for bit-exact multi-core session migration.
    """
    if target.n_cores <= 1:
        return base
    schedule = compile_network(
        spec, n_cores=target.n_cores, qspec=cfg.qspec,
        assumed_sparsity=target.assumed_sparsity,
        force_mode=target.force_mode,
        force_stationarity=target.stationarity)
    return compile_engine(base, schedule,
                          device_parallel=target.device_parallel)


def compile(network, params=None, target: Optional[DeployTarget] = None,
            *, spec: Optional[SNNSpec] = None,
            check: str = "warn") -> CompiledSNN:
    """Deploy a network onto a :class:`DeployTarget`.

    Two forms, one per quantization provenance:

      ``compile(spec, float_params, target)``
          quantize ``float_params`` into the integer engine with
          per-tensor scales (untrained / ad-hoc parameters — the legacy
          ``build_engine`` chain, bit-for-bit);

      ``compile(exported, spec, target)``
          deploy a trained :class:`~repro.snn.export.ExportedNetwork`
          (per-channel power-of-two scales — the legacy
          ``snn.export.deploy`` chain, bit-for-bit).  Optionally keep the
          trainer's float params for :meth:`CompiledSNN.verify` by passing
          ``compile(exported, float_params, target, spec=spec)``.

    ``target`` defaults to ``DeployTarget()`` (4/7-bit, single core, jnp
    backend).  ``target.n_cores > 1`` compiles the network across a core
    grid — bit-exact with single-core execution.

    ``check`` gates the build on deploy-time static analysis
    (``repro.analysis``: overflow certification + schedule
    verification).  ``"strict"`` raises
    :class:`~repro.analysis.AnalysisError` on any error-level finding,
    ``"warn"`` (the default) emits a ``RuntimeWarning``, ``"off"`` skips
    the analysis at compile time (``CompiledSNN.report()`` still
    computes it on demand).
    """
    if check not in CHECK_MODES:
        raise ValueError(
            f"check must be one of {CHECK_MODES}, got {check!r}")
    target = target or DeployTarget()
    with obs_trace.default_tracer().span(
            "spidr.compile", cat="compile", backend=target.backend,
            n_cores=target.n_cores, weight_bits=target.weight_bits):
        compiled = _compile(network, params, target, spec)
    if check != "off":
        from .. import analysis

        report = analysis.analyze_deployment(
            compiled.spec, target.qspec, compiled.schedule)
        compiled._analysis = report
        if report.errors:
            if check == "strict":
                raise analysis.AnalysisError(report)
            warnings.warn(
                f"static analysis found {len(report.errors)} violation(s) "
                f"in {report.subject} — see CompiledSNN.report() "
                "(compile with check='strict' to fail the build)",
                RuntimeWarning, stacklevel=2)
    return compiled


def _compile(network, params, target: DeployTarget,
             spec: Optional[SNNSpec]) -> CompiledSNN:
    cfg = _engine_config(target)

    def tuned(engine: SNNEngine) -> SNNEngine:
        if target.autotune and cfg.backend == "fused":
            return _autotune_engine(engine, spec, target, cfg)
        return engine

    if isinstance(network, ExportedNetwork):
        if spec is None and isinstance(params, SNNSpec):
            spec, params = params, None
        if spec is None:
            raise ValueError(
                "deploying an ExportedNetwork needs its SNNSpec: "
                "compile(exported, spec, target) or "
                "compile(exported, float_params, target, spec=spec)")
        if target.weight_bits != network.weight_bits:
            raise ValueError(
                f"target executes {target.weight_bits}-bit weights but the "
                f"network was exported at {network.weight_bits}-bit — "
                f"re-export, or deploy with DeployTarget(weight_bits="
                f"{network.weight_bits})")
        base = tuned(deploy(network, spec, cfg, n_cores=1))
        exported = network
    elif isinstance(network, SNNSpec):
        spec = network
        if params is None:
            raise ValueError(
                "compiling an SNNSpec needs its float params: "
                "compile(spec, params, target) — params from "
                "core.network.init_params or a snn.train fit; a trained "
                "integer artifact deploys via compile(exported, spec, "
                "target) instead")
        base = build_engine(spec, params, cfg, tune=tuned)
        exported = None
    else:
        raise TypeError(
            f"compile() takes an SNNSpec or an ExportedNetwork, got "
            f"{type(network).__name__} — build a spec with "
            "core.network.gesture_net/optical_flow_net (or a config's "
            "reduced()), or an exported network with snn.train + "
            "snn.export")
    with obs_trace.default_tracer().span(
            "compiler.schedule", cat="compile", n_cores=target.n_cores):
        engine = _apply_schedule(base, spec, target, cfg)
    return CompiledSNN(spec=spec, target=target, engine=engine,
                       base_engine=base, exported=exported, params=params)


def load(path, spec: Optional[SNNSpec] = None,
         target: Optional[DeployTarget] = None,
         step: Optional[int] = None) -> CompiledSNN:
    """Rebuild a deployment from a :meth:`CompiledSNN.save` checkpoint.

    Reads the standard ``snn.export`` artifact under ``path`` (any
    checkpoint written by ``save_exported`` loads too), validates it, and
    deploys it onto ``target``.  ``spec`` defaults to the paper network
    named in the checkpoint's metadata, restored to the event geometry
    (``input_hw``/``timesteps``) the artifact was saved at —
    ``CompiledSNN.save`` records it, so a save→load round trip rebuilds
    the deployment exactly.  Pass the spec explicitly for artifacts
    written by a bare legacy ``save_exported`` call at reduced geometry
    (without it, the paper network's full-size geometry is assumed).
    ``target`` defaults to the checkpoint's exported precision on one
    core.
    """
    ckpt = Checkpointer(str(path))
    if step is None:
        step = ckpt.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint steps under {ckpt.directory} — was the "
                "deployment saved with CompiledSNN.save (or "
                "snn.export.save_exported)?")
    if spec is None:
        from ..snn.export import read_export_meta
        from ..snn.train import spec_for

        info = read_export_meta(ckpt, step)
        name = info.get("name")
        try:
            spec = spec_for(name)
        except (ValueError, TypeError):
            raise ValueError(
                f"checkpoint step {step} names network {name!r}, which is "
                "not one of the paper's specs — pass the SNNSpec it was "
                "trained with: load(path, spec=...)") from None
        if "input_hw" in info:
            spec = dataclasses.replace(
                spec, input_hw=tuple(info["input_hw"]),
                timesteps=int(info.get("timesteps", spec.timesteps)))
    exported = load_exported(ckpt, spec, step)
    if target is None:
        target = DeployTarget(weight_bits=exported.weight_bits)
    return compile(exported, spec, target)


# ---------------------------------------------------------------------------
# Live-session snapshots: CompiledSNN.snapshot -> spidr.restore
# ---------------------------------------------------------------------------
def _spec_info(spec: SNNSpec) -> dict:
    """The spec geometry a snapshot pins (and restore re-validates)."""
    return {"name": spec.name, "input_hw": list(spec.input_hw),
            "in_channels": int(spec.in_channels),
            "timesteps": int(spec.timesteps), "readout": spec.readout,
            "n_layers": len(spec.layers)}


def _target_from_info(d: dict) -> DeployTarget:
    """Rebuild the snapshot's :class:`DeployTarget` from its JSON form."""
    kw = dict(d)
    kw["block"] = tuple(kw["block"])
    try:
        return DeployTarget(**kw)
    except TypeError as e:
        raise ValueError(
            f"the snapshot's DeployTarget does not match this build's "
            f"fields: {e} — re-snapshot with this version") from e


def _layer_arrays_template(spec: SNNSpec, per_channel: bool) -> list:
    """Structure template for the snapshot's weight tree.

    Shapes are derived from the spec alone (weights are not needed to
    *describe* the tree, only to fill it); ``per_channel`` mirrors the
    provenance recorded in the snapshot — exported networks carry (K,)
    scale/threshold vectors, per-tensor deployments carry scalars.
    """
    like = []
    for layer in spec.layers:
        if layer.kind not in ("conv", "fc"):
            like.append(None)
            continue
        f, k = layer.fan_in, layer.c_out
        sshape = (k,) if per_channel else ()
        like.append({"w_q": np.zeros((f, k), np.int8),
                     "w_scale": np.zeros(sshape, np.float64),
                     "thr_int": np.zeros(sshape, np.int32)})
    return like


def _session_state_template(spec: SNNSpec, capacity: int,
                            n_cores: int) -> dict:
    """Structure template matching ``StreamSessionManager.state_dict``.

    Built engine-free: Vmem shapes come from the network definition
    (``core.network._init_state``), so restore can describe the serialized
    tree before any engine exists — the weights themselves are part of the
    same checkpoint being restored.
    """
    from ..core.network import _init_spikes, _init_state

    vmem = [None if v is None else np.zeros(v.shape, np.int32)
            for v in _init_state(spec, capacity)]
    n_l = sum(1 for layer in spec.layers if layer.kind in ("conv", "fc"))
    engine_state = {
        "vmem": vmem,
        "readout_acc": np.zeros(spec.readout_shape(capacity), np.int32),
        "out_counts": np.zeros((n_l, capacity), np.int32),
        "in_counts": np.zeros((n_l, capacity), np.int32),
    }
    spikes = _init_spikes(spec, capacity)
    if spikes:
        engine_state["spikes"] = [np.zeros(z.shape, np.int8) for z in spikes]
    return {
        "schema": np.int64(SESSION_SCHEMA_VERSION),
        "engine_state": engine_state,
        "table": {
            "active": np.zeros(capacity, np.bool_),
            "ended": np.zeros(capacity, np.bool_),
            "timesteps": np.zeros(capacity, np.int64),
            "spikes": np.zeros(capacity, np.int64),
            "cycles": np.zeros(capacity, np.int64),
            "energy_uj": np.zeros(capacity, np.float64),
            "route_cycles": np.zeros((capacity, n_cores), np.int64),
            "core_cycles": np.zeros((capacity, n_cores), np.int64),
            "imbalance": np.ones(capacity, np.float64),
            "ticks": np.int64(0),
        },
        "clocks": [[PipelineState.zero().to_dict()
                    for _ in range(n_cores)] for _ in range(capacity)],
    }


def _compile_from_arrays(spec: SNNSpec, target: DeployTarget,
                         cfg: EngineConfig, arrays: list,
                         per_channel: bool, name: str) -> CompiledSNN:
    """Rebuild a deployment from a snapshot's serialized integer weights,
    through the same build chain the original took (``deploy`` for
    exported networks, direct :class:`EngineLayer` construction mirroring
    ``build_engine`` for per-tensor) — so the restored engine is
    bit-identical to the one snapshotted."""
    if per_channel:
        ex_layers = tuple(
            None if d is None else ExportedLayer(
                w_q=np.asarray(d["w_q"], np.int8),
                scale=np.asarray(d["w_scale"], np.float32),
                thr_int=np.asarray(d["thr_int"], np.int32))
            for d in arrays)
        exported = ExportedNetwork(name=name,
                                   weight_bits=target.weight_bits,
                                   layers=ex_layers)
        base = deploy(exported, spec, cfg, n_cores=1)
    else:
        exported = None
        layers = []
        for layer, d in zip(spec.layers, arrays):
            if layer.kind == "conv":
                layers.append(EngineLayer(
                    kind="conv", neuron=layer.conv.neuron,
                    w_q=jnp.asarray(np.asarray(d["w_q"], np.int8)),
                    w_scale=float(d["w_scale"]),
                    thr_int=int(d["thr_int"]),
                    kh=layer.conv.kh, kw=layer.conv.kw,
                    stride=layer.conv.stride, padding=layer.conv.padding))
            elif layer.kind == "fc":
                layers.append(EngineLayer(
                    kind="fc", neuron=layer.fc.neuron,
                    w_q=jnp.asarray(np.asarray(d["w_q"], np.int8)),
                    w_scale=float(d["w_scale"]),
                    thr_int=int(d["thr_int"])))
            elif layer.kind == "pool":
                layers.append(EngineLayer(kind="pool"))
            else:
                layers.append(EngineLayer(kind="adaptive_pool",
                                          target_hw=layer.target_hw))
        base = SNNEngine(spec=spec, cfg=cfg, layers=tuple(layers))
    if target.autotune and cfg.backend == "fused":
        base = _autotune_engine(base, spec, target, cfg)
    engine = _apply_schedule(base, spec, target, cfg)
    return CompiledSNN(spec=spec, target=target, engine=engine,
                       base_engine=base, exported=exported)


def read_snapshot_meta(path, step: Optional[int] = None) -> dict:
    """Read a :meth:`CompiledSNN.snapshot` artifact's metadata.

    No state is loaded — just the JSON record: format version, deployment
    target, spec geometry, session geometries, and the caller's ``extra``
    bookkeeping, plus the resolved ``step``.  Raises ``FileNotFoundError``
    when no step exists and ``ValueError`` when the checkpoint is not a
    session snapshot.
    """
    ckpt = Checkpointer(str(path))
    if step is None:
        step = ckpt.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no snapshot steps under {ckpt.directory} — was "
                "CompiledSNN.snapshot called?")
    with open(os.path.join(ckpt.directory,
                           f"step_{step:09d}", "meta.json")) as f:
        meta = json.load(f)
    info = meta.get(_SNAPSHOT_META_KEY)
    if info is None:
        raise ValueError(
            f"checkpoint step {step} under {ckpt.directory} is not a spidr "
            f"session snapshot (no {_SNAPSHOT_META_KEY!r} metadata) — "
            "weight artifacts from CompiledSNN.save load via spidr.load; "
            "snapshots come from CompiledSNN.snapshot")
    return dict(info, step=int(step))


def restore(path, spec: Optional[SNNSpec] = None,
            compiled: Optional[CompiledSNN] = None,
            step: Optional[int] = None) -> CompiledSNN:
    """Resume a serving deployment from a :meth:`CompiledSNN.snapshot`.

    Validates the checkpoint (crc32 per leaf, format/schema versions),
    rebuilds the deployment from its serialized integer weights onto the
    snapshot's :class:`DeployTarget`, reopens every serialized streaming
    session and reloads its slots, table and handshake clocks.  Every
    resumed stream then emits spikes, readouts and cumulative cycle/energy
    attribution byte-identical to the uninterrupted run — on any backend
    and core count the snapshot was taken at.

    ``spec`` is only needed for networks that are not one of the paper's
    named specs (the snapshot records the name + event geometry, like
    :func:`load`).  Pass ``compiled`` to migrate onto a prepared replica
    instead of rebuilding: it must be compiled for the identical target
    and carry byte-identical weights, or ``ValueError`` — a snapshot's
    session state is meaningless on any other deployment.
    """
    with obs_trace.default_tracer().span("snapshot.restore",
                                         cat="durability", path=str(path)):
        return _restore(path, spec, compiled, step)


def _restore(path, spec: Optional[SNNSpec],
             compiled: Optional[CompiledSNN],
             step: Optional[int]) -> CompiledSNN:
    info = read_snapshot_meta(path, step)
    step = info["step"]
    target = _target_from_info(info["target"])
    per_channel = info["provenance"] == "exported"
    sinfo = dict(info["spec"])
    if compiled is not None:
        spec = compiled.spec
    if spec is None:
        from ..snn.train import spec_for

        try:
            spec = spec_for(sinfo["name"])
        except (ValueError, TypeError):
            raise ValueError(
                f"snapshot names network {sinfo['name']!r}, which is not "
                "one of the paper's specs — pass the SNNSpec it was "
                "compiled with: restore(path, spec=...)") from None
        spec = dataclasses.replace(spec, input_hw=tuple(sinfo["input_hw"]),
                                   timesteps=int(sinfo["timesteps"]))
    if _spec_info(spec) != sinfo:
        raise ValueError(
            f"spec geometry {_spec_info(spec)} does not match the "
            f"snapshot's {sinfo} — restore onto the network the snapshot "
            "was taken on")
    cfg = _engine_config(target)
    like = {"layers": _layer_arrays_template(spec, per_channel),
            "sessions": [_session_state_template(spec, s["capacity"],
                                                 target.n_cores)
                         for s in info["sessions"]]}
    # host=True: the session tables carry int64/float64 accounting which
    # must round-trip exactly (32-bit jax would truncate it).
    tree = Checkpointer(str(path)).restore(step, like, host=True)
    if compiled is not None:
        if compiled.target != target:
            raise ValueError(
                f"snapshot was taken on {target}, but the prepared replica "
                f"is compiled for {compiled.target} — migration is only "
                "bit-exact onto the identical DeployTarget")
        mine = compiled._layer_arrays()
        for i, (a, b) in enumerate(zip(mine, tree["layers"])):
            same = (a is None) == (b is None) and (
                a is None or (np.array_equal(a["w_q"], b["w_q"])
                              and np.array_equal(a["w_scale"], b["w_scale"])
                              and np.array_equal(a["thr_int"],
                                                 b["thr_int"])))
            if not same:
                raise ValueError(
                    f"weight layer {i} of the prepared replica is not "
                    "byte-identical to the snapshot's — a session snapshot "
                    "only resumes on the deployment it was taken from")
    else:
        compiled = _compile_from_arrays(spec, target, cfg, tree["layers"],
                                        per_channel, sinfo["name"])
    for geo, sess_state in zip(info["sessions"], tree["sessions"]):
        session = compiled.open_stream(geo["capacity"], geo["chunk_T"])
        session.load_state_dict(sess_state)
    return compiled
