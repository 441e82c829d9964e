"""Batched serving driver: LM continuous batching + SNN event-stream serving.

Runs a real serving loop on host devices (reduced configs on CPU):
  python -m repro.launch.serve --arch qwen1.5-0.5b --reduced --requests 16
  python -m repro.launch.serve --snn gesture --requests 8
  python -m repro.launch.serve --snn optical-flow --requests 4 --jnp
  python -m repro.launch.serve --snn gesture --streaming --chunk-T 2
  python -m repro.launch.serve --snn gesture --n-cores 4 --jnp

The SNN path deploys through the unified facade (``repro.spidr``): one
``DeployTarget`` declares precision/cores/backend/chunking, and the
resulting ``CompiledSNN`` serves whole DVS event streams — requests are
batched up to a fixed capacity (shapes never change -> no recompilation),
each batch runs one fused scan-over-time inference, and the reply carries
the rate/Vmem readout plus the chip-cost estimate (cycles/energy) from the
calibrated models.

With ``--streaming`` the SNN path switches to *stateful* serving: each
request's events are delivered in chunks of ``--chunk-T`` timesteps, live
streams keep persistent per-slot Vmem between chunks
(``CompiledSNN.open_stream()``), newly arrived streams are admitted into
retired slots mid-flight (continuous batching over neuron state), and every
reply carries the incremental readout plus cumulative cycles/energy for
that stream alone.  Results are bit-identical to whole-stream serving.

The SNN serving loop itself lives in ``repro.serving`` behind the
``spidr.serve`` facade — this module is now a thin CLI over it
(``--replicas N`` spreads streams across a fleet of N engine replicas).
The old in-module server classes remain as deprecated shims below.

Design (scaled-down vLLM-style):
  * a request queue feeds a PREFILL worker (one request at a time — CPU
    demo; on a pod this is a separate prefill mesh),
  * decoded requests join the DECODE batch, stepped together; finished
    sequences retire and free their cache slot for the next waiter
    (continuous batching with slot reuse),
  * the decode step is one jit'd function over a fixed-capacity batch —
    shapes never change, so no recompilation during serving.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import get_config
from repro.models import model as M
from repro.models.transformer import init_decode_state
from repro.runtime.compile_cache import configure_compile_cache
from repro.serving import BatchWorker, StreamRequest, StreamWorker

# Structured logging (repro.obs.logs): ``main()`` calls
# ``obs.logging_setup(json_mode=args.log_json)`` — every record carries the
# current stream's request id (``rid=...`` in text mode, ``"request_id"``
# in --log-json mode) via a contextvar, replacing the old module-level
# ``logging.basicConfig``.
log = logging.getLogger("repro.serve")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    generated: list = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None


class Server:
    """Fixed-capacity continuous-batching server."""

    def __init__(self, cfg, params, capacity: int = 8, ctx_len: int = 256):
        self.cfg, self.params = cfg, params
        self.capacity, self.ctx_len = capacity, ctx_len
        self.decode_step = jax.jit(M.make_decode_step(cfg), donate_argnums=(1,))
        self.prefill = jax.jit(M.make_prefill_step(cfg))
        # Batched cache: slot i belongs to active request i (or empty).
        self.cache = init_decode_state(cfg, capacity, ctx_len)
        self.slots: list = [None] * capacity
        self.slot_len = np.zeros(capacity, np.int32)
        self.next_tok = np.zeros((capacity, 1), np.int32)
        self.waiting: list = []
        self.done: list = []

    def submit(self, req: Request):
        req.submitted_at = time.monotonic()
        self.waiting.append(req)

    def _admit(self):
        for i in range(self.capacity):
            if self.slots[i] is None and self.waiting:
                req = self.waiting.pop(0)
                # Prefill one request; copy its KV into slot i.
                logits, cache1 = self.prefill(
                    self.params, {"tokens": jnp.asarray(req.prompt[None, :])}
                )
                tok = int(jnp.argmax(logits[0]))
                req.generated.append(tok)
                req.first_token_at = time.monotonic()
                self._copy_into_slot(i, cache1, len(req.prompt))
                self.slots[i] = req
                self.slot_len[i] = len(req.prompt)
                self.next_tok[i, 0] = tok

    def _copy_into_slot(self, i, cache1, plen):
        def put(dst, src):
            if dst is None or not hasattr(dst, "ndim"):
                return dst
            if dst.ndim >= 2 and src is not None:
                # layer-stacked: (L, B=cap, ...) <- (L, 1, ...)
                pad = [(0, 0)] * src.ndim
                if dst.ndim == src.ndim and dst.shape[1] == self.capacity:
                    sl = [slice(None)] * dst.ndim
                    sl[1] = slice(i, i + 1)
                    upd = src
                    if src.shape[3:4] and dst.shape[3] != src.shape[3] and dst.ndim > 3:
                        # seq capacity differs: right-pad/truncate
                        tgt = dst.shape[3]
                        if src.shape[3] < tgt:
                            pad[3] = (0, tgt - src.shape[3])
                            upd = jnp.pad(src, pad)
                        else:
                            upd = src[:, :, :, :tgt]
                    return dst.at[tuple(sl)].set(upd.astype(dst.dtype))
                return dst
            return dst

        # dense/moe KV caches: prefill returns k/v as (L, B, Hkv, S, hd)
        for key in self.cache:
            if key == "len":
                continue
            src = cache1.get(key) if isinstance(cache1, dict) else None
            if src is None:
                continue
            if key in ("k", "v"):
                # cache1 seq dim = prompt len; place at [.., :plen, :]
                dst = self.cache[key]
                upd = src.astype(dst.dtype)
                self.cache[key] = jax.lax.dynamic_update_slice(
                    dst, upd, (0, i, 0, 0, 0)[: dst.ndim]
                )
            else:
                self.cache[key] = put(self.cache[key], src)

    def step(self):
        self._admit()
        active = [i for i in range(self.capacity) if self.slots[i] is not None]
        if not active:
            return False
        # One batched decode step for every active slot (idle slots ride along).
        self.cache["len"] = jnp.asarray(int(self.slot_len.max()), jnp.int32)
        logits, self.cache = self.decode_step(
            self.params, self.cache, {"tokens": jnp.asarray(self.next_tok)}
        )
        toks = np.asarray(jnp.argmax(logits, axis=-1))
        for i in active:
            req = self.slots[i]
            tok = int(toks[i])
            req.generated.append(tok)
            self.slot_len[i] += 1
            self.next_tok[i, 0] = tok
            if len(req.generated) >= req.max_new or self.slot_len[i] >= self.ctx_len - 1:
                req.done_at = time.monotonic()
                self.done.append(req)
                self.slots[i] = None  # free slot: continuous batching
                self.slot_len[i] = 0
        return True


# ---------------------------------------------------------------------------
# SNN event-stream serving (fused multi-timestep engine).
# ---------------------------------------------------------------------------
#: Deprecated alias -- the request object moved to ``repro.serving``.
SNNRequest = StreamRequest


def _warn_deprecated(old: str) -> None:
    warnings.warn(
        f"repro.launch.serve.{old} is deprecated; serve through "
        "spidr.serve(compiled, spidr.ServeConfig(...)) instead "
        "(see docs/serving.md)",
        DeprecationWarning, stacklevel=3)


class SNNServer(BatchWorker):
    """Deprecated shim: use ``spidr.serve(compiled, batch=True)``.

    The whole-stream batching loop now lives in
    :class:`repro.serving.BatchWorker`; this subclass only adds the
    ``DeprecationWarning``.
    """

    def __init__(self, compiled, capacity: int = 4):
        _warn_deprecated("SNNServer")
        super().__init__(compiled, capacity)


class StreamingSNNServer(StreamWorker):
    """Deprecated shim: use ``spidr.serve(compiled, spidr.ServeConfig(...))``.

    The stateful continuous-batching loop (persistent-Vmem slots,
    watchdog/rewind durability, snapshot/restore) now lives in
    :class:`repro.serving.StreamWorker`; this subclass only adds the
    ``DeprecationWarning``.  ``restore`` is inherited and returns this
    class, so drilled snapshots keep resuming through the old name.
    """

    def __init__(self, *args, **kwargs):
        _warn_deprecated("StreamingSNNServer")
        super().__init__(*args, **kwargs)


def serve_snn(args):
    from repro import spidr
    from repro.configs import spidr_gesture, spidr_optflow
    from repro.core.network import init_params
    from repro.snn.data import make_flow_batch, make_gesture_batch

    spec = (spidr_gesture.reduced() if args.snn == "gesture"
            else spidr_optflow.reduced())
    # Telemetry opt-in must precede spidr.compile so the compile spans land
    # in the same registry/trace as the serving loop.
    metrics_out = getattr(args, "metrics_out", None)
    metrics_every = getattr(args, "metrics_every", 0)
    trace_out = getattr(args, "trace_out", None)
    if metrics_out:
        obs.enable_metrics()
    if trace_out:
        obs.enable_tracing()
    params = init_params(jax.random.PRNGKey(0), spec)
    # One declarative target covers what used to be EngineConfig + the
    # compile_network/compile_engine hand-wiring: precision pair, backend
    # (interpret auto-selects off-TPU), core count, stream geometry.
    target = spidr.DeployTarget(
        weight_bits=args.weight_bits,
        backend="jnp" if args.jnp else "fused",
        n_cores=args.n_cores,
        chunk_T=args.chunk_T,
        stream_capacity=args.capacity,
    )
    compiled = spidr.compile(spec, params, target)

    if compiled.schedule is not None:
        log.info("compiled %s onto %d cores (%d channel-split layers)\n%s",
                 spec.name, args.n_cores, compiled.schedule.n_split_layers,
                 compiled.schedule.describe())

    make = make_gesture_batch if args.snn == "gesture" else make_flow_batch
    ev, _ = make(jax.random.PRNGKey(1), batch=args.requests,
                 timesteps=spec.timesteps, hw=spec.input_hw)

    # Per-stream pipeline timelines need per-chunk input counts, which only
    # exist on the multi-core (scheduled) deployment.
    want_timeline = bool(trace_out) and compiled.schedule is not None

    replicas = getattr(args, "replicas", 1)
    if args.streaming:
        fleet = spidr.serve(compiled, spidr.ServeConfig(
            n_replicas=replicas,
            capacity=args.capacity,
            chunk_T=args.chunk_T,
            max_queue=max(64, args.requests),
            watchdog_s=getattr(args, "watchdog_s", None),
            snapshot_dir=getattr(args, "snapshot_dir", None),
            snapshot_every=getattr(args, "snapshot_every", 0),
            collect_chunk_counts=want_timeline))
        for r in range(args.requests):
            fleet.submit(np.asarray(ev[:, r]), rid=r)
        t0 = time.monotonic()
        ticks = 0
        while fleet.step():
            ticks += 1
            if metrics_out and metrics_every and ticks % metrics_every == 0:
                obs.default_registry().write(metrics_out)
        dt = time.monotonic() - t0
        done = fleet.done
        lat = [r.done_at - r.submitted_at for r in done]
        ttfr = [r.first_reply_at - r.submitted_at for r in done]
        log.info(
            "streamed %d %s streams (%d timesteps, chunk_T=%d) over %d "
            "replica(s) in %.2fs (%.1f streams/s, %d fleet ticks); "
            "first-reply p50 %.3fs; latency p50 %.3fs; backend=%s",
            len(done), args.snn, spec.timesteps, args.chunk_T,
            fleet.n_replicas, dt, len(done) / dt, ticks,
            float(np.median(ttfr)), float(np.median(lat)),
            compiled.engine.cfg.backend,
        )
        cyc = [r.cycles for r in done]
        uj = [r.energy_uj for r in done]
        log.info(
            "chip estimate/stream (cumulative): %.0f cycles p50, %.1f uJ p50",
            float(np.median(cyc)), float(np.median(uj)),
        )
        _export_telemetry(compiled, metrics_out, trace_out,
                          [(r.rid, r.input_counts) for r in done]
                          if want_timeline else [])
        fleet.shutdown()
        return fleet

    fleet = spidr.serve(compiled, spidr.ServeConfig(
        n_replicas=replicas, capacity=args.capacity, batch=True,
        max_queue=max(64, args.requests)))
    for r in range(args.requests):
        fleet.submit(np.asarray(ev[:, r]), rid=r)

    t0 = time.monotonic()
    fleet.drain()
    dt = time.monotonic() - t0
    done = fleet.done
    lat = [r.done_at - r.submitted_at for r in done]
    total_counts = None
    batches = 0
    for w in fleet.workers:
        batches += w.batches
        if w.total_input_counts is not None:
            total_counts = (w.total_input_counts if total_counts is None
                            else total_counts + w.total_input_counts)
    mean_counts = total_counts / max(len(done), 1)
    cost = compiled.cost(input_counts=mean_counts)
    log.info(
        "served %d %s streams (%d timesteps each) over %d replica(s) in "
        "%.2fs (%.1f streams/s, %d batches); latency p50 %.3fs; backend=%s",
        len(done), args.snn, spec.timesteps, fleet.n_replicas, dt,
        len(done) / dt, batches, float(np.median(lat)),
        compiled.engine.cfg.backend,
    )
    if compiled.schedule is None:
        log.info(
            "chip estimate/stream: %.2f ms @%dMHz, %.1f uJ, sparsity "
            "%.1f%%, async speedup %.2fx",
            cost.latency_ms, 50, cost.energy_uj, 100 * cost.mean_sparsity,
            cost.async_speedup,
        )
    else:
        log.info(
            "multi-core attribution/stream: makespan %d cycles, per-core "
            "busy %s, routing %s, load imbalance %.2fx, energy %.1f uJ "
            "(%.2f uJ routing)",
            cost.makespan_cycles, cost.busy_cycles.tolist(),
            cost.routing_cycles.tolist(), cost.load_imbalance,
            cost.energy_uj, cost.routing_energy_uj,
        )
    _export_telemetry(compiled, metrics_out, trace_out,
                      [("batch-mean", mean_counts)] if want_timeline else [])
    fleet.shutdown()
    return fleet


def _export_telemetry(compiled, metrics_out, trace_out, stream_counts):
    """Final metrics dump + Chrome-trace export for the serving run.

    ``stream_counts``: (label, per-timestep input counts) pairs — each is
    re-priced through the multi-core pipeline model and merged into the
    trace as its own process row (pid 100+i), so Perfetto shows the host
    spans and every stream's per-core busy/routing/idle clocks side by
    side.
    """
    if metrics_out:
        obs.default_registry().write(metrics_out)
        log.info("metrics written to %s", metrics_out)
    if not trace_out:
        return
    extra = []
    for i, (label, counts) in enumerate(stream_counts):
        if counts is None:
            continue
        extra.extend(compiled.pipeline_trace(
            input_counts=counts, label=f"stream {label}", pid=100 + i))
    obs.default_tracer().export(trace_out, extra_events=extra)
    log.info("chrome trace written to %s (%d pipeline-timeline events)",
             trace_out, len(extra))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--capacity", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--snn", choices=["gesture", "optical-flow"], default=None,
                    help="serve DVS event streams through the SNN engine "
                         "instead of the LM decode path")
    ap.add_argument("--weight-bits", type=int, default=4, choices=[4, 6, 8])
    ap.add_argument("--jnp", action="store_true",
                    help="SNN path: pure-jnp backend instead of Pallas")
    ap.add_argument("--streaming", action="store_true",
                    help="SNN path: stateful streaming serving — events "
                         "arrive in chunks, Vmem persists per slot between "
                         "chunks, replies are incremental")
    ap.add_argument("--chunk-T", type=int, default=2, dest="chunk_T",
                    help="timesteps per delivered chunk in --streaming mode")
    ap.add_argument("--replicas", type=int, default=1,
                    help="SNN path: serve through a fleet of N engine "
                         "replicas (spidr.serve) — streams are scheduled "
                         "across them")
    ap.add_argument("--watchdog-s", type=float, default=None,
                    dest="watchdog_s",
                    help="--streaming: per-tick watchdog deadline; a hung "
                         "tick rewinds to the last completed tick and "
                         "replays")
    ap.add_argument("--snapshot-dir", default=None, dest="snapshot_dir",
                    help="--streaming: persist the full serving state here "
                         "(weights + live sessions + cursors) for "
                         "zero-downtime restore")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    dest="snapshot_every",
                    help="--streaming: snapshot every N ticks (0 = never)")
    ap.add_argument("--n-cores", type=int, default=1, dest="n_cores",
                    help="SNN path: compile the network across a grid of N "
                         "SpiDR cores (repro.compiler) — the one-core "
                         "outputs, per-core cost attribution")
    ap.add_argument("--metrics-out", default=None, dest="metrics_out",
                    help="enable telemetry and write the final metrics dump "
                         "here (.json -> JSON, else Prometheus text)")
    ap.add_argument("--metrics-every", type=int, default=0,
                    dest="metrics_every",
                    help="--streaming: also rewrite --metrics-out every N "
                         "ticks (0 = only at the end)")
    ap.add_argument("--trace-out", default=None, dest="trace_out",
                    help="enable span tracing and export a Chrome-trace/"
                         "Perfetto JSON (compile + serving spans; "
                         "multi-core runs add per-stream pipeline timelines)")
    ap.add_argument("--log-json", action="store_true", dest="log_json",
                    help="emit one JSON object per log record instead of "
                         "text (each record carries the stream request id)")
    args = ap.parse_args()

    obs.logging_setup(json_mode=args.log_json)
    configure_compile_cache()

    if args.snn:
        serve_snn(args)
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    server = Server(cfg, params, capacity=args.capacity, ctx_len=64)

    rng = np.random.default_rng(0)
    for r in range(args.requests):
        server.submit(Request(
            rid=r,
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
            max_new=args.max_new,
        ))
    t0 = time.monotonic()
    steps = 0
    while server.step():
        steps += 1
    dt = time.monotonic() - t0
    lat = [r.done_at - r.submitted_at for r in server.done]
    ttft = [r.first_token_at - r.submitted_at for r in server.done]
    toks = sum(len(r.generated) for r in server.done)
    log.info(
        "served %d requests, %d tokens in %.2fs (%.1f tok/s); "
        "TTFT p50 %.3fs; latency p50 %.3fs; decode steps %d",
        len(server.done), toks, dt, toks / dt,
        float(np.median(ttft)), float(np.median(lat)), steps,
    )


if __name__ == "__main__":
    main()
