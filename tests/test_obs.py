"""Observability stack: metrics registry, span tracer, pipeline timeline.

The contracts under test (docs/observability.md):

* metric primitives behave (bucket edges pinned, Prometheus/JSON export,
  kind conflicts rejected);
* session metrics are *chunking-invariant* — the cumulative stream
  counters read identically whether a stream was served 1, 3 or T
  timesteps per tick;
* telemetry-disabled serving is bit-exact with telemetry enabled (the
  hooks only read engine state) and the disabled default registry is
  inert;
* traces are schema-valid Chrome-trace JSON with monotonic timestamps;
* the pipeline-timeline export conserves cycles exactly: per core,
  summed busy+routing durations equal ``MulticoreCost.busy_cycles``;
* the serving/durability layers record their counters (admissions,
  rejections, watchdog firings, rewinds) and ``benchmarks/run.py``'s
  ``meta`` key rides through ``tools/check_bench.py`` unseen.
"""
import argparse
import io
import json
import logging
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, spidr
from repro.configs import lif_firenet, spidr_gesture
from repro.core.network import init_params
from repro.obs.metrics import FRACTION_BUCKETS, LATENCY_BUCKETS_S


@pytest.fixture(autouse=True)
def _isolate_obs_defaults():
    """Each test gets fresh (disabled) process-wide defaults."""
    prev_reg, prev_tr = obs.default_registry(), obs.default_tracer()
    obs.set_default_registry(obs.MetricsRegistry(enabled=False))
    obs.set_default_tracer(obs.Tracer(enabled=False))
    yield
    obs.set_default_registry(prev_reg)
    obs.set_default_tracer(prev_tr)


def _compile(n_cores=1, timesteps=6, hw=(16, 16)):
    spec = spidr_gesture.reduced(hw=hw, timesteps=timesteps)
    params = init_params(jax.random.PRNGKey(0), spec)
    return spidr.compile(
        spec, params, spidr.DeployTarget(backend="jnp", n_cores=n_cores))


@pytest.fixture(scope="module")
def compiled1():
    return _compile(n_cores=1)


@pytest.fixture(scope="module")
def compiled4():
    return _compile(n_cores=4, timesteps=2)


def _stream(t=6, hw=(16, 16), seed=0, thresh=0.9):
    rng = np.random.default_rng(seed)
    return (rng.random((t,) + hw + (2,)) > thresh).astype(np.float32)


# ---------------------------------------------------------------------------
# Metric primitives.
# ---------------------------------------------------------------------------
class TestMetricsRegistry:
    def test_bucket_edges_are_pinned(self):
        # Dashboards and recorded baselines depend on these exact edges —
        # changing them is a breaking change, not a tweak.
        assert FRACTION_BUCKETS == (0.01, 0.05, 0.10, 0.25, 0.50, 0.75,
                                    0.90, 0.95, 0.99, 1.0)
        assert LATENCY_BUCKETS_S == (0.0005, 0.001, 0.0025, 0.005, 0.01,
                                     0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                                     2.5, 5.0, 10.0)

    def test_counter_gauge_histogram(self):
        reg = obs.MetricsRegistry(enabled=True)
        c = reg.counter("c_total", "a counter")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("g", "a gauge")
        g.set(7)
        g.dec(3)
        assert g.value == 4
        h = reg.histogram("h", "a histogram", edges=(1.0, 2.0))
        for v in (0.5, 1.5, 99.0):
            h.observe(v)
        assert list(h.bucket_counts) == [1, 1, 1]  # +Inf overflow bucket
        assert h.count == 3 and h.total == 101.0
        assert list(h.cumulative()) == [1, 2, 3]

    def test_kind_conflict_rejected(self):
        reg = obs.MetricsRegistry(enabled=True)
        reg.counter("x", "as counter")
        with pytest.raises(ValueError, match="x"):
            reg.gauge("x", "as gauge")

    def test_histogram_edges_must_ascend(self):
        reg = obs.MetricsRegistry(enabled=True)
        with pytest.raises(ValueError):
            reg.histogram("bad", "edges", edges=(2.0, 1.0))

    def test_prometheus_text_format(self):
        reg = obs.MetricsRegistry(enabled=True)
        reg.counter("req_total", "requests", labels={"slot": 0}).inc(5)
        reg.histogram("lat", "latency", edges=(0.1, 1.0)).observe(0.05)
        text = reg.to_prometheus()
        assert "# HELP req_total requests" in text
        assert "# TYPE req_total counter" in text
        assert 'req_total{slot="0"} 5' in text
        assert 'lat_bucket{le="0.1"} 1' in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_sum 0.05" in text and "lat_count 1" in text

    def test_write_picks_format_from_suffix(self, tmp_path):
        reg = obs.MetricsRegistry(enabled=True)
        reg.counter("n_total", "n").inc()
        as_json = json.loads(reg.write(tmp_path / "m.json").read_text())
        assert as_json["n_total"][0]["value"] == 1.0
        as_prom = reg.write(tmp_path / "m.prom").read_text()
        assert "n_total 1" in as_prom

    def test_registry_truthiness_is_the_enable_switch(self):
        # Instrumentation sites guard with `if reg:` — a disabled registry
        # costs one __bool__ per site and nothing else.
        assert not obs.MetricsRegistry(enabled=False)
        assert obs.MetricsRegistry(enabled=True)
        assert not obs.default_registry()  # fixture default: disabled


# ---------------------------------------------------------------------------
# Session metrics through the facade.
# ---------------------------------------------------------------------------
def _serve_stream(compiled, stream, chunk_T, metrics=None, tracer=None):
    session = compiled.open_stream(capacity=2, chunk_T=chunk_T,
                                   metrics=metrics, tracer=tracer)
    slot = session.open()
    update = None
    for start in range(0, stream.shape[0], chunk_T):
        update = session.step({slot: stream[start:start + chunk_T]})[slot]
    session.close(slot)
    return update


class TestSessionMetrics:
    def test_chunking_invariant_counters(self, compiled1):
        """Cumulative stream counters are identical at chunk_T 1, 3 and T."""
        stream = _stream(t=6)
        dumps = []
        for chunk_T in (1, 3, 6):
            reg = obs.MetricsRegistry(enabled=True)
            _serve_stream(compiled1, stream, chunk_T, metrics=reg)
            dumps.append(reg.to_dict())
        invariant = ("spidr_stream_timesteps_total",
                     "spidr_stream_input_spikes_total",
                     "spidr_stream_output_spikes_total",
                     "spidr_stream_cycles_total")
        for name in invariant:
            vals = [d[name][0]["value"] for d in dumps]
            assert vals[0] == vals[1] == vals[2], (name, vals)
        uj = [d["spidr_stream_energy_uj_total"][0]["value"] for d in dumps]
        assert uj[1] == pytest.approx(uj[0], rel=1e-9)
        assert uj[2] == pytest.approx(uj[0], rel=1e-9)
        # Tick count is chunking-DEPENDENT by design: 6, 2 and 1 ticks.
        ticks = [d["spidr_session_ticks_total"][0]["value"] for d in dumps]
        assert ticks == [6.0, 2.0, 1.0]

    def test_disabled_mode_bit_exact(self, compiled1):
        """Telemetry on vs pinned-off: identical readout/cycles/energy."""
        stream = _stream(t=6, seed=3)
        reg, tr = obs.MetricsRegistry(enabled=True), obs.Tracer()
        on = _serve_stream(compiled1, stream, 3, metrics=reg, tracer=tr)
        off = _serve_stream(compiled1, stream, 3, metrics=False, tracer=False)
        np.testing.assert_array_equal(np.asarray(on.readout),
                                      np.asarray(off.readout))
        assert (on.cycles, on.energy_uj) == (off.cycles, off.energy_uj)

    def test_sparsity_histogram_and_occupancy(self, compiled1):
        reg = obs.MetricsRegistry(enabled=True)
        session = compiled1.open_stream(capacity=2, chunk_T=3, metrics=reg)
        slot = session.open()
        session.step({slot: _stream(t=3, thresh=0.95)})
        d = reg.to_dict()
        h = d["spidr_chunk_sparsity"][0]
        assert tuple(h["buckets"]["edges"]) == FRACTION_BUCKETS
        assert h["count"] == 1
        assert d["spidr_session_occupancy"][0]["value"] == 1.0
        assert d["spidr_chunk_nonzero_tile_frac"][0]["count"] == 1

    def test_compiled_metrics_scrape(self, compiled1):
        obs.enable_metrics()
        session = compiled1.open_stream(capacity=2, chunk_T=3)
        slot = session.open()
        session.step({slot: _stream(t=3)})
        assert "spidr_session_ticks_total 1" in compiled1.metrics()
        as_json = compiled1.metrics(fmt="json")
        assert as_json["spidr_session_ticks_total"][0]["value"] == 1.0
        with pytest.raises(ValueError):
            compiled1.metrics(fmt="xml")


# ---------------------------------------------------------------------------
# Span tracer.
# ---------------------------------------------------------------------------
class TestTracer:
    def test_chrome_trace_schema_and_monotonic_ts(self, tmp_path):
        tr = obs.Tracer()
        with tr.span("outer", cat="t", k=1):
            with tr.span("inner", cat="t"):
                pass
        tr.instant("tick")
        path = tmp_path / "trace.json"
        tr.export(path)
        doc = json.loads(path.read_text())
        assert doc["displayTimeUnit"] == "ms"
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in spans} == {"outer", "inner"}
        for e in spans:
            assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
            assert e["dur"] >= 0
        ts = [e["ts"] for e in spans]
        assert ts == sorted(ts)
        # Export sorts by open time, so the enclosing span leads even
        # though it closed last.
        assert spans[0]["name"] == "outer"
        assert any(e["ph"] == "i" and e["name"] == "tick"
                   for e in doc["traceEvents"])
        assert any(e["ph"] == "M" for e in doc["traceEvents"])

    def test_span_args_recorded(self):
        tr = obs.Tracer()
        with tr.span("s", cat="c", layer=3, kind="conv"):
            pass
        (ev,) = [e for e in tr.to_chrome()["traceEvents"] if e["ph"] == "X"]
        assert ev["args"] == {"layer": 3, "kind": "conv"}

    @pytest.mark.parametrize("enabled", [True, False])
    def test_span_args_set_at_the_end_are_recorded(self, enabled):
        tr = obs.Tracer(enabled=enabled)
        with tr.span("s", layer=3) as args:
            args["pad_w"] = 6
        spans = [e for e in tr.to_chrome()["traceEvents"] if e["ph"] == "X"]
        assert [e["args"] for e in spans] == (
            [{"layer": 3, "pad_w": 6}] if enabled else [])

    def test_disabled_tracer_records_nothing(self):
        tr = obs.Tracer(enabled=False)
        assert not tr
        with tr.span("s"):
            pass
        assert [e for e in tr.to_chrome()["traceEvents"]
                if e["ph"] == "X"] == []

    def test_max_events_drops_and_counts(self):
        tr = obs.Tracer(max_events=2)
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        assert len([e for e in tr.to_chrome()["traceEvents"]
                    if e["ph"] == "X"]) == 2
        assert tr.dropped_events == 3

    def test_session_tracing_via_facade(self, compiled1):
        tr = obs.Tracer()
        _serve_stream(compiled1, _stream(t=6), 3, tracer=tr)
        spans = [e for e in tr.to_chrome()["traceEvents"] if e["ph"] == "X"]
        tick = ["run_chunk", "session.frame", "session.upload",
                "session.dispatch", "session.fetch", "session.price"]
        assert [e["name"] for e in spans] == tick + tick + ["session.close"]
        assert all(e["cat"] == "session" for e in spans)
        # Each tick's phases nest in its run_chunk and share its tick.
        for parent, kids in ((spans[0], spans[1:6]), (spans[6], spans[7:12])):
            end = parent["ts"] + parent["dur"]
            assert all(parent["ts"] <= k["ts"] and k["ts"] + k["dur"] <= end
                       for k in kids)
            assert {k["args"]["tick"] for k in kids} == {
                parent["args"]["tick"]}
        assert spans[1]["args"]["bytes"] == 4 * 3 * 2 * 16 * 16 * 2
        assert spans[4]["args"]["bytes"] > 0

    def test_compile_spans_on_default_tracer(self):
        obs.enable_tracing()
        _compile(n_cores=1, timesteps=2)
        names = {e["name"] for e in
                 obs.default_tracer().to_chrome()["traceEvents"]
                 if e["ph"] == "X"}
        assert {"spidr.compile", "engine.build"} <= names


def _profiled(tmp_path, fn):
    """Run ``fn`` under the JAX profiler; the host line's spans, by name."""
    import glob
    import gzip

    jax.profiler.start_trace(str(tmp_path), create_perfetto_trace=True)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "perfetto_trace.json.gz"),
                        recursive=True)
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    spans = {}
    for e in events:
        if e.get("ph") == "X":
            spans.setdefault(e["name"], []).append(e)
    return spans


def _inside(kid, parent) -> bool:
    return (parent["ts"] <= kid["ts"]
            and kid["ts"] + kid["dur"] <= parent["ts"] + parent["dur"])


PROGRAM_SPANS = {"fleet.step", "fleet.place", "worker.mark", "serve.tick",
                 "run_chunk", "session.frame", "session.upload",
                 "session.dispatch", "session.fetch", "session.price",
                 "session.close"}


class TestProfilerSpans:
    """An enabled tracer also writes its spans into an active
    ``jax.profiler`` trace, on the device trace's clock."""

    def _serve(self, compiled):
        fleet = spidr.serve(compiled, capacity=2, chunk_T=3, mode="sync")
        for rid in range(2):
            fleet.submit(_stream(t=6, seed=rid), rid=rid)
        fleet.drain()
        fleet.shutdown()

    def test_enabled_tracer_nests_program_spans_in_the_profile(
            self, compiled1, tmp_path):
        obs.enable_tracing()
        spans = _profiled(tmp_path, lambda: self._serve(compiled1))
        assert PROGRAM_SPANS <= set(spans)
        steps = spans["fleet.step"]
        # The worker marks its first rewind point when it is built.
        spans["worker.mark"] = sorted(spans["worker.mark"],
                                      key=lambda e: e["ts"])[1:]
        for name, parent in (("fleet.place", "fleet.step"),
                             ("worker.mark", "fleet.step"),
                             ("serve.tick", "fleet.step"),
                             ("run_chunk", "serve.tick"),
                             ("session.close", "serve.tick"),
                             ("session.fetch", "run_chunk"),
                             ("session.price", "run_chunk")):
            for kid in spans[name]:
                assert any(_inside(kid, p) for p in spans[parent]), name
        # Arguments ride along: every tick's phases carry its tick, the
        # byte counts are those of the work inside.
        fetch = spans["session.fetch"]
        chunks = spans["run_chunk"]
        assert [f["args"]["tick"] for f in fetch] == [
            c["args"]["tick"] for c in chunks] == ["0", "1"]
        assert all(int(f["args"]["bytes"]) > 0 for f in fetch)
        assert all("queued" in s["args"] for s in steps)
        # A slot retired in a tick is traced under that tick.
        assert {c["args"]["tick"] for c in spans["session.close"]} == {"1"}
        # The rewind point copies the session table and pins the device
        # state by reference.
        marks = spans["worker.mark"]
        pinned = compiled1.open_stream(capacity=2, chunk_T=3).state_nbytes
        assert all(0 < int(m["args"]["bytes"]) < int(m["args"]["pinned"])
                   for m in marks)
        assert {int(m["args"]["pinned"]) for m in marks} == {pinned}
        assert {"spidr.compile", "engine.build"}.isdisjoint(spans)

    def test_disabled_tracer_writes_nothing_into_the_profile(
            self, compiled1, tmp_path):
        spans = _profiled(tmp_path, lambda: self._serve(compiled1))
        assert PROGRAM_SPANS.isdisjoint(spans)

    def test_disabled_span_is_the_shared_null_span(self):
        from repro.obs.trace import _NULL_SPAN

        assert obs.Tracer(enabled=False).span("s", k=1) is _NULL_SPAN

    def test_obs_imports_and_traces_without_jax(self):
        code = (
            "import sys, types\n"
            "sys.modules['jax'] = None\n"
            "pkg = types.ModuleType('repro'); pkg.__path__ = ['src/repro']\n"
            "sys.modules['repro'] = pkg\n"
            "import repro.obs as obs\n"
            "t = obs.Tracer()\n"
            "with t.span('s', k=1):\n"
            "    pass\n"
            "assert [e['name'] for e in t.events] == ['s']\n")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr

    def test_tracing_leaves_every_answer_bit_identical(self, compiled1):
        stream = _stream(t=6, seed=3)
        off = _serve_stream(compiled1, stream, 2,
                            tracer=obs.Tracer(enabled=False))
        on = _serve_stream(compiled1, stream, 2, tracer=obs.Tracer())
        np.testing.assert_array_equal(on.readout, off.readout)
        assert (on.cycles, on.energy_uj, on.spikes) == (
            off.cycles, off.energy_uj, off.spikes)

    @pytest.mark.parametrize("backend", ["jnp", "fused"])
    def test_chunk_step_names_every_weight_layer(self, backend):
        from repro.engine.inference import init_state, run_chunk

        spec = spidr_gesture.reduced(hw=(16, 16), timesteps=2)
        compiled = spidr.compile(
            spec, init_params(jax.random.PRNGKey(0), spec),
            spidr.DeployTarget(backend=backend))
        engine = compiled.engine
        step = jax.jit(lambda st, ev: run_chunk(
            engine, st, ev, collect_counts=True, collect_readouts=True))
        text = step.lower(init_state(engine, 2),
                          jnp.zeros((2, 2, 16, 16, 2))).as_text(
                              debug_info=True)
        for scope in ("spidr.L0.kernel", "spidr.L0.patches",
                      "spidr.L0.counts", "spidr.pool0", "spidr.readout"):
            assert scope in text, scope

    @pytest.mark.parametrize("net", ["firenet", "gesture"])
    def test_chunk_step_names_the_recurrent_join(self, net):
        from repro.engine.inference import init_state, run_chunk

        spec = (lif_firenet.reduced(hw=(8, 10), timesteps=2)
                if net == "firenet"
                else spidr_gesture.reduced(hw=(16, 16), timesteps=2))
        compiled = spidr.compile(
            spec, init_params(jax.random.PRNGKey(0), spec),
            spidr.DeployTarget(backend="jnp"))
        engine = compiled.engine
        step = jax.jit(lambda st, ev: run_chunk(
            engine, st, ev, collect_counts=True, collect_readouts=True))
        text = step.lower(init_state(engine, 2), jnp.zeros(
            (2, 2) + tuple(spec.input_hw) + (2,))).as_text(debug_info=True)
        if net == "firenet":
            for scope in ("spidr.L1.recur", "spidr.L4.recur"):
                assert scope in text, scope
        else:
            assert ".recur" not in text


# ---------------------------------------------------------------------------
# Pipeline timeline: the cost model as a trace.
# ---------------------------------------------------------------------------
class TestPipelineTimeline:
    def test_busy_cycles_conserved_exactly(self, compiled4):
        ev = jnp.asarray(_stream(t=2)[:, None])
        out = compiled4.run(ev)
        events = compiled4.pipeline_trace(out)
        totals = obs.busy_cycle_totals(events)
        cost = compiled4.cost(out)
        n_cores = len(cost.busy_cycles)
        assert n_cores == 4
        for core in range(n_cores):
            assert int(totals.get(core, 0)) == int(cost.busy_cycles[core])

    def test_collect_timeline_does_not_change_cost(self, compiled4):
        from repro.engine.cost import estimate_multicore_cost

        ev = jnp.asarray(_stream(t=2)[:, None])
        out = compiled4.run(ev)
        counts = np.asarray(out.input_counts)
        plain = estimate_multicore_cost(compiled4.spec, compiled4.schedule,
                                        counts)
        timed = estimate_multicore_cost(compiled4.spec, compiled4.schedule,
                                        counts, collect_timeline=True)
        assert plain.timeline is None and timed.timeline
        assert plain.makespan_cycles == timed.makespan_cycles
        np.testing.assert_array_equal(plain.busy_cycles, timed.busy_cycles)
        np.testing.assert_array_equal(plain.compute_cycles,
                                      timed.compute_cycles)

    def test_core_tracks_are_gapless_with_idle_tail(self, compiled4):
        """Per core: back-to-back intervals; a core shorter than the plan
        makespan gets an idle tail up to it."""
        ev = jnp.asarray(_stream(t=2)[:, None])
        out = compiled4.run(ev)
        cost = compiled4.cost(out)
        events = compiled4.pipeline_trace(out)
        totals = obs.busy_cycle_totals(events)
        for core in range(4):
            spans = sorted((e for e in events
                            if e.get("ph") == "X" and e["tid"] == core),
                           key=lambda e: e["ts"])
            for prev, nxt in zip(spans, spans[1:]):
                assert prev["ts"] + prev["dur"] == nxt["ts"]
            end = spans[-1]["ts"] + spans[-1]["dur"]
            assert end == max(float(cost.makespan_cycles), totals[core])

    def test_timeline_requires_collect_flag(self, compiled4):
        ev = jnp.asarray(_stream(t=2)[:, None])
        cost = compiled4.cost(compiled4.run(ev))  # priced WITHOUT timeline
        with pytest.raises(ValueError, match="collect_timeline"):
            obs.multicore_timeline(cost)

    def test_single_core_has_no_pipeline_trace(self, compiled1):
        ev = jnp.asarray(_stream(t=6)[:, None])
        out = compiled1.run(ev)
        with pytest.raises(ValueError):
            compiled1.pipeline_trace(out)

    def test_write_chrome_trace_sorted(self, tmp_path, compiled4):
        from repro.obs.timeline import write_chrome_trace

        ev = jnp.asarray(_stream(t=2)[:, None])
        events = compiled4.pipeline_trace(compiled4.run(ev))
        path = write_chrome_trace(list(reversed(events)), tmp_path / "p.json")
        doc = json.loads(path.read_text())
        ts = [e["ts"] for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert ts == sorted(ts)


# ---------------------------------------------------------------------------
# Serving + durability counters.
# ---------------------------------------------------------------------------
class TestServingTelemetry:
    def test_streaming_server_counters(self, compiled1):
        from repro.serving import StreamRequest, StreamWorker

        obs.enable_metrics()
        server = StreamWorker(compiled1, capacity=2, chunk_T=3)
        for rid in range(3):   # 3 streams into 2 slots: 1+ deferred ticks
            server.submit(StreamRequest(rid=rid, events=_stream(t=6, seed=rid)))
        ticks = 0
        while server.step():
            ticks += 1
        d = obs.default_registry().to_dict()
        assert d["spidr_serve_admissions_total"][0]["value"] == 3.0
        assert d["spidr_serve_rejections_total"][0]["value"] >= 1.0
        assert d["spidr_serve_tick_seconds"][0]["count"] == ticks
        assert tuple(d["spidr_serve_tick_seconds"][0]["buckets"]["edges"]) \
            == LATENCY_BUCKETS_S
        assert len(server.done) == 3

    def test_watchdog_counter(self):
        from repro.runtime.fault_tolerance import StepWatchdog

        reg = obs.MetricsRegistry(enabled=True)
        c = reg.counter("spidr_serve_watchdog_timeouts_total", "firings")
        wd = StepWatchdog(0.01, counter=c)
        wd.arm()
        time.sleep(0.05)
        wd.disarm()
        assert wd.timed_out and c.value == 1.0

    def test_retrying_on_restart_hook(self):
        from repro.runtime.fault_tolerance import (
            RestartableFailure, retrying,
        )

        calls = {"n": 0}
        restarts = []

        def step():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RestartableFailure("poisoned")
            return "ok"

        fn = retrying(step, lambda *a, **k: None,
                      on_restart=lambda: restarts.append(1))
        assert fn() == "ok"
        assert restarts == [1]

    def test_rewind_counter_via_injected_fault(self, compiled1):
        from repro.serving import StreamRequest, StreamWorker

        obs.enable_metrics()
        server = StreamWorker(compiled1, capacity=2, chunk_T=3,
                                    fail_at_tick=1)
        server.submit(StreamRequest(rid=0, events=_stream(t=6)))
        while server.step():
            pass
        assert server.restarts == 1
        d = obs.default_registry().to_dict()
        assert d["spidr_serve_rewinds_total"][0]["value"] == 1.0


# ---------------------------------------------------------------------------
# Structured logging.
# ---------------------------------------------------------------------------
class TestLogging:
    def _logger(self, name, json_mode):
        buf = io.StringIO()
        lg = logging.getLogger(name)
        lg.handlers.clear()
        obs.logging_setup(json_mode=json_mode, logger=lg, stream=buf)
        return lg, buf

    def test_request_id_in_text_records(self):
        lg, buf = self._logger("test.obs.text", json_mode=False)
        from repro.obs.logs import request_context

        lg.info("outside")
        with request_context(42):
            lg.info("inside")
        lines = buf.getvalue().strip().splitlines()
        assert "rid=- outside" in lines[0]
        assert "rid=42 inside" in lines[1]

    def test_request_id_in_json_records(self):
        lg, buf = self._logger("test.obs.json", json_mode=True)
        from repro.obs.logs import request_context

        with request_context(7):
            lg.warning("hot slot %d", 3)
        rec = json.loads(buf.getvalue())
        assert rec["request_id"] == "7"
        assert rec["level"] == "WARNING"
        assert rec["message"] == "hot slot 3"
        assert rec["logger"] == "test.obs.json"

    def test_setup_is_idempotent(self):
        lg, _ = self._logger("test.obs.idem", json_mode=False)
        obs.logging_setup(logger=lg, stream=io.StringIO())
        obs.logging_setup(logger=lg, stream=io.StringIO())
        ours = [h for h in lg.handlers
                if getattr(h, "_spidr_obs_handler", False)]
        assert len(ours) == 1


# ---------------------------------------------------------------------------
# End to end: the serving CLI path and the bench-meta contract.
# ---------------------------------------------------------------------------
class TestEndToEnd:
    def test_serve_snn_writes_metrics_and_trace(self, tmp_path):
        from repro.launch.serve import serve_snn

        args = argparse.Namespace(
            snn="gesture", weight_bits=4, jnp=True, n_cores=4, chunk_T=2,
            capacity=2, requests=3, streaming=True,
            metrics_out=str(tmp_path / "m.prom"),
            metrics_every=1, trace_out=str(tmp_path / "t.json"))
        server = serve_snn(args)
        assert len(server.done) == 3
        prom = (tmp_path / "m.prom").read_text()
        assert "spidr_session_ticks_total" in prom
        assert "spidr_serve_admissions_total 3" in prom
        assert "spidr_serve_tick_seconds_bucket" in prom
        doc = json.loads((tmp_path / "t.json").read_text())
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        names = {e["name"] for e in spans}
        assert {"spidr.compile", "serve.tick", "run_chunk"} <= names
        # One pipeline-timeline process row per finished stream.
        stream_pids = {e["pid"] for e in spans if e.get("cat") == "busy"}
        assert stream_pids == {100, 101, 102}
        ts = [e["ts"] for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert ts == sorted(ts)

    def test_check_bench_ignores_meta_key(self, tmp_path):
        results = [{"name": "x", "ablation": "a", "cycles": 100,
                    "exact": True}]
        base = {"schema": 1, "suite": "s", "results": results}
        fresh = {"schema": 1, "suite": "s", "results": results,
                 "meta": {"git_sha": "deadbeef", "jax": "0.0.0",
                          "timestamp": "2026-01-01T00:00:00+00:00"}}
        (tmp_path / "baseline.json").write_text(json.dumps(base))
        (tmp_path / "fresh.json").write_text(json.dumps(fresh))
        proc = subprocess.run(
            [sys.executable, "tools/check_bench.py",
             str(tmp_path / "fresh.json"),
             "--baseline", str(tmp_path / "baseline.json")],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_committed_baseline_has_meta(self):
        payload = json.loads(
            open("benchmarks/baseline.json", encoding="utf-8").read())
        assert {"git_sha", "jax", "jaxlib", "python",
                "timestamp"} <= set(payload["meta"])
