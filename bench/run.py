#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration (``bench/configs/<name>.json``, whose layers its network
description ``bench/networks/<topology>.py`` walks), traffic mix
(``bench/traffic/<name>.json``, whose clip pattern and arrival policy are
``bench/patterns/<name>.py`` and ``bench/arrivals/<name>.py``) and metric
readers (``bench/metrics/<metric>.py``) are found by the names given
there.

A run builds the cell's clip pool and arrivals from ``--seed`` and random
integer weights from the configuration's weight seed (see
``make_weights``; ``--weights-seed`` replaces it), compiles the network with ``spidr.compile`` on the fused
backend and serves it with ``spidr.serve`` in sync mode, one replica per
chip.  It warms up the cell's own shapes through a whole clip lifecycle,
then drives the fleet from its own loop for ``--seconds``: open loop
(clips due on a Poisson schedule, latency from each clip's due time) or
closed loop at saturation (frames per second over whole ticks).  After
the window it drains the clips in flight, reads the device's peak memory,
frees the program's state and checks every served clip's readout, cycles
and energy against the plain reference in ``bench/reference.py``.

With ``--trace 1`` the window runs under the JAX profiler and the result
line carries the cell's per-layer metrics, the device's busy and window
seconds and a breakdown; with ``--trace 0`` it carries the end-to-end
metrics.  The last line on standard output is one JSON object; the
numbers compared are its last key, ``checks``, and the last lines on
standard error.  Without a TPU, or with fewer chips than the cell asks
for, the run exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import generator, loop, named, reference, work  # noqa: E402
from bench import trace_reduce  # noqa: E402
from bench.record import RunRecord  # noqa: E402

DRAIN_S = 60.0
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """The machine lacks the chips the cell asks for."""


# ---------------------------------------------------------------------------
# Finding a cell's files by name.
# ---------------------------------------------------------------------------
def load_benchmark(root: pathlib.Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_plan(bench: dict, root: pathlib.Path, workload: str) -> dict:
    """Everything one cell names: its entry, configuration, traffic and
    the metrics it reports, each found by the name in ``bench``.  The
    configuration gains ``bench_root``, the tree whose network
    description (``bench/networks/<topology>.py``) it is read with."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    cfg["bench_root"] = str(root)
    traffic_file = root / "bench" / "traffic" / f"{cell['traffic']}.json"
    traffic = json.loads(traffic_file.read_text())

    def applies(m):
        return workload in m["workloads"] if "workloads" in m else True

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {"cell": cell, "cfg": cfg, "traffic": traffic, "end_to_end": e2e,
            "per_layer": per_layer}


def metric_reader(root: pathlib.Path, name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    return named.load(root, "metrics", name).read


# ---------------------------------------------------------------------------
# Set-up.
# ---------------------------------------------------------------------------
def _device_tag(devices) -> str:
    return f"[{devices[0].platform} {devices[0].device_kind} x{len(devices)}]"


def program_spec(cfg: dict):
    """The program's network for ``cfg``, checked against the
    configuration file by its network description, so the program runs
    the file as stated."""
    module, attr = cfg["spec"].split(":")
    spec = getattr(importlib.import_module(module), attr)
    spec = dataclasses.replace(spec, input_hw=tuple(cfg["input_hw"]),
                               timesteps=cfg["timesteps"])
    mismatch = named.network(cfg).check_program(spec, cfg)
    if mismatch:
        raise ValueError(f"{cfg['spec']} does not match {cfg['name']}: "
                         + "; ".join(mismatch))
    return spec


def make_weights(cfg: dict):
    """Random integer weights from the configuration's weight seed, made
    on the device in one jitted call: ``(q, params)`` with ``q`` the int8
    weights per weight layer, (F, K) with F the layer's ``fan_in`` (in
    (kh, kw, c_in) order for a conv), and ``params`` what the program is
    given per spec layer, as the network description makes it from the
    float32 weights.  Each layer's float weights are ``q * s`` with ``s =
    threshold / thr_int`` and one weight at the top level, so the
    program's per-tensor quantization recovers ``q`` and ``thr_int``.

    The weights follow the configuration, not the run's ``--seed``: the
    served chunk step holds the weights as constants of its program, so a
    new weight set would be a new program, compiled in every run's set-up
    and never found in the persistent cache."""
    import jax
    import jax.numpy as jnp

    levels = cfg["weights"]["levels"]
    thr = cfg["neuron"]["threshold"]
    layers = reference.weight_layers(cfg)

    @jax.jit
    def gen(key):
        qs, ws = [], []
        for layer in layers:
            key, k = jax.random.split(key)
            q = jax.random.randint(k, (layer["fan_in"], layer["c_out"]),
                                   -levels, levels + 1, jnp.int32)
            q = q.at[0, 0].set(levels)
            qs.append(q.astype(jnp.int8))
            ws.append(q.astype(jnp.float32)
                      * jnp.float32(thr / layer["thr_int"]))
        return qs, ws

    qs, ws = gen(jax.random.key(cfg["weights"]["seed"]))
    return qs, named.network(cfg).program_params(cfg, ws)


class CompileLog:
    """What JAX compiled, per phase of the run (``phase`` names the
    current one): programs lowered (each then compiled or loaded from the
    persistent cache), persistent-cache hits and misses, and seconds spent
    in the backend compiler."""

    def __init__(self):
        self.phase = "set-up"
        self.counts: dict = {}

    def _add(self, key, value=1):
        per = self.counts.setdefault(self.phase, {})
        per[key] = per.get(key, 0) + value

    def _on_duration(self, event, duration_secs, **kwargs):
        if event == LOWER_EVENT:
            self._add("lowered")
        elif event == BACKEND_COMPILE_EVENT:
            self._add("compile_s", duration_secs)

    def _on_event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            self._add("cache_hits")
        elif event == CACHE_MISS_EVENT:
            self._add("cache_misses")

    def get(self, phase: str, key: str):
        return self.counts.get(phase, {}).get(key, 0)

    def summary(self, phase: str) -> str:
        return (f"{self.get(phase, 'lowered')} programs lowered, "
                f"{self.get(phase, 'cache_hits')} cache hits, "
                f"{self.get(phase, 'cache_misses')} cache misses, "
                f"{self.get(phase, 'compile_s'):.3f} s in the compiler")

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    """A cell's deployment, warmed up and ready for its window."""

    root: pathlib.Path     # the tree whose arrival policies are read
    devices: list
    peak: dict
    pool: np.ndarray
    weights: list          # the reference's int8 weights, one per layer
    fleet: object
    overloaded: type       # the fleet's load-shedding exception
    capacity: int
    say: object


def find_devices(plan: dict, require_tpu: bool) -> list:
    """The cell's devices; raises :class:`NoChip` without them."""
    import jax

    chips = plan["cell"]["chips"]
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        raise NoChip(f"{plan['cell']['name']} needs {chips} TPU chip(s); "
                     f"JAX found {len(devices)} {devices[0].platform} "
                     "device(s)")
    return devices[:chips]


def prepare(plan: dict, root: pathlib.Path, seed: int,
            require_tpu: bool = True, log=None) -> Served:
    """Set-up: pool, weights, compile, serve and warm up the cell."""
    import jax

    cfg, traffic = plan["cfg"], plan["traffic"]
    chips = plan["cell"]["chips"]
    devices = find_devices(plan, require_tpu)
    tag = _device_tag(devices)

    def say(msg):
        print(f"{tag} {msg}", file=log or sys.stdout, flush=True)

    def phase(name):
        say(f"set-up {name} done at {time.monotonic() - _PROCESS_START:.3f} s")

    phase("devices")
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.runtime.compile_cache import configure_compile_cache

    say(f"compile cache: {configure_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro import spidr

    peak = work.peak_for(devices[0].device_kind) if require_tpu \
        else work.peak_for("TPU v5 lite")
    spec = program_spec(cfg)
    phase("imports")
    pool = generator.clip_pool(traffic, cfg, seed, root)
    phase("clip pool")
    qs, params = make_weights(cfg)
    phase("weights")
    target = spidr.DeployTarget(**cfg["deploy"])
    compiled = spidr.compile(spec, params, target)
    if require_tpu and compiled.engine.cfg.interpret:
        raise RuntimeError("the fused kernels would run interpreted")
    phase("spidr.compile")
    capacity = target.stream_capacity
    fleet = spidr.serve(compiled, spidr.ServeConfig(
        n_replicas=chips, devices="auto" if chips > 1 else None,
        max_queue=traffic["max_queue_per_slot"] * capacity * chips,
        mode="sync"))
    phase("spidr.serve")
    loop.warm_up(fleet, pool[: 2 * chips, :target.chunk_T], chips,
                 spidr.FleetOverloaded)
    phase("warm-up")
    return Served(root=root, devices=devices, peak=peak, pool=pool,
                  weights=[np.asarray(q) for q in qs], fleet=fleet,
                  overloaded=spidr.FleetOverloaded, capacity=capacity,
                  say=say)


def drive(served: Served, traffic: dict, seconds: float, seed: int,
          span=loop.no_span, on_window_end=None, rid_base: int = 0,
          mark=None):
    """The measured window and its drain, as the traffic's arrival policy
    ``bench/arrivals/<arrivals>.py`` drives them: its ``drive`` returns
    ``(clips, ticks, horizon, t0)`` as the loops of ``bench/loop.py`` do."""
    policy = named.load(served.root, "arrivals", traffic["arrivals"])
    return policy.drive(
        served.fleet, served.pool, traffic, seconds, seed,
        slots=served.capacity * len(served.devices), drain_s=DRAIN_S,
        overloaded=served.overloaded, span=span,
        on_window_end=on_window_end, rid_base=rid_base, mark=mark)


def host_usage() -> dict:
    """This process's CPU seconds, page faults and context switches, and
    the machine's load average: printed for the window, so that a slow run
    can be told from a slow machine."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"user_s": ru.ru_utime, "system_s": ru.ru_stime,
            "minor_faults": ru.ru_minflt, "major_faults": ru.ru_majflt,
            "voluntary_switches": ru.ru_nvcsw,
            "involuntary_switches": ru.ru_nivcsw,
            "load_1min": os.getloadavg()[0]}


def run_cell(bench: dict, root: pathlib.Path, workload: str, seed: int,
             seconds: float, trace: bool, require_tpu: bool = True,
             log=None, weights_seed: int | None = None) -> dict:
    """Run ``workload`` once and return its result object.

    ``require_tpu=False`` skips the look for a chip (tests drive the rest
    of a run on the CPU with it).  Raises :class:`NoChip` when the chip
    is required and missing.  ``weights_seed`` replaces the
    configuration's weight seed (a check of other weight sets; each
    compiles its chunk step anew).
    """
    import jax

    plan = cell_plan(bench, root, workload)
    if weights_seed is not None:
        plan["cfg"]["weights"] = dict(plan["cfg"]["weights"],
                                      seed=weights_seed)
    cfg, traffic = plan["cfg"], plan["traffic"]
    chips = plan["cell"]["chips"]
    with CompileLog() as compiles:
        served = prepare(plan, root, seed, require_tpu, log)
        devices, say = served.devices, served.say
        say(f"set-up: {compiles.summary('set-up')}")

        tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        window_span = None
        span = loop.no_span
        mark = None
        trace_from = []
        if trace:
            # The last ``trace_seconds`` of the window are traced: the
            # profiler keeps a bounded number of events, so a whole long
            # window of the gesture cell would come back with most device
            # ops missing.  The Python tracer would time every call of the
            # host path and slow it; the harness's own spans are host
            # events of level 1.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            span = jax.profiler.TraceAnnotation

            def start_trace():
                nonlocal window_span
                trace_from.append(time.monotonic())
                jax.profiler.start_trace(tmp, create_perfetto_trace=True,
                                         profiler_options=options)
                window_span = jax.profiler.TraceAnnotation(
                    trace_reduce.WINDOW_SPAN)
                window_span.__enter__()

            mark = (max(0.0, seconds - traffic["trace_seconds"]), start_trace)

        usage = [host_usage()]

        def window_end():
            usage.append(host_usage())
            compiles.phase = "drain"
            if window_span is not None:
                window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()

        compiles.phase = "window"
        clips, ticks, horizon, t0 = drive(served, traffic, seconds, seed,
                                          span, window_end, mark=mark)
        compiles.phase = "after"
    first_tick = t0 + (ticks[0].start if ticks else 0.0)
    setup_s = first_tick - _PROCESS_START
    trace_events = None
    if trace:
        if not trace_from:
            raise RuntimeError("the window closed before its traced part "
                               "began: trace_seconds is shorter than a tick")
        found = sorted(pathlib.Path(tmp).rglob("perfetto_trace.json.gz"))
        trace_events = trace_reduce.load_events(found[-1])
        shutil.rmtree(tmp, ignore_errors=True)
    say(f"compiles in window: {compiles.get('window', 'lowered')} "
        f"({compiles.summary('window')}); in the drain: "
        f"{compiles.get('drain', 'lowered')}")

    memory_peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
                      for d in devices) if require_tpu else 0
    served.fleet.shutdown()
    served.fleet = None
    gc.collect()

    window_ticks = loop.whole_ticks(ticks, seconds)
    longest = sorted(ticks, key=lambda t: t.start - t.end)[:3]
    say("longest ticks (start s, length s): " + ", ".join(
        f"({t.start:.3f}, {t.end - t.start:.3f})" for t in longest))
    late = max((c.submitted - c.due for c in clips
                if c.submitted is not None), default=0.0)
    say("host in window: " + ", ".join(
        f"{k} {usage[1][k] - usage[0][k]:.6g}" if k != "load_1min" else
        f"{k} {usage[0][k]:.2f} -> {usage[1][k]:.2f}" for k in usage[0])
        + f"; cores {len(os.sched_getaffinity(0))}")
    say(f"ticks in window: {len(window_ticks)}; clips: {len(clips)}, "
        f"done {sum(c.done is not None for c in clips)}, "
        f"shed {sum(c.shed for c in clips)}; generator late by at most "
        f"{late * 1e3:.3f} ms")
    t_check = time.monotonic()
    checks = check_clips(cfg, served.weights, served.pool, clips,
                         cfg["deploy"]["chunk_T"])
    say(f"reference check of {sum(c.done is not None for c in clips)} "
        f"served clips: {time.monotonic() - t_check:.3f} s")

    rec = RunRecord(cfg=cfg, traffic=traffic, chips=chips, seconds=seconds,
                    clips=clips, ticks=window_ticks, horizon=horizon,
                    peak=served.peak,
                    trace_from=trace_from[0] - t0 if trace_from else None)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": len(clips),
              "failed": sum(c.failed for c in clips),
              "metrics": {}, "device": device}
    if trace:
        rec.trace = trace_reduce.reduce_trace(trace_events)
        say(f"trace: {sum(b > 0 for _, b in rec.trace['steps'])} of "
            f"{len(rec.trace['steps'])} traced ticks hold device work; "
            f"{len(rec.traced_ticks())} ticks from {rec.trace_from:.3f} s")
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        metrics = plan["per_layer"]
    else:
        metrics = [m for m in plan["end_to_end"] if m["name"] != "setup_s"]
    for m in metrics:
        value = metric_reader(root, m["name"])(rec)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    if trace:
        result["breakdown"] = {"device_ops": rec.trace["device_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
    else:
        result["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def check_clips(cfg: dict, q_weights: list, pool: np.ndarray, clips: list,
                chunk_T: int) -> dict:
    """Compare every served clip with the plain reference.

    Returns ``{name: (value, limit)}``: clips whose readout, cycles or
    energy differ from the reference's (each exact, limit 0), clips that
    were admitted and never answered (limit 0), and whether any clip was
    compared at all (``compared_none``, limit 0).
    """
    served = [c for c in clips if c.done is not None]
    unanswered = sum(1 for c in clips if not c.shed and c.done is None)
    idx = sorted({c.pool_index for c in served})
    readouts, counts = (reference.reference_run(cfg, q_weights, pool[idx])
                        if idx else (None, None))
    row = {p: i for i, p in enumerate(idx)}
    costs = {p: reference.chip_cost(cfg, counts[row[p]], chunk_T)
             for p in idx}
    bad_readout = bad_cycles = bad_energy = 0
    for c in served:
        req = c.handle.request
        i = row[c.pool_index]
        got = np.asarray(req.readout)
        want = readouts[i]
        if got.shape != want.shape or not np.array_equal(got, want):
            bad_readout += 1
        cycles, energy = costs[c.pool_index]
        bad_cycles += int(req.cycles) != cycles
        bad_energy += float(req.energy_uj) != energy
    return {"readout_mismatch": (bad_readout, 0),
            "cycles_mismatch": (bad_cycles, 0),
            "energy_mismatch": (bad_energy, 0),
            "unanswered": (unanswered, 0),
            "compared_none": (int(not served), 0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--weights-seed", type=int, default=None,
                    help="replace the configuration's weight seed")
    args = ap.parse_args(argv)
    bench = load_benchmark(ROOT)
    try:
        result = run_cell(bench, ROOT, args.workload, args.seed,
                          args.seconds, bool(args.trace),
                          weights_seed=args.weights_seed)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    d = result["device"]
    for name, c in result["checks"].items():
        print(f"[{d['platform']} {d['kind']} x{d['count']}] check {name}: "
              f"{c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
