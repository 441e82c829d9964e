#!/usr/bin/env python
"""Zero-downtime upgrade drill: SIGKILL a streaming server, restore, compare.

For every configuration in the matrix (gesture + optical-flow, 1 and 4
cores, fused-Pallas and jnp backends) the drill:

  1. serves a deterministic multi-stream workload uninterrupted in-process
     and records every stream's final readout / cumulative cycles / energy
     (the reference);
  2. launches a child process that serves the same workload with
     per-tick snapshots and SIGKILLs *itself mid-chunk* at a randomized
     tick — after the session stepped, before any bookkeeping, the worst
     possible instant;
  3. launches a second child that restores from the latest on-disk
     snapshot (``repro.serving.StreamWorker.restore``) and serves to
     completion;
  4. asserts the restored results are byte-identical to the reference for
     every stream — zero sessions lost state.

Usage:
  python tools/upgrade_drill.py --smoke --out drill_report.json
  python tools/upgrade_drill.py --seed 7          # full geometry

Exit status is non-zero if any configuration mismatches; the JSON report
records per-config kill ticks and per-stream verdicts.  The drill runs on
the CPU in every process (it sets ``JAX_PLATFORMS=cpu`` before JAX loads).
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402


def matrix():
    return [{"task": task, "n_cores": cores, "backend": backend}
            for task in ("gesture", "optical-flow")
            for cores in (1, 4)
            for backend in ("fused", "jnp")]


def geometry(smoke: bool) -> dict:
    if smoke:
        return {"hw": [16, 16], "timesteps": 6, "capacity": 2,
                "chunk_T": 2, "n_streams": 4}
    return {"hw": [32, 32], "timesteps": 10, "capacity": 3,
            "chunk_T": 2, "n_streams": 6}


def build(cfg: dict):
    """Deterministically compile the config's deployment (any process)."""
    import jax

    from repro import spidr
    from repro.configs import spidr_gesture, spidr_optflow
    from repro.core.network import init_params

    mod = spidr_gesture if cfg["task"] == "gesture" else spidr_optflow
    spec = mod.reduced(hw=tuple(cfg["hw"]), timesteps=cfg["timesteps"])
    params = init_params(jax.random.PRNGKey(0), spec)
    target = spidr.DeployTarget(
        weight_bits=4, n_cores=cfg["n_cores"], backend=cfg["backend"],
        chunk_T=cfg["chunk_T"], stream_capacity=cfg["capacity"])
    return spidr.compile(spec, params, target), spec


def make_requests(cfg: dict, seed: int) -> dict:
    """The drill workload: streams of *differing* lengths (slot churn),
    regenerated identically in every process from the seed alone."""
    from repro.serving import StreamRequest

    spec_c = 2
    h, w = cfg["hw"]
    t_max = cfg["timesteps"]
    rng = np.random.default_rng(seed)
    reqs = {}
    for rid in range(cfg["n_streams"]):
        t = int(rng.integers(max(2, t_max // 2), t_max + 1))
        ev = (rng.random((t, h, w, spec_c)) < 0.1).astype(np.float32)
        reqs[rid] = StreamRequest(rid=rid, events=ev)
    return reqs


def results_of(server) -> dict:
    return {str(r.rid): {
        "readout": np.asarray(r.readout).tolist(),
        "cycles": int(r.cycles),
        "energy_uj": float(r.energy_uj),
        "timesteps": int(r.cursor),
    } for r in server.done}


def serve_reference(cfg: dict, seed: int):
    """Uninterrupted run; returns (results, n_ticks)."""
    from repro.serving import StreamWorker

    compiled, _ = build(cfg)
    server = StreamWorker(compiled, capacity=cfg["capacity"],
                                chunk_T=cfg["chunk_T"])
    for rid, req in sorted(make_requests(cfg, seed).items()):
        server.submit(req)
    while server.step():
        pass
    return results_of(server), server.ticks


# ---------------------------------------------------------------------------
# Child modes (run in their own process).
# ---------------------------------------------------------------------------
def child_serve(cfg: dict, seed: int, snap_dir: str, die_at: int) -> None:
    """Serve with per-tick snapshots; SIGKILL ourselves mid-tick at
    ``die_at`` — after the session stepped, before bookkeeping/snapshot."""
    from repro import obs
    from repro.serving import StreamWorker

    # Trace the whole doomed run: compile/autotune spans plus every
    # serve.tick/run_chunk up to the fatal tick.  The trace is exported
    # from the mid-tick hook — synchronously, before the SIGKILL lands —
    # so the parent can embed the kill-tick span timeline in its report.
    obs.enable_tracing()
    tracer = obs.default_tracer()
    compiled, _ = build(cfg)
    server = StreamWorker(compiled, capacity=cfg["capacity"],
                                chunk_T=cfg["chunk_T"],
                                snapshot_dir=snap_dir, snapshot_every=1)

    def kill_mid_tick(tick: int) -> None:
        if tick == die_at:
            os.makedirs(snap_dir, exist_ok=True)
            tracer.export(os.path.join(snap_dir, "kill_trace.json"))
            os.kill(os.getpid(), signal.SIGKILL)

    server.mid_tick_hook = kill_mid_tick
    for rid, req in sorted(make_requests(cfg, seed).items()):
        server.submit(req)
    while server.step():
        pass
    raise SystemExit(3)  # reached only if the kill tick never arrived


def child_restore(cfg: dict, seed: int, snap_dir: str, out: str) -> None:
    """Fresh process: restore the latest snapshot, serve to completion."""
    from repro.serving import StreamWorker

    server = StreamWorker.restore(snap_dir,
                                        make_requests(cfg, seed))
    resumed_at = server.ticks
    while server.step():
        pass
    with open(out, "w") as f:
        json.dump({"results": results_of(server),
                   "resumed_at_tick": resumed_at,
                   "final_tick": server.ticks}, f)


# ---------------------------------------------------------------------------
# The drill.
# ---------------------------------------------------------------------------
def spawn(extra: list) -> subprocess.CompletedProcess:
    # The children inherit the CPU pin ``main`` set before JAX loaded.
    return subprocess.run([sys.executable, os.path.abspath(__file__)] + extra,
                          capture_output=True, text=True, timeout=1200)


def drill_config(cfg: dict, seed: int) -> dict:
    t0 = time.monotonic()
    reference, n_ticks = serve_reference(cfg, seed)
    # Randomized kill tick: >= 2 so at least one snapshot exists on disk.
    kill_rng = np.random.default_rng(seed * 1000 + cfg["n_cores"])
    die_at = int(kill_rng.integers(2, max(n_ticks, 2) + 1))
    record = dict(cfg, ticks=n_ticks, die_at_tick=die_at,
                  streams=len(reference))

    with tempfile.TemporaryDirectory(prefix="spidr_drill_") as tmp:
        snap = os.path.join(tmp, "snap")
        cfg_json = json.dumps(cfg)
        a = spawn(["--child", "serve", "--cfg", cfg_json, "--dir", snap,
                   "--seed", str(seed), "--die-at", str(die_at)])
        record["serve_returncode"] = a.returncode
        if a.returncode != -signal.SIGKILL:
            record.update(ok=False, error=(
                f"serve child exited {a.returncode}, expected SIGKILL "
                f"({-signal.SIGKILL}): {a.stderr[-2000:]}"))
            return record
        trace_path = os.path.join(snap, "kill_trace.json")
        if os.path.exists(trace_path):
            with open(trace_path) as f:
                spans = [e for e in json.load(f)["traceEvents"]
                         if e.get("ph") == "X"]
            # The span timeline leading into the kill: the last few
            # completed spans (the fatal tick's run_chunk is the newest —
            # its serve.tick parent never closed, the process died inside).
            record["kill_trace"] = {
                "total_spans": len(spans),
                "final_spans": [
                    {"name": e["name"], "cat": e.get("cat"),
                     "ts_us": e["ts"], "dur_us": e["dur"],
                     "args": e.get("args", {})}
                    for e in spans[-8:]],
            }
        out = os.path.join(tmp, "results.json")
        b = spawn(["--child", "restore", "--cfg", cfg_json, "--dir", snap,
                   "--seed", str(seed), "--out", out])
        if b.returncode != 0:
            record.update(ok=False, error=(
                f"restore child exited {b.returncode}: {b.stderr[-2000:]}"))
            return record
        with open(out) as f:
            restored = json.load(f)

    record["resumed_at_tick"] = restored["resumed_at_tick"]
    mismatches = []
    for rid, want in reference.items():
        got = restored["results"].get(rid)
        if got != want:
            mismatches.append({"rid": rid, "want": want, "got": got})
    lost = sorted(set(reference) - set(restored["results"]))
    record.update(ok=not mismatches and not lost, mismatches=mismatches,
                  lost_streams=lost,
                  wall_s=round(time.monotonic() - t0, 2))
    return record


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometry (CI): same 8-config matrix")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="write a JSON report here")
    ap.add_argument("--child", choices=["serve", "restore"], default=None)
    ap.add_argument("--cfg", default=None)
    ap.add_argument("--dir", default=None)
    ap.add_argument("--die-at", type=int, default=None, dest="die_at")
    args = ap.parse_args()
    # A CPU drill: pin the parent too, before anything imports JAX, so it
    # never holds an accelerator while its children run elsewhere.
    os.environ["JAX_PLATFORMS"] = "cpu"

    if args.child is not None:
        cfg = json.loads(args.cfg)
        if args.child == "serve":
            child_serve(cfg, args.seed, args.dir, args.die_at)
        else:
            child_restore(cfg, args.seed, args.dir, args.out)
        return 0

    geo = geometry(args.smoke)
    records = []
    for cfg in matrix():
        cfg = dict(cfg, **geo)
        print(f"[drill] {cfg['task']} x {cfg['n_cores']} core(s) x "
              f"{cfg['backend']} ...", flush=True)
        rec = drill_config(cfg, args.seed)
        verdict = "OK" if rec["ok"] else f"FAIL ({rec.get('error', 'diff')})"
        print(f"[drill]   killed at tick {rec.get('die_at_tick')}/"
              f"{rec.get('ticks')}, resumed at "
              f"{rec.get('resumed_at_tick', '?')}: {verdict}", flush=True)
        records.append(rec)

    ok = all(r["ok"] for r in records)
    report = {"seed": args.seed, "smoke": bool(args.smoke),
              "ok": ok, "configs": records}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"[drill] report -> {args.out}")
    print(f"[drill] {'ALL OK' if ok else 'FAILURES'}: "
          f"{sum(r['ok'] for r in records)}/{len(records)} configs "
          "restored with zero lost state")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
