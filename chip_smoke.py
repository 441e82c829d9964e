#!/usr/bin/env python3
"""Smoke run of the served SNN path on a TPU, at the paper's published sizes.

    python chip_smoke.py                # one chip: gesture + optical flow
    python chip_smoke.py --four-chips   # four chips: replica fleet + core mesh

The default run has two phases, one per paper network at its published
size: gesture (64x64x2, T=20) and optical flow (288x384x2, T=10).  Each
phase builds random weights from ``--seed`` and compiles them with
``spidr.compile`` on the fused Pallas backend.  It serves synthetic DVS
streams through ``spidr.serve`` in streaming mode and compares every
finished stream's readout, cycles and energy byte for byte with the ``jnp``
backend on the same events, served the same way and run whole-stream.

``--four-chips`` runs only the multi-device paths: the gesture fleet on
four replicas (one per device) and the gesture network compiled onto a
four-device ``cores`` mesh.  Each is compared byte for byte with its
one-device run of the same events.

The per-phase lines come from this one smoke run; they are not benchmark
numbers.  The last line is the verdict,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any exception or mismatch ends the run with a non-zero exit.  Without a
TPU the script prints ``{"ok": false, ...}`` and exits 2.  Everything runs
in this one process: a second process could not reach the chip this one
holds.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

CHUNK_T = 2
WEIGHT_BITS = 4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result or ran off the chip."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _report(phase: str, line: dict) -> None:
    dev = _device_info()
    line = dict(device=f"{dev['kind']} x{dev['count']}", **line)
    print(f"smoke run, {phase}: {json.dumps(line)}", flush=True)


def check_on_chip(compiled) -> None:
    """The deployment runs compiled Pallas kernels, not the interpreter:
    the lowered chunk step must hold a TPU custom call."""
    import jax
    import jax.numpy as jnp

    from repro.engine import init_state, run_chunk

    engine = compiled.engine
    _check(engine.cfg.interpret is False,
           f"{compiled.spec.name}: fused kernels would run interpreted")
    cap = compiled.target.stream_capacity
    spec = compiled.spec
    state = jax.eval_shape(lambda: init_state(engine, cap))
    ev = jax.ShapeDtypeStruct(
        (compiled.target.chunk_T, cap) + tuple(spec.input_hw)
        + (spec.in_channels,), jnp.float32)
    step = jax.jit(lambda st, e: run_chunk(engine, st, e, collect_counts=True,
                                           collect_readouts=True))
    _check("tpu_custom_call" in step.lower(state, ev).as_text(),
           f"{spec.name}: the lowered chunk step holds no TPU kernel")


def serve_streams(compiled, events: np.ndarray, config,
                  after_first_tick=None):
    """Serve every stream of ``events`` (T, B, H, W, C) through
    ``spidr.serve``; returns ``({rid: (readout, cycles, energy)}, tick
    seconds)``.  A tick is one fused chunk step over every open slot; it
    ends in the host read of the step's readouts, which waits for the
    device."""
    from repro import spidr

    results, ticks = {}, []
    with spidr.serve(compiled, config) as fleet:
        for rid in range(events.shape[1]):
            fleet.submit(events[:, rid], rid=rid)
        while True:
            t0 = time.perf_counter()
            busy = fleet.step()
            if not busy:
                break
            ticks.append(time.perf_counter() - t0)
            if after_first_tick is not None and len(ticks) == 1:
                after_first_tick(fleet)
        for req in fleet.done:
            _check(req.cursor == events.shape[0],
                   f"stream {req.rid} ended at t={req.cursor}")
            results[req.rid] = (np.asarray(req.readout), int(req.cycles),
                                float(req.energy_uj))
    _check(sorted(results) == list(range(events.shape[1])),
           f"served {sorted(results)} of {events.shape[1]} streams")
    return results, ticks


def same_results(a: dict, b: dict, readouts_only: bool = False) -> bool:
    """Byte-for-byte equality of two ``serve_streams`` result sets."""
    if a.keys() != b.keys():
        return False
    for rid in a:
        (ra, ca, ea), (rb, cb, eb) = a[rid], b[rid]
        if ra.dtype != rb.dtype or ra.shape != rb.shape \
                or ra.tobytes() != rb.tobytes():
            return False
        if not readouts_only and (ca != cb or ea != eb):
            return False
    return True


def _events(spec, n_streams: int, seed: int) -> np.ndarray:
    import jax

    from repro.snn.data import make_flow_batch, make_gesture_batch

    make = make_gesture_batch if spec.readout == "rate" else make_flow_batch
    ev, _ = make(jax.random.PRNGKey(seed), batch=n_streams,
                 timesteps=spec.timesteps, hw=spec.input_hw)
    return np.asarray(ev)


def _compile(spec, seed: int, **target_kw):
    import jax

    from repro import spidr
    from repro.core.network import init_params

    params = init_params(jax.random.PRNGKey(seed), spec)
    target = spidr.DeployTarget(weight_bits=WEIGHT_BITS, chunk_T=CHUNK_T,
                                **target_kw)
    t0 = time.perf_counter()
    compiled = spidr.compile(spec, params, target, check="strict")
    return compiled, params, time.perf_counter() - t0


def network_phase(name: str, spec, n_streams: int, capacity: int,
                  seed: int) -> None:
    """One network at the given size: fused serving vs the jnp oracle."""
    import jax.numpy as jnp

    from repro import spidr

    compiled, params, compile_s = _compile(
        spec, seed, backend="fused", stream_capacity=capacity)
    check_on_chip(compiled)
    events = _events(spec, n_streams, seed + 1)
    config = spidr.ServeConfig(n_replicas=1, max_queue=n_streams)
    served, ticks = serve_streams(compiled, events, config)

    oracle = spidr.compile(spec, params, dataclasses.replace(
        compiled.target, backend="jnp"), check="off")
    oracle_served, _ = serve_streams(oracle, events, config)
    whole = np.asarray(oracle.run(jnp.asarray(events)).readout)
    vs_served = same_results(served, oracle_served)
    vs_whole = all(
        np.array_equal(served[rid][0], whole[rid]) for rid in served)
    steady = np.asarray(ticks[1:]) * 1e3
    _report(name, {
        "input": list(spec.input_hw) + [spec.in_channels],
        "timesteps": spec.timesteps, "capacity": capacity,
        "chunk_T": CHUNK_T, "streams_served": len(served),
        "spidr_compile_s": compile_s,
        "first_chunk_step_s (includes XLA and Mosaic compile)": ticks[0],
        "steady_chunk_steps": int(steady.size),
        "steady_chunk_step_ms_p50": float(np.median(steady)),
        "steady_chunk_step_ms_max": float(steady.max()),
        "exact_vs_jnp_served": vs_served,
        "exact_vs_jnp_whole_stream": vs_whole,
    })
    _check(vs_served, f"{name}: fused streams differ from the jnp oracle's")
    _check(vs_whole, f"{name}: fused readouts differ from whole-stream jnp")


def four_chip_phase(spec, n_streams: int, capacity: int, seed: int) -> None:
    """The replica fleet and the core mesh, each against one device."""
    import jax

    from repro import spidr

    devices = jax.devices()[:4]
    compiled, _, _ = _compile(spec, seed, backend="fused",
                              stream_capacity=capacity)
    check_on_chip(compiled)
    events = _events(spec, n_streams, seed + 1)
    one, _ = serve_streams(compiled, events, spidr.ServeConfig(
        n_replicas=1, max_queue=n_streams))

    placement = {}

    def record_placement(fleet):
        for i, worker in enumerate(fleet.workers):
            placement[i] = worker.sessions.devices

    four, ticks = serve_streams(compiled, events, spidr.ServeConfig(
        n_replicas=4, devices="auto", max_queue=n_streams),
        after_first_tick=record_placement)
    placed = all(placement[i] == {devices[i]} for i in range(4))
    fleet_exact = same_results(one, four)
    _report("four-replica fleet", {
        "streams_served": len(four),
        "replica_devices": [sorted(str(d) for d in placement[i])
                            for i in range(4)],
        "each_replica_on_its_own_device": placed,
        "steady_chunk_step_ms_p50": float(
            np.median(np.asarray(ticks[1:]) * 1e3)),
        "exact_vs_one_device": fleet_exact,
    })
    _check(placed, f"replica state is not one device per replica: "
           f"{placement}")
    _check(fleet_exact, "the four-replica fleet differs from one device")

    mesh, _, _ = _compile(spec, seed, backend="fused",
                          stream_capacity=capacity, n_cores=4)
    _check(mesh.engine.device_parallel,
           "the 4-core plan did not take the device mesh")
    check_on_chip(mesh)
    emulated, _, _ = _compile(spec, seed, backend="fused",
                              stream_capacity=capacity, n_cores=4,
                              device_parallel=False)
    config = spidr.ServeConfig(n_replicas=1, max_queue=n_streams)
    on_mesh, ticks = serve_streams(mesh, events, config)
    on_one, _ = serve_streams(emulated, events, config)
    mesh_exact = same_results(on_mesh, on_one)
    readouts_exact = same_results(on_mesh, one, readouts_only=True)
    _report("four-core mesh", {
        "streams_served": len(on_mesh),
        "steady_chunk_step_ms_p50": float(
            np.median(np.asarray(ticks[1:]) * 1e3)),
        "exact_vs_one_device_plan": mesh_exact,
        "readouts_exact_vs_single_core": readouts_exact,
    })
    _check(mesh_exact, "the 4-core mesh differs from its one-device plan")
    _check(readouts_exact, "the 4-core mesh readouts differ from one core")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true", dest="four_chips",
                    help="run only the 4-replica fleet and the 4-core mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = _device_info()
    need = 4 if args.four_chips else 1
    if device["platform"] != "tpu" or device["count"] < need:
        print(json.dumps({"ok": False, "reason": f"needs {need} TPU chip(s)",
                          "device": device}), flush=True)
        return 2

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.configs import spidr_gesture, spidr_optflow
    from repro.runtime.compile_cache import configure_compile_cache

    _report("setup", {"compile_cache": configure_compile_cache()})
    if args.four_chips:
        four_chip_phase(spidr_gesture.CONFIG, n_streams=8, capacity=4,
                        seed=args.seed)
    else:
        network_phase("gesture", spidr_gesture.CONFIG, n_streams=8,
                      capacity=4, seed=args.seed)
        network_phase("optical flow", spidr_optflow.CONFIG, n_streams=2,
                      capacity=2, seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
