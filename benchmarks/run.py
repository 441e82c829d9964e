"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``us_per_call`` is the wall
time of the underlying model evaluation on this host; ``derived`` carries
the reproduced quantity vs the paper's reported value.

  table1_chip_summary    Table I  : power / GOPS / TOPS/W grid
  fig4_aer_overhead      Fig 4    : AER vs raw break-even sparsity
  fig5_sparsity_profile  Fig 5    : per-layer input sparsity of both SNNs
  fig10_switching        Fig 10   : even/odd batching energy amortization
  fig13_pipeline         Fig 13   : async handshake vs rigid-sync makespan
  fig14_energy_breakdown Fig 14   : component energy at 75% / 95% sparsity
  fig16_accuracy_energy  Fig 16   : accuracy/energy trade-off at 4/6/8 bit
  fig17_sparsity_sweep   Fig 17   : peak GOPS + TOPS/W vs sparsity x precision
  spike_gemm_kernel      (TPU adaptation): zero-skip kernel tile-skip rates
  engine_zero_skip       (TPU adaptation): fused multi-timestep engine —
                         zero-skip vs dense ablation at several sparsity
                         levels, exactness vs the pure-jnp reference
  kernel_blocksparse     (perf gate): the block-sparse fused kernel —
                         zero-skip vs dense vs the jnp oracle at
                         several sparsities, recording measured wall-us
                         NEXT TO the analytic roofline bound
                         (``roofline.analysis.PerfModel``) so
                         ``tools/check_bench.py --tol-roofline`` can gate
                         the measured/bound ratio across PRs
  streaming_occupancy    (serving): chunked stateful streaming vs
                         whole-stream batch at several occupancy levels —
                         throughput, latency, and exactness of the
                         persistent-Vmem session path
  fleet_scaling          (serving): spidr.serve fleet of 1/2/4 engine
                         replicas under an open-loop arrival process —
                         p50/p99 chunk latency, streams/s, shed rate,
                         live-migration count, with every completed
                         stream gated bit-exact vs a whole-stream run
  compiler_multicore     (compiler): single- vs 4-core compiled execution
                         at 60/90/95% input sparsity — exactness, per-core
                         cycles, routing overhead, load imbalance
  qat_sweep              (train->deploy): deploy-exact QAT training at
                         every weight/Vmem precision pair, exported and
                         compiled onto 1 and 4 cores — deployed
                         accuracy/AEE vs modeled cycles/energy, with the
                         train->deploy round trip asserted bit-exact
  facade_overhead        (api): spidr-facade dispatch cost vs a direct
                         jitted engine call — asserts the unified
                         deployment API adds <1% wall time
  telemetry_overhead     (obs): instrumented streaming tick with telemetry
                         hard-off vs disabled (the default) vs fully
                         enabled — asserts the disabled-mode hooks add
                         <1% to ``StreamSessionManager.step``

Every ablation deploys through the unified ``repro.spidr`` facade
(``DeployTarget`` -> ``spidr.compile`` -> ``CompiledSNN``) — the same
entry path as the launchers, examples and docs.

``python benchmarks/run.py`` runs everything; ``--streaming`` runs only the
streaming-vs-whole-stream ablation; ``--qat-sweep`` only the train->deploy
precision sweep; ``--facade-overhead`` only the facade micro-bench;
``--perf`` only the block-sparse kernel perf ablation; ``--smoke`` runs a
reduced compiler/engine/QAT/facade/kernel subset sized for CI.

Ablations that feed the cross-PR perf trajectory also append
machine-readable records to ``BENCH_compiler.json`` (``--out`` to
relocate): one object per ablation with cycles, energy, wall time and
sparsity — ``tools/check_bench.py`` diffs that file against the committed
``benchmarks/baseline.json`` to gate regressions in CI.
"""
from __future__ import annotations

import argparse
import dataclasses
import datetime
import json
import pathlib
import platform
import subprocess
import sys
import time

import numpy as np

# Machine-readable results accumulated across ablations, written to
# ``BENCH_compiler.json`` by ``main`` so the perf trajectory is trackable
# across PRs (CI uploads the file as an artifact).
RESULTS: list = []


def _row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")


def _record(name: str, **fields):
    RESULTS.append({"name": name, **fields})


def _run_meta() -> dict:
    """Provenance stamped into every results file (git sha, versions, host).

    ``tools/check_bench.py`` only reads the ``results`` list, so this key
    rides along without affecting the regression gate — it exists so a
    regression flagged weeks later can be tied to the exact commit,
    dependency set and host that produced the numbers.
    """
    import jax
    import jaxlib

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=pathlib.Path(__file__).resolve().parent,
        ).stdout.strip() or "unknown"
    except OSError:
        sha = "unknown"
    return {
        "git_sha": sha,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "python": platform.python_version(),
        "platform": platform.platform(),
        "argv": sys.argv[1:],
        "timestamp": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
    }


def _write_results(path: str) -> None:
    payload = {
        "schema": 1,
        "suite": "spidr-benchmarks",
        "meta": _run_meta(),
        "results": RESULTS,
    }
    p = pathlib.Path(path)
    p.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {len(RESULTS)} records to {p}")


def _timeit(fn, n=3):
    fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def table1_chip_summary():
    from repro.core.energy import HW, TABLE1_PAPER, gops, power_mw, tops_per_watt

    for hw, key in ((HW(50e6, 0.9), "50MHz_0.9V"), (HW(150e6, 1.0), "150MHz_1.0V")):
        us = _timeit(lambda: power_mw(hw))
        p = power_mw(hw)
        _row(f"table1_power_{key}", us,
             f"model={p:.2f}mW paper={TABLE1_PAPER[key]['power_mw']}mW")
        for bits in (4, 6, 8):
            g = gops(0.95, bits, hw.freq_hz)
            tw = tops_per_watt(0.95, bits, hw)
            _row(
                f"table1_{key}_{bits}b", 0.0,
                f"GOPS={g:.2f}/{TABLE1_PAPER[key]['gops'][bits]} "
                f"TOPSW={tw:.2f}/{TABLE1_PAPER[key]['topsw'][bits]}",
            )


def fig4_aer_overhead():
    from repro.core.zero_skip import aer_breakeven_sparsity, aer_overhead

    n = 288 * 384 * 2  # optical-flow input layer positions
    us = _timeit(lambda: aer_overhead(n, 0.9))
    brk = aer_breakeven_sparsity(n)
    _row("fig4_breakeven", us, f"sparsity={brk:.3f} paper~0.947")
    for s in (0.6, 0.8, 0.9, 0.947, 0.99):
        _row(f"fig4_overhead_s{int(s*1000)}", 0.0,
             f"aer/raw={aer_overhead(n, s):.2f}")


def fig5_sparsity_profile():
    import jax

    from repro.core.network import gesture_net, init_params, run_snn
    from repro.core.quant import QuantSpec
    from repro.snn.data import make_gesture_batch

    spec = gesture_net()
    params = init_params(jax.random.PRNGKey(0), spec)
    ev, _ = make_gesture_batch(jax.random.PRNGKey(1), batch=2, timesteps=8,
                               hw=(64, 64))

    def run():
        return run_snn(params, ev, spec, QuantSpec(4), record_spikes=True)[1]

    us = _timeit(run, n=1)
    counts = np.asarray(run())  # (T, layers)
    sizes = []
    h = w = 64
    for l in spec.layers:
        if l.kind == "conv":
            sizes.append(2 * h * w * l.c_out)
        elif l.kind == "pool":
            h, w = h // 2, w // 2
        elif l.kind == "adaptive_pool":
            h = w = l.target_hw
        elif l.kind == "fc":
            sizes.append(2 * l.c_out)
    for i, sz in enumerate(sizes):
        sp = 1.0 - counts[:, i].mean() / sz * 8  # per-timestep mean over T...
        sp = max(0.0, min(1.0, 1.0 - counts[:, i].mean() / (sz / 8)))
        _row(f"fig5_layer{i}_sparsity", us if i == 0 else 0.0, f"sparsity={sp:.3f}")


def fig10_switching():
    from repro.core.energy import energy_per_op_batched
    from repro.core.s2a import S2AConfig, simulate_s2a

    rng = np.random.default_rng(0)
    m = (rng.random((128, 16)) < 0.15).astype(np.int8)
    us = _timeit(lambda: simulate_s2a(m, S2AConfig(16)), n=1)
    st = simulate_s2a(m, S2AConfig(16))
    reduction = energy_per_op_batched(1) / energy_per_op_batched(15)
    _row("fig10_batch15_reduction", us,
         f"energy_ratio={reduction:.2f} paper=1.5")
    _row("fig10_fifo16_runlen", 0.0,
         f"mean_run={st.mean_run_length:.1f} switches={st.switches}")
    for b in (1, 2, 4, 8, 15, 16, 32):
        _row(f"fig10_eop_b{b}", 0.0, f"E/op={energy_per_op_batched(b):.3f}")


def fig13_pipeline():
    from repro.core.pipeline import simulate_pipeline

    rng = np.random.default_rng(0)
    cc = rng.integers(100, 900, (20, 9))
    us = _timeit(lambda: simulate_pipeline(cc), n=2)
    res = simulate_pipeline(cc)
    _row("fig13_async_speedup", us,
         f"vs_sync={res.speedup_vs_sync:.2f}x util={res.cm_utilization.mean():.2f}")


def fig14_energy_breakdown():
    from repro.core.energy import chunk_energy_breakdown_nj

    us = _timeit(lambda: chunk_energy_breakdown_nj(0.75))
    for s in (0.75, 0.95):
        br = chunk_energy_breakdown_nj(s)
        total = sum(br.values())
        parts = " ".join(f"{k}={v/total:.2f}" for k, v in br.items())
        _row(f"fig14_breakdown_s{int(s*100)}", us if s == 0.75 else 0.0,
             f"total={total:.1f}nJ {parts}")
    e75 = sum(chunk_energy_breakdown_nj(0.75).values())
    e95 = sum(chunk_energy_breakdown_nj(0.95).values())
    _row("fig14_75_to_95_reduction", 0.0,
         f"ratio={e75/e95:.2f} paper>2.0")


def fig16_accuracy_energy(steps: int = 120):
    """Accuracy/energy trade-off at 4/6/8-bit (trend; synthetic data)."""
    import jax

    from repro.core.energy import chunk_energy_total_nj
    from repro.core.network import gesture_net
    from repro.snn.data import make_gesture_batch
    from repro.snn.train import TrainConfig, evaluate, init_train_state, train_step

    spec = gesture_net()
    for bits in (4, 6, 8):
        cfg = TrainConfig(weight_bits=bits, lr=4e-3)
        state = init_train_state(jax.random.PRNGKey(0), spec, cfg)
        key = jax.random.PRNGKey(1)
        t0 = time.perf_counter()
        for step in range(steps):
            key, k = jax.random.split(key)
            ev, lbl = make_gesture_batch(k, batch=8, timesteps=5, hw=(64, 64))
            state, m = train_step(state, (ev, lbl), spec, cfg)
        us = (time.perf_counter() - t0) / steps * 1e6
        key, k = jax.random.split(key)
        ev, lbl = make_gesture_batch(k, batch=32, timesteps=5, hw=(64, 64))
        acc = evaluate(state.params, [(ev, lbl)], spec, cfg)
        # Energy per inference from the calibrated model: chunks x E_chunk.
        # 20 timesteps, measured layer mapping -> chunk count per timestep.
        from repro.core.modes import CoreConfig, map_layer
        from repro.core.quant import QuantSpec

        core = CoreConfig(QuantSpec(bits))
        passes = sum(map_layer(ls, core).total_passes for ls in spec.layer_shapes())
        e_inf = passes * spec.timesteps * chunk_energy_total_nj(0.95) / 1e3  # uJ
        # The optical-flow net (32 ch) shows the precision->passes effect the
        # paper plots (gesture's 16 channels fit one pass at every precision).
        from repro.core.network import optical_flow_net

        fspec = optical_flow_net()
        fpasses = sum(map_layer(ls, core).total_passes for ls in fspec.layer_shapes())
        e_flow = fpasses * fspec.timesteps * chunk_energy_total_nj(0.95) / 1e6  # mJ
        _row(f"fig16_{bits}b", us,
             f"gesture_acc={acc:.2f} gesture_E={e_inf:.1f}uJ flow_E={e_flow:.2f}mJ")


def fig17_sparsity_sweep():
    from repro.core.energy import HW, gops, tops_per_watt

    us = _timeit(lambda: gops(0.9, 4))
    for bits in (4, 6, 8):
        for s in (0.6, 0.7, 0.8, 0.9, 0.95, 0.99):
            _row(f"fig17_{bits}b_s{int(s*100)}", us if s == 0.6 else 0.0,
                 f"GOPS={gops(s, bits, 150e6):.1f} TOPSW={tops_per_watt(s, bits, HW(50e6, 0.9)):.2f}")


def spike_gemm_kernel():
    """TPU-adaptation ablation: tile zero-skip on REAL event structure.

    Unstructured Bernoulli sparsity never empties a 128x128 tile (measured
    0% skip) — but DVS events are spatially clustered, and after im2col the
    cluster structure makes whole fan-in tiles empty.  This is the finding
    recorded in DESIGN.md §2: the S2A's per-event skip transfers to the MXU
    only at tile granularity and only because event data is clustered.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.layers import im2col
    from repro.core.zero_skip import tile_skip_fraction
    from repro.kernels.ref import spike_gemm_ref
    from repro.kernels.spike_gemm import spike_gemm
    from repro.snn.data import make_gesture_batch

    rng = np.random.default_rng(0)
    # Clustered events from the DVS synthesizer -> im2col spike matrix.
    ev, _ = make_gesture_batch(jax.random.PRNGKey(0), batch=1, timesteps=1,
                               hw=(128, 128))
    cols = np.asarray(im2col(ev[0], 3, 3, 1, 1)[0], np.int8)  # (P, 18)
    m = cols[: (cols.shape[0] // 128) * 128]
    w = rng.integers(-8, 8, (m.shape[1], 48)).astype(np.int8)
    sparsity = float((m == 0).mean())
    for tile in ((128, 18), (8, 18)):
        frac = tile_skip_fraction(m, tile)
        _row(f"spike_gemm_dvs_tile{tile[0]}x{tile[1]}", 0.0,
             f"sparsity={sparsity:.3f} tiles_skipped={frac:.2f}")
    out = spike_gemm(jnp.array(m), jnp.array(w), interpret=True)
    ok = bool((np.asarray(out) == np.asarray(
        spike_gemm_ref(jnp.array(m), jnp.array(w)))).all())
    us = _timeit(
        lambda: spike_gemm(jnp.array(m), jnp.array(w), interpret=True).block_until_ready(),
        n=1,
    )
    _row("spike_gemm_dvs_exact", us, f"exact={ok}")
    # Unstructured control: shows WHY clustering matters.
    for s in (0.95, 0.99):
        mr = (rng.random((512, 512)) > s).astype(np.int8)
        frac = tile_skip_fraction(mr, (128, 128))
        frac8 = tile_skip_fraction(mr, (8, 128))
        _row(f"spike_gemm_iid_s{int(s*100)}", 0.0,
             f"tiles128_skipped={frac:.2f} tiles8_skipped={frac8:.2f}")


def engine_zero_skip():
    """Fused engine ablation: tile zero-skip vs dense at several sparsities.

    Runs the reduced gesture network end to end (scan over timesteps, fused
    Pallas kernels in interpret mode) on Bernoulli event streams at 60/90/95%
    input sparsity.  Reports: exactness of the fused zero-skip path vs both
    the dense fused path and the pure-jnp reference, the fraction of
    (block_m x block_k) spike tiles the kernel skips at the first layer, and
    wall time per stream for skip vs dense.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro import spidr
    from repro.configs import spidr_gesture
    from repro.core.layers import im2col
    from repro.core.network import init_params
    from repro.core.zero_skip import tile_skip_fraction

    spec = spidr_gesture.reduced(hw=(32, 32), timesteps=3)
    params = init_params(jax.random.PRNGKey(0), spec)
    block = (128, 128, 128)
    target = spidr.DeployTarget(weight_bits=4, backend="fused",
                                interpret=True, block=block)
    skip_eng = spidr.compile(spec, params, target)
    dense_eng = spidr.compile(spec, params,
                              dataclasses.replace(target, skip_empty=False))
    ref_eng = spidr.compile(spec, params,
                            dataclasses.replace(target, backend="reference"))

    rng = np.random.default_rng(0)
    for s in (0.60, 0.90, 0.95):
        ev = jnp.asarray(
            (rng.random((spec.timesteps, 1) + spec.input_hw + (2,)) > s)
            .astype(np.float32)
        )
        out = skip_eng.run(ev)
        us = _timeit(lambda: jax.block_until_ready(skip_eng.run(ev)), n=1)
        us_dense = _timeit(
            lambda: jax.block_until_ready(dense_eng.run(ev)), n=1
        )
        dense = dense_eng.run(ev)
        ref = ref_eng.run(ev)
        exact = bool(
            (np.asarray(out.readout) == np.asarray(dense.readout)).all()
            and (np.asarray(out.readout) == np.asarray(ref.readout)).all()
            and (np.asarray(out.spike_counts)
                 == np.asarray(ref.spike_counts)).all()
        )
        cols = np.asarray(im2col(ev[0], 3, 3, 1, 1)[0], np.int8)
        frac = tile_skip_fraction(cols, (block[0], cols.shape[1]))
        cost = skip_eng.cost(out)
        _row(f"engine_s{int(s*100)}_skip", us,
             f"exact={exact} tiles_skipped={frac:.2f} "
             f"chip_uJ={cost.energy_uj:.1f}")
        _row(f"engine_s{int(s*100)}_dense", us_dense,
             f"skip_vs_dense_wall={us_dense/max(us,1):.2f}x")


def _clustered_events(rng, timesteps, hw, sparsity, batch=1):
    """DVS-like clustered event frames at a target global sparsity.

    Real event streams are spatially clustered — a moving edge lights a
    patch, not i.i.d. pixels — and that clustering is what empties whole
    (bm x bk) im2col tiles (DESIGN.md: Bernoulli sparsity at the same
    level never empties a 128-wide tile, measured 0% skip).  Each
    timestep actives one moving square patch at ~50% internal density,
    sized so the frame-global sparsity hits ``sparsity``.
    """
    h, w = hw
    budget = (1.0 - sparsity) * h * w * 2   # active sites per timestep
    side = min(h, max(2, int(np.ceil(np.sqrt(budget)))))
    density = budget / (2 * side * side)
    ev = np.zeros((timesteps, batch) + hw + (2,), np.float32)
    for t in range(timesteps):
        y = (t * 7) % max(1, h - side + 1)
        x = (t * 11) % max(1, w - side + 1)
        for b in range(batch):
            patch = (rng.random((side, side, 2)) < density).astype(np.float32)
            ev[t, b, y:y + side, x:x + side] = patch
    return ev


def kernel_blocksparse(smoke: bool = False):
    """Perf-gate ablation: the block-sparse fused kernel.

    Runs the reduced gesture network through three schedules of the SAME
    computation — the per-timestep fused kernel with block skipping, the
    same kernel dense (``skip_empty=False``) and the pure-jnp oracle — on
    clustered DVS-like event streams at several global sparsities,
    asserting all three bit-exact.  Next to the measured wall time it
    records the analytic roofline bound from
    ``roofline.analysis.PerfModel`` (via ``CompiledSNN.roofline``),
    priced with the MEASURED first-layer nonzero-tile fraction
    (``kernels.spike_tile_bitmap`` over the im2col spike matrix).  The
    bound is an ideal-hardware floor — interpret-mode CPU wall clock sits
    far above it — so ``tools/check_bench.py`` gates the measured/bound
    RATIO against the committed baseline: the bound normalizes
    shape/sparsity/tiling out of the wall clock, and a ratio regression
    means the implementation got slower relative to what the dataflow
    says it should cost.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro import spidr
    from repro.configs import spidr_gesture
    from repro.core.layers import im2col
    from repro.core.network import init_params
    from repro.kernels.fused_lif_gemm import spike_tile_bitmap

    hw = (16, 16) if smoke else (32, 32)
    timesteps = 4 if smoke else 6
    spec = spidr_gesture.reduced(hw=hw, timesteps=timesteps)
    params = init_params(jax.random.PRNGKey(0), spec)
    block = (128, 128, 128)
    pert_target = spidr.DeployTarget(
        weight_bits=4, backend="fused", interpret=True, block=block,
        skip_empty=True)
    pert_eng = spidr.compile(spec, params, pert_target)
    dense_eng = spidr.compile(spec, params,
                              dataclasses.replace(pert_target,
                                                  skip_empty=False))
    jnp_eng = spidr.compile(spec, params,
                            dataclasses.replace(pert_target, backend="jnp"))
    n_weight_layers = len(spec.layer_shapes())

    rng = np.random.default_rng(0)
    sparsities = (0.95,) if smoke else (0.60, 0.90, 0.95)
    for s in sparsities:
        ev = jnp.asarray(_clustered_events(rng, timesteps, hw, s))
        out_pert = pert_eng.run(ev)
        out_dense = dense_eng.run(ev)
        out_jnp = jnp_eng.run(ev)
        exact = bool(
            (np.asarray(out_pert.readout) == np.asarray(out_dense.readout)).all()
            and (np.asarray(out_pert.readout) == np.asarray(out_jnp.readout)).all()
            and (np.asarray(out_pert.spike_counts)
                 == np.asarray(out_jnp.spike_counts)).all()
        )
        us_dense = _timeit(lambda: jax.block_until_ready(dense_eng.run(ev)), n=1)
        us_pert = _timeit(lambda: jax.block_until_ready(pert_eng.run(ev)), n=1)
        # Measured block sparsity at the input layer: the im2col spike
        # matrix's nonzero-(bm x bk)-tile fraction, the tiles the kernel's
        # zero-skip passes over.  Deeper layers are priced dense (their
        # spike stacks live on-device; pricing them dense only makes the
        # bound a firmer floor — the ratio gate tracks relative change
        # either way).
        cols = jnp.stack([im2col(ev[t], 3, 3, 1, 1)[0] for t in range(timesteps)])
        frac0 = float(spike_tile_bitmap(cols.astype(jnp.int8), block).mean())
        fracs = [frac0] + [1.0] * (n_weight_layers - 1)
        bound_pert = pert_eng.roofline(batch=1, nonzero_tile_fracs=fracs)
        pct = int(s * 100)
        _row(f"kernel_s{pct}_per_t", us_pert,
             f"exact={exact} bound_us={bound_pert['bound_us']:.1f} "
             f"nonzero_tile_frac={frac0:.2f} "
             f"speedup_vs_dense={us_dense / max(us_pert, 1):.2f}x")
        _record(f"kernel_s{pct}_per_t",
                wall_us=float(us_pert), bound_us=float(bound_pert["bound_us"]),
                bytes_moved=float(bound_pert["bytes_moved"]),
                macs=float(bound_pert["macs"]),
                ablation="kernel_blocksparse", sparsity=s,
                nonzero_tile_frac=frac0, exact=exact)


def compiler_multicore(smoke: bool = False):
    """Compiler ablation: single-core vs compiled 4-core execution.

    Runs the reduced gesture network through the multi-core compiler
    (``compile_network`` -> ``compile_engine``) at 60/90/95% input
    sparsity and reports, per sparsity level: bit-exactness of the 4-core
    engine vs the single-core path, wall time for both, the modeled
    single-core makespan vs the multi-core per-core makespans (max =
    plan latency), the spike-routing overhead, and the load-imbalance
    metric.  The crossover is the point: routing costs cycles per spike,
    so the multi-core plan wins only once sparsity (or per-core load)
    is high enough — exactly the trade the partitioner's cost model makes.

    Every level appends machine-readable records (cycles, energy, wall
    time, sparsity) to ``BENCH_compiler.json`` for cross-PR tracking.
    """
    import jax
    import jax.numpy as jnp

    from repro import spidr
    from repro.configs import spidr_gesture
    from repro.core.network import init_params

    hw = (16, 16) if smoke else (32, 32)
    timesteps = 2 if smoke else 4
    n_cores = 4
    spec = spidr_gesture.reduced(hw=hw, timesteps=timesteps)
    params = init_params(jax.random.PRNGKey(0), spec)
    eng = spidr.compile(spec, params, spidr.DeployTarget(backend="jnp"))
    meng = spidr.compile(spec, params,
                         spidr.DeployTarget(backend="jnp", n_cores=n_cores))

    rng = np.random.default_rng(0)
    for s in (0.60, 0.90, 0.95):
        ev = jnp.asarray(
            (rng.random((timesteps, 1) + spec.input_hw + (2,)) > s)
            .astype(np.float32)
        )
        out1 = eng.run(ev)
        out4 = meng.run(ev)
        us1 = _timeit(lambda: jax.block_until_ready(eng.run(ev)), n=1)
        us4 = _timeit(lambda: jax.block_until_ready(meng.run(ev)), n=1)
        exact = bool(
            (np.asarray(out1.readout) == np.asarray(out4.readout)).all()
            and (np.asarray(out1.spike_counts)
                 == np.asarray(out4.spike_counts)).all()
        )
        counts = np.asarray(out1.input_counts)
        c1 = eng.cost(out1)
        c4 = meng.cost(input_counts=counts)
        # Observability invariant: the pipeline-timeline export conserves
        # cycles — per core, summed busy+routing event durations must equal
        # the cost model's busy_cycles exactly (no sampling, no rounding).
        from repro.obs.timeline import busy_cycle_totals

        totals = busy_cycle_totals(meng.pipeline_trace(input_counts=counts))
        timeline_exact = all(
            int(totals.get(core, 0)) == int(c4.busy_cycles[core])
            for core in range(n_cores))
        _row(f"compiler_s{int(s*100)}_1core", us1,
             f"makespan={c1.makespan_cycles} uJ={c1.energy_uj:.1f}")
        _row(
            f"compiler_s{int(s*100)}_{n_cores}core", us4,
            f"exact={exact} makespan={c4.makespan_cycles} "
            f"imbalance={c4.load_imbalance:.2f} "
            f"routing={int(c4.routing_cycles.sum())} "
            f"dup={c4.duplication_cycles} timeline_exact={timeline_exact}",
        )
        _record(
            f"compiler_s{int(s*100)}_1core",
            ablation="compiler_multicore", n_cores=1, sparsity=s,
            cycles=int(c1.makespan_cycles), energy_uj=float(c1.energy_uj),
            wall_us=float(us1), measured_sparsity=float(c1.mean_sparsity),
        )
        _record(
            f"compiler_s{int(s*100)}_{n_cores}core",
            ablation="compiler_multicore", n_cores=n_cores, sparsity=s,
            cycles=int(c4.makespan_cycles), energy_uj=float(c4.energy_uj),
            wall_us=float(us4), measured_sparsity=float(c4.mean_sparsity),
            exact=exact, timeline_exact=timeline_exact,
            per_core_busy_cycles=[int(x) for x in c4.busy_cycles],
            routing_cycles=int(c4.routing_cycles.sum()),
            duplication_cycles=int(c4.duplication_cycles),
            load_imbalance=float(c4.load_imbalance),
        )


def qat_sweep(smoke: bool = False):
    """Train->deploy ablation: the Fig 16 trade-off as a deployable pipeline.

    For each weight/Vmem precision pair (4/7, 6/11, 8/15): train the
    reduced gesture net (plus, in the full run, the reduced optical-flow
    net) with the deploy-exact QAT forward for a smoke budget, fold the
    weights into the engine's integer format (``snn.export``), deploy
    through the multi-core compiler on 1 and 4 cores, and report the
    *deployed* accuracy/AEE together with the modeled cycles/energy — the
    accuracy-vs-energy reconfigurability trade the paper claims (C2).
    Every combination appends a machine-readable record, and a broken
    train->deploy round trip raises — full/nightly runs fail loudly, not
    only through the JSON gate.

    The train+export loop is ``snn.train.precision_sweep`` itself (one
    source of truth); this ablation layers the deployment costs on top.
    """
    import jax
    import jax.numpy as jnp

    from repro import spidr
    from repro.snn.export import dequantize_readout, verify_roundtrip
    from repro.snn.train import (
        TrainConfig, effective_spec, make_batch_fn, precision_sweep, spec_for,
    )

    steps = 4 if smoke else 30
    tasks = ("gesture",) if smoke else ("gesture", "optical-flow")
    for task in tasks:
        spec0 = spec_for(task)
        hw = (16, 16) if (smoke or task != "gesture") else (32, 32)
        cfg0 = TrainConfig(
            lr=4e-3, steps=steps, warmup=1, batch=4 if smoke else 8,
            hw=hw, timesteps=2 if smoke else 4, seed=0, eval_batches=1,
        )
        sweep = precision_sweep(task, bits=(4, 6, 8), cfg=cfg0, spec=spec0)
        for bits, res in sweep.items():
            cfg = dataclasses.replace(cfg0, weight_bits=bits)
            state, history, exported = (res["state"], res["history"],
                                        res["exported"])
            train_us = history["wall_s"] / steps * 1e6
            espec = effective_spec(spec0, cfg)
            # 32 eval samples: the accuracy quantum (1/32) stays below
            # check_bench's default --tol-metric so single-sample flips on
            # a dependency bump cannot trip the CI gate.
            ev, target = make_batch_fn(espec, cfg, batch=32)(
                jax.random.PRNGKey(123))

            eng1 = spidr.compile(exported, state.params,
                                 spidr.DeployTarget(weight_bits=bits),
                                 spec=espec)
            out1 = eng1.run(ev)
            # Reuse the engine output for the QAT parity proof (the full
            # verify() would re-run the engine plus the python-loop
            # reference oracle for results this ablation never records).
            rt = verify_roundtrip(state.params, espec, eng1.engine, ev,
                                  exported, engine_out=out1)
            readout = dequantize_readout(exported, espec, out1.readout)
            if espec.readout == "rate":
                metric, value = "accuracy", float(
                    jnp.mean(jnp.argmax(readout, axis=-1) == target))
            else:
                metric, value = "aee", float(
                    jnp.mean(jnp.linalg.norm(readout - target, axis=-1)))
            counts = np.asarray(out1.input_counts)
            c1 = eng1.cost(out1)

            eng4 = spidr.compile(exported, state.params,
                                 spidr.DeployTarget(weight_bits=bits,
                                                    n_cores=4), spec=espec)
            out4 = eng4.run(ev)
            exact4 = rt.exact and bool(
                (np.asarray(out1.readout) == np.asarray(out4.readout)).all())
            c4 = eng4.cost(input_counts=counts)
            assert rt.exact, (
                f"train->deploy parity broken for {task} @ {bits}b: {rt}")
            assert exact4, (
                f"4-core deployment diverged for {task} @ {bits}b")

            _row(f"qat_{task}_{bits}b", train_us,
                 f"{metric}={value:.3f} roundtrip_exact={rt.exact} "
                 f"loss={history['loss'][-1]:.3f}")
            _row(f"qat_{task}_{bits}b_deploy", 0.0,
                 f"1core_cycles={c1.makespan_cycles} uJ={c1.energy_uj:.2f} "
                 f"4core_cycles={c4.makespan_cycles} uJ={c4.energy_uj:.2f} "
                 f"4core_exact={exact4}")
            common = dict(ablation="qat_sweep", task=task, weight_bits=bits,
                          metric=metric, metric_value=value,
                          train_loss=float(history["loss"][-1]))
            _record(f"qat_{task}_{bits}b_1core", n_cores=1,
                    cycles=int(c1.makespan_cycles),
                    energy_uj=float(c1.energy_uj), exact=bool(rt.exact),
                    wall_us=float(train_us), **common)
            _record(f"qat_{task}_{bits}b_4core", n_cores=4,
                    cycles=int(c4.makespan_cycles),
                    energy_uj=float(c4.energy_uj), exact=exact4,
                    wall_us=float(train_us), **common)


def facade_overhead(smoke: bool = False):
    """Facade micro-bench: ``CompiledSNN.run`` vs a direct jitted engine call.

    The ``spidr`` facade is the single entry path for every launcher,
    benchmark and example, so its dispatch cost must be negligible.  Both
    calls bottom out in the *same* jitted computation, so the facade can
    only add Python-side dispatch; end-to-end wall deltas at the 1% level
    are unmeasurable under scheduler noise (shared CI runners jitter far
    more than that between identical runs).  This ablation therefore
    measures exactly the added term: the async (unblocked) dispatch cost
    of ``CompiledSNN.run`` vs a hand-jitted ``run_engine`` closure over
    the same engine — min over rounds of round-averaged call cost — and
    asserts that delta is under 1% of the blocked whole-run wall time.
    The record lands in ``BENCH_compiler.json`` (``within_budget`` is a
    hard exactness-style gate in ``tools/check_bench.py``).
    """
    import jax
    import jax.numpy as jnp

    from repro import spidr
    from repro.configs import spidr_gesture
    from repro.core.network import init_params
    from repro.engine import run_engine

    spec = spidr_gesture.reduced(hw=(16, 16), timesteps=8)
    params = init_params(jax.random.PRNGKey(0), spec)
    compiled = spidr.compile(spec, params, spidr.DeployTarget(backend="jnp"))
    direct = jax.jit(lambda ev: run_engine(compiled.engine, ev))

    rng = np.random.default_rng(0)
    ev = jnp.asarray(
        (rng.random((spec.timesteps, 8) + spec.input_hw + (2,)) > 0.9)
        .astype(np.float32))
    jax.block_until_ready(compiled.run(ev))   # warm both jit caches
    jax.block_until_ready(direct(ev))

    def dispatch_us(fn, calls=10):
        """Average async dispatch cost per call (enqueue, don't block)."""
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(ev)
        dt = (time.perf_counter() - t0) / calls * 1e6
        jax.block_until_ready(out)
        return dt

    rounds = 5 if smoke else 10
    disp_facade = min(dispatch_us(compiled.run) for _ in range(rounds))
    disp_direct = min(dispatch_us(direct) for _ in range(rounds))
    us_run = float(np.median(
        [_timeit(lambda: jax.block_until_ready(compiled.run(ev)), n=1)
         for _ in range(3)]))
    overhead = max(0.0, disp_facade - disp_direct) / us_run
    within_budget = overhead < 0.01
    _row("facade_overhead", us_run,
         f"dispatch_facade_us={disp_facade:.1f} "
         f"dispatch_direct_us={disp_direct:.1f} "
         f"overhead={overhead*100:.3f}% within_budget={within_budget}")
    _record("facade_overhead", ablation="facade_overhead",
            wall_us=float(us_run), dispatch_facade_us=float(disp_facade),
            dispatch_direct_us=float(disp_direct),
            overhead_frac=float(overhead), within_budget=bool(within_budget))
    assert within_budget, (
        f"facade dispatch added {overhead*100:.2f}% wall time over the "
        "direct jitted engine call (budget: <1%)")


def telemetry_overhead(smoke: bool = False):
    """Telemetry micro-bench: instrumented streaming step, off vs on.

    ``StreamSessionManager.step`` is the serving hot loop, so its telemetry
    hooks must be free when telemetry is off — the default.  Three
    identically-configured managers run the same steady-state tick on the
    same engine: telemetry pinned hard off (``metrics=False, tracer=False``),
    the shipping default (a process-wide registry that is *disabled* — every
    hook reduces to one ``if`` check), and fully enabled (live registry +
    tracer recording every tick).  Per-tick wall time is min-over-rounds of
    round-averaged ticks, the same noise discipline as ``facade_overhead``.

    The hard <1% gate is on the DISABLED mode — the cost the
    instrumentation imposes on users who never asked for telemetry
    (``within_budget`` is exactness-gated in ``tools/check_bench.py``).
    The enabled-mode overhead is recorded alongside for tracking; it does
    real per-tick work (sparsity/occupancy/cycle-delta metrics + one span)
    and is expected to cost ~1%.
    """
    import jax

    from repro import obs, spidr
    from repro.configs import spidr_gesture
    from repro.core.network import init_params
    from repro.engine.streaming import StreamSessionManager

    spec = spidr_gesture.reduced(hw=(16, 16), timesteps=8)
    params = init_params(jax.random.PRNGKey(0), spec)
    compiled = spidr.compile(spec, params, spidr.DeployTarget(backend="jnp"))
    capacity, chunk_T = 4, 2

    rng = np.random.default_rng(0)
    chunks = {i: (rng.random((chunk_T,) + spec.input_hw + (2,)) > 0.9)
              .astype(np.float32) for i in range(capacity)}

    def make(metrics, tracer):
        mgr = StreamSessionManager(compiled.engine, capacity=capacity,
                                   chunk_T=chunk_T, metrics=metrics,
                                   tracer=tracer)
        for _ in range(capacity):
            mgr.open()
        mgr.step(chunks)   # warm the jit cache
        return mgr

    mgr_off = make(False, False)
    mgr_default = make(obs.MetricsRegistry(enabled=False),
                       obs.Tracer(enabled=False))
    mgr_on = make(obs.MetricsRegistry(enabled=True),
                  obs.Tracer(enabled=True))

    def tick_us(mgr, ticks=10):
        t0 = time.perf_counter()
        for _ in range(ticks):
            mgr.step(chunks)
        return (time.perf_counter() - t0) / ticks * 1e6

    # Interleave the three managers within every round: host-load drift
    # between rounds then hits all three equally, and the per-manager min
    # picks each one's best case under the same conditions.
    rounds = 6 if smoke else 10
    samples: dict = {"off": [], "default": [], "on": []}
    for _ in range(rounds):
        samples["off"].append(tick_us(mgr_off))
        samples["default"].append(tick_us(mgr_default))
        samples["on"].append(tick_us(mgr_on))
    t_off = min(samples["off"])
    t_default = min(samples["default"])
    t_on = min(samples["on"])
    overhead_disabled = max(0.0, t_default - t_off) / t_off
    overhead_enabled = max(0.0, t_on - t_off) / t_off
    within_budget = overhead_disabled < 0.01
    _row("telemetry_overhead", t_off,
         f"tick_off_us={t_off:.1f} tick_disabled_us={t_default:.1f} "
         f"tick_enabled_us={t_on:.1f} "
         f"overhead_disabled={overhead_disabled*100:.3f}% "
         f"overhead_enabled={overhead_enabled*100:.3f}% "
         f"within_budget={within_budget}")
    _record("telemetry_overhead", ablation="telemetry_overhead",
            wall_us=float(t_off), tick_disabled_us=float(t_default),
            tick_enabled_us=float(t_on),
            overhead_disabled_frac=float(overhead_disabled),
            overhead_enabled_frac=float(overhead_enabled),
            within_budget=bool(within_budget))
    assert within_budget, (
        f"disabled telemetry added {overhead_disabled*100:.2f}% to the "
        "streaming tick (budget: <1% — the hooks must be free when off)")


def streaming_occupancy():
    """Serving ablation: chunked streaming vs whole-stream batch inference.

    Serves the reduced gesture network at several occupancy levels (how many
    of the session's slots hold live streams).  For each level: wall time and
    per-stream latency through the persistent-Vmem streaming path
    (``StreamSessionManager`` via ``repro.serving.StreamWorker``, chunk_T
    timesteps per tick) vs one whole-stream ``run_engine`` call over the
    same streams,
    plus bit-exactness of the streamed readouts against the whole-stream
    result.  Uses the jnp backend so the numbers measure the serving loop,
    not the Pallas interpreter.
    """
    import jax
    import jax.numpy as jnp

    from repro import spidr
    from repro.configs import spidr_gesture
    from repro.core.network import init_params
    from repro.serving import StreamRequest, StreamWorker
    from repro.snn.data import make_gesture_batch

    spec = spidr_gesture.reduced(hw=(16, 16), timesteps=6)
    params = init_params(jax.random.PRNGKey(0), spec)
    eng = spidr.compile(spec, params, spidr.DeployTarget(backend="jnp"))
    capacity, chunk_T = 4, 3

    ev, _ = make_gesture_batch(jax.random.PRNGKey(1), batch=capacity,
                               timesteps=spec.timesteps, hw=spec.input_hw)
    ev_np = np.asarray(ev)

    for occ in (1, 2, 4):
        whole = eng.run(jnp.asarray(ev_np[:, :occ]))
        # One server per occupancy level: after a drain every slot is free
        # again, so repeated drains measure the steady-state serving loop
        # (the jitted session step compiles once, on the warm-up drain).
        server = StreamWorker(eng, capacity=capacity, chunk_T=chunk_T)

        def drain():
            for r in range(occ):
                server.submit(StreamRequest(rid=r, events=ev_np[:, r]))
            while server.step():
                pass

        us_stream = _timeit(drain, n=2)
        ev_occ = jnp.asarray(ev_np[:, :occ])  # CompiledSNN.run is jitted
        us_whole = _timeit(
            lambda: jax.block_until_ready(eng.run(ev_occ)), n=2)
        done = {r.rid: r for r in server.done[-occ:]}
        exact = all(
            (np.asarray(done[r].readout) == np.asarray(whole.readout)[r]).all()
            for r in range(occ)
        )
        lat = [r.done_at - r.submitted_at for r in server.done[-occ:]]
        _row(
            f"streaming_occ{occ}of{capacity}", us_stream,
            f"exact={exact} streams_per_s={occ / (us_stream / 1e6):.1f} "
            f"p50_latency_ms={np.median(lat) * 1e3:.1f} "
            f"whole_stream_us={us_whole:.0f} "
            f"stream_vs_whole={us_stream / max(us_whole, 1):.2f}x",
        )


def fleet_scaling(smoke: bool = False, trace_out: str = None):
    """Serving-fleet ablation: throughput/latency scaling across replicas.

    Drives ``spidr.serve`` end to end: a synthetic open-loop arrival
    process submits DVS streams into a sync-mode fleet of 1/2/4 engine
    replicas (1024 streams in the full run, 48 in ``--smoke``) with a
    bounded admission queue, so the run exercises scheduling, explicit
    load shedding (``FleetOverloaded``) and at least one live cross-replica
    migration per multi-replica point.  Reports p50/p99 per-chunk (fleet
    tick) latency, streams/sec, shed rate and migration count, and gates
    exactness: every completed stream's readout — including migrated and
    re-placed ones — must match a whole-stream ``CompiledSNN.run`` of the
    same events bit for bit.  ``trace_out`` additionally exports the
    fleet's Chrome trace (serve.tick + fleet.migrate spans) for the CI
    artifact.
    """
    import jax
    import jax.numpy as jnp

    from repro import obs, spidr
    from repro.configs import spidr_gesture
    from repro.core.network import init_params
    from repro.serving import FleetOverloaded
    from repro.snn.data import make_gesture_batch

    if trace_out:
        obs.enable_tracing()

    spec = spidr_gesture.reduced(hw=(16, 16), timesteps=6)
    params = init_params(jax.random.PRNGKey(0), spec)
    compiled = spidr.compile(spec, params, spidr.DeployTarget(backend="jnp"))

    if smoke:
        n_streams, capacity, chunk_T = 48, 2, 3
        burst, max_queue, replica_counts = 4, 8, (1, 2)
    else:
        n_streams, capacity, chunk_T = 1024, 8, 3
        burst, max_queue, replica_counts = 6, 32, (1, 2, 4)

    # A bank of distinct synthetic streams, cycled over by rid.  Stream
    # lengths alternate between the full window and half of it (variable-
    # length DVS streams stagger completions, so slots free up while other
    # streams still run — the window live migration needs).  The
    # whole-stream run of the bank at each length is the bit-exactness
    # reference for every completed stream (migrated ones included).
    bank = 16
    lengths = (spec.timesteps, spec.timesteps // 2)
    ev, _ = make_gesture_batch(jax.random.PRNGKey(1), batch=bank,
                               timesteps=spec.timesteps, hw=spec.input_hw)
    ev_np = np.asarray(ev)
    whole = {length: np.asarray(compiled.run(
        jnp.asarray(ev_np[:length])).readout) for length in set(lengths)}

    def _events(rid):
        return ev_np[:lengths[rid % len(lengths)], rid % bank]

    for r in replica_counts:
        fleet = spidr.serve(compiled, spidr.ServeConfig(
            n_replicas=r, capacity=capacity, chunk_T=chunk_T,
            max_queue=max_queue, migrate_every=8))
        tick_s: list = []
        shed = 0
        i = 0
        t0 = time.perf_counter()
        while True:
            for _ in range(burst):
                if i >= n_streams:
                    break
                try:
                    fleet.submit(_events(i), rid=i)
                except FleetOverloaded:
                    shed += 1
                i += 1
            t1 = time.perf_counter()
            alive = fleet.step()
            tick_s.append(time.perf_counter() - t1)
            if r > 1 and fleet.migrations == 0 \
                    and any(w.slots for w in fleet.workers):
                # Force one live migration per multi-replica point (the
                # backlogged phase has no free slot; the drain tail does).
                try:
                    fleet.migrate()
                except (RuntimeError, ValueError):
                    pass
            if i >= n_streams and not alive:
                break
        wall_s = time.perf_counter() - t0
        done = fleet.done
        exact = all(
            np.array_equal(
                np.asarray(req.readout),
                whole[lengths[req.rid % len(lengths)]][req.rid % bank])
            for req in done)
        fleet.shutdown()

        p50 = float(np.percentile(tick_s, 50) * 1e3)
        p99 = float(np.percentile(tick_s, 99) * 1e3)
        shed_rate = shed / max(n_streams, 1)
        name = f"fleet_r{r}" + ("_smoke" if smoke else "")
        _row(name, wall_s * 1e6 / max(len(tick_s), 1),
             f"exact={exact} completed={len(done)}/{n_streams} "
             f"shed_rate={shed_rate:.3f} migrations={fleet.migrations} "
             f"streams_per_s={len(done) / wall_s:.1f} "
             f"p50_chunk_ms={p50:.2f} p99_chunk_ms={p99:.2f}")
        rec = dict(
            ablation="fleet_scaling", replicas=r, streams=n_streams,
            completed=len(done), shed=shed, shed_rate=round(shed_rate, 4),
            migrations=fleet.migrations,
            streams_per_s=round(len(done) / wall_s, 2),
            p50_chunk_ms=round(p50, 3), p99_chunk_ms=round(p99, 3),
            exact=bool(exact))
        if r > 1:
            rec["migration_exact"] = bool(exact and fleet.migrations > 0)
        _record(name, **rec)
        if trace_out and r == replica_counts[-1]:
            obs.default_tracer().export(trace_out)
            print(f"# fleet chrome trace written to {trace_out}")


ALL = [
    table1_chip_summary,
    fig4_aer_overhead,
    fig5_sparsity_profile,
    fig10_switching,
    fig13_pipeline,
    fig14_energy_breakdown,
    fig16_accuracy_energy,
    fig17_sparsity_sweep,
    spike_gemm_kernel,
    engine_zero_skip,
    kernel_blocksparse,
    streaming_occupancy,
    fleet_scaling,
    compiler_multicore,
    qat_sweep,
    facade_overhead,
    telemetry_overhead,
]

# CI-sized subset: every ablation that feeds BENCH_compiler.json, on
# reduced shapes (a compiled-path or train->deploy regression fails this
# job visibly).
SMOKE = [lambda: compiler_multicore(smoke=True), lambda: qat_sweep(smoke=True),
         lambda: facade_overhead(smoke=True),
         lambda: kernel_blocksparse(smoke=True),
         lambda: telemetry_overhead(smoke=True)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--streaming", action="store_true",
                    help="run only the streaming-vs-whole-stream ablation")
    ap.add_argument("--qat-sweep", action="store_true",
                    help="run only the train->deploy precision sweep")
    ap.add_argument("--facade-overhead", action="store_true",
                    help="run only the spidr-facade dispatch micro-bench "
                         "(asserts <1%% overhead vs direct engine calls)")
    ap.add_argument("--perf", action="store_true",
                    help="run only the block-sparse kernel perf ablation "
                         "(wall-us vs roofline bound, for the CI perf gate)")
    ap.add_argument("--fleet", action="store_true",
                    help="run only the spidr.serve fleet-scaling ablation "
                         "(1k streams over 1/2/4 replicas; p50/p99 chunk "
                         "latency, streams/s, shed rate, migration "
                         "exactness; --smoke serves a CI-sized subset)")
    ap.add_argument("--fleet-trace-out", default=None, dest="fleet_trace_out",
                    help="--fleet: also export the fleet's Chrome trace "
                         "(serve.tick/fleet.migrate spans) to this path")
    ap.add_argument("--telemetry-overhead", action="store_true",
                    dest="telemetry_overhead",
                    help="run only the telemetry micro-bench (asserts "
                         "disabled-mode hooks add <1%% to the streaming "
                         "tick)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized subset of the tracked ablations")
    ap.add_argument("--out", default="BENCH_compiler.json",
                    help="path for the machine-readable results JSON")
    args = ap.parse_args()
    if args.streaming:
        fns = [streaming_occupancy]
    elif args.qat_sweep:
        fns = [lambda: qat_sweep(smoke=args.smoke)]
    elif args.facade_overhead:
        fns = [lambda: facade_overhead(smoke=args.smoke)]
    elif args.perf:
        fns = [lambda: kernel_blocksparse(smoke=args.smoke)]
    elif args.fleet:
        fns = [lambda: fleet_scaling(smoke=args.smoke,
                                     trace_out=args.fleet_trace_out)]
    elif args.telemetry_overhead:
        fns = [lambda: telemetry_overhead(smoke=args.smoke)]
    elif args.smoke:
        fns = SMOKE
    else:
        fns = ALL
    print("name,us_per_call,derived")
    for fn in fns:
        fn()
    if RESULTS:
        _write_results(args.out)


if __name__ == "__main__":
    main()
