"""End-to-end training driver.

Runs REAL training on whatever devices exist (CPU here, TPU pod in prod):
  python -m repro.launch.train --arch qwen1.5-0.5b --reduced --steps 50
  python -m repro.launch.train --snn gesture --weight-bits 4 --steps 200
  python -m repro.launch.train --snn optical-flow --weight-bits 8 --reduced

LM archs train on the synthetic token pipeline; the paper's SNNs train on
synthetic DVS streams.  ``--snn`` runs the full train->deploy QAT pipeline:
deploy-exact surrogate-gradient training (``snn.train.fit``), export into
the engine's signed-integer format, checkpoint of both the float params and
the integer artifact, and a round-trip proof that the deployed engine
reproduces the training graph's spike trains bit-exactly (on 1 core and,
when ``--n-cores`` > 1, on the compiled multi-core plan).  Fault tolerance:
checkpoint every N steps, watchdog, straggler stats; resume is automatic
from the checkpoint directory.
"""
from __future__ import annotations

import argparse
import logging
import time

import jax

from repro.checkpoint.checkpoint import Checkpointer
from repro.configs.base import get_config
from repro.data.pipeline import TokenPipeline
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.runtime.compile_cache import configure_compile_cache
from repro.runtime.loop import LoopConfig, TrainingLoop

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
log = logging.getLogger("repro.train")


def train_lm(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh()
    log.info("arch=%s params=%.2fM mesh=%s", cfg.name, cfg.param_count() / 1e6,
             dict(mesh.shape))

    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(key, cfg)
    opt_state = M.init_opt_state(params)

    train_step = M.make_train_step(cfg, lr=args.lr)
    with mesh:
        jitted = jax.jit(train_step, donate_argnums=(0, 1))

        pipe = TokenPipeline(
            batch=args.batch, seq_len=args.seq, vocab=cfg.vocab_size,
            seed=args.seed, embeds_dim=0 if cfg.embed_inputs else cfg.d_model,
        )
        ckpt = Checkpointer(args.ckpt_dir)
        loop = TrainingLoop(
            step_fn=lambda p, o, s, b: jitted(p, o, s, b),
            batch_fn=pipe.batch_at,
            checkpointer=ckpt,
            cfg=LoopConfig(
                total_steps=args.steps,
                checkpoint_every=args.ckpt_every,
                watchdog_deadline_s=args.watchdog_s,
            ),
        )
        t0 = time.time()
        params, opt_state, history = loop.run(params, opt_state)
        dt = time.time() - t0
    log.info(
        "done: %d steps in %.1fs; loss %.4f -> %.4f; stragglers=%d restarts=%d",
        args.steps, dt, history[0], history[-1],
        loop.stragglers.flagged, loop.restarts,
    )
    return history


def train_snn(args):
    """The train->deploy QAT pipeline for the paper's SNNs.

    fit (deploy-exact QAT) -> export integers -> checkpoint both artifacts
    -> reload -> deploy through the compiler -> prove bit-exact parity.
    """
    import os

    from repro import spidr
    from repro.snn.export import export_network
    from repro.snn.train import (
        TrainConfig, effective_spec, fit, make_batch_fn, spec_for,
    )

    task = args.snn or args.arch.removeprefix("spidr-")
    spec = spec_for(task)
    hw = (32, 32) if args.reduced and spec.readout == "rate" else None
    hw = (24, 32) if args.reduced and spec.readout == "vmem" else hw
    tcfg = TrainConfig(
        weight_bits=args.weight_bits, lr=args.lr, steps=args.steps,
        batch=args.batch, seed=args.seed,
        hw=hw, timesteps=5 if args.reduced else None,
        ckpt_every=args.ckpt_every,
    )
    ckpt = Checkpointer(args.ckpt_dir)
    state, history = fit(spec, tcfg, ckpt=ckpt)

    # Fold into the integer engine format and persist both artifacts: the
    # facade's save/load ride on the snn.export checkpoint format.
    from repro.core.quant import QuantSpec

    run_spec = effective_spec(spec, tcfg)
    exported = export_network(state.params, run_spec, QuantSpec(args.weight_bits))
    export_dir = os.path.join(args.ckpt_dir, "exported")
    spidr.compile(
        exported, run_spec,
        spidr.DeployTarget(weight_bits=args.weight_bits),
    ).save(export_dir, step=args.steps)

    # Round-trip proof on a fresh stream, single- and multi-core, through
    # the reloaded artifact (what production would actually deploy).
    ev, _ = make_batch_fn(run_spec, tcfg, batch=2)(jax.random.PRNGKey(99))
    for n_cores in sorted({1, args.n_cores}):
        target = spidr.DeployTarget(weight_bits=args.weight_bits,
                                    n_cores=n_cores)
        compiled = spidr.load(export_dir, spec=run_spec, target=target)
        report = compiled.verify(ev, params=state.params)
        rt = report.roundtrip
        log.info("round-trip %d-core: exact=%s (readout_mismatch=%g, "
                 "spike_mismatch=%d)", n_cores, report.exact,
                 rt.readout_mismatch, rt.spike_mismatch)
        if not report.exact:
            raise SystemExit(
                f"train->deploy parity broken on {n_cores} core(s): {report}")
    log.info("done: loss %.4f -> %.4f, %s=%.4f; exported %d-bit integers "
             "to %s", history["loss"][0], history["loss"][-1],
             history["metric"], history["final"], args.weight_bits,
             export_dir)
    return history["loss"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="LM arch name, or spidr-gesture / spidr-optical-flow")
    ap.add_argument("--snn", choices=("gesture", "optical-flow"), default=None,
                    help="train one of the paper's SNNs through the "
                         "train->export->deploy QAT pipeline")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--weight-bits", type=int, default=4, choices=(4, 6, 8))
    ap.add_argument("--n-cores", type=int, default=1,
                    help="also prove parity on a compiled n-core plan")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--watchdog-s", type=float, default=3600.0)
    args = ap.parse_args()
    if args.snn is None and args.arch is None:
        ap.error("pass --snn gesture|optical-flow or --arch <name>")
    configure_compile_cache()
    if args.snn or args.arch.startswith("spidr-"):
        train_snn(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
