"""Surrogate-gradient BPTT training for the paper's SNNs (QAT at 4/6/8 bit).

The accelerator needs no modified training methodology (Table III row
"Modified Training: No") — networks are trained offline with standard
surrogate-gradient BPTT + quantization-aware weights, then deployed
bit-exactly (digital CIM).  This module is that offline trainer:

  loss = cross-entropy over rate-coded output spikes   (gesture)
         average endpoint error (AEE) on final Vmem    (optical flow)

The spike nonlinearity's triangle surrogate lives in ``core.neuron``; the
weight fake-quant STE in ``core.quant``; both are exercised here through
``core.network.run_snn`` so training and deployment share one definition.
The default training mode is ``"qat"`` — the *deploy-exact* forward
(per-channel power-of-two fake quant, scaled Vmem saturation, digital leak
shift) whose spike trains are bit-identical to the exported integer engine
(see ``snn.export``); ``mode="train"`` keeps the legacy float-dynamics STE
path for ablations.

Three layers of API:

  * ``train_step`` / ``evaluate``     — one jitted scan-over-T batched
    update / metric pass (the building blocks).
  * ``fit``                           — full training run on the synthetic
    DVS streams: cosine LR schedule with warmup, periodic eval, optional
    checkpointing of the float params.
  * ``precision_sweep``               — the Fig 16 driver: train + export
    at every supported weight/Vmem precision pair (4/7, 6/11, 8/15) for
    either head, returning the trained state, the exported integers and
    the eval metric per precision.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..checkpoint.checkpoint import Checkpointer
from ..core.network import (
    SNNSpec,
    gesture_net,
    init_params,
    optical_flow_net,
    run_snn,
)
from ..core.quant import QuantSpec
from ..optim.optimizer import (
    adamw,
    apply_updates,
    clip_by_global_norm,
    linear_warmup_cosine,
)

__all__ = [
    "TrainConfig",
    "TrainState",
    "effective_spec",
    "evaluate",
    "fit",
    "init_train_state",
    "make_batch_fn",
    "precision_sweep",
    "spec_for",
    "train_step",
]

log = logging.getLogger("repro.snn.train")


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Hashable (jit-static) training configuration."""

    weight_bits: int = 4
    mode: str = "qat"            # "qat" (deploy-exact) | "train" (legacy)
    lr: float = 1e-3
    weight_decay: float = 1e-4
    grad_clip: float = 1.0
    # Schedule / loop shape (used by ``fit``; ``train_step`` only needs the
    # schedule fields).
    steps: int = 100
    warmup: int = 10
    lr_final_frac: float = 0.1
    batch: int = 8
    timesteps: Optional[int] = None     # None -> spec.timesteps
    hw: Optional[tuple] = None          # None -> spec.input_hw
    eval_every: int = 0                 # 0 = eval only at the end
    eval_batch: int = 32
    eval_batches: int = 2
    ckpt_every: int = 0                 # 0 = no checkpointing
    seed: int = 0

    def __post_init__(self):
        assert self.mode in ("qat", "train"), self.mode


@dataclasses.dataclass
class TrainState:
    params: list
    opt_state: dict
    step: int


def init_train_state(key, spec: SNNSpec, cfg: TrainConfig) -> TrainState:
    spec.require_chain("snn.train")
    params = init_params(key, spec)
    _, opt_state = adamw(lr=cfg.lr, weight_decay=cfg.weight_decay, params=params)
    return TrainState(params=params, opt_state=opt_state, step=0)


def _loss_fn(params, batch, spec: SNNSpec, cfg: TrainConfig):
    inputs, target = batch
    qspec = QuantSpec(cfg.weight_bits)
    out, _ = run_snn(params, inputs, spec, qspec, mode=cfg.mode)
    if spec.readout == "rate":
        logits = out  # spike counts as logits (rate code)
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, target[:, None], axis=1))
        acc = jnp.mean(jnp.argmax(logits, axis=-1) == target)
        return loss, {"loss": loss, "accuracy": acc}
    # Optical flow: average endpoint error on the Vmem readout.
    aee = jnp.mean(jnp.linalg.norm(out - target, axis=-1))
    return aee, {"loss": aee, "aee": aee}


@partial(jax.jit, static_argnames=("spec", "cfg"))
def _train_step_impl(params, opt_state, step, batch, spec: SNNSpec,
                     cfg: TrainConfig):
    (loss, metrics), grads = jax.value_and_grad(_loss_fn, has_aux=True)(
        params, batch, spec, cfg
    )
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    schedule = linear_warmup_cosine(cfg.lr, cfg.warmup, cfg.steps,
                                    cfg.lr_final_frac)
    update_fn, _ = adamw(lr=cfg.lr, weight_decay=cfg.weight_decay,
                         params=params, lr_schedule=schedule)
    updates, opt_state = update_fn(grads, opt_state, params, step)
    params = apply_updates(params, updates)
    metrics["grad_norm"] = gnorm
    return params, opt_state, metrics


def train_step(state: TrainState, batch, spec: SNNSpec, cfg: TrainConfig):
    """One jitted scan-over-T batched QAT update; returns (state', metrics)."""
    params, opt_state, metrics = _train_step_impl(
        state.params, state.opt_state, state.step, batch, spec, cfg,
    )
    return TrainState(params, opt_state, state.step + 1), metrics


@partial(jax.jit, static_argnames=("spec", "cfg"))
def _eval_impl(params, batch, spec: SNNSpec, cfg: TrainConfig):
    return _loss_fn(params, batch, spec, cfg)[1]


def evaluate(params, batches, spec: SNNSpec, cfg: TrainConfig,
             metric: str = "accuracy") -> float:
    vals = []
    for batch in batches:
        vals.append(float(_eval_impl(params, batch, spec, cfg)[metric]))
    return sum(vals) / len(vals)


# ---------------------------------------------------------------------------
# Full training runs on the synthetic DVS streams.
# ---------------------------------------------------------------------------
def effective_spec(spec: SNNSpec, cfg: TrainConfig) -> SNNSpec:
    """``spec`` with the config's frame-size/timestep overrides applied.

    ``cfg.hw`` / ``cfg.timesteps`` shrink the network *and* its data
    consistently (the topology is shape-agnostic); the returned spec is the
    one training actually runs — and therefore the one to export/deploy.
    """
    return dataclasses.replace(
        spec,
        input_hw=tuple(cfg.hw) if cfg.hw else spec.input_hw,
        timesteps=cfg.timesteps or spec.timesteps,
    )


def make_batch_fn(spec: SNNSpec, cfg: TrainConfig,
                  batch: Optional[int] = None) -> Callable:
    """``key -> (events, target)`` sampler for ``spec``'s head."""
    from .data import make_flow_batch, make_gesture_batch

    spec = effective_spec(spec, cfg)
    hw, ts = spec.input_hw, spec.timesteps
    b = batch or cfg.batch
    if spec.readout == "rate":
        return lambda key: make_gesture_batch(key, batch=b, timesteps=ts, hw=hw)
    return lambda key: make_flow_batch(key, batch=b, timesteps=ts, hw=hw)


def _eval_metric(spec: SNNSpec) -> str:
    return "accuracy" if spec.readout == "rate" else "aee"


def fit(
    spec: SNNSpec,
    cfg: TrainConfig,
    key: Optional[jax.Array] = None,
    ckpt: Optional[Checkpointer] = None,
    log_every: int = 20,
):
    """Train ``spec`` on synthetic DVS streams for ``cfg.steps`` updates.

    Returns ``(state, history)`` where ``history`` carries the per-step
    losses, any periodic eval points and the final eval metric
    (``accuracy`` for rate heads, ``aee`` for flow heads).
    """
    spec = effective_spec(spec, cfg)
    key = jax.random.PRNGKey(cfg.seed) if key is None else key
    k_init, k_data, k_eval = jax.random.split(key, 3)
    state = init_train_state(k_init, spec, cfg)
    batch_fn = make_batch_fn(spec, cfg)
    eval_fn = make_batch_fn(spec, cfg, batch=cfg.eval_batch)
    metric = _eval_metric(spec)

    def run_eval():
        keys = jax.random.split(k_eval, max(cfg.eval_batches, 1))
        return evaluate(state.params, [eval_fn(k) for k in keys], spec, cfg,
                        metric)

    losses, evals = [], []
    t0 = time.time()
    for step in range(cfg.steps):
        k_data, k = jax.random.split(k_data)
        state, m = train_step(state, batch_fn(k), spec, cfg)
        losses.append(float(m["loss"]))
        if log_every and step % log_every == 0:
            log.info("step %d/%d loss=%.4f grad_norm=%.2f", step, cfg.steps,
                     losses[-1], float(m["grad_norm"]))
        if cfg.eval_every and (step + 1) % cfg.eval_every == 0:
            evals.append((step + 1, run_eval()))
        if ckpt is not None and cfg.ckpt_every and \
                (step + 1) % cfg.ckpt_every == 0:
            ckpt.save_async(step + 1, state.params)
    if ckpt is not None:
        ckpt.wait()
    final = run_eval()
    history = {
        "loss": losses,
        "evals": evals,
        "metric": metric,
        "final": final,
        "wall_s": time.time() - t0,
    }
    log.info("fit(%s, %db): loss %.4f -> %.4f, %s=%.4f in %.1fs",
             spec.name, cfg.weight_bits,
             losses[0] if losses else float("nan"),
             losses[-1] if losses else float("nan"),
             metric, final, history["wall_s"])
    return state, history


def spec_for(task: str) -> SNNSpec:
    """``"gesture"`` / ``"optical-flow"`` -> the paper's network spec."""
    if task in ("gesture", "spidr-gesture"):
        return gesture_net()
    if task in ("optical-flow", "optical_flow", "flow", "spidr-optical-flow"):
        return optical_flow_net()
    raise ValueError(f"unknown SNN task {task!r}")


def precision_sweep(
    task: str = "gesture",
    bits: tuple = (4, 6, 8),
    cfg: Optional[TrainConfig] = None,
    spec: Optional[SNNSpec] = None,
    key: Optional[jax.Array] = None,
) -> dict:
    """Train + export one network per weight/Vmem precision pair.

    The Fig 16 trade-off driver: for each ``b`` in ``bits``, trains
    ``task``'s network with the deploy-exact QAT forward at ``b``-bit
    weights ((2b-1)-bit Vmem), folds it into the integer format, and
    records the eval metric.  Returns ``{bits: {"state", "history",
    "exported", "metric"}}``; deployment cost (cycles/energy per core
    count) is layered on by ``benchmarks/run.py --qat-sweep``.
    """
    from .export import export_network

    base = cfg or TrainConfig()
    spec = spec or spec_for(task)
    out = {}
    for b in bits:
        bcfg = dataclasses.replace(base, weight_bits=b)
        state, history = fit(spec, bcfg, key=key)
        exported = export_network(state.params, effective_spec(spec, bcfg),
                                  QuantSpec(b))
        out[b] = {
            "state": state,
            "history": history,
            "exported": exported,
            "metric": history["final"],
        }
    return out
