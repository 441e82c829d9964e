"""A network description that is not a chain, for the harness's tests.

Two 3x3 spiking convolutions, stride 1, padding 1: ``conv`` and then
``conv_skip``, whose input current is its convolution of the first
layer's spikes plus those spikes themselves (an identity skip, so both
layers have the same channels).  The skip is one more input per output
channel: the second layer's fan-in counts it in the work, and its input
count counts the first layer's spikes twice, once through the
convolution and once through the skip.  No program serves it.
"""
import jax
import jax.numpy as jnp

from bench.networks import chain


def weight_layers(cfg):
    return [dict(layer, fan_in=layer["kh"] * layer["kw"] * layer["c_in"],
                 shape=(layer["kh"], layer["kw"], layer["c_in"],
                        layer["c_out"]), spatial=True)
            for layer in cfg["layers"]]


def layer_work(cfg):
    h, w = cfg["input_hw"]
    return [(h * w, layer["kh"] * layer["kw"] * layer["c_in"]
             + (layer["kind"] == "conv_skip"), layer["c_out"])
            for layer in cfg["layers"]]


def check_program(spec, cfg):
    got = [sl.kind for sl in spec.layers]
    want = [layer["kind"] for layer in cfg["layers"]]
    return [] if got == want else [f"layers: program {got}, file {want}"]


def program_params(cfg, weights):
    return list(weights)


def _state_shapes(cfg, batch):
    h, w = cfg["input_hw"]
    return [(batch, h, w, layer["c_out"]) for layer in cfg["layers"]]


def reference_run(cfg, weights, clips, vmem_bits=None, budget_bytes=1.5e9):
    vmem_bits = cfg["vmem_bits"] if vmem_bits is None else vmem_bits
    fire = chain.fire_fn(cfg, vmem_bits)
    first, second = cfg["layers"]

    def step(ws, vmem, x):
        v1, s1 = fire(chain.conv(x, ws[0], first), vmem[0], first["thr_int"])
        a1 = s1.astype(jnp.float32)
        v2, s2 = fire(chain.conv(a1, ws[1], second) + a1, vmem[1],
                      second["thr_int"])
        counts = jnp.stack([jnp.sum(x != 0, axis=(1, 2, 3)),
                            2 * jnp.sum(s1, axis=(1, 2, 3))], axis=1)
        return [v1, v2], s2, v2, counts

    return chain.run_blocks(
        cfg, jax.jit(step), chain.shaped_weights(weight_layers(cfg), weights),
        clips, lambda b: _state_shapes(cfg, b), budget_bytes)
