"""Pallas TPU kernel: fused spike-GEMM + neuron update (one timestep, one layer).

SpiDR's inner loop interleaves the compute macro (weight->Vmem accumulation,
C1) and the neuron macro (leak/threshold/reset, C8) on SRAM-resident state;
the membrane potential never leaves the array between the two phases.  The
TPU analogue is to fuse both phases into a single kernel invocation so the
Vmem tile stays in VMEM between the MXU accumulation and the VPU neuron
update — composing ``spike_gemm`` + ``lif_step_fused`` instead costs two
extra HBM round-trips of the (M, N) Vmem tensor per timestep.

    acc[m, n]  = sum_k S[m, k] * W[k, n]          (MXU, zero-skipped tiles)
    v', s      = neuron_update(v[m, n], acc[m, n]) (VPU, same invocation)

Grid = (M/bm, N/bn, K/bk) with k innermost (sequential on TPU): the output
Vmem block doubles as the revisited accumulator; the neuron update runs once,
on the final k step.  Tile-level zero-skipping is identical to
``spike_gemm``: an all-zero (bm x bk) spike tile issues no MXU work.

Two variants share this structure:

* ``fused_lif_gemm``      — float32; bit-identical to
  ``lif_step_ref(v, spike_gemm_ref(S, W))``.
* ``fused_lif_gemm_int``  — integer datapath with ``QuantSpec`` saturation
  semantics: the wide int32 accumulation is saturated once into the
  (2W-1)-bit Vmem field (``partial``), then added (saturating) into the
  carried Vmem — exactly ``neuron_step_int(v, saturate(S @ W))``, and
  bit-equal to ``cim_macro.accumulate_sequential`` whenever no intermediate
  sum leaves the Vmem range.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = [
    "fused_lif_gemm",
    "fused_lif_gemm_int",
    "spike_tile_bitmap",
    "DEFAULT_BLOCK",
]

DEFAULT_BLOCK = (128, 128, 128)  # (bm, bn, bk)


def _tile_has_spike(s_tile):
    """Scalar predicate: does the spike tile hold any nonzero entry?

    An integer max reduction rather than ``jnp.any``: Mosaic cannot
    relayout the boolean vector ``jnp.any`` reduces.
    """
    if not jnp.issubdtype(s_tile.dtype, jnp.floating):
        s_tile = s_tile.astype(jnp.int32)
    return jnp.max(jnp.abs(s_tile)) > 0


def _fused_kernel_f32(
    s_ref, w_ref, v_ref, o_v_ref, o_s_ref,
    *, n_k, threshold, leak, soft_reset, skip_empty,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_v_ref[...] = jnp.zeros_like(o_v_ref)
        o_s_ref[...] = jnp.zeros_like(o_s_ref)

    s_tile = s_ref[...]
    if skip_empty:
        @pl.when(_tile_has_spike(s_tile))
        def _accumulate():
            o_v_ref[...] += jnp.dot(
                s_tile, w_ref[...], preferred_element_type=jnp.float32
            )
    else:
        o_v_ref[...] += jnp.dot(
            s_tile, w_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(k == n_k - 1)
    def _neuron():
        v = v_ref[...]
        if leak != 1.0:
            v = v * leak
        v = v + o_v_ref[...]
        s = (v >= threshold).astype(v.dtype)
        if soft_reset:
            v_next = v - s * threshold
        else:
            v_next = v * (1.0 - s)
        o_v_ref[...] = v_next
        o_s_ref[...] = s


def _fused_int_body(
    s_ref, w_ref, v_ref, o_v_ref, o_s_ref, get_threshold,
    *, n_k, leak_shift, soft_reset, v_min, v_max, skip_empty,
):
    """Shared integer kernel body.

    ``get_threshold`` supplies the firing threshold at neuron time: a
    static scalar (per-tensor quantization) or a ``(1, bn)`` int32 tile
    read from a threshold operand (per-channel exported networks) — the
    accumulate/leak/saturate/fire/reset program is identical either way.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_v_ref[...] = jnp.zeros_like(o_v_ref)
        o_s_ref[...] = jnp.zeros_like(o_s_ref)

    s_tile = s_ref[...]

    def _accumulate():
        # int8 x int8 -> int32 is the MXU's integer path; Mosaic refuses
        # int32 operands.
        o_v_ref[...] += jax.lax.dot_general(
            s_tile, w_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    if skip_empty:
        pl.when(_tile_has_spike(s_tile))(_accumulate)
    else:
        _accumulate()

    @pl.when(k == n_k - 1)
    def _neuron():
        # Column-adder saturation of the accumulated partials (quant.sat_add
        # semantics), then the neuron-macro program on the carried Vmem.
        threshold = get_threshold()
        partial = jnp.clip(o_v_ref[...], v_min, v_max)
        v = v_ref[...]
        if leak_shift > 0:
            v = v - (v >> leak_shift)
        v = jnp.clip(v + partial, v_min, v_max)
        s = (v >= threshold).astype(jnp.int32)
        if soft_reset:
            v_next = jnp.clip(v - s * threshold, v_min, v_max)
        else:
            v_next = v * (1 - s)
        o_v_ref[...] = v_next
        o_s_ref[...] = s


def _fused_kernel_int(s_ref, w_ref, v_ref, o_v_ref, o_s_ref,
                      *, threshold, **kw):
    _fused_int_body(s_ref, w_ref, v_ref, o_v_ref, o_s_ref,
                    lambda: threshold, **kw)


def _fused_kernel_int_vec(s_ref, w_ref, v_ref, t_ref, o_v_ref, o_s_ref, **kw):
    # t_ref is (1, bn) — one threshold per output channel, broadcast down
    # the rows at the compare.
    _fused_int_body(s_ref, w_ref, v_ref, o_v_ref, o_s_ref,
                    lambda: t_ref[...], **kw)


def _fused_call(kernel, s, w, v, out_dtype, block, interpret, thr=None,
                thr_pad=0):
    """Shared pallas_call plumbing; ``thr`` adds an optional per-output-
    channel ``(N,)`` operand (padded with ``thr_pad``), blocked ``(1, bn)``
    and broadcast down the rows inside the kernel."""
    m, k = s.shape
    k2, n = w.shape
    assert k == k2, (s.shape, w.shape)
    assert v.shape == (m, n), (v.shape, (m, n))
    bm, bn, bk = block

    pad_m, pad_n, pad_k = -m % bm, -n % bn, -k % bk
    s = jnp.pad(s, ((0, pad_m), (0, pad_k)))
    w = jnp.pad(w, ((0, pad_k), (0, pad_n)))
    v = jnp.pad(v, ((0, pad_m), (0, pad_n)))
    gm, gn, gk = s.shape[0] // bm, w.shape[1] // bn, s.shape[1] // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
    ]
    operands = [s, w, v]
    if thr is not None:
        assert thr.shape == (n,), (thr.shape, n)
        operands.append(
            jnp.pad(thr, (0, pad_n), constant_values=thr_pad)[None, :])
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))

    v_out, s_out = pl.pallas_call(
        functools.partial(kernel, n_k=gk),
        grid=(gm, gn, gk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s.shape[0], w.shape[1]), out_dtype),
            jax.ShapeDtypeStruct((s.shape[0], w.shape[1]), out_dtype),
        ],
        interpret=interpret,
    )(*operands)
    return v_out[:m, :n], s_out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=(
        "threshold", "leak", "soft_reset", "block", "interpret", "skip_empty"
    ),
)
def fused_lif_gemm(
    spikes: jax.Array,   # (M, K) in {0,1}, any int/bool/float dtype
    weights: jax.Array,  # (K, N) float32
    v: jax.Array,        # (M, N) float32 carried Vmem
    threshold: float = 1.0,
    leak: float = 1.0,
    soft_reset: bool = False,
    block: tuple = DEFAULT_BLOCK,
    interpret: bool = False,
    skip_empty: bool = True,
):
    """Fused float timestep: ``(v', s) = lif(v, spikes @ weights)``."""
    kernel = functools.partial(
        _fused_kernel_f32,
        threshold=threshold, leak=leak, soft_reset=soft_reset,
        skip_empty=skip_empty,
    )
    return _fused_call(
        kernel, spikes.astype(jnp.float32), weights.astype(jnp.float32),
        v.astype(jnp.float32), jnp.float32, block, interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "threshold", "leak_shift", "soft_reset", "vmem_bits", "block",
        "interpret", "skip_empty",
    ),
)
def _fused_int_scalar(
    spikes, weights, v, *, threshold, leak_shift, soft_reset, vmem_bits,
    block, interpret, skip_empty,
):
    v_min, v_max = -(1 << (vmem_bits - 1)), (1 << (vmem_bits - 1)) - 1
    kernel = functools.partial(
        _fused_kernel_int,
        threshold=threshold, leak_shift=leak_shift, soft_reset=soft_reset,
        v_min=v_min, v_max=v_max, skip_empty=skip_empty,
    )
    return _fused_call(
        kernel, spikes.astype(jnp.int8), weights.astype(jnp.int8),
        v.astype(jnp.int32), jnp.int32, block, interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "leak_shift", "soft_reset", "vmem_bits", "block", "interpret",
        "skip_empty",
    ),
)
def _fused_int_vec(
    spikes, weights, v, threshold, *, leak_shift, soft_reset, vmem_bits,
    block, interpret, skip_empty,
):
    v_min, v_max = -(1 << (vmem_bits - 1)), (1 << (vmem_bits - 1)) - 1
    kernel = functools.partial(
        _fused_kernel_int_vec,
        leak_shift=leak_shift, soft_reset=soft_reset,
        v_min=v_min, v_max=v_max, skip_empty=skip_empty,
    )
    # Pad channels get threshold v_max+1: a saturated Vmem can never reach
    # it, so the (discarded) padding never spikes.
    return _fused_call(
        kernel, spikes.astype(jnp.int8), weights.astype(jnp.int8),
        v.astype(jnp.int32), jnp.int32, block, interpret,
        thr=threshold.astype(jnp.int32), thr_pad=v_max + 1,
    )


def spike_tile_bitmap(spikes: jax.Array, block: tuple = DEFAULT_BLOCK):
    """Per-tile spike bitmap: ``(T, ceil(M/bm), ceil(K/bk))`` int32.

    Entry ``[t, i, kk]`` is 1 iff the ``(bm, bk)`` spike tile at grid cell
    ``(i, kk)`` of timestep ``t`` (``spikes`` padded to block multiples)
    holds at least one spike — the tiles the kernel's zero-skip passes
    over are the zero entries.  A 2-D ``(M, K)`` input is treated as a
    single timestep and returns a 2-D ``(gm, gk)`` map.  ``block`` is
    ``(bm, bn, bk)``; ``bn`` is unused (the bitmap is independent of the
    output tiling).
    """
    bm, _, bk = block
    squeeze = spikes.ndim == 2
    if squeeze:
        spikes = spikes[None]
    t, m, k = spikes.shape
    s = jnp.pad(spikes, ((0, 0), (0, -m % bm), (0, -k % bk)))
    tiles = s.reshape(t, s.shape[1] // bm, bm, s.shape[2] // bk, bk)
    out = jnp.any(tiles != 0, axis=(2, 4)).astype(jnp.int32)
    return out[0] if squeeze else out


def fused_lif_gemm_int(
    spikes: jax.Array,   # (M, K) in {0,1}
    weights: jax.Array,  # (K, N) int8
    v: jax.Array,        # (M, N) int32 holding (2W-1)-bit values
    threshold,           # int, or (N,) int32 per-channel thresholds
    leak_shift: int = 0,
    soft_reset: bool = False,
    vmem_bits: int = 7,
    block: tuple = DEFAULT_BLOCK,
    interpret: bool = False,
    skip_empty: bool = True,
):
    """Fused integer timestep, bit-exact with the macro datapath.

    Equals ``neuron_step_int(v, saturate(spikes @ weights, spec), ...)`` and
    therefore ``accumulate_sequential`` when no intermediate overflow occurs.

    ``threshold`` may be a Python int (per-tensor quantization; baked into
    the kernel as a compile-time constant, the original behavior) or an
    ``(N,)`` integer array of per-output-channel thresholds (per-channel
    exported networks; passed as a kernel operand).
    """
    kw = dict(leak_shift=leak_shift, soft_reset=soft_reset,
              vmem_bits=vmem_bits, block=block, interpret=interpret,
              skip_empty=skip_empty)
    if isinstance(threshold, (int, np.integer)):
        return _fused_int_scalar(spikes, weights, v, threshold=int(threshold),
                                 **kw)
    threshold = jnp.asarray(threshold)
    if threshold.ndim == 0:
        threshold = jnp.broadcast_to(threshold, (weights.shape[1],))
    return _fused_int_vec(spikes, weights, v, threshold, **kw)
