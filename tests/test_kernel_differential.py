"""Differential harness for the block-sparse fused hot path.

The fused kernel (``fused_lif_gemm_int``), walked over timesteps with the
Vmem carried from call to call, re-schedules the engine's hot loop two
ways — whole-tile spike skipping and block shapes — and both must be
*invisible* in the output: integer accumulation is exact, so any
divergence from the sequential per-timestep oracle is a bug, not noise.

This module is the oracle sweep:

  * a parametrized differential matrix over pinned shapes (including every
    non-divisible-by-block edge we have hit), all three precision pairs
    (4/7, 6/11, 8/15), sparsities {0.0, 0.5, 0.95, 1.0}, scalar and
    per-neuron thresholds, hard and soft reset, leak shifts, every block
    of {32, 128}^3 the TPU's lane rule admits, and saturation-boundary
    inputs pinned at the +-Vmem clip;
  * failures name the FIRST divergent (timestep, row, col) with both
    values — a schedule bug localizes to a tile boundary instantly;
  * a hypothesis-driven random-shape sweep (nightly: the ``slow`` marker)
    that searches the shape space the pinned matrix cannot cover;
  * chunking: ``run_chunk`` over chunks of 1, 3 and 6 timesteps, and a
    stream snapshot restored into a session whose chunks fall elsewhere.

Everything runs the kernels in interpret mode (CPU container); on TPU the
same code compiles to Mosaic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dev extra absent: property tests skip, rest run
    from _hypothesis_stub import given, settings, st

from repro import spidr
from repro.core.layers import SpikingConvParams, SpikingDenseParams
from repro.core.network import SNNLayer, SNNSpec, init_params
from repro.core.neuron import NeuronConfig
from repro.core.quant import QuantSpec
from repro.engine import (
    EngineConfig,
    build_engine,
    init_state,
    run_chunk,
    run_engine,
)
from repro.kernels import ref
from repro.kernels.fused_lif_gemm import fused_lif_gemm_int, spike_tile_bitmap
from repro.spidr.target import PRECISION_PAIRS

# Small blocks so test-sized shapes still produce multi-tile grids (the
# schedule bugs this harness hunts live on tile boundaries).
BLOCK = (32, 32, 32)

# Pinned regression shapes: every (T, M, K, N) that exercises a distinct
# padding/masking edge of the (bm, bn, bk) = (32, 32, 32) tiling.
PINNED_SHAPES = [
    (1, 1, 1, 1),        # degenerate minimum: everything is padding
    (5, 7, 33, 19),      # no dimension divides its block
    (3, 65, 96, 70),     # m and n overrun one tile, k exact
    (4, 32, 32, 32),     # exactly one tile — no masking at all
    (6, 9, 5, 33),       # n overruns the tile by one lane
    (2, 130, 30, 4),     # tall-skinny: 5 m-tiles, sub-tile k and n
]

SPARSITIES = (0.0, 0.5, 0.95, 1.0)

# Every (bm, bn, bk) of {32, 128}^3.  On a TPU, ``bn`` and ``bk`` are lane
# dimensions: each must be a multiple of 128 or cover its whole dimension
# in one block (``docs/kernels.md``); ``bm`` is a sublane dimension, which
# both sizes satisfy as multiples of 32.
BLOCKS = [(bm, bn, bk) for bm in (32, 128) for bn in (32, 128)
          for bk in (32, 128)]


def _lane_legal(block, k, n):
    """Does the TPU's lane rule admit ``block`` for fan-in ``k`` and ``n``
    output channels?"""
    _, bn, bk = block
    return all(b % 128 == 0 or b >= d for b, d in ((bn, n), (bk, k)))


def _case(T, M, K, N, vmem_bits, sparsity, seed=0, weight_bits=None,
          v0_mode="random"):
    """Random inputs for one differential case (deterministic by seed)."""
    rng = np.random.default_rng(seed)
    wb = weight_bits or (vmem_bits + 1) // 2
    w_max = (1 << (wb - 1)) - 1
    v_max = (1 << (vmem_bits - 1)) - 1
    spikes = jnp.asarray(
        (rng.random((T, M, K)) >= sparsity).astype(np.int8))
    weights = jnp.asarray(
        rng.integers(-w_max - 1, w_max + 1, (K, N)), jnp.int8)
    if v0_mode == "random":
        v0 = jnp.asarray(
            rng.integers(-v_max - 1, v_max + 1, (M, N)), jnp.int32)
    else:  # saturation boundary: start pinned at the clip rails
        rail = v_max if v0_mode == "high" else -v_max - 1
        v0 = jnp.full((M, N), rail, jnp.int32)
    return spikes, weights, v0


def _oracle(spikes, weights, v0, threshold, leak_shift, soft_reset,
            vmem_bits):
    """Sequential per-timestep oracle: ``ref.fused_lif_gemm_int_ref``."""
    v = jnp.asarray(v0, jnp.int32)
    vs, ss = [], []
    for t in range(spikes.shape[0]):
        v, s = ref.fused_lif_gemm_int_ref(
            spikes[t], weights, v, threshold, leak_shift, soft_reset,
            vmem_bits)
        vs.append(v)
        ss.append(s)
    return jnp.stack(vs), jnp.stack(ss)


def _assert_traj_equal(got, want, what):
    """Bit-exact or name the FIRST divergent (timestep, row, col)."""
    g, w = np.asarray(got), np.asarray(want)
    assert g.shape == w.shape, f"{what}: shape {g.shape} != {w.shape}"
    if (g == w).all():
        return
    t, r, c = np.argwhere(g != w)[0]
    raise AssertionError(
        f"{what} diverges first at (timestep={t}, row={r}, col={c}): "
        f"got {g[t, r, c]}, want {w[t, r, c]} "
        f"[{int((g != w).sum())} of {g.size} entries differ]")


def _kernel_walk(spikes, weights, v0, threshold, **kw):
    """The fused kernel over each timestep, Vmem carried from call to call,
    as the engine walks it: ``(v_traj, s_traj)``, both ``(T, M, N)``."""
    v, vs, ss = v0, [], []
    for t in range(spikes.shape[0]):
        v, s = fused_lif_gemm_int(spikes[t], weights, v, threshold=threshold,
                                  interpret=True, **kw)
        vs.append(v)
        ss.append(s)
    return jnp.stack(vs), jnp.stack(ss)


def _run_and_compare(spikes, weights, v0, threshold, *, vmem_bits,
                     leak_shift=0, soft_reset=False, skip_empty=True,
                     block=BLOCK):
    v_traj, s_traj = _kernel_walk(
        spikes, weights, v0, threshold, leak_shift=leak_shift,
        soft_reset=soft_reset, vmem_bits=vmem_bits, block=block,
        skip_empty=skip_empty)
    want_v, want_s = _oracle(spikes, weights, v0, threshold, leak_shift,
                             soft_reset, vmem_bits)
    _assert_traj_equal(s_traj, want_s, "spike trajectory")
    _assert_traj_equal(v_traj, want_v, "Vmem trajectory")


# ---------------------------------------------------------------------------
# The differential matrix (tier-1).
# ---------------------------------------------------------------------------
class TestDifferentialMatrix:
    @pytest.mark.parametrize("wb,vb", PRECISION_PAIRS)
    @pytest.mark.parametrize("sparsity", SPARSITIES)
    def test_precision_pairs_at_every_sparsity(self, wb, vb, sparsity):
        """All three silicon precision pairs on a nothing-divides shape."""
        spikes, weights, v0 = _case(5, 7, 33, 19, vb, sparsity,
                                    seed=wb, weight_bits=wb)
        thr = max(1, 1 << (vb - 3))
        _run_and_compare(spikes, weights, v0, thr, vmem_bits=vb,
                         leak_shift=2, soft_reset=(wb == 6))

    @pytest.mark.parametrize("shape", PINNED_SHAPES)
    def test_pinned_nondivisible_shapes(self, shape):
        """Regression pins for the padding/masking bug class: shapes whose
        every dimension sits off a tile boundary must not read or write
        padding lanes."""
        T, M, K, N = shape
        spikes, weights, v0 = _case(T, M, K, N, 7, 0.5, seed=sum(shape))
        _run_and_compare(spikes, weights, v0, 16, vmem_bits=7,
                         leak_shift=3, soft_reset=(T % 2 == 0))

    @pytest.mark.parametrize("v0_mode", ["high", "low"])
    @pytest.mark.parametrize("soft_reset", [False, True])
    def test_saturation_boundary(self, v0_mode, soft_reset):
        """Vmem pinned at the clip rails: accumulate straight into (and
        past) saturation in both directions; the kernel's single-clip
        order must match the oracle exactly."""
        rng = np.random.default_rng(7)
        vb, wb = 7, 4
        w_max = (1 << (wb - 1)) - 1
        spikes = jnp.asarray((rng.random((4, 33, 40)) < 0.8).astype(np.int8))
        # Extreme same-sign weights force the accumulator over the rail.
        sign = 1 if v0_mode == "high" else -1
        weights = jnp.full((40, 21), sign * w_max, jnp.int8)
        _, _, v0 = _case(4, 33, 40, 21, vb, 0.5, v0_mode=v0_mode,
                         weight_bits=wb)
        _run_and_compare(spikes, weights, v0, 16, vmem_bits=vb,
                         soft_reset=soft_reset)

    def test_vector_threshold(self):
        """Per-neuron thresholds route through the vector kernel variant."""
        spikes, weights, v0 = _case(3, 40, 17, 50, 11, 0.5, seed=11)
        rng = np.random.default_rng(5)
        thr = jnp.asarray(rng.integers(1, 1 << 9, (50,)), jnp.int32)
        v_traj, s_traj = _kernel_walk(spikes, weights, v0, thr,
                                      vmem_bits=11, block=BLOCK)
        want_v, want_s = _oracle(spikes, weights, v0, thr, 0, False, 11)
        _assert_traj_equal(s_traj, want_s, "spike trajectory")
        _assert_traj_equal(v_traj, want_v, "Vmem trajectory")

    def test_skip_and_dense_agree(self):
        """Block skipping must be invisible (C3: exactness)."""
        spikes, weights, v0 = _case(4, 70, 65, 33, 7, 0.97, seed=3)
        args = dict(vmem_bits=7, block=BLOCK)
        a = _kernel_walk(spikes, weights, v0, 16, skip_empty=True, **args)
        b = _kernel_walk(spikes, weights, v0, 16, skip_empty=False, **args)
        _assert_traj_equal(a[0], b[0], "Vmem trajectory (skip vs dense)")
        _assert_traj_equal(a[1], b[1], "spike trajectory (skip vs dense)")

    def test_all_zero_input_skips_every_tile(self):
        """sparsity=1.0: the bitmap is all zero, every tile is skipped, and
        the output is still exactly the oracle's (leak-only dynamics)."""
        spikes, weights, v0 = _case(4, 40, 33, 20, 7, 1.0, seed=9)
        assert int(spike_tile_bitmap(spikes, BLOCK).sum()) == 0
        _run_and_compare(spikes, weights, v0, 8, vmem_bits=7, leak_shift=1)

    @pytest.mark.parametrize("block", BLOCKS,
                             ids=["x".join(map(str, b)) for b in BLOCKS])
    def test_every_legal_block_is_bitexact(self, block):
        """Every block of {32, 128}^3 on every pinned shape the TPU's lane
        rule admits it for: the tiling is invisible in the output."""
        shapes = [sh for sh in PINNED_SHAPES
                  if _lane_legal(block, sh[2], sh[3])]
        assert shapes
        for T, M, K, N in shapes:
            spikes, weights, v0 = _case(T, M, K, N, 7, 0.5, seed=M + K)
            _run_and_compare(spikes, weights, v0, 16, vmem_bits=7,
                             leak_shift=2, soft_reset=(M % 2 == 0),
                             block=block)


class TestBitmapFormat:
    def test_shape_and_dtype(self):
        s = jnp.zeros((3, 100, 70), jnp.int8)
        bm = spike_tile_bitmap(s, BLOCK)
        assert bm.shape == (3, 4, 3)  # ceil(100/32) x ceil(70/32)
        assert bm.dtype == jnp.int32
        assert int(bm.sum()) == 0

    def test_single_spike_lights_exactly_one_tile(self):
        s = np.zeros((2, 100, 70), np.int8)
        s[1, 99, 69] = 1  # last row/col: lives in the padded edge tile
        bm = np.asarray(spike_tile_bitmap(jnp.asarray(s), BLOCK))
        assert bm.sum() == 1 and bm[1, 3, 2] == 1

    def test_2d_input_is_one_timestep(self):
        s = np.zeros((40, 40), np.int8)
        s[0, 0] = 1
        bm = np.asarray(spike_tile_bitmap(jnp.asarray(s), BLOCK))
        assert bm.shape == (2, 2) and bm[0, 0] == 1 and bm.sum() == 1


# ---------------------------------------------------------------------------
# Hypothesis sweep (nightly: random shapes the pinned matrix cannot cover).
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestHypothesisSweep:
    @settings(max_examples=40, deadline=None)
    @given(
        T=st.integers(1, 6),
        M=st.integers(1, 140),
        K=st.integers(1, 140),
        N=st.integers(1, 70),
        pair=st.sampled_from(PRECISION_PAIRS),
        sparsity=st.sampled_from(SPARSITIES),
        leak_shift=st.integers(0, 3),
        soft_reset=st.booleans(),
        seed=st.integers(0, 2**16),
    )
    def test_random_shapes(self, T, M, K, N, pair, sparsity, leak_shift,
                           soft_reset, seed):
        wb, vb = pair
        spikes, weights, v0 = _case(T, M, K, N, vb, sparsity, seed=seed,
                                    weight_bits=wb)
        thr = max(1, 1 << (vb - 3))
        _run_and_compare(spikes, weights, v0, thr, vmem_bits=vb,
                         leak_shift=leak_shift, soft_reset=soft_reset)


# ---------------------------------------------------------------------------
# Chunking: any partition of a stream into chunks is the whole stream.
# ---------------------------------------------------------------------------
def _mini_spec(hw=(16, 16), timesteps=6):
    n = NeuronConfig(model="lif", reset="soft", threshold=0.5, leak_shift=3)
    return SNNSpec(
        name="mini", input_hw=hw, in_channels=2, timesteps=timesteps,
        layers=(
            SNNLayer("conv", 2, 8, conv=SpikingConvParams(3, 3, 1, 1, n)),
            SNNLayer("pool"),
            SNNLayer("conv", 8, 8, conv=SpikingConvParams(3, 3, 1, 1, n)),
            SNNLayer("adaptive_pool", target_hw=2),
            SNNLayer("fc", 32, 5, fc=SpikingDenseParams(n)),
        ),
        readout="rate",
    )


def _fused_engine(spec, seed=0):
    params = init_params(jax.random.PRNGKey(seed), spec)
    cfg = EngineConfig(QuantSpec(4), interpret=True, block=(64, 64, 64),
                       backend="fused")
    return build_engine(spec, params, cfg)


def _jnp_engine(spec, seed=0):
    params = init_params(jax.random.PRNGKey(seed), spec)
    return build_engine(spec, params,
                        EngineConfig(QuantSpec(4), backend="jnp"))


def _events(spec, batch=2, seed=0, sparsity=0.9):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        (rng.random((spec.timesteps, batch) + spec.input_hw + (2,))
         > sparsity).astype(np.float32))


class TestChunkingTimesTiling:
    @pytest.mark.parametrize("chunk_T", [1, 3, 6])
    def test_chunking_not_multiple_of_tblk(self, chunk_T):
        """The chunked fused walk at chunk_T in {1, 3, T}: the Vmem carried
        across chunk boundaries gives the jnp whole-stream run exactly."""
        spec = _mini_spec()
        eng = _fused_engine(spec)
        ev = _events(spec)
        whole = run_engine(_jnp_engine(spec), ev)
        state = init_state(eng, ev.shape[1])
        out = None
        for t0 in range(0, spec.timesteps, chunk_T):
            state, out = run_chunk(eng, state, ev[t0:t0 + chunk_T])
        np.testing.assert_array_equal(np.asarray(out.readout),
                                      np.asarray(whole.readout))
        np.testing.assert_array_equal(
            np.asarray(state.out_counts).sum(axis=1),
            np.asarray(whole.spike_counts).sum(axis=0))

    def test_stream_snapshot_restore_mid_tile(self):
        """A session snapshot taken after 3 timesteps at chunk_T=3 restores
        into a twin that delivers 2 timesteps a chunk, so the snapshot sits
        mid-chunk of the twin's chunking: the rest of the stream ends with
        the uninterrupted session's readout, spikes and cycles."""
        spec = _mini_spec()
        params = init_params(jax.random.PRNGKey(0), spec)
        target = spidr.DeployTarget(weight_bits=4, backend="fused",
                                    interpret=True, block=(64, 64, 64),
                                    chunk_T=3, stream_capacity=2)
        compiled = spidr.compile(spec, params, target)
        sess = compiled.open_stream(2, 3)
        s0 = sess.open()
        rng = np.random.default_rng(4)
        frames = (rng.random((9,) + spec.input_hw + (2,)) < 0.1) \
            .astype(np.float32)

        sess.step({s0: frames[:3]})
        snap = sess.state_dict()
        for t0 in (3, 6):
            want = sess.step({s0: frames[t0:t0 + 3]})[s0]
        twin = compiled.open_stream(2, 2)
        twin.load_state_dict(snap)
        for t0 in (3, 5, 7):
            got = twin.step({s0: frames[t0:t0 + 2]})[s0]
        np.testing.assert_array_equal(np.asarray(got.readout),
                                      np.asarray(want.readout))
        assert (got.spikes, got.timesteps, got.cycles, got.energy_uj) == \
            (want.spikes, want.timesteps, want.cycles, want.energy_uj)
