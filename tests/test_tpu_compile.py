"""Compile rehearsal: the served path's fused kernels for a described TPU v5e.

Nothing here runs on a chip.  The TPU compiler is installed with JAX and
compiles for a chip that is described, not attached, so these tests catch
what interpret mode cannot: block shapes the Mosaic lowering refuses,
operand types the MXU does not take, and reductions it cannot relayout.
Widths are the paper networks' real ones: gesture conv2 at batch 4 and an
optical-flow 32->32 layer at batch 1; and LIF-FireNet's recurrent layer
and 1x1 head at one 260x346 frame.  One whole chunk step is compiled too:
LIF-FireNet's at 16 slots of 260x346 frames, which must hold no relayout
loop and fit the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers must
all collect the same tests.

The same file checks where the persistent compilation cache is placed.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import spidr
from repro.configs import lif_firenet
from repro.core.network import init_params
from repro.engine.inference import init_state, run_chunk
from repro.kernels.autotune import _default_candidates
from repro.kernels.fused_lif_gemm import (
    fused_lif_gemm_int,
    fused_lif_gemm_int_tblk,
)
from repro.runtime import compile_cache

# (M, F, K): GEMM rows, fan-in, output channels.
WIDTHS = {
    "gesture_conv2_b4": (16384, 144, 16),
    "flow_conv32_b1": (110592, 288, 32),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # A described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache off around them.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("per_channel", [False, True],
                         ids=["scalar_thr", "channel_thr"])
def test_fused_int_compiles(one_chip, width, per_channel):
    m, f, k = WIDTHS[width]
    args = [_sds(one_chip, (m, f), jnp.int8),
            _sds(one_chip, (f, k), jnp.int8),
            _sds(one_chip, (m, k), jnp.int32)]
    if per_channel:
        args.append(_sds(one_chip, (k,), jnp.int32))
        fn = lambda s, w, v, t: fused_lif_gemm_int(s, w, v, threshold=t)
    else:
        fn = lambda s, w, v: fused_lif_gemm_int(s, w, v, threshold=3,
                                                leak_shift=4)
    _assert_kernel_compiles(fn, *args)


# LIF-FireNet's widths beyond the paper networks' at one 260x346 frame:
# a recurrent layer (its input joined to its own spikes, F = 9 * 64) and
# the 1x1 head (F = 32, K = 2, run at a threshold of v_max + 1).
FIRENET_WIDTHS = {
    "firenet_recurrent_b1": ((89960, 576, 32), 28),
    "firenet_head_b1": ((89960, 32, 2), 64),
}


@pytest.mark.parametrize("width", sorted(FIRENET_WIDTHS))
def test_fused_int_compiles_at_firenet_widths(one_chip, width):
    (m, f, k), thr = FIRENET_WIDTHS[width]
    args = [_sds(one_chip, (m, f), jnp.int8),
            _sds(one_chip, (f, k), jnp.int8),
            _sds(one_chip, (m, k), jnp.int32)]
    _assert_kernel_compiles(
        lambda s, w, v: fused_lif_gemm_int(s, w, v, threshold=thr,
                                           leak_shift=1), *args)


def test_firenet_chunk_step_has_no_relayout_loops(one_chip):
    """The served chunk step (16 slots, two timesteps) at 260x346: carried
    at a tile-aligned width, no (B, H, W, C) <-> (B*H*W, C) reshape is
    copied through a ``wide.*`` relayout loop (58 of them at 346 wide),
    and arguments, outputs and temporaries fit in 13.5 GB."""
    spec = lif_firenet.CONFIG
    engine = spidr.compile(
        spec, init_params(jax.random.PRNGKey(0), spec),
        spidr.DeployTarget(backend="fused", interpret=False)).engine
    slots = 16
    state = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                         jax.eval_shape(lambda: init_state(engine, slots)))
    events = _sds(one_chip, (2, slots) + spec.input_hw + (2,), jnp.float32)
    compiled = jax.jit(lambda st, ev: run_chunk(
        engine, st, ev, collect_counts=True,
        collect_readouts=True)).lower(state, events).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"wide\.\S*body", text)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= 13.5e9, total


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("per_channel", [False, True],
                         ids=["scalar_thr", "channel_thr"])
def test_tblk_compiles(one_chip, width, per_channel):
    m, f, k = WIDTHS[width]
    args = [_sds(one_chip, (4, m, f), jnp.int8),
            _sds(one_chip, (f, k), jnp.int8),
            _sds(one_chip, (m, k), jnp.int32)]
    if per_channel:
        args.append(_sds(one_chip, (k,), jnp.int32))
        fn = lambda s, w, v, t: fused_lif_gemm_int_tblk(s, w, v, threshold=t,
                                                        soft_reset=True)
    else:
        fn = lambda s, w, v: fused_lif_gemm_int_tblk(s, w, v, threshold=3)
    _assert_kernel_compiles(fn, *args)


def test_every_autotune_candidate_compiles(one_chip):
    """The tuner never offers a tiling the chip's compiler refuses."""
    m, f, k = WIDTHS["gesture_conv2_b4"]
    t = 4
    cands = _default_candidates(m, f, k, timesteps=t)
    assert cands
    for cand in cands:
        args = [_sds(one_chip, (cand.t_block, m, f), jnp.int8),
                _sds(one_chip, (f, k), jnp.int8),
                _sds(one_chip, (m, k), jnp.int32)]
        _assert_kernel_compiles(
            lambda s, w, v, cand=cand: fused_lif_gemm_int_tblk(
                s, w, v, threshold=3, block=cand.block), *args)


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_cache_dir_from_environment_is_left_in_force(
        monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_without_environment_is_fixed_in_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    first = compile_cache.configure_compile_cache()
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.configure_compile_cache() == first
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(checkout, ".jax_cache")
