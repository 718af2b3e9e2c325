"""Compile the main path's TPU programs for a described (not attached)
TPU v5e chip, at the paper's widths (`configs/snn_chip.ARCH`).

The TPU compiler is installed even where no chip is, so these catch what
interpret mode cannot: an op or a block shape Mosaic refuses, a kernel
over its VMEM, a program that does not fit the device.  Nothing runs, so
they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.snn_chip import ARCH
from repro.core import zspe as Z
from repro.core.engine import CompiledEngine, _pick_engine_block
from repro.core.soc import ChipSimulator
from repro.kernels.fused_timestep import (fused_timestep_codebook,
                                          fused_timestep_dense,
                                          fused_timestep_words)

LAYERS = list(zip(ARCH.layer_sizes[:-1], ARCH.layer_sizes[1:]))
V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("batch", [8, 32, 12])
@pytest.mark.parametrize("n_pre,n_post", LAYERS,
                         ids=[f"{a}x{b}" for a, b in LAYERS])
@pytest.mark.parametrize("layout", ["codebook", "dense", "words"])
def test_fused_kernel_compiles_for_v5e(one_chip, layout, n_pre, n_post,
                                       batch):
    """Every kernel layout at every ARCH layer, with the tile the fused
    engine picks (B=12: an odd batch the server and benches can send);
    the word layout with every weight nonzero, as the benchmark's
    tables are."""
    kw = Z.spike_word_count(n_pre)
    k = kw * Z.SPIKE_WORD_BITS
    block = _pick_engine_block(batch, k, n_post, interpret=False,
                               layout=layout,
                               all_nonzero=layout == "words")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    spikes = sds((batch, kw), jnp.uint16)
    state = (sds((batch, n_post), jnp.float32),
             sds((batch, n_post), jnp.int32))
    if layout == "codebook":
        lowered = fused_timestep_codebook.lower(
            spikes, sds((k, n_post), jnp.int8),
            sds((ARCH.weight_levels, n_post), jnp.float32), *state,
            gather=False, block=block, interpret=False)
    elif layout == "words":
        lowered = fused_timestep_words.lower(
            spikes, sds((k, n_post), jnp.int8),
            sds((1, n_post), jnp.float32), *state, all_nonzero=True,
            block=block, interpret=False)
    else:
        lowered = fused_timestep_dense.lower(
            spikes, sds((k, n_post), jnp.float32), *state, block=block,
            interpret=False)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.fixture(scope="module")
def arch_sim():
    rng = np.random.default_rng(0)
    weights = [rng.normal(0.0, 3.0 / np.sqrt(a), (a, b)).astype(np.float32)
               for a, b in LAYERS]
    return ChipSimulator(weights, engine="compiled", leak=ARCH.leak,
                         threshold=ARCH.threshold, freq_hz=ARCH.freq_hz,
                         mapping_strategy="greedy")


@pytest.mark.parametrize("batch", [8, 32])
def test_compiled_engine_compiles_for_v5e(one_chip, arch_sim, batch):
    """The compiled engine's whole run (scan over T=20, weights baked in
    as constants) compiles for one chip and fits its HBM."""
    run = CompiledEngine(arch_sim)._build_run()
    trains = jax.ShapeDtypeStruct(
        (batch, ARCH.timesteps, ARCH.layer_sizes[0]), jnp.float32,
        sharding=one_chip)
    mem = jax.jit(run).lower(trains).compile().memory_analysis()
    used = (mem.generated_code_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


@pytest.mark.parametrize("batch", [32, 12])
def test_recurrent_kernel_compiles_for_v5e(one_chip, batch):
    """The SHD network's recurrent layer (700 + 1024 rows -> 1024) as the
    fused engine feeds it: 44 input words, then 64 words of the layer's
    own last-step spikes, with the tile the engine picks."""
    kw = Z.spike_word_count(700) + Z.spike_word_count(1024)
    k, n = kw * Z.SPIKE_WORD_BITS, 1024
    block = _pick_engine_block(batch, k, n, interpret=False,
                               all_nonzero=True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = fused_timestep_codebook.lower(
        sds((batch, kw), jnp.uint16), sds((k, n), jnp.int8),
        sds((16, n), jnp.float32), sds((batch, n), jnp.float32),
        sds((batch, n), jnp.int32), gather=False, all_nonzero=True,
        block=block, interpret=False, name="snn_fused_l1")
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("all_nonzero", [True, False],
                         ids=["nonzero", "zero-words"])
@pytest.mark.parametrize("batch", [32, 12])
def test_recurrent_word_kernel_compiles_for_v5e(one_chip, batch,
                                                all_nonzero):
    """The SHD recurrent layer on the word layout (int8 words, a step
    row), with the tile the engine picks for that layout; with zero
    words the touch counts take the bf16 nonzero-mask dot."""
    kw = Z.spike_word_count(700) + Z.spike_word_count(1024)
    k, n = kw * Z.SPIKE_WORD_BITS, 1024
    block = _pick_engine_block(batch, k, n, interpret=False, layout="words",
                               all_nonzero=all_nonzero)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = fused_timestep_words.lower(
        sds((batch, kw), jnp.uint16), sds((k, n), jnp.int8),
        sds((1, n), jnp.float32), sds((batch, n), jnp.float32),
        sds((batch, n), jnp.int32), all_nonzero=all_nonzero, block=block,
        interpret=False, name="snn_fused_l1")
    assert "tpu_custom_call" in lowered.compile().as_text()
