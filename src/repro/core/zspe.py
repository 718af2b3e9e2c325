"""ZSPE + SPE — zero-skip sparse spike processing (paper C1) and its
cycle-accurate performance model.

Chip microarchitecture (Fig. 1/2):
  * ZSPE loads 16 pre-synaptic spikes per cycle from the ping-pong cache and
    scans them in parallel, forwarding the *weight indexes* of valid (=1)
    spikes to the SPEs.  Zero spikes produce no downstream work.
  * Two SPEs dequantize 4 synapse weights per cycle total from the shared
    codebook (2 x "4-bit synapse computing" lanes, 8-bit combined) and
    accumulate partial membrane potentials.
  * The neuron updater integrates MPs and fires (see core/neuron.py).

Functional model: a spike-driven matmul  I = S @ dequant(idx, codebook)
with S a binary {0,1} matrix.  `zspe_matmul` is the pure-jnp semantics
(the Pallas kernel in kernels/zspe_spmm.py must match it exactly);
`CycleModel` reproduces the throughput curve of Fig. 3.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.quant import QuantizedTensor, dequantize


# Precision of every synaptic-current matmul (`spikes @ w`) in the engines,
# the reference loop and the fused kernel.  A TPU's default f32 matmul
# rounds its operands to bf16; HIGHEST keeps the f32 codebook weights, so
# the simulated chip integrates the same currents as on the CPU.
CURRENT_PRECISION = jax.lax.Precision.HIGHEST


def zspe_matmul(spikes: jax.Array, weights: jax.Array) -> jax.Array:
    """Spike-driven synaptic integration: (B, n_pre) {0,1} x (n_pre, n_post).

    Zero-skip is a *performance* feature; semantics are the plain product.
    """
    return spikes.astype(weights.dtype) @ weights


# ---------------------------------------------------------------------------
# Spike words — the chip's on-wire spike format (16 spikes per word)
# ---------------------------------------------------------------------------
#
# The ZSPE front-end loads 16 pre-synaptic spikes per cycle as one word from
# the ping-pong cache and scans the word's bits in parallel; an all-zero
# word generates no synaptic work at all.  These helpers are the software
# model of that format: binary spike vectors travel as uint16 words (32x
# fewer bytes than f32 lanes), and `empty_spike_words` is the per-row count
# of words the ZSPE scan skips outright — the skip telemetry the fused
# engine emits and tests/test_engine_equiv.py checks against a numpy
# popcount oracle.

SPIKE_WORD_BITS = 16


def spike_word_count(n: int) -> int:
    """Words needed for `n` spikes (the last word zero-padded)."""
    return -(-int(n) // SPIKE_WORD_BITS)


def pack_spike_words(spikes: jax.Array) -> jax.Array:
    """(..., K) {0,1} -> (..., ceil(K/16)) uint16, LSB-first per word.

    Padding bits (K up to the word boundary) are zero, so popcounts over
    packed words equal popcounts over the unpacked spikes exactly.
    """
    k = spikes.shape[-1]
    kw = spike_word_count(k)
    pad = kw * SPIKE_WORD_BITS - k
    bits = jnp.asarray(spikes != 0, jnp.uint16)
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    bits = bits.reshape(*bits.shape[:-1], kw, SPIKE_WORD_BITS)
    shifts = jnp.arange(SPIKE_WORD_BITS, dtype=jnp.uint16)
    return jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint16)


def unpack_spike_words(packed: jax.Array, n: int | None = None) -> jax.Array:
    """Inverse of `pack_spike_words` -> (..., n) f32 {0,1}.

    `n` crops the trailing word's zero padding (defaults to all 16*Kw
    lanes, which is the padded width the fused kernel consumes).
    """
    shifts = jnp.arange(SPIKE_WORD_BITS, dtype=jnp.uint16)
    bits = (packed[..., None] >> shifts) & jnp.uint16(1)
    flat = bits.reshape(*packed.shape[:-1],
                        packed.shape[-1] * SPIKE_WORD_BITS)
    if n is not None:
        flat = flat[..., :n]
    return flat.astype(jnp.float32)


def empty_spike_words(packed: jax.Array) -> jax.Array:
    """Per-row count of all-zero 16-spike words (the ZSPE word-scan skip)."""
    return jnp.sum((packed == 0).astype(jnp.int32), axis=-1)


def zspe_matmul_q(spikes: jax.Array, q: QuantizedTensor) -> jax.Array:
    return zspe_matmul(spikes, dequantize(q))


@dataclasses.dataclass(frozen=True)
class CoreGeometry:
    """Per-core resources (register-table configurables + fixed datapath)."""

    spike_lanes: int = 16        # ZSPE parallel spike window
    spe_lanes: int = 4           # synapses processed per cycle (2 SPEs x 2)
    freq_hz: float = 200e6       # nominal core clock
    max_neurons: int = 8192      # 160K neurons / 20 cores
    pipeline_depth: int = 4      # caches -> ZSPE -> SPE -> updater
    write_lanes: int = 4         # register-table index writes per cycle
                                 # (plasticity stage; shares the SPE port
                                 # width into the weight-index SRAM)


@dataclasses.dataclass(frozen=True)
class CycleModel:
    """Cycle/throughput model of one neuromorphic core.

    For a layer with `n_pre` inputs, `n_post` outputs (fanout per spike =
    n_post mapped on the core), a timestep with spike sparsity `s`
    (fraction of ZEROS) costs:

        spike-load cycles : ceil(n_pre / 16)                (ZSPE scan)
        synapse cycles    : ceil(nnz * n_post / 4)          (SPE, zero-skip)
        update cycles     : ceil(n_touched / 1)             (neuron updater)

    and the pipeline overlaps stages, so the critical path is the max of the
    stage costs plus fill/drain.  The baseline ("traditional") scheme
    processes every synapse regardless of spike value and updates every
    neuron: synapse cycles = ceil(n_pre * n_post / 4), updates = n_post.
    """

    geom: CoreGeometry = CoreGeometry()

    def stage_cycles(self, n_pre: int, n_post: int, nnz: float, touched: float,
                     zero_skip: bool = True, partial_update: bool = True):
        g = self.geom
        load = -(-n_pre // g.spike_lanes)
        syn_ops = (nnz if zero_skip else n_pre) * n_post
        # integer cycle counts, as documented: the SPEs cannot issue a
        # fractional cycle, nor can the updater touch 2.5 neurons
        syn = math.ceil(syn_ops / g.spe_lanes)
        upd = math.ceil(touched) if partial_update else n_post
        return load, syn, upd

    def timestep_cycles(self, n_pre: int, n_post: int, nnz: float,
                        touched: float, zero_skip: bool = True,
                        partial_update: bool = True,
                        writes: float | None = None) -> float:
        load, syn, upd = self.stage_cycles(
            n_pre, n_post, nnz, touched, zero_skip, partial_update)
        # 4-stage pipeline: stages overlap; throughput set by slowest stage.
        crit = max(load, syn, upd)
        if writes is not None:
            # plasticity stage: register-table index writes drain through
            # `write_lanes` ports, overlapped with the other stages
            crit = max(crit, math.ceil(writes / self.geom.write_lanes))
        return crit + self.geom.pipeline_depth

    def stage_cycles_array(self, n_pre: int, n_post, nnz, touched,
                           zero_skip: bool = True, partial_update: bool = True):
        """Array-native `stage_cycles`: `n_post`/`touched` may be jnp arrays
        (one entry per core slice of a layer) and `nnz` a traced scalar, so
        the compiled engine can price every core of a layer in one
        vectorized expression inside `jax.lax.scan`.  Applies the same
        `ceil` as the scalar path; the engines feed it integer-exact
        per-slice nnz/touched counts, so the two paths cannot disagree
        at a ceil boundary."""
        g = self.geom
        load = -(-n_pre // g.spike_lanes)
        syn = jnp.ceil((nnz if zero_skip else float(n_pre)) * n_post
                       / g.spe_lanes)
        upd = jnp.ceil(touched) if partial_update else n_post
        return load, syn, upd

    def timestep_cycles_array(self, n_pre: int, n_post, nnz, touched,
                              zero_skip: bool = True,
                              partial_update: bool = True,
                              writes=None):
        """Array-native `timestep_cycles` (jnp.maximum instead of max()).

        `writes=None` (the inference default) emits the exact pre-plasticity
        expression, keeping the plasticity-off jaxpr unchanged.  With
        integer-exact write counts and a power-of-two `write_lanes` the f32
        division is exact, so ceil here agrees with the scalar path's
        math.ceil bit-for-bit."""
        load, syn, upd = self.stage_cycles_array(
            n_pre, n_post, nnz, touched, zero_skip, partial_update)
        crit = jnp.maximum(jnp.maximum(jnp.asarray(load, jnp.float32), syn), upd)
        if writes is not None:
            crit = jnp.maximum(crit, jnp.ceil(writes / self.geom.write_lanes))
        return crit + self.geom.pipeline_depth

    def sop_count(self, n_pre: int, n_post: int, nnz: float,
                  zero_skip: bool = True) -> float:
        """SOPs actually *performed*.  With zero-skip only valid-spike
        synapses are ops; the baseline performs them all (zeros included)."""
        return (nnz if zero_skip else n_pre) * n_post

    def gsops(self, n_pre: int, n_post: int, sparsity: float,
              zero_skip: bool = True, partial_update: bool = True) -> float:
        """Computing efficiency (GSOP/s) at a given spike sparsity.

        Convention matches the paper's Fig. 3: throughput is quoted in
        *synaptic operations delivered per second*, where a delivered SOP is
        a valid-spike synaptic update (so at sparsity 1.0 throughput -> 0).
        """
        nnz = n_pre * (1.0 - sparsity)
        touched = n_post * min(1.0, nnz / max(n_post, 1) * 4)  # rough touch est.
        cyc = self.timestep_cycles(n_pre, n_post, nnz, touched,
                                   zero_skip, partial_update)
        sops = n_pre * (1.0 - sparsity) * n_post
        return sops / cyc * self.geom.freq_hz / 1e9
