"""What a cell runs, made from `--seed`: the pieces of codebook weights
that every network kind draws its layers from, and spike trains.

A kind (`bench/networks/<kind>.py`, its `make`) builds its weights
directly in the chip's codebook form from these, with no k-means: per
layer, 16 levels that are signed 8-bit words times one scale
(`layer_levels`), and a 4-bit index per synapse (`device_indices`).
The levels come in +/- pairs, so every weight is nonzero and the
layer's mean weight is 0; the scale sets the weight spread near
`weight_gain / sqrt(fan_in)`, which makes the hidden layers and the
output layer fire.

The scale is rounded to `scale_mantissa_bits` (11) significant bits.  A
weight then has at most 18 significant bits, so a synaptic current's
partial sums are exact f32 numbers in any summation order as long as
they stay under 2**24 units of the scale's last bit, which the weights'
spread keeps them far from (`bench/tests` checks it at the NMNIST
network): currents then do not depend on how a matmul is tiled or
which device runs it.  At 18 bits a weight is more than a 3-pass bf16
matmul (precision HIGH) keeps, so a current computed one precision step
below f32 HIGHEST differs.

The indices (the bulk: 13.7M for the NMNIST network) are drawn on the
device in one jitted call; the levels are 16 numbers per layer, drawn on
the host.  The trains come from the configuration's input generator
(`bench/inputs/<kind>.py`), at the width the network kind takes in.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import registry
from bench.reference import high_precision_weights


@dataclasses.dataclass(frozen=True)
class LayerCodebook:
    """One layer's weights: `levels[idx]`."""

    idx: np.ndarray        # (n_pre, n_post) int8, host copy
    words: np.ndarray      # (n_levels,) int32 signed W-bit register words
    scale: np.float32      # fixed-point step
    levels: np.ndarray     # (n_levels,) f32 = words * scale

    def dense(self) -> np.ndarray:
        return self.levels[self.idx.astype(np.int64)]


def layer_levels(config: dict, seed: int, layer: int, fan_in: int):
    """(words, scale) of one layer, a pure function of (seed, layer).

    The scale is `weight_gain / sqrt(fan_in)` over a nominal word rms,
    rounded to `scale_mantissa_bits` significant bits (odd, so all of
    them count).  The words are drawn among those whose level a 3-pass
    bf16 product cannot hold (`reference.high_precision_weights` changes
    it), so every weight needs f32 HIGHEST.
    """
    n_levels, bits = int(config["weight_levels"]), int(config["weight_bits"])
    qmax = 2 ** (bits - 1) - 1
    nominal_rms = 0.75 * qmax
    raw = float(config["weight_gain"]) / math.sqrt(fan_in) / nominal_rms
    mbits = int(config["scale_mantissa_bits"])
    e = math.floor(math.log2(raw)) - (mbits - 1)
    m = min(int(round(raw / 2.0 ** e)) | 1, 2 ** mbits - 1)
    scale = np.float32(m * 2.0 ** e)
    cand = np.arange(1, qmax + 1)
    lv = cand.astype(np.float32) * scale
    cand = cand[high_precision_weights(lv) != lv]
    if len(cand) < n_levels // 2:
        raise ValueError(f"layer {layer}: only {len(cand)} words need f32 "
                         f"HIGHEST at this scale; raise scale_mantissa_bits")
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x57, layer]))
    mags = np.sort(rng.choice(cand, n_levels // 2, replace=False))
    return np.concatenate([-mags[::-1], mags]).astype(np.int32), scale


@partial(jax.jit, static_argnums=(1, 2))
def _draw_indices(key, shapes: tuple, n_levels: int):
    return tuple(
        jax.random.randint(jax.random.fold_in(key, i), s, 0, n_levels,
                           dtype=jnp.int8)
        for i, s in enumerate(shapes))


def device_indices(seed: int, shapes: tuple, n_levels: int):
    """Uniform int8 indices in [0, n_levels) of each shape, on the device,
    in one jitted call: a pure function of `seed`."""
    # seeds may exceed 32 bits: fold the high word in
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return _draw_indices(key, shapes, n_levels)


def make_trains(config: dict, n: int, seed: int) -> np.ndarray:
    """`n` distinct spike trains (n, T, n_in) f32 from the config's input
    generator, a pure function of `seed`."""
    spec = config["input"]
    gen = registry.load_module("inputs", spec["kind"])
    trains = gen.make(spec, n, int(config["timesteps"]), seed)
    n_in = registry.network(config).n_in(config)
    if trains.shape[-1] != n_in:
        raise ValueError(f"input width {trains.shape[-1]} differs from the "
                         f"network's {n_in}")
    return trains
