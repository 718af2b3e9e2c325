"""What a cell runs, made from `--seed`: codebook weights and spike trains.

Weights are built directly in the chip's codebook form, with no k-means:
per layer, 16 levels that are signed 8-bit words times one scale, and a
4-bit index per synapse.  The levels come in +/- pairs, so every weight
is nonzero and the layer's mean weight is 0; the scale sets the weight
spread near `weight_gain / sqrt(fan_in)`, which makes the hidden layers
and the output layer fire.

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
the host.
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


def _device_indices(seed: int, shapes: tuple, n_levels: int):
    # seeds may exceed 32 bits: fold the high word in
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return _draw_indices(key, shapes, n_levels)


def make_weights(config: dict, seed: int):
    """-> (program weights: list of `quant.QuantizedTensor` on the device,
    reference weights: list of `LayerCodebook` on the host)."""
    from repro.core.quant import QuantizedTensor

    sizes = [int(s) for s in config["layer_sizes"]]
    shapes = tuple(zip(sizes[:-1], sizes[1:]))
    idx_dev = _device_indices(seed, shapes, int(config["weight_levels"]))
    program, reference = [], []
    for li, ((n_pre, _), idx) in enumerate(zip(shapes, idx_dev)):
        words, scale = layer_levels(config, seed, li, n_pre)
        levels = words.astype(np.float32) * scale
        program.append(QuantizedTensor(
            idx=idx, codebook=jnp.asarray(levels[None, :]),
            scale=jnp.asarray([scale], jnp.float32), group_axis_size=0))
        reference.append(LayerCodebook(idx=np.asarray(idx), words=words,
                                       scale=scale, levels=levels))
    return program, reference


def chip_plan(sim) -> dict:
    """The mapping compiler's placement and routes of `sim`, as the plain
    data `reference.run` prices from: per weight layer its core slices
    `[core, lo, hi]`, per layer that fires into another one flow per
    slice (`src`, `dsts`, `links`), and the level-2 router nodes."""
    n_layers = len(sim.mapping.layer_sizes) - 1
    return {
        "layers": [[[a.core_id, a.neuron_lo, a.neuron_hi]
                    for a in sim.mapping.cores_of_layer(li + 1)]
                   for li in range(n_layers)],
        "routes": [[{"src": int(r.src), "dsts": [int(d) for d in r.dsts],
                     "links": [[int(u), int(v)] for u, v in r.links]}
                    for r in sim._layer_routes[li + 1]]
                   for li in range(n_layers - 1)],
        "level2_nodes": sorted(int(x) for x in sim._level2),
    }


def make_trains(config: dict, n: int, seed: int) -> np.ndarray:
    """`n` distinct spike trains (n, T, n_in) f32 from the config's input
    generator, a pure function of `seed`."""
    spec = config["input"]
    gen = registry.load_module("inputs", spec["kind"])
    trains = gen.make(spec, n, int(config["timesteps"]), seed)
    if trains.shape[-1] != int(config["layer_sizes"][0]):
        raise ValueError(f"input width {trains.shape[-1]} differs from the "
                         f"network's {config['layer_sizes'][0]}")
    return trains
