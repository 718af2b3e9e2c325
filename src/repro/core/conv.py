"""Convolutional layers on the chip: one shared filter per layer.

A conv layer maps an (H, W, C_in) spike map to an (H', W', C_out) one
through a k x k filter at a stride, with zero padding; both maps are
flattened in HWC order (index (y * W + x) * C + c), which is the order
the layer's neurons are numbered, placed on cores and sent over the NoC.
A spiking k x k sum-pool is the same layer with a filter that is
diagonal over channels (`pool`).

Every core of the layer holds the same filter, as one `RegisterTable`
of codebook words.  A core holds a contiguous slice [lo, hi) of the
output neurons, which covers output rows `ya..yb`; its **input band**
is the input rows its receptive fields cover,
`max(0, ya*s - pad) .. min(H - 1, yb*s - pad + k - 1)`, all columns and
channels: the contiguous run of input neurons the core scans.  Per core
and step the chip then does:

* input spikes: the spikes in the band;
* empty words: the band's ZSPE scan, over the global 16-spike words of
  the layer's input that the band spans (`band_words` of them);
* SOPs performed: each spike in the band times its synapses into the
  core's neurons (the nonzero filter taps that land on one of them,
  borders counted exactly), which is the core's sum of the per-neuron
  touch counts `conv(spikes, filter != 0)`;
* neurons touched: those whose touch count is nonzero.

Nominal SOPs are the layer's real synapse count (`synapses`).  A
neuron's spikes reach only the cores whose input band holds it
(`reaches`), so the NoC flows of the layer before a conv layer are
local, not all-to-all.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.zspe import CURRENT_PRECISION, SPIKE_WORD_BITS


@dataclasses.dataclass(frozen=True)
class ConvGeometry:
    """One conv (or spiking sum-pool) layer's shape."""

    height: int
    width: int
    c_in: int
    c_out: int
    kernel: int
    stride: int = 1
    pad: int = 0
    pool: bool = False         # a k x k sum-pool: diagonal over channels

    def __post_init__(self):
        if min(self.height, self.width, self.c_in, self.c_out,
               self.kernel, self.stride) <= 0 or self.pad < 0:
            raise ValueError(f"bad conv geometry {self}")
        if self.pool and self.c_in != self.c_out:
            raise ValueError(f"a pool keeps its channels: {self}")
        if self.out_height <= 0 or self.out_width <= 0:
            raise ValueError(f"{self} has no output")

    @property
    def out_height(self) -> int:
        return (self.height + 2 * self.pad - self.kernel) // self.stride + 1

    @property
    def out_width(self) -> int:
        return (self.width + 2 * self.pad - self.kernel) // self.stride + 1

    @property
    def n_in(self) -> int:
        return self.height * self.width * self.c_in

    @property
    def n_out(self) -> int:
        return self.out_height * self.out_width * self.c_out

    @property
    def filter_shape(self) -> tuple[int, int, int, int]:
        return (self.kernel, self.kernel, self.c_in, self.c_out)

    @property
    def fan_in(self) -> int:
        """Filter taps into one neuron (inside the map)."""
        return self.kernel * self.kernel * (1 if self.pool else self.c_in)

    def band(self, lo: int, hi: int) -> tuple[int, int]:
        """The input band [start, stop) of output neurons [lo, hi)."""
        row = self.out_width * self.c_out
        ya, yb = lo // row, (hi - 1) // row
        r0 = max(0, ya * self.stride - self.pad)
        r1 = min(self.height - 1,
                 yb * self.stride - self.pad + self.kernel - 1)
        step = self.width * self.c_in
        return r0 * step, (r1 + 1) * step

    def band_words(self, lo: int, hi: int) -> tuple[int, int]:
        """The global 16-spike words [start, stop) the band spans."""
        a, b = self.band(lo, hi)
        return a // SPIKE_WORD_BITS, -(-b // SPIKE_WORD_BITS)

    def reaches(self, src_lo: int, src_hi: int, lo: int, hi: int) -> bool:
        """Whether input neurons [src_lo, src_hi) meet the band of output
        neurons [lo, hi)."""
        a, b = self.band(lo, hi)
        return src_lo < b and a < src_hi

    def pads(self) -> tuple[tuple[int, int], tuple[int, int]]:
        """Zero padding (low, high) of rows and columns that the output
        reads."""
        hi_y = (self.out_height - 1) * self.stride + self.kernel \
            - self.height - self.pad
        hi_x = (self.out_width - 1) * self.stride + self.kernel \
            - self.width - self.pad
        return (self.pad, max(hi_y, 0)), (self.pad, max(hi_x, 0))


class ConvLayer(NamedTuple):
    """A conv weight layer as `ChipSimulator` takes it: the geometry and
    a `quant.QuantizedTensor` of the (k, k, C_in, C_out) filter under one
    whole-tensor codebook."""

    geometry: ConvGeometry
    filter: object


def synapses_per_neuron(geom: ConvGeometry, nonzero) -> np.ndarray:
    """(n_out,) int64: the nonzero filter taps into each output neuron
    that land inside the input map (borders counted exactly)."""
    nz = np.asarray(nonzero) != 0                       # (k, k, Ci, Co)
    taps = nz.sum(axis=2).astype(np.int64)              # (k, k, Co)
    (py, _), (px, _) = geom.pads()
    k, s = geom.kernel, geom.stride
    ys = np.arange(geom.out_height)[:, None] * s - py + np.arange(k)
    xs = np.arange(geom.out_width)[:, None] * s - px + np.arange(k)
    vy = ((ys >= 0) & (ys < geom.height)).astype(np.int64)   # (Ho, k)
    vx = ((xs >= 0) & (xs < geom.width)).astype(np.int64)    # (Wo, k)
    per = np.einsum("ya,xb,abc->yxc", vy, vx, taps)
    return per.reshape(-1)


def conv_spikes(x: jax.Array, w: jax.Array, geom: ConvGeometry,
                precision=CURRENT_PRECISION) -> jax.Array:
    """(..., n_in) spikes through the (k, k, C_in, C_out) filter `w` ->
    (..., n_out): one XLA conv at `precision`, HWC in and out."""
    lead = x.shape[:-1]
    img = x.reshape((-1, geom.height, geom.width, geom.c_in))
    out = jax.lax.conv_general_dilated(
        img.astype(w.dtype), w, window_strides=(geom.stride, geom.stride),
        padding=geom.pads(), dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)
    return out.reshape(lead + (geom.n_out,))


def tap_patches(x: jax.Array, geom: ConvGeometry) -> jax.Array:
    """(B, n_in) 0/1 spikes -> (C_in*k*k, B*H'*W') bf16: for each output
    pixel (columns, batch-major then HW) the input spikes under each
    filter tap (rows, in (c_in, dy, dx) order), zero outside the map.
    A filter's words in that order, (C_out, C_in*k*k), times these
    columns are the layer's currents, one MXU pass.  Built by one XLA
    patches conv with an identity filter, exact for 0/1 spikes."""
    img = x.astype(jnp.bfloat16).reshape(x.shape[0], geom.height,
                                         geom.width, geom.c_in)
    p = jax.lax.conv_general_dilated_patches(
        img, (geom.kernel, geom.kernel), (geom.stride, geom.stride),
        geom.pads(), dimension_numbers=("NHWC", "OIHW", "CNHW"))
    return p.reshape(p.shape[0], -1)


@dataclasses.dataclass(frozen=True)
class SliceTables:
    """A conv layer's per-core tables, one row per core slice: the input
    band [start, stop) (`band`), the global words it spans (`words`),
    and the synapses into the slice's neurons (`synapses`); with the
    layer's synapse count (`total_synapses`, its nominal SOPs a step)."""

    band: np.ndarray           # (A, 2) int64
    words: np.ndarray          # (A, 2) int64
    synapses: np.ndarray       # (A,) int64
    total_synapses: int

    def onehots(self, n_in: int) -> tuple[np.ndarray, np.ndarray]:
        """(n_in, A) and (words of n_in, A) f32 indicators of each
        slice's band of input neurons and of input words."""
        kw = -(-n_in // SPIKE_WORD_BITS)
        band = np.zeros((n_in, len(self.band)), np.float32)
        words = np.zeros((kw, len(self.band)), np.float32)
        for i, ((a, b), (wa, wb)) in enumerate(zip(self.band, self.words)):
            band[a:b, i] = 1.0
            words[wa:wb, i] = 1.0
        return band, words


def slice_tables(geom: ConvGeometry, nonzero, slices) -> SliceTables:
    """`SliceTables` of a conv layer whose nonzero filter taps are
    `nonzero` (k, k, C_in, C_out), held by core slices [(lo, hi)]."""
    per = synapses_per_neuron(geom, nonzero)
    return SliceTables(
        band=np.array([geom.band(lo, hi) for lo, hi in slices], np.int64),
        words=np.array([geom.band_words(lo, hi) for lo, hi in slices],
                       np.int64),
        synapses=np.array([per[lo:hi].sum() for lo, hi in slices],
                          np.int64),
        total_synapses=int(per.sum()))
