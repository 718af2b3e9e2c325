"""Pallas TPU kernel: one conv layer-step from the filter's integer words.

The conv counterpart of `fused_timestep.fused_timestep_words`.  A conv
layer's cores share one filter (core/conv.py), so the kernel's weight
operand is that filter once, as int8 table words (C_out, C_in*k*k) plus
the table's one fixed-point step, never an (n_pre, n_post) matrix.  The
input arrives as the layer's tap patches (`conv.tap_patches`): for each
output pixel (a lane) the 0/1 input spikes under each filter tap (a
sublane), zero outside the map.  Per (C_out, bm) lane tile:

  SPE             currents = (words @ patches) * step: one bf16 MXU pass
                  of 0/1 spikes times integers |word| <= 128, whose f32
                  sums are exact while each output channel's sum of
                  |word| stays under 2**24 (the lowering checks it, per
                  filter), then the step once -- the correctly rounded
                  f32 current, in any tiling.
  touch counts    (filter != 0) @ patches, one more bf16 pass: the
                  spikes reaching each neuron through nonzero taps,
                  exact integers; a neuron is touched when its count is
                  nonzero, and the counts summed over a core's neurons
                  are its performed SOPs.
  neuron updater  the partial-update LIF step of `fused_timestep`
                  (`_lif_tile`) on the same tile.

Channels are sublanes and output pixels (batch-major, then HW) lanes,
so the membrane state is lane-dense at any channel count.  The caller
pads channels to a multiple of 8, taps to a multiple of 128 and pixels
to a multiple of the lane tile, all with zeros: a padded channel or
pixel takes no current and no touch, and never fires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_timestep import (VMEM_BUDGET_BYTES,
                                          VMEM_LIMIT_BYTES, _lif_tile, _widen)

LANE_TILES = (4096, 2048, 1024, 512, 256, 128)


def vmem_bytes(c: int, k: int, bm: int) -> int:
    """Conservative VMEM footprint of one grid step: the patch tile, the
    words and nonzero mask, the step, v/elapsed in and four state tiles
    out, double-buffered, plus the widened words and mask, the f32
    current and count tiles and the LIF temporaries."""
    def tile(rows, cols, itemsize):
        sub = 8 * (4 // itemsize)
        return (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize

    blocks = (tile(k, bm, 2) + 2 * tile(c, k, 1) + tile(1, 1, 4)
              + 6 * tile(c, bm, 4))
    temps = 4 * tile(c, k, 4) + 10 * tile(c, bm, 4)
    return 2 * blocks + temps


def pick_lane_tile(c: int, k: int, m: int, interpret: bool) -> int:
    """The lane tile of an (c, k) x (k, m) conv layer-step: all of `m`
    in interpret mode (one exact tile), else the widest of `LANE_TILES`
    not past `m` rounded up to 128 whose `vmem_bytes` fit the budget."""
    if interpret:
        return m
    m128 = -(-m // 128) * 128
    for bm in LANE_TILES:
        if bm <= m128 and vmem_bytes(c, k, bm) <= VMEM_BUDGET_BYTES:
            return bm
    raise ValueError(f"no conv lane tile of a ({c}, {k}) x ({k}, {m}) "
                     f"layer-step fits the {VMEM_BUDGET_BYTES >> 20} MiB "
                     f"VMEM budget")


def _kernel(p_ref, w_ref, nz_ref, st_ref, v_ref, el_ref,
            vo_ref, elo_ref, sp_ref, tc_ref, *, threshold: float,
            leak: float, reset: float, partial_update: bool, decays):
    p = p_ref[...]                                      # (k, bm) bf16 0/1
    cur = jnp.dot(_widen(w_ref[...]), p,
                  preferred_element_type=jnp.float32) * st_ref[...]
    tcnt = jnp.dot(_widen(nz_ref[...]), p,
                   preferred_element_type=jnp.float32)
    vo, elo, sp, _ = _lif_tile(
        v_ref[...], el_ref[...], cur, tcnt, threshold=threshold, leak=leak,
        reset=reset, partial_update=partial_update, decays=decays)
    vo_ref[...] = vo
    elo_ref[...] = elo
    sp_ref[...] = sp
    tc_ref[...] = tcnt.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "threshold", "leak", "reset", "partial_update", "block", "interpret",
    "name", "decays"))
def conv_timestep(
    patches: jax.Array,       # (K, M) bf16 0/1 tap patches
    words: jax.Array,         # (C, K) int8 filter words
    nonzero: jax.Array,       # (C, K) int8 0/1: the filter's nonzero taps
    step: jax.Array,          # (1, 1) f32 fixed-point step
    v: jax.Array,             # (C, M) f32 membrane potential
    elapsed: jax.Array,       # (C, M) int32 idle-step stamps
    *,
    threshold: float = 1.0,
    leak: float = 0.9,
    reset: float = 0.0,
    partial_update: bool = True,
    block: int | None = None,
    interpret: bool = True,
    name: str | None = None,
    decays: tuple | None = None,
):
    """One conv layer-step.  Returns (v', elapsed', spikes (C, M) f32,
    touch counts (C, M) int32).  `block` is the lane tile (None: all of
    M); `name` names the kernel in the compiled program and its
    profile; `decays` (`neuron.decay_table`) makes the leak exact."""
    k, m = patches.shape
    c = v.shape[0]
    bm = m if block is None else block
    assert m % bm == 0 and words.shape == (c, k), (patches.shape,
                                                   words.shape, block)
    kern = functools.partial(_kernel, threshold=threshold, leak=leak,
                             reset=reset, partial_update=partial_update,
                             decays=decays)
    state = pl.BlockSpec((c, bm), lambda j: (0, j))
    whole = pl.BlockSpec((c, k), lambda j: (0, 0))
    return pl.pallas_call(
        kern,
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((k, bm), lambda j: (0, j)), whole, whole,
                  pl.BlockSpec((1, 1), lambda j: (0, 0)), state, state],
        out_specs=[state, state, state, state],
        out_shape=[
            jax.ShapeDtypeStruct((c, m), v.dtype),          # v'
            jax.ShapeDtypeStruct((c, m), elapsed.dtype),    # elapsed'
            jax.ShapeDtypeStruct((c, m), v.dtype),          # spikes
            jax.ShapeDtypeStruct((c, m), jnp.int32),        # touch counts
        ],
        input_output_aliases={4: 0, 5: 1},
        compiler_params=(None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES)),
        interpret=interpret,
        name=name,
    )(patches, words, nonzero, step, v, elapsed)
