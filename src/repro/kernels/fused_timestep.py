"""Pallas TPU kernel: one fused ZSPE -> codebook-dequant -> LIF timestep.

This is the software image of the chip's 4-level core pipeline (caches ->
ZSPE -> SPE -> neuron updater, paper Fig. 1/2) collapsed into one VMEM
pass per layer-step — membrane state never spills between stages, exactly
as the hardware keeps partial MPs resident across the pipeline.  See
DESIGN.md §4 for the full kernel layout; §2 for the block-skip rationale.

Stage map (chip -> kernel):

  ping-pong cache   spikes arrive **bitpacked**: uint16 words of 16
                    spikes each (`core.zspe.pack_spike_words`), 32x fewer
                    HBM bytes than f32 lanes.  The kernel unpacks a
                    (bm, Kw) word tile in VMEM (an exact 0/1 expand
                    matmul, then lane shifts).
  ZSPE word scan    the word tile is popcounted; an all-empty spike tile
                    takes the `pl.when` skip branch — no dequant, no MXU
                    work, just the partial-update bookkeeping (elapsed+1).
                    Per-row empty-word counts are emitted as the skip
                    telemetry the energy model and tests consume.
  SPE               weights arrive codebook-compressed and the dense f32
                    matrix never exists in HBM.  Where every level of a
                    layer's tables is an exact (<= 8-bit) integer word
                    times the core's fixed-point step (the lowering
                    checks it), the kernel takes the int8 **word** of
                    each synapse plus a per-column step, as the chip's
                    SPE does: it accumulates the words in one bf16 MXU
                    pass (0/1 spikes x integers, exact, f32 sums under
                    2**24) and multiplies the column by its step once.
                    Otherwise it takes log2(N)-bit codebook indexes plus
                    a per-column level table (`RegisterTable` words x
                    scale, f32), expanded **in-register** by N
                    compare+select passes (TPU VPU-friendly) or a flat
                    one-pass gather (faster under interpret mode on
                    CPU), bit-identical f32 values, for an f32 HIGHEST
                    matmul.
  neuron updater    the partial-update LIF step (paper C2) runs on the
                    same VMEM tile: lazy-leak decay, integrate, fire,
                    hard reset, `elapsed` stamp — using the integer-exact
                    connectivity touch counts (`spikes @ (w != 0)`), so
                    the touch set cannot flip on float cancellation.

Grid is (M/bm, N/bn); K is **not** tiled — each kernel instance reduces
over the full (word-padded) K so the f32 accumulation grouping matches a
plain `spikes @ w` matmul (K zero-padding is bit-neutral; see
tests/test_fused_kernel.py).  The engine invokes it with bm=M, bn=N in
interpret mode, which makes the fused path bit-identical to the compiled
engine's dense matmul + `lif_step`; smaller blocks are for real-TPU VMEM
budgets, where tiling only perturbs float currents at the ulp level (on
the word layout not at all: its sums are exact integers).

The dense-weight variant (`fused_timestep_dense`) exists for float
(unquantized) simulators — same ZSPE/LIF fusion, weights as plain f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the chip's spike-word width and the synaptic-current matmul precision —
# single source of truth with the packing side and the array engines
# (core.zspe has no kernels dependency, so no import cycle)
from repro.core.zspe import CURRENT_PRECISION, SPIKE_WORD_BITS

_WORD_SHIFT = SPIKE_WORD_BITS.bit_length() - 1       # lane k -> word k >> 4
_CHUNK_WORDS = 128                 # words per unpack matmul (2048 lanes)

# scoped VMEM the compiled kernel may use (a v5e core has 128 MiB); the
# engine's tile picker keeps `vmem_bytes` under VMEM_BUDGET_BYTES
VMEM_LIMIT_BYTES = 64 << 20
VMEM_BUDGET_BYTES = 48 << 20


def _unpack_words(pk: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(bm, kw) uint16 -> ((bm, kw*16) f32 {0,1}, (bm, 1) int32 popcounts).

    2-D and 32-bit throughout, because Mosaic has neither a uint16 -> f32
    cast nor a reshape that splits the lane axis.  The words widen to
    int32 and split into bytes; per run of up to `_CHUNK_WORDS` words an
    exact 0/1 expand matmul copies word k >> 4 into lane k (bytes and 0/1
    are exact in bf16, and each lane sums exactly one nonzero term); a
    lane-iota shift then selects bit k & 15.
    """
    bm, kw = pk.shape
    words = pk.astype(jnp.int32)
    lo = (words & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
    hi = (words >> 8).astype(jnp.float32).astype(jnp.bfloat16)
    cw = min(kw, _CHUNK_WORDS)
    shape = (cw, cw * SPIKE_WORD_BITS)
    expand = ((jax.lax.broadcasted_iota(jnp.int32, shape, 1) >> _WORD_SHIFT)
              == jax.lax.broadcasted_iota(jnp.int32, shape, 0)
              ).astype(jnp.float32).astype(jnp.bfloat16)
    pieces = []
    for c0 in range(0, kw, cw):
        n = min(cw, kw - c0)
        e = expand[:n, :n * SPIKE_WORD_BITS]
        pieces.append(
            jnp.dot(lo[:, c0:c0 + n], e, preferred_element_type=jnp.float32)
            + 256.0 * jnp.dot(hi[:, c0:c0 + n], e,
                              preferred_element_type=jnp.float32))
    lanes = (pieces[0] if len(pieces) == 1
             else jnp.concatenate(pieces, axis=1)).astype(jnp.int32)
    shift = jax.lax.broadcasted_iota(jnp.int32, lanes.shape, 1) & (
        SPIKE_WORD_BITS - 1)
    bits = (lanes >> shift) & 1
    return bits.astype(jnp.float32), jnp.sum(bits, axis=1, keepdims=True)


def vmem_bytes(bm: int, bn: int, kw: int, *, layout: str = "codebook",
               n_levels: int = 16, all_nonzero: bool = False) -> int:
    """Conservative VMEM footprint of one grid step of the compiled kernel.

    Counts every operand block double-buffered (spike words, the weight
    slab, the level table or step row, v/elapsed in; four state tiles and
    two row counters out), each padded to its (sublane, 128-lane) tile,
    plus the in-kernel temporaries: the unpack's expand matrix and its
    iotas, the unpacked spike lanes, the weight slab as the MXU takes it
    (`codebook`: the dequantized f32 slab with its int32 indexes and
    select temporaries; `words`: the bf16 words, their int32 and f32
    steps of widening, and the bf16 spikes; `dense`: the f32 slab), its
    nonzero mask unless `all_nonzero`, and the LIF tile arithmetic.
    """
    k = kw * SPIKE_WORD_BITS

    def tile(rows, cols, itemsize):
        sub = 8 * (4 // itemsize)
        return (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize

    slab, table, temp = {
        "codebook": (tile(k, bn, 1), tile(n_levels, bn, 4),
                     4 * tile(k, bn, 4)),
        "words": (tile(k, bn, 1), tile(1, bn, 4),
                  2 * tile(k, bn, 4) + tile(k, bn, 2) + tile(bm, k, 2)),
        "dense": (tile(k, bn, 4), 0, tile(k, bn, 4)),
    }[layout]
    mask = 0 if all_nonzero else tile(k, bn, 2 if layout == "words" else 4)
    blocks = (tile(bm, kw, 2) + slab + table
              + 6 * tile(bm, bn, 4) + 2 * tile(bm, 1, 4))
    cw = min(kw, _CHUNK_WORDS)
    temps = (3 * tile(cw, cw * SPIKE_WORD_BITS, 4) + 6 * tile(bm, k, 4)
             + temp + mask + 8 * tile(bm, bn, 4))
    return 2 * blocks + temps


def _dequant_columns(idx: jax.Array, cbw: jax.Array,
                     gather: bool) -> jax.Array:
    """Expand (K, bn) indexes against per-column level values (L, bn).

    Both strategies produce the identical f32 element `cbw[idx[k, n], n]`:
    a flat one-pass gather (fast on the CPU interpret path) or L
    compare+select passes (VPU-friendly on real TPU, no dynamic gather).
    """
    if gather:
        k, bn = idx.shape
        cols = jax.lax.broadcasted_iota(jnp.int32, (k, bn), 1)
        return cbw.reshape(-1)[idx * bn + cols]
    w = jnp.zeros(idx.shape, jnp.float32)
    for l in range(cbw.shape[0]):
        w = w + jnp.where(idx == l, cbw[l:l + 1, :], 0.0)
    return w


def _lif_tile(v, el, cur, tcnt, *, threshold, leak, reset, partial_update,
              decays=None):
    """The neuron-updater stage on one (bm, bn) tile.

    Expression-for-expression the same float program as
    `core.neuron.lif_step` (hard reset), so a jitted caller sees
    bit-identical v / elapsed / spikes.  `decays` (`neuron.decay_table`)
    gives `leak**pending` exactly, by selects over its entries.
    """
    if partial_update:
        touched = tcnt > 0
        pending = el + 1
        if decays is None:
            leak_n = leak ** pending.astype(v.dtype)
        else:
            leak_n = jnp.full(v.shape, decays[-1], v.dtype)
            for n, d in enumerate(decays[:-1]):
                leak_n = jnp.where(pending == n, d, leak_n)
        decay = jnp.where(touched, leak_n, 1.0)
        v_int = v * decay + cur
        v_eff = jnp.where(touched, v_int, -jnp.inf)
        spikes = ((v_eff - threshold) >= 0.0).astype(v.dtype)
        v_new = jnp.where(spikes > 0, reset,
                          jnp.where(touched, v_int, v))
        el_new = jnp.where(touched, 0, pending)
    else:
        v_int = v * leak + cur
        spikes = ((v_int - threshold) >= 0.0).astype(v.dtype)
        touched = jnp.ones(v.shape, bool)
        v_new = jnp.where(spikes > 0, reset, v_int)
        el_new = jnp.zeros_like(el)
    return v_new, el_new, spikes, touched.astype(jnp.int32)


def _widen(words: jax.Array) -> jax.Array:
    """(K, bn) int8 words -> bf16, exact (|word| <= 256), through int32
    and f32 (Mosaic converts neither int8 nor int32 to bf16 directly)."""
    return words.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)


def _kernel(pk_ref, w0_ref, w1_ref, v_ref, el_ref,
            vo_ref, elo_ref, sp_ref, tc_ref, nnz_ref, ew_ref, *,
            layout: str, gather: bool, threshold: float, leak: float,
            reset: float, partial_update: bool, all_nonzero: bool,
            decays=None):
    j = pl.program_id(1)
    pk = pk_ref[...]                                   # (bm, kw) uint16
    s, nnz_rows = _unpack_words(pk)                    # (bm, K), (bm, 1)

    @pl.when(j == 0)
    def _spike_stats():                                # once per m-tile
        nnz_ref[...] = nnz_rows
        ew_ref[...] = jnp.sum((pk.astype(jnp.int32) == 0).astype(jnp.int32),
                              axis=1, keepdims=True)

    v = v_ref[...]
    el = el_ref[...]
    nnz_tile = jnp.sum(nnz_rows)

    @pl.when(nnz_tile == 0)
    def _skip():
        # ZSPE saw only empty words: no synaptic work, no touches.  The
        # partial-update bookkeeping still runs (elapsed accrues) — with
        # full update the plain leak step must still be applied.
        vo, elo, sp, _ = _lif_tile(
            v, el, jnp.zeros_like(v), jnp.zeros_like(el),
            threshold=threshold, leak=leak, reset=reset,
            partial_update=partial_update, decays=decays)
        vo_ref[...] = vo
        elo_ref[...] = elo
        sp_ref[...] = sp
        tc_ref[...] = jnp.zeros_like(el) if partial_update \
            else jnp.ones_like(el)

    @pl.when(nnz_tile > 0)
    def _work():
        if layout == "words":
            # integer words: one bf16 pass, exact (0/1 x |word| <= 256,
            # f32 sums under 2**24), then the column's step once — the
            # correctly rounded f32 of the exact current
            sb = s.astype(jnp.bfloat16)
            w = _widen(w0_ref[...])                        # (K, bn) bf16
            cur = jnp.dot(sb, w, preferred_element_type=jnp.float32
                          ) * w1_ref[...]
        else:
            w = (_dequant_columns(w0_ref[...].astype(jnp.int32),
                                  w1_ref[...], gather)
                 if layout == "codebook" else w0_ref[...])   # (K, bn) f32
            cur = jnp.dot(s, w, precision=CURRENT_PRECISION,
                          preferred_element_type=jnp.float32)
        # integer-exact touch counts: valid spikes through nonzero
        # synapses.  With a fully-nonzero weight slab (the static
        # `all_nonzero` flag, decided at lowering time) the nonzero mask
        # is all-ones and the count matmul collapses to the per-row
        # popcount — the identical integers, one MXU pass cheaper.
        if all_nonzero:
            tcnt = jnp.broadcast_to(nnz_rows.astype(jnp.float32), v.shape)
        elif layout == "words":
            tcnt = jnp.dot(sb, (w != 0).astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        else:
            nz = (w != 0.0).astype(jnp.float32)
            tcnt = jnp.dot(s, nz, precision=CURRENT_PRECISION,
                           preferred_element_type=jnp.float32)
        vo, elo, sp, tc = _lif_tile(
            v, el, cur, tcnt, threshold=threshold, leak=leak, reset=reset,
            partial_update=partial_update, decays=decays)
        vo_ref[...] = vo
        elo_ref[...] = elo
        sp_ref[...] = sp
        tc_ref[...] = tc


def _call(pk, w0, w1, v, elapsed, *, layout, gather, threshold, leak,
          reset, partial_update, all_nonzero, block, interpret, name,
          decays=None):
    m, kw = pk.shape
    k = kw * SPIKE_WORD_BITS
    n = v.shape[-1]
    bm, bn = (m, n) if block is None else block
    assert m % bm == 0 and n % bn == 0, ((m, n), block)
    assert w0.shape[0] == k, (w0.shape, k)

    kern = functools.partial(
        _kernel, layout=layout, gather=gather, threshold=threshold,
        leak=leak, reset=reset, partial_update=partial_update,
        all_nonzero=all_nonzero, decays=decays)
    state_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    row_spec = pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
    in_specs = [
        pl.BlockSpec((bm, kw), lambda i, j: (i, 0)),
        pl.BlockSpec((k, bn), lambda i, j: (0, j)),
    ]
    operands = [pk, w0]
    if w1 is not None:           # the level table or the step row
        in_specs.append(pl.BlockSpec((w1.shape[0], bn), lambda i, j: (0, j)))
        operands.append(w1)
    in_specs += [state_spec, state_spec]
    operands += [v, elapsed]
    n_in = len(operands)

    return pl.pallas_call(
        kern if w1 is not None else _drop_w1(kern),
        grid=(m // bm, n // bn),
        in_specs=in_specs,
        out_specs=[state_spec, state_spec, state_spec, state_spec,
                   row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), v.dtype),        # v'
            jax.ShapeDtypeStruct((m, n), elapsed.dtype),  # elapsed'
            jax.ShapeDtypeStruct((m, n), v.dtype),        # spikes
            jax.ShapeDtypeStruct((m, n), jnp.int32),      # touched mask
            jax.ShapeDtypeStruct((m, 1), jnp.int32),      # nnz per row
            jax.ShapeDtypeStruct((m, 1), jnp.int32),      # empty words/row
        ],
        # membrane state is read-modify-write: donate the input buffers
        input_output_aliases={n_in - 2: 0, n_in - 1: 1},
        compiler_params=(None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES)),
        interpret=interpret,
        name=name,
    )(*operands)


def _drop_w1(kern):
    """Adapt the 3-weight-operand kernel signature to the dense variant
    (no codebook operand)."""
    def wrapped(pk_ref, w_ref, v_ref, el_ref, *out_refs):
        return kern(pk_ref, w_ref, None, v_ref, el_ref, *out_refs)
    return wrapped


@functools.partial(jax.jit, static_argnames=(
    "threshold", "leak", "reset", "partial_update", "gather",
    "all_nonzero", "block", "interpret", "name", "decays"))
def fused_timestep_codebook(
    packed: jax.Array,        # (M, Kw) uint16 spike words
    idx: jax.Array,           # (Kw*16, N) int8 codebook indexes
    cbw: jax.Array,           # (n_levels, N) f32 per-column level values
    v: jax.Array,             # (M, N) f32 membrane potential
    elapsed: jax.Array,       # (M, N) int32 idle-step stamps
    *,
    threshold: float = 1.0,
    leak: float = 0.9,
    reset: float = 0.0,
    partial_update: bool = True,
    gather: bool = True,
    all_nonzero: bool = False,
    block: tuple[int, int] | None = None,
    interpret: bool = True,
    name: str | None = None,
    decays: tuple | None = None,
):
    """One fused layer-timestep, codebook-compressed weights.

    `all_nonzero` asserts (statically, decided at lowering time) that
    every real weight element is nonzero, collapsing the touch-count
    matmul to the per-row popcount — same integers, one MXU pass less.

    Returns (v', elapsed', spikes, touched, nnz_rows, empty_words).
    `block=None` runs a single (M, N) tile — the engine's bit-exact
    configuration; pass (bm, bn) divisors to tile for TPU VMEM.  `name`
    names the kernel in the compiled program and its profile; `decays`
    (`neuron.decay_table`) makes the leak exact.
    """
    return _call(packed, idx, cbw, v, elapsed, layout="codebook",
                 gather=gather, threshold=threshold, leak=leak, reset=reset,
                 partial_update=partial_update, all_nonzero=all_nonzero,
                 block=block, interpret=interpret, name=name, decays=decays)


@functools.partial(jax.jit, static_argnames=(
    "threshold", "leak", "reset", "partial_update", "all_nonzero", "block",
    "interpret", "name", "decays"))
def fused_timestep_words(
    packed: jax.Array,        # (M, Kw) uint16 spike words
    words: jax.Array,         # (Kw*16, N) int8 integer weight words
    step: jax.Array,          # (1, N) f32 per-column fixed-point step
    v: jax.Array,             # (M, N) f32 membrane potential
    elapsed: jax.Array,       # (M, N) int32 idle-step stamps
    *,
    threshold: float = 1.0,
    leak: float = 0.9,
    reset: float = 0.0,
    partial_update: bool = True,
    all_nonzero: bool = False,
    block: tuple[int, int] | None = None,
    interpret: bool = True,
    name: str | None = None,
    decays: tuple | None = None,
):
    """One fused layer-timestep, weights as integer words x a per-column
    step: the current is `(spikes @ words) * step`.

    The caller guarantees what makes that exact: every |word| <= 256 and
    every column's sum of |word| under 2**24, so the word sums are exact
    integers in any order and any (bm, bn) tiling, and the current is
    one correctly rounded f32 product.  Where the levels `word * step`
    make an exact f32 current in the codebook layout, the two layouts
    agree bit for bit.  `decays` (`neuron.decay_table`) makes the leak
    exact.  Returns what `fused_timestep_codebook` returns.
    """
    return _call(packed, words, step, v, elapsed, layout="words",
                 gather=False, threshold=threshold, leak=leak, reset=reset,
                 partial_update=partial_update, all_nonzero=all_nonzero,
                 block=block, interpret=interpret, name=name, decays=decays)


@functools.partial(jax.jit, static_argnames=(
    "threshold", "leak", "reset", "partial_update", "all_nonzero", "block",
    "interpret", "name", "decays"))
def fused_timestep_dense(
    packed: jax.Array,        # (M, Kw) uint16 spike words
    weights: jax.Array,       # (Kw*16, N) f32 dense weights
    v: jax.Array,
    elapsed: jax.Array,
    *,
    threshold: float = 1.0,
    leak: float = 0.9,
    reset: float = 0.0,
    partial_update: bool = True,
    all_nonzero: bool = False,
    block: tuple[int, int] | None = None,
    interpret: bool = True,
    name: str | None = None,
    decays: tuple | None = None,
):
    """Dense-weight variant (float simulators): same ZSPE/LIF fusion.

    `all_nonzero` refers to the REAL weight rows; the zero rows padding
    K to the word boundary never see spikes, so they cannot affect the
    collapsed touch counts."""
    return _call(packed, weights, None, v, elapsed, layout="dense",
                 gather=False, threshold=threshold, leak=leak, reset=reset,
                 partial_update=partial_update, all_nonzero=all_nonzero,
                 block=block, interpret=interpret, name=name, decays=decays)
