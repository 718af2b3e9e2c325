"""Batched XLA-compiled chip engines: scan-over-time, vmap-over-batch.

`ChipSimulator.run` (core/soc.py) is an interpretive Python loop — one
sample, one timestep, one layer at a time, with every counter crossing
the host boundary.  That is the right shape for a *reference* model and
the wrong shape for throughput: the chip's dataflow is static per
(mapping, T), so the whole inference can be one XLA program.

Three array engines share one lowering (`lower_tables`) and one
pricing/report stage (`_EngineBase.run_batch` -> `energy.price_batched`,
the same function the interpretive reference uses, so the paths cannot
drift).  NoC accounting is source-exact: the programs emit integer
per-core fired counts (`out @ slice_onehot`) and the host replays them
against the per-flow `noc.FlowTable` vectors in float64, adding the
bottleneck router's M/M/1 `contention_cycles` to the wall clock —
identical arithmetic to the reference loop (DESIGN.md §7).  Each engine
keeps one layer-step body: a recurrent layer, packet drop, trace
counters and a learnable layer are branches in it, chosen at trace time
from the simulator's configuration.  The engines:

* `CompiledEngine` (PR 2) — the mapping, cycle and NoC models lowered to
  arrays; per layer-step a dense `spikes @ w` against dequantized f32
  weight constants plus a separate `lif_step`.  `jax.lax.scan` over T
  under `jax.vmap` over the batch; `layer_steps` computes the counters
  inside the step.

* `FusedEngine` (PR 4) — the chip's actual pipeline shape: each
  layer-step is ONE Pallas kernel (kernels/fused_timestep.py) that scans
  **bitpacked 16-spike words** (uint16, 32x fewer HBM bytes than f32
  lanes), popcounts and zero-skips empty spike tiles (`pl.when`),
  dequantizes codebook indexes against `RegisterTable` words in-register
  (the dense f32 matrix never exists in HBM — indexes are int8, 4x
  smaller) or, where the tables are exact integer words x one step per
  core (`_lower_word_layer`), accumulates the int8 words in one bf16 MXU
  pass and applies the step once (`word_layers` counts those layers),
  and applies the partial-update LIF step in the same VMEM pass.
  Spikes stay packed between layers; per-row empty-word counts
  are emitted as ZSPE skip telemetry (`StepStats.spike_words_skipped`).
  Its inference step emits only the kernels' outputs, one int32 row
  block a step, and `layer_counters` computes every counter from the
  stacked blocks after the scan.  A plastic run's step keeps its
  counters in the scan, through the same `layer_counters`: its
  learnable layers' weights are scan state.
  In interpret mode the kernel runs one (B, K, N) tile whose float
  program is expression-identical to the compiled engine's, so the two
  array engines agree bit-exactly; vs the interpretive reference the
  usual compiled-vs-reference contract applies (below).

* `ShardedEngine` (PR 8) — the cores axis across devices in one
  `shard_map` program, spikes exchanged as packed words between
  layer-steps; chains only, with a plastic body of its own.

A recurrent layer (`ChipSimulator(..., recurrent=(li,))`) reads its
forward spikes and then its own spikes of the last step, which ride the
scan carry beside the LIF states; a chain without plasticity carries the
states alone.

A conv layer (core/conv.py) runs as an XLA conv at HIGHEST precision in
`CompiledEngine` and as one `snn_conv_l<i>` kernel
(kernels/conv_timestep.py) from its filter's int8 words and one step in
`FusedEngine` (`_build_conv_run`); both emit its per-core SOPs
(`sops_core_<i>`), which the host prices in place of nnz x n_post, and
count its cores' input bands and cycles as core/conv.py defines them.
A network without a conv layer traces the same programs as before.

The compiled and fused engines shard the batch across available devices
with `shard_map` (batch axis, weights replicated) when the batch divides
the device count.  Each `run_batch` enqueues the engine's XLA program
and, behind it, `_host_slab`, which packs the output counts and every
counter the host prices into one f32 array, read back in one transfer.
The fused engine builds its zero membrane state and packs the input
spikes inside its program, so nothing but the trains crosses from the
host.

The bit-identical-spikes contract is validated on the CPU backend,
where XLA's reduction order for the (B, n) @ (n, m) batched matmul
matches the reference's per-sample product.  Every current matmul over
f32 weights runs at `zspe.CURRENT_PRECISION` (HIGHEST), so a TPU keeps
the f32 codebook weights instead of rounding them to bf16 (the fused
word layout's bf16 pass is exact by construction).  Its MXU still sums
in its own order and its `leak ** n` differs in the last bit, so TPU
currents and decays can differ from the CPU's by a few ulps: spikes
agree unless a membrane value lands within that distance of the
threshold.
`chip_smoke.py` holds the TPU to exact agreement on its workload.

Differential testing lives in tests/test_engine_equiv.py (both engines
vs the reference, fused vs compiled bit-exact, skip counters vs a numpy
popcount oracle); benchmarks/engine_bench.py runs the three-way
compiled/fused/reference sweep.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy as E
from repro.core import noc as NOC
from repro.core import zspe as Z
from repro.core.neuron import (LIFState, decay_table, init_batch_state,
                                init_state, lif_step, touch_mask)

if TYPE_CHECKING:  # pragma: no cover - import cycle guard (soc -> engine)
    from repro.core.soc import ChipReport, ChipSimulator


@dataclasses.dataclass(frozen=True)
class LayerTables:
    """Array lowering of one layer's core assignments."""

    n_pre: int
    n_post: int
    slice_sizes: np.ndarray    # (A,) neurons held by each core slice
    core_index: np.ndarray     # (A,) dense index into the active-core list
    slice_onehot: np.ndarray   # (n_post, A) f32 neuron -> core-slice indicator
    # a conv layer's per-core input bands (core/conv.py); None otherwise
    conv: "ConvTables | None" = None


@dataclasses.dataclass(frozen=True)
class ConvTables:
    """A conv layer's per-core lowering: each slice's input band as an
    indicator over input neurons and over input words, the words it
    spans, and its synapses."""

    band_onehot: np.ndarray    # (n_pre, A) f32
    word_onehot: np.ndarray    # (input words, A) f32
    band_words: np.ndarray     # (A,) f32 words each band spans
    synapses: np.ndarray       # (A,) f32 synapses into each slice


def slice_sums(x: jax.Array, onehot) -> jax.Array:
    """(..., n) integers under 2**24 -> (..., A) f32 sums over each core
    slice of `onehot` (n, A), exact: an f32 product at HIGHEST precision
    keeps every integer (a TPU's default one-pass bf16 product would
    round counts past 256)."""
    return jnp.matmul(x.astype(jnp.float32), onehot,
                      precision=jax.lax.Precision.HIGHEST)


@dataclasses.dataclass(frozen=True)
class EngineTables:
    """Everything the traced step function closes over, in array form."""

    layers: tuple[LayerTables, ...]
    # flows[li]: layer li+1 -> li+2, and -> li+1 itself when recurrent
    flows: tuple[NOC.FlowTable | None, ...]
    n_active_cores: int
    nominal_sops_per_step: int
    # back_hops[li]: a recurrent layer's per-flow hops beyond the tree to
    # the next layer alone (its back-edge share), else None
    back_hops: tuple[np.ndarray | None, ...]


def lower_tables(sim: "ChipSimulator") -> EngineTables:
    """Lower a simulator's mapping + precompiled routes to pure arrays.

    `slice_onehot` segments a layer's neuron axis into its core slices:
    `out @ slice_onehot` yields integer-exact per-core fired/touched
    counts inside the scan.  Row `i` of layer `li`'s count vector aligns
    with row `i` of `flows[li]` (both follow `cores_of_layer` assignment
    order), which is what makes the per-flow NoC replay source-exact.
    """
    active = sim.mapping.active_core_ids()
    dense = {cid: i for i, cid in enumerate(active)}
    layers = []
    for li, w in enumerate(sim.weights):
        asn = sim.mapping.cores_of_layer(li + 1)
        conv = li in sim.conv
        n_pre, n_post = ((sim.layer_sizes[li], sim.layer_sizes[li + 1])
                         if conv else (int(w.shape[0]), int(w.shape[1])))
        onehot = np.zeros((n_post, len(asn)), np.float32)
        for i, a in enumerate(asn):
            onehot[a.neuron_lo:a.neuron_hi, i] = 1.0
        ct = None
        if conv:
            tabs = sim.conv_slices(li)
            band, words = tabs.onehots(n_pre)
            ct = ConvTables(
                band_onehot=band, word_onehot=words,
                band_words=(tabs.words[:, 1] - tabs.words[:, 0]
                            ).astype(np.float32),
                synapses=tabs.synapses.astype(np.float32))
        layers.append(LayerTables(
            n_pre=n_pre, n_post=n_post,
            slice_sizes=np.array([a.n_neurons for a in asn], np.float32),
            core_index=np.array([dense[a.core_id] for a in asn], np.int32),
            slice_onehot=onehot, conv=ct))
    def table(routes):
        return NOC.compile_flow_table(routes, sim.router,
                                      n_nodes=sim.adj.shape[0],
                                      interconnect=sim.interconnect)

    L = len(sim.weights)
    flows = tuple(table(sim._layer_routes[li + 1])
                  if li + 1 in sim._layer_routes else None
                  for li in range(L))
    nominal = sum(sim.conv_slices(li).total_synapses if lt.conv is not None
                  else lt.n_pre * lt.n_post for li, lt in enumerate(layers))
    return EngineTables(layers=tuple(layers), flows=flows,
                        n_active_cores=len(active),
                        nominal_sops_per_step=nominal,
                        back_hops=tuple(sim._back_hops.get(li + 1)
                                        for li in range(L)))


@dataclasses.dataclass(frozen=True)
class FusedLayerWeights:
    """One layer's weight operand for the fused kernel.

    Codebook form when every core slice of the layer has a programmed
    `RegisterTable` whose words reproduce the executed weights exactly,
    in one of two layouts: `words` (int8, each synapse's table word) +
    `step` (per-column fixed-point step) where every level is exactly
    word x step and the word sums stay exact (`_lower_word_layer`), else
    `idx` (int8 indexes) + `cbw` (per-column level values = words x
    scale).  Dense f32 fallback otherwise (float-only simulators).  Rows
    are padded to the 16-spike word boundary with zeros — bit-neutral,
    since the padded spike bits are zero too.  A recurrent layer's input
    is two spike-word streams, the forward input's and its own last-step
    spikes' (`fed_words` words), so its rows are padded per stream: the
    fed-back rows start on a word, and the kernel takes the two word
    runs concatenated, unchanged.
    """

    n_pre: int
    n_post: int
    kw: int                        # spike words per input row
    idx: jax.Array | None          # (kw*16, n_post) int8
    cbw: jax.Array | None          # (n_levels, n_post) f32
    dense: jax.Array | None        # (kw*16, n_post) f32
    all_nonzero: bool = False      # every real weight element != 0: the
                                   # touch-count matmul collapses to the
                                   # per-row spike popcount (same ints)
    fed_words: int = 0             # words of the fed-back stream, at the
                                   # end of each input row; 0: not recurrent
    words: jax.Array | None = None  # (kw*16, n_post) int8 table words
    step: jax.Array | None = None   # (1, n_post) f32 fixed-point step

    @property
    def word_layout(self) -> bool:
        return self.words is not None

    @property
    def codebook_mode(self) -> bool:
        return self.idx is not None or self.word_layout

    def hbm_bytes_per_step(self, batch: int) -> int:
        """Weight + input-spike HBM traffic for one timestep at `batch`."""
        spikes = batch * self.kw * 2                       # uint16 words
        if self.word_layout:
            return self.words.size * 1 + self.step.size * 4 + spikes
        if self.codebook_mode:
            return (self.idx.size * 1 + self.cbw.size * 4 + spikes)
        return self.dense.size * 4 + spikes


def _layer_tables(sim: "ChipSimulator", li: int) -> list | None:
    """[(assignment, RegisterTable)] of layer `li`'s core slices, or None
    when a core has two tables, a slice has none, or the slices do not
    cover the layer."""
    # one physical core holds one assignment, so core_id keys the table
    # regardless of list ordering (deploy's per-core PTQ orders tables by
    # (layer, slice), the simulator by mapping.assignments)
    by_core: dict[int, object] = {}
    for rt in sim.register_tables:
        if rt.core_id in by_core:
            return None                                # ambiguous: bail
        by_core[rt.core_id] = rt
    slices = [(a, by_core.get(a.core_id))
              for a in sim.mapping.assignments if a.layer == li + 1]
    if not slices or any(rt is None for _, rt in slices):
        return None
    if sum(a.n_neurons for a, _ in slices) != sim.layer_sizes[li + 1]:
        return None
    return slices


def _lower_codebook_layer(sim: "ChipSimulator", li: int, fill: float = 0.0,
                          ) -> tuple[np.ndarray, np.ndarray] | None:
    """Rebuild (idx, cbw) for layer `li` from the per-core RegisterTables.

    Returns None when any slice lacks a programmed table or the table
    words do not reproduce the executed weights bit-exactly — the caller
    then falls back to the dense-weight kernel.

    `fill` pads unprogrammed codebook rows (slices whose table holds
    fewer than the layer-max levels).  The fused kernel wants 0.0 (a
    padded row dequantizes to nothing); the plasticity lowering wants
    +inf so `quant.project_to_codebook` can never select a row the
    core's table does not actually hold.
    """
    w = np.asarray(sim.weights[li], np.float32)
    n_pre, n_post = w.shape
    slices = _layer_tables(sim, li)
    if slices is None:
        return None
    n_levels = max(rt.weight_levels for _, rt in slices)
    idx = np.zeros((n_pre, n_post), np.int8)
    cbw = np.full((n_levels, n_post), fill, np.float32)
    for a, rt in slices:
        if not rt.codebook_words:
            return None
        cb = rt.codebook()                                 # (L,) f32
        cols = w[:, a.neuron_lo:a.neuron_hi]
        ii = np.argmin(np.abs(cols[:, :, None] - cb[None, None, :]), axis=-1)
        if not np.array_equal(cb[ii], cols):
            return None                                    # not table-exact
        idx[:, a.neuron_lo:a.neuron_hi] = ii.astype(np.int8)
        cbw[:len(cb), a.neuron_lo:a.neuron_hi] = cb[:, None]
    return idx, cbw


def _lower_word_layer(sim: "ChipSimulator", idx: np.ndarray, li: int,
                      ) -> tuple[np.ndarray, np.ndarray] | None:
    """(words (n_pre, n_post) int8, step (1, n_post) f32) for layer `li`,
    whose table indexes are `idx`: each synapse's table word and each
    column's fixed-point step, so that the weight is word x step.

    None unless, for every column: (i) each level of its table equals
    word x step exactly (f64, step finite and > 0), (ii) each word fits
    int8, so it is exact in bf16 too, and (iii) the sum of |word| down
    the column is under 2**24, so the word sums are exact f32 integers
    in any order.  Then `(spikes @ words) * step` is the correctly
    rounded f32 current.  A `quant.quantize()` table's step has a full
    f32 mantissa and fails (i).
    """
    slices = _layer_tables(sim, li)
    words = np.zeros(idx.shape, np.int8)
    step = np.zeros((1, idx.shape[1]), np.float32)
    for a, rt in slices:
        tw = np.asarray(rt.codebook_words, np.int64)
        st = np.float32(rt.codebook_scale)
        if not (np.isfinite(st) and st > 0) or tw.min() < -128 \
                or tw.max() > 127 or not np.array_equal(
                    tw * np.float64(st), rt.codebook().astype(np.float64)):
            return None
        cols = slice(a.neuron_lo, a.neuron_hi)
        words[:, cols] = tw[idx[:, cols]]
        step[0, cols] = st
    if np.abs(words.astype(np.int32)).sum(axis=0).max() >= 1 << 24:
        return None
    return words, step


@dataclasses.dataclass(frozen=True)
class ConvLayerWeights:
    """A conv layer's operand for `kernels/conv_timestep.py`: the
    filter's int8 table words and nonzero taps (k, k, C_in, C_out), as
    every core of the layer holds them, and the table's step."""

    geometry: "object"             # conv.ConvGeometry
    words: jax.Array               # (k, k, C_in, C_out) int8
    nonzero: jax.Array             # (k, k, C_in, C_out) int8 0/1
    step: jax.Array                # (1, 1) f32

    codebook_mode = True
    word_layout = False            # the dense word kernel's layout: no

    def hbm_bytes_per_step(self, batch: int) -> int:
        """Filter words, nonzero taps, the step and the input spikes'
        words, for one timestep at `batch`."""
        return (self.words.size * 2 + 4
                + batch * -(-self.geometry.n_in // Z.SPIKE_WORD_BITS) * 2)

    def operands(self) -> tuple[jax.Array, jax.Array]:
        """(words, nonzero) as the kernel takes them: (C_out, K), the taps
        in `conv.tap_patches`' (c_in, dy, dx) order, with the channels
        padded to a multiple of 8 and the taps to a multiple of 128, with
        zeros."""
        c = self.words.shape[-1]
        k = math.prod(self.words.shape[:-1])
        pad = ((0, -(-c // 8) * 8 - c), (0, -(-k // 128) * 128 - k))
        return tuple(jnp.pad(jnp.transpose(a, (3, 2, 0, 1)).reshape(c, k),
                             pad) for a in (self.words, self.nonzero))


def lower_conv_words(sim: "ChipSimulator", li: int) -> ConvLayerWeights:
    """Conv weight layer `li`'s filter as table words and one step.

    Every core of the layer must hold the same table (the filter is
    shared), each of its levels must be word x step exactly with int8
    words, and each output channel's sum of |word| over the filter must
    stay under 2**24, so the kernel's word sums are exact f32 integers
    in any order; raises ValueError otherwise (a conv layer has no
    dense fallback)."""
    slices = _layer_tables(sim, li)
    tables = {(rt.codebook_words, rt.codebook_scale)
              for _, rt in slices or []}
    if len(tables) != 1 or not next(iter(tables))[0]:
        raise ValueError(f"conv weight layer {li}: its cores must hold one "
                         f"programmed table, the shared filter's")
    rt = slices[0][1]
    tw = np.asarray(rt.codebook_words, np.int64)
    st = np.float32(rt.codebook_scale)
    cb = rt.codebook()
    if not (np.isfinite(st) and st > 0) or tw.min() < -128 or tw.max() > 127 \
            or not np.array_equal(tw * np.float64(st), cb.astype(np.float64)):
        raise ValueError(f"conv weight layer {li}: its table is not int8 "
                         f"words x one step")
    w = np.asarray(sim.weights[li], np.float32)
    ii = np.argmin(np.abs(w[..., None] - cb), axis=-1)
    if not np.array_equal(cb[ii], w):
        raise ValueError(f"conv weight layer {li}: the filter is not its "
                         f"table's levels")
    words = tw[ii]
    if np.abs(words).sum(axis=(0, 1, 2)).max() >= 1 << 24:
        raise ValueError(f"conv weight layer {li}: a channel's word sums "
                         f"reach 2**24, past exact f32")
    return ConvLayerWeights(
        geometry=sim.conv[li], words=jnp.asarray(words, jnp.int8),
        nonzero=jnp.asarray(w != 0, jnp.int8),
        step=jnp.full((1, 1), st, jnp.float32))


def lower_plasticity_tables(sim: "ChipSimulator"):
    """Per-layer plasticity lowering: None for frozen layers, else the
    (idx0 int8 (n_pre, n_post), cbw f32 (L, n_post)) pair whose indexes
    every engine scan-carries and learns over.

    Initial indexes come from the post-fault RegisterTables (faults
    corrupt tables in `ChipSimulator.__init__`, before any lowering), so
    `FaultConfig` codebook corruption lands in the *initial* state only —
    the learning dynamics themselves are never perturbed.  Unprogrammed
    codebook rows are +inf so projection cannot select them; both the
    argmin here and `project_to_codebook` break ties to the lowest index,
    making every initial index a projection fixed point (a zero update
    never counts as a write).
    """
    cfg = sim.plasticity
    if not cfg.enabled:
        return tuple(None for _ in sim.weights)
    out = []
    for li in range(len(sim.weights)):
        if not cfg.learns(li):
            out.append(None)
            continue
        t = _lower_codebook_layer(sim, li, fill=np.inf)
        if t is None:
            raise ValueError(
                f"plasticity on layer {li} requires table-exact codebook "
                f"register tables (quantized weights, or float weights "
                f"with a quant_cfg) — the chip has no register words to "
                f"write otherwise")
        out.append(t)
    if not any(t is not None for t in out):
        raise ValueError(
            f"plasticity enabled but layers={cfg.layers} selects none of "
            f"the network's {len(sim.weights)} layers")
    return tuple(out)


def _pick_engine_block(m: int, k: int, n: int, interpret: bool, *,
                       layout: str = "codebook", n_levels: int = 16,
                       all_nonzero: bool = False) -> tuple[int, int] | None:
    """Kernel tile for one engine layer-step ((m, k) spikes, (k, n) weights
    in `layout`: "codebook", "words" or "dense").

    Interpret mode runs one exact (m, n) tile — that is what makes the
    fused path bit-exact against the compiled engine.  A compiled (TPU)
    tile must be one Mosaic accepts and must fit VMEM:

    * `bm` divides m and is m itself or a multiple of 16 (the uint16
      spike-word block packs two rows per sublane), at most 128 rows
      unless no such divisor exists;
    * `bn` divides n and is n itself or a multiple of 128 lanes;
    * `fused_timestep.vmem_bytes` — every operand and temporary of the
      kernel — stays within `VMEM_BUDGET_BYTES`.

    The largest `bm` comes first, so the weight slab is fetched once per
    row tile, then the widest `bn` that fits.
    """
    if interpret:
        return None
    from repro.kernels import fused_timestep as F

    kw = Z.spike_word_count(k)
    bms = [d for d in range(min(m, 128), 0, -1)
           if m % d == 0 and (d == m or d % 16 == 0)] or [m]
    bns = [d for d in range(n, 0, -1)
           if n % d == 0 and (d == n or d % 128 == 0)]
    for bm in bms:
        for bn in bns:
            if F.vmem_bytes(bm, bn, kw, layout=layout, n_levels=n_levels,
                            all_nonzero=all_nonzero) <= F.VMEM_BUDGET_BYTES:
                return bm, bn
    raise ValueError(
        f"no fused-kernel tile of a ({m}, {k}) x ({k}, {n}) layer-step "
        f"fits the {F.VMEM_BUDGET_BYTES >> 20} MiB VMEM budget — use "
        f"engine='compiled'")


def _pad_streams(rows: np.ndarray, widths: list[int]) -> np.ndarray:
    """Zero-pad each run of `widths` rows to the 16-spike word boundary."""
    parts, lo = [], 0
    for n in widths:
        pad = Z.spike_word_count(n) * Z.SPIKE_WORD_BITS - n
        parts.append(np.pad(rows[lo:lo + n], ((0, pad), (0, 0))))
        lo += n
    return np.concatenate(parts)


def lower_fused_weights(sim: "ChipSimulator"
                        ) -> tuple[FusedLayerWeights | ConvLayerWeights, ...]:
    """Lower every layer to its fused-kernel weight operand (a conv
    layer to its filter's words, `lower_conv_words`)."""
    out = []
    for li, w in enumerate(sim.weights):
        if li in sim.conv:
            out.append(lower_conv_words(sim, li))
            continue
        n_pre, n_post = int(w.shape[0]), int(w.shape[1])
        # input streams: the forward spikes, then a recurrent layer's own
        streams = ([n_pre - n_post, n_post] if li in sim.recurrent
                   else [n_pre])
        kw = sum(Z.spike_word_count(n) for n in streams)
        fed = Z.spike_word_count(n_post) if li in sim.recurrent else 0
        nz = bool(np.all(np.asarray(w) != 0))
        cbk = _lower_codebook_layer(sim, li)
        wl = None if cbk is None else _lower_word_layer(sim, cbk[0], li)
        if wl is not None:
            out.append(FusedLayerWeights(
                n_pre=n_pre, n_post=n_post, kw=kw, idx=None, cbw=None,
                dense=None, all_nonzero=nz, fed_words=fed,
                words=jnp.asarray(_pad_streams(wl[0], streams)),
                step=jnp.asarray(wl[1])))
        elif cbk is not None:
            idx, cbw = cbk
            out.append(FusedLayerWeights(
                n_pre=n_pre, n_post=n_post, kw=kw,
                idx=jnp.asarray(_pad_streams(idx, streams)),
                cbw=jnp.asarray(cbw), dense=None, all_nonzero=nz,
                fed_words=fed))
        else:
            dense = _pad_streams(np.asarray(w, np.float32), streams)
            out.append(FusedLayerWeights(
                n_pre=n_pre, n_post=n_post, kw=kw,
                idx=None, cbw=None, dense=jnp.asarray(dense),
                all_nonzero=nz, fed_words=fed))
    return tuple(out)


def _shard_map(fn, mesh, in_specs, out_specs):
    """`jax.shard_map` as every engine mesh uses it.  The varying-axes
    check is off: bodies close over replicated tables and return
    per-shard counters the check cannot type."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# shared execution / pricing stage
# ---------------------------------------------------------------------------

# counters the host prices from every body that emits them (`writes`:
# plastic; `skip_words`: fused, or traced with `skip_words`)
HOST_COUNTERS = ("writes", "nnz", "touched", "wall", "skip_words")


@jax.jit
def _host_slab(out: jax.Array, counters: list[jax.Array]) -> jax.Array:
    """(B, T, n_out) output spikes and the priced counters (B leading)
    -> one (B, X) f32 slab: the output counts, then each counter
    flattened per sample.  Every counter is f32 and holds integers or
    f32 cycle sums, so the host's f64 view is exact."""
    B = out.shape[0]
    parts = [jnp.sum(out, axis=1)] + [c.reshape(B, -1) for c in counters]
    assert all(p.dtype == jnp.float32 for p in parts), \
        [p.dtype for p in parts]
    return jnp.concatenate(parts, axis=1)


class _EngineBase:
    """Lowering + execution + pricing shared by both array engines.

    Subclasses provide `_make_executable(sharded)` returning a callable
    from an f32 (B, T, n_in) spike-train array to the per-step counter
    dict `ys` (leaves lead with the batch axis).  `run_batch` prices the
    counters through `energy.price_batched` — the identical code path
    for both engines and the interpretive reference.
    """

    def __init__(self, sim: "ChipSimulator", shard: bool = True):
        from repro.telemetry.trace import TraceConfig

        self.sim = sim
        self.tables = lower_tables(sim)
        self.shard = shard
        self.last_run_sharded = False
        self._exec: dict[bool, object] = {}
        # capture config is fixed at construction (the simulator builds
        # each engine once); trace-off lowers the exact PR-5 scan outputs
        self.trace = getattr(sim, "trace", None) or TraceConfig()
        self.last_trace = None       # ChipTrace of the latest traced run
        # on-chip learning (core/plasticity.py): disabled keeps every
        # lowering below byte-identical to the inference-only programs
        from repro.core.plasticity import NULL_PLASTICITY
        self.plast = getattr(sim, "plasticity", None) or NULL_PLASTICITY
        self.plast_tables = (sim.plasticity_tables() if self.plast.enabled
                             else tuple(None for _ in sim.weights))
        self.last_learned = None     # per-layer learned indexes (B leading)
        self.last_elig = None        # per-layer eligibility (reward mode)
        self.calls = 0               # run_batch calls so far (span `call`)
        self.word_layers = 0         # layers on the fused word layout
        self.conv_layers = 0         # layers on the fused conv kernel

    # -- trace construction (subclass hooks) --------------------------------

    def _make_executable(self, sharded: bool):
        raise NotImplementedError

    def _shard_wrap(self, fn, n_args: int = 1):
        """Wrap a batched-run function in a shard_map over the batch axis
        (weights/tables are closure constants -> replicated)."""
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("batch",))
        spec = P("batch")
        return _shard_map(fn, mesh, (spec,) * n_args, spec)

    # -- plasticity state plumbing ------------------------------------------

    def _adapt_learned(self, li: int, idx: jax.Array) -> jax.Array:
        """Subclass hook: engine-layout view of a (B, n_pre, n_post)
        global learned-index array (fused pads rows to the spike-word
        boundary; the base layout IS the global layout)."""
        return idx

    def _initial_learned(self, batch: int, learned) -> list:
        """Materialize the per-layer initial-index operand: table idx0 by
        default, overridden per layer by `learned` entries ((n_pre,
        n_post) broadcast over the batch, or per-sample (B, ...))."""
        if learned is not None and len(learned) != len(self.plast_tables):
            raise ValueError(
                f"learned must carry one entry per layer "
                f"({len(self.plast_tables)}), got {len(learned)}")
        out = []
        for li, pt in enumerate(self.plast_tables):
            if pt is None:
                if learned is not None and learned[li] is not None:
                    raise ValueError(
                        f"learned[{li}] given but layer {li} is frozen")
                out.append(None)
                continue
            src = pt[0] if learned is None or learned[li] is None \
                else learned[li]
            base = jnp.asarray(src, jnp.int8)
            if base.ndim == 2:
                base = jnp.broadcast_to(base, (batch,) + base.shape)
            if base.ndim != 3 or int(base.shape[0]) != batch:
                raise ValueError(
                    f"learned[{li}]: expected (n_pre, n_post) or "
                    f"({batch}, n_pre, n_post), got {base.shape}")
            # materialized copy: the fused engine donates this operand
            out.append(self._adapt_learned(li, jnp.array(base)))
        return out

    def apply_reward(self, reward):
        """Reward-mode trial commit: convert the eligibility the last run
        accumulated into projected index writes, priced per sample."""
        from repro.core import plasticity as PLC

        if self.plast.mode != "reward" or self.last_elig is None:
            raise ValueError(
                "apply_reward needs a completed reward-mode run to commit")
        self.last_learned, info = PLC.commit_reward(
            self.plast, self.plast_tables, self.last_learned,
            self.last_elig, reward, self.sim.write_model,
            self.sim.cycle_model)
        self.last_elig = None
        return info

    # -- execution ----------------------------------------------------------

    def _upload(self, spike_trains) -> jax.Array:
        """(B, T, n_in) spike trains as an f32 device array, under the
        `snn.upload` span; its `bytes` stat counts what crosses from the
        host (0 for an array already on the device)."""
        moved = (0 if isinstance(spike_trains, jax.Array)
                 else 4 * int(np.size(spike_trains)))
        with jax.profiler.TraceAnnotation("snn.upload", bytes=moved):
            trains = jnp.asarray(spike_trains, jnp.float32)
        if trains.ndim != 3:
            raise ValueError(f"expected (batch, T, n_in), got {trains.shape}")
        return trains

    def _dispatch(self, key, trains: jax.Array, learned) -> dict:
        """Enqueue the executable `key` on `trains`, under the
        `snn.dispatch` span (pack and scan; the device may still run)."""
        with jax.profiler.TraceAnnotation("snn.dispatch"):
            if not self.plast.enabled:
                if learned is not None:
                    raise ValueError("learned indexes passed but plasticity "
                                     "is off")
                return self._exec[key](trains)
            return self._exec[key](
                trains, self._initial_learned(int(trains.shape[0]), learned))

    def run_raw(self, spike_trains: jax.Array, learned=None) -> dict:
        """Run the XLA program; returns the per-step counter arrays."""
        trains = self._upload(spike_trains)
        ndev = len(jax.devices())
        sharded = bool(self.shard and ndev > 1
                       and int(trains.shape[0]) % ndev == 0)
        if sharded not in self._exec:
            self._exec[sharded] = self._make_executable(sharded)
        self.last_run_sharded = sharded
        return self._dispatch(sharded, trains, learned)

    def run_batch(self, spike_trains: jax.Array, learned=None
                  ) -> tuple[np.ndarray, list["ChipReport"]]:
        """(B, T, n_in) spike trains -> ((B, n_out) f32 counts as a host
        array, per-sample ChipReports).

        The output counts and every counter the host prices come back in
        one transfer: a small jitted program (`_host_slab`) packs them
        into one (B, X) f32 slab behind the engine's program, and the
        host splits it by `_readback_keys`.  NoC pricing happens here, on
        the host, in float64: the scan emits integer-exact per-core fired
        counts (`fired_core_{li}`) and the per-flow replay
        (`noc.replay_flows_exact`) + the M/M/1 contention term
        (`noc.contention_cycles`) run the same f64 arithmetic the
        interpretive reference does, so the engines cannot drift from it.

        Each phase runs under a `jax.profiler.TraceAnnotation` span, on
        the profiler's clock beside the device ops: `snn.run_batch`
        (stats `call`, `batch`, `steps`, `word_layers` where a fused
        layer runs on the word layout, and `conv_layers` where fused
        layers run on the conv kernel) holds `snn.upload` (`bytes`),
        `snn.dispatch`, `snn.device_wait` (launches the slab's program
        and waits for it), `snn.readback` (`transfers`: 1, `bytes`),
        `snn.noc_replay` (`flows`: the flows replayed; `back_flows`:
        those of them that also reach their own layer) and `snn.price`.
        With no profiler running a span costs about a microsecond.
        """
        self.calls += 1
        batch, steps = (tuple(np.shape(spike_trains)) + (0, 0))[:2]
        stats = dict(call=self.calls, batch=int(batch), steps=int(steps))
        if self.word_layers:
            stats["word_layers"] = self.word_layers
        if self.conv_layers:
            stats["conv_layers"] = self.conv_layers
        with jax.profiler.TraceAnnotation("snn.run_batch", **stats):
            return self._run_batch(spike_trains, learned)

    def _readback_keys(self, ys: dict) -> list[str]:
        """The counters the host prices, in the slab's order after the
        output counts: those of `HOST_COUNTERS` the body emits, the
        per-core fired counts of each layer with flows (of every layer
        under trace), and under trace the per-core touched counts."""
        keys = [k for k in HOST_COUNTERS if k in ys]
        keys += [f"fired_core_{li}" for li, ft in enumerate(self.tables.flows)
                 if ft is not None or self.trace.enabled]
        if self.trace.enabled:
            keys += [f"touched_core_{li}"
                     for li in range(len(self.tables.layers))]
        # a conv layer's per-core SOPs, and under trace its per-core
        # input spikes
        conv = [li for li, lt in enumerate(self.tables.layers)
                if lt.conv is not None]
        keys += [f"sops_core_{li}" for li in conv]
        if self.trace.enabled:
            keys += [f"nnz_core_{li}" for li in conv]
        return keys

    def _run_batch(self, spike_trains, learned):
        ys = self.run_raw(spike_trains, learned=learned)
        # injected transient dispatch faults fire HERE: the scan ran, the
        # readback is lost (mid-flight), so a retry can succeed
        self.sim._consume_transient_fault()

        if self.plast.enabled:
            # learned state is stashed per engine (B leading, global
            # neuron layout) for warm-starting the next run / the reward
            # commit; writes price below alongside the other counters
            self.last_learned = [
                ys.pop(f"learned_idx_{li}") if pt is not None else None
                for li, pt in enumerate(self.plast_tables)]
            if self.plast.mode == "reward":
                self.last_elig = [
                    ys.pop(f"elig_{li}") if pt is not None else None
                    for li, pt in enumerate(self.plast_tables)]

        keys = self._readback_keys(ys)
        with jax.profiler.TraceAnnotation("snn.device_wait"):
            slab = _host_slab(ys["out"], [ys[k] for k in keys])
            jax.block_until_ready(slab)
        with jax.profiler.TraceAnnotation(
                "snn.readback", transfers=1, bytes=int(slab.nbytes)):
            flat = np.asarray(slab, np.float64)
        B = flat.shape[0]
        n_out = int(ys["out"].shape[-1])
        host, lo = {}, n_out
        for k in keys:                       # views of the one host array
            shape = tuple(ys[k].shape[1:])
            n = math.prod(shape)
            host[k] = flat[:, lo:lo + n].reshape((B,) + shape)
            lo += n
        return flat[:, :n_out].astype(np.float32), self._reports(host)

    def _reports(self, host: dict) -> list["ChipReport"]:
        """Per-sample ChipReports from the priced counters on the host
        (`_readback_keys`, each (B, T, ...) float64); under trace also
        sets `last_trace`."""
        from repro.core.soc import ChipReport, StepStats

        sim = self.sim
        tbl = self.tables
        L = len(tbl.layers)
        B, T = host["nnz"].shape[:2]
        writes = host.get("writes")                      # (B, T, L)
        writes_total = (writes.sum(axis=(1, 2)) if writes is not None
                        else np.zeros(B))
        n_posts = np.array([lt.n_post for lt in tbl.layers], np.float64)
        nnz = host["nnz"]                                # (B, T, L)
        spikes_in = nnz.sum(axis=(1, 2))
        conv = [li for li, lt in enumerate(tbl.layers) if lt.conv is not None]
        if conv:
            # a conv layer's spikes perform their own synapses, per core
            dense = np.where([lt.conv is None for lt in tbl.layers],
                             n_posts, 0.0)
            performed = (nnz * dense).sum(axis=(1, 2)) + sum(
                host[f"sops_core_{li}"].sum(axis=(1, 2)) for li in conv)
        else:
            performed = (nnz * n_posts).sum(axis=(1, 2))
        neurons_touched = host["touched"].sum(axis=(1, 2))
        core_wall = host["wall"]                         # (B, T) core-only
        skipped_words = (host["skip_words"].sum(axis=(1, 2))
                         if "skip_words" in host else np.zeros(B))
        nominal = float(tbl.nominal_sops_per_step) * T

        # exact per-flow NoC replay: counts are integers, pricing is f64;
        # a recurrent layer's trees also reach its own cores, in the step
        # its spikes fire; they are its next step's recurrent input
        with jax.profiler.TraceAnnotation(
                "snn.noc_replay",
                flows=sum(ft.n_flows for ft in tbl.flows if ft is not None),
                back_flows=sum(len(bh) for bh in tbl.back_hops
                               if bh is not None)):
            noc_hops = np.zeros(B)
            noc_pj = np.zeros(B)
            routed = np.zeros(B)
            back_hops = np.zeros(B)
            recurrent_sops = np.zeros(B)
            load = np.zeros((B, T, sim.adj.shape[0]))
            for li, ft in enumerate(tbl.flows):
                if ft is None:
                    continue
                fired_core = host[f"fired_core_{li}"]
                h, e, ld = NOC.replay_flows_exact(ft, fired_core)
                noc_hops += h.sum(axis=1)
                noc_pj += e.sum(axis=1)
                load += ld
                routed += fired_core.sum(axis=(1, 2))
                if tbl.back_hops[li] is not None:
                    back_hops += (fired_core @ tbl.back_hops[li]).sum(axis=1)
                    recurrent_sops += (fired_core[:, :-1].sum(axis=(1, 2))
                                       * n_posts[li])
            contention = NOC.contention_cycles(
                load.max(axis=2), core_wall, sim.router)  # (B, T)
            wall = (core_wall + contention).sum(axis=1)
            noc_contention = contention.sum(axis=1)

        if self.trace.enabled:
            # every derived series (cycles, router load, contention) is
            # recomputed host-side by build_trace from these integer
            # counters — one implementation for all three engines
            from repro.telemetry.trace import build_trace

            core_nnz = core_sops = None
            if conv:
                # per slice; a dense slice's are its layer's nnz, and
                # nnz x its neurons
                core_nnz = np.concatenate([
                    host[f"nnz_core_{li}"] if lt.conv is not None else
                    np.repeat(nnz[..., li:li + 1], len(lt.slice_sizes), -1)
                    for li, lt in enumerate(tbl.layers)], axis=-1)
                core_sops = np.concatenate([
                    host[f"sops_core_{li}"] if lt.conv is not None else
                    nnz[..., li:li + 1] * lt.slice_sizes.astype(np.float64)
                    for li, lt in enumerate(tbl.layers)], axis=-1)
            self.last_trace = build_trace(
                sim,
                np.concatenate([host[f"fired_core_{li}"]
                                for li in range(L)], axis=-1),
                np.concatenate([host[f"touched_core_{li}"]
                                for li in range(L)], axis=-1),
                nnz,
                host["skip_words"] if self.trace.skip_words
                and "skip_words" in host else None,
                weight_writes=writes, core_nnz=core_nnz,
                core_sops=core_sops)

        with jax.profiler.TraceAnnotation("snn.price"):
            priced = E.price_batched(
                sim.core_model, sim.riscv,
                nominal_sops=np.full(B, nominal), performed_sops=performed,
                noc_energy_pj=noc_pj, wall_cycles=wall, steps=T,
                freq_hz=sim.freq_hz, zero_skip=sim.zero_skip,
                partial_update=sim.partial_update,
                weight_writes=writes_total, write_model=sim.write_model)

            reports = []
            for b in range(B):
                acc = StepStats(
                    nominal_sops=nominal,
                    performed_sops=float(performed[b]),
                    spikes_in=float(spikes_in[b]),
                    spikes_routed=float(routed[b]),
                    neurons_touched=float(neurons_touched[b]),
                    noc_hops=float(noc_hops[b]),
                    noc_energy_pj=float(noc_pj[b]),
                    noc_contention_cycles=float(noc_contention[b]),
                    spike_words_skipped=float(skipped_words[b]),
                    weight_writes=float(writes_total[b]),
                    recurrent_sops=float(recurrent_sops[b]),
                    back_noc_hops=float(back_hops[b]),
                )
                reports.append(ChipReport(
                    steps=T, stats=acc,
                    energy_pj=float(priced["total_pj"][b]),
                    core_energy_pj=float(priced["core_pj"][b]),
                    noc_energy_pj=float(noc_pj[b]),
                    riscv_energy_pj=float(priced["riscv_pj"][b]),
                    wall_cycles=float(wall[b]), freq_hz=sim.freq_hz,
                    write_energy_pj=float(priced["write_pj"][b])))
        return reports

    def run(self, spike_train: jax.Array,
            learned=None) -> tuple[np.ndarray, "ChipReport"]:
        """Single-sample convenience wrapper (batch of 1)."""
        counts, reports = self.run_batch(jnp.asarray(spike_train)[None],
                                         learned=learned)
        return counts[0], reports[0]


class CompiledEngine(_EngineBase):
    """One XLA program per (mapping, T, batch) instead of O(T x layers x
    cores) Python dispatches.

    Spike semantics are bit-identical to the interpretive loop (same
    `lif_step`, same matmuls, just traced); the accounting counters are
    exact integer counts emitted per step and summed in float64 on the
    host, so SOP/flit/energy totals agree with the reference within
    float32 rounding of the cycle expressions (<< 1e-6 relative).
    """

    def _build_run(self):
        from repro.core import plasticity as PLC

        sim = self.sim
        tbl = self.tables
        weights = tuple(sim.weights)
        nonzero_w = tuple(sim.nonzero_weights)
        conv = sim.conv
        lif = sim.lif
        cyc = sim.cycle_model
        n_active = tbl.n_active_cores
        layer_consts = [
            (lt, jnp.asarray(lt.slice_sizes), jnp.asarray(lt.core_index),
             jnp.asarray(lt.slice_onehot))
            for lt in tbl.layers
        ]
        has_flow = [ft is not None for ft in tbl.flows]
        traced = self.trace.enabled
        trace_skips = traced and self.trace.skip_words
        # per-hop packet drop (faults.DropPlan); None lowers the exact
        # fault-free scan — same xs, same ops, bit-identical jaxpr
        drop = getattr(sim, "drop_plan", None)
        # on-chip learning: the learnable layers' codebook indexes and
        # traces are scan state; none when plasticity is off
        plast = self.plast
        cbws = {li: jnp.asarray(pt[1])
                for li, pt in enumerate(self.plast_tables) if pt is not None}
        reward = plast.mode == "reward"

        def conv_step(li, x, state, wall, decays):
            """Conv weight layer `li` of one sample's timestep (an XLA
            conv at HIGHEST precision): -> (its spikes, its counters,
            its per-core counts)."""
            from repro.core.conv import conv_spikes

            geom = conv[li]
            lt, slices, core_idx, onehot = layer_consts[li]
            ct = lt.conv
            current = conv_spikes(x, weights[li], geom)
            tcnt = conv_spikes(x, nonzero_w[li], geom)
            st, out, touched = lif_step(state, current, lif,
                                        touched=tcnt > 0, decays=decays)
            sops_core = slice_sums(tcnt, onehot)
            core_touched = touched.astype(jnp.float32) @ onehot
            core_cyc = cyc.conv_timestep_cycles_array(
                jnp.asarray(ct.band_words), sops_core, core_touched,
                jnp.asarray(ct.synapses), slices, sim.zero_skip,
                sim.partial_update)
            wall = wall + jax.ops.segment_sum(core_cyc, core_idx,
                                              num_segments=n_active)
            counts = {"state": st, "wall": wall,
                      "nnz": jnp.sum(x != 0).astype(jnp.float32),
                      "touched": jnp.sum(touched).astype(jnp.float32),
                      "fired": jnp.sum(out).astype(jnp.float32)}
            cores = {f"sops_core_{li}": sops_core}
            if has_flow[li] or traced:
                cores[f"fired_core_{li}"] = out @ onehot
            if traced:
                cores[f"touched_core_{li}"] = core_touched
                cores[f"nnz_core_{li}"] = (
                    (x != 0).astype(jnp.float32) @ ct.band_onehot)
            if trace_skips:
                empty = (Z.pack_spike_words(x) == 0).astype(jnp.float32)
                counts["skip"] = jnp.sum(empty @ ct.word_onehot)
            return out, counts, cores

        def layer_steps(carry, xs, decays=None):
            """One timestep of every layer.  The carry holds the LIF
            states, each recurrent layer's last-step spikes (`fed`) and
            each learnable layer's (indexes, pre trace, post trace,
            eligibility) (`learn`); the last two are empty for a chain
            without plasticity, whose carry is then the states alone."""
            states, fed, learn = carry
            spikes, t = xs if drop is not None else (xs, None)
            wall = jnp.zeros((n_active,), jnp.float32)
            nnzs, toucheds, fireds, skips, wr = [], [], [], [], []
            fired_cores = {}
            new_states = []
            new_fed = {}
            new_learn = {}
            for li, w in enumerate(weights):
                if li in conv:
                    with jax.named_scope(f"snn_compiled_l{li + 1}"):
                        spikes, counts, cores = conv_step(
                            li, spikes, states[li], wall, decays)
                    new_states.append(counts.pop("state"))
                    wall = counts.pop("wall")
                    fired_cores.update(cores)
                    nnzs.append(counts["nnz"])
                    toucheds.append(counts["touched"])
                    fireds.append(counts["fired"])
                    wr.append(jnp.float32(0.0))
                    if trace_skips:
                        skips.append(counts["skip"])
                    continue
                with jax.named_scope(f"snn_compiled_l{li + 1}"):
                    lt, slices, core_idx, onehot = layer_consts[li]
                    # a recurrent layer reads its forward input, then
                    # its own last-step spikes
                    x = (jnp.concatenate([spikes, fed[li]]) if li in fed
                         else spikes)
                    nzw = nonzero_w[li]
                    if li in learn:
                        # live weights from the carried indexes — the
                        # chip's SPEs dequantizing the current registers
                        pidx, xpre, xpost, elig = learn[li]
                        w = PLC.dequant_indices(pidx, cbws[li])
                        nzw = (w != 0).astype(jnp.float32)
                    nnz = jnp.sum(x != 0).astype(jnp.float32)
                    if trace_skips:
                        # ZSPE skip telemetry on the layer's input spikes —
                        # packs exactly like the fused engine's native
                        # empty-word counter, so the two agree bit-for-bit
                        skip = Z.empty_spike_words(Z.pack_spike_words(spikes))
                        if li in fed:
                            skip = skip + Z.empty_spike_words(
                                Z.pack_spike_words(fed[li]))
                        skips.append(skip.astype(jnp.float32))
                    current = jnp.matmul(
                        x, w, precision=Z.CURRENT_PRECISION)
                    st, out, touched = lif_step(
                        states[li], current, lif,
                        touched=touch_mask(x, nzw), decays=decays)
                    new_states.append(st)
                    if li in fed:
                        new_fed[li] = out
                    tsum = jnp.sum(touched).astype(jnp.float32)
                    # integer-exact per-core-slice touched counts: the cycle
                    # model ceils them, and exact ints cannot straddle a ceil
                    # boundary between f32 (here) and f64 (reference)
                    core_touched = touched.astype(jnp.float32) @ onehot
                    core_writes = None
                    writes_l = jnp.float32(0.0)
                    if li in learn:
                        if reward:
                            xp, xq, e = PLC.elig_step(
                                plast, x, out, xpre, xpost, elig)
                            new_learn[li] = (pidx, xp, xq, e)
                        else:
                            ni, xp, xq, changed = PLC.stdp_step(
                                plast, x, out, xpre, xpost, pidx, cbws[li])
                            new_learn[li] = (ni, xp, xq, elig)
                            # integer-exact per-post write counts ->
                            # per-core plasticity-stage occupancy + energy
                            col_ch = jnp.sum(changed, axis=0
                                             ).astype(jnp.float32)
                            core_writes = col_ch @ onehot
                            writes_l = jnp.sum(col_ch)
                    core_cyc = cyc.timestep_cycles_array(
                        lt.n_pre, slices, nnz, core_touched,
                        sim.zero_skip, sim.partial_update, writes=core_writes)
                    wall = wall + jax.ops.segment_sum(
                        core_cyc, core_idx, num_segments=n_active)
                    fired = jnp.sum(out).astype(jnp.float32)
                    if has_flow[li] or traced:
                        # per-source-core fired counts, row-aligned with the
                        # layer's FlowTable; priced exactly on the host
                        fired_cores[f"fired_core_{li}"] = out @ onehot
                    if traced:
                        fired_cores[f"touched_core_{li}"] = core_touched
                    nnzs.append(nnz)
                    toucheds.append(tsum)
                    fireds.append(fired)
                    wr.append(writes_l)
                    # fired counters above are pre-drop (the source fired and
                    # committed the energy); the next layer integrates what
                    # survived the hops
                    if drop is not None and drop.keep_p[li] is not None:
                        spikes = out * drop.mask(li, t)
                    else:
                        spikes = out
            ys = {
                "nnz": jnp.stack(nnzs),
                "touched": jnp.stack(toucheds),
                "fired": jnp.stack(fireds),
                "wall": jnp.max(wall),
                "out": spikes,
                **fired_cores,
            }
            if trace_skips:
                ys["skip_words"] = jnp.stack(skips)
            if plast.enabled:
                ys["writes"] = jnp.stack(wr)
            return (tuple(new_states), new_fed, new_learn), ys

        def one_sample(train, idx0=None):
            states = tuple(init_state(lt.n_post) for lt in tbl.layers)
            fed = {li: jnp.zeros((int(weights[li].shape[1]),), jnp.float32)
                   for li in sim.recurrent}
            learn = {li: (idx0[li],
                          jnp.zeros((int(weights[li].shape[0]),), jnp.float32),
                          jnp.zeros((int(weights[li].shape[1]),), jnp.float32),
                          jnp.zeros(weights[li].shape, jnp.float32)
                          if reward else None)
                     for li in cbws}
            xs = (train if drop is None
                  else (train, jnp.arange(train.shape[0])))
            # a network with conv layers takes every leak exact
            # (`decay_table`)
            decays = (decay_table(float(lif.leak), train.shape[0])
                      if conv and lif.partial_update else None)
            (_, _, learn), ys = jax.lax.scan(
                functools.partial(layer_steps, decays=decays),
                (states, fed, learn), xs)
            for li, (idx, _, _, elig) in learn.items():
                ys[f"learned_idx_{li}"] = idx
                if reward:
                    ys[f"elig_{li}"] = elig
            return ys

        def run(*args):          # (B, T, n_in) f32 [, per-layer B-led idx]
            return jax.vmap(one_sample)(*args)

        return run

    def _make_executable(self, sharded: bool):
        fn = self._build_run()
        if sharded:
            fn = self._shard_wrap(fn, n_args=2 if self.plast.enabled else 1)
        return jax.jit(fn)


@dataclasses.dataclass(frozen=True)
class ShardedLayer:
    """One layer's cores-axis lowering: per-shard weight-column blocks.

    `w` / `nzw` stack each shard's owned weight columns (gathered by
    neuron ownership, zero-padded to the common width `width`), `onehot`
    the matching rows of the layer's slice-onehot, and `pos` maps every
    global neuron id to its lane in the all-gathered bit vector
    (shard * 16*words + local index).  Every core's neuron slice lives
    wholly inside one shard, so per-core counters are exact partial sums.
    """

    width: int                    # padded neurons per shard
    words: int                    # uint16 spike words per shard
    w: jax.Array                  # (S, n_pre, width) f32
    nzw: jax.Array                # (S, n_pre, width) f32
    onehot: jax.Array             # (S, width, A) f32
    pos: jax.Array                # (n_post,) int32 gather into S*words*16 bits


class ShardedEngine(_EngineBase):
    """Cores-axis `shard_map` engine: a multi-chip board as ONE XLA
    program across host devices.

    Domains map contiguously onto `n_shards` mesh devices; each device
    holds only its shard's weight columns (`spikes @ w_local` — column
    blocks of a matmul are bit-exact on the CPU backend, so per-device
    shards reproduce the unsharded engine's spikes bit-for-bit) and its
    slice of the LIF state.  After each layer-step the shard packs its
    output spikes into uint16 words (`zspe.pack_spike_words`) and
    exchanges them with every other shard via `all_gather` over the
    "cores" mesh axis — the domain-boundary spike traffic, 16 spikes per
    word — then gathers the bits back into global neuron order for the
    next layer's fan-in.  Counters (`nnz`, touched, per-core fired) are
    exact integer partial sums combined with `psum`, so
    `_EngineBase.run_batch` prices NoC/contention/energy through the
    identical host-side f64 pipeline as the other engines (<= 1e-6 vs
    the reference, like `CompiledEngine`).

    Composes with batch sharding: with `nb * n_shards <= ndev` the mesh
    is 2-D ("batch", "cores") and the batch splits across `nb` device
    rows.  `n_shards` defaults to `min(n_devices, n_domains)`; a
    single-domain mapping (or one device) degenerates to S=1, which
    keeps the differential suite runnable anywhere.
    """

    def __init__(self, sim: "ChipSimulator", shard: bool = True,
                 n_shards: int | None = None):
        from repro.core.soc import refuse_layers

        refuse_layers(
            sim, ["recurrent", "conv"],
            "ShardedEngine runs chains only (a recurrent layer's fed-back "
            "spikes and a conv layer's input bands would need their own "
            "cross-shard exchange; use engine='compiled' or 'fused')")
        super().__init__(sim, shard=shard)
        max_node = max(a.core_id for a in sim.mapping.assignments)
        self.n_domains = (max_node // NOC.DOMAIN_STRIDE + 1
                          if max_node >= NOC.N_NODES else 1)
        ndev = len(jax.devices())
        if n_shards is None:
            n_shards = max(1, min(ndev, self.n_domains))
        if not 1 <= n_shards <= ndev:
            raise ValueError(f"n_shards={n_shards} needs 1..{ndev} devices")
        if n_shards > self.n_domains:
            raise ValueError(
                f"n_shards={n_shards} exceeds the mapping's "
                f"{self.n_domains} domain(s) — shards split on domain "
                f"boundaries")
        self.n_shards = n_shards
        self._owned: list[list[np.ndarray]] = []
        self.sharded_layers = self._lower_shards()
        self._plast_shards = self._lower_plast_shards()

    def _shard_of_core(self, core_id: int) -> int:
        dom = (core_id // NOC.DOMAIN_STRIDE
               if core_id >= NOC.N_NODES else 0)
        return dom * self.n_shards // self.n_domains

    def _lower_shards(self) -> tuple[ShardedLayer, ...]:
        sim = self.sim
        S = self.n_shards
        out = []
        for li, w in enumerate(sim.weights):
            w = np.asarray(w, np.float32)
            nzw = np.asarray(sim.nonzero_weights[li], np.float32)
            lt = self.tables.layers[li]
            n_pre, n_post = lt.n_pre, lt.n_post
            owner = np.zeros(n_post, np.int32)
            for a in sim.mapping.cores_of_layer(li + 1):
                owner[a.neuron_lo:a.neuron_hi] = self._shard_of_core(
                    a.core_id)
            owned = [np.flatnonzero(owner == s) for s in range(S)]
            width = max(int(o.size) for o in owned)
            words = Z.spike_word_count(max(width, 1))
            ws = np.zeros((S, n_pre, width), np.float32)
            nzs = np.zeros((S, n_pre, width), np.float32)
            oh = np.zeros((S, width, lt.slice_onehot.shape[1]), np.float32)
            pos = np.zeros(n_post, np.int32)
            for s, o in enumerate(owned):
                ws[s, :, :o.size] = w[:, o]
                nzs[s, :, :o.size] = nzw[:, o]
                oh[s, :o.size] = lt.slice_onehot[o]
                pos[o] = s * words * Z.SPIKE_WORD_BITS + np.arange(o.size)
            self._owned.append(owned)
            out.append(ShardedLayer(
                width=width, words=words, w=jnp.asarray(ws),
                nzw=jnp.asarray(nzs), onehot=jnp.asarray(oh),
                pos=jnp.asarray(pos)))
        return tuple(out)

    def _lower_plast_shards(self):
        """Cores-axis view of the plasticity tables: per learnable layer a
        (cbw_s (S, L, width) f32, colpos (n_post,) int32) pair.  Padded
        width columns get the level set [0, inf, ...] — their index-0
        entries are projection fixed points with zero traffic, so pads
        can never write.  `colpos` reassembles all-gathered local columns
        back into global neuron order (shard * width + lane)."""
        out: list[tuple | None] = []
        S = self.n_shards
        for li, pt in enumerate(self.plast_tables):
            if pt is None:
                out.append(None)
                continue
            cbw = np.asarray(pt[1], np.float32)        # (L, n_post) global
            width = self.sharded_layers[li].width
            cbw_s = np.full((S, cbw.shape[0], width), np.inf, np.float32)
            cbw_s[:, 0, :] = 0.0
            colpos = np.zeros(cbw.shape[1], np.int32)
            for s, o in enumerate(self._owned[li]):
                cbw_s[s, :, :o.size] = cbw[:, o]
                colpos[o] = s * width + np.arange(o.size)
            out.append((jnp.asarray(cbw_s), jnp.asarray(colpos)))
        return out

    def _shard_learned(self, idx0: list) -> list:
        """(B, n_pre, n_post) global learned indexes -> per-layer
        (S, B, n_pre, width) shard stacks (pad columns index 0)."""
        out = []
        for li, g in enumerate(idx0):
            if g is None:
                out.append(None)
                continue
            g = np.asarray(g, np.int8)
            width = self.sharded_layers[li].width
            arr = np.zeros((self.n_shards,) + g.shape[:-1] + (width,),
                           np.int8)
            for s, o in enumerate(self._owned[li]):
                arr[s, ..., :o.size] = g[..., o]
            out.append(jnp.asarray(arr))
        return out

    def _build_body(self):
        """The per-device program: full-fan-in layer steps on local
        weight-column shards, bitpacked spike exchange between layers."""
        sim = self.sim
        tbl = self.tables
        S = self.n_shards
        lif = sim.lif
        cyc = sim.cycle_model
        n_active = tbl.n_active_cores
        layer_consts = [
            (lt, jnp.asarray(lt.slice_sizes), jnp.asarray(lt.core_index))
            for lt in tbl.layers
        ]
        has_flow = [ft is not None for ft in tbl.flows]
        traced = self.trace.enabled
        trace_skips = traced and self.trace.skip_words
        shl = self.sharded_layers
        drop = getattr(sim, "drop_plan", None)

        def body(trains, *stacks):
            # per-device views: each P("cores") operand arrives (1, ...)
            local = [s[0] for s in stacks]
            w_l = local[0::3]
            nzw_l = local[1::3]
            oh_l = local[2::3]

            def step(states, xs):
                spikes, t = xs if drop is not None else (xs, None)
                # spikes: full (n_pre,) f32
                wall = jnp.zeros((n_active,), jnp.float32)
                nnzs, toucheds, fireds, skips = [], [], [], []
                fired_cores = {}
                new_states = []
                for li, sl in enumerate(shl):
                    lt, slices, core_idx = layer_consts[li]
                    nnz = jnp.sum(spikes != 0).astype(jnp.float32)
                    if trace_skips:
                        skips.append(Z.empty_spike_words(
                            Z.pack_spike_words(spikes))
                            .astype(jnp.float32))
                    current = jnp.matmul(             # (width,) local
                        spikes, w_l[li], precision=Z.CURRENT_PRECISION)
                    st, out_l, touched_l = lif_step(
                        states[li], current, lif,
                        touched=touch_mask(spikes, nzw_l[li]))
                    new_states.append(st)
                    # exact integer partial sums; every core slice lives
                    # in one shard, so psum reassembles the global counts
                    tsum = jax.lax.psum(
                        jnp.sum(touched_l).astype(jnp.float32), "cores")
                    core_touched = jax.lax.psum(
                        touched_l.astype(jnp.float32) @ oh_l[li], "cores")
                    core_cyc = cyc.timestep_cycles_array(
                        lt.n_pre, slices, nnz, core_touched,
                        sim.zero_skip, sim.partial_update)
                    wall = wall + jax.ops.segment_sum(
                        core_cyc, core_idx, num_segments=n_active)
                    if has_flow[li] or traced:
                        fired_cores[f"fired_core_{li}"] = jax.lax.psum(
                            out_l @ oh_l[li], "cores")
                    if traced:
                        fired_cores[f"touched_core_{li}"] = core_touched
                    # domain-boundary exchange: 16 spikes per uint16 word
                    packed = Z.pack_spike_words(out_l)   # (words,) uint16
                    gathered = jax.lax.all_gather(packed, "cores",
                                                  tiled=True)
                    bits = Z.unpack_spike_words(
                        gathered, S * sl.words * Z.SPIKE_WORD_BITS)
                    spikes = bits[sl.pos]               # global order
                    nnzs.append(nnz)
                    toucheds.append(tsum)
                    # fired is counted pre-drop, on the gathered globals
                    fireds.append(jnp.sum(spikes).astype(jnp.float32))
                    if drop is not None and drop.keep_p[li] is not None:
                        spikes = spikes * drop.mask(li, t)
                ys = {
                    "nnz": jnp.stack(nnzs),
                    "touched": jnp.stack(toucheds),
                    "fired": jnp.stack(fireds),
                    "wall": jnp.max(wall),
                    "out": spikes,
                    **fired_cores,
                }
                if trace_skips:
                    ys["skip_words"] = jnp.stack(skips)
                return tuple(new_states), ys

            def one_sample(train):
                states = tuple(init_state(sl.width) for sl in shl)
                xs = (train if drop is None
                      else (train, jnp.arange(train.shape[0])))
                _, ys = jax.lax.scan(step, states, xs)
                return ys

            return jax.vmap(one_sample)(trains)

        if not self.plast.enabled:
            return body

        # ---- plasticity path: local index/trace state, psum'd writes -----
        # Each shard carries its owned weight-index columns (plus pre
        # traces over the full fan-in, which is replicated arithmetic on
        # the gathered global spikes), so the learning rule runs on
        # exactly the column blocks the inference matmul uses.  Finals
        # are all-gathered back to global neuron order at the end.
        from repro.core import plasticity as PLC

        plast = self.plast
        plast_shards = self._plast_shards
        reward = plast.mode == "reward"
        n_pres = [lt.n_pre for lt in tbl.layers]

        def body_plast(trains, idx0, *stacks):
            local = [s[0] for s in stacks]
            nbase = 3 * len(shl)
            w_l = local[0:nbase:3]
            nzw_l = local[1:nbase:3]
            oh_l = local[2:nbase:3]
            extra = local[nbase:]
            cbw_l: dict[int, jax.Array] = {}
            k = 0
            for li, ps in enumerate(plast_shards):
                if ps is not None:
                    cbw_l[li] = extra[k]
                    k += 1
            idx_l = [None if x is None else x[0] for x in idx0]

            def step_plast(carry, xs):
                states, pidx, xpre, xpost, elig = carry
                spikes, t = xs if drop is not None else (xs, None)
                wall = jnp.zeros((n_active,), jnp.float32)
                nnzs, toucheds, fireds, skips, wr = [], [], [], [], []
                fired_cores = {}
                new_states = []
                nidx, nxpre, nxpost, nelig = (list(pidx), list(xpre),
                                              list(xpost), list(elig))
                for li, sl in enumerate(shl):
                    lt, slices, core_idx = layer_consts[li]
                    learns = li in cbw_l
                    if learns:
                        w = PLC.dequant_indices(pidx[li], cbw_l[li])
                        nzw = (w != 0).astype(jnp.float32)
                    else:
                        w = w_l[li]
                        nzw = nzw_l[li]
                    nnz = jnp.sum(spikes != 0).astype(jnp.float32)
                    if trace_skips:
                        skips.append(Z.empty_spike_words(
                            Z.pack_spike_words(spikes))
                            .astype(jnp.float32))
                    current = jnp.matmul(           # (width,) local
                        spikes, w, precision=Z.CURRENT_PRECISION)
                    st, out_l, touched_l = lif_step(
                        states[li], current, lif,
                        touched=touch_mask(spikes, nzw))
                    new_states.append(st)
                    tsum = jax.lax.psum(
                        jnp.sum(touched_l).astype(jnp.float32), "cores")
                    core_touched = jax.lax.psum(
                        touched_l.astype(jnp.float32) @ oh_l[li], "cores")
                    core_writes = None
                    writes_l = jnp.float32(0.0)
                    if learns:
                        if reward:
                            xp, xq, e = PLC.elig_step(
                                plast, spikes, out_l, xpre[li],
                                xpost[li], elig[li])
                            nxpre[li], nxpost[li], nelig[li] = xp, xq, e
                        else:
                            ni, xp, xq, changed = PLC.stdp_step(
                                plast, spikes, out_l, xpre[li],
                                xpost[li], pidx[li], cbw_l[li])
                            nidx[li], nxpre[li], nxpost[li] = ni, xp, xq
                            col_ch = jnp.sum(changed, axis=0
                                             ).astype(jnp.float32)
                            core_writes = jax.lax.psum(
                                col_ch @ oh_l[li], "cores")
                            writes_l = jax.lax.psum(
                                jnp.sum(col_ch), "cores")
                    core_cyc = cyc.timestep_cycles_array(
                        lt.n_pre, slices, nnz, core_touched,
                        sim.zero_skip, sim.partial_update,
                        writes=core_writes)
                    wall = wall + jax.ops.segment_sum(
                        core_cyc, core_idx, num_segments=n_active)
                    if has_flow[li] or traced:
                        fired_cores[f"fired_core_{li}"] = jax.lax.psum(
                            out_l @ oh_l[li], "cores")
                    if traced:
                        fired_cores[f"touched_core_{li}"] = core_touched
                    packed = Z.pack_spike_words(out_l)
                    gathered = jax.lax.all_gather(packed, "cores",
                                                  tiled=True)
                    bits = Z.unpack_spike_words(
                        gathered, S * sl.words * Z.SPIKE_WORD_BITS)
                    spikes = bits[sl.pos]
                    nnzs.append(nnz)
                    toucheds.append(tsum)
                    fireds.append(jnp.sum(spikes).astype(jnp.float32))
                    wr.append(writes_l)
                    if drop is not None and drop.keep_p[li] is not None:
                        spikes = spikes * drop.mask(li, t)
                ys = {
                    "nnz": jnp.stack(nnzs),
                    "touched": jnp.stack(toucheds),
                    "fired": jnp.stack(fireds),
                    "writes": jnp.stack(wr),
                    "wall": jnp.max(wall),
                    "out": spikes,
                    **fired_cores,
                }
                if trace_skips:
                    ys["skip_words"] = jnp.stack(skips)
                return (tuple(new_states), nidx, nxpre, nxpost, nelig), ys

            def one_sample(train, i0):
                states = tuple(init_state(sl.width) for sl in shl)
                xpre0 = [None if i is None else
                         jnp.zeros((n_pres[li],), jnp.float32)
                         for li, i in enumerate(i0)]
                xpost0 = [None if i is None else
                          jnp.zeros((shl[li].width,), jnp.float32)
                          for li, i in enumerate(i0)]
                elig0 = [jnp.zeros((n_pres[li], shl[li].width),
                                   jnp.float32)
                         if (i is not None and reward) else None
                         for li, i in enumerate(i0)]
                xs = (train if drop is None
                      else (train, jnp.arange(train.shape[0])))
                carry = (states, list(i0), xpre0, xpost0, elig0)
                final, ys = jax.lax.scan(step_plast, carry, xs)
                _, fidx, _, _, felig = final
                for li, i in enumerate(i0):
                    if i is not None:
                        ys[f"learned_loc_{li}"] = fidx[li]
                        if reward:
                            ys[f"elig_loc_{li}"] = felig[li]
                return ys

            ys = jax.vmap(one_sample)(trains, idx_l)

            def to_global(loc, colpos):
                # (B, n_pre, width) local -> (B, n_pre, n_post) global,
                # replicated across the cores axis
                g = jax.lax.all_gather(loc, "cores", tiled=False)
                flat = jnp.transpose(g, (1, 2, 0, 3))
                flat = flat.reshape(flat.shape[0], flat.shape[1], -1)
                return flat[..., colpos]

            for li, ps in enumerate(plast_shards):
                if ps is None:
                    continue
                ys[f"learned_idx_{li}"] = to_global(
                    ys.pop(f"learned_loc_{li}"), ps[1])
                if reward:
                    ys[f"elig_{li}"] = to_global(
                        ys.pop(f"elig_loc_{li}"), ps[1])
            return ys

        return body_plast

    def _make_executable(self, nb: int):
        from jax.sharding import Mesh, PartitionSpec as P

        S = self.n_shards
        devices = np.array(jax.devices()[:nb * S]).reshape(nb, S)
        mesh = Mesh(devices, ("batch", "cores"))
        stacks = []
        for sl in self.sharded_layers:
            stacks.extend((sl.w, sl.nzw, sl.onehot))
        body = self._build_body()
        if not self.plast.enabled:
            fn = _shard_map(
                body, mesh, (P("batch"),) + (P("cores"),) * len(stacks),
                P("batch"))
            jfn = jax.jit(fn)
            return lambda trains: jfn(trains, *stacks)
        plast_stacks = [ps[0] for ps in self._plast_shards
                        if ps is not None]
        fn = _shard_map(
            body, mesh, (P("batch"), P("cores", "batch"))
            + (P("cores"),) * (len(stacks) + len(plast_stacks)), P("batch"))
        jfn = jax.jit(fn)
        return lambda trains, idx0: jfn(
            trains, self._shard_learned(idx0), *stacks, *plast_stacks)

    def run_raw(self, spike_trains: jax.Array, learned=None) -> dict:
        trains = self._upload(spike_trains)
        nb_max = len(jax.devices()) // self.n_shards
        nb = (nb_max if self.shard and nb_max > 1
              and int(trains.shape[0]) % nb_max == 0 else 1)
        if nb not in self._exec:
            self._exec[nb] = self._make_executable(nb)
        self.last_run_sharded = self.n_shards > 1 or nb > 1
        return self._dispatch(nb, trains, learned)


class FusedEngine(_EngineBase):
    """The fused-kernel hot path: one Pallas kernel per layer-step.

    Spikes travel bitpacked (uint16 16-spike words) through the whole
    scan — the input train is packed once, each layer's output spikes are
    re-packed for the next layer — and weights stay codebook-compressed
    (int8 indexes + per-column RegisterTable level values) whenever the
    simulator's register tables reproduce the executed weights exactly.
    The zero membrane state is built inside the program, so a call is
    one launch; the final state is returned (`last_states`).

    In interpret mode (CPU) each kernel runs one (B, K, N) tile whose
    float program matches the compiled engine expression-for-expression:
    with word-aligned layer widths the two array engines produce
    bit-identical spikes, states and counters (tests assert equality, not
    closeness).  When a layer width is not a multiple of 16, the zero
    bits padding the last spike word can regroup a small matmul's
    reduction by an ulp — integer counters stay exact, and spikes agree
    under the same empirical contract as compiled-vs-reference.
    """

    def __init__(self, sim: "ChipSimulator", shard: bool = True):
        if sim.lif.reset_mode != "hard":
            raise ValueError(
                "FusedEngine supports hard reset only (the chip's updater); "
                f"got reset_mode={sim.lif.reset_mode!r} — use "
                "engine='compiled'")
        super().__init__(sim, shard=shard)
        self.fused_weights = lower_fused_weights(sim)
        self.word_layers = sum(lw.word_layout for lw in self.fused_weights)
        self.conv_layers = sum(isinstance(lw, ConvLayerWeights)
                               for lw in self.fused_weights)
        self.last_states = None      # final LIF states of the last run

    @property
    def codebook_layers(self) -> int:
        return sum(lw.codebook_mode for lw in self.fused_weights)

    def hbm_bytes_per_step(self, batch: int) -> int:
        """Weight + spike HBM bytes per timestep (the fused operands)."""
        return sum(lw.hbm_bytes_per_step(batch) for lw in self.fused_weights)

    def _build_run(self):
        from repro.kernels.fused_timestep import (fused_timestep_codebook,
                                                  fused_timestep_dense,
                                                  fused_timestep_words)
        from repro.kernels.ops import interpret_default

        sim = self.sim
        tbl = self.tables
        lif = sim.lif
        cyc = sim.cycle_model
        n_active = tbl.n_active_cores
        interp = interpret_default()
        fused_w = self.fused_weights
        layer_consts = [
            (lt, jnp.asarray(lt.slice_sizes)[None, :],
             jnp.asarray(lt.core_index), jnp.asarray(lt.slice_onehot))
            for lt in tbl.layers
        ]
        has_flow = [ft is not None for ft in tbl.flows]
        traced = self.trace.enabled
        drop = getattr(sim, "drop_plan", None)
        lif_kw = dict(threshold=float(lif.threshold), leak=float(lif.leak),
                      reset=float(lif.reset),
                      partial_update=bool(lif.partial_update))

        def layer_apply(li, packed, state, decays=None):
            lw = fused_w[li]
            layout = ("words" if lw.word_layout else
                      "codebook" if lw.codebook_mode else "dense")
            block = _pick_engine_block(
                int(packed.shape[0]), lw.kw * Z.SPIKE_WORD_BITS, lw.n_post,
                interp, layout=layout,
                n_levels=int(lw.cbw.shape[0]) if layout == "codebook" else 0,
                all_nonzero=lw.all_nonzero)
            # a stable kernel name per layer, for the profile's op line
            kw = dict(all_nonzero=lw.all_nonzero, block=block,
                      interpret=interp, name=f"snn_fused_l{li + 1}",
                      decays=decays, **lif_kw)
            if layout == "words":
                return fused_timestep_words(packed, lw.words, lw.step,
                                            state.v, state.elapsed, **kw)
            if layout == "codebook":
                return fused_timestep_codebook(
                    packed, lw.idx, lw.cbw, state.v, state.elapsed,
                    gather=interp, **kw)
            return fused_timestep_dense(packed, lw.dense, state.v,
                                        state.elapsed, **kw)

        def layer_counters(li, wall, out, tc, nnz_rows, ew, writes=None):
            """Layer li's counters from its kernel's outputs (or the
            plastic body's, `writes` its per-neuron index writes), over
            any leading axes (..., B): -> (wall plus its cores' cycles,
            {counter: (..., B)}, per-core fired and touched counts)."""
            lt, slices, core_idx, onehot = layer_consts[li]
            nnz = nnz_rows[..., 0].astype(jnp.float32)     # (..., B)
            ew = ew[..., 0]
            tsum = jnp.sum(tc, axis=-1).astype(jnp.float32)
            fired = jnp.sum(out, axis=-1)                  # (..., B)
            # exact per-slice touched counts (tc is the 0/1 mask)
            core_touched = tc.astype(jnp.float32) @ onehot  # (..., B, A)
            core_cyc = cyc.timestep_cycles_array(
                lt.n_pre, slices, nnz[..., None], core_touched,
                sim.zero_skip, sim.partial_update,
                writes=None if writes is None else writes @ onehot)
            per_core = lambda c: jax.ops.segment_sum(  # noqa: E731
                c, core_idx, num_segments=n_active)
            for _ in range(core_cyc.ndim - 1):
                per_core = jax.vmap(per_core)
            wall = wall + per_core(core_cyc)
            cores = {}
            if has_flow[li] or traced:
                cores[f"fired_core_{li}"] = out @ onehot
            if traced:
                cores[f"touched_core_{li}"] = core_touched
            counts = {"nnz": nnz, "touched": tsum, "fired": fired,
                      "skip_words": ew.astype(jnp.float32)}
            if writes is not None:
                counts["writes"] = jnp.sum(writes, axis=-1)
            return wall, counts, cores

        def stack_counters(wall, out, layers):
            """Per-layer (counts, per-core counts) -> ys: each counter
            stacked over the layers (last axis), the wall as its busiest
            core's, the last layer's spikes."""
            ys = {}
            for _, cores in layers:
                ys.update(cores)
            for k in layers[0][0]:
                ys[k] = jnp.stack([counts[k] for counts, _ in layers],
                                  axis=-1)
            ys["wall"] = jnp.max(wall, axis=-1)
            ys["out"] = out
            return ys

        if self.conv_layers:
            return self._build_conv_run(layer_apply, layer_counters,
                                        stack_counters)

        def step(carry, xs):   # (states, last spike words), (B, kw0) [+ t]
            """One timestep of every layer, a recurrent layer taking its
            forward words then its own last step's, each run starting on
            a word.  A step emits only its kernels' outputs, as one int32
            row block, and `counters` reads them after the scan: a step
            of a 100-step scan runs a few device ops, not dozens.  A
            chain carries no spike words (`fed` is empty)."""
            states, fed = carry
            packed, t = xs if drop is not None else (xs, None)
            new_states, new_fed, rows = [], {}, []
            for li in range(len(fused_w)):
                x = (jnp.concatenate([packed, fed[li]], axis=-1)
                     if li in fed else packed)
                vo, eo, out, tc, nnz_rows, ew = layer_apply(
                    li, x, states[li])
                new_states.append(LIFState(v=vo, elapsed=eo))
                rows += [out.astype(jnp.int32), tc, nnz_rows, ew]
                # the rows are pre-drop; the next layer's spike words
                # carry only the packets that survived the hops
                if drop is not None and drop.keep_p[li] is not None:
                    out = out * drop.mask(li, t)
                packed = Z.pack_spike_words(out)
                if li in fed:
                    new_fed[li] = packed
            return (tuple(new_states), new_fed), jnp.concatenate(rows,
                                                                 axis=-1)

        def counters(rows):                  # (T, B, W) -> ys, T leading
            wall = jnp.zeros(rows.shape[:2] + (n_active,), jnp.float32)
            cols = [rows[..., lo:hi] for lo, hi in row_slices]
            layers = []
            for li in range(len(fused_w)):
                out, tc, nnz_rows, ew = cols[4 * li:4 * li + 4]
                out = out.astype(jnp.float32)
                wall, counts, cores = layer_counters(li, wall, out, tc,
                                                     nnz_rows, ew)
                layers.append((counts, cores))
            return stack_counters(wall, out, layers)

        # the row block's columns: per layer (out, touched, nnz, empty words)
        row_slices, lo = [], 0
        for lw in fused_w:
            for width in (lw.n_post, lw.n_post, 1, 1):
                row_slices.append((lo, lo + width))
                lo += width

        def scan_trains(step_fn, carry, trains):  # (B, T, n_in) f32
            # packing is the program's first op: (T, B, kw0) uint16 words
            packed_t = jnp.swapaxes(Z.pack_spike_words(trains), 0, 1)
            xs = (packed_t if drop is None
                  else (packed_t, jnp.arange(packed_t.shape[0])))
            return jax.lax.scan(step_fn, carry, xs)   # T leading

        def batch_major(ys):
            return jax.tree_util.tree_map(lambda a: jnp.swapaxes(a, 0, 1), ys)

        def zero_states(batch):
            return tuple(init_batch_state(batch, lw.n_post) for lw in fused_w)

        if not self.plast.enabled:
            def run(trains):                 # (B, T, n_in) f32
                B = trains.shape[0]
                fed = {li: jnp.zeros((B, fused_w[li].fed_words), jnp.uint16)
                       for li in sim.recurrent}
                (states, _), rows = scan_trains(
                    step, (zero_states(B), fed), trains)
                return batch_major(counters(rows)), states

            return run

        # ---- plasticity path ---------------------------------------------
        # Learnable layers leave the Pallas kernel and run the batched jnp
        # program instead: their weights are per-sample scan state, which
        # the kernel's static closure operands cannot express.  The jnp
        # expressions (unpack -> per-column dequant gather -> batched
        # matmul -> elementwise lif_step) are the batch-native form of
        # exactly what the compiled engine traces per sample under vmap,
        # so the two engines stay bit-identical at word-aligned widths.
        # Frozen layers keep the fused kernel.  The counters stay in the
        # scan, beside the per-step index writes they price.
        from repro.core import plasticity as PLC

        plast = self.plast
        cbws = [None if pt is None else jnp.asarray(pt[1])
                for pt in self.plast_tables]
        reward = plast.mode == "reward"

        def step_plast(carry, xs):
            states, pidx, xpre, xpost, elig = carry
            packed, t = xs if drop is not None else (xs, None)
            B = packed.shape[0]
            wall = jnp.zeros((B, n_active), jnp.float32)
            new_states, layers = [], []
            nidx, nxpre, nxpost, nelig = (list(pidx), list(xpre),
                                          list(xpost), list(elig))
            for li in range(len(fused_w)):
                writes = None
                if cbws[li] is None:
                    vo, eo, out, tc, nnz_rows, ew = layer_apply(
                        li, packed, states[li])
                    new_states.append(LIFState(v=vo, elapsed=eo))
                else:
                    s = Z.unpack_spike_words(packed)           # (B, kp)
                    w = PLC.dequant_indices(pidx[li], cbws[li])
                    current = jnp.einsum("bk,bkn->bn", s, w,
                                         precision=Z.CURRENT_PRECISION)
                    nzw = (w != 0).astype(jnp.float32)
                    tm = jnp.einsum("bk,bkn->bn", s, nzw) > 0
                    st, out, tc = lif_step(states[li], current, lif,
                                           touched=tm)
                    new_states.append(st)
                    nnz_rows = jnp.sum(s != 0, axis=-1, keepdims=True)
                    ew = Z.empty_spike_words(packed)[:, None]
                    if reward:
                        nxpre[li], nxpost[li], nelig[li] = PLC.elig_step(
                            plast, s, out, xpre[li], xpost[li], elig[li])
                    else:
                        nidx[li], nxpre[li], nxpost[li], changed = \
                            PLC.stdp_step(plast, s, out, xpre[li],
                                          xpost[li], pidx[li], cbws[li])
                        # integer-exact per-post write counts -> per-core
                        # plasticity-stage occupancy + priced energy
                        writes = jnp.sum(changed, axis=-2
                                         ).astype(jnp.float32)  # (B, N)
                wall, counts, cores = layer_counters(
                    li, wall, out, tc, nnz_rows, ew, writes=writes)
                counts.setdefault("writes", jnp.zeros((B,), jnp.float32))
                layers.append((counts, cores))
                nxt = (out * drop.mask(li, t)
                       if drop is not None and drop.keep_p[li] is not None
                       else out)
                packed = Z.pack_spike_words(nxt)
            return ((tuple(new_states), nidx, nxpre, nxpost, nelig),
                    stack_counters(wall, out, layers))

        kps = [lw.kw * Z.SPIKE_WORD_BITS for lw in fused_w]

        def run(trains, idx0):               # idx0: row-padded, B leading
            B = trains.shape[0]
            xpre0 = [None if c is None else
                     jnp.zeros((B, kps[li]), jnp.float32)
                     for li, c in enumerate(cbws)]
            xpost0 = [None if c is None else
                      jnp.zeros((B, fused_w[li].n_post), jnp.float32)
                      for li, c in enumerate(cbws)]
            elig0 = [jnp.zeros((B, kps[li], fused_w[li].n_post), jnp.float32)
                     if (c is not None and reward) else None
                     for li, c in enumerate(cbws)]
            carry = (zero_states(B), list(idx0), xpre0, xpost0, elig0)
            final, ys = scan_trains(step_plast, carry, trains)
            return batch_major(ys), final

        return run

    def _build_conv_run(self, layer_apply, layer_counters, stack_counters):
        """The program of a network with conv layers (no recurrent layer,
        plasticity or packet drop: the simulator refuses those).  A conv
        layer-step is one `snn_conv_l<i>` kernel (kernels/
        conv_timestep.py) on its input's tap patches; a dense one keeps
        its `snn_fused_l<i>` kernel.  A conv layer's rows in the step's
        int32 block are its output spike words and its per-core SOPs and
        touched neurons; its input band counts are read after the scan,
        from its input's spikes (the trains, or the layer before's
        rows)."""
        from repro.core.conv import tap_patches
        from repro.kernels.conv_timestep import conv_timestep, pick_lane_tile
        from repro.kernels.ops import interpret_default

        sim = self.sim
        tbl = self.tables
        cyc = sim.cycle_model
        n_active = tbl.n_active_cores
        interp = interpret_default()
        fused_w = self.fused_weights
        L = len(fused_w)
        conv_ops = {li: lw.operands() for li, lw in enumerate(fused_w)
                    if isinstance(lw, ConvLayerWeights)}
        has_flow = [ft is not None for ft in tbl.flows]
        traced = self.trace.enabled
        lif = sim.lif
        lif_kw = dict(threshold=float(lif.threshold), leak=float(lif.leak),
                      reset=float(lif.reset),
                      partial_update=bool(lif.partial_update))

        def conv_shape(li, batch):
            """(channels, pixels, lane tile, padded pixels) of conv
            layer `li`'s kernel at `batch`."""
            g = fused_w[li].geometry
            c, k = conv_ops[li][0].shape
            m = batch * g.out_height * g.out_width
            bm = pick_lane_tile(c, k, m, interp)
            return c, m, bm, -(-m // bm) * bm

        def to_hwc(a, li, batch):
            """(channels, pixels) kernel layout -> (B, n_out) HWC."""
            g = fused_w[li].geometry
            pix = g.out_height * g.out_width
            a = a[:g.c_out, :batch * pix].reshape(g.c_out, batch, pix)
            return jnp.transpose(a, (1, 2, 0)).reshape(batch, pix * g.c_out)

        def conv_apply(li, x, state, decays):
            """(B, n_in) 0/1 spikes through conv layer `li`: -> (state,
            spikes (B, n_out), per-core SOPs and touched (B, A))."""
            lw, (words, nonzero) = fused_w[li], conv_ops[li]
            B = x.shape[0]
            _, m, bm, m_pad = conv_shape(li, B)
            p = tap_patches(x, lw.geometry)
            p = jnp.pad(p, ((0, words.shape[1] - p.shape[0]),
                            (0, m_pad - m)))
            vo, eo, sp, tc = conv_timestep(
                p, words, nonzero, lw.step, state.v, state.elapsed,
                block=None if interp else bm, interpret=interp,
                name=f"snn_conv_l{li + 1}", decays=decays, **lif_kw)
            tcnt = to_hwc(tc, li, B)
            touched = tcnt > 0 if lif.partial_update else \
                jnp.ones(tcnt.shape, bool)
            onehot = tbl.layers[li].slice_onehot
            return (LIFState(v=vo, elapsed=eo), to_hwc(sp, li, B),
                    slice_sums(tcnt, onehot), slice_sums(touched, onehot))

        def step(states, packed, decays):    # LIF states, (B, kw0) words
            new_states, rows = [], []
            x = None                          # the last layer's 0/1 spikes
            for li in range(L):
                if li in conv_ops:
                    if x is None:
                        x = Z.unpack_spike_words(packed, tbl.layers[li].n_pre)
                    st, out, sops, touched = conv_apply(li, x, states[li],
                                                        decays)
                    new_states.append(st)
                    packed = Z.pack_spike_words(out)
                    rows += [packed.astype(jnp.int32),
                             sops.astype(jnp.int32),
                             touched.astype(jnp.int32)]
                else:
                    vo, eo, out, tc, nnz_rows, ew = layer_apply(
                        li, packed, states[li], decays)
                    new_states.append(LIFState(v=vo, elapsed=eo))
                    rows += [out.astype(jnp.int32), tc, nnz_rows, ew]
                    packed = Z.pack_spike_words(out)
                x = out
            return tuple(new_states), jnp.concatenate(rows, axis=-1)

        # the row block's columns: per conv layer (spike words, per-core
        # SOPs, per-core touched), per dense one (out, touched, nnz, empty
        # words)
        row_slices, lo = [], 0
        for li, lw in enumerate(fused_w):
            n_cores = len(tbl.layers[li].slice_sizes)
            widths = ((Z.spike_word_count(lw.geometry.n_out), n_cores,
                       n_cores) if li in conv_ops
                      else (lw.n_post, lw.n_post, 1, 1))
            cols = []
            for width in widths:
                cols.append((lo, lo + width))
                lo += width
            row_slices.append(cols)

        def conv_counters(li, wall, in_bits, in_words, out, sops, touched):
            """Conv layer `li`'s counters over (T, B): its input band
            counts from its input's spikes and words, its cycles from its
            per-core SOPs and touched neurons."""
            lt = tbl.layers[li]
            ct = lt.conv
            empty = (in_words == 0).astype(jnp.float32) @ ct.word_onehot
            core_cyc = cyc.conv_timestep_cycles_array(
                jnp.asarray(ct.band_words), sops, touched,
                jnp.asarray(ct.synapses), jnp.asarray(lt.slice_sizes),
                sim.zero_skip, sim.partial_update)
            per_core = lambda c: jax.ops.segment_sum(  # noqa: E731
                c, jnp.asarray(lt.core_index), num_segments=n_active)
            for _ in range(core_cyc.ndim - 1):
                per_core = jax.vmap(per_core)
            cores = {f"sops_core_{li}": sops}
            if has_flow[li] or traced:
                cores[f"fired_core_{li}"] = out @ lt.slice_onehot
            if traced:
                cores[f"touched_core_{li}"] = touched
                cores[f"nnz_core_{li}"] = in_bits @ ct.band_onehot
            counts = {"nnz": jnp.sum(in_bits, axis=-1),
                      "touched": jnp.sum(touched, axis=-1),
                      "fired": jnp.sum(out, axis=-1),
                      "skip_words": jnp.sum(empty, axis=-1)}
            return wall + per_core(core_cyc), counts, cores

        def counters(rows, packed_t):        # (T, B, W) -> ys, T leading
            wall = jnp.zeros(rows.shape[:2] + (n_active,), jnp.float32)
            in_words = packed_t
            in_bits = None
            layers = []
            for li in range(L):
                cols = [rows[..., a:b] for a, b in row_slices[li]]
                n_pre = tbl.layers[li].n_pre
                if li in conv_ops:
                    words, sops, touched = cols
                    if in_bits is None:
                        in_bits = Z.unpack_spike_words(in_words, n_pre)
                    if in_words is None:
                        in_words = Z.pack_spike_words(in_bits)
                    out = Z.unpack_spike_words(words.astype(jnp.uint16),
                                               tbl.layers[li].n_post)
                    wall, counts, cores = conv_counters(
                        li, wall, in_bits, in_words, out,
                        sops.astype(jnp.float32),
                        touched.astype(jnp.float32))
                    in_words = words
                else:
                    out, tc, nnz_rows, ew = cols
                    out = out.astype(jnp.float32)
                    wall, counts, cores = layer_counters(li, wall, out, tc,
                                                         nnz_rows, ew)
                    in_words = None
                in_bits = out
                layers.append((counts, cores))
            return stack_counters(wall, out, layers)

        def zero_states(batch):
            states = []
            for li, lw in enumerate(fused_w):
                if li in conv_ops:
                    c, _, _, m_pad = conv_shape(li, batch)
                    states.append(LIFState(
                        v=jnp.zeros((c, m_pad), jnp.float32),
                        elapsed=jnp.zeros((c, m_pad), jnp.int32)))
                else:
                    states.append(init_batch_state(batch, lw.n_post))
            return tuple(states)

        def run(trains):                     # (B, T, n_in) f32
            B = trains.shape[0]
            packed_t = jnp.swapaxes(Z.pack_spike_words(trains), 0, 1)
            # every layer's leak exact (`decay_table`), conv or dense
            decays = (decay_table(float(lif.leak), trains.shape[1])
                      if lif.partial_update else None)
            states, rows = jax.lax.scan(
                functools.partial(step, decays=decays), zero_states(B),
                packed_t)
            # final states in the (B, n_post) neuron order
            states = tuple(
                LIFState(v=to_hwc(st.v, li, B),
                         elapsed=to_hwc(st.elapsed, li, B))
                if li in conv_ops else st for li, st in enumerate(states))
            ys = jax.tree_util.tree_map(lambda a: jnp.swapaxes(a, 0, 1),
                                        counters(rows, packed_t))
            return ys, states                # ys batch-major

        return run

    def _adapt_learned(self, li: int, idx: jax.Array) -> jax.Array:
        """Pad learned-index rows to the spike-word boundary.  Padded
        rows never see a spike (their packed bits are zero) and their
        pre-trace stays zero, so they are write-free fixed points."""
        kp = self.fused_weights[li].kw * Z.SPIKE_WORD_BITS
        pad = kp - int(idx.shape[-2])
        if pad:
            idx = jnp.pad(idx, [(0, 0)] * (idx.ndim - 2) + [(0, pad), (0, 0)])
        return idx

    def _make_executable(self, sharded: bool):
        fn = self._build_run()
        if sharded:
            fn = self._shard_wrap(fn, n_args=2 if self.plast.enabled else 1)

        if not self.plast.enabled:
            run_jit = jax.jit(fn)

            def executable(trains):          # (B, T, n_in) f32
                ys, self.last_states = run_jit(trains)
                return ys

            return executable

        run_jit = jax.jit(fn, donate_argnums=(1,))   # donate learned idx
        plast_tables = self.plast_tables
        fused_w = self.fused_weights
        reward = self.plast.mode == "reward"

        def executable(trains, idx0):        # idx0: row-padded, B leading
            ys, final = run_jit(trains, idx0)
            self.last_states = final[0]
            fidx, felig = final[1], final[4]
            for li, pt in enumerate(plast_tables):
                if pt is None:
                    continue
                n_pre = fused_w[li].n_pre   # crop the word-boundary pad
                ys[f"learned_idx_{li}"] = fidx[li][:, :n_pre, :]
                if reward:
                    ys[f"elig_{li}"] = felig[li][:, :n_pre, :]
            return ys

        return executable
