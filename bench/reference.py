"""The plain reference: the chip's SNN semantics and pricing in NumPy.

It imports nothing of the program.  What every network kind shares
lives here; how a kind's layers connect, and so which spikes reach
which layer, lives in its `bench/networks/<kind>.py`, which composes
these pieces.  Per layer-step, for a batch of spike trains:

    current  = spikes @ W                               (f32)
    touched  = any valid spike reaches a nonzero synapse
    pending  = elapsed + 1
    v_int    = v * leak**pending + current              (touched only)
    spike    = touched and v_int >= threshold
    v        = reset if spike, v_int if touched, else v (lazy leak)
    elapsed  = 0 if touched else pending

(`touched_neurons`, then `lif_step`: the partial-update LIF with hard
reset of the paper, and of `core/neuron.py`), and the per-sample
`ChipReport` the chip model prices, with zero-skip and partial update
on, as the paper's chip runs (`chip_report`, from each weight layer's
`Edge` and each firing population's `Flows`):

* counters: input spikes, SOPs performed (spikes x fan-out), neurons
  touched, spikes routed between layers, nominal SOPs, empty 16-spike
  words, and NoC hops;
* wall cycles: per core and timestep the 4-stage pipeline's slowest
  stage (ZSPE scan of the input in 16-spike words, SPE synapses 4 per
  cycle, one neuron update per cycle) plus its fill, summed over the
  core's slices; the busiest core sets the step, and the busiest
  router's queue (M/M/1 over the step) adds its contention cycles;
* energy: core energy from the paper's calibrated core model (the five
  anchors of its Fig. 3 / section II-A, solved in closed form), NoC
  energy per hop (P2P or broadcast rate of the CMRouter), the duty-cycled
  RISC-V over the wall time, and their total.

The placement of neuron slices on cores and the routes between them are
the mapping compiler's; they enter as plain data (a kind's `plan`), and
every price is worked out here from them.

`high_precision_weights(w)` gives the weights that a TPU's 3-pass bf16
matmul (precision HIGH) multiplies when the left operand is 0/1: the
weight's high bf16 half cut toward zero, plus the remainder rounded to
bf16 (bit-equal to the TPU's HIGH currents at these configurations'
weights; one rounding step of 16 of the weight's up to 18 significant
bits).  Run with them, a kind's reference is the control
(`control=True`): the same network one precision step below the f32
HIGHEST that the program states.
"""

from __future__ import annotations

import dataclasses

import numpy as np

SPIKE_WORD_BITS = 16

# the paper's core-model anchors (section II-A, Fig. 3)
_GSOPS_BEST, _GSOPS_S40 = 0.627, 0.426
_PJ_BEST, _PJ_S40 = 0.627, 1.196
_FREQ_GHZ = 0.2

# core pipeline: 16-spike ZSPE window, 2 SPEs x 2 synapses per cycle,
# one neuron update per cycle, 4 stages to fill
_SPE_LANES, _PIPELINE_DEPTH = 4, 4

# CMRouter (Fig. 4/5): pJ per hop, P2P and per destination of a 1-to-3
# broadcast; spikes a router moves per cycle.  A hop over a level-2
# (off-chip) router is the program's stated estimate, 10x a P2P hop.
_E_HOP_P2P, _E_HOP_BCAST, _E_HOP_L2 = 0.026, 0.009, 0.26
_ROUTER_SPIKES_PER_CYCLE = 0.4

# RISC-V (Fig. 6): 0.434 mW average after a 43% saving; asleep at 5% of
# active power; 200 control cycles per timestep
_RISCV_ACTIVE_MW = 0.434 / (1.0 - 0.43)
_RISCV_SLEEP, _RISCV_CTRL_CYCLES = 0.05, 200.0

# per-sample counters, in this order (check.report_fields reads the
# program's ChipReports into the same columns)
FIELDS = ("spikes_in", "performed_sops", "neurons_touched", "spikes_routed",
          "nominal_sops", "spike_words_skipped", "noc_hops",
          "core_energy_pj", "noc_energy_pj", "riscv_energy_pj", "energy_pj",
          "wall_cycles", "noc_contention_cycles")


def core_pj_per_nominal_sop(density):
    """Core energy per nominal SOP at input density 1 - sparsity, with
    zero-skip and partial update on."""
    a = _FREQ_GHZ / _GSOPS_BEST
    b = (_FREQ_GHZ / _GSOPS_S40 - a) / (1.0 - 0.4)
    alpha = _PJ_BEST / a
    gamma = (_PJ_S40 - alpha * (a + 0.6 * b)) / 0.6
    return alpha * (a + b * density) + gamma * density


def _bf16_nearest(x: np.ndarray) -> np.ndarray:
    """Round f32 to bf16 (nearest, ties to even), returned as f32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + (np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1)))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _bf16_toward_zero(x: np.ndarray) -> np.ndarray:
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    return (u & np.uint32(0xFFFF0000)).view(np.float32)


def high_precision_weights(w: np.ndarray) -> np.ndarray:
    hi = _bf16_toward_zero(w)
    return hi + _bf16_nearest(w - hi)


def empty_words(s: np.ndarray) -> np.ndarray:
    """(B, K) 0/1 -> (B,) count of all-zero 16-spike words (zero-padded)."""
    b, k = s.shape
    kw = -(-k // SPIKE_WORD_BITS)
    padded = np.pad(s != 0, ((0, 0), (0, kw * SPIKE_WORD_BITS - k)))
    return (~padded.reshape(b, kw, SPIKE_WORD_BITS).any(-1)).sum(-1)


def slice_sums(x: np.ndarray, slices) -> np.ndarray:
    """(B, n) -> (B, A): sums over each [lo, hi) neuron slice."""
    return np.stack([x[:, lo:hi].sum(-1) for lo, hi in slices], -1)


def touched_neurons(s: np.ndarray, nnz: np.ndarray, nonzero_w,
                    n_post: int, matmul=np.matmul) -> np.ndarray:
    """(B, n_post) neurons that a valid spike of `s` (B, n_pre) reaches
    through a nonzero synapse.  `nonzero_w` is the layer's 0/1 f32 mask
    of nonzero synapses, or None when every synapse is nonzero (then any
    input spike touches every neuron); `nnz` (B,) counts `s`'s spikes."""
    if nonzero_w is None:
        return np.broadcast_to((nnz > 0)[:, None], (len(s), n_post))
    return matmul(s, nonzero_w) > 0


def lif_step(v: np.ndarray, elapsed: np.ndarray, current: np.ndarray,
             touched: np.ndarray, *, leak: float, threshold: float,
             reset: float) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One partial-update LIF step with lazy leak and hard reset, in f32:
    -> (v, elapsed, spike)."""
    leak32, thr32, reset32 = (np.float32(leak), np.float32(threshold),
                              np.float32(reset))
    pending = elapsed + 1
    decay = np.where(touched, leak32 ** pending.astype(np.float32),
                     np.float32(1.0))
    v_int = v * decay + current
    spike = touched & (v_int >= thr32)
    v = np.where(spike, reset32, np.where(touched, v_int, v))
    return v, np.where(touched, 0, pending).astype(np.int32), spike


@dataclasses.dataclass(frozen=True)
class Edge:
    """One weight layer (an edge of the network's graph) as its target
    cores price it.

    `nnz` and `skip` (B, T): the spikes it takes in per step, and their
    all-zero 16-spike words; `n_pre`: its input width, which the ZSPE
    scans in 16-spike words; `fan_out`: the SOPs each input spike
    performs (the nominal SOPs are `n_pre * fan_out` per step);
    `slices`: the target's core slices `[core, lo, hi]`, with `touched`
    (B, T, A) the neurons each slice updates per step.  A core's SPEs
    take each input spike's `hi - lo` synapses of the slice.
    """

    nnz: np.ndarray
    skip: np.ndarray
    n_pre: int
    fan_out: int
    slices: list
    touched: np.ndarray


@dataclasses.dataclass(frozen=True)
class Flows:
    """The spikes one population sends over the NoC: `fired` (B, T, A)
    per source slice, and `routes`, one flow per slice in the same order
    (`src` core, `dsts`, directed `links` [u, v]); `srcs` are the
    slices' cores, which the flows' sources must be."""

    fired: np.ndarray
    routes: list
    srcs: list


def core_wall_cycles(edges) -> np.ndarray:
    """(B, T): per step the busiest core's pipeline cycles over every
    slice it holds, in the edges' order."""
    cores = sorted({c for e in edges for c, _, _ in e.slices})
    B, T = edges[0].nnz.shape
    core_cycles = np.zeros((B, T, len(cores)))
    for e in edges:
        scan = -(-e.n_pre // SPIKE_WORD_BITS)
        for a, (core, lo, hi) in enumerate(e.slices):
            syn = np.ceil(e.nnz * (hi - lo) / _SPE_LANES)
            upd = e.touched[:, :, a]
            core_cycles[:, :, cores.index(core)] += np.maximum(
                np.maximum(scan, syn), upd) + _PIPELINE_DEPTH
    return core_cycles.max(axis=-1)


def _flow_tables(routes, level2_nodes, n_nodes: int):
    """Per flow of one layer: hops, energy per spike, router occupancy."""
    l2 = set(int(x) for x in level2_nodes)
    hops = np.zeros(len(routes))
    pj = np.zeros(len(routes))
    load = np.zeros((len(routes), n_nodes))
    for i, r in enumerate(routes):
        links = [(int(u), int(v)) for u, v in r["links"]]
        n_l2 = sum(1 for u, v in links if u in l2 or v in l2)
        e_l1 = _E_HOP_P2P if len(r["dsts"]) == 1 else _E_HOP_BCAST
        hops[i] = len(links)
        pj[i] = e_l1 * (len(links) - n_l2) + _E_HOP_L2 * n_l2
        for u, _ in links:
            load[i, u] += 1
    return hops, pj, load


def noc_traffic(flows, level2_nodes, n_nodes: int, shape: tuple):
    """Every flow replayed with its source slice's spikes, in the flows'
    order, over `shape` (B, T) -> (hops (B,), NoC pJ (B,), router
    occupancy (B, T, n_nodes))."""
    B, T = shape
    hops = np.zeros(B)
    noc_pj = np.zeros(B)
    router = np.zeros((B, T, n_nodes))
    for i, f in enumerate(flows):
        if [int(r["src"]) for r in f.routes] != list(f.srcs):
            raise ValueError(f"flow set {i}: the routes' sources are not "
                             f"its slices' cores {list(f.srcs)}")
        f_hops, f_pj, f_load = _flow_tables(f.routes, level2_nodes, n_nodes)
        hops += (f.fired @ f_hops).sum(axis=1)
        noc_pj += (f.fired @ f_pj).sum(axis=1)
        router += f.fired @ f_load
    return hops, noc_pj, router


def contention_cycles(router: np.ndarray, core_wall: np.ndarray
                      ) -> np.ndarray:
    """(B, T): the busiest router's M/M/1 queue over each step."""
    service = router.max(axis=-1) / _ROUTER_SPIKES_PER_CYCLE
    return service + service * service / np.maximum(core_wall, 1e-9)


def riscv_energy_pj(wall: np.ndarray, timesteps: int,
                    freq_hz: float) -> np.ndarray:
    """The duty-cycled RISC-V over `wall` cycles of `timesteps` steps."""
    duty = np.minimum(1.0, timesteps * _RISCV_CTRL_CYCLES
                      / np.maximum(wall, 1.0))
    riscv_mw = _RISCV_ACTIVE_MW * (duty + _RISCV_SLEEP * (1.0 - duty))
    return riscv_mw * 1e-3 * (wall / float(freq_hz)) * 1e12


def chip_report(edges, flows, *, level2_nodes, freq_hz: float
                ) -> np.ndarray:
    """Per-sample `ChipReport` (B, len(FIELDS)) of a network from its
    weight layers (`Edge`s) and the populations that fire into another
    (`Flows`)."""
    B, T = edges[0].nnz.shape
    performed = sum((e.nnz * e.fan_out).sum(axis=1) for e in edges)
    nominal = float(sum(e.n_pre * e.fan_out for e in edges)) * T
    core_wall = core_wall_cycles(edges)                     # (B, T)
    cores = [c for e in edges for c, _, _ in e.slices]
    nodes = [int(x) for f in flows for r in f.routes
             for link in r["links"] for x in link]
    hops, noc_pj, router = noc_traffic(flows, level2_nodes,
                                       1 + max(cores + nodes), (B, T))
    contention = contention_cycles(router, core_wall)
    wall = (core_wall + contention).sum(axis=1)
    core_pj = core_pj_per_nominal_sop(performed / nominal) * nominal
    riscv_pj = riscv_energy_pj(wall, T, freq_hz)
    cols = {
        "spikes_in": sum(e.nnz.sum(axis=1) for e in edges),
        "performed_sops": performed,
        "neurons_touched": sum(e.touched.sum(axis=(1, 2)) for e in edges),
        "spikes_routed": sum((f.fired.sum(axis=(1, 2)) for f in flows),
                             np.zeros(B)),
        "nominal_sops": np.full(B, nominal),
        "spike_words_skipped": sum(e.skip.sum(axis=1) for e in edges),
        "noc_hops": hops,
        "core_energy_pj": core_pj,
        "noc_energy_pj": noc_pj,
        "riscv_energy_pj": riscv_pj,
        "energy_pj": core_pj + noc_pj + riscv_pj,
        "wall_cycles": wall,
        "noc_contention_cycles": contention.sum(axis=1),
    }
    return np.stack([cols[f] for f in FIELDS], axis=-1)
