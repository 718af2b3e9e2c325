"""The plain reference: the chip's SNN semantics and pricing in NumPy.

It imports nothing of the program.  Per layer-step, for a batch of
spike trains:

    current  = spikes @ W                               (f32)
    touched  = any valid spike reaches a nonzero synapse
    pending  = elapsed + 1
    v_int    = v * leak**pending + current              (touched only)
    spike    = touched and v_int >= threshold
    v        = reset if spike, v_int if touched, else v (lazy leak)
    elapsed  = 0 if touched else pending

(the partial-update LIF with hard reset of the paper, and of
`core/neuron.py`), and the per-sample `ChipReport` the chip model
prices, with zero-skip and partial update on, as the paper's chip runs:

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
the mapping compiler's; they enter as plain data (`plan`, see `run`),
and every price is worked out here from them.

`high_precision_weights(w)` gives the weights that a TPU's 3-pass bf16
matmul (precision HIGH) multiplies when the left operand is 0/1: the
weight's high bf16 half cut toward zero, plus the remainder rounded to
bf16 (bit-equal to the TPU's HIGH currents at these configurations'
weights; one rounding step of 16 of the weight's up to 18 significant
bits).  Run with them, the reference is the control (`control=True`):
the same network one precision step below the f32 HIGHEST that the
program states.
"""
from __future__ import annotations

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


def _empty_words(s: np.ndarray) -> np.ndarray:
    """(B, K) 0/1 -> (B,) count of all-zero 16-spike words (zero-padded)."""
    b, k = s.shape
    kw = -(-k // SPIKE_WORD_BITS)
    padded = np.pad(s != 0, ((0, 0), (0, kw * SPIKE_WORD_BITS - k)))
    return (~padded.reshape(b, kw, SPIKE_WORD_BITS).any(-1)).sum(-1)


def _slice_sums(x: np.ndarray, slices) -> np.ndarray:
    """(B, n) -> (B, A): sums over each [lo, hi) neuron slice."""
    return np.stack([x[:, lo:hi].sum(-1) for lo, hi in slices], -1)


def simulate(weights, trains, *, leak: float, threshold: float,
             reset: float = 0.0, slices=None, matmul=np.matmul) -> dict:
    """(B, T, n_in) 0/1 trains through the network.

    Returns `counts` (B, n_out) output spike counts and the per-step
    per-layer counters `nnz`, `touched`, `fired`, `skip` (B, T, L).  With
    `slices` (per layer, the [lo, hi) neuron ranges of its core slices)
    also `touched_slices` and `fired_slices`: per layer (B, T, A).
    `matmul` computes a layer's currents.
    """
    trains = np.asarray(trains, np.float32)
    B, T, _ = trains.shape
    leak32, thr32, reset32 = (np.float32(leak), np.float32(threshold),
                              np.float32(reset))
    nonzero = [bool(np.all(w != 0)) for w in weights]
    nzw = [None if nz else (w != 0).astype(np.float32)
           for w, nz in zip(weights, nonzero)]
    v = [np.zeros((B, w.shape[1]), np.float32) for w in weights]
    elapsed = [np.zeros((B, w.shape[1]), np.int32) for w in weights]
    steps = {k: [] for k in ("nnz", "touched", "fired", "skip")}
    per_slice = {k: [[] for _ in weights] for k in ("touched_slices",
                                                    "fired_slices")}
    counts = np.zeros((B, weights[-1].shape[1]), np.float64)
    for t in range(T):
        s = trains[:, t, :]
        for li, w in enumerate(weights):
            nnz = (s != 0).sum(-1)
            steps["nnz"].append(nnz)
            steps["skip"].append(_empty_words(s))
            current = matmul(s, w)
            if nonzero[li]:
                touched = np.broadcast_to((nnz > 0)[:, None], current.shape)
            else:
                touched = matmul(s, nzw[li]) > 0
            pending = elapsed[li] + 1
            decay = np.where(touched, leak32 ** pending.astype(np.float32),
                             np.float32(1.0))
            v_int = v[li] * decay + current
            spike = touched & (v_int >= thr32)
            v[li] = np.where(spike, reset32, np.where(touched, v_int, v[li]))
            elapsed[li] = np.where(touched, 0, pending).astype(np.int32)
            steps["touched"].append(touched.sum(-1))
            steps["fired"].append(spike.sum(-1))
            if slices is not None:
                per_slice["touched_slices"][li].append(
                    _slice_sums(touched, slices[li]))
                per_slice["fired_slices"][li].append(
                    _slice_sums(spike, slices[li]))
            s = spike.astype(np.float32)
        counts += s
    L = len(weights)
    out = {k: np.stack(v_, -1).astype(np.float64).reshape(B, T, L)
           for k, v_ in steps.items()}
    if slices is not None:
        for k, layers in per_slice.items():
            out[k] = [np.stack(x, 1).astype(np.float64) for x in layers]
    out["counts"] = counts
    return out


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


def sample_fields(out: dict, config: dict, plan: dict) -> np.ndarray:
    """Per-sample `ChipReport` (B, len(FIELDS)) from `simulate`'s output
    (run with the plan's slices)."""
    sizes = [int(x) for x in config["layer_sizes"]]
    n_post = np.asarray(sizes[1:], np.float64)
    B, T, L = out["nnz"].shape
    performed = (out["nnz"] * n_post).sum(axis=(1, 2))
    nominal = float(sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))) * T

    # wall cycles of the cores: per step, the busiest core
    cores = sorted({c for layer in plan["layers"] for c, _, _ in layer})
    core_cycles = np.zeros((B, T, len(cores)))
    for li, layer in enumerate(plan["layers"]):
        scan = -(-sizes[li] // SPIKE_WORD_BITS)
        nnz = out["nnz"][:, :, li]
        for a, (core, lo, hi) in enumerate(layer):
            syn = np.ceil(nnz * (hi - lo) / _SPE_LANES)
            upd = out["touched_slices"][li][:, :, a]
            core_cycles[:, :, cores.index(core)] += np.maximum(
                np.maximum(scan, syn), upd) + _PIPELINE_DEPTH
    core_wall = core_cycles.max(axis=-1)                      # (B, T)

    # the NoC: every flow replayed with its source slice's spikes
    nodes = [int(x) for routes in plan["routes"] for f in routes
             for link in f["links"] for x in link]
    n_nodes = 1 + max(cores + nodes)
    hops = np.zeros(B)
    noc_pj = np.zeros(B)
    router = np.zeros((B, T, n_nodes))
    for li, routes in enumerate(plan["routes"]):
        fired = out["fired_slices"][li]                       # (B, T, A)
        srcs = [c for c, _, _ in plan["layers"][li]]
        if [int(r["src"]) for r in routes] != srcs:
            raise ValueError(f"layer {li}: the routes' sources {srcs} are "
                             f"not its slices' cores")
        f_hops, f_pj, f_load = _flow_tables(routes, plan["level2_nodes"],
                                            n_nodes)
        hops += (fired @ f_hops).sum(axis=1)
        noc_pj += (fired @ f_pj).sum(axis=1)
        router += fired @ f_load
    service = router.max(axis=-1) / _ROUTER_SPIKES_PER_CYCLE
    contention = service + service * service / np.maximum(core_wall, 1e-9)
    wall = (core_wall + contention).sum(axis=1)

    core_pj = core_pj_per_nominal_sop(performed / nominal) * nominal
    duty = np.minimum(1.0, T * _RISCV_CTRL_CYCLES / np.maximum(wall, 1.0))
    riscv_mw = _RISCV_ACTIVE_MW * (duty + _RISCV_SLEEP * (1.0 - duty))
    riscv_pj = riscv_mw * 1e-3 * (wall / float(config["freq_hz"])) * 1e12
    cols = {
        "spikes_in": out["nnz"].sum(axis=(1, 2)),
        "performed_sops": performed,
        "neurons_touched": out["touched"].sum(axis=(1, 2)),
        "spikes_routed": out["fired"][:, :, :-1].sum(axis=(1, 2)),
        "nominal_sops": np.full(B, nominal),
        "spike_words_skipped": out["skip"].sum(axis=(1, 2)),
        "noc_hops": hops,
        "core_energy_pj": core_pj,
        "noc_energy_pj": noc_pj,
        "riscv_energy_pj": riscv_pj,
        "energy_pj": core_pj + noc_pj + riscv_pj,
        "wall_cycles": wall,
        "noc_contention_cycles": contention.sum(axis=1),
    }
    return np.stack([cols[f] for f in FIELDS], axis=-1)


def run(layers, trains: np.ndarray, config: dict, plan: dict, *,
        control: bool = False, block: int = 32
        ) -> tuple[np.ndarray, np.ndarray]:
    """Reference (or control) over `trains` in blocks of rows.

    `layers` are `workload.LayerCodebook`s.  `plan` is the mapping as
    data: `layers`, per weight layer its slices `[core, lo, hi]` in the
    compiler's order; `routes`, per layer that fires into another, one
    flow per slice (`src` core, `dsts`, directed `links` [u, v]); and
    `level2_nodes`.  Returns (counts (N, n_out), fields (N, len(FIELDS))).
    """
    weights = [lc.dense() for lc in layers]
    if control:
        weights = [high_precision_weights(w) for w in weights]
    slices = [[(lo, hi) for _, lo, hi in layer] for layer in plan["layers"]]
    counts, fields = [], []
    for lo in range(0, len(trains), block):
        out = simulate(weights, trains[lo:lo + block],
                       leak=float(config["leak"]),
                       threshold=float(config["threshold"]),
                       reset=float(config.get("reset", 0.0)), slices=slices)
        counts.append(out["counts"])
        fields.append(sample_fields(out, config, plan))
    return np.concatenate(counts), np.concatenate(fields)
