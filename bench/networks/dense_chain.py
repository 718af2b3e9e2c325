"""A dense chain: layer i feeds layer i + 1 through every synapse.

`config["layer_sizes"]` gives the widths, input first.  Each weight
layer is one codebook (`workload.layer_levels`, 8 +/- pairs of words,
so every synapse is nonzero) with uniform 4-bit indices, and each
layer's spikes travel to the next over the flows the mapping compiler
routed from its core slices.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from bench import reference as shared
from bench import workload


def sizes(config: dict) -> list[int]:
    return [int(s) for s in config["layer_sizes"]]


def n_in(config: dict) -> int:
    return sizes(config)[0]


def make(config: dict, seed: int):
    """-> (program weights: list of `quant.QuantizedTensor` on the device,
    reference weights: list of `workload.LayerCodebook` on the host)."""
    from repro.core.quant import QuantizedTensor

    widths = sizes(config)
    shapes = tuple(zip(widths[:-1], widths[1:]))
    idx_dev = workload.device_indices(seed, shapes,
                                      int(config["weight_levels"]))
    program, ref_layers = [], []
    for li, ((n_pre, _), idx) in enumerate(zip(shapes, idx_dev)):
        words, scale = workload.layer_levels(config, seed, li, n_pre)
        levels = words.astype(np.float32) * scale
        program.append(QuantizedTensor(
            idx=idx, codebook=jnp.asarray(levels[None, :]),
            scale=jnp.asarray([scale], jnp.float32), group_axis_size=0))
        ref_layers.append(workload.LayerCodebook(
            idx=np.asarray(idx), words=words, scale=scale, levels=levels))
    return program, ref_layers


def simulator(config: dict, traffic: dict, program_weights):
    """The program's `ChipSimulator` of the chain on the traffic's engine,
    its array engine lowered; on `fused`, every layer runs from the
    codebook."""
    from repro.core.quant import CodebookConfig
    from repro.core.soc import ChipSimulator

    sim = ChipSimulator(
        program_weights, quant_cfg=CodebookConfig(
            n_levels=int(config["weight_levels"]),
            bit_width=int(config["weight_bits"])),
        engine=traffic["engine"], leak=float(config["leak"]),
        threshold=float(config["threshold"]),
        freq_hz=float(config["freq_hz"]))
    engine = sim.array_engine()
    if traffic["engine"] == "fused" and \
            engine.codebook_layers != len(program_weights):
        raise RuntimeError(f"fused engine runs {engine.codebook_layers} of "
                           f"{len(program_weights)} layers from the codebook")
    return sim


def plan(sim, config: dict) -> dict:
    """The mapping compiler's placement and routes of `sim`, as the plain
    data `reference` prices from: per weight layer its core slices
    `[core, lo, hi]`, per layer that fires into another one flow per
    slice (`src`, `dsts`, `links`), and the level-2 router nodes."""
    n_layers = len(sizes(config)) - 1
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


def simulate(weights, trains, *, leak: float, threshold: float,
             reset: float = 0.0, slices=None, matmul=np.matmul) -> dict:
    """(B, T, n_in) 0/1 trains through the chain.

    Returns `counts` (B, n_out) output spike counts and the per-step
    per-layer counters `nnz`, `touched`, `fired`, `skip` (B, T, L).  With
    `slices` (per layer, the [lo, hi) neuron ranges of its core slices)
    also `touched_slices` and `fired_slices`: per layer (B, T, A).
    `matmul` computes a layer's currents.
    """
    trains = np.asarray(trains, np.float32)
    B, T, _ = trains.shape
    nzw = [None if np.all(w != 0) else (w != 0).astype(np.float32)
           for w in weights]
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
            steps["skip"].append(shared.empty_words(s))
            current = matmul(s, w)
            touched = shared.touched_neurons(s, nnz, nzw[li], w.shape[1],
                                             matmul)
            v[li], elapsed[li], spike = shared.lif_step(
                v[li], elapsed[li], current, touched, leak=leak,
                threshold=threshold, reset=reset)
            steps["touched"].append(touched.sum(-1))
            steps["fired"].append(spike.sum(-1))
            if slices is not None:
                per_slice["touched_slices"][li].append(
                    shared.slice_sums(touched, slices[li]))
                per_slice["fired_slices"][li].append(
                    shared.slice_sums(spike, slices[li]))
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


def sample_fields(out: dict, config: dict, plan: dict) -> np.ndarray:
    """Per-sample `ChipReport` (B, len(FIELDS)) from `simulate`'s output
    (run with the plan's slices): weight layer i is an edge of fan-out
    `layer_sizes[i + 1]` into its slices, and every layer but the last
    sends its spikes over its routes."""
    widths = sizes(config)
    edges = [shared.Edge(
        nnz=out["nnz"][:, :, li], skip=out["skip"][:, :, li],
        n_pre=widths[li], fan_out=widths[li + 1], slices=layer,
        touched=out["touched_slices"][li])
        for li, layer in enumerate(plan["layers"])]
    flows = [shared.Flows(
        fired=out["fired_slices"][li], routes=routes,
        srcs=[c for c, _, _ in plan["layers"][li]])
        for li, routes in enumerate(plan["routes"])]
    return shared.chip_report(edges, flows,
                                 level2_nodes=plan["level2_nodes"],
                                 freq_hz=float(config["freq_hz"]))


def reference(ref_layers, trains: np.ndarray, config: dict, plan: dict,
                  *, control: bool = False, block: int = 32
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Reference (or control) over `trains` in blocks of rows.

    `plan` is the mapping as data (from `plan`): `layers`, per weight layer
    its slices `[core, lo, hi]` in the compiler's order; `routes`, per
    layer that fires into another, one flow per slice (`src` core,
    `dsts`, directed `links` [u, v]); and `level2_nodes`.  Returns
    (counts (N, n_out), fields (N, len(FIELDS))).
    """
    weights = [lc.dense() for lc in ref_layers]
    if control:
        weights = [shared.high_precision_weights(w) for w in weights]
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


def least_bytes(config: dict, batch: int) -> float:
    """Every weight once at log2(N) bits, one level table per layer (N
    words of W bits), the input spikes at 1 bit, the output counts as
    int32."""
    widths = sizes(config)
    n_levels, wbits = int(config["weight_levels"]), int(config["weight_bits"])
    idx_bits = math.ceil(math.log2(n_levels))
    weights = sum(a * b for a, b in zip(widths[:-1], widths[1:])) \
        * idx_bits / 8
    tables = (len(widths) - 1) * n_levels * wbits / 8
    spikes = batch * int(config["timesteps"]) * widths[0] / 8
    outputs = batch * widths[-1] * 4
    return weights + tables + spikes + outputs
