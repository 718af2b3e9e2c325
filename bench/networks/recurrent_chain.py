"""A chain whose recurrent layers feed back their own spikes.

`config["layer_sizes"]` gives the widths, input first, and
`config["recurrent"]` the populations (1 = the first hidden layer) that
also take their own spikes of the last step:

    I_t = W_in^T s_in[t] + W_rec^T s_h[t-1]          (s_h[-1] = 0)

A recurrent weight layer is one (n_pre + n_post, n_post) matrix, its
forward rows then its fed-back rows, under one codebook
(`workload.layer_levels` at that fan-in, so every synapse is nonzero)
with uniform 4-bit indices, as a core stores it.  It is one `Edge` over
the concatenated fan-in: its input spikes per step are the forward
ones and the fed-back ones, scanned as two 16-spike word streams.  Its
spikes travel one multicast tree per source core, in the step they fire,
to every core of the next layer and of its own (one `Flows`; the tree's
links are the mapping compiler's, its destinations are checked here).
`config["weight_gain"]` is one gain, or one per weight layer.
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


def recurrent_layers(config: dict) -> list[int]:
    """The weight layers that feed back (population p is weight layer
    p - 1)."""
    return sorted(int(p) - 1 for p in config.get("recurrent", []))


def fan_ins(config: dict) -> list[int]:
    widths, rec = sizes(config), recurrent_layers(config)
    return [widths[li] + (widths[li + 1] if li in rec else 0)
            for li in range(len(widths) - 1)]


def _layer_config(config: dict, li: int) -> dict:
    gain = config["weight_gain"]
    return dict(config, weight_gain=gain[li]) if isinstance(gain, list) \
        else config


def make(config: dict, seed: int):
    """-> (program weights: list of `quant.QuantizedTensor` on the device,
    reference weights: list of `workload.LayerCodebook` on the host)."""
    from repro.core.quant import QuantizedTensor

    shapes = tuple(zip(fan_ins(config), sizes(config)[1:]))
    idx_dev = workload.device_indices(seed, shapes,
                                      int(config["weight_levels"]))
    program, ref_layers = [], []
    for li, ((fan_in, _), idx) in enumerate(zip(shapes, idx_dev)):
        words, scale = workload.layer_levels(_layer_config(config, li), seed,
                                             li, fan_in)
        levels = words.astype(np.float32) * scale
        program.append(QuantizedTensor(
            idx=idx, codebook=jnp.asarray(levels[None, :]),
            scale=jnp.asarray([scale], jnp.float32), group_axis_size=0))
        ref_layers.append(workload.LayerCodebook(
            idx=np.asarray(idx), words=words, scale=scale, levels=levels))
    return program, ref_layers


def simulator(config: dict, traffic: dict, program_weights):
    """The program's `ChipSimulator` of the network on the traffic's
    engine, its recurrent layers fed back, its array engine lowered; on
    `fused`, every layer runs from the codebook."""
    from repro.core.quant import CodebookConfig
    from repro.core.soc import ChipSimulator

    sim = ChipSimulator(
        program_weights, quant_cfg=CodebookConfig(
            n_levels=int(config["weight_levels"]),
            bit_width=int(config["weight_bits"])),
        engine=traffic["engine"], leak=float(config["leak"]),
        threshold=float(config["threshold"]),
        freq_hz=float(config["freq_hz"]),
        recurrent=tuple(recurrent_layers(config)))
    engine = sim.array_engine()
    if traffic["engine"] == "fused" and \
            engine.codebook_layers != len(program_weights):
        raise RuntimeError(f"fused engine runs {engine.codebook_layers} of "
                           f"{len(program_weights)} layers from the codebook")
    return sim


def _routes(flows) -> list[dict]:
    return [{"src": int(r.src), "dsts": [int(d) for d in r.dsts],
             "links": [[int(u), int(v)] for u, v in r.links]} for r in flows]


def plan(sim, config: dict) -> dict:
    """The mapping compiler's placement and routes of `sim`, as plain
    data: per weight layer its core slices `[core, lo, hi]` and the
    flows its spikes travel, one per slice (`routes`, empty where it
    fires into no other layer), the recurrent weight layers, and the
    level-2 router nodes."""
    n_layers = len(sizes(config)) - 1
    return {
        "layers": [[[a.core_id, a.neuron_lo, a.neuron_hi]
                    for a in sim.mapping.cores_of_layer(li + 1)]
                   for li in range(n_layers)],
        "routes": [_routes(sim._layer_routes.get(li + 1, []))
                   for li in range(n_layers)],
        "recurrent": recurrent_layers(config),
        "level2_nodes": sorted(int(x) for x in sim._level2),
    }


def simulate(weights, trains, recurrent, *, leak: float, threshold: float,
             reset: float = 0.0, slices=None, matmul=np.matmul) -> dict:
    """(B, T, n_in) 0/1 trains through the network; weight layers in
    `recurrent` take their own last-step spikes after their forward
    input.

    Returns `counts` (B, n_out) output spike counts and the per-step
    per-layer counters `nnz` (every input spike), `fed` (the fed-back
    ones), `touched`, `fired`, `skip` (B, T, L).  With `slices` (per
    layer, the [lo, hi) neuron ranges of its core slices) also
    `touched_slices` and `fired_slices`: per layer (B, T, A).  `matmul`
    computes a layer's currents.
    """
    trains = np.asarray(trains, np.float32)
    B, T, _ = trains.shape
    nzw = [None if np.all(w != 0) else (w != 0).astype(np.float32)
           for w in weights]
    v = [np.zeros((B, w.shape[1]), np.float32) for w in weights]
    elapsed = [np.zeros((B, w.shape[1]), np.int32) for w in weights]
    last = {li: np.zeros((B, weights[li].shape[1]), np.float32)
            for li in recurrent}
    steps = {k: [] for k in ("nnz", "fed", "touched", "fired", "skip")}
    per_slice = {k: [[] for _ in weights] for k in ("touched_slices",
                                                    "fired_slices")}
    counts = np.zeros((B, weights[-1].shape[1]), np.float64)
    for t in range(T):
        s = trains[:, t, :]
        for li, w in enumerate(weights):
            streams = [s, last[li]] if li in last else [s]
            x = np.concatenate(streams, axis=-1)
            nnz = (x != 0).sum(-1)
            steps["nnz"].append(nnz)
            steps["fed"].append((streams[-1] != 0).sum(-1) if li in last
                                else np.zeros(B, np.int64))
            steps["skip"].append(sum(shared.empty_words(z) for z in streams))
            current = matmul(x, w)
            touched = shared.touched_neurons(x, nnz, nzw[li], w.shape[1],
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
            if li in last:
                last[li] = s
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
    `layer_sizes[i + 1]` over its whole fan-in; a layer sends its spikes
    over its routes, each to every core of the next layer and, if it is
    recurrent, of its own."""
    widths = sizes(config)
    edges = [shared.Edge(
        nnz=out["nnz"][:, :, li], skip=out["skip"][:, :, li],
        n_pre=fan_in, fan_out=widths[li + 1], slices=layer,
        touched=out["touched_slices"][li])
        for li, (fan_in, layer) in enumerate(zip(fan_ins(config),
                                                 plan["layers"]))]
    layers, flows = plan["layers"], []
    for li, (layer, routes) in enumerate(zip(layers, plan["routes"])):
        reached = layers[li + 1:li + 2] + (
            [layer] if li in plan["recurrent"] else [])
        want = sorted({c for cores in reached for c, _, _ in cores})
        if any(sorted(r["dsts"]) != want for r in routes) or \
                bool(routes) != bool(want):
            raise ValueError(f"layer {li}: its routes do not reach exactly "
                             f"the cores {want}")
        if routes:
            flows.append(shared.Flows(fired=out["fired_slices"][li],
                                      routes=routes,
                                      srcs=[c for c, _, _ in layer]))
    return shared.chip_report(edges, flows,
                              level2_nodes=plan["level2_nodes"],
                              freq_hz=float(config["freq_hz"]))


def reference(ref_layers, trains: np.ndarray, config: dict, plan: dict,
              *, control: bool = False, block: int = 32
              ) -> tuple[np.ndarray, np.ndarray]:
    """Reference (or control) over `trains` in blocks of rows; `plan` is
    `plan`'s data.  Returns (counts (N, n_out), fields (N, len(FIELDS)))."""
    weights = [lc.dense() for lc in ref_layers]
    if control:
        weights = [shared.high_precision_weights(w) for w in weights]
    slices = [[(lo, hi) for _, lo, hi in layer] for layer in plan["layers"]]
    counts, fields = [], []
    for lo in range(0, len(trains), block):
        out = simulate(weights, trains[lo:lo + block], plan["recurrent"],
                       leak=float(config["leak"]),
                       threshold=float(config["threshold"]),
                       reset=float(config.get("reset", 0.0)), slices=slices)
        counts.append(out["counts"])
        fields.append(sample_fields(out, config, plan))
    return np.concatenate(counts), np.concatenate(fields)


def layer_least_bytes(config: dict, batch: int, li: int) -> float:
    """The least bytes of weight layer `li` in one batch: its weights
    once at log2(N) bits, its level table (N words of W bits), and its
    input spikes, forward and fed back, at 1 bit."""
    n_levels, wbits = int(config["weight_levels"]), int(config["weight_bits"])
    fan_in, n_post = fan_ins(config)[li], sizes(config)[li + 1]
    return (fan_in * n_post * math.ceil(math.log2(n_levels)) / 8
            + n_levels * wbits / 8
            + batch * int(config["timesteps"]) * fan_in / 8)


def least_bytes(config: dict, batch: int) -> float:
    """Every weight once at log2(N) bits (a recurrent layer's fed-back
    rows too; no padding row is a weight), one level table per layer (N
    words of W bits), the input spikes at 1 bit, the output counts as
    int32."""
    widths = sizes(config)
    n_levels, wbits = int(config["weight_levels"]), int(config["weight_bits"])
    idx_bits = math.ceil(math.log2(n_levels))
    weights = sum(f * n for f, n in zip(fan_ins(config), widths[1:])) \
        * idx_bits / 8
    tables = (len(widths) - 1) * n_levels * wbits / 8
    spikes = batch * int(config["timesteps"]) * widths[0] / 8
    outputs = batch * widths[-1] * 4
    return weights + tables + spikes + outputs
