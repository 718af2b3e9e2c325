"""A convolutional chain with shared filters, in SLAYER's notation.

`config["spec"]` names the network (Shrestha & Orchard, NeurIPS 2018),
e.g. `34x34x2-12c5-2a-64c5-2a-10o`: the input map HxWxC, then `Nck` a
k x k conv to N channels at stride 1 (`config["padding"]`: "same", k // 2
on each side, or "valid"), `ka` a spiking k x k sum-pool of stride k and
no padding, and `No` a dense layer of N neurons, last.  Maps are
flattened in HWC order.

Weights, from the seed:

* a conv layer: one codebook over its (k, k, C_in, C_out) filter
  (`workload.layer_levels` at fan-in k*k*C_in, 8 +/- pairs, every tap
  nonzero) with uniform 4-bit indices;
* a pool: a filter diagonal over channels whose every tap is one word,
  1 x a step of `threshold`, so that one input spike fires its neuron
  (its membrane is never negative); its table holds that word and
  zeros;
* the dense output: one codebook, as `dense_chain`'s layers.

`config["weight_gain"]` is one gain, or one per weight layer (a pool's
is unused).

Each core holds a contiguous slice of a layer's neurons (the mapping
compiler's); a conv or pool core scans its **input band**, the input
rows that its neurons' receptive fields cover, and the reference prices
it per core: input spikes in the band, the band's empty 16-spike words
(over the global words of the input that the band spans, all of which
its ZSPE scans), SOPs = its neurons' touch counts (spikes reaching them
through nonzero taps) summed, and touched neurons.  A core's cycles are
its slowest stage, band words, ceil(SOPs / 4) or touched neurons, plus
the pipeline's fill.  A dense core is priced as `bench/reference.py`
prices it.  A layer's spikes travel one multicast tree per source core,
to the cores of the next layer whose band meets the source slice (all of
them, before a dense layer); the trees' links are the mapping
compiler's, their destinations are checked here.

Currents are computed in f64 by shifted-tap sums and rounded once to
f32, so they are the correctly rounded f32 of the exact current; the
control rounds the weights to what a 3-pass bf16 product keeps first.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from bench import reference as shared
from bench import workload


def geometry(config: dict) -> tuple[int, list[dict]]:
    """(input width, weight layers): each a dict of its shape, `kind`
    "conv", "pool" or "dense"."""
    head, *tokens = config["spec"].split("-")
    h, w, c = (int(v) for v in head.split("x"))
    n_in, same = h * w * c, config.get("padding", "same") == "same"
    layers = []
    for tok in tokens:
        if tok.endswith("o"):
            layers.append({"kind": "dense", "n_pre": h * w * c,
                           "n_post": int(tok[:-1])})
            continue
        if "c" in tok:
            n, k = (int(v) for v in tok.split("c"))
            g = {"kind": "conv", "k": k, "s": 1, "pad": k // 2 if same else 0,
                 "c_out": n}
        else:
            k = int(tok[:-1])
            g = {"kind": "pool", "k": k, "s": k, "pad": 0, "c_out": c}
        g.update(h=h, w=w, c_in=c)
        g["ho"] = (h + 2 * g["pad"] - k) // g["s"] + 1
        g["wo"] = (w + 2 * g["pad"] - k) // g["s"] + 1
        g["n_pre"], g["n_post"] = h * w * c, g["ho"] * g["wo"] * g["c_out"]
        layers.append(g)
        h, w, c = g["ho"], g["wo"], g["c_out"]
    return n_in, layers


def n_in(config: dict) -> int:
    return geometry(config)[0]


def conv_layers(config: dict) -> list[int]:
    """The weight layers that run as convolutions (convs and pools)."""
    return [li for li, g in enumerate(geometry(config)[1])
            if g["kind"] != "dense"]


def _filter_shape(g: dict) -> tuple[int, int, int, int]:
    return (g["k"], g["k"], g["c_in"], g["c_out"])


def _fan_in(g: dict) -> int:
    return g["n_pre"] if g["kind"] == "dense" else g["k"] ** 2 * g["c_in"]


def _layer_config(config: dict, li: int) -> dict:
    gain = config["weight_gain"]
    return dict(config, weight_gain=gain[li]) if isinstance(gain, list) \
        else config


def _pool_codebook(config: dict, g: dict) -> workload.LayerCodebook:
    n_levels = int(config["weight_levels"])
    words = np.zeros(n_levels, np.int32)
    words[1] = 1
    scale = np.float32(config["threshold"])
    idx = np.zeros(_filter_shape(g), np.int8)
    idx[:, :, np.arange(g["c_in"]), np.arange(g["c_in"])] = 1
    return workload.LayerCodebook(idx=idx, words=words, scale=scale,
                                  levels=words.astype(np.float32) * scale)


def make(config: dict, seed: int):
    """-> (program weights: per layer a `conv.ConvLayer` (conv, pool) or
    a `quant.QuantizedTensor` (dense), on the device; reference weights:
    list of `workload.LayerCodebook` on the host, a filter's `idx` of
    shape (k, k, C_in, C_out))."""
    from repro.compiler.ir import parse_conv_spec
    from repro.core.conv import ConvLayer
    from repro.core.quant import QuantizedTensor

    _, geoms = geometry(config)
    program_geoms = parse_conv_spec(config["spec"],
                                    config.get("padding", "same"))[1]
    drawn = [li for li, g in enumerate(geoms) if g["kind"] != "pool"]
    shapes = tuple(_filter_shape(geoms[li]) if geoms[li]["kind"] == "conv"
                   else (geoms[li]["n_pre"], geoms[li]["n_post"])
                   for li in drawn)
    idx_dev = dict(zip(drawn, workload.device_indices(
        seed, shapes, int(config["weight_levels"]))))
    program, ref_layers = [], []
    for li, g in enumerate(geoms):
        if g["kind"] == "pool":
            lc = _pool_codebook(config, g)
            idx = jnp.asarray(lc.idx)
        else:
            words, scale = workload.layer_levels(_layer_config(config, li),
                                                 seed, li, _fan_in(g))
            lc = workload.LayerCodebook(
                idx=np.asarray(idx_dev[li]), words=words, scale=scale,
                levels=words.astype(np.float32) * scale)
            idx = idx_dev[li]
        q = QuantizedTensor(idx=idx, codebook=jnp.asarray(lc.levels[None, :]),
                            scale=jnp.asarray([lc.scale], jnp.float32),
                            group_axis_size=0)
        pg = program_geoms[li]
        if g["kind"] == "dense":
            if pg != g["n_post"]:
                raise ValueError(f"layer {li}: the program reads {pg}")
            program.append(q)
        else:
            if (pg.n_in, pg.n_out, pg.pool) != (g["n_pre"], g["n_post"],
                                                g["kind"] == "pool"):
                raise ValueError(f"layer {li}: the program reads {pg}")
            program.append(ConvLayer(pg, q))
        ref_layers.append(lc)
    return program, ref_layers


def simulator(config: dict, traffic: dict, program_weights):
    """The program's `ChipSimulator` on the traffic's engine, its array
    engine lowered; on `fused`, every conv and pool runs on the conv
    kernel and every layer from the codebook."""
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
    if traffic["engine"] == "fused" and (
            engine.codebook_layers != len(program_weights)
            or engine.conv_layers != len(conv_layers(config))):
        raise RuntimeError(f"fused engine runs {engine.codebook_layers} of "
                           f"{len(program_weights)} layers from the codebook "
                           f"and {engine.conv_layers} on the conv kernel")
    return sim


def plan(sim, config: dict) -> dict:
    """The mapping compiler's placement and routes of `sim`, as plain
    data: per weight layer its core slices `[core, lo, hi]`, per layer
    that fires into another one flow per slice (`src`, `dsts`,
    `links`), and the level-2 router nodes."""
    n_layers = len(geometry(config)[1])
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


def band(g: dict, lo: int, hi: int) -> tuple[int, int]:
    """The input band [start, stop) of a conv layer's neurons [lo, hi):
    the input rows their receptive fields cover, whole."""
    row = g["wo"] * g["c_out"]
    r0 = max(0, (lo // row) * g["s"] - g["pad"])
    r1 = min(g["h"] - 1, ((hi - 1) // row) * g["s"] - g["pad"] + g["k"] - 1)
    return r0 * g["w"] * g["c_in"], (r1 + 1) * g["w"] * g["c_in"]


def _taps(x: np.ndarray, g: dict):
    """(B, n_in) -> each filter tap's (dy, dx, (B, Ho, Wo, C_in)) view
    of the zero-padded input map."""
    k, s, p = g["k"], g["s"], g["pad"]
    img = x.reshape(len(x), g["h"], g["w"], g["c_in"])
    hp = max((g["ho"] - 1) * s + k, g["h"] + p)
    wp = max((g["wo"] - 1) * s + k, g["w"] + p)
    pad = np.zeros((len(x), hp, wp, g["c_in"]), x.dtype)
    pad[:, p:p + g["h"], p:p + g["w"]] = img
    for dy in range(k):
        for dx in range(k):
            yield dy, dx, pad[:, dy:dy + (g["ho"] - 1) * s + 1:s,
                              dx:dx + (g["wo"] - 1) * s + 1:s]


def conv(x: np.ndarray, w: np.ndarray, g: dict) -> np.ndarray:
    """(B, n_in) through the (k, k, C_in, C_out) filter `w`, in f64, by
    shifted-tap sums -> (B, n_out) f64 (HWC)."""
    out = np.zeros((len(x), g["ho"], g["wo"], g["c_out"]))
    w = np.asarray(w, np.float64)
    for dy, dx, tap in _taps(np.asarray(x, np.float64), g):
        out += tap @ w[dy, dx]
    return out.reshape(len(x), -1)


def synapses(g: dict, nonzero: np.ndarray) -> np.ndarray:
    """(n_out,): the nonzero taps into each neuron inside the map."""
    return conv(np.ones((1, g["n_pre"])), nonzero, g)[0]


def simulate(weights, trains, geoms, slices, *, leak: float,
             threshold: float, reset: float = 0.0) -> dict:
    """(B, T, n_in) 0/1 trains through the network; `slices` per layer
    the [lo, hi) of its core slices.

    Returns `counts` (B, n_out) and, per layer (B, T, A): `fired`,
    `touched`, `nnz` (input spikes per core: a conv core's in its band,
    a dense core's all), `sops` (performed SOPs per core), `skip` (empty
    words per core: a conv core's in its band); and per layer (B, T):
    `layer_nnz` and `layer_skip` (the dense layer's one scan)."""
    trains = np.asarray(trains, np.float32)
    B, T, _ = trains.shape
    nz = [(np.asarray(w) != 0).astype(np.float64) for w in weights]
    v = [np.zeros((B, g["n_post"]), np.float32) for g in geoms]
    elapsed = [np.zeros((B, g["n_post"]), np.int32) for g in geoms]
    keys = ("fired", "touched", "nnz", "sops", "skip")
    per = {k: [[] for _ in geoms] for k in keys}
    layer_nnz = [[] for _ in geoms]
    counts = np.zeros((B, geoms[-1]["n_post"]), np.float64)
    word = shared.SPIKE_WORD_BITS
    for t in range(T):
        s = trains[:, t, :]
        for li, (w, g) in enumerate(zip(weights, geoms)):
            nnz = (s != 0).sum(-1)
            layer_nnz[li].append(nnz)
            if g["kind"] == "dense":
                current = (s.astype(np.float64)
                           @ np.asarray(w, np.float64)).astype(np.float32)
                tcnt = s.astype(np.float64) @ nz[li]
                bands = [(0, g["n_pre"])] * len(slices[li])
            else:
                current = conv(s, w, g).astype(np.float32)
                tcnt = conv(s, nz[li], g)
                bands = [band(g, lo, hi) for lo, hi in slices[li]]
            touched = tcnt > 0
            v[li], elapsed[li], spike = shared.lif_step(
                v[li], elapsed[li], current, touched, leak=leak,
                threshold=threshold, reset=reset)
            padded = np.pad(s != 0, ((0, 0), (0, -len(s[0]) % word)))
            empty = ~padded.reshape(B, -1, word).any(-1)
            rows = {"fired": [], "touched": [], "nnz": [], "sops": [],
                    "skip": []}
            for (lo, hi), (a, b) in zip(slices[li], bands):
                rows["fired"].append(spike[:, lo:hi].sum(-1))
                rows["touched"].append(touched[:, lo:hi].sum(-1))
                rows["nnz"].append((s[:, a:b] != 0).sum(-1))
                # a dense core's SPEs take every input spike's synapses
                # into its slice, a conv core's its neurons' touch counts
                rows["sops"].append(nnz * (hi - lo) if g["kind"] == "dense"
                                    else tcnt[:, lo:hi].sum(-1))
                rows["skip"].append(empty[:, a // word:-(-b // word)]
                                    .sum(-1))
            for k in keys:
                per[k][li].append(np.stack(rows[k], -1))
            s = spike.astype(np.float32)
        counts += s
    out = {k: [np.stack(x, 1).astype(np.float64) for x in per[k]]
           for k in keys}
    out["layer_nnz"] = [np.stack(x, 1).astype(np.float64)
                        for x in layer_nnz]
    out["counts"] = counts
    return out


def sample_fields(out: dict, weights, geoms, config: dict, plan: dict
                  ) -> np.ndarray:
    """Per-sample `ChipReport` (B, len(FIELDS)) from `simulate`'s output
    and the plan: per core cycles and the layers' flows, checked against
    the connectivity the geometry gives."""
    layers = plan["layers"]
    B, T = out["counts"].shape[0], out["nnz"][0].shape[1]
    lanes, depth = shared._SPE_LANES, shared._PIPELINE_DEPTH
    cores = sorted({c for layer in layers for c, _, _ in layer})
    core_cycles = np.zeros((B, T, len(cores)))
    nominal = 0.0
    skipped = np.zeros(B)
    for li, (g, layer) in enumerate(zip(geoms, layers)):
        if g["kind"] == "dense":
            words = np.full(len(layer), -(-g["n_pre"] // shared.SPIKE_WORD_BITS))
            nominal += g["n_pre"] * g["n_post"]
            # a dense layer's one scan of its whole input
            skipped += out["skip"][li][:, :, 0].sum(axis=1)
        else:
            per = synapses(g, np.asarray(weights[li]) != 0)
            nominal += per.sum()
            words = np.array([
                -(-b // shared.SPIKE_WORD_BITS) - a // shared.SPIKE_WORD_BITS
                for a, b in (band(g, lo, hi) for _, lo, hi in layer)])
            skipped += out["skip"][li].sum(axis=(1, 2))
        cyc = np.maximum(np.maximum(words, np.ceil(out["sops"][li] / lanes)),
                         out["touched"][li]) + depth
        for a, (core, _, _) in enumerate(layer):
            core_cycles[:, :, cores.index(core)] += cyc[:, :, a]
    core_wall = core_cycles.max(axis=-1)
    flows = []
    for li, routes in enumerate(plan["routes"]):
        g = geoms[li + 1]
        for (_, lo, hi), r in zip(layers[li], routes):
            want = sorted(c for c, a, b in layers[li + 1]
                          if g["kind"] == "dense"
                          or (lo < band(g, a, b)[1] and band(g, a, b)[0] < hi))
            if sorted(r["dsts"]) != want:
                raise ValueError(f"layer {li}: a route from core {r['src']} "
                                 f"does not reach exactly the cores {want}")
        flows.append(shared.Flows(fired=out["fired"][li], routes=routes,
                                  srcs=[c for c, _, _ in layers[li]]))
    nodes = [int(x) for f in flows for r in f.routes
             for link in r["links"] for x in link]
    hops, noc_pj, router = shared.noc_traffic(
        flows, plan["level2_nodes"], 1 + max(cores + nodes), (B, T))
    contention = shared.contention_cycles(router, core_wall)
    wall = (core_wall + contention).sum(axis=1)
    performed = sum(x.sum(axis=(1, 2)) for x in out["sops"])
    nominal *= T
    core_pj = shared.core_pj_per_nominal_sop(performed / nominal) * nominal
    riscv_pj = shared.riscv_energy_pj(wall, T, float(config["freq_hz"]))
    cols = {
        "spikes_in": sum(x.sum(axis=1) for x in out["layer_nnz"]),
        "performed_sops": performed,
        "neurons_touched": sum(x.sum(axis=(1, 2)) for x in out["touched"]),
        "spikes_routed": sum(f.fired.sum(axis=(1, 2)) for f in flows),
        "nominal_sops": np.full(B, nominal),
        "spike_words_skipped": skipped,
        "noc_hops": hops,
        "core_energy_pj": core_pj,
        "noc_energy_pj": noc_pj,
        "riscv_energy_pj": riscv_pj,
        "energy_pj": core_pj + noc_pj + riscv_pj,
        "wall_cycles": wall,
        "noc_contention_cycles": contention.sum(axis=1),
    }
    return np.stack([cols[f] for f in shared.FIELDS], axis=-1)


def reference(ref_layers, trains: np.ndarray, config: dict, plan: dict,
              *, control: bool = False, block: int = 32
              ) -> tuple[np.ndarray, np.ndarray]:
    """Reference (or control) over `trains` in blocks of rows; `plan` is
    `plan`'s data.  Returns (counts (N, n_out), fields (N, len(FIELDS)))."""
    _, geoms = geometry(config)
    weights = [lc.dense() for lc in ref_layers]
    if control:
        weights = [shared.high_precision_weights(w) for w in weights]
    slices = [[(lo, hi) for _, lo, hi in layer] for layer in plan["layers"]]
    counts, fields = [], []
    for lo in range(0, len(trains), block):
        out = simulate(weights, trains[lo:lo + block], geoms, slices,
                       leak=float(config["leak"]),
                       threshold=float(config["threshold"]),
                       reset=float(config.get("reset", 0.0)))
        counts.append(out["counts"])
        fields.append(sample_fields(out, weights, geoms, config, plan))
    return np.concatenate(counts), np.concatenate(fields)


def _weight_bytes(config: dict, g: dict) -> float:
    """One layer's distinct weights once at log2(N) bits: a filter's
    entries (a pool's diagonal taps: its only synapse words), a dense
    layer's matrix; and its level table (N words of W bits)."""
    n_levels, wbits = int(config["weight_levels"]), int(config["weight_bits"])
    idx_bits = math.ceil(math.log2(n_levels))
    if g["kind"] == "dense":
        n = g["n_pre"] * g["n_post"]
    elif g["kind"] == "conv":
        n = math.prod(_filter_shape(g))
    else:
        n = g["k"] ** 2 * g["c_in"]
    return n * idx_bits / 8 + n_levels * wbits / 8


def layer_least_bytes(config: dict, batch: int, li: int) -> float:
    """The least bytes of weight layer `li` in one batch: its weights
    and table (`_weight_bytes`) and its input spikes at 1 bit."""
    g = geometry(config)[1][li]
    return (_weight_bytes(config, g)
            + batch * int(config["timesteps"]) * g["n_pre"] / 8)


def least_bytes(config: dict, batch: int) -> float:
    """Every distinct weight once at log2(N) bits (a shared filter's
    words once, not once per position), one level table per layer, the
    input spikes at 1 bit, the output counts as int32."""
    width, geoms = geometry(config)
    weights = sum(_weight_bytes(config, g) for g in geoms)
    spikes = batch * int(config["timesteps"]) * width / 8
    outputs = batch * geoms[-1]["n_post"] * 4
    return weights + spikes + outputs
