"""A conv SNN with shared filters, through the compiler, every engine and
the server.

The network is SLAYER-style notation (`compiler.ir.parse_conv_spec`),
small: `12x12x2-4c3-2a-8c3-2a-10o` (two 3x3 convs at SAME padding, two
2x2 sum-pools, a dense output).  The conv filters are seeded codebooks
of small words times a power of two, so every current is an exact f32
sum in any order, and the engines are held bit for bit to the
interpretive reference and to a plain `jax.numpy` conv SNN: output
counts and every counter exactly, energy and cycles to f64 summation
order (the reference sums per step, the engines per batch).
"""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compiler as CC
from repro.compiler.ir import parse_conv_spec
from repro.core.conv import ConvGeometry, ConvLayer
from repro.core.quant import CodebookConfig, QuantizedTensor
from repro.core.soc import ChipSimulator
from repro.telemetry.trace import TraceConfig

SPEC = "12x12x2-4c3-2a-8c3-2a-10o"
QCFG = CodebookConfig(n_levels=16, bit_width=8)
COUNTERS = ("nominal_sops", "performed_sops", "spikes_in", "spikes_routed",
            "neurons_touched", "noc_hops", "noc_contention_cycles")
PRICED = ("energy_pj", "core_energy_pj", "noc_energy_pj", "riscv_energy_pj",
          "wall_cycles")


def codebook(idx, words, scale):
    return QuantizedTensor(idx=jnp.asarray(idx, jnp.int8),
                           codebook=jnp.asarray(np.asarray(words)[None]
                                                * scale, jnp.float32),
                           scale=jnp.asarray([scale], jnp.float32),
                           group_axis_size=0)


def network(spec=SPEC, seed=0):
    """ConvLayers (seeded codebook filters; pools one word at the
    threshold, diagonal over channels) and a dense codebook output."""
    rng = np.random.default_rng(seed)
    words = np.concatenate([-np.arange(40, 0, -5), np.arange(5, 41, 5)])
    out = []
    for g in parse_conv_spec(spec)[1]:
        if isinstance(g, int):
            n_pre = out[-1].geometry.n_out
            out.append(codebook(rng.integers(0, 16, (n_pre, g)), words,
                                2.0 ** -5))
        elif g.pool:
            idx = np.zeros(g.filter_shape, np.int8)
            idx[:, :, np.arange(g.c_in), np.arange(g.c_in)] = 1
            out.append(ConvLayer(g, codebook(idx, [0, 1] + [0] * 14, 1.0)))
        else:
            out.append(ConvLayer(g, codebook(
                rng.integers(0, 16, g.filter_shape), words, 2.0 ** -5)))
    return out


def trains(n_in=288, batch=3, steps=4, density=0.15, seed=1):
    rng = np.random.default_rng(seed)
    return (rng.random((batch, steps, n_in)) < density).astype(np.float32)


def plain_conv_snn(layers, x, leak=0.9, threshold=1.0):
    """The network in plain `jax.numpy`, f32 at HIGHEST precision, one
    sample at a time: -> (counts, spikes in, performed SOPs, touched,
    nominal SOPs)."""
    hi = jax.lax.Precision.HIGHEST
    ws, geoms = [], []
    for layer in layers:
        q = layer.filter if isinstance(layer, ConvLayer) else layer
        ws.append(np.asarray(q.codebook)[0][np.asarray(q.idx)])
        geoms.append(layer.geometry if isinstance(layer, ConvLayer)
                     else None)

    def conv(s, w, g):
        img = jnp.asarray(s).reshape(1, g.height, g.width, g.c_in)
        out = jax.lax.conv_general_dilated(
            img, jnp.asarray(w), (g.stride, g.stride), g.pads(),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
        return np.asarray(out).reshape(-1)

    res = []
    for sample in x:
        v = [np.zeros(g.n_out if g else w.shape[1], np.float32)
             for w, g in zip(ws, geoms)]
        el = [np.zeros(len(a), np.int32) for a in v]
        counts = np.zeros(len(v[-1]))
        spikes_in = sops = touched_n = nominal = 0.0
        for s in sample:
            for li, (w, g) in enumerate(zip(ws, geoms)):
                nz = (w != 0).astype(np.float32)
                if g is None:
                    cur = np.asarray(jnp.matmul(s, w, precision=hi))
                    tcnt = s @ nz
                    sops += s.sum() * w.shape[1]
                    nominal += w.size
                else:
                    cur = conv(s, w, g)
                    tcnt = conv(s, nz, g)
                    sops += tcnt.sum()
                    nominal += conv(np.ones_like(s), nz, g).sum()
                spikes_in += s.sum()
                touched = tcnt > 0
                pending = el[li] + 1
                decay = np.where(touched, np.float32(leak) ** pending.astype(
                    np.float32), np.float32(1.0))
                v_int = v[li] * decay + cur
                spike = touched & (v_int >= np.float32(threshold))
                v[li] = np.where(spike, 0.0, np.where(touched, v_int, v[li])
                                 ).astype(np.float32)
                el[li] = np.where(touched, 0, pending)
                touched_n += touched.sum()
                s = spike.astype(np.float32)
            counts += s
        res.append((counts, spikes_in, sops, touched_n, nominal))
    return res


def test_slayer_spec_parses_to_its_populations():
    n_in, layers = parse_conv_spec("34x34x2-12c5-2a-64c5-2a-10o")
    net = CC.from_conv_spec("34x34x2-12c5-2a-64c5-2a-10o")
    assert n_in == 2312
    assert net.layer_sizes() == (2312, 13872, 3468, 18496, 4096, 10)
    assert net.total_neurons == 39942
    assert [l.kind for l in net.layers] == ["input"] + ["conv"] * 4 \
        + ["dense"]
    c1, p1, c2, p2, head = layers
    assert (c1.pad, c1.out_height, c2.pad, c2.out_height) == (2, 34, 2, 17)
    assert (p1.stride, p1.out_height, p2.out_height) == (2, 17, 8)
    assert p1.pool and not c1.pool and head == 10
    assert net.layers[3].fan_in == 5 * 5 * 12 and net.layers[2].fan_in == 4
    for bad in ("34x34-12c5-10o", "34x34x2-12c4-10o", "34x34x2-10o-12c5",
                "34x34x2-3z"):
        with pytest.raises(ValueError):
            parse_conv_spec(bad)


@pytest.mark.parametrize("engine", ["fused", "compiled"])
def test_engines_match_the_reference_and_a_plain_conv_snn(engine):
    layers = network()
    x = trains()
    ref = ChipSimulator(layers, quant_cfg=QCFG, engine="reference")
    sim = ChipSimulator(layers, quant_cfg=QCFG, engine=engine)
    ref_counts, ref_reports = ref.run_batch(x)
    counts, reports = sim.run_batch(x)
    assert np.array_equal(counts, ref_counts)
    assert np.asarray(counts).sum() > 0
    for a, b in zip(reports, ref_reports):
        for f in COUNTERS:
            assert getattr(a.stats, f) == getattr(b.stats, f), f
        for f in PRICED:   # f64 sums per step (reference), per batch
            np.testing.assert_allclose(getattr(a, f), getattr(b, f),
                                       rtol=1e-12, err_msg=f)
    for (c, spikes_in, sops, touched, nominal), r, got in zip(
            plain_conv_snn(layers, x), reports, np.asarray(counts)):
        assert np.array_equal(got, c)
        assert (r.stats.spikes_in, r.stats.performed_sops,
                r.stats.neurons_touched, r.stats.nominal_sops) == (
                    spikes_in, sops, touched, nominal)
    # every conv layer's performed SOPs: below nominal, above zero
    assert all(0 < r.stats.performed_sops < r.stats.nominal_sops
               for r in reports)


def test_engines_trace_the_same_per_core_counts():
    layers = network()
    x = trains(seed=4)
    traces = {}
    for engine in ("reference", "compiled", "fused"):
        sim = ChipSimulator(layers, quant_cfg=QCFG, engine=engine,
                            trace=TraceConfig(enabled=True))
        _, reports = sim.run_batch(x)
        traces[engine] = (sim.last_trace(), reports)
    ref, ref_reports = traces["reference"]
    assert ref.nnz_core is not None and ref.sops_core is not None
    for engine in ("compiled", "fused"):
        t, reports = traces[engine]
        for f in ("fired", "touched", "nnz", "nnz_core", "sops_core",
                  "cycles", "skip_words"):
            assert np.array_equal(getattr(t, f), getattr(ref, f)), (engine, f)
        assert np.array_equal(t.wall_cycles(),
                              [r.wall_cycles for r in reports])
    # a conv core's SOPs sum to the layer's; its band holds fewer spikes
    # than the layer's input where the band is not the whole map
    sim = ChipSimulator(layers, quant_cfg=QCFG, engine="reference")
    conv_slices = np.flatnonzero(np.isin(ref.slice_layer, list(sim.conv)))
    nnz_layer = ref.nnz[..., ref.slice_layer[conv_slices]]
    assert np.all(ref.nnz_core[..., conv_slices] <= nnz_layer)
    assert np.any(ref.nnz_core[..., conv_slices] < nnz_layer)


@pytest.mark.parametrize("engine", ["compiled", "fused"])
def test_snn_server_serves_what_run_batch_returns(engine):
    from repro.serve.snn_server import SnnRequest, SnnServer

    sim = ChipSimulator(network(), quant_cfg=QCFG, engine=engine)
    x = trains(batch=6, seed=8)
    counts, reports = sim.run_batch(x[:4])
    srv = SnnServer(sim, batch_slots=4)
    for uid, ev in enumerate(x):
        srv.submit(SnnRequest(uid=uid, events=ev))
    done = {r.uid: r for r in srv.run()}
    assert len(done) == 6
    for uid in range(4):
        assert done[uid].prediction == int(np.argmax(np.asarray(counts[uid])))
        assert done[uid].energy_pj == reports[uid].energy_pj
    for uid in (4, 5):
        c, r = sim.run(jnp.asarray(x[uid]))
        assert done[uid].prediction == int(np.argmax(np.asarray(c)))
        np.testing.assert_allclose(done[uid].energy_pj, r.energy_pj,
                                   rtol=1e-12)


@pytest.mark.parametrize("engine", ["compiled", "fused"])
def test_run_batch_span_counts_conv_layers(monkeypatch, engine):
    """The fused engine's call span counts the layers it runs on the
    conv kernel (`conv_layers`: the two convs and two pools); the
    compiled engine's has no such stat.  The NoC replay span's `flows`
    counts the replayed trees, one per source core."""
    spans = []

    class Span:
        def __init__(self, name, **stats):
            spans.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    sim = ChipSimulator(network(), quant_cfg=QCFG, engine=engine)
    sim.run_batch(trains())
    eng = sim.array_engine()
    (call,) = [s for n, s in spans if n == "snn.run_batch"]
    want = {"call": 1, "batch": 3, "steps": 4}
    if engine == "fused":
        want.update(word_layers=1, conv_layers=4)
    assert call == want
    (replay,) = [s for n, s in spans if n == "snn.noc_replay"]
    flows = sum(ft.n_flows for ft in eng.tables.flows if ft is not None)
    assert replay["flows"] == flows > 0


def _by_layer(groups):
    by_layer = {}
    for g in groups:
        by_layer.setdefault(g.layer, []).append(g)
    return by_layer


def test_conv_flows_reach_only_the_cores_whose_band_they_meet():
    net = CC.from_conv_spec("34x34x2-12c5-2a-64c5-2a-10o")
    compiled = CC.compile_network(net, verify=True)
    by_layer = _by_layer(compiled.groups)
    core = compiled.placement.assignment
    local = 0
    for layer, flows in compiled.routed.layer_flows.items():
        srcs, dsts = by_layer[layer], by_layer[layer + 1]
        geom = net.layers[layer + 1].conv
        for s, fr in zip(srcs, flows):
            want = sorted(core[d.gid] for d in dsts
                          if geom is None or geom.reaches(s.lo, s.hi, d.lo,
                                                          d.hi))
            assert fr.src == core[s.gid] and sorted(fr.dsts) == want
            local += len(want) < len(dsts)
    assert local > 0
    # the placement's flows follow the same connectivity
    flows = CC.group_traffic(net, compiled.groups)
    pairs = {(s, d) for s, d, _ in flows}
    groups = {g.gid: g for g in compiled.groups}
    for s, d in pairs:
        geom = net.layers[groups[d].layer].conv
        assert geom is None or geom.reaches(groups[s].lo, groups[s].hi,
                                            groups[d].lo, groups[d].hi)
    # and the simulator routes the same trees
    sim = ChipSimulator(network(), quant_cfg=QCFG, engine="reference")
    for li, routes in sim._layer_routes.items():
        geom = sim.conv.get(li)
        for s, r in zip(sim.mapping.cores_of_layer(li), routes):
            want = sorted(a.core_id for a in sim.mapping.cores_of_layer(
                li + 1) if geom is None or geom.reaches(
                    s.neuron_lo, s.neuron_hi, a.neuron_lo, a.neuron_hi))
            assert sorted(r.dsts) == want


def test_conv_band_and_synapses_by_hand():
    g = ConvGeometry(4, 4, 1, 2, 3, 1, 1)       # 4x4x1 -> 4x4x2, SAME
    assert g.n_out == 32
    # output rows 1..2 (neurons 8..23) read input rows 0..3
    assert g.band(8, 24) == (0, 16)
    assert g.band(0, 8) == (0, 8)               # row 0 reads rows 0..1
    assert g.band_words(0, 8) == (0, 1)
    # input rows 2..3 reach output row 3, not output row 0
    assert g.reaches(8, 16, 24, 32) and not g.reaches(8, 16, 0, 8)
    from repro.core.conv import synapses_per_neuron

    per = synapses_per_neuron(g, np.ones(g.filter_shape))
    # corner 4 taps, edge 6, inside 9; per channel
    assert per.reshape(4, 4, 2)[0, 0, 0] == 4
    assert per.reshape(4, 4, 2)[0, 1, 1] == 6
    assert per.reshape(4, 4, 2)[1, 1, 0] == 9
    assert per.sum() == 2 * (4 * 4 + 8 * 6 + 4 * 9)


@pytest.mark.parametrize("extra", ["sharded", "plasticity", "drop",
                                   "faults"])
def test_sharded_plastic_drop_and_faults_refuse_a_conv_layer(extra):
    from repro.core.plasticity import PlasticityConfig
    from repro.faults import FaultConfig

    layers = network()
    if extra == "sharded":
        sim = ChipSimulator(layers, quant_cfg=QCFG, engine="sharded")
        with pytest.raises(NotImplementedError,
                           match="weight layer 0 is a conv layer"):
            sim.run_batch(trains())
        return
    kw = {"plasticity": {"plasticity": PlasticityConfig(
              enabled=True, mode="stdp", lr=0.4)},
          "drop": {"faults": FaultConfig(drop_p=0.2, seed=1)},
          "faults": {"faults": FaultConfig(dead_cores=(12,), seed=1)},
          }[extra]
    with pytest.raises(NotImplementedError,
                       match="weight layer 0 is a conv layer"):
        ChipSimulator(layers, quant_cfg=QCFG, engine="compiled", **kw)


def test_a_conv_filter_must_be_quantized_and_fit_its_geometry():
    layers = network()
    g = layers[0].geometry
    with pytest.raises(TypeError, match="conv weight layer 0"):
        ChipSimulator([ConvLayer(g, np.zeros(g.filter_shape, np.float32))]
                      + layers[1:], quant_cfg=QCFG)
    wrong = ConvGeometry(12, 12, 2, 4, 5, 1, 2)
    with pytest.raises(ValueError, match="conv weight layer 0"):
        ChipSimulator([ConvLayer(wrong, layers[0].filter)] + layers[1:],
                      quant_cfg=QCFG)


def test_the_lowered_conv_operand_is_the_filter():
    """The fused engine holds a conv layer as its filter's words and one
    step; no constant of its program has a conv layer's (n_pre, n_post)
    shape."""
    sim = ChipSimulator(network(), quant_cfg=QCFG, engine="fused")
    eng = sim.fused_engine()
    assert eng.conv_layers == 4 and eng.codebook_layers == 5
    for li, g in sim.conv.items():
        lw = eng.fused_weights[li]
        assert lw.words.shape == g.filter_shape and lw.words.dtype == jnp.int8
        assert lw.step.shape == (1, 1)
    dense_shapes = {(g.n_in, g.n_out) for g in sim.conv.values()}
    jaxpr = jax.make_jaxpr(eng._build_run())(
        jax.ShapeDtypeStruct((3, 4, 288), jnp.float32))
    shapes = {tuple(c.shape) for c in jaxpr.consts if hasattr(c, "shape")}
    assert not shapes & dense_shapes
    assert "snn_conv_l1" in str(jaxpr) and "snn_fused_l5" in str(jaxpr)


def test_a_conv_filter_past_exact_word_sums_is_refused():
    from repro.core.engine import lower_conv_words

    g = ConvGeometry(4, 4, 1, 1, 3, 1, 1)
    sim = ChipSimulator([ConvLayer(g, codebook(
        np.zeros(g.filter_shape), [3] * 16, 0.1))], quant_cfg=QCFG,
        engine="compiled")
    with pytest.raises(ValueError, match="not int8 words x one step"):
        lower_conv_words(sim, 0)


def _program_digest(kind, engine):
    rng = np.random.default_rng(0)
    words = np.concatenate([-np.arange(40, 0, -5), np.arange(5, 41, 5)])
    shapes = ([(48, 64), (64, 32), (32, 10)] if kind == "chain"
              else [(48 + 64, 64), (64, 10)])
    sim = ChipSimulator([codebook(rng.integers(0, 16, s), words, 2.0 ** -5)
                         for s in shapes], quant_cfg=QCFG, engine=engine,
                        recurrent=() if kind == "chain" else (0,))
    text = str(jax.make_jaxpr(sim.array_engine()._build_run())(
        jax.ShapeDtypeStruct((4, 5, 48), jnp.float32)))
    return hashlib.sha256(text.encode()).hexdigest()


# the programs of a network without a conv layer, as the engines traced
# them before conv layers existed
PROGRAMS = {
    ("chain", "fused"):
        "31735e4c9cf4027dcb72a9056cd5abd8d86a51d8a4cffb54965733b587858dbc",
    ("chain", "compiled"):
        "d321f1c090933229541e690ebd2318eca6112ec62e1b5ec997947e29876e49c5",
    ("recurrent", "fused"):
        "eae54cea800d70104e18abc547770665964eb5ede2351f5d61dc28defd3cefcb",
    ("recurrent", "compiled"):
        "2388da95ea56d6c753992e2475c966c918277c21fb270beff2dd3f833bc6911f",
}


@pytest.mark.parametrize("kind,engine", sorted(PROGRAMS))
def test_programs_without_a_conv_layer_are_unchanged(kind, engine):
    assert _program_digest(kind, engine) == PROGRAMS[kind, engine]


def _compile_digest(net):
    c = CC.compile_network(net, verify=True)
    data = {
        "asg": sorted(c.placement.assignment.items()),
        "flows": {str(k): [(f.src, list(f.dsts), [list(x) for x in f.links],
                            f.hops, f.mode) for f in v]
                  for k, v in sorted(c.routed.layer_flows.items())},
        "tables": sorted((n, sorted((list(k), list(v)) for k, v in t.items()))
                         for n, t in c.routed.router_tables.tables.items()),
        "cost": c.cost}
    return hashlib.sha256(json.dumps(data).encode()).hexdigest()


# placements, routes and router tables of the benchmark's dense and
# recurrent networks, as compiled before conv layers existed
@pytest.mark.parametrize("net,want", [
    ((2312, 4096, 1024, 10),
     "2b169d5d9a4cb45b5355dd3803813dee988b868ecb1e581764a3457b8a2ec7d9"),
    (CC.from_layer_sizes([700, 1024, 20], recurrent=[1]),
     "df118818eccb48f171d6000deccf0b8d56c762f5d91e7d1d2bacb936bae96fe8"),
], ids=["nmnist_mlp", "shd_rsnn"])
def test_dense_and_recurrent_compiles_are_unchanged(net, want):
    assert _compile_digest(net) == want


def test_the_exact_leak_is_numpys_power_for_every_pending_count():
    """`decay_table` gives the correctly rounded f32 `leak**n`, as the
    benchmark's NumPy reference computes it, and `lif_step` with it
    decays a membrane untouched for n steps by exactly that."""
    from repro.core.neuron import LIFParams, LIFState, decay_table, lif_step

    n = np.arange(41, dtype=np.float32)
    table = decay_table(0.9, 40)
    assert np.array_equal(np.asarray(table, np.float32),
                          np.float32(0.9) ** n)
    v = np.full(40, 0.75, np.float32)
    state = LIFState(v=jnp.asarray(v), elapsed=jnp.arange(40, dtype=jnp.int32))
    st, _, _ = lif_step(state, jnp.zeros(40), LIFParams(leak=0.9),
                        touched=jnp.ones(40, bool), decays=table)
    assert np.array_equal(np.asarray(st.v), v * (np.float32(0.9) ** n[1:]))
