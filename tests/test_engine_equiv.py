"""Differential tests: the batched array engines (compiled scan/vmap and
fused Pallas-kernel) vs the interpretive reference simulator.

Each array engine (core/engine.py) must be a *drop-in* for the
reference loop: spikes bit-identical, SOP/flit/energy accounting within
1e-6 relative, across dense and conv-shaped networks, single- and
multi-domain mappings, quantized and fp32 weights, batch 1 and batch 8.
The fused engine is additionally held to a *stronger* contract vs the
compiled engine — bit-exact equality of spikes AND accounting (its
kernel runs the identical float program) — and its ZSPE spike-word skip
telemetry is checked against a numpy popcount oracle.  Engine invariants
(batched == stacked, zero input, placement permutation) are
property-tested via tests/hypothesis_compat.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.quant import CodebookConfig
from repro.core.soc import ChipSimulator, CoreAssignment, Mapping

REL_TOL = 1e-6

STAT_FIELDS = ("nominal_sops", "performed_sops", "spikes_in",
               "spikes_routed", "neurons_touched", "noc_hops",
               "noc_energy_pj", "noc_contention_cycles")
REPORT_FIELDS = ("energy_pj", "core_energy_pj", "noc_energy_pj",
                 "riscv_energy_pj", "wall_cycles")

ENGINES = ("compiled", "fused")


def make_weights(rng, sizes, scale=0.5):
    return [jnp.asarray(rng.normal(0, scale, (sizes[i], sizes[i + 1])),
                        jnp.float32)
            for i in range(len(sizes) - 1)]


def make_trains(rng, batch, timesteps, n_in, density=0.25):
    return jnp.asarray(rng.random((batch, timesteps, n_in)) < density,
                       jnp.float32)


def sim_pair(weights, mapping=None, quant_cfg=None, engine="compiled", **kw):
    """Reference + array-engine simulators sharing one mapping."""
    ref = ChipSimulator(weights, engine="reference", mapping=mapping,
                        quant_cfg=quant_cfg, **kw)
    comp = ChipSimulator(weights, engine=engine, mapping=ref.mapping,
                         quant_cfg=quant_cfg, **kw)
    return ref, comp


def assert_equivalent(ref, comp, trains):
    counts_c, reps_c = comp.run_batch(trains)
    for b in range(int(trains.shape[0])):
        counts_r, rep_r = ref.run_reference(trains[b])
        np.testing.assert_array_equal(
            np.asarray(counts_c[b]), np.asarray(counts_r),
            err_msg=f"sample {b}: compiled spikes differ from reference")
        for f in STAT_FIELDS:
            a = getattr(rep_r.stats, f)
            c = getattr(reps_c[b].stats, f)
            assert abs(a - c) <= REL_TOL * max(abs(a), 1.0), (b, f, a, c)
        for f in REPORT_FIELDS:
            a = getattr(rep_r, f)
            c = getattr(reps_c[b], f)
            assert abs(a - c) <= REL_TOL * max(abs(a), 1.0), (b, f, a, c)


def conv_shaped_sizes():
    """im2col'd layer sizes of a small spiking conv net."""
    from repro import compiler as COMP
    from repro.models.snn_conv import ConvSNNConfig

    cfg = ConvSNNConfig(in_shape=(8, 8, 2), channels=(4, 8), n_classes=10)
    return COMP.from_conv_config(cfg).layer_sizes()


def multi_domain_mapping(sizes):
    """Force a >20-core mapping so it spans two level-1 domains."""
    from repro import compiler as COMP

    spec = COMP.ChipSpec(neurons_per_core=8, max_domains=2)
    compiled = COMP.compile_network(list(sizes), spec)
    mapping = compiled.to_soc_mapping()
    assert compiled.n_domains_used >= 2, "case must exercise scale-up"
    return mapping


# ---------------------------------------------------------------------------
# randomized differential cases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_dense_fp32_matches_reference(seed, batch, engine):
    rng = np.random.default_rng(seed)
    n_hidden = int(rng.integers(32, 128))
    sizes = (int(rng.integers(16, 64)), n_hidden, 10)
    w = make_weights(rng, sizes)
    ref, comp = sim_pair(w, mapping_strategy="greedy", engine=engine)
    assert_equivalent(ref, comp, make_trains(rng, batch, 10, sizes[0]))


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("batch", [1, 8])
def test_dense_quantized_matches_reference(batch, engine):
    rng = np.random.default_rng(7)
    sizes = (48, 96, 32, 10)
    w = make_weights(rng, sizes, scale=0.1)
    ref, comp = sim_pair(w, quant_cfg=CodebookConfig(n_levels=16, bit_width=8),
                         engine=engine)
    if engine == "fused":
        # the registers are programmed -> every layer must run compressed
        fe = comp.fused_engine()
        assert fe.codebook_layers == len(w)
    assert_equivalent(ref, comp, make_trains(rng, batch, 12, sizes[0]))


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("batch", [1, 8])
def test_conv_shaped_matches_reference(batch, engine):
    rng = np.random.default_rng(11)
    sizes = conv_shaped_sizes()
    w = make_weights(rng, sizes, scale=0.15)
    ref, comp = sim_pair(w, engine=engine)
    assert_equivalent(ref, comp, make_trains(rng, batch, 6, sizes[0],
                                             density=0.15))


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("batch", [1, 8])
def test_multi_domain_matches_reference(batch, engine):
    rng = np.random.default_rng(23)
    sizes = (16, 128, 64)
    mapping = multi_domain_mapping(sizes)
    w = make_weights(rng, sizes)
    ref, comp = sim_pair(w, mapping=mapping, engine=engine)
    assert ref.interconnect is not None        # level-2 pricing active
    assert_equivalent(ref, comp, make_trains(rng, batch, 8, sizes[0],
                                             density=0.3))


@pytest.mark.parametrize("engine", ENGINES)
def test_baseline_scheme_matches_reference(engine):
    """No zero-skip / full MP update (the paper's 'traditional' baseline)."""
    rng = np.random.default_rng(3)
    sizes = (32, 64, 10)
    w = make_weights(rng, sizes)
    ref, comp = sim_pair(w, zero_skip=False, partial_update=False,
                         engine=engine)
    assert_equivalent(ref, comp, make_trains(rng, 2, 8, sizes[0]))


def test_run_dispatches_by_engine():
    rng = np.random.default_rng(4)
    w = make_weights(rng, (24, 32, 10))
    train = make_trains(rng, 1, 6, 24)[0]
    ref, comp = sim_pair(w)
    counts_r, rep_r = ref.run(train)           # reference path via run()
    for engine in ENGINES:
        sim = ChipSimulator(w, engine=engine, mapping=ref.mapping)
        counts_c, rep_c = sim.run(train)       # array single-sample path
        np.testing.assert_array_equal(np.asarray(counts_c),
                                      np.asarray(counts_r))
        assert (abs(rep_c.energy_pj - rep_r.energy_pj)
                <= REL_TOL * rep_r.energy_pj)
    with pytest.raises(ValueError):
        ChipSimulator(w, engine="warp-drive")


# ---------------------------------------------------------------------------
# engine invariants (property tests)
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 1000), batch=st.integers(2, 5))
def test_batched_equals_stacked_per_sample(seed, batch):
    """vmap over a batch == the same samples run one at a time."""
    rng = np.random.default_rng(seed)
    sizes = (24, 48, 10)
    w = make_weights(rng, sizes)
    sim = ChipSimulator(w, engine="compiled", mapping_strategy="greedy")
    trains = make_trains(rng, batch, 8, sizes[0])
    counts_b, reps_b = sim.run_batch(trains)
    for b in range(batch):
        counts_1, rep_1 = sim.run(trains[b])
        np.testing.assert_array_equal(np.asarray(counts_b[b]),
                                      np.asarray(counts_1))
        assert reps_b[b].energy_pj == rep_1.energy_pj
        assert reps_b[b].stats.performed_sops == rep_1.stats.performed_sops
        assert reps_b[b].wall_cycles == rep_1.wall_cycles


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 1000))
def test_zero_input_leak_only(seed):
    """All-zero spike trains: no SOPs performed, no flits routed, energy
    is leak/pipeline-only (core at sparsity 1 + RISC-V), never zero."""
    rng = np.random.default_rng(seed)
    sizes = (16, int(rng.integers(24, 64)), 10)
    w = make_weights(rng, sizes)
    sim = ChipSimulator(w, engine="compiled", mapping_strategy="greedy")
    counts, reps = sim.run_batch(jnp.zeros((2, 6, sizes[0]), jnp.float32))
    assert float(jnp.abs(counts).max()) == 0.0
    for rep in reps:
        assert rep.stats.performed_sops == 0.0
        assert rep.stats.spikes_in == 0.0
        assert rep.stats.noc_hops == 0.0
        assert rep.stats.spikes_routed == 0.0
        assert rep.noc_energy_pj == 0.0
        assert rep.stats.sparsity == 1.0
        assert rep.energy_pj > 0.0
        np.testing.assert_allclose(
            rep.energy_pj, rep.core_energy_pj + rep.riscv_energy_pj,
            rtol=1e-12)


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 1000))
def test_total_sops_permutation_invariant(seed):
    """Total SOPs depend on the network + spikes, not on which physical
    core each slice landed on."""
    rng = np.random.default_rng(seed)
    sizes = (24, 96, 10)
    w = make_weights(rng, sizes)
    base = ChipSimulator(w, engine="compiled", mapping_strategy="greedy")
    active = base.mapping.active_core_ids()
    perm = dict(zip(active, rng.permutation(active)))
    permuted = Mapping(
        assignments=[CoreAssignment(core_id=int(perm[a.core_id]),
                                    layer=a.layer, neuron_lo=a.neuron_lo,
                                    neuron_hi=a.neuron_hi)
                     for a in base.mapping.assignments],
        layer_sizes=list(base.mapping.layer_sizes))
    shuf = ChipSimulator(w, engine="compiled", mapping=permuted)
    trains = make_trains(rng, 2, 6, sizes[0])
    _, reps_a = base.run_batch(trains)
    _, reps_b = shuf.run_batch(trains)
    for ra, rb in zip(reps_a, reps_b):
        assert ra.stats.nominal_sops == rb.stats.nominal_sops
        assert ra.stats.performed_sops == rb.stats.performed_sops
        assert ra.stats.neurons_touched == rb.stats.neurons_touched


# ---------------------------------------------------------------------------
# fused engine: stronger contracts than the compiled/reference pair
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", [False, True])
def test_fused_bitexact_vs_compiled(quant):
    """Fused vs compiled is not a tolerance comparison: with word-aligned
    layer widths (every n_pre a multiple of 16, so spike packing adds no
    K padding) the fused kernel runs the identical float program, and
    spikes AND every accounting field must be exactly equal."""
    rng = np.random.default_rng(17)
    sizes = (48, 80, 32, 10)
    w = make_weights(rng, sizes, scale=0.2)
    qcfg = CodebookConfig(n_levels=16, bit_width=8) if quant else None
    comp = ChipSimulator(w, engine="compiled", quant_cfg=qcfg)
    fus = ChipSimulator(w, engine="fused", mapping=comp.mapping,
                        quant_cfg=qcfg)
    trains = make_trains(rng, 4, 10, sizes[0])
    counts_c, reps_c = comp.run_batch(trains)
    counts_f, reps_f = fus.run_batch(trains)
    np.testing.assert_array_equal(np.asarray(counts_f), np.asarray(counts_c))
    for rc, rf in zip(reps_c, reps_f):
        for f in STAT_FIELDS:
            assert getattr(rf.stats, f) == getattr(rc.stats, f), f
        for f in REPORT_FIELDS:
            assert getattr(rf, f) == getattr(rc, f), f


def test_fused_skip_words_match_popcount_oracle():
    """The fused engine's ZSPE skip telemetry == an exact numpy popcount:
    for every (sample, step), the number of all-zero 16-spike words in
    the layer's input."""
    from repro.core.zspe import SPIKE_WORD_BITS

    rng = np.random.default_rng(29)
    n_in, n_out = 70, 12                        # 70 spikes -> 5 words/step
    w = make_weights(rng, (n_in, n_out))
    sim = ChipSimulator(w, engine="fused", mapping_strategy="greedy")
    trains = make_trains(rng, 3, 9, n_in, density=0.05)
    ys = sim.fused_engine().run_raw(trains)
    skip = np.asarray(ys["skip_words"])         # (B, T, L=1)
    assert skip.shape == (3, 9, 1)

    t_np = np.asarray(trains)                   # exact word-level oracle
    n_words = -(-n_in // SPIKE_WORD_BITS)
    padded = np.zeros((3, 9, n_words * SPIKE_WORD_BITS), np.float32)
    padded[:, :, :n_in] = t_np
    words = padded.reshape(3, 9, n_words, SPIKE_WORD_BITS)
    expected = (words.sum(-1) == 0).sum(-1)     # empty words per (b, t)
    assert expected.sum() > 0, "case must exercise the word-skip path"
    assert expected.sum() < 3 * 9 * n_words, "case must also do work"
    np.testing.assert_array_equal(skip[:, :, 0], expected)

    # the per-report aggregate is the plain sum of the telemetry
    _, reps = sim.run_batch(trains)
    for b, rep in enumerate(reps):
        assert rep.stats.spike_words_skipped == expected[b].sum()


def test_fused_per_core_register_tables_run_compressed():
    """Deploy-style per-core PTQ: every layer must lower to codebook mode
    (RegisterTable words consumed in-register) and match the reference."""
    from repro.core.soc import map_network
    from repro.deploy import fit_per_core_codebooks
    from repro.models import snn as SNN
    from repro.models.snn import SNNConfig

    cfg = SNNConfig(layer_sizes=(64, 48, 10), timesteps=6)
    params = SNN.init_params(cfg, jax.random.PRNGKey(0))
    mapping = map_network(list(cfg.layer_sizes), strategy="anneal")
    pq = fit_per_core_codebooks(params, mapping, CodebookConfig(16, 8))

    ref = ChipSimulator(pq.weights, engine="reference", mapping=mapping,
                        register_tables=pq.tables)
    fus = ChipSimulator(pq.weights, engine="fused", mapping=mapping,
                        register_tables=pq.tables)
    fe = fus.fused_engine()
    assert fe.codebook_layers == len(pq.weights)
    # codebook operands are int8 indexes: materially fewer weight HBM
    # bytes even at this toy size (the asymptotic >= 4x — f32 vs int8,
    # level table amortized over large K — is asserted at NMNIST scale
    # by benchmarks/engine_bench.py)
    dense_bytes = sum(lw.n_pre * lw.n_post * 4 for lw in fe.fused_weights)
    fused_w_bytes = sum(
        lw.idx.size * 1 + lw.cbw.size * 4 for lw in fe.fused_weights)
    assert dense_bytes / fused_w_bytes >= 1.9
    rng = np.random.default_rng(5)
    assert_equivalent(ref, fus, make_trains(rng, 4, 6, 64, density=0.2))


def _bench_kind_sim(kind, sizes, engine, mapping=None):
    """A network of one of the benchmark's kinds (`bench/networks`), at
    small widths: codebook weights of 8-bit words times a step of 11
    significant bits, drawn from a seed above 2**32."""
    import pathlib
    import sys

    root = str(pathlib.Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import registry

    config = {"layer_sizes": list(sizes), "timesteps": 8, "threshold": 1.0,
              "leak": 0.9, "reset": 0.0, "weight_levels": 16,
              "weight_bits": 8, "freq_hz": 1e8, "weight_gain": 3.0,
              "scale_mantissa_bits": 11}
    if kind == "recurrent_chain":
        config.update(recurrent=[1], weight_gain=[2.0, 3.0])
    net = registry.load_module("networks", kind)
    program, _ = net.make(config, 4294967311)
    if mapping is None:
        return net.simulator(config, {"engine": engine}, program)
    return ChipSimulator(program, engine=engine, mapping=mapping,
                         quant_cfg=CodebookConfig(16, 8), leak=0.9,
                         threshold=1.0, freq_hz=1e8,
                         recurrent=(0,) if kind == "recurrent_chain" else ())


@pytest.mark.parametrize("kind,sizes", [
    ("dense_chain", (50, 64, 32, 10)), ("recurrent_chain", (50, 64, 10)),
    ("quantized", (48, 80, 32, 10))], ids=["nmnist", "shd", "quantized"])
def test_fused_word_layout_gate(kind, sizes):
    """Every layer of the benchmark's kinds (tables of exact 8-bit words
    x an 11-bit step) lowers to the word layout, and the run equals the
    compiled engine's exactly; a `quantize()`d network's steps carry a
    full f32 mantissa, so it keeps the select-decode layout."""
    n_layers = len(sizes) - 1
    if kind == "quantized":
        w = make_weights(np.random.default_rng(17), sizes, scale=0.2)
        fus = ChipSimulator(w, engine="fused",
                            quant_cfg=CodebookConfig(16, 8))
        comp = ChipSimulator(w, engine="compiled", mapping=fus.mapping,
                             quant_cfg=CodebookConfig(16, 8))
    else:
        fus = _bench_kind_sim(kind, sizes, "fused")
        comp = _bench_kind_sim(kind, sizes, "compiled", mapping=fus.mapping)
    fe = fus.fused_engine()
    assert fe.codebook_layers == n_layers
    words = 0 if kind == "quantized" else n_layers
    assert fe.word_layers == words
    assert sum(lw.idx is not None for lw in fe.fused_weights) \
        == n_layers - words
    for lw in fe.fused_weights:
        held, table = (lw.words, lw.step) if lw.word_layout \
            else (lw.idx, lw.cbw)
        assert held.dtype == jnp.int8
        assert held.shape == (lw.kw * 16, lw.n_post)
        # HBM bytes a step: the int8 slab, its step row or level table,
        # the uint16 spike words
        assert lw.hbm_bytes_per_step(4) \
            == held.size + 4 * table.size + 4 * lw.kw * 2
    trains = make_trains(np.random.default_rng(3), 4, 8, sizes[0],
                         density=0.2)
    counts_c, reps_c = comp.run_batch(trains)
    counts_f, reps_f = fus.run_batch(trains)
    assert np.asarray(counts_c).sum() > 0
    np.testing.assert_array_equal(np.asarray(counts_f), np.asarray(counts_c))
    for rc, rf in zip(reps_c, reps_f):
        for f in STAT_FIELDS:
            assert getattr(rf.stats, f) == getattr(rc.stats, f), f
        for f in REPORT_FIELDS:
            assert getattr(rf, f) == getattr(rc, f), f


def test_fused_word_layout_needs_int8_words():
    """Tables of 16-bit words (a power-of-two step, so every level is
    exact) do not fit the int8 word slab: the select decode keeps them."""
    from repro.core.quant import QuantizedTensor

    rng = np.random.default_rng(2)
    words = np.concatenate([-np.arange(1000, 200, -100),
                            np.arange(300, 1100, 100)])
    w = [QuantizedTensor(
        idx=jnp.asarray(rng.integers(0, 16, (a, b)), jnp.int8),
        codebook=jnp.asarray(words[None] * 2.0 ** -12, jnp.float32),
        scale=jnp.asarray([2.0 ** -12], jnp.float32), group_axis_size=0)
        for a, b in ((32, 16), (16, 8))]
    fe = ChipSimulator(w, engine="fused",
                       quant_cfg=CodebookConfig(16, 16)).fused_engine()
    assert (fe.codebook_layers, fe.word_layers) == (2, 0)


def test_fused_shard_map_multi_device():
    """With >= 2 devices and a divisible batch the fused engine runs the
    program through shard_map and still matches the reference exactly."""
    if len(jax.devices()) < 2:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=2")
    rng = np.random.default_rng(31)
    sizes = (48, 64, 10)
    w = make_weights(rng, sizes)
    ref = ChipSimulator(w, engine="reference")
    fus = ChipSimulator(w, engine="fused", mapping=ref.mapping)
    trains = make_trains(rng, 4, 8, sizes[0])
    counts, reps = fus.run_batch(trains)
    assert fus.fused_engine().last_run_sharded
    for b in range(4):
        counts_r, rep_r = ref.run_reference(trains[b])
        np.testing.assert_array_equal(np.asarray(counts[b]),
                                      np.asarray(counts_r))
        assert (abs(reps[b].energy_pj - rep_r.energy_pj)
                <= REL_TOL * rep_r.energy_pj)
    # a batch that does not divide the device count falls back cleanly
    counts3, _ = fus.run_batch(trains[:3])
    assert not fus.fused_engine().last_run_sharded
    np.testing.assert_array_equal(np.asarray(counts3),
                                  np.asarray(counts[:3]))


def test_fused_run_batch_makes_no_host_transfer():
    """A fused `run_batch` on trains already on the device is one program
    launch: the zero membrane state and the spike packing are built in
    the program, so nothing crosses from the host to the device, and the
    answer equals an unguarded run's."""
    rng = np.random.default_rng(43)
    w = make_weights(rng, (48, 32, 10))
    trains = jax.device_put(make_trains(rng, 8, 6, 48))
    guarded = ChipSimulator(w, engine="fused")
    free = ChipSimulator(w, engine="fused", mapping=guarded.mapping)
    with jax.transfer_guard_host_to_device("disallow"):
        counts_g, reps_g = guarded.run_batch(trains)
    counts_f, reps_f = free.run_batch(trains)
    np.testing.assert_array_equal(np.asarray(counts_g), np.asarray(counts_f))
    assert reps_g == reps_f


def test_fused_engine_block_selection():
    """Interpret mode runs one exact tile (the bit-exact config); the
    real-TPU path picks tiles Mosaic accepts (bm = m or a multiple of 16
    rows, bn = n or a multiple of 128 lanes) whose whole kernel footprint
    fits the VMEM budget, and refuses a layer no tile fits."""
    from repro.core.engine import _pick_engine_block
    from repro.kernels.fused_timestep import VMEM_BUDGET_BYTES, vmem_bytes

    assert _pick_engine_block(32, 2320, 512, interpret=True) is None
    for m, k, n in [(32, 8192, 8192), (12, 2320, 4096), (3, 16, 509),
                    (8, 1024, 10), (256, 4096, 1024)]:
        for layout in ("codebook", "words"):
            bm, bn = _pick_engine_block(m, k, n, interpret=False,
                                        layout=layout)
            assert m % bm == 0 and (bm == m or bm % 16 == 0)
            assert n % bn == 0 and (bn == n or bn % 128 == 0)
            assert vmem_bytes(bm, bn, k // 16, layout=layout) \
                <= VMEM_BUDGET_BYTES
    assert _pick_engine_block(12, 2320, 4096, interpret=False)[0] == 12
    assert _pick_engine_block(3, 16, 509, interpret=False) == (3, 509)
    with pytest.raises(ValueError, match="VMEM"):
        _pick_engine_block(8, 1 << 17, 128, interpret=False)


def test_fused_rejects_soft_reset():
    from repro.core.neuron import LIFParams

    rng = np.random.default_rng(2)
    w = make_weights(rng, (16, 8))
    sim = ChipSimulator(w, engine="fused", lif=LIFParams(reset_mode="soft"))
    with pytest.raises(ValueError, match="hard reset"):
        sim.fused_engine()


# ---------------------------------------------------------------------------
# source-exact NoC accounting (PR 5 tentpole)
# ---------------------------------------------------------------------------

def test_noc_accounting_is_source_exact():
    """Two firing patterns with EQUAL total fired spikes but different
    source cores must price differently — the uniform-split heuristic
    could not tell them apart.  All three engines must agree per pattern.
    The probe network is shared with benchmarks/contention_bench.py via
    repro.core.probes."""
    from repro.core.probes import source_exact_patterns, source_exact_probe

    sim_c, srcs, dst = source_exact_probe("compiled")
    sim_r, *_ = source_exact_probe("reference")
    sim_f, *_ = source_exact_probe("fused")
    near_tr, far_tr, (near_hops, far_hops) = source_exact_patterns(
        sim_c, srcs, dst)
    assert near_hops != far_hops
    reports = []
    for tr in (near_tr, far_tr):
        assert_equivalent(sim_r, sim_c, tr)   # reference vs compiled
        assert_equivalent(sim_r, sim_f, tr)   # reference vs fused
        _, [rep_c] = sim_c.run_batch(tr)
        reports.append(rep_c)
    assert reports[0].stats.spikes_routed == reports[1].stats.spikes_routed
    # ...but the near-core pattern is strictly cheaper on the NoC
    assert reports[0].stats.noc_energy_pj < reports[1].stats.noc_energy_pj
    assert reports[0].stats.noc_hops < reports[1].stats.noc_hops


@pytest.mark.parametrize("engine", ENGINES + ("reference",))
def test_zero_spike_batches_are_finite(engine):
    """All-padding (zero) batches through run_batch on every engine: all
    counters zero, and no NaN/inf anywhere in the derived report fields."""
    rng = np.random.default_rng(41)
    w = make_weights(rng, (32, 48, 10))
    sim = ChipSimulator(w, engine=engine, mapping_strategy="greedy")
    counts, reps = sim.run_batch(jnp.zeros((3, 5, 32), jnp.float32))
    assert float(jnp.abs(counts).max()) == 0.0
    for rep in reps:
        s = rep.stats
        assert s.performed_sops == 0.0 and s.spikes_in == 0.0
        assert s.spikes_routed == 0.0 and s.noc_hops == 0.0
        assert s.noc_energy_pj == 0.0 and s.noc_contention_cycles == 0.0
        for val in (rep.pj_per_sop, rep.power_mw, s.sparsity,
                    rep.energy_pj, rep.wall_cycles, rep.gsops):
            assert np.isfinite(val), (engine, val)
        assert s.sparsity == 1.0


def test_step_stats_sparsity_zero_nominal():
    """A default-constructed (or zero-input) StepStats reports sparsity
    1.0 instead of raising ZeroDivisionError — same convention as
    energy.price_batched."""
    from repro.core.soc import StepStats

    assert StepStats().sparsity == 1.0
    assert StepStats(nominal_sops=0.0, performed_sops=0.0).sparsity == 1.0
    assert StepStats(nominal_sops=10.0, performed_sops=5.0).sparsity == 0.5


# ---------------------------------------------------------------------------
# array-native NoC replay agrees with the interpretive replay
# ---------------------------------------------------------------------------

def test_flow_table_matches_replay_flows():
    """`compile_flow_table` + `replay_flows_array` == `replay_flows` for
    uniform per-flow spike counts (hops, energy, cycles), with and
    without the level-2 interconnect pricing."""
    from repro.core import energy as E
    from repro.core import noc as NOC

    rng = np.random.default_rng(5)
    rt = NOC.RoutingTable(NOC.fullerene_adjacency())
    flows = NOC.uniform_random_flows(rng, 40, bcast_frac=0.4)
    routes = [NOC.compile_flow(rt, src, dsts) for src, dsts, _ in flows]
    params = NOC.RouterParams()
    for interconnect in (None, E.InterconnectEnergyModel.from_router(params)):
        for n_spikes in (1, 7):
            ref = NOC.replay_flows([(r, n_spikes) for r in routes], params,
                                   interconnect=interconnect)
            table = NOC.compile_flow_table(routes, params,
                                           interconnect=interconnect)
            hops, energy, cycles = NOC.replay_flows_array(
                table, n_spikes, params)
            assert hops == ref.total_hops
            np.testing.assert_allclose(energy, ref.energy_pj, rtol=1e-12)
            np.testing.assert_allclose(cycles, ref.cycles, rtol=1e-12)
            assert int(table.dst_fanout.sum()) * n_spikes == ref.spikes_delivered


def test_replay_flows_exact_matches_replay_flows():
    """Per-flow exact replay (the engines' path) == the interpretive
    `replay_flows` on identical per-flow spike counts, including the
    router-load vector that feeds the contention model."""
    from repro.core import energy as E
    from repro.core import noc as NOC

    rng = np.random.default_rng(9)
    rt = NOC.RoutingTable(NOC.fullerene_adjacency())
    flows = NOC.uniform_random_flows(rng, 30, bcast_frac=0.3)
    routes = [NOC.compile_flow(rt, src, dsts) for src, dsts, _ in flows]
    counts = rng.integers(0, 12, size=len(routes))
    params = NOC.RouterParams()
    for interconnect in (None, E.InterconnectEnergyModel.from_router(params)):
        table = NOC.compile_flow_table(routes, params,
                                       interconnect=interconnect)
        np.testing.assert_array_equal(
            table.src_core, [r.src for r in routes])
        ref = NOC.replay_flows(
            [(r, int(c)) for r, c in zip(routes, counts)], params,
            interconnect=interconnect)
        hops, energy, load = NOC.replay_flows_exact(table, counts)
        assert hops == ref.total_hops
        np.testing.assert_allclose(energy, ref.energy_pj, rtol=1e-12)
        np.testing.assert_array_equal(load, ref.router_load)
        # batched leading axes broadcast through
        h2, e2, l2 = NOC.replay_flows_exact(
            table, np.stack([counts, 2 * counts]))
        assert h2.shape == (2,) and l2.shape == (2, NOC.N_NODES)
        np.testing.assert_allclose(h2[0], hops)
        np.testing.assert_allclose(e2[1], 2 * energy, rtol=1e-12)


def test_contention_cycles_model():
    """Zero spikes cost zero; light load approaches pure serialization;
    the term grows superlinearly with the bottleneck load."""
    from repro.core import noc as NOC

    p = NOC.RouterParams()
    assert float(NOC.contention_cycles(0.0, 100.0, p)) == 0.0
    light = float(NOC.contention_cycles(1.0, 1e6, p))
    np.testing.assert_allclose(light, 1.0 / p.peak_throughput, rtol=1e-3)
    c1 = float(NOC.contention_cycles(100.0, 50.0, p))
    c2 = float(NOC.contention_cycles(200.0, 50.0, p))
    assert c2 > 2 * c1                       # superlinear in load
    arr = NOC.contention_cycles(np.array([[0.0, 10.0], [20.0, 40.0]]),
                                np.full((2, 2), 64.0), p)
    assert arr.shape == (2, 2) and arr[0, 0] == 0.0
    assert np.all(np.diff(arr.ravel()) > 0)


def test_fullerene_saturates_after_mesh():
    """Acceptance: the fullerene fabric sustains a higher injection rate
    before bottleneck-router saturation than the 4x8 mesh (and the mesh
    beats the tree)."""
    from repro.core import noc as NOC

    full = NOC.saturation_injection_rate(NOC.fullerene_adjacency(),
                                         NOC.core_ids())
    mesh = NOC.saturation_injection_rate(NOC.mesh_2d(4, 8), np.arange(32))
    tree = NOC.saturation_injection_rate(NOC.tree(32, 2), np.arange(32))
    assert full > mesh > tree


# ---------------------------------------------------------------------------
# serving path rides the batched engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_snn_server_batches_requests(engine):
    from repro.serve.snn_server import SnnRequest, SnnServer

    rng = np.random.default_rng(0)
    sizes = (32, 64, 10)
    w = make_weights(rng, sizes)
    sim = ChipSimulator(w, engine=engine, mapping_strategy="greedy")
    srv = SnnServer(sim, batch_slots=4)
    events = [np.asarray(rng.random((8, 32)) < 0.3, np.float32)
              for _ in range(6)]
    for uid, ev in enumerate(events):
        srv.submit(SnnRequest(uid=uid, events=ev))
    done = srv.run()
    assert len(done) == 6
    for r in done:
        assert 0 <= r.prediction < 10
        assert r.energy_pj > 0
        # per-request telemetry matches a direct single-sample run
        counts, rep = sim.run(jnp.asarray(r.events))
        assert int(np.argmax(np.asarray(counts))) == r.prediction
        np.testing.assert_allclose(r.energy_pj, rep.energy_pj, rtol=1e-12)

    with pytest.raises(ValueError):
        SnnServer(ChipSimulator(w, engine="reference"), batch_slots=2)


def test_snn_server_partial_group_no_padded_telemetry():
    """A partial group (fewer requests than batch_slots) pads the batch
    with all-zero trains; the padded slots' telemetry must never reach a
    real request, and the queue must drain per group in one pass."""
    from repro.serve.snn_server import SnnRequest, SnnServer

    rng = np.random.default_rng(3)
    sizes = (24, 40, 10)
    w = make_weights(rng, sizes)
    sim = ChipSimulator(w, engine="compiled", mapping_strategy="greedy")
    srv = SnnServer(sim, batch_slots=4)
    events = [np.asarray(rng.random((7, 24)) < 0.4, np.float32)
              for _ in range(5)]                      # group of 4 + 1 partial
    for uid, ev in enumerate(events):
        srv.submit(SnnRequest(uid=uid, events=ev))
    done = srv.run()
    assert len(done) == 5 and srv.queue == []
    # what a padded (all-zero) slot would report
    _, [pad_rep] = sim.run_batch(jnp.zeros((1, 7, 24), jnp.float32))
    for r in done:
        counts, rep = sim.run(jnp.asarray(r.events))  # ground truth per uid
        np.testing.assert_allclose(r.energy_pj, rep.energy_pj, rtol=1e-12)
        np.testing.assert_allclose(r.pj_per_sop, rep.pj_per_sop, rtol=1e-12)
        assert r.prediction == int(np.argmax(np.asarray(counts)))
        # real requests fire spikes here; a padded-slot leak would hand
        # them the zero-input report instead
        assert r.energy_pj != pad_rep.energy_pj
