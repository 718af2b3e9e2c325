"""A recurrent hidden layer, through every engine and the server.

Weight layer 0 of these networks feeds back its own spikes of the last
step (`ChipSimulator(..., recurrent=(0,))`): its weight is one
(n_in + n_hidden, n_hidden) matrix.  The weights are exact small
multiples of a power of two, so every current is an exact f32 sum in
any order, and the engines are held to the interpretive reference:
spikes and every counter exactly; energy and cycles to f64 summation
order (the reference sums per step, the engines per batch).  The fused
engine is held to the compiled one exactly, and both to the benchmark
kind's independent NumPy reference (`bench/networks/recurrent_chain.py`)
within the benchmark's own limits.  The hidden width 64 is word-aligned
(the fed-back rows start on a 16-spike word in both layouts); 50 inputs
and 60 hidden neurons are not.
"""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import CodebookConfig, QuantizedTensor
from repro.core.soc import ChipSimulator

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

QCFG = CodebookConfig(n_levels=16, bit_width=8)
SHAPES = {"aligned": (48, 64, 10), "unaligned": (50, 60, 10)}
COUNTERS = ("nominal_sops", "performed_sops", "spikes_in", "spikes_routed",
            "neurons_touched", "noc_hops", "recurrent_sops", "back_noc_hops")
PRICED = ("energy_pj", "core_energy_pj", "noc_energy_pj", "riscv_energy_pj",
          "wall_cycles")


def codebook(rng, n_pre, n_post, scale):
    """Codebook weights: 8 +/- pairs of small words times a power of two,
    uniform 4-bit indices (every synapse nonzero)."""
    words = np.concatenate([-np.arange(8, 0, -1), np.arange(1, 9)])
    return QuantizedTensor(
        idx=jnp.asarray(rng.integers(0, 16, (n_pre, n_post)), jnp.int8),
        codebook=jnp.asarray(words[None] * scale, jnp.float32),
        scale=jnp.asarray([scale], jnp.float32), group_axis_size=0)


def network(shape, seed=0):
    n_in, n_h, n_out = SHAPES[shape]
    rng = np.random.default_rng(seed)
    return [codebook(rng, n_in + n_h, n_h, 2.0 ** -5),
            codebook(rng, n_h, n_out, 2.0 ** -4)]


def trains(n_in, batch=4, steps=8, density=0.3, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.random((batch, steps, n_in)) < density,
                       jnp.float32)


def sims(weights, engine, **kw):
    ref = ChipSimulator(weights, quant_cfg=QCFG, engine="reference",
                        recurrent=(0,), **kw)
    return ref, ChipSimulator(weights, quant_cfg=QCFG, engine=engine,
                              recurrent=(0,), mapping=ref.mapping, **kw)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("engine", ["compiled", "fused"])
def test_engine_matches_reference(engine, shape):
    weights = network(shape)
    ref, sim = sims(weights, engine)
    x = trains(SHAPES[shape][0])
    counts, reports = sim.run_batch(x)
    assert sim.n_in == SHAPES[shape][0]
    for b in range(x.shape[0]):
        want_counts, want = ref.run_reference(x[b])
        np.testing.assert_array_equal(np.asarray(counts[b]),
                                      np.asarray(want_counts))
        for f in COUNTERS:
            assert getattr(reports[b].stats, f) == getattr(want.stats, f), f
        for f in PRICED + ("noc_contention_cycles",):
            got = getattr(reports[b], f, None)
            got = getattr(reports[b].stats, f) if got is None else got
            exp = getattr(want, f, None)
            exp = getattr(want.stats, f) if exp is None else exp
            assert got == pytest.approx(exp, rel=1e-12, abs=0), f
    # the back-edge carries traffic, and the fed-back spikes do work
    assert all(r.stats.recurrent_sops > 0 for r in reports)
    assert all(0 < r.stats.back_noc_hops < r.stats.noc_hops for r in reports)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_fused_equals_compiled(shape):
    weights = network(shape)
    fused = ChipSimulator(weights, quant_cfg=QCFG, engine="fused",
                          recurrent=(0,))
    comp = ChipSimulator(weights, quant_cfg=QCFG, engine="compiled",
                         recurrent=(0,), mapping=fused.mapping)
    assert fused.fused_engine().codebook_layers == 2
    x = trains(SHAPES[shape][0], batch=8, steps=10, seed=4)
    c_f, r_f = fused.run_batch(x)
    c_c, r_c = comp.run_batch(x)
    np.testing.assert_array_equal(np.asarray(c_f), np.asarray(c_c))
    for a, b in zip(r_f, r_c):
        for f in COUNTERS + ("noc_energy_pj", "noc_contention_cycles"):
            assert getattr(a.stats, f) == getattr(b.stats, f), f
        for f in PRICED:
            assert getattr(a, f) == getattr(b, f), f


@pytest.mark.parametrize("engine", ["compiled", "fused"])
def test_matches_the_benchmark_kinds_reference(engine):
    """The program against `recurrent_chain`'s NumPy reference, which
    imports nothing of it, on the kind's own weights and the compiler's
    anneal mapping (the hidden layer over many cores, so the back-edge
    broadcasts)."""
    from bench import check, registry

    kind = registry.load_module("networks", "recurrent_chain")
    config = {"layer_sizes": [50, 64, 10], "recurrent": [1],
              "timesteps": 12, "threshold": 1.0, "leak": 0.9, "reset": 0.0,
              "weight_levels": 16, "weight_bits": 8, "freq_hz": 1e8,
              "weight_gain": [2.0, 3.0], "scale_mantissa_bits": 11}
    program, layers = kind.make(config, 4294967311)
    sim = kind.simulator(config, {"engine": engine}, program)
    assert len(sim.mapping.cores_of_layer(1)) > 4
    plan = kind.plan(sim, config)
    x = np.asarray(trains(50, batch=6, steps=12, density=0.2, seed=7))
    counts, reports = sim.run_batch(x)
    ref_counts, ref_fields = kind.reference(layers, x, config, plan)
    numbers = check.compare_trains(counts, check.report_fields(reports),
                                   ref_counts, ref_fields,
                                   compare_skips=engine == "fused")
    assert numbers["differing_trains"] == 0
    assert numbers["wall_rel_gap"] <= 1e-12
    assert numbers["energy_rel_gap"] <= 1e-12
    assert ref_counts.sum() > 0


@pytest.mark.parametrize("engine", ["reference", "compiled", "fused"])
def test_zero_recurrent_weights_equal_the_chain(engine):
    """W_rec = 0: the spikes are the chain's; the fed-back spikes still
    arrive, so only their input spikes, SOPs and the trees' back-edge
    hops add."""
    rng = np.random.default_rng(3)
    n_in, n_h, n_out = 48, 64, 10
    w_in = (rng.integers(-8, 9, (n_in, n_h)) * 2.0 ** -5).astype(np.float32)
    w_out = (rng.integers(-8, 9, (n_h, n_out)) * 2.0 ** -4
             ).astype(np.float32)
    w_rec = np.concatenate([w_in, np.zeros((n_h, n_h), np.float32)])
    chain = ChipSimulator([w_in, w_out], engine=engine)
    rec = ChipSimulator([w_rec, w_out], engine=engine, recurrent=(0,),
                        mapping=chain.mapping)
    x = trains(n_in, batch=3, steps=9, seed=5)
    c_chain, r_chain = chain.run_batch(x)
    c_rec, r_rec = rec.run_batch(x)
    np.testing.assert_array_equal(np.asarray(c_rec), np.asarray(c_chain))
    for a, b in zip(r_rec, r_chain):
        fed = a.stats.recurrent_sops / n_h
        assert fed > 0
        assert a.stats.spikes_in - fed == b.stats.spikes_in
        assert a.stats.performed_sops - a.stats.recurrent_sops \
            == b.stats.performed_sops
        assert a.stats.neurons_touched == b.stats.neurons_touched
        # one tree per source core: each hidden spike is routed once
        assert a.stats.spikes_routed == b.stats.spikes_routed
        assert a.stats.noc_hops - a.stats.back_noc_hops == b.stats.noc_hops


@pytest.mark.parametrize("engine", ["compiled", "fused"])
def test_snn_server_serves_what_run_batch_returns(engine):
    from repro.serve.snn_server import SnnRequest, SnnServer

    weights = network("unaligned")
    sim = ChipSimulator(weights, quant_cfg=QCFG, engine=engine,
                        recurrent=(0,))
    x = np.asarray(trains(50, batch=6, steps=8, seed=8))
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


def test_sharded_engine_refuses_a_recurrent_network():
    sim = ChipSimulator(network("aligned"), quant_cfg=QCFG, engine="sharded",
                        recurrent=(0,))
    with pytest.raises(NotImplementedError, match="chains only"):
        sim.run_batch(trains(48))


@pytest.mark.parametrize("extra", ["plasticity", "drop"])
def test_plasticity_and_packet_drop_refuse_a_recurrent_network(extra):
    from repro.core.plasticity import PlasticityConfig
    from repro.faults import FaultConfig

    kw = ({"plasticity": PlasticityConfig(enabled=True, mode="stdp",
                                          lr=0.4)}
          if extra == "plasticity" else
          {"faults": FaultConfig(drop_p=0.2, seed=1)})
    with pytest.raises(NotImplementedError, match="one layer-step body"):
        ChipSimulator(network("aligned"), quant_cfg=QCFG, engine="compiled",
                      recurrent=(0,), **kw)


def test_recurrent_weight_shape_is_checked():
    w_in, w_out = network("aligned")
    with pytest.raises(ValueError, match="recurrent weight layer 1"):
        ChipSimulator([w_in, w_out], quant_cfg=QCFG, recurrent=(0, 1))
    with pytest.raises(ValueError, match="not one of the 2 weight layers"):
        ChipSimulator([w_in, w_out], quant_cfg=QCFG, recurrent=(2,))


def _engine_scan(sim, n_in):
    """The scan equation of the engine program at B=2, T=3."""
    run = sim.array_engine()._build_run()
    jaxpr = jax.make_jaxpr(run)(jnp.zeros((2, 3, n_in), jnp.float32))
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    if not scans:                             # the compiled engine vmaps
        scans = [e for eq in jaxpr.jaxpr.eqns
                 for sub in jax.core.subjaxprs(eq)
                 for e in sub.eqns if e.primitive.name == "scan"]
    (scan,) = scans
    return scan


def _scan_carry(sim, n_in):
    """The avals of the engine program's scan carry."""
    scan = _engine_scan(sim, n_in)
    n = scan.params["num_carry"]
    return [v.aval for v in scan.invars[scan.params["num_consts"]:][:n]]


@pytest.mark.parametrize("engine", ["compiled", "fused"])
def test_chain_carry_holds_no_spike_state(engine):
    """A chain's scan carries each layer's LIF state (v, elapsed) and
    nothing else; a recurrent layer adds its last step's spikes."""
    w_in, w_out = network("aligned")
    chain = ChipSimulator([codebook(np.random.default_rng(0), 48, 64,
                                    2.0 ** -5), w_out],
                          quant_cfg=QCFG, engine=engine)
    carry = _scan_carry(chain, 48)
    assert [str(a.dtype) for a in carry] == ["float32", "int32"] * 2
    rec = ChipSimulator([w_in, w_out], quant_cfg=QCFG, engine=engine,
                        recurrent=(0,))
    carry = _scan_carry(rec, 48)
    spikes = "float32" if engine == "compiled" else "uint16"
    assert [str(a.dtype) for a in carry] == \
        ["float32", "int32"] * 2 + [spikes]
    width = 64 if engine == "compiled" else 64 // 16
    assert carry[-1].shape[-1] == width


@pytest.mark.parametrize("network_kind", ["chain", "recurrent", "chain_drop"])
def test_fused_scan_emits_one_row_block_a_step(network_kind):
    """Every fused inference program's step emits its kernels' outputs
    as one int32 (B, W) row block and nothing else; the counters are
    computed from the stacked blocks after the scan."""
    from repro.faults import FaultConfig

    w_in, w_out = network("aligned")
    if network_kind == "recurrent":
        sim = ChipSimulator([w_in, w_out], quant_cfg=QCFG, engine="fused",
                            recurrent=(0,))
    else:
        kw = ({"faults": FaultConfig(drop_p=0.2, seed=1)}
              if network_kind == "chain_drop" else {})
        sim = ChipSimulator([codebook(np.random.default_rng(0), 48, 64,
                                      2.0 ** -5), w_out],
                            quant_cfg=QCFG, engine="fused", **kw)
    assert (sim.drop_plan is not None) == (network_kind == "chain_drop")
    scan = _engine_scan(sim, 48)
    (rows,) = scan.outvars[scan.params["num_carry"]:]
    assert str(rows.aval.dtype) == "int32"
    # per layer: spikes, touched mask, input spikes, empty words
    assert rows.aval.shape == (3, 2, 2 * (64 + 10) + 4)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_traces_carry_the_recurrent_counters(shape):
    """`ChipTrace` splits out each recurrent layer's fed-back SOPs and
    back-edge hops, and the three engines' traces agree."""
    from repro.telemetry.trace import TraceConfig

    weights = network(shape)
    tc = TraceConfig(enabled=True)
    ref = ChipSimulator(weights, quant_cfg=QCFG, engine="reference",
                        recurrent=(0,), trace=tc)
    x = trains(SHAPES[shape][0], batch=2, steps=6, seed=2)
    _, want = ref.run_batch(x)
    base = ref.last_trace()
    assert base.recurrent_sops[..., 1].sum() == 0          # the readout
    for b in range(2):
        assert base.recurrent_sops[b].sum() == want[b].stats.recurrent_sops
        assert base.back_noc_hops[b].sum() == want[b].stats.back_noc_hops
        assert base.noc_hops[b].sum() == want[b].stats.noc_hops
    np.testing.assert_allclose(base.wall_cycles(),
                               [r.wall_cycles for r in want], rtol=1e-12)
    for engine in ("compiled", "fused"):
        sim = ChipSimulator(weights, quant_cfg=QCFG, engine=engine,
                            recurrent=(0,), trace=tc, mapping=ref.mapping)
        sim.run_batch(x)
        got = sim.last_trace()
        for f in ("fired", "touched", "nnz", "skip_words", "recurrent_sops",
                  "back_noc_hops", "noc_hops", "router_load"):
            np.testing.assert_array_equal(getattr(got, f), getattr(base, f),
                                          err_msg=f"{engine}: {f}")

