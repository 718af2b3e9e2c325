"""One readback a `run_batch` call.

The output counts and every counter the host prices come back as one
f32 slab (`engine._host_slab`), read in one transfer and split on the
host.  Each case here holds that read to the one it replaces: one
`np.asarray` per key of `run_raw`'s outputs and the counts summed from
its output spikes, priced by the same `_reports`.  Counts, every
`ChipReport` field and a traced run's `ChipTrace` must be bit-identical.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plasticity import PlasticityConfig
from repro.core.quant import CodebookConfig
from repro.core.soc import ChipSimulator
from repro.telemetry import TraceConfig

SIZES = (32, 48, 16)              # word-aligned widths (fused plasticity)
QUANT = CodebookConfig(n_levels=8, bit_width=8)


def _weights(sizes=SIZES, seed=0):
    rng = np.random.default_rng(seed)
    return [np.asarray(rng.normal(0, 1.2 / np.sqrt(a), (a, b)), np.float32)
            for a, b in zip(sizes[:-1], sizes[1:])]


def _recurrent_weights(seed=0):
    n_in, n_h, n_out = SIZES
    w_in, w_out = _weights(seed=seed)
    w_rec = np.random.default_rng(seed + 7).normal(
        0, 0.6 / np.sqrt(n_h), (n_h, n_h)).astype(np.float32)
    return [np.concatenate([w_in, w_rec]), w_out]


CASES = {
    "compiled": lambda: ChipSimulator(_weights(), engine="compiled"),
    "sharded": lambda: ChipSimulator(_weights(), engine="sharded"),
    "fused": lambda: ChipSimulator(_weights(), engine="fused"),
    "fused_recurrent": lambda: ChipSimulator(
        _recurrent_weights(), engine="fused", recurrent=(0,)),
    "compiled_traced": lambda: ChipSimulator(
        _weights(), engine="compiled", trace=TraceConfig(enabled=True)),
    "fused_traced": lambda: ChipSimulator(
        _weights(), engine="fused", trace=TraceConfig(enabled=True)),
    "compiled_plastic": lambda: ChipSimulator(
        _weights(), engine="compiled", quant_cfg=QUANT,
        plasticity=PlasticityConfig(enabled=True, mode="stdp", lr=0.4)),
    "fused_plastic_reward": lambda: ChipSimulator(
        _weights(), engine="fused", quant_cfg=QUANT,
        plasticity=PlasticityConfig(enabled=True, mode="reward", lr=0.4,
                                    elig_pre=0.1, layers=(1,))),
}


def _trains(batch=4, steps=6, seed=1):
    rng = np.random.default_rng(seed)
    return (rng.random((batch, steps, SIZES[0])) < 0.3).astype(np.float32)


def _assert_traces_equal(a, b):
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            np.testing.assert_array_equal(x, y, err_msg=f.name)
        else:
            assert x == y, f.name


@pytest.mark.parametrize("case", sorted(CASES))
def test_slab_read_equals_the_per_key_read(case):
    sim = CASES[case]()
    eng = sim.array_engine()
    trains = _trains()
    counts, reports = sim.run_batch(trains)
    trace = sim.last_trace()

    ys = eng.run_raw(trains)
    want_counts = np.asarray(jnp.sum(ys["out"], axis=1))
    host = {k: np.asarray(v, np.float64) for k, v in ys.items()
            if not k.startswith(("learned_idx_", "elig_"))}
    want_reports = eng._reports(host)

    np.testing.assert_array_equal(counts, want_counts)
    assert counts.dtype == want_counts.dtype
    assert reports == want_reports
    assert any(r.stats.spikes_routed > 0 for r in reports)
    assert (trace is None) == ("traced" not in case)
    if trace is not None:
        _assert_traces_equal(trace, sim.last_trace())


@pytest.mark.parametrize("case", sorted(CASES))
def test_counts_come_back_as_a_host_array(case):
    sim = CASES[case]()
    trains = _trains(batch=3)
    counts, reports = sim.run_batch(trains)
    assert type(counts) is np.ndarray
    assert counts.shape == (3, SIZES[-1])
    assert counts.dtype == np.float32
    assert len(reports) == 3
    single, _ = sim.run(trains[0])
    assert type(single) is np.ndarray
    np.testing.assert_array_equal(single, counts[0])


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_transfer_a_call(monkeypatch, case):
    sim = CASES[case]()
    eng = sim.array_engine()
    seen = []

    class Span:
        def __init__(self, name, **stats):
            seen.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    trains = _trains()
    for _ in range(2):
        sim.run_batch(trains)
    ys = eng.run_raw(trains)
    slab_bytes = 4 * trains.shape[0] * SIZES[-1] + sum(
        int(ys[k].nbytes) for k in eng._readback_keys(ys))
    assert [s for n, s in seen if n == "snn.readback"] \
        == [{"transfers": 1, "bytes": slab_bytes}] * 2
