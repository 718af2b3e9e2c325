"""The `recurrent_chain` network kind and its cell, `shd.fused.b32`.

* The cell is discovered with its kind, input generator and metrics.
* Golden digests hold its weights, trains, mapping plan, reference and
  control fixed, on a seed under 2**32 and one above it
  (`fixtures/shd_rsnn_golden.json`; regenerate it with this file's
  `golden(seed)` only where a change means to alter them).
* Its currents are exact f32 sums, and its control (the reference at a
  TPU's 3-pass bf16 precision) fails `energy_rel_gap`.
* Least bytes and `rec_roofline.batch` against hand counts.
* A whole run of a small recurrent configuration on the CPU, through
  `run_cell`: `correct` when sound, not when its reference is broken.
"""
import dataclasses
import hashlib
import json
import math

import numpy as np
import pytest

from bench import leastwork, registry, workload
from bench.record import RunRecord
import bench.run as R

GOLDEN_FILE = registry.BENCH_DIR / "tests" / "fixtures" \
    / "shd_rsnn_golden.json"
CELL = "shd.fused.b32"
V5E = leastwork.peaks("TPU v5 lite")


def config():
    return registry.load_json(registry.BENCH_DIR / "configs"
                              / "shd_rsnn.json")


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def golden(seed: int) -> dict:
    """The digests of one seed: weights (reference and program), 8
    trains, the plan of the compiled engine's simulator, and the
    reference and control on trains[:4]."""
    cell = registry.files_cell("shd_rsnn", "closed_b32_compiled")
    cfg = cell.config
    net = registry.network(cfg)
    program, layers = net.make(cfg, seed)
    trains = workload.make_trains(cfg, 8, seed)
    _, sim, _, _, plan = R.build(cell, seed, {})
    del sim
    counts, fields = net.reference(layers, trains[:4], cfg, plan)
    c_counts, c_fields = net.reference(layers, trains[:4], cfg, plan,
                                       control=True)
    return {
        "idx": digest(*[lc.idx for lc in layers]),
        "words": digest(*[lc.words for lc in layers]),
        "scales": digest(*[np.float32(lc.scale) for lc in layers]),
        "program": digest(*[x for q in program
                            for x in (q.idx, q.codebook, q.scale)]),
        "trains": digest(trains),
        "plan": hashlib.sha256(json.dumps(plan, sort_keys=True)
                               .encode()).hexdigest(),
        "reference": digest(counts, fields),
        "control": digest(c_counts, c_fields),
    }


def test_cell_is_discovered_with_its_kind():
    cell = registry.cell(CELL)
    assert cell.chips == 1
    assert cell.config["network"] == "recurrent_chain"
    assert cell.traffic == registry.load_json(
        registry.BENCH_DIR / "traffic" / "closed_b32_fused.json")
    assert {m["name"] for m in cell.end_to_end} == {"trains_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle.batch", "engine_roofline.batch", "sop_mfu.batch",
        "rec_roofline.batch"}
    net = registry.network(cell.config)
    for fn in ("make", "simulator", "plan", "reference", "least_bytes",
               "n_in", "recurrent_layers", "layer_least_bytes"):
        assert callable(getattr(net, fn)), fn
    assert net.n_in(cell.config) == 700
    assert net.recurrent_layers(cell.config) == [0]
    assert net.fan_ins(cell.config) == [700 + 1024, 1024]
    # the chain's cell reads no recurrent layer
    assert "rec_roofline.batch" not in {
        m["name"] for m in registry.cell("nmnist.fused.b32").per_layer}


@pytest.mark.parametrize("seed", [7, 6442450951])
def test_golden_digests(seed):
    want = registry.load_json(GOLDEN_FILE)
    assert want["config"] == "shd_rsnn"
    assert golden(seed) == want["seeds"][str(seed)]


def test_input_is_shd_shaped_and_seeded():
    cfg = config()
    a = workload.make_trains(cfg, 16, 2 ** 33 + 3)
    b = workload.make_trains(cfg, 16, 2 ** 33 + 3)
    c = workload.make_trains(cfg, 16, 2 ** 33 + 4)
    assert a.shape == (16, 100, 700) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert set(np.unique(a)) <= {0.0, 1.0}
    assert 0.035 < a.mean() < 0.045                # about 4%


def test_currents_are_exact_f32_sums_and_the_hidden_layer_works():
    """f32 and f64 currents agree at every layer-step, and the hidden
    layer neither dies nor saturates: recurrent SOPs are most of the
    performed SOPs."""
    cfg = config()
    net = registry.network(cfg)
    inexact = []

    def matmul(s, w):
        a = s @ w
        inexact.append(int(np.sum(a != s.astype(np.float64) @ w)))
        return a

    for seed in (11, 2147483659):
        _, layers = net.make(cfg, seed)
        out = net.simulate([lc.dense() for lc in layers],
                           workload.make_trains(cfg, 16, seed), [0],
                           leak=cfg["leak"], threshold=cfg["threshold"],
                           matmul=matmul)
        rate = out["fired"][:, :, 0].mean() / 1024
        assert 0.028 < rate < 0.2, rate
        rec_sops = out["fed"][:, :, 0].sum() * 1024
        sops = (out["nnz"][:, :, 0].sum() * 1024
                + out["nnz"][:, :, 1].sum() * 20)
        assert rec_sops / sops >= 0.5
        assert out["counts"].sum() > 0
    assert sum(inexact) == 0


@pytest.mark.parametrize("seed", [2147483659, 4093])
def test_control_fails_energy_rel_gap(seed):
    cell = registry.files_cell("shd_rsnn", "closed_b32_fused")
    driver, sim, state, layers, plan = R.build(cell, seed, {})
    del sim
    got = driver.correctness({"calls": [(0, None, None)]}, state, layers,
                             plan, cell.config, cell.traffic, control=True)
    limits = cell.config["limits"]
    assert got["energy_rel_gap"] > limits["energy_rel_gap"], got
    assert got["differing_trains"] > limits["differing_trains"], got


@pytest.mark.parametrize("batch", [32, 8])
def test_least_bytes_by_hand(batch):
    cfg = config()
    net = registry.network(cfg)
    weights = ((700 + 1024) * 1024 + 1024 * 20) * 4 / 8
    assert net.least_bytes(cfg, batch) == (
        weights + 2 * 16 + batch * 100 * 700 / 8 + batch * 20 * 4)
    assert net.layer_least_bytes(cfg, batch, 0) == (
        (700 + 1024) * 1024 / 2 + 16 + batch * 100 * (700 + 1024) / 8)


def run_record(cfg, device_ops, sops=(3.0e8, 2.0e8)):
    fields = []
    for s in sops:
        f = np.zeros((32, 13))
        f[:, 1] = s / 32                                   # performed_sops
        fields.append((0, None, f))
    return RunRecord(config=cfg, traffic={}, seed=0, setup_s=0.0,
                     drive={"calls": fields}, trace={
                         "busy_s": 1.0, "device_ops": device_ops},
                     peak=V5E)


def test_rec_roofline_by_hand():
    read = registry.load_module("metrics", "rec_roofline.batch").read
    cfg = config()
    ops = [["snn_fused_l1.10", 0.004], ["snn_fused_l2.10", 0.001],
           ["copy.3", 0.002], ["snn_fused_l1", 0.001]]
    layer_bytes = registry.network(cfg).layer_least_bytes(cfg, 32, 0)
    least = sum(max(2 * s / 197e12, layer_bytes / 819e9)
                for s in (3.0e8, 2.0e8))
    assert read(run_record(cfg, ops)) == pytest.approx(
        100 * least / 0.005, rel=1e-12)
    # ops-bound here: 2 x 3e8 SOPs over the peak beats the bytes
    assert 2 * 3.0e8 / 197e12 > layer_bytes / 819e9
    assert read(run_record(cfg, ops[1:3])) is None
    chain = registry.load_json(registry.BENCH_DIR / "configs"
                               / "nmnist_mlp.json")
    assert read(run_record(chain, ops)) is None
    assert read(dataclasses.replace(run_record(cfg, ops), trace=None)) \
        is None


SMALL = {
    "name": "small_rsnn", "network": "recurrent_chain",
    "layer_sizes": [40, 64, 10], "recurrent": [1], "timesteps": 6,
    "threshold": 1.0, "leak": 0.9, "reset": 0.0, "weight_levels": 16,
    "weight_bits": 8, "freq_hz": 1e8, "weight_gain": [2.0, 3.0],
    "scale_mantissa_bits": 11,
    "input": {"kind": "cochlea_stream", "channels": 40, "n_classes": 20,
              "band_sigma": 2.0, "band_rate": 0.5, "background_rate": 0.02},
    "limits": {"differing_trains": 0, "energy_rel_gap": 1e-9,
               "wall_rel_gap": 1e-9},
}


@pytest.mark.parametrize("engine", ["compiled", "fused"])
def test_small_recurrent_run_is_correct_and_a_broken_reference_is_not(
        monkeypatch, engine):
    monkeypatch.setattr(leastwork, "peaks", lambda kind: V5E)
    cell = registry.Cell(
        name="small.b4", chips=1, config=SMALL,
        traffic={"driver": "closed_loop", "engine": engine, "batch": 4,
                 "pool_batches": 2},
        end_to_end=({"name": "trains_per_s", "unit": "trains/s"},
                    {"name": "setup_s", "unit": "s"}),
        per_layer=())
    sound = R.run_cell(cell, 4294967311, 0.3, False, 0.0, "cpu")
    assert sound["correct"] is True, sound["checks"]
    assert sound["checks"]["wall_rel_gap"]["value"] == 0.0
    net = registry.network(SMALL)
    real = net.reference
    monkeypatch.setattr(net, "reference", lambda layers, trains, config,
                        plan, **kw: real(layers, trains, dict(
                            config, threshold=1.5), plan, **kw))
    broken = R.run_cell(cell, 4294967311, 0.3, False, 0.0, "cpu")
    assert broken["correct"] is False
    assert math.isfinite(sound["metrics"]["trains_per_s"]["value"])
