"""The `conv_chain` network kind and its cell, `nmnist_cnn.fused.b32`.

* The cell is discovered with its kind, input generator and metrics.
* The kind's geometry is SLAYER's N-MNIST network, layer by layer.
* Least bytes count a shared filter's words once, by hand.
* `conv_roofline.batch` against a hand count.
* A whole run of a small conv configuration on the CPU, through
  `run_cell`: `correct` when sound; not when a spike of the program's
  answer is altered, nor when the reference is broken.
* The control (the reference at a TPU's 3-pass bf16 precision) differs
  from the reference by more than the cell's limits.
"""
import dataclasses
import math

import numpy as np
import pytest

from bench import check, leastwork, registry
from bench.record import RunRecord
import bench.run as R

CELL = "nmnist_cnn.fused.b32"
V5E = leastwork.peaks("TPU v5 lite")


def config():
    return registry.load_json(registry.BENCH_DIR / "configs"
                              / "nmnist_slayer_cnn.json")


SMALL = dict(config(), name="small_cnn", spec="12x12x2-4c3-2a-8c3-2a-10o",
             timesteps=4, input={"kind": "event_stream", "height": 12,
                                 "width": 12, "n_classes": 10})


def test_cell_is_discovered_with_its_kind():
    cell = registry.cell(CELL)
    assert cell.chips == 1
    assert cell.config["network"] == "conv_chain"
    assert cell.traffic == registry.load_json(
        registry.BENCH_DIR / "traffic" / "closed_b32_fused.json")
    assert {m["name"] for m in cell.end_to_end} == {"trains_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle.batch", "engine_roofline.batch", "sop_mfu.batch",
        "conv_roofline.batch"}
    net = registry.network(cell.config)
    for fn in ("make", "simulator", "plan", "reference", "least_bytes",
               "n_in", "conv_layers", "layer_least_bytes"):
        assert callable(getattr(net, fn)), fn
    assert net.n_in(cell.config) == 34 * 34 * 2
    assert net.conv_layers(cell.config) == [0, 1, 2, 3]
    for other in ("nmnist.fused.b32", "shd.fused.b32"):
        assert "conv_roofline.batch" not in {
            m["name"] for m in registry.cell(other).per_layer}


def test_geometry_is_slayers_nmnist_network():
    n_in, geoms = registry.network(config()).geometry(config())
    assert n_in == 2312
    assert [(g["kind"], g["n_post"]) for g in geoms] == [
        ("conv", 13872), ("pool", 3468), ("conv", 18496), ("pool", 4096),
        ("dense", 10)]
    assert sum(g["n_post"] for g in geoms) == 39942
    assert [(g.get("pad"), g.get("s")) for g in geoms[:4]] == [
        (2, 1), (0, 2), (2, 1), (0, 2)]


@pytest.mark.parametrize("batch", [32, 8])
def test_least_bytes_count_a_shared_filter_once(batch):
    cfg = config()
    net = registry.network(cfg)
    entries = 5 * 5 * 2 * 12 + 2 * 2 * 12 + 5 * 5 * 12 * 64 + 2 * 2 * 64 \
        + 4096 * 10
    want = entries * 4 / 8 + 5 * 16 + batch * 20 * 2312 / 8 + batch * 10 * 4
    assert net.least_bytes(cfg, batch) == want
    # the 5.51M synapses, a word each at 4 bits, would be 80x the words
    assert 80 * net.least_bytes(cfg, 0) < 5.51e6 * 4 / 8
    assert net.layer_least_bytes(cfg, batch, 2) == (
        5 * 5 * 12 * 64 / 2 + 16 + batch * 20 * 3468 / 8)


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


def test_conv_roofline_by_hand():
    read = registry.load_module("metrics", "conv_roofline.batch").read
    cfg = config()
    net = registry.network(cfg)
    ops = [["snn_conv_l1.4", 0.002], ["snn_conv_l3.4", 0.003],
           ["snn_fused_l5.4", 0.001], ["copy.3", 0.002],
           ["snn_conv_l2", 0.001]]
    conv_bytes = sum(net.layer_least_bytes(cfg, 32, li) for li in range(4))
    least = sum(max(2 * s / 197e12, conv_bytes / 819e9)
                for s in (3.0e8, 2.0e8))
    assert read(run_record(cfg, ops)) == pytest.approx(
        100 * least / 0.006, rel=1e-12)
    assert read(run_record(cfg, ops[2:4])) is None
    chain = registry.load_json(registry.BENCH_DIR / "configs"
                               / "nmnist_mlp.json")
    assert read(run_record(chain, ops)) is None
    assert read(dataclasses.replace(run_record(cfg, ops), trace=None)) \
        is None


def small_cell(engine):
    return registry.Cell(
        name="small_cnn.b4", chips=1, config=SMALL,
        traffic={"driver": "closed_loop", "engine": engine, "batch": 4,
                 "pool_batches": 2},
        end_to_end=({"name": "trains_per_s", "unit": "trains/s"},
                    {"name": "setup_s", "unit": "s"}),
        per_layer=())


@pytest.mark.parametrize("engine", ["compiled", "fused"])
def test_small_conv_run_is_correct_and_a_broken_reference_is_not(
        monkeypatch, engine):
    monkeypatch.setattr(leastwork, "peaks", lambda kind: V5E)
    sound = R.run_cell(small_cell(engine), 4294967311, 0.3, False, 0.0,
                       "cpu")
    assert sound["correct"] is True, sound["checks"]
    assert sound["checks"]["differing_trains"]["value"] == 0
    assert math.isfinite(sound["metrics"]["trains_per_s"]["value"])
    net = registry.network(SMALL)
    real = net.reference
    monkeypatch.setattr(net, "reference", lambda layers, trains, config,
                        plan, **kw: real(layers, trains, dict(
                            config, threshold=1.5), plan, **kw))
    broken = R.run_cell(small_cell(engine), 4294967311, 0.3, False, 0.0,
                        "cpu")
    assert broken["correct"] is False


def test_an_altered_spike_is_caught():
    cell = small_cell("fused")
    driver, sim, state, layers, plan = R.build(cell, 77, {})
    counts, reports = sim.run_batch(state[0])
    fields = check.report_fields(reports)
    net = registry.network(SMALL)
    ref_counts, ref_fields = net.reference(layers, state[0], SMALL, plan)
    assert check.compare_trains(counts, fields, ref_counts, ref_fields,
                                True)["differing_trains"] == 0
    altered = np.array(counts, np.float64)
    altered[1, int(np.argmax(altered[1]))] += 1
    got = check.compare_trains(altered, fields, ref_counts, ref_fields, True)
    assert got["differing_trains"] == 1
    fields = fields.copy()
    fields[2, 1] += 1                               # one more SOP
    assert check.compare_trains(counts, fields, ref_counts, ref_fields,
                                True)["differing_trains"] == 1


@pytest.mark.parametrize("seed", [2147483659, 4093])
def test_control_differs_by_more_than_the_limits(seed):
    """The SLAYER network's reference against its control, on 8 trains
    of the seed: more differing trains and a wider energy gap than the
    limits allow."""
    cell = registry.files_cell("nmnist_slayer_cnn", "closed_b32_compiled")
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, batch=8,
                                                  pool_batches=1))
    driver, sim, state, layers, plan = R.build(cell, seed, {})
    del sim
    got = driver.correctness({"calls": [(0, None, None)]}, state, layers,
                             plan, cell.config, cell.traffic, control=True)
    limits = cell.config["limits"]
    assert got["energy_rel_gap"] > limits["energy_rel_gap"], got
    assert got["differing_trains"] > limits["differing_trains"], got
