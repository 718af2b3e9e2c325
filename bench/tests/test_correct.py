"""The comparison that decides `correct` can fail.

* The control (the reference at a TPU's 3-pass bf16 precision) differs
  from the reference by more than the cell's limits, at the cells' own
  widths, pools and mapping, on every seed tried.
* The configuration's currents are exact f32 sums.
* The reference prices a hand-sized network as counted by hand.
* A whole run on the CPU, with the device check skipped and the timed
  path broken underneath, comes out `correct: false`; unbroken, `true`.
  The faults: an output spike altered where the engine produces it, the
  NoC replay skipped, the NoC contention left out of the wall cycles,
  and in the serve cell a request lost at admission.
"""
import dataclasses

import numpy as np
import pytest

from bench import leastwork, reference, registry, workload

SEEDS = [2147483659, 11, 4093]
CELLS = {"nmnist.fused.b32": ("nmnist_mlp", "closed_b32_fused"),
         "nmnist.compiled.b32": ("nmnist_mlp", "closed_b32_compiled"),
         "nmnist.serve.poisson": ("nmnist_mlp", "serve_poisson")}


def cell_of(name: str) -> registry.Cell:
    """A cell from its files, with the end-to-end metrics its driver
    reports, whether or not BENCHMARK.json lists it yet."""
    cell = registry.files_cell(*CELLS[name])
    first = ({"name": "serve_p95_ms", "unit": "ms"}
             if cell.traffic["driver"] == "open_loop" else
             {"name": "trains_per_s", "unit": "trains/s"})
    return dataclasses.replace(cell, name=name, end_to_end=(
        first, {"name": "setup_s", "unit": "s"}))


def control_numbers(cell: registry.Cell, seed: int) -> dict:
    """The control's numbers over the cell's whole pool of trains (each
    pool train answered once), with the cell's own mapping."""
    import bench.run as R

    driver, sim, state, layers, plan = R.build(cell, seed, {})
    del sim
    if cell.traffic["driver"] == "closed_loop":
        rec = {"calls": [(k, None, None) for k in range(len(state))]}
    else:
        n = len(state)
        rec = {"pick": np.arange(n), "requests": [
            type("R", (), {"uid": i, "status": "served"})()
            for i in range(n)]}
    return driver.correctness(rec, state, layers, plan, cell.config,
                              cell.traffic, control=True)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_fails_on_every_seed(name):
    cell = cell_of(name)
    limits = cell.config["limits"]
    for seed in SEEDS:
        got = control_numbers(cell, seed)
        assert any(v > limits[k] for k, v in got.items()), (
            f"seed {seed}: control {got} within limits {limits}")


def test_currents_are_exact_f32_sums():
    """Every synaptic current of a run is the exact sum of its weights:
    f32 and f64 matmuls agree at every layer-step, so no summation order
    or device can change it."""
    cfg = registry.load_json(registry.BENCH_DIR / "configs"
                             / "nmnist_mlp.json")
    inexact = []

    def matmul(s, w):
        a = s @ w
        inexact.append(int(np.sum(a != s.astype(np.float64) @ w)))
        return a

    net = registry.network(cfg)
    for seed in SEEDS:
        _, layers = net.make(cfg, seed)
        net.simulate([lc.dense() for lc in layers],
                     workload.make_trains(cfg, 32, seed),
                     leak=cfg["leak"], threshold=cfg["threshold"],
                     matmul=matmul)
    assert sum(inexact) == 0


def test_control_weights_hold_every_level_differently():
    cfg = registry.load_json(registry.BENCH_DIR / "configs"
                             / "nmnist_mlp.json")
    _, layers = registry.network(cfg).make(cfg, SEEDS[0])
    for lc in layers:
        assert np.all(reference.high_precision_weights(lc.levels)
                      != lc.levels)


TINY = {
    "name": "tiny", "layer_sizes": [64, 256, 128, 10], "timesteps": 6,
    "threshold": 1.0, "leak": 0.9, "reset": 0.0, "weight_levels": 16,
    "weight_bits": 8, "freq_hz": 1e8, "weight_gain": 3.0,
    "scale_mantissa_bits": 11,
    "input": {"kind": "event_stream", "height": 4, "width": 8,
              "n_classes": 10},
    "limits": {"differing_trains": 0, "energy_rel_gap": 1e-9,
               "wall_rel_gap": 1e-9, "differing_requests": 0,
               "never_completed": 0},
}


def tiny_cell(name):
    cell = cell_of(name)
    traffic = dict(cell.traffic)
    if traffic["driver"] == "closed_loop":
        traffic.update(batch=8, pool_batches=2)
    else:
        traffic.update(rate_per_s=40.0, pool_trains=16)
    return registry.Cell(name=name, chips=1, config=TINY, traffic=traffic,
                         end_to_end=cell.end_to_end, per_layer=cell.per_layer)


@pytest.fixture
def no_peaks(monkeypatch):
    monkeypatch.setattr(leastwork, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def alter_one_spike(monkeypatch):
    """The engine's per-step output spikes, altered where produced: the
    first sample's first output neuron flips at the first timestep."""
    from repro.core import engine as ENG

    real = ENG._EngineBase.run_raw

    def broken(self, spike_trains, learned=None):
        ys = dict(real(self, spike_trains, learned=learned))
        out = ys["out"]
        ys["out"] = out.at[0, 0, 0].set(1.0 - out[0, 0, 0])
        return ys

    monkeypatch.setattr(ENG._EngineBase, "run_raw", broken)


def skip_noc_replay(monkeypatch):
    """The host's per-flow NoC replay skipped: no hops, energy or router
    load."""
    from repro.core import noc as NOC

    def skipped(table, fired):
        fired = np.asarray(fired, np.float64)
        zero = np.zeros(fired.shape[:-1])
        return zero, zero, np.zeros(fired.shape[:-1]
                                    + table.router_load.shape[1:])

    monkeypatch.setattr(NOC, "replay_flows_exact", skipped)


def drop_contention(monkeypatch):
    """The wall cycles priced without the routers' contention."""
    from repro.core import noc as NOC

    monkeypatch.setattr(NOC, "contention_cycles",
                        lambda spikes, compute, params=None:
                        np.zeros(np.shape(compute)))


def lose_one_request(monkeypatch):
    """The server's admission loses its first request: queued, never in
    the queue, never answered."""
    from repro.serve.snn_server import SnnServer

    real = SnnServer.submit

    def losing(self, req):
        req = real(self, req)
        if req.uid == 0 and req in self.queue:
            self.queue.remove(req)
        return req

    monkeypatch.setattr(SnnServer, "submit", losing)


FAULTS = {"sound": None, "altered": alter_one_spike,
          "noc_skipped": skip_noc_replay, "no_contention": drop_contention,
          "request_lost": lose_one_request}
CASES = [(c, f) for c in CELLS for f in sorted(FAULTS)
         if f != "request_lost" or c == "nmnist.serve.poisson"]


@pytest.mark.parametrize("name,fault", CASES)
def test_run_is_correct_only_when_sound(monkeypatch, no_peaks, name, fault):
    import bench.run as R

    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    result = R.run_cell(tiny_cell(name), 2147483659, 0.5, False, 0.0, "cpu")
    assert result["correct"] is (fault == "sound"), result["checks"]
    assert list(result)[-1] == "checks"


def hand_network():
    """A 2-4-3 network on two cores: one input spike at t=0 makes all
    four hidden neurons fire (weight 1.0), which the outputs (0.1) do
    not answer.  Layer 1 sits on core 12, layer 2 on core 13; the hidden
    spikes travel 12 -> router 0 -> 13."""
    weights = [np.full((2, 4), 1.0, np.float32),
               np.full((4, 3), 0.1, np.float32)]
    trains = np.zeros((1, 2, 2), np.float32)
    trains[0, 0, 0] = 1.0
    config = {"layer_sizes": [2, 4, 3], "freq_hz": 1e8}
    plan = {"layers": [[[12, 0, 4]], [[13, 0, 3]]],
            "routes": [[{"src": 12, "dsts": [13],
                         "links": [[12, 0], [0, 13]]}]],
            "level2_nodes": []}
    net = registry.network(config)
    out = net.simulate(weights, trains, leak=0.9, threshold=1.0,
                       slices=[[(0, 4)], [(0, 3)]])
    return dict(zip(reference.FIELDS, net.sample_fields(
        out, config, plan)[0])), out


def test_reference_counts_by_hand():
    fields, out = hand_network()
    assert out["counts"].sum() == 0
    assert fields["spikes_in"] == 1 + 4      # input, then hidden spikes
    assert fields["performed_sops"] == 1 * 4 + 4 * 3
    assert fields["nominal_sops"] == (2 * 4 + 4 * 3) * 2
    assert fields["neurons_touched"] == 4 + 3
    assert fields["spikes_routed"] == 4
    assert fields["noc_hops"] == 4 * 2
    assert leastwork.least_ops(fields["performed_sops"]) == 32


def test_reference_prices_by_hand():
    fields, _ = hand_network()
    # t=0: core 12 updates 4 neurons (4 cycles), core 13 takes 12 SOPs at
    # 4 a cycle (3), each plus a fill of 4; t=1: one scan word, plus fill
    core_wall = max(4, 3) + 4 + (1 + 4)
    service = 4 / 0.4                          # 4 spikes through router 0
    contention = service + service ** 2 / 8
    assert fields["noc_contention_cycles"] == contention
    assert fields["wall_cycles"] == core_wall + contention
    assert fields["noc_energy_pj"] == pytest.approx(4 * 2 * 0.026)
    riscv_mw = 0.434 / 0.57                    # duty 1: 400 control cycles
    assert fields["riscv_energy_pj"] == pytest.approx(
        riscv_mw * 1e-3 * (core_wall + contention) / 1e8 * 1e12)
    assert fields["energy_pj"] == pytest.approx(
        fields["core_energy_pj"] + fields["noc_energy_pj"]
        + fields["riscv_energy_pj"])
    assert fields["core_energy_pj"] == pytest.approx(
        reference.core_pj_per_nominal_sop(16 / 40) * 40)
