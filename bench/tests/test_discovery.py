"""A configuration, a traffic mix, a per-layer metric and a network kind
added as files, with entries in BENCHMARK.json, drive a run with no
other edit; and a run without a chip, or without the program, prints
no result."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

from bench import registry

ROOT = registry.ROOT

DUMMY_CONFIG = {
    "name": "dummy_mlp", "layer_sizes": [64, 96, 10], "timesteps": 3,
    "threshold": 1.0, "leak": 0.9, "reset": 0.0, "weight_levels": 16,
    "weight_bits": 8, "freq_hz": 1e8, "weight_gain": 3.0,
    "scale_mantissa_bits": 11,
    "input": {"kind": "event_stream", "height": 4, "width": 8,
              "n_classes": 10},
    "limits": {"differing_trains": 0, "energy_rel_gap": 1e-9,
               "wall_rel_gap": 1e-9},
}
DUMMY_TRAFFIC = {"driver": "closed_loop", "engine": "compiled", "batch": 4,
                 "pool_batches": 2, "check_calls": 2}
DUMMY_METRIC = '''"""dummy_calls.batch: calls made in the window."""


def read(run):
    return float(len(run.drive["calls"]))
'''

# runs one cell on the CPU through run_cell, peaks stubbed (no chip here)
DRIVE = '''
import json, sys
from bench import leastwork, registry
import bench.run as R
leastwork.peaks = lambda kind: {"bf16_flops_per_s": 1e12,
                                "hbm_bytes_per_s": 1e11}
cell = registry.cell("dummy.compiled.b4")
res = R.run_cell(cell, 5, 0.3, False, 0.0, "cpu")
per_layer = {m["name"]: registry.load_module("metrics", m["name"]).read(
    type("Run", (), {"drive": {"calls": [0, 1, 2]}})())
    for m in cell.per_layer}
print(json.dumps({"result": res, "per_layer": per_layer}))
'''


def copy_tree(dst: pathlib.Path) -> pathlib.Path:
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    return dst


def env(extra_path: str = "") -> dict:
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e["PYTHONPATH"] = extra_path
    return e


def test_added_files_are_found_and_driven(tmp_path):
    root = copy_tree(tmp_path)
    (root / "bench/configs/dummy_mlp.json").write_text(
        json.dumps(DUMMY_CONFIG))
    (root / "bench/traffic/closed_b4_compiled.json").write_text(
        json.dumps(DUMMY_TRAFFIC))
    (root / "bench/metrics/dummy_calls.batch.py").write_text(DUMMY_METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "dummy_mlp", "source": "test", "reduced": [], "why": "test",
        "file": "bench/configs/dummy_mlp.json"})
    bench["workloads"].append({
        "name": "dummy.compiled.b4", "config": "dummy_mlp",
        "traffic": "closed_b4_compiled", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("dummy.compiled.b4")
    bench["per_layer"].append({
        "name": "dummy_calls.batch", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "test", "moves": "trains_per_s",
        "workloads": ["dummy.compiled.b4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the program comes from this checkout's src/
    out = subprocess.run(
        [sys.executable, "-c", DRIVE], cwd=root, capture_output=True,
        text=True, timeout=600, env=env(f"{root}:{ROOT / 'src'}"))
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["result"]["correct"] is True
    assert set(got["result"]["metrics"]) == {"trains_per_s", "setup_s"}
    assert got["per_layer"] == {"dummy_calls.batch": 3.0}


# a network kind of its own: each neuron sees a window of the layer
# below, every other synapse is zero (the reference's touched path for
# zero synapses); its reference composes bench/reference.py's pieces
LOCAL_NETWORK = """\
\"\"\"A locally connected chain: neuron j of layer i + 1 sees `window`
neurons of layer i from (j * stride) mod (n_pre - window + 1); every
other synapse is zero.\"\"\"
import math

import numpy as np

from bench import reference as shared
from bench import registry, workload


def sizes(config):
    return [int(s) for s in config["layer_sizes"]]


def n_in(config):
    return sizes(config)[0]


def masks(config):
    window, stride = int(config["window"]), int(config["stride"])
    out = []
    for n_pre, n_post in zip(sizes(config)[:-1], sizes(config)[1:]):
        lo = (np.arange(n_post) * stride) % (n_pre - window + 1)
        rows = np.arange(n_pre)[:, None]
        out.append((rows >= lo) & (rows < lo + window))
    return out


def make(config, seed):
    widths = sizes(config)
    idx = workload.device_indices(seed, tuple(zip(widths[:-1], widths[1:])),
                                  int(config["weight_levels"]))
    dense = []
    for li, (mask, i) in enumerate(zip(masks(config), idx)):
        words, scale = workload.layer_levels(config, seed, li,
                                             int(config["window"]))
        levels = words.astype(np.float32) * scale
        dense.append(np.where(mask, levels[np.asarray(i).astype(np.int64)],
                              np.float32(0.0)))
    return dense, dense


def simulator(config, traffic, program_weights):
    from repro.core.soc import ChipSimulator

    sim = ChipSimulator(program_weights, engine=traffic["engine"],
                        leak=float(config["leak"]),
                        threshold=float(config["threshold"]),
                        freq_hz=float(config["freq_hz"]))
    sim.array_engine()
    return sim


def plan(sim, config):
    # the program maps it as a chain of layers
    return registry.load_module("networks", "dense_chain").plan(sim, config)


def reference(ref_layers, trains, config, plan, *, control=False,
              block=32):
    weights = [shared.high_precision_weights(w) if control else w
               for w in ref_layers]
    nzw = [(w != 0).astype(np.float32) for w in weights]
    slices = [[(lo, hi) for _, lo, hi in layer] for layer in plan["layers"]]
    trains = np.asarray(trains, np.float32)
    B, T, _ = trains.shape
    v = [np.zeros((B, w.shape[1]), np.float32) for w in weights]
    el = [np.zeros((B, w.shape[1]), np.int32) for w in weights]
    rec = {k: [[] for _ in weights] for k in ("nnz", "skip", "touched",
                                                "fired")}
    counts = np.zeros((B, weights[-1].shape[1]))
    for t in range(T):
        s = trains[:, t]
        for li, w in enumerate(weights):
            nnz = (s != 0).sum(-1)
            touched = shared.touched_neurons(s, nnz, nzw[li], w.shape[1])
            v[li], el[li], spike = shared.lif_step(
                v[li], el[li], s @ w, touched, leak=config["leak"],
                threshold=config["threshold"], reset=config["reset"])
            rec["nnz"][li].append(nnz)
            rec["skip"][li].append(shared.empty_words(s))
            rec["touched"][li].append(shared.slice_sums(touched,
                                                        slices[li]))
            rec["fired"][li].append(shared.slice_sums(spike, slices[li]))
            s = spike.astype(np.float32)
        counts += s
    rec = {k: [np.stack(x, 1).astype(np.float64) for x in layers]
           for k, layers in rec.items()}
    widths = sizes(config)
    edges = [shared.Edge(nnz=rec["nnz"][li], skip=rec["skip"][li],
                         n_pre=widths[li], fan_out=widths[li + 1],
                         slices=layer, touched=rec["touched"][li])
             for li, layer in enumerate(plan["layers"])]
    flows = [shared.Flows(fired=rec["fired"][li], routes=routes,
                          srcs=[c for c, _, _ in plan["layers"][li]])
             for li, routes in enumerate(plan["routes"])]
    return counts, shared.chip_report(edges, flows,
                                      level2_nodes=plan["level2_nodes"],
                                      freq_hz=config["freq_hz"])


def least_bytes(config, batch):
    widths = sizes(config)
    n_levels = int(config["weight_levels"])
    synapses = sum(int(config["window"]) * b for b in widths[1:])
    tables = (len(widths) - 1) * n_levels * int(config["weight_bits"]) / 8
    return (synapses * math.ceil(math.log2(n_levels)) / 8 + tables
            + batch * int(config["timesteps"]) * widths[0] / 8
            + batch * widths[-1] * 4)
"""
LOCAL_CONFIG = dict(DUMMY_CONFIG, name="local_chain", network="local_chain",
                    layer_sizes=[64, 96, 32], window=8, stride=3,
                    weight_gain=1.5)

# the local network run on the CPU: sound, with its reference broken (a
# threshold it does not have), and engine_roofline's least bytes
LOCAL_DRIVE = """
import json
import numpy as np
from bench import leastwork, registry
from bench.record import RunRecord
import bench.run as R
leastwork.peaks = lambda kind: {"bf16_flops_per_s": 1e12,
                                "hbm_bytes_per_s": 1e11}
cell = registry.cell("local.compiled.b4")
net = registry.network(cell.config)
sound = R.run_cell(cell, 4294967311, 0.3, False, 0.0, "cpu")
real = net.reference
net.reference = lambda layers, trains, config, plan, **kw: real(
    layers, trains, dict(config, threshold=1.5 * config["threshold"]),
    plan, **kw)
broken = R.run_cell(cell, 4294967311, 0.3, False, 0.0, "cpu")
roofline = registry.load_module("metrics", "engine_roofline.batch").read(
    RunRecord(config=cell.config, traffic=cell.traffic, seed=0, setup_s=0.0,
              drive={"calls": [(0, None, np.zeros((4, 13)))]},
              trace={"busy_s": 1.0},
              peak={"bf16_flops_per_s": 1e30, "hbm_bytes_per_s": 1.0}))
print(json.dumps({"sound": sound, "broken": broken, "roofline": roofline,
                  "least_bytes": net.least_bytes(cell.config, 4),
                  "kind": net.__file__}))
"""


def test_added_network_is_found_and_driven(tmp_path):
    root = copy_tree(tmp_path)
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    (root / "bench/networks/local_chain.py").write_text(LOCAL_NETWORK)
    (root / "bench/configs/local_chain.json").write_text(
        json.dumps(LOCAL_CONFIG))
    (root / "bench/traffic/closed_b4_local.json").write_text(
        json.dumps(DUMMY_TRAFFIC))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "local_chain", "source": "test", "reduced": [],
        "why": "test", "file": "bench/configs/local_chain.json"})
    bench["workloads"].append({
        "name": "local.compiled.b4", "config": "local_chain",
        "traffic": "closed_b4_local", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("local.compiled.b4")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = subprocess.run(
        [sys.executable, "-c", LOCAL_DRIVE], cwd=root, capture_output=True,
        text=True, timeout=600, env=env(f"{root}:{ROOT / 'src'}"))
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    # no file of the copied bench/ was edited, only files added
    assert all((root / p).read_bytes() == b for p, b in before.items())
    assert got["kind"] == str(root / "bench/networks/local_chain.py")
    assert got["sound"]["correct"] is True, got["sound"]["checks"]
    assert got["broken"]["correct"] is False, got["broken"]["checks"]
    # engine_roofline's least bytes are the kind's, which count only the
    # window's synapses: fewer than the dense chain's
    assert got["roofline"] == 100.0 * got["least_bytes"]
    dense = (64 * 96 + 96 * 32) / 2 + 2 * 16 + 4 * 3 * 64 / 8 + 4 * 32 * 4
    window = (8 * 96 + 8 * 32) / 2 + 2 * 16 + 4 * 3 * 64 / 8 + 4 * 32 * 4
    assert got["least_bytes"] == window < dense


def run_bench(cwd: pathlib.Path, pythonpath: str = ""):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nmnist.fused.b32",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=env(pythonpath))


def test_no_chip_no_result():
    out = run_bench(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    out = run_bench(copy_tree(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
