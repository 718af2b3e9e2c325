"""A configuration, a traffic mix and a per-layer metric added as files,
with entries in BENCHMARK.json, drive a run with no other edit; and a
run without a chip, or without the program, prints no result."""
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
