"""Find every part of a cell by its name, as `BENCHMARK.json` gives it.

Each configuration, traffic mix, per-layer metric, traffic driver,
input generator and network kind lives in a file of its own, named
after it:

    bench/configs/<config>.json       sizes of one network, and its limits
    bench/traffic/<traffic>.json      parameters of one traffic mix
    bench/metrics/<metric>.py         `read(run) -> float | None`
    bench/drivers/<driver>.py         `drive(cell, seconds, ...)`
    bench/inputs/<kind>.py            `make(spec, n, timesteps, seed)`
    bench/networks/<kind>.py          the network's shape, below

so a cell, a mix, a metric or a network is added by adding files and
entries, without editing any file that is already here.

A configuration names its network kind under `"network"`
(`dense_chain` where the key is absent).  Everything that knows the
network's shape is in that kind's module, which has:

    make(config, seed) -> (program_weights, ref_layers)
        the weights from the seed, as the simulator takes them (on the
        device) and as the kind's reference takes them (on the host)
    simulator(config, traffic, program_weights) -> ChipSimulator
        the program's simulator on the traffic's `engine`, lowered
    plan(sim, config) -> dict
        the mapping compiler's placement and routes, as plain data
    reference(ref_layers, trains, config, plan, *, control=False,
              block=32) -> (counts (N, n_out), fields (N, len(FIELDS)))
        the plain reference (the control with `control`), composed of
        `bench/reference.py`'s shared LIF step and pricing
    least_bytes(config, batch) -> float
        the least bytes one batch needs (`bench/leastwork.py`)
    n_in(config) -> int
        the input width the trains must have
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_module(kind: str, name: str):
    """Import `bench/<kind>/<name>.py` (names may hold dots)."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def _network(kind: str):
    return load_module("networks", kind)


def network(config: dict):
    """The module of the configuration's network kind,
    `bench/networks/<kind>.py`; loaded once per process."""
    return _network(config.get("network", "dense_chain"))


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of `BENCHMARK.json`, with its files read."""

    name: str
    chips: int
    config: dict           # bench/configs/<config>.json
    traffic: dict          # bench/traffic/<traffic>.json
    end_to_end: tuple      # the end-to-end metric entries this cell reports
    per_layer: tuple       # the per-layer metric entries this cell reports


def _reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # a per-layer metric without `workloads` goes wherever its end-to-end
    # metric is reported
    return metric.get("moves", metric["name"]) in e2e_names


def cell(name: str, bench: dict | None = None,
         root: pathlib.Path = ROOT) -> Cell:
    bench = bench if bench is not None else benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    e2e = tuple(m for m in bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _reports(m, name, e2e_names))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def files_cell(config: str, traffic: str) -> Cell:
    """A configuration under a traffic mix, read from their files alone,
    with no metrics: for tools that drive a mix not in BENCHMARK.json."""
    return Cell(name=f"{config}.{traffic}", chips=1,
                config=load_json(BENCH_DIR / "configs" / f"{config}.json"),
                traffic=load_json(BENCH_DIR / "traffic" / f"{traffic}.json"),
                end_to_end=(), per_layer=())
