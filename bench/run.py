#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up builds the cell from `--seed` through its configuration's
network kind (`bench/networks/<kind>.py`: codebook weights on the
device, the `ChipSimulator` with its mapping and the cell's engine) and
its input generator (spike trains on the host), and compiles and warms
the cell's one shape.  The program holds the weights as constants, so
its engine programs differ from seed to seed: they compile in every
run, as for any new network, and are kept out of the persistent cache
(`cache_writes_off`), so that set-up does the same work whether a seed
ran before or not.  Then the cell's traffic driver runs for
`--seconds`, with no compilation inside the window.  `--trace 0`
reports the cell's end-to-end metrics; `--trace 1` runs the window
under the profiler and reports its per-layer metrics instead.  After
the window what the timed path produced is compared with the plain
reference (the network kind's `reference`, built on
`bench/reference.py`) on the host: every answer of the window, against
the reference of its trains; that decides `correct`.

Standard error ends with the numbers compared, each beside its limit;
the last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import registry, tracing, workload  # noqa: E402
from bench.record import RunRecord  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache, at a fixed path inside the checkout (or
    where `JAX_COMPILATION_CACHE_DIR` says).  Every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@contextlib.contextmanager
def cache_writes_off():
    """Programs compiled inside are not written to the persistent cache
    (what is in it is still read)."""
    import jax

    key = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, key)
    jax.config.update(key, 1e12)
    try:
        yield
    finally:
        jax.config.update(key, before)


def device_check(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX's first device is "
                         f"{devices[0].platform!r}; no result")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}; no result")
    return devices


class CompileCounter:
    """Counts XLA compilations (and loads from the persistent cache)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1


def build(cell: registry.Cell, seed: int, split: dict):
    """Weights, trains, the simulator of a cell and its mapping as data,
    each through the configuration's network kind
    (`registry.network`); `split` collects the seconds of each set-up
    phase."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    net = registry.network(cfg)
    driver = registry.load_module("drivers", traffic["driver"])
    t = time.perf_counter()
    program_w, layers = net.make(cfg, seed)
    jax.block_until_ready(program_w)
    split["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    state = driver.pool(workload.make_trains(cfg, driver.pool_size(traffic),
                                             seed), traffic)
    split["inputs"] = time.perf_counter() - t
    t = time.perf_counter()
    sim = net.simulator(cfg, traffic, program_w)
    split["simulator_and_lowering"] = time.perf_counter() - t
    t = time.perf_counter()
    plan = net.plan(sim, cfg)
    split["mapping_plan"] = time.perf_counter() - t
    del program_w
    return driver, sim, state, layers, plan


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device_kind: str) -> dict:
    """Set up, drive the window, compare; -> the result line."""
    import jax

    from bench import check, leastwork

    peak = leastwork.peaks(device_kind)
    compiles = CompileCounter()
    # process start to here: imports, the TPU runtime, the device check
    split: dict[str, float] = {"start": time.perf_counter() - t_start}
    driver, sim, state, layers, plan = build(cell, seed, split)
    t = time.perf_counter()
    with cache_writes_off():
        driver.warm(sim, state, cell.traffic)
    split["compile_and_warmup"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    log("setup_s split: " + ", ".join(f"{k} {v:.3f} s"
                                      for k, v in split.items())
        + f" (total {setup_s:.3f} s, {compiles.n} compilations)")

    before = compiles.n
    ctx = tracing.capture() if trace else contextlib.nullcontext()
    with ctx as events:
        rec = driver.drive(sim, state, cell.traffic, seconds, seed,
                           jax.profiler.TraceAnnotation)
    in_window = compiles.n - before
    if in_window:
        raise RuntimeError(f"{in_window} compilations inside the window")
    log(f"window: {rec['window_s']:.3f} s, 0 compilations inside it")
    mem = jax.devices()[0].memory_stats() or {}
    device = {"platform": jax.devices()[0].platform, "kind": device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    summary = tracing.reduce(events) if trace else None
    if trace:
        if summary is None:
            raise RuntimeError("the trace holds no device operation")
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        log(f"trace: busy {summary['busy_s']:.6f} s of "
            f"{summary['window_s']:.6f} s on {summary['devices']} "
            f"device(s), {summary['gaps']} idle gaps; idle by host "
            f"activity {json.dumps(summary['idle_by_host'])}; longest gaps "
            f"start at {json.dumps(summary['longest_gap_starts_s'])} s")
    if "late_s" in rec and len(rec["late_s"]):
        late = 1e3 * rec["late_s"]
        log(f"generator lateness over {len(late)} requests: median "
            f"{np.median(late):.3f} ms, p99 {np.quantile(late, 0.99):.3f} "
            f"ms, max {late.max():.3f} ms")
    if "calls" in rec and rec["calls"]:
        n = len(rec["calls"])
        lt, bound = leastwork.least_time(cell.config, rec["batch"],
                                         rec["performed_sops"] / n, peak)
        least_bytes = registry.network(cell.config).least_bytes(
            cell.config, rec["batch"])
        log(f"roofline: least time per batch {lt * 1e6:.3f} us, "
            f"{bound}-bound ({rec['performed_sops'] / n:.0f} SOPs, "
            f"{least_bytes:.0f} bytes per batch)")

    run = RunRecord(config=cell.config, traffic=cell.traffic, seed=seed,
                    setup_s=setup_s, drive=rec, trace=summary, peak=peak)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = registry.load_module("metrics", m["name"]).read(run)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   f"nothing")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    attempted, failed = driver.outcome(rec)
    # the reference runs on the host once the program's state is freed
    del sim
    gc.collect()
    t = time.perf_counter()
    numbers = driver.correctness(rec, state, layers, plan, cell.config,
                                 cell.traffic)
    correct, checks = check.verdict(numbers, cell.config["limits"])
    log(f"reference comparison: {time.perf_counter() - t:.3f} s")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["longest_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = registry.cell(args.workload)
    cache = enable_compile_cache()
    devices = device_check(cell.chips)
    log(f"device: {devices[0].device_kind} x{len(devices)}, compile cache "
        f"{cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, devices[0].device_kind)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
