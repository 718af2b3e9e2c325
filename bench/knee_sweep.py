#!/usr/bin/env python3
"""Sweep the offered rate of a serve cell to find its knee: the highest
rate served with no growing backlog.  One process builds the cell once
and drives the cell's open loop at each rate in turn.

    python3 bench/knee_sweep.py --config nmnist_mlp \
        --traffic serve_poisson --seed 1 --seconds 10 \
        --rates 280,320,360,400,450

Prints one JSON line per rate: requests offered and served, latency
percentiles from due time, the share of requests later than 50 ms, the
backlog (requests due but not yet answered) at the middle and at the end
of the window, and the full (generation 2) garbage collections of the
host process inside the window with the longest one.  A backlog that
grows from the middle to the end marks a rate above the knee.

With `--stall-ms M`, every submit, step or wait of the driver that lasts
longer than M ms is printed too (`"stall"` lines): when it started, how
long it took, the process's CPU seconds, context switches and page
faults inside it, the garbage collections inside it, and the Python
stacks of the main thread that a sampling thread saw inside it, with the
longest gap between two samples.  A gap as long as the stall means the
process stood still (descheduled, or in C code that holds the GIL); CPU
seconds near the stall's length mean it was working.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import gc
import json
import pathlib
import resource
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import registry  # noqa: E402
from bench import run as R  # noqa: E402


def backlog(rec: dict, t: float) -> int:
    done = rec["due"] + rec["latency_ms"] / 1e3
    return int(np.sum((rec["due"] <= t) & (done > t)))


class FullCollections:
    """Durations of the host's generation-2 garbage collections."""

    def __init__(self):
        self.ms: list[float] = []
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.ms.append((time.perf_counter() - self._t0) * 1e3)
            self._t0 = None


def _usage() -> tuple:
    r = resource.getrusage(resource.RUSAGE_SELF)
    return (r.ru_utime + r.ru_stime, r.ru_nvcsw, r.ru_nivcsw, r.ru_minflt,
            r.ru_majflt)


def _stack(frame, depth: int = 6) -> str:
    names = []
    while frame is not None and len(names) < depth:
        code = frame.f_code
        names.append(f"{pathlib.Path(code.co_filename).name}:"
                     f"{frame.f_lineno} {code.co_name}")
        frame = frame.f_back
    return " < ".join(names)


class StallProbe:
    """Times every annotated block of the driver; keeps those longer than
    `threshold_ms` with what the process did inside them."""

    def __init__(self, threshold_ms: float, period_s: float = 0.002):
        self.threshold_ms = threshold_ms
        self.period_s = period_s
        self.main = threading.main_thread().ident
        # lists, read by slicing: a slice copies in one step, where
        # iterating could meet an append from the sampler or a collection
        self.samples: list[tuple[float, str]] = []
        self.gcs: list[tuple[float, float, int]] = []
        self.stalls: list[dict] = []
        self.t0 = time.perf_counter()
        self._gc_start = None
        self._stop = threading.Event()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._sample, daemon=True)
        self._thread.start()

    def _sample(self) -> None:
        while not self._stop.wait(self.period_s):
            frame = sys._current_frames().get(self.main)
            self.samples.append((time.perf_counter(), _stack(frame)))

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.gcs.append((self._gc_start, time.perf_counter(),
                             info.get("generation")))
            self._gc_start = None

    @contextlib.contextmanager
    def annotate(self, name: str):
        t0, u0 = time.perf_counter(), _usage()
        try:
            yield
        finally:
            t1, u1 = time.perf_counter(), _usage()
            if (t1 - t0) * 1e3 > self.threshold_ms:
                self._keep(name, t0, t1, u0, u1)

    def _keep(self, name, t0, t1, u0, u1) -> None:
        inside = [(t, s) for t, s in self.samples[:] if t0 <= t <= t1]
        ts = [t0] + [t for t, _ in inside] + [t1]
        cpu, vol, invol, minflt, majflt = (b - a for a, b in zip(u0, u1))
        self.stalls.append({
            "in": name, "at_s": t0 - self.t0, "ms": (t1 - t0) * 1e3,
            "cpu_ms": cpu * 1e3, "voluntary_switches": vol,
            "involuntary_switches": invol, "minor_faults": minflt,
            "major_faults": majflt,
            "gc_ms": sum((min(b, t1) - max(a, t0)) * 1e3
                         for a, b, _ in self.gcs[:] if b > t0 and a < t1),
            "samples": len(inside),
            "max_sample_gap_ms": max(b - a for a, b in zip(ts, ts[1:])) * 1e3,
            "stacks": collections.Counter(
                s for _, s in inside).most_common(3)})

    def close(self) -> None:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--stall-ms", type=float, default=0.0)
    args = ap.parse_args(argv)

    import jax

    cell = registry.files_cell(args.config, args.traffic)
    R.enable_compile_cache()
    R.device_check(cell.chips)
    driver, sim, state, _, _ = R.build(cell, args.seed, {})
    driver.warm(sim, state, cell.traffic)
    full = FullCollections()
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_per_s=rate)
        full.ms.clear()
        probe = StallProbe(args.stall_ms) if args.stall_ms else None
        rec = driver.drive(sim, state, traffic, args.seconds, args.seed,
                           probe.annotate if probe else
                           jax.profiler.TraceAnnotation)
        if probe:
            probe.close()
            for stall in probe.stalls:
                print(json.dumps({"rate_per_s": rate, "stall": stall}),
                      flush=True)
        lat = np.sort(rec["latency_ms"])
        served = int(np.isfinite(lat).sum())
        pct = {f"p{q}": float(lat[max(0, int(np.ceil(q / 100 * len(lat)))
                                      - 1)]) for q in (50, 95, 99)}
        total, n = rec["occupancy"]
        print(json.dumps({
            "rate_per_s": rate, "offered": len(lat), "served": served,
            "served_per_s": served / args.seconds, **pct,
            "backlog_mid": backlog(rec, args.seconds / 2),
            "backlog_end": backlog(rec, args.seconds),
            "drained_s": rec["drained_s"],
            "occupancy": total / n / rec["slots"] if n else None,
            "late_p99_ms": float(np.quantile(rec["late_s"], 0.99) * 1e3),
            "share_over_50ms": float(np.mean(lat > 50.0)),
            "full_gc": len(full.ms),
            "full_gc_max_ms": max(full.ms, default=0.0),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
