"""Closed loop: back-to-back `ChipSimulator.run_batch` calls.

One caller sends the next batch when the last one has completed: its
output counts and its per-sample `ChipReport`s are on the host.  Batches
cycle through a pool of distinct trains made at set-up; the program
caches no result, so a repeated batch is computed again in full.

Traffic keys: `engine`, `batch`, `pool_batches`.
"""
from __future__ import annotations

import time

import numpy as np

from bench import check, reference, registry


def pool_size(traffic: dict) -> int:
    return int(traffic["batch"]) * int(traffic["pool_batches"])


def pool(trains: np.ndarray, traffic: dict) -> list[np.ndarray]:
    b = int(traffic["batch"])
    return [trains[i:i + b] for i in range(0, len(trains) - b + 1, b)]


def warm(sim, batches, traffic: dict) -> None:
    """Compile and run the cell's one shape twice."""
    for x in batches[:2]:
        np.asarray(sim.run_batch(x)[0])


def drive(sim, batches, traffic: dict, seconds: float, seed: int,
          annotate) -> dict:
    """Run until `seconds` have passed; the window ends when the last call
    started inside it completes."""
    calls = []
    t0 = time.perf_counter()
    t = t0
    i = 0
    while t - t0 < seconds:
        k = i % len(batches)
        with annotate("bench.run_batch"):
            counts, reports = sim.run_batch(batches[k])
            counts = np.asarray(counts)
        with annotate("bench.record"):
            calls.append((k, counts, check.report_fields(reports)))
        i += 1
        t = time.perf_counter()
    return {"window_s": t - t0, "calls": calls,
            "batch": int(traffic["batch"]),
            "trains": sum(len(c[1]) for c in calls),
            "performed_sops": float(sum(
                c[2][:, reference.FIELDS.index("performed_sops")].sum()
                for c in calls))}


def outcome(record: dict) -> tuple[int, int]:
    return record["trains"], 0


def correctness(record: dict, batches, layers, plan: dict, config: dict,
                traffic: dict, control: bool = False) -> dict:
    """Compare every call of the window with the network kind's reference
    (or, with `control`, its control with its reference), computed once
    per batch of the pool."""
    net = registry.network(config)
    calls = record["calls"]
    numbers = {"differing_trains": 0, "energy_rel_gap": 0.0,
               "wall_rel_gap": 0.0}
    for k in sorted({c[0] for c in calls}):
        ref_counts, ref_fields = net.reference(layers, batches[k], config,
                                               plan)
        mine = [c for c in calls if c[0] == k]
        if control:
            got = net.reference(layers, batches[k], config, plan,
                                control=True)
            mine = [(k, *got)] * len(mine)
        for _, counts, fields in mine:
            numbers = check.merge(numbers, check.compare_trains(
                counts, fields, ref_counts, ref_fields,
                compare_skips=traffic["engine"] == "fused"))
    return numbers
