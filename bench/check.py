"""The comparison that decides `correct`.

What the timed path produced is held against the network kind's
`reference` (`bench/networks/<kind>.py`) on the same spike trains, once
the window has closed:

* batch cells: every train of every `run_batch` call of the window.
  - `differing_trains`: trains whose output spike counts, or any exact
    counter of their `ChipReport` (`EXACT`), differ from the
    reference's.
  - `energy_rel_gap`: the widest relative gap, over those trains, of the
    core, NoC, RISC-V and total energy (`ENERGY`): f64 functions of exact
    counts, equal but for the order of a few sums.
  - `wall_rel_gap`: the same for the wall cycles and the NoC contention
    cycles in them (`WALL`).
* serve cells: every served request.  A request differs when its spike
  counts or its prediction (the argmax) differ.  Numbers compared:
  `differing_requests`, `energy_rel_gap` of each served request's total
  energy, and `never_completed` (requests due in the window that got no
  answer at all, a minute past its close; a shed request is an answer,
  counted in `failed`).

Each number has its limit in the configuration's file (`limits`); the
readings each was set from are in PERF.md.
"""
from __future__ import annotations

import numpy as np

from bench.reference import FIELDS

ENERGY = ("core_energy_pj", "noc_energy_pj", "riscv_energy_pj", "energy_pj")
WALL = ("wall_cycles", "noc_contention_cycles")
EXACT = tuple(f for f in FIELDS if f not in ENERGY + WALL)


def report_fields(reports) -> np.ndarray:
    """The program's per-sample `ChipReport`s, in `reference.FIELDS`
    order."""
    return np.array([[r.stats.spikes_in, r.stats.performed_sops,
                      r.stats.neurons_touched, r.stats.spikes_routed,
                      r.stats.nominal_sops, r.stats.spike_words_skipped,
                      r.stats.noc_hops, r.core_energy_pj, r.noc_energy_pj,
                      r.riscv_energy_pj, r.energy_pj, r.wall_cycles,
                      r.stats.noc_contention_cycles] for r in reports],
                    np.float64)


def rel_gap(got, ref) -> float:
    """The widest |got - ref| / |ref| (0 where both are 0)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.size == 0:
        return 0.0
    gap = np.abs(got - ref)
    return float(np.max(np.where(gap == 0, 0.0,
                                 gap / np.maximum(np.abs(ref), 1e-300))))


def _cols(names) -> list[int]:
    return [FIELDS.index(f) for f in names]


def compare_trains(counts, fields, ref_counts, ref_fields,
                   compare_skips: bool) -> dict:
    """-> {differing_trains, energy_rel_gap, wall_rel_gap} of one batch."""
    counts = np.asarray(counts, np.float64)
    exact = _cols(f for f in EXACT
                  if compare_skips or f != "spike_words_skipped")
    differ = (np.any(counts != ref_counts, axis=-1)
              | np.any(fields[:, exact] != ref_fields[:, exact], axis=-1))
    return {"differing_trains": int(differ.sum()),
            "energy_rel_gap": rel_gap(fields[:, _cols(ENERGY)],
                                      ref_fields[:, _cols(ENERGY)]),
            "wall_rel_gap": rel_gap(fields[:, _cols(WALL)],
                                    ref_fields[:, _cols(WALL)])}


def merge(a: dict, b: dict) -> dict:
    """Two batches' numbers as one: counts add, gaps take the wider."""
    return {k: a[k] + b[k] if k.startswith("differing") else max(a[k], b[k])
            for k in a}


def differing_requests(counts, predictions, ref_counts) -> np.ndarray:
    counts = np.asarray(counts, np.float64)
    return (np.any(counts != ref_counts, axis=-1)
            | (np.asarray(predictions) != np.argmax(ref_counts, axis=-1)))


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value": v, "limit": l}})."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
