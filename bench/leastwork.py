"""The least work one batch of a cell needs, and the chip's peaks.

The same functions hold for every engine:

* operations: 2 x SOPs performed (spikes x fan-out, the paper's unit),
  from the run's own exact counters;
* bytes: the network kind's `least_bytes(config, batch)`
  (`bench/networks/<kind>.py`): every distinct weight once per batch at
  log2(N) bits, its level tables (N words of W bits), the input spikes
  at 1 bit, and the output counts as int32.  Weights are counted once
  per batch, not per timestep, and a weight shared by many synapses
  once: a kernel may keep them resident across T, and counting only
  the useful work gives a share no implementation can push past the
  peak.
"""
from __future__ import annotations

import json
import pathlib

from bench import registry

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (have {sorted(table)})")
    return table[device_kind]


def least_ops(performed_sops: float) -> float:
    return 2.0 * performed_sops


def least_time(config: dict, batch: int, performed_sops: float,
               peak: dict) -> tuple[float, str]:
    """(seconds, "ops" | "bytes"): the larger bound and which it is."""
    t_ops = least_ops(performed_sops) / peak["bf16_flops_per_s"]
    t_bytes = (registry.network(config).least_bytes(config, batch)
               / peak["hbm_bytes_per_s"])
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
