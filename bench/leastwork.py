"""The least work one batch of a cell needs, and the chip's peaks.

The same functions hold for every engine:

* operations: 2 x SOPs performed (spikes x fan-out, the paper's unit),
  from the run's own exact counters;
* bytes: every weight once per batch at log2(N) bits, one level table
  per layer (N words of W bits), the input spikes at 1 bit, and the
  output counts as int32.  Weights are counted once per batch, not per
  timestep: a kernel may keep them resident across T, and counting only
  the useful work gives a share no implementation can push past the
  peak.
"""
from __future__ import annotations

import json
import math
import pathlib

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


def least_bytes(config: dict, batch: int) -> float:
    sizes = [int(s) for s in config["layer_sizes"]]
    n_levels, wbits = int(config["weight_levels"]), int(config["weight_bits"])
    idx_bits = math.ceil(math.log2(n_levels))
    weights = sum(a * b for a, b in zip(sizes[:-1], sizes[1:])) * idx_bits / 8
    tables = (len(sizes) - 1) * n_levels * wbits / 8
    spikes = batch * int(config["timesteps"]) * sizes[0] / 8
    outputs = batch * sizes[-1] * 4
    return weights + tables + spikes + outputs


def least_time(config: dict, batch: int, performed_sops: float,
               peak: dict) -> tuple[float, str]:
    """(seconds, "ops" | "bytes"): the larger bound and which it is."""
    t_ops = least_ops(performed_sops) / peak["bf16_flops_per_s"]
    t_bytes = least_bytes(config, batch) / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
