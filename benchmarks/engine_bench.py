"""Engine benchmark: three-way compiled / fused / reference comparison on
an NMNIST-scale MLP, plus a (batch, T, sparsity) sweep of the two array
engines and the HBM-traffic accounting of the fused operands.

Acceptance targets:
  * compiled >= 10x the interpretive reference at batch 32, T=20 (PR 2);
  * the fused Pallas path's HBM bytes per timestep (weights as int8
    codebook indexes + RegisterTable level values, spikes as uint16
    16-spike words) drop >= 4x vs the compiled engine's dense f32 weight
    constants + f32 spike lanes — hardware-independent, asserted here;
  * fused wall-clock >= the compiled path (interpret mode on CPU; on a
    real TPU the zero-skip + bitpacking target is >= 2x, tracked via the
    fused_speedup_vs_compiled trajectory metric).

Run:  PYTHONPATH=src python benchmarks/engine_bench.py [--batch 32]
      [--timesteps 20] [--no-sweep] [--out engine_bench.json]
"""
from __future__ import annotations

import argparse
import json
import time

import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.timing import measure
except ImportError:          # script mode: python benchmarks/engine_bench.py
    from timing import measure

NMNIST_LAYERS = (2312, 512, 10)      # 34x34x2 events -> hidden -> classes
INPUT_DENSITY = 0.10                 # NMNIST-like event sparsity regime
SWEEP = (                            # (batch, timesteps, input density)
    (8, 10, 0.10),
    (32, 20, 0.10),
    (32, 20, 0.02),                  # ~98% sparse: the zero-skip regime
)


def build_sims(seed: int = 0, quantized: bool = True):
    from repro.core.quant import CodebookConfig
    from repro.core.soc import ChipSimulator

    rng = np.random.default_rng(seed)
    weights = [
        jnp.asarray(rng.normal(0, 0.4, (NMNIST_LAYERS[i], NMNIST_LAYERS[i + 1])),
                    jnp.float32)
        for i in range(len(NMNIST_LAYERS) - 1)
    ]
    qcfg = CodebookConfig(n_levels=16, bit_width=8) if quantized else None
    ref = ChipSimulator(weights, freq_hz=100e6, engine="reference",
                        quant_cfg=qcfg)
    comp = ChipSimulator(weights, freq_hz=100e6, engine="compiled",
                         mapping=ref.mapping, quant_cfg=qcfg)
    fused = ChipSimulator(weights, freq_hz=100e6, engine="fused",
                          mapping=ref.mapping, quant_cfg=qcfg)
    return ref, comp, fused


def make_trains(batch: int, timesteps: int, density: float, seed: int = 0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.random((batch, timesteps, NMNIST_LAYERS[0])) < density,
        jnp.float32)


def _time_batch(sim, trains, reps: int = 5):
    """Stabilized timing (warmup + median-of-reps, see benchmarks.timing)
    plus the last run's (counts, reports)."""
    state = {}

    def run():
        counts, reports = sim.run_batch(trains)
        counts.block_until_ready()
        state["counts"], state["reports"] = counts, reports

    timing = measure(run, warmup=1, reps=reps)
    return timing, state["counts"], state["reports"]


def hbm_bytes_per_step_compiled(sim, batch: int) -> int:
    """The compiled engine's per-timestep weight + spike traffic: every
    layer's dense f32 matrix (scan constant) + f32 spike lanes."""
    return sum(int(w.shape[0]) * int(w.shape[1]) * 4
               + batch * int(w.shape[0]) * 4
               for w in sim.weights)


def main(emit, batch: int = 32, timesteps: int = 20, sweep: bool = True) -> dict:
    import jax

    ref, comp, fused = build_sims()
    trains = make_trains(batch, timesteps, INPUT_DENSITY)

    comp_t, counts_c, reports_c = _time_batch(comp, trains)
    fused_t, counts_f, reports_f = _time_batch(fused, trains)
    comp_first, comp_s = comp_t.first_s, comp_t.median_s
    fused_first, fused_s = fused_t.first_s, fused_t.median_s

    # the interpretive reference is too slow to repeat: one timed call
    t0 = time.perf_counter()
    counts_r, reports_r = ref.run_batch(trains)
    reference_s = time.perf_counter() - t0

    # spikes must agree exactly on every backend; the current matmuls run
    # at zspe.CURRENT_PRECISION, so a TPU keeps the f32 weights too
    assert np.array_equal(np.asarray(counts_c), np.asarray(counts_r)), \
        "compiled/reference spike mismatch"
    assert np.array_equal(np.asarray(counts_f), np.asarray(counts_r)), \
        "fused/reference spike mismatch"

    fe = fused.fused_engine()
    # HBM accounting at the canonical batch (32) so the trajectory metric
    # is invariant to the CLI --batch used for the wall-clock smoke
    HBM_REF_BATCH = 32
    hbm_c = hbm_bytes_per_step_compiled(comp, HBM_REF_BATCH)
    hbm_f = fe.hbm_bytes_per_step(HBM_REF_BATCH)
    hbm_reduction = hbm_c / max(hbm_f, 1)
    assert hbm_reduction >= 4.0, (
        f"fused HBM bytes/step must drop >= 4x vs dense f32 constants "
        f"(got {hbm_reduction:.2f}x: {hbm_c} -> {hbm_f})")
    assert fe.codebook_layers == len(fused.weights), \
        "fused path must run every layer codebook-compressed"

    speedup = reference_s / max(comp_s, 1e-9)
    fused_speedup = reference_s / max(fused_s, 1e-9)
    fused_vs_comp = comp_s / max(fused_s, 1e-9)
    skip_words = float(np.mean(
        [r.stats.spike_words_skipped for r in reports_f]))
    table = {
        "layer_sizes": list(NMNIST_LAYERS),
        "batch": batch,
        "timesteps": timesteps,
        "reference_s": round(reference_s, 4),
        "compiled_s": round(comp_s, 4),
        "compiled_spread": round(comp_t.spread, 3),
        "compile_and_first_s": round(comp_first, 4),
        "timing_reps": len(comp_t.times_s),
        "speedup": round(speedup, 2),
        "samples_per_s_compiled": round(batch / max(comp_s, 1e-9), 1),
        "samples_per_s_reference": round(batch / max(reference_s, 1e-9), 1),
        "pj_per_sop": round(reports_c[0].pj_per_sop, 4),
        # fused engine (PR 4)
        "fused_s": round(fused_s, 4),
        "fused_spread": round(fused_t.spread, 3),
        "fused_compile_and_first_s": round(fused_first, 4),
        "samples_per_s_fused": round(batch / max(fused_s, 1e-9), 1),
        "fused_speedup": round(fused_speedup, 2),
        "fused_speedup_vs_compiled": round(fused_vs_comp, 3),
        "fused_pj_per_sop": round(reports_f[0].pj_per_sop, 4),
        "fused_codebook_layers": fe.codebook_layers,
        "fused_spike_words_skipped_mean": round(skip_words, 1),
        "hbm_bytes_per_step_compiled": hbm_c,
        "hbm_bytes_per_step_fused": hbm_f,
        "hbm_reduction_fused": round(hbm_reduction, 2),
        "sharded": fe.last_run_sharded,
        "n_devices": len(jax.devices()),
    }

    if sweep:
        rows = []
        for b, t, dens in SWEEP:
            tr = make_trains(b, t, dens, seed=b + t)
            ct, cc, _ = _time_batch(comp, tr, reps=3)
            ft_, cf, frep = _time_batch(fused, tr, reps=3)
            cs, fs = ct.median_s, ft_.median_s
            assert np.array_equal(np.asarray(cc), np.asarray(cf))
            rows.append({
                "batch": b, "timesteps": t, "sparsity": round(1 - dens, 3),
                "compiled_s": round(cs, 4), "fused_s": round(fs, 4),
                "fused_vs_compiled": round(cs / max(fs, 1e-9), 3),
                "pj_per_sop": round(frep[0].pj_per_sop, 4),
            })
        table["sweep"] = rows

    emit("engine_batched_vs_reference", comp_s * 1e6,
         {"speedup": table["speedup"],
          "samples_per_s": table["samples_per_s_compiled"]})
    emit("engine_fused_vs_compiled", fused_s * 1e6,
         {"fused_vs_compiled": table["fused_speedup_vs_compiled"],
          "hbm_reduction": table["hbm_reduction_fused"]})
    return table


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src"))
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--timesteps", type=int, default=20)
    ap.add_argument("--no-sweep", action="store_true",
                    help="skip the (batch, T, sparsity) sweep")
    ap.add_argument("--out", default=None,
                    help="write the result table to this JSON file")
    args = ap.parse_args()

    def emit(name, us, derived):
        print(f"{name},{us:.1f},{json.dumps(derived)}")

    table = main(emit, batch=args.batch, timesteps=args.timesteps,
                 sweep=not args.no_sweep)
    print(json.dumps(table, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
        print(f"# -> {args.out}", file=sys.stderr)
