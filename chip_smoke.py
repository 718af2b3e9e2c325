#!/usr/bin/env python3
"""Smoke run: the paper's 20-core SNN on a TPU, through the entry points a
user calls, checked against the interpretive reference on the host CPU.

With no arguments (one chip):

1. Device check: JAX's first device must be a TPU and the Pallas kernels
   must compile with Mosaic (no interpret mode), else exit non-zero.
2. The paper's configuration, `configs/snn_chip.ARCH`: layers
   2312-4096-1024-10, T=20, N=16-level W=8-bit codebooks, leak 0.9,
   threshold 1.0.  Weights are random from `--seed` and quantized once;
   event trains come from `data.synthetic.EventStream`.
3. `ChipSimulator(engine="compiled")` and `engine="fused"` (one shared
   mapping) run `run_batch` at B=8 and B=32.  Every output count must
   equal the reference engine's, run on the host CPU in this process, and
   every report's energy must agree with it within 1e-6.
4. `SnnServer` over the fused simulator serves 16 requests: all served,
   none degraded, no retry or fault, each prediction the argmax of
   `run_batch` on the same trains.

With `--four-chips` it runs only the multi-chip paths and what they are
compared with: the fleet board (`benchmarks/fleet_bench.FULL`) cores-
sharded over 4 chips against the compiled engine on one of them, and the
batch-sharded compiled and fused engines on 4 chips against 1.

Times printed are single-call smoke timings, not benchmarks.  The last
line of standard output is one JSON object naming the device.

Run:  python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

WEIGHT_GAIN = 3.0        # weight std = gain / sqrt(fan-in): ~6% hidden firing
BATCHES = (8, 32)
SERVE_REQUESTS = 16
SERVE_SLOTS = 8
ENERGY_REL_TOL = 1e-6


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def device_check(n_chips: int):
    import jax

    from repro.kernels.ops import interpret_default

    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX's first device is {devices[0].platform!r}")
    check(not interpret_default(), "Pallas kernels would run interpreted")
    check(len(devices) >= n_chips,
          f"needs {n_chips} chips, JAX sees {len(devices)}")
    return devices


def quantized_weights(sizes, cfg, seed: int):
    """Random weights from `seed`, quantized ONCE so every simulator (TPU
    and host CPU) programs the identical register tables."""
    import jax.numpy as jnp

    from repro.core import quant as Q

    rng = np.random.default_rng(seed)
    out = []
    for a, b in zip(sizes[:-1], sizes[1:]):
        w = rng.normal(0.0, WEIGHT_GAIN / np.sqrt(a), (a, b))
        q = Q.quantize(jnp.asarray(w, jnp.float32), cfg)
        out.append(Q.QuantizedTensor(
            idx=np.asarray(q.idx), codebook=np.asarray(q.codebook),
            scale=np.asarray(q.scale), group_axis_size=q.group_axis_size))
    return out


def build_sim(qweights, cfg, arch, engine: str, mapping=None):
    from repro.core.soc import ChipSimulator

    return ChipSimulator(qweights, quant_cfg=cfg, mapping=mapping,
                         engine=engine, leak=arch.leak,
                         threshold=arch.threshold, freq_hz=arch.freq_hz)


def reference_on_cpu(qweights, cfg, arch, trains):
    """The interpretive reference engine on the host CPU: the oracle."""
    import jax
    import jax.numpy as jnp

    with jax.default_device(jax.devices("cpu")[0]):
        ref = build_sim(qweights, cfg, arch, "reference")
        t0 = time.perf_counter()
        counts, reports = ref.run_batch(jnp.asarray(trains))
        counts = np.asarray(counts)
    print(f"reference (host CPU): {len(trains)} samples in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    return ref.mapping, counts, reports


def timed_batch(sim, trains):
    import jax.numpy as jnp

    t0 = time.perf_counter()
    counts, reports = sim.run_batch(jnp.asarray(trains))
    counts = np.asarray(counts.block_until_ready())
    return counts, reports, time.perf_counter() - t0


def engine_phase(name, sim, trains, ref_counts, ref_reports) -> list[str]:
    """run_batch at each smoke batch; returns the failures it found."""
    failures = []
    for b in BATCHES:
        counts, reports, first_s = timed_batch(sim, trains[:b])
        again, _, steady_s = timed_batch(sim, trains[:b])
        mismatch = int(np.sum(counts != ref_counts[:b]))
        energy_rel = max(
            abs(r.energy_pj - q.energy_pj) / max(abs(q.energy_pj), 1.0)
            for r, q in zip(reports, ref_reports[:b]))
        print(f"{name} B={b}: compile+first call {first_s:.3f} s, "
              f"steady batch {steady_s:.4f} s (smoke timing, not a "
              f"benchmark), pj_per_sop {reports[0].pj_per_sop:.6f}, "
              f"output spikes {int(counts.sum())}, mismatching "
              f"(sample, class) counts {mismatch}/{counts.size}, "
              f"energy rel err {energy_rel:.3e}", flush=True)
        if mismatch:
            failures.append(f"{name} B={b}: {mismatch} counts differ from "
                            f"the CPU reference")
        if not np.array_equal(counts, again):
            failures.append(f"{name} B={b}: a repeated batch differs")
        if energy_rel > ENERGY_REL_TOL:
            failures.append(f"{name} B={b}: energy rel err {energy_rel}")
    return failures


def serve_phase(sim, trains) -> None:
    """SnnServer over the fused simulator: every request served exactly as
    run_batch computes it, with no retry, fault or degraded result."""
    from repro.serve.snn_server import SnnRequest, SnnServer

    # the expected counts double as the warm-up of the slot-sized
    # executable, so no dispatch below pays a compile
    expected = np.concatenate([
        timed_batch(sim, trains[i:i + SERVE_SLOTS])[0]
        for i in range(0, len(trains), SERVE_SLOTS)])
    server = SnnServer(sim, batch_slots=SERVE_SLOTS)
    t0 = time.perf_counter()
    for i, ev in enumerate(trains):
        server.submit(SnnRequest(uid=i, events=ev))
    done = sorted(server.run(), key=lambda r: r.uid)
    serve_s = time.perf_counter() - t0
    m = server.metrics
    retries = m.get("snn_retries").value
    faults = m.get("snn_faults_injected").value
    degraded = m.get("snn_degraded_total").value
    print(f"server: {len(done)} requests in {serve_s:.4f} s (smoke timing), "
          f"statuses {sorted({r.status for r in done})}, retries {retries}, "
          f"faults {faults}, degraded {degraded}", flush=True)
    check(len(done) == len(trains), f"server completed {len(done)} of "
          f"{len(trains)} requests")
    check(all(r.status == "served" for r in done), "a request was not served")
    check(not any(r.degraded for r in done) and degraded == 0,
          "a request was served degraded")
    check(retries == 0 and faults == 0, "the server retried or saw a fault")
    for r in done:
        check(np.array_equal(r.spike_counts, expected[r.uid])
              and r.prediction == int(np.argmax(expected[r.uid])),
              f"request {r.uid}: served result differs from run_batch")


def one_chip(seed: int) -> None:
    from repro.configs.snn_chip import ARCH
    from repro.core.quant import CodebookConfig
    from repro.data.synthetic import EventStream

    cfg = CodebookConfig(n_levels=ARCH.weight_levels,
                         bit_width=ARCH.weight_bits)
    t0 = time.perf_counter()
    qweights = quantized_weights(ARCH.layer_sizes, cfg, seed)
    stream = EventStream(timesteps=ARCH.timesteps, seed=seed)
    check(stream.n_inputs == ARCH.layer_sizes[0],
          "EventStream width differs from the network's input layer")
    trains = np.asarray(stream.batch(max(BATCHES))[0])
    print(f"setup: weights {ARCH.layer_sizes} quantized N={cfg.n_levels} "
          f"W={cfg.bit_width}, trains {trains.shape} density "
          f"{trains.mean():.4f}, {time.perf_counter() - t0:.3f} s",
          flush=True)

    mapping, ref_counts, ref_reports = reference_on_cpu(
        qweights, cfg, ARCH, trains)
    check(ref_counts.sum() > 0, "the reference network never fires an output")

    failures = []
    sims = {}
    for name in ("compiled", "fused"):
        sims[name] = build_sim(qweights, cfg, ARCH, name, mapping=mapping)
        if name == "fused":
            layers = sims[name].fused_engine().codebook_layers
            check(layers == len(qweights),
                  f"fused engine runs {layers} of {len(qweights)} layers "
                  f"codebook-compressed")
        failures += engine_phase(name, sims[name], trains, ref_counts,
                                 ref_reports)
    check(not failures, "; ".join(failures))

    req_trains = np.asarray(stream.batch(SERVE_REQUESTS, step=1)[0])
    serve_phase(sims["fused"], req_trains)


def four_chips() -> None:
    """Cores-sharded fleet board, and batch sharding, on 4 chips."""
    import jax
    import jax.numpy as jnp

    from benchmarks.fleet_bench import FULL, sharded_equiv_study
    from repro import compiler as COMP
    from repro.compiler.ir import from_layer_sizes
    from repro.configs.snn_chip import ARCH
    from repro.core.engine import CompiledEngine, FusedEngine
    from repro.core.quant import CodebookConfig
    from repro.data.synthetic import EventStream

    t0 = time.perf_counter()
    spec = COMP.ChipSpec(neurons_per_core=FULL["neurons_per_core"],
                         max_domains=FULL["max_domains"])
    cn = COMP.compile_network(from_layer_sizes(FULL["sizes"]), spec, seed=0,
                              anneal_iters=FULL["anneal_iters"])
    print(f"fleet board: {len(FULL['sizes']) - 1} layers on "
          f"{cn.n_domains_used} domains, mapped in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    t0 = time.perf_counter()
    eq = sharded_equiv_study(FULL, cn)
    print(f"sharded vs compiled: {json.dumps(eq)} "
          f"({time.perf_counter() - t0:.3f} s with compiles, smoke timing)",
          flush=True)
    check(eq["n_shards"] == 4 and eq["ran_sharded"],
          f"the board ran on {eq['n_shards']} shards, not 4")
    check(eq["bit_identical"], "sharded outputs differ from compiled")
    check(eq["report_rel_err"] <= ENERGY_REL_TOL,
          f"sharded energy rel err {eq['report_rel_err']}")

    cfg = CodebookConfig(n_levels=ARCH.weight_levels,
                         bit_width=ARCH.weight_bits)
    qweights = quantized_weights(ARCH.layer_sizes, cfg, 0)
    trains = jnp.asarray(EventStream(timesteps=ARCH.timesteps).batch(8)[0])
    sim = build_sim(qweights, cfg, ARCH, "compiled")
    for cls in (CompiledEngine, FusedEngine):
        wide, narrow = cls(sim), cls(sim, shard=False)
        t0 = time.perf_counter()
        y4 = jax.block_until_ready(wide.run_raw(trains))
        y1 = jax.block_until_ready(narrow.run_raw(trains))
        same = set(y4) == set(y1) and all(
            np.array_equal(np.asarray(y4[k]), np.asarray(y1[k])) for k in y4)
        print(f"{cls.__name__} batch-sharded on {len(jax.devices())} chips "
              f"vs 1: sharded={wide.last_run_sharded}, bit_identical={same} "
              f"({time.perf_counter() - t0:.3f} s with compiles, smoke "
              f"timing)", flush=True)
        check(wide.last_run_sharded and not narrow.last_run_sharded,
              f"{cls.__name__}: batch sharding did not engage as expected")
        check(same, f"{cls.__name__}: 4-chip outputs differ from 1 chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and event trains")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded paths")
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = device_check(4 if args.four_chips else 1)
    print(f"device: {devices[0].device_kind} x{len(devices)}, compile cache "
          f"{cache}", flush=True)
    if args.four_chips:
        four_chips()
    else:
        one_chip(args.seed)
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
