"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; each module's `main(emit)`
also returns its full table (dumped to benchmarks/results.json).

``--out BENCH.json`` additionally writes the **bench trajectory**: a
schema-stable flat metric map (see `trajectory()`) that
scripts/bench_compare.py diffs against the committed baseline
(BENCH_pr3.json) to fail CI on >20% regressions in engine throughput or
pJ/SOP.  Keys are append-only: removing or renaming one is itself a CI
failure, so the trajectory stays comparable across PRs.

Sections run fault-tolerantly: a raising section records an ``error``
entry (nulling its trajectory metrics, which any gated metric turns into
a failure) and the rest still run; the harness exits nonzero at the end
if any section failed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

TRAJECTORY_SCHEMA_VERSION = 1

SECTIONS = ("fig3", "fig5", "noc", "compiler", "engine", "deploy", "fig6",
            "table1", "kernels", "roofline", "telemetry", "serve", "fleet",
            "fault", "learn")


def lane() -> str:
    """Which execution lane produced this trajectory.  Timing metrics are
    only comparable within a lane: Pallas interpret-mode on CPU and real
    device execution differ by orders of magnitude, so bench_compare
    refuses to diff across lanes (see scripts/bench_compare.py)."""
    from repro.kernels.ops import interpret_default

    return "interpret" if interpret_default() else "device"


def provenance() -> dict:
    """Host/runtime fingerprint recorded next to the trajectory so a
    regression report can be read against *where* it was measured."""
    import platform

    import jax

    return {
        "platform": platform.platform(),
        "machine": platform.machine(),
        "python": platform.python_version(),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
        "cpu_count": os.cpu_count(),
    }


def trajectory(results: dict) -> dict:
    """Flatten the full results into the schema-stable metric map.

    Every key must always be present (None when its section was skipped);
    bench_compare treats a missing/None gated metric as a failure.
    """
    eng = results.get("engine") or {}
    tel = results.get("telemetry") or {}
    tel_cap = tel.get("capture") or {}
    tel_srv = tel.get("serve") or {}
    srv_sweep = (results.get("serve") or {}).get("sweep") or {}
    comp = results.get("compiler") or {}
    t1 = results.get("table1") or {}
    dep = results.get("deploy") or {}
    noc = results.get("noc") or {}
    noc_eng = noc.get("engine") or {}
    nm = next((r for r in t1.get("workloads", [])
               if str(r.get("workload", "")).startswith("NMNIST")), {})
    anneal = next((r for r in comp.get("mapping_cost", [])
                   if r.get("strategy") == "anneal"), {})
    metrics = {
        # engine throughput (speedup is same-host-normalized: compiled vs
        # reference on identical hardware, so it compares across machines)
        "engine.speedup": eng.get("speedup"),
        "engine.pj_per_sop": eng.get("pj_per_sop"),
        "engine.samples_per_s_compiled": eng.get("samples_per_s_compiled"),
        "engine.compiled_s": eng.get("compiled_s"),
        # fused Pallas engine (PR 4): same-host ratio vs compiled, energy
        # parity, and the hardware-independent HBM-traffic reduction of
        # the codebook-word + spike-word operands
        "engine.fused_speedup_vs_compiled":
            eng.get("fused_speedup_vs_compiled"),
        "engine.samples_per_s_fused": eng.get("samples_per_s_fused"),
        "engine.fused_pj_per_sop": eng.get("fused_pj_per_sop"),
        "engine.hbm_reduction_fused": eng.get("hbm_reduction_fused"),
        # chip energy model at the paper's NMNIST operating point
        "chip.nmnist_sim_pj_per_sop": nm.get("sim_pj_per_sop"),
        "chip.nmnist_model_pj_per_sop": nm.get("model_chip_pj_per_sop"),
        # mapping compiler quality
        "compiler.anneal_improvement": anneal.get("vs_contiguous"),
        # NoC contention (PR 5): saturation onset of the fullerene fabric,
        # its margin over the 4x8 mesh under identical uniform traffic,
        # the engine-level contention share of wall cycles, and the
        # source-exactness probe (equal spike totals, different source
        # cores, different NoC energy — 0.0 would mean the accounting
        # regressed to a split heuristic)
        "noc.contention_saturation_fullerene":
            (noc.get("saturation_inject_rate") or {}).get("fullerene"),
        "noc.contention_saturation_ratio_vs_mesh":
            noc.get("saturation_ratio_vs_mesh"),
        "noc.contention_wall_share": noc_eng.get("contention_wall_share"),
        "noc.source_exact_delta":
            (noc_eng.get("source_exact_probe") or {}).get("relative_delta"),
        # train->deploy pipeline energy parity
        "deploy.pj_per_sop_regularized": dep.get("regularized_pj_per_sop"),
        "deploy.pj_per_sop_baseline": dep.get("baseline_pj_per_sop"),
        "deploy.pj_per_sop_saving": dep.get("pj_per_sop_saving"),
        "deploy.accuracy_chip_regularized": dep.get("regularized_accuracy_chip"),
        "deploy.claim_reg_beats_baseline": (
            None if "claim_reg_beats_baseline" not in dep
            else float(bool(dep["claim_reg_beats_baseline"]))),
        # telemetry subsystem (PR 6): trace capture must stay bounded;
        # serve latency quantiles are informational (ungated) but their
        # presence is what the CI telemetry-smoke job checks
        "telemetry.capture_overhead_x": tel_cap.get("capture_overhead_x"),
        "serve.request_latency_p50_ms": tel_srv.get("p50_ms"),
        "serve.request_latency_p99_ms": tel_srv.get("p99_ms"),
        # serving tier (PR 7): sustained-load sweep of the continuous-
        # batching server.  Throughput/p99 are host wall-clock (timing
        # threshold); shed_rate is recorded at the deep-overload point
        # (3x capacity) where bounded admission makes it structurally
        # nonzero — a zero here would mean shed accounting broke.  The
        # saturation ratio vs the drain-loop baseline is same-host
        # normalized like engine.speedup.
        "serve.throughput_eps": srv_sweep.get("throughput_eps"),
        "serve.p99_ms": srv_sweep.get("p99_ms_low_rate"),
        "serve.shed_rate": srv_sweep.get("shed_rate_overload"),
        "serve.saturation_ratio_vs_drain":
            srv_sweep.get("saturation_ratio_vs_drain"),
    }
    # hierarchical compiler + cores-axis sharded engine (PR 8): compile
    # seconds at the fleet board scale, single-layer recompile speedup
    # against the cached per-domain placements, fullerene-vs-mesh
    # saturation at equal node count, and the sharded-engine equivalence
    # claim (1.0 == spikes bit-identical AND reports within 1e-6)
    from benchmarks import fault_bench, fleet_bench, learn_bench

    metrics.update(fleet_bench.metrics(results.get("fleet")))
    # fault-injection subsystem (PR 9): random-kill survivability of the
    # fullerene fabric vs an equal-node mesh, the fault-aware repair
    # speedup over a from-scratch faulty compile, and the differential /
    # zero-cost-off claim flags (1.0, or a -100% change any gate trips)
    metrics.update(fault_bench.metrics(results.get("fault")))
    # on-chip plasticity (PR 10): engines-learn-identically and
    # zero-cost-off claim flags, the runtime price of carrying mutable
    # synaptic state through the scan, and the continual-adaptation
    # recovery fraction with its write-energy ledger
    metrics.update(learn_bench.metrics(results.get("learn")))
    return {"schema_version": TRAJECTORY_SCHEMA_VERSION,
            "lane": lane(), "provenance": provenance(),
            "metrics": metrics}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="write the schema-stable bench-trajectory JSON here")
    ap.add_argument("--only", default=None,
                    help=f"comma list of sections to run (default: all of "
                         f"{','.join(SECTIONS)})")
    ap.add_argument("--deploy-steps", type=int, default=60,
                    help="training steps per deploy_bench variant")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else set(SECTIONS)
    unknown = only - set(SECTIONS)
    if unknown:
        ap.error(f"unknown section(s) {sorted(unknown)}; "
                 f"valid: {','.join(SECTIONS)}")

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    sys.path.insert(0, root)                    # `python benchmarks/run.py`
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (compiler_bench, contention_bench, deploy_bench,
                            engine_bench, fault_bench, fig3_core_efficiency,
                            fig5_noc, fig6_riscv_power, fleet_bench,
                            kernel_bench, learn_bench, roofline, serve_bench,
                            table1_chip, telemetry_bench)

    results = {}
    failed: list[str] = []
    print("name,us_per_call,derived")

    def emit(name, us, derived):
        print(f"{name},{us:.1f},\"{json.dumps(derived, default=str)}\"")

    def section(name, fn):
        """Run one bench section fault-tolerantly: a raising section
        records `{"error": ...}` in its results slot (its trajectory
        metrics go None, which fails any gated metric downstream) and
        the remaining sections still run — one broken table must not
        cost the diagnostics of the other twelve.  The harness exits
        nonzero at the end if anything failed."""
        if name not in only:
            return
        try:
            results[name] = fn()
        except Exception as e:
            import traceback

            traceback.print_exc()
            results[name] = {"error": f"{type(e).__name__}: {e}"}
            failed.append(name)
            print(f"# section {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)

    section("fig3", lambda: fig3_core_efficiency.main(emit))
    section("fig5", lambda: fig5_noc.main(emit))
    section("noc", lambda: contention_bench.main(emit))
    section("compiler", lambda: compiler_bench.main(emit))
    section("engine", lambda: engine_bench.main(emit))
    section("deploy",
            lambda: deploy_bench.main(emit, steps=args.deploy_steps))
    section("fig6", lambda: fig6_riscv_power.main(emit))
    section("table1", lambda: table1_chip.main(emit))
    section("kernels", lambda: kernel_bench.main(emit))
    section("roofline", lambda: roofline.main(
        emit, os.environ.get("REPRO_DRYRUN_JSON", "dryrun_results.json")))
    section("telemetry", lambda: telemetry_bench.main(emit))
    section("serve", lambda: serve_bench.main(emit))
    # fleet + fault always run the tiny (CI-scale) configurations so
    # trajectories stay comparable across hosts; the full boards are
    # standalone runs
    section("fleet", lambda: fleet_bench.main(emit, tiny=True))
    section("fault", lambda: fault_bench.main(emit, tiny=True))
    section("learn", lambda: learn_bench.main(emit, tiny=True))

    out = os.path.join(os.path.dirname(__file__), "results.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"# full tables -> {out}", file=sys.stderr)

    if args.out:
        traj = trajectory(results)
        with open(args.out, "w") as f:
            json.dump(traj, f, indent=1, sort_keys=True)
        print(f"# bench trajectory -> {args.out}", file=sys.stderr)

    if failed:
        print(f"# {len(failed)} section(s) failed: {', '.join(failed)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
