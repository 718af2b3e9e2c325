#!/usr/bin/env python3
"""Where the host time of a batch cell goes, by the program's spans.

    python3 bench/span_probe.py --workload nmnist.fused.b32 --seed <n> \
        --seconds 10 [--fixture <path>] [--out <path>]

Sets a cell up as `bench/run.py` does, then drives three windows of
`--seconds` on one seed: untraced, under the profiler (`spans.capture`),
untraced again.  Prints one JSON line: trains/s of each window (what the
profiler costs), the traced window's `spans.reduce` (span table, idle
gaps named by the innermost span, device ops), the per-layer metric
readers of the batch path on it, and the spans' cost with no profiler
running (microseconds per call, a loop of the spans `run_batch` opens).
`--fixture` writes a trace of two more calls in the form
`bench/tests/fixtures/` keeps; `--out` writes the whole summary.
Needs a TPU, like `bench/run.py`.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import check, registry, spans, tracing  # noqa: E402
from bench import run as R  # noqa: E402
from bench.record import RunRecord  # noqa: E402

READERS = ("upload_ms.batch", "dispatch_ms.batch", "device_wait_ms.batch",
           "readback_ms.batch", "noc_replay_ms.batch", "price_ms.batch",
           "upload_mb.batch", "readback_transfers.batch",
           "device_idle.batch", "engine_roofline.batch")
TIMES = READERS[:6]


def span_cost_us(n: int = 20000) -> float:
    """Microseconds per call of the spans `run_batch` opens, with their
    stats, when no profiler runs."""
    import jax

    ta = jax.profiler.TraceAnnotation
    t = time.perf_counter()
    for i in range(n):
        with ta("snn.run_batch", call=i, batch=32, steps=20):
            with ta("snn.upload", bytes=5918720):
                pass
            with ta("snn.dispatch"):
                pass
            with ta("snn.device_wait"):
                pass
            with ta("snn.readback", transfers=10, bytes=1 << 20):
                pass
            with ta("snn.noc_replay", flows=40):
                pass
            with ta("snn.price"):
                pass
    return (time.perf_counter() - t) / n * 1e6


def two_calls(sim, batches, annotate) -> None:
    """Two calls as the closed-loop driver makes them."""
    for x in batches[:2]:
        with annotate("bench.run_batch"):
            counts, reports = sim.run_batch(x)
            np.asarray(counts)
        with annotate("bench.record"):
            check.report_fields(reports)


def fixture(events, device_kind: str, workload: str) -> dict:
    """Device ops (names cut to the op), `bench.*` annotations and
    `snn.*` spans of a short trace, times rounded to whole ns."""
    ev, sp = [], []
    for e in events:
        if isinstance(e, spans.Span):
            sp.append([e.plane, e.line, e.name, round(e.start_ns),
                       round(e.dur_ns), e.stats])
        else:
            name = (tracing.op_name(e.name) if e.plane.startswith("/device:")
                    else e.name)
            ev.append([e.plane, e.line, name, round(e.start_ns),
                       round(e.dur_ns)])
    return {"source": f"{device_kind}, {workload}, two run_batch calls; "
                      "device ops of the XLA Ops line (names cut to the "
                      "op), bench annotations and the program's snn spans "
                      "with their stats; times rounded to whole ns",
            "events": ev, "spans": sp}


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", type=pathlib.Path)
    ap.add_argument("--out", type=pathlib.Path)
    args = ap.parse_args(argv)

    cell = registry.cell(args.workload)
    R.enable_compile_cache()
    devices = R.device_check(cell.chips)
    kind = devices[0].device_kind
    from bench import leastwork

    peak = leastwork.peaks(kind)
    compiles = R.CompileCounter()
    split: dict[str, float] = {}
    driver, sim, state, _, _ = R.build(cell, args.seed, split)
    with R.cache_writes_off():
        driver.warm(sim, state, cell.traffic)
    annotate = jax.profiler.TraceAnnotation
    before = compiles.n

    def window():
        rec = driver.drive(sim, state, cell.traffic, args.seconds,
                           args.seed, annotate)
        return rec, rec["trains"] / rec["window_s"]

    _, untraced_a = window()
    with spans.capture() as events:
        rec, traced = window()
    _, untraced_b = window()
    summary = spans.reduce(events)
    if summary is None:
        raise RuntimeError("the trace holds no device operation")
    run = RunRecord(config=cell.config, traffic=cell.traffic,
                    seed=args.seed, setup_s=0.0, drive=rec, trace=summary,
                    peak=peak)
    metrics = {m: registry.load_module("metrics", m).read(run)
               for m in READERS}
    calls = len(rec["calls"])
    result = {
        "device": kind, "workload": args.workload, "seed": args.seed,
        "trains_per_s": {"untraced_before": untraced_a, "traced": traced,
                         "untraced_after": untraced_b},
        "calls": calls,
        "window_ms_per_call": 1e3 * summary["window_s"] / calls,
        "phase_ms_per_call_sum": sum(metrics[m] or 0.0 for m in TIMES),
        "metrics": metrics,
        "span_cost_us_per_call_profiler_off": span_cost_us(),
        "spans": summary["spans"],
        "idle_share": summary["idle_share"],
        "idle_by_host": summary["idle_by_host"],
        "longest_gaps": summary["longest_gaps"],
        "device_ops": summary["device_ops"],
    }
    if args.fixture is not None:
        with spans.capture() as short:
            two_calls(sim, state, annotate)
        args.fixture.parent.mkdir(parents=True, exist_ok=True)
        args.fixture.write_text(json.dumps(fixture(short, kind,
                                                   args.workload)))
    if compiles.n != before:
        raise RuntimeError(f"{compiles.n - before} compilations after "
                           f"warm-up")
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(result, indent=1))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
