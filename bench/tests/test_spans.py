"""The program spans' reduction (`bench/spans.py`) and the per-layer
readers of the batch path, on hand-made traces against a 1 ns timeline,
on the span-less two-call fixture, on a recorded chip trace with the
program's spans, and on a CPU profiler capture of the program itself."""
import json
import pathlib

import numpy as np
import pytest

from bench import registry, spans, tracing
from bench.record import RunRecord
from bench.spans import Span
from bench.tracing import Event

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
SPANLESS = FIXTURES / "fused_two_calls.json"
SPANNED = FIXTURES / "fused_two_calls_spans.json"
DEV = "/device:TPU:0"
HOST = "/host:CPU"
TIME_READERS = ("upload_ms.batch", "dispatch_ms.batch",
                "device_wait_ms.batch", "readback_ms.batch",
                "noc_replay_ms.batch", "price_ms.batch")
COUNT_READERS = ("upload_mb.batch", "readback_transfers.batch")


def load(path):
    d = json.loads(path.read_text())
    return ([Event(*e) for e in d["events"]]
            + [Span(*s) for s in d.get("spans", [])])


def oracle(events):
    """Self time and summed stats per span, and gap names, by a 1 ns
    timeline: at each instant of a host line the innermost annotation is
    the open one that started last (the shorter on a tie)."""
    notes = [e for e in events if not e.plane.startswith("/device:")
             and e.name.startswith(("bench.", "snn."))]
    bench = [e for e in notes if e.name.startswith("bench.")]
    w0 = int(min(e.start_ns for e in bench))
    w1 = int(max(e.start_ns + e.dur_ns for e in bench))
    n = w1 - w0
    busy = np.zeros(n, bool)
    for e in events:
        if e.plane.startswith("/device:") and e.line in tracing.OP_LINES:
            s = int(max(e.start_ns, w0)) - w0
            t = int(min(e.start_ns + e.dur_ns, w1)) - w0
            if t > s:
                busy[s:t] = True
    names = sorted({e.name for e in notes})
    inner = {}                          # line -> (n,) index into names
    for line in sorted({(e.plane, e.line) for e in notes}):
        own = [e for e in notes if (e.plane, e.line) == line]
        idx = np.full(n, -1, np.int8)
        for e in sorted(own, key=lambda e: (e.start_ns, -e.dur_ns)):
            s = int(max(e.start_ns, w0)) - w0
            t = int(min(e.start_ns + e.dur_ns, w1)) - w0
            if t > s:
                idx[s:t] = names.index(e.name)
        inner[line] = idx
    self_ns = {}
    for idx in inner.values():
        for i, name in enumerate(names):
            self_ns[name] = self_ns.get(name, 0) + int((idx == i).sum())
    stats = {}
    for e in notes:
        if isinstance(e, Span) and w0 <= e.start_ns < w1:
            row = stats.setdefault(e.name, {})
            for k, v in e.stats.items():
                if k != "call":
                    row[k] = row.get(k, 0) + v
    edges = np.flatnonzero(np.diff(np.r_[0, (~busy).astype(np.int8), 0]))
    named = []
    for a, b in zip(edges[::2], edges[1::2]):
        cover = {}
        for idx in inner.values():
            seen = idx[a:b]
            for i, k in enumerate(np.bincount(seen[seen >= 0],
                                              minlength=len(names))):
                if k:
                    cover[names[i]] = cover.get(names[i], 0) + int(k)
        best = max(cover.items(), key=lambda kv: (kv[1], kv[0]),
                   default=("none", 0))[0]
        named.append((best, int(b - a)))
    return self_ns, stats, named, (w1 - w0) * 1e-9


def check_against_oracle(events):
    got = spans.reduce(events)
    self_ns, stats, named, window_s = oracle(events)
    assert got["window_s"] == pytest.approx(window_s, abs=1e-9)
    for name, row in got["spans"].items():
        assert row["self_s"] == pytest.approx(self_ns[name] * 1e-9,
                                              abs=2e-9 * row["count"])
        assert row["stats"] == stats.get(name, {})
    assert set(got["spans"]) == {k for k in self_ns if k.startswith("snn.")
                                 and self_ns[k] > 0}
    assert got["gaps"] == len(named)
    by_host = {}
    for nm, d in named:
        by_host[nm] = by_host.get(nm, 0.0) + d * 1e-9
    assert dict(got["idle_by_host"]) == pytest.approx(by_host, abs=1e-8)
    longest = sorted(named, key=lambda nd: -nd[1])[:10]
    assert [d for _, d in got["longest_gaps"]] == pytest.approx(
        [d * 1e-9 for _, d in longest], abs=2e-9)
    return got


def nested_trace():
    """Two calls of a program with nested spans on one line and a second
    thread's annotation on another; device ops leave gaps of each kind."""
    ev = [
        Event(HOST, "main", "bench.run_batch", 0, 1000),
        Event(HOST, "main", "bench.record", 1000, 100),
        Event(HOST, "main", "bench.run_batch", 1100, 900),
        Event(DEV, "XLA Ops", "%snn_fused_l1.3 = f32[8] custom-call()",
              300, 250),
        Event(DEV, "XLA Ops", "%copy.1 = f32[8] copy()", 560, 40),
        Event(DEV, "XLA Ops", "%snn_fused_l1.3 = f32[8] custom-call()",
              1500, 300),
        Event(DEV, "XLA Modules", "jit_run", 300, 300),   # not an op line
    ]
    sp = []
    for c, base in ((1, 0), (2, 1100)):
        sp += [
            Span(HOST, "main", "snn.run_batch", base + 10, 880,
                 {"call": c, "batch": 32, "steps": 20}),
            Span(HOST, "main", "snn.upload", base + 20, 200,
                 {"bytes": 5918720}),
            Span(HOST, "main", "snn.dispatch", base + 230, 50, {}),
            Span(HOST, "main", "snn.device_wait", base + 290, 300, {}),
            Span(HOST, "main", "snn.readback", base + 600, 120,
                 {"transfers": 7, "bytes": 4096}),
            Span(HOST, "main", "snn.noc_replay", base + 730, 60,
                 {"flows": 40}),
            Span(HOST, "main", "snn.price", base + 800, 80, {}),
        ]
    # a server thread's group upload, overlapping the second call
    sp.append(Span(HOST, "worker", "snn.upload", 1050, 400,
                   {"bytes": 1000}))
    return ev + sp


def test_nested_spans_match_the_timeline():
    got = check_against_oracle(nested_trace())
    rb = got["spans"]["snn.run_batch"]
    assert rb["count"] == 2
    assert rb["stats"] == {"batch": 64, "steps": 40}   # `call` left out
    # each call's own time: 880 less its children (200+50+300+120+60+80)
    assert rb["self_s"] == pytest.approx(2 * 70e-9)
    assert rb["total_s"] == pytest.approx(2 * 880e-9)
    up = got["spans"]["snn.upload"]
    assert (up["count"], up["stats"]) == (3, {"bytes": 2 * 5918720 + 1000})
    # the longest gap, [1100, 1500), is mostly the worker's upload and
    # the main line's upload; the innermost pieces name it, never the
    # enclosing bench.run_batch
    assert all(n.startswith("snn.") or n == "bench.record"
               for n, _ in got["longest_gaps"])


NESTED_PER_CALL = {                     # by hand from nested_trace()
    "upload_ms.batch": (200 + 200 + 400) * 1e-6 / 2,
    "dispatch_ms.batch": 50e-6, "device_wait_ms.batch": 300e-6,
    "readback_ms.batch": 120e-6, "noc_replay_ms.batch": 60e-6,
    "price_ms.batch": 80e-6,
    "upload_mb.batch": (2 * 5918720 + 1000) / 1e6 / 2,
    "readback_transfers.batch": 7.0}


@pytest.mark.parametrize("name", TIME_READERS + COUNT_READERS)
def test_reader_on_the_nested_trace(name):
    summary = spans.reduce(nested_trace())
    run = RunRecord(config={}, traffic={}, seed=0, setup_s=0.0, drive={},
                    trace=summary, peak={})
    assert registry.load_module("metrics", name).read(run) == pytest.approx(
        NESTED_PER_CALL[name], rel=1e-9)


def test_window_busy_and_device_ops_are_tracings():
    events = nested_trace()
    base = tracing.reduce(events)
    got = spans.reduce(events)
    for key in ("window_s", "busy_s", "idle_share", "devices", "gaps",
                "device_ops"):
        assert got[key] == base[key], key


def test_spanless_fixture_reads_as_before():
    """A trace without program spans: window, busy time, idle share
    and device ops as `tracing.reduce` reads them, so `device_idle.batch`
    and `engine_roofline.batch` read the same; the span readers read
    nothing."""
    events = load(SPANLESS)
    base = tracing.reduce(events)
    got = check_against_oracle(events)
    for key in ("window_s", "busy_s", "idle_share", "devices", "gaps",
                "device_ops"):
        assert got[key] == base[key], key
    assert got["window_s"] == pytest.approx(0.027194151, abs=1e-12)
    assert got["busy_s"] == pytest.approx(0.006425639, abs=1e-12)
    assert got["idle_share"] == pytest.approx(0.76371246155, abs=1e-10)
    assert got["spans"] == {}
    drive = {"calls": [(0, None, np.zeros((32, 9)))] * 2, "window_s": 1.0,
             "trains": 64, "performed_sops": 0.0}
    read = {}
    for trace_of in (tracing.reduce, spans.reduce):
        run = RunRecord(config=registry.cell("nmnist.fused.b32").config,
                        traffic={}, seed=0, setup_s=0.0, drive=drive,
                        trace=trace_of(events),
                        peak={"bf16_flops_per_s": 1.97e14,
                              "hbm_bytes_per_s": 8.19e11})
        read[trace_of] = [
            registry.load_module("metrics", name).read(run)
            for name in ("device_idle.batch", "engine_roofline.batch")]
        for name in TIME_READERS + COUNT_READERS:
            assert registry.load_module("metrics", name).read(run) is None
    assert read[spans.reduce] == read[tracing.reduce]
    assert read[spans.reduce][0] == pytest.approx(76.371246155, abs=1e-8)


def spanned_run():
    events = load(SPANNED)
    summary = spans.reduce(events)
    return summary, RunRecord(config={}, traffic={}, seed=0, setup_s=0.0,
                              drive={}, trace=summary, peak={})


def test_recorded_spans_match_the_timeline():
    got = check_against_oracle(load(SPANNED))
    assert got["spans"]["snn.run_batch"]["count"] == 2
    # the program's spans name the idle gaps, not the bench's call
    assert got["longest_gaps"][0][0].startswith("snn.")
    # each layer's fused kernel under its own name on the XLA Ops line
    assert [n.split(".")[0] for n, _ in got["device_ops"][:3]] == [
        "snn_fused_l1", "snn_fused_l2", "snn_fused_l3"]


@pytest.mark.parametrize("name", TIME_READERS + COUNT_READERS)
def test_reader_on_the_recorded_trace(name):
    summary, run = spanned_run()
    value = registry.load_module("metrics", name).read(run)
    assert value is not None and value > 0
    rows = summary["spans"]
    if name == "upload_mb.batch":
        # the f32 trains of one B=32, T=20, 2312-input call
        assert value == pytest.approx(32 * 20 * 2312 * 4 / 1e6)
    elif name == "readback_transfers.batch":
        assert value == rows["snn.readback"]["stats"]["transfers"] / 2
    else:
        span = "snn." + name.split("_ms.")[0]
        assert value == pytest.approx(1e3 * rows[span]["self_s"] / 2)


def test_phases_cover_the_call():
    """The six phase times and the call's own time add up to the calls'
    spans: nothing of a call is left unnamed."""
    summary, run = spanned_run()
    phases = sum(registry.load_module("metrics", n).read(run)
                 for n in TIME_READERS)
    rb = summary["spans"]["snn.run_batch"]
    assert phases + 1e3 * rb["self_s"] / 2 == pytest.approx(
        1e3 * rb["total_s"] / 2, rel=1e-9)


def test_cpu_capture_holds_the_program_spans():
    """A real profiler capture on the CPU: each span of a call, with the
    stats the program gave it."""
    import jax

    from repro.core.soc import ChipSimulator

    rng = np.random.default_rng(0)
    sim = ChipSimulator([rng.normal(0, 0.5, (8, 16)).astype(np.float32),
                         rng.normal(0, 0.5, (16, 4)).astype(np.float32)],
                        engine="fused")
    trains = (rng.random((4, 3, 8)) < 0.4).astype(np.float32)
    np.asarray(sim.run_batch(trains)[0])
    with spans.capture() as events:
        with jax.profiler.TraceAnnotation("bench.run_batch"):
            sim.run_batch(trains)
    got = {e.name: e.stats for e in events if isinstance(e, Span)}
    assert set(got) == {"snn.run_batch", "snn.upload", "snn.dispatch",
                        "snn.device_wait", "snn.readback", "snn.noc_replay",
                        "snn.price"}
    assert got["snn.run_batch"] == {"call": 2, "batch": 4, "steps": 3}
    assert got["snn.upload"] == {"bytes": trains.nbytes}
    assert got["snn.readback"]["transfers"] > 0
