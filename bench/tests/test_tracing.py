"""The trace reduction on a small recorded trace and on a hand-made one."""
import json
import pathlib

import numpy as np
import pytest

from bench import tracing
from bench.tracing import Event

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "fused_two_calls.json"
DEV = "/device:TPU:0"


def recorded():
    return [Event(*e) for e in json.loads(FIXTURE.read_text())["events"]]


def oracle(events):
    """Busy share and gap names by a 1 ns timeline, the slow way."""
    notes = [e for e in events if e.name.startswith("bench.")]
    w0 = int(min(e.start_ns for e in notes))
    w1 = int(max(e.start_ns + e.dur_ns for e in notes))
    busy = np.zeros(w1 - w0, bool)
    for e in events:
        if e.plane.startswith("/device:") and e.line in tracing.OP_LINES:
            s = int(max(e.start_ns, w0)) - w0
            t = int(min(e.start_ns + e.dur_ns, w1)) - w0
            if t > s:
                busy[s:t] = True
    edges = np.flatnonzero(np.diff(np.r_[0, (~busy).astype(np.int8), 0]))
    gaps = [(w0 + a, w0 + b) for a, b in zip(edges[::2], edges[1::2])]
    named = []
    for gs, ge in gaps:
        ov = [(min(ge, n.start_ns + n.dur_ns) - max(gs, n.start_ns), n.name)
              for n in notes]
        best = max(ov)
        named.append((best[1] if best[0] > 0 else "none", ge - gs))
    return busy.mean(), (w1 - w0) * 1e-9, named


def test_recorded_trace_matches_the_timeline():
    events = recorded()
    got = tracing.reduce(events)
    busy, window_s, named = oracle(events)
    assert got["window_s"] == pytest.approx(window_s, abs=1e-9)
    assert got["idle_share"] == pytest.approx(1 - busy, abs=1e-6)
    assert got["gaps"] == len(named)
    by_host = {}
    for n, d in named:
        by_host[n] = by_host.get(n, 0.0) + d * 1e-9
    assert len(got["longest_gap_starts_s"]) == len(got["longest_gaps"])
    assert dict(got["idle_by_host"]) == pytest.approx(by_host, abs=1e-8)
    longest = sorted(named, key=lambda nd: -nd[1])[:10]
    assert [n for n, _ in got["longest_gaps"]] == [n for n, _ in longest]
    # the fused kernel of the first layer is the largest device op
    assert got["device_ops"][0][0].startswith("fused_timestep_codebook")


def test_hand_made_trace():
    ns = 1e9
    events = [
        Event("/host:CPU", "python3", "bench.run_batch", 0, 100),
        Event("/host:CPU", "python3", "bench.record", 100, 20),
        Event("/host:CPU", "python3", "bench.run_batch", 120, 80),
        # a while loop holding two ops, then a copy after it
        Event(DEV, "XLA Ops", "%while.1 = (...) while()", 10, 50),
        Event(DEV, "XLA Ops", "%kernel.2 = f32[8] custom-call()", 15, 20),
        Event(DEV, "XLA Ops", "%fusion.3 = f32[8] fusion()", 40, 10),
        Event(DEV, "XLA Ops", "%copy.4 = f32[8] copy()", 150, 30),
        Event(DEV, "XLA Modules", "jit_run", 10, 50),     # not an op line
    ]
    got = tracing.reduce(events)
    assert got["window_s"] == pytest.approx(200 / ns)
    assert got["busy_s"] == pytest.approx(80 / ns)
    assert got["idle_share"] == pytest.approx(0.6)
    # idle: [0,10) run_batch, [60,150) mostly run_batch 40 vs record 20
    # vs run_batch 30 -> the first run_batch, [180,200) run_batch
    assert [n for n, _ in got["longest_gaps"]] == ["bench.run_batch"] * 3
    assert [d for _, d in got["longest_gaps"]] == pytest.approx(
        [90 / ns, 20 / ns, 10 / ns])
    assert dict(got["device_ops"]) == pytest.approx({
        "copy.4": 30 / ns, "kernel.2": 20 / ns, "while.1": 20 / ns,
        "fusion.3": 10 / ns})


def test_no_device_op_reads_nothing():
    assert tracing.reduce(
        [Event("/host:CPU", "python3", "bench.step", 0, 10)]) is None
