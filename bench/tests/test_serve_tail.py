"""The open-loop tail under a fake clock: latency runs from the due time,
and shed or unanswered requests count as misses."""
import importlib.util
import math
import types

import numpy as np
import pytest

from bench import registry
from bench.drivers import open_loop

N_IN, N_OUT = 8, 3


class FakeClock:
    """Fake time; every reading advances it by 1 us, as a spinning loop
    would see."""

    TICK = 1e-6

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        self.now += self.TICK
        return self.now

    def sleep(self, s):
        self.now += s


class FakeSim:
    """What SnnServer needs of a simulator; each dispatch takes `cost_s`
    of fake time and answers counts = the row sums of the trains."""

    engine = "fused"
    register_tables = []

    def __init__(self, clock, cost_s):
        self.clock, self.cost_s = clock, cost_s
        self.weights = [np.zeros((N_IN, 4)), np.zeros((4, N_OUT))]
        self.mapping = types.SimpleNamespace(active_core_ids=lambda: [0])

    def run_batch(self, trains):
        self.clock.now += self.cost_s
        x = np.asarray(trains)
        counts = np.repeat(x.sum(axis=(1, 2))[:, None], N_OUT, axis=1)
        rep = types.SimpleNamespace(energy_pj=1.0, pj_per_sop=1.0)
        return counts, [rep] * len(x)


def p95_reader():
    spec = importlib.util.spec_from_file_location(
        "p95", registry.BENCH_DIR / "metrics" / "serve_p95_ms.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def drive(monkeypatch, traffic, cost_s, seconds=1.0):
    clock = FakeClock()
    monkeypatch.setattr(open_loop, "time", clock)
    trains = np.ones((4, 2, N_IN), np.float32)
    null = lambda name: __import__("contextlib").nullcontext()  # noqa: E731
    rec = open_loop.drive(FakeSim(clock, cost_s), trains, traffic, seconds,
                          7, null)
    return rec


def test_latency_runs_from_due_time(monkeypatch):
    traffic = {"rate_per_s": 20.0, "arrival_seed": 3, "slots": 4,
               "max_queue_depth": 256}
    rec = drive(monkeypatch, traffic, cost_s=0.010)
    due = open_loop.arrivals(traffic, 1.0, 7)
    assert np.array_equal(rec["due"], due)
    # replay the one-thread loop by hand: submit what is due, serve one
    # group of up to 4, each dispatch 10 ms
    now, queue, done, nxt = 0.0, [], {}, 0
    while nxt < len(due) or queue:
        while nxt < len(due) and due[nxt] <= now:
            queue.append(nxt)
            nxt += 1
        if queue:
            group, queue = queue[:4], queue[4:]
            now += 0.010
            for i in group:
                done[i] = now
        elif nxt < len(due):
            now = due[nxt]
    # the clock's ticks add a few us per request
    expect = np.array([(done[i] - due[i]) * 1e3 for i in range(len(due))])
    assert np.allclose(rec["latency_ms"], expect, atol=0.05)
    s = np.sort(expect)
    p95 = s[math.ceil(0.95 * len(s)) - 1]
    run = types.SimpleNamespace(drive=rec)
    assert p95_reader()(run) == pytest.approx(p95, abs=0.05)
    assert open_loop.outcome(rec) == (len(due), 0)


def test_shed_requests_count_as_misses(monkeypatch):
    # 200 requests/s against one slot served in 50 ms: the 4-deep queue
    # overflows, and the shed requests are infinitely late
    traffic = {"rate_per_s": 200.0, "arrival_seed": 3, "slots": 1,
               "max_queue_depth": 4}
    rec = drive(monkeypatch, traffic, cost_s=0.050)
    statuses = [r.status for r in rec["requests"]]
    shed = statuses.count("shed")
    assert shed > 0
    assert np.isinf(rec["latency_ms"]).sum() == shed
    assert open_loop.outcome(rec) == (len(rec["due"]), shed)
    assert math.isinf(p95_reader()(types.SimpleNamespace(drive=rec)))
