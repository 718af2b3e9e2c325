"""Open loop: Poisson arrivals of single-train requests into `SnnServer`.

Arrivals are due at a fixed rate whatever the server does.  The gaps
between them are one fixed set (drawn from the traffic's own
`arrival_seed`), put in another order by each run's seed, so every seed
offers the same load.  Each request is one train of the pool, picked by
the seed.

One thread submits every request that is due, then calls `step()`,
which serves at most one slot group.  A request's latency runs from the
moment it was due, not from its submit, so a stalled generator shows as
latency; how late the generator ran is reported beside it.  Requests due
in the window are waited for until a minute past its close; one still
unanswered then, or shed at admission, counts as infinitely late.

Traffic keys: `engine`, `rate_per_s`, `arrival_seed`, `slots`,
`max_queue_depth`, `pool_trains`.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import check, reference, registry

GRACE_S = 60.0


def pool_size(traffic: dict) -> int:
    return int(traffic["pool_trains"])


def pool(trains: np.ndarray, traffic: dict) -> np.ndarray:
    return trains


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of the requests due in it."""
    rate = float(traffic["rate_per_s"])
    n = int(rate * seconds * 1.5) + 64
    base = np.random.default_rng(int(traffic["arrival_seed"]))
    gaps = base.exponential(1.0 / rate, n)
    order = np.random.default_rng(np.random.SeedSequence([seed, 0xA7]))
    due = np.cumsum(gaps[order.permutation(n)])
    return due[due < seconds]


def make_server(sim, traffic: dict):
    from repro.serve.snn_server import SnnServer

    return SnnServer(sim, batch_slots=int(traffic["slots"]),
                     max_queue_depth=traffic.get("max_queue_depth", 256),
                     clock=time.perf_counter)


def warm(sim, trains: np.ndarray, traffic: dict) -> None:
    """Compile the slot-sized program and serve one group through a
    throwaway server."""
    from repro.serve.snn_server import SnnRequest

    slots = int(traffic["slots"])
    for _ in range(2):
        np.asarray(sim.run_batch(trains[:slots])[0])
    server = make_server(sim, traffic)
    for i in range(slots):
        server.submit(SnnRequest(uid=i, events=trains[i]))
    server.run()


def drive(sim, trains: np.ndarray, traffic: dict, seconds: float, seed: int,
          annotate) -> dict:
    from repro.serve.snn_server import SnnRequest

    due = arrivals(traffic, seconds, seed)
    pick = np.random.default_rng(np.random.SeedSequence([seed, 0xB3])
                                 ).integers(0, len(trains), len(due))
    server = make_server(sim, traffic)          # fresh metrics per window
    reqs, late = [], np.zeros(len(due))
    nxt = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if nxt < len(due) and due[nxt] <= now:
            with annotate("bench.submit"):
                while nxt < len(due) and due[nxt] <= now:
                    late[nxt] = now - due[nxt]
                    reqs.append(server.submit(SnnRequest(
                        uid=nxt, events=trains[pick[nxt]])))
                    nxt += 1
        if server.queue and now < seconds + GRACE_S:
            with annotate("bench.step"):
                for r in server.step():
                    r.events = None     # answered: drop the train's copy
        elif nxt < len(due):
            with annotate("bench.traffic"):
                wait = due[nxt] - (time.perf_counter() - t0)
                if wait > 1e-3:
                    time.sleep(wait - 5e-4)
        else:
            break
    t_end = time.perf_counter() - t0
    lat = np.array([
        (r.t_complete - t0 - d) * 1e3 if r.status == "served" else math.inf
        for r, d in zip(reqs, due)])
    m = server.metrics
    wait_h = m.get("snn_request_queue_wait_ms")
    occ_h = m.get("snn_batch_occupancy")
    return {
        "window_s": float(seconds), "drained_s": t_end, "due": due,
        "latency_ms": lat, "late_s": late, "pick": pick,
        "requests": reqs, "slots": server.slots,
        "queue_wait_ms": (wait_h.sum, wait_h.count),
        "occupancy": (occ_h.sum, occ_h.count),
        "performed_sops": None,
    }


def outcome(record: dict) -> tuple[int, int]:
    served = sum(r.status == "served" for r in record["requests"])
    return len(record["due"]), len(record["due"]) - served


def correctness(record: dict, trains, layers, plan: dict, config: dict,
                traffic: dict, control: bool = False) -> dict:
    """Compare every served request with the network kind's reference
    (or, with `control`, its control with its reference), computed once
    per train of the pool."""
    reqs = record["requests"]
    never = sum(r.status not in ("served", "shed") for r in reqs)
    served = [r for r in reqs if r.status == "served"]
    if not served:
        return {"differing_requests": 0, "energy_rel_gap": 0.0,
                "never_completed": never}
    rows = np.array([record["pick"][r.uid] for r in served])
    used = np.unique(rows)
    at = np.searchsorted(used, rows)
    energy = reference.FIELDS.index("energy_pj")
    net = registry.network(config)
    ref_counts, ref_fields = net.reference(layers, trains[used], config,
                                           plan)
    ref_counts, ref_energy = ref_counts[at], ref_fields[at, energy]
    if control:
        counts, fields = net.reference(layers, trains[used], config, plan,
                                       control=True)
        counts, got_energy = counts[at], fields[at, energy]
        preds = np.argmax(counts, axis=-1)
    else:
        counts = np.stack([r.spike_counts for r in served])
        preds = np.array([r.prediction for r in served])
        got_energy = np.array([r.energy_pj for r in served])
    differ = int(check.differing_requests(counts, preds, ref_counts).sum())
    return {"differing_requests": differ,
            "energy_rel_gap": check.rel_gap(got_energy, ref_energy),
            "never_completed": never}
