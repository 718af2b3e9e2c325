"""slot_occupancy.serve: mean real requests per dispatched slot group
(`snn_batch_occupancy` sum / count) / slots, in %."""


def read(run):
    total, n = run.drive.get("occupancy", (0.0, 0))
    return 100.0 * total / n / run.drive["slots"] if n else None
