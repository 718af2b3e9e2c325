"""engine_roofline.batch: the least time of the window's batches
(`leastwork.least_time`: the larger of 2 x SOPs performed over the bf16
peak and the least bytes over HBM bandwidth) / device busy time, in %."""
from bench import leastwork
from bench.reference import FIELDS


def read(run):
    if (run.trace is None or not run.trace["busy_s"]
            or "calls" not in run.drive):
        return None
    col = FIELDS.index("performed_sops")
    least = sum(leastwork.least_time(run.config, len(f), f[:, col].sum(),
                                     run.peak)[0]
                for _, _, f in run.drive["calls"])
    return 100.0 * least / run.trace["busy_s"]
