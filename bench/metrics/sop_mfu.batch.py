"""sop_mfu.batch: the whole step's share of the chip's bf16 peak, in %:
2 x SOPs performed in the window (exact counters) / window seconds /
peak."""
from bench import leastwork


def read(run):
    sops = run.drive.get("performed_sops")
    if not sops:
        return None
    rate = leastwork.least_ops(sops) / run.drive["window_s"]
    return 100.0 * rate / run.peak["bf16_flops_per_s"]
