"""conv_roofline.batch: the conv and pool layers' kernels' share of
their roofline, in %, over the traced window.

Numerator: those layers' least time summed over the window's batches,
per batch the larger of 2 x their SOPs over the bf16 peak and their
least bytes over HBM bandwidth (the network kind's `layer_least_bytes`
summed over its `conv_layers`: each filter's distinct words once at
log2(N) bits, its level table, and its input spikes at 1 bit).  Their
SOPs are the batch's performed SOPs, which also hold the dense output
layer's: at the SLAYER network's firing rates those are about 2% of
them, an overstatement of the conv layers' by that much.

Denominator: the device self time of the ops named after the conv
layers' kernels (`snn_conv_l<i>`, with XLA's `.<n>` suffix) among the
trace's device ops.

Reads nothing where the network kind has no conv layer, the run is
untraced, or no op of those names ran.
"""
from bench import leastwork, registry
from bench.reference import FIELDS


def read(run):
    if run.trace is None or "calls" not in run.drive:
        return None
    kind = registry.network(run.config)
    layers = getattr(kind, "conv_layers", lambda config: [])(run.config)
    if not layers:
        return None
    kernels = {f"snn_conv_l{li + 1}" for li in layers}
    busy = sum(t for name, t in run.trace["device_ops"]
               if name.split(".")[0] in kernels)
    if not busy:
        return None
    col = FIELDS.index("performed_sops")
    least = 0.0
    for _, _, fields in run.drive["calls"]:
        t_ops = (leastwork.least_ops(fields[:, col].sum())
                 / run.peak["bf16_flops_per_s"])
        t_bytes = sum(kind.layer_least_bytes(run.config, len(fields), li)
                      for li in layers) / run.peak["hbm_bytes_per_s"]
        least += max(t_ops, t_bytes)
    return 100.0 * least / busy
