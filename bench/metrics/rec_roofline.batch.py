"""rec_roofline.batch: the recurrent layer's fused kernel's share of its
roofline, in %, over the traced window.

Numerator: the layer's least time summed over the window's batches, per
batch the larger of 2 x its SOPs over the bf16 peak and its least bytes
over HBM bandwidth (the network kind's `layer_least_bytes`: its weights
once at log2(N) bits, its level table, and its input words, forward and
fed back, at 1 bit).  Its SOPs are the batch's performed SOPs, which
also hold the readout's: a hidden spike performs 20 readout SOPs and
1024 in the recurrent layer, and an input spike 1024 there, so they
overstate the layer's by under 2% (20 / 1024).

Denominator: the device self time of the ops named after the layer's
kernel (`snn_fused_l<i>`, with XLA's `.<n>` suffix) among the trace's
device ops.

Reads nothing where the network kind has no recurrent layer, the run is
untraced, or no op of that name ran.
"""
from bench import leastwork, registry
from bench.reference import FIELDS


def read(run):
    if run.trace is None or "calls" not in run.drive:
        return None
    kind = registry.network(run.config)
    layers = getattr(kind, "recurrent_layers", lambda config: [])(run.config)
    if not layers:
        return None
    li = layers[0]
    kernel = f"snn_fused_l{li + 1}"
    busy = sum(t for name, t in run.trace["device_ops"]
               if name.split(".")[0] == kernel)
    if not busy:
        return None
    col = FIELDS.index("performed_sops")
    least = 0.0
    for _, _, fields in run.drive["calls"]:
        t_ops = (leastwork.least_ops(fields[:, col].sum())
                 / run.peak["bf16_flops_per_s"])
        t_bytes = (kind.layer_least_bytes(run.config, len(fields), li)
                   / run.peak["hbm_bytes_per_s"])
        least += max(t_ops, t_bytes)
    return 100.0 * least / busy
