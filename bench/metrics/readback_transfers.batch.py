"""readback_transfers.batch: device arrays read back to the host per
`snn.run_batch` call: the `transfers` stat of the program's
`snn.readback` spans in the traced window / the calls in it
(`spans.per_call`)."""
from bench import spans


def read(run):
    return spans.per_call(run.trace, "snn.readback", "transfers")
