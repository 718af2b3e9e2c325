"""readback_ms.batch: device to host readback of the counters, in ms per
`snn.run_batch` call: the self time of the program's `snn.readback`
spans in the traced window / the calls in it (`spans.per_call`)."""
from bench import spans


def read(run):
    s = spans.per_call(run.trace, "snn.readback")
    return None if s is None else 1e3 * s
