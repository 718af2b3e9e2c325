"""device_wait_ms.batch: the host's wait for the engine program's results,
in ms per `snn.run_batch` call: the self time of the program's
`snn.device_wait` spans in the traced window / the calls in it
(`spans.per_call`)."""
from bench import spans


def read(run):
    s = spans.per_call(run.trace, "snn.device_wait")
    return None if s is None else 1e3 * s
