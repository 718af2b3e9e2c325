"""upload_mb.batch: MB (1e6 bytes) moved from the host to the device
per `snn.run_batch` call: the `bytes` stat of the program's `snn.upload`
spans in the traced window / the calls in it (`spans.per_call`)."""
from bench import spans


def read(run):
    b = spans.per_call(run.trace, "snn.upload", "bytes")
    return None if b is None else b / 1e6
