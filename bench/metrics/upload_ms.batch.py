"""upload_ms.batch: host to device upload of the spike trains, in ms per
`snn.run_batch` call: the self time of the program's `snn.upload` spans
in the traced window / the calls in it (`spans.per_call`)."""
from bench import spans


def read(run):
    s = spans.per_call(run.trace, "snn.upload")
    return None if s is None else 1e3 * s
