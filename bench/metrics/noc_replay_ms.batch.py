"""noc_replay_ms.batch: the host's NoC replay and router contention, in ms
per `snn.run_batch` call: the self time of the program's
`snn.noc_replay` spans in the traced window / the calls in it
(`spans.per_call`)."""
from bench import spans


def read(run):
    s = spans.per_call(run.trace, "snn.noc_replay")
    return None if s is None else 1e3 * s
