"""device_idle.batch: 1 - device busy / traced window, in %, from the
profiler trace (batch cells)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
