"""device_idle.serve: 1 - device busy / traced window, in %, from the
profiler trace (serve cells)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
