"""serve_p95_ms: 95th percentile (nearest rank), over every request due
in the window, of completion time minus due time.  A shed request, or
one unanswered a minute past the window, counts as infinitely late."""
import math

import numpy as np


def read(run):
    lat = run.drive.get("latency_ms")
    if lat is None or len(lat) == 0:
        return None
    s = np.sort(np.asarray(lat, np.float64))
    return float(s[max(0, math.ceil(0.95 * len(s)) - 1)])
