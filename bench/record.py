"""What a metric reader (`bench/metrics/<name>.py`) is given."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class RunRecord:
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    seed: int
    setup_s: float          # process start to the window's start
    drive: dict             # the driver's record of the window
    trace: dict | None      # tracing.reduce of the window, traced runs only
    peak: dict              # peaks.json row of the device kind
