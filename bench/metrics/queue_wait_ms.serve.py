"""queue_wait_ms.serve: mean submit -> dispatch wait of the window's
served requests (`snn_request_queue_wait_ms` sum / count of a server
built just before the window)."""


def read(run):
    total, n = run.drive.get("queue_wait_ms", (0.0, 0))
    return total / n if n else None
