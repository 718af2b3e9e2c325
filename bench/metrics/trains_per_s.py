"""trains_per_s: event trains completed in the window / its seconds.

A call completes when its counts and ChipReports are on the host; the
window ends when the last call started inside it completes."""


def read(run):
    if "calls" not in run.drive:
        return None
    return run.drive["trains"] / run.drive["window_s"]
