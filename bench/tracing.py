"""Profiler capture of a window, and its reduction to device metrics.

The reduction works on plain event tuples (`Event`), so a small recorded
trace can be kept as a test fixture:

* busy: the union of the intervals in which an operation ran on a device
  (the events of its `XLA Ops` line), clipped to the window, averaged
  over the devices that ran any;
* window: from the start of the first `bench.*` host annotation to the
  end of the last one (the measured loop as the host saw it);
* idle gaps: the window minus busy, each named by the `bench.*`
  annotation that overlaps it most (what the host was doing);
* device ops: device self time (an op's time less the ops nested in it,
  such as a scan's `while` less its body) summed per operation name.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
from typing import NamedTuple

OP_LINES = ("XLA Ops",)
ANNOTATION_PREFIX = "bench."


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


@contextlib.contextmanager
def capture():
    """Trace the enclosed block; yields a list that holds the window's
    events once the block has exited.  The trace files are deleted."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # a Python tracer would swamp the host
    opts.host_tracer_level = 1         # user annotations
    out: list[Event] = []
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        with jax.profiler.trace(logdir, profiler_options=opts):
            yield out
        out.extend(load_events(logdir))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def load_events(logdir: str) -> list[Event]:
    """Device-op events and `bench.*` annotations of an xplane trace."""
    from jax.profiler import ProfileData

    events: list[Event] = []
    for path in glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True):
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            device = plane.name.startswith("/device:")
            for line in plane.lines:
                for ev in line.events:
                    if ((device and line.name in OP_LINES)
                            or ev.name.startswith(ANNOTATION_PREFIX)):
                        events.append(Event(plane.name, line.name, ev.name,
                                            float(ev.start_ns),
                                            float(ev.duration_ns)))
    return events


def _union(intervals):
    """Sorted, merged [(start, end)]."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(events: list[Event], top: int = 10) -> dict | None:
    """Window, busy and idle time, gaps by host activity, top device ops.

    Returns None when the trace holds no annotation or no device op.
    """
    notes = sorted((e.start_ns, e.start_ns + e.dur_ns, e.name)
                   for e in events if e.name.startswith(ANNOTATION_PREFIX))
    ops = [e for e in events
           if e.plane.startswith("/device:") and e.line in OP_LINES]
    if not notes or not ops:
        return None
    w0 = notes[0][0]
    w1 = max(n[1] for n in notes)
    per_device: dict[str, list] = {}
    for e in ops:
        s, t = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
        if t > s:
            per_device.setdefault(e.plane, []).append((s, t, e.name))
    if not per_device:
        return None
    op_time = _self_time(per_device)
    busy = {d: _union((s, t) for s, t, _ in iv)
            for d, iv in per_device.items()}
    busy_ns = sum(sum(e - s for s, e in iv) for iv in busy.values()) \
        / len(busy)
    # gaps of the first device (one chip per cell, or the devices of a
    # batch-sharded program, which run in step)
    first = busy[sorted(busy)[0]]
    gaps, cur = [], w0
    for s, e in first:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        gaps.append((cur, w1))
    named = _name_gaps(gaps, notes)
    by_host: dict[str, float] = {}
    for name, dur, _ in named:
        by_host[name] = by_host.get(name, 0.0) + dur
    longest = sorted(named, key=lambda g: -g[1])[:top]
    window_ns = w1 - w0
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "devices": len(busy),
        "gaps": len(gaps),
        "idle_by_host": sorted(((k, v * 1e-9) for k, v in by_host.items()),
                               key=lambda kv: -kv[1]),
        "longest_gaps": [[n, d * 1e-9] for n, d, _ in longest],
        "longest_gap_starts_s": [(s - w0) * 1e-9 for _, _, s in longest],
        "device_ops": [[n, t * 1e-9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
    }


def op_name(hlo: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _self_time(per_device: dict) -> dict[str, float]:
    """Op name -> summed self time: ops on a device line nest (a scan's
    `while` holds its body), so each op's time less its children's."""
    total: dict[str, float] = {}
    for iv in per_device.values():
        stack: list[list] = []          # [end, name, self time]

        def close(upto):
            while stack and stack[-1][0] <= upto:
                end, name, own = stack.pop()
                total[name] = total.get(name, 0.0) + own

        for s, t, hlo in sorted(iv, key=lambda x: (x[0], -x[1])):
            close(s)
            if stack:
                stack[-1][2] -= min(t, stack[-1][0]) - s
            stack.append([t, op_name(hlo), t - s])
        close(float("inf"))
    return total


def _name_gaps(gaps, notes) -> list[tuple[str, float, float]]:
    """(name, length, start) of each gap, named by the annotation
    overlapping it most ("none" when no annotation covers it).  Both
    lists are sorted by start."""
    out = []
    j = 0
    for gs, ge in gaps:
        while j < len(notes) and notes[j][1] <= gs:
            j += 1
        best, best_ov = "none", 0.0
        k = j
        while k < len(notes) and notes[k][0] < ge:
            ov = min(ge, notes[k][1]) - max(gs, notes[k][0])
            if ov > best_ov:
                best, best_ov = notes[k][2], ov
            k += 1
        out.append((best, ge - gs, gs))
    return out
