"""The program's own spans in a profiler trace, and their reduction.

The program marks each phase of its batch path with a
`jax.profiler.TraceAnnotation` span named `snn.*`, and gives its counts
as span stats (`core/engine.py` `_EngineBase.run_batch`,
`serve/snn_server.py`).  They sit on the profiler's clock, beside the
device ops and the bench's own `bench.*` annotations.  `capture` keeps
them with the events `tracing` keeps, as `Span` tuples (an `Event` with
its stats), and `reduce` adds to `tracing.reduce`'s summary:

* spans: per `snn.*` name, over the spans that start in the window, the
  count, total and self seconds and the summed stats (all but `call`,
  a sequence number).  Self time is a span's duration less the part its
  child spans on the same host line cover;
* idle gaps named by the innermost annotation, `bench.*` or `snn.*`, that
  covers most of each gap (where the host was, not only which bench call
  it was in): `idle_by_host`, `longest_gaps`, `longest_gap_starts_s`.

The window, busy time, idle share and device ops are `tracing.reduce`'s,
unchanged.  `per_call` is what the per-layer metric readers of the
batch path read.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
from typing import NamedTuple

from bench import tracing

SPAN_PREFIX = "snn."
CALL_SPAN = "snn.run_batch"


class Span(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict


@contextlib.contextmanager
def capture():
    """`tracing.capture`, keeping the program's `snn.*` spans too."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # a Python tracer would swamp the host
    opts.host_tracer_level = 1         # user annotations
    out: list = []
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        with jax.profiler.trace(logdir, profiler_options=opts):
            yield out
        out.extend(tracing.load_events(logdir))
        out.extend(load_spans(logdir))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def load_spans(logdir: str) -> list[Span]:
    """The `snn.*` spans of an xplane trace, with their stats."""
    from jax.profiler import ProfileData

    spans: list[Span] = []
    for path in glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True):
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(plane.name, line.name, ev.name,
                                          float(ev.start_ns),
                                          float(ev.duration_ns),
                                          dict(ev.stats)))
    return spans


def _annotation(e) -> bool:
    return (not e.plane.startswith("/device:")
            and e.name.startswith((tracing.ANNOTATION_PREFIX, SPAN_PREFIX)))


def innermost(notes) -> list[tuple[float, float, str]]:
    """Disjoint (start, end, name) pieces of the host lines: at each
    instant of a line, the innermost annotation open on it.  A piece of
    a span is its self time."""
    by_line: dict[tuple, list] = {}
    for e in notes:
        by_line.setdefault((e.plane, e.line), []).append(
            (e.start_ns, e.start_ns + e.dur_ns, e.name))
    pieces = []
    for iv in by_line.values():
        stack: list[list] = []          # [end, name], innermost last
        cur = 0.0                       # where the innermost's piece starts

        def close(upto):
            nonlocal cur
            while stack and stack[-1][0] <= upto:
                end, name = stack.pop()
                if end > cur:
                    pieces.append((cur, end, name))
                cur = max(cur, end)

        for s, e, name in sorted(iv, key=lambda x: (x[0], -x[1])):
            close(s)
            if stack and s > cur:
                pieces.append((cur, s, stack[-1][1]))
            # a child ends with its parent at the latest
            stack.append([min(e, stack[-1][0]) if stack else e, name])
            cur = s
        close(float("inf"))
    return sorted(pieces)


def _gaps(events, w0: float, w1: float) -> list[tuple[float, float]]:
    """Idle intervals of the window on the first device, as
    `tracing.reduce` finds them."""
    per_device: dict[str, list] = {}
    for e in events:
        if e.plane.startswith("/device:") and e.line in tracing.OP_LINES:
            s, t = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
            if t > s:
                per_device.setdefault(e.plane, []).append((s, t))
    first = tracing._union(per_device[sorted(per_device)[0]])
    gaps, cur = [], w0
    for s, e in first:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        gaps.append((cur, w1))
    return gaps


def _name_gaps(gaps, pieces) -> list[tuple[str, float, float]]:
    """(name, length, start) of each gap: the name whose innermost
    pieces cover most of it ("none" where no annotation is open)."""
    out = []
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        cover: dict[str, float] = {}
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            ov = min(ge, pieces[k][1]) - max(gs, pieces[k][0])
            if ov > 0:
                cover[pieces[k][2]] = cover.get(pieces[k][2], 0.0) + ov
            k += 1
        best = max(cover.items(), key=lambda kv: (kv[1], kv[0]),
                   default=("none", 0.0))[0]
        out.append((best, ge - gs, gs))
    return out


def reduce(events: list, top: int = 10) -> dict | None:
    """`tracing.reduce`, with the `spans` table and the gaps named by the
    innermost annotation.  None where `tracing.reduce` reads nothing."""
    out = tracing.reduce(events, top)
    if out is None:
        return None
    notes = [e for e in events if _annotation(e)]
    bench = [e for e in notes if e.name.startswith(tracing.ANNOTATION_PREFIX)]
    w0 = min(e.start_ns for e in bench)
    w1 = max(e.start_ns + e.dur_ns for e in bench)
    pieces = innermost(notes)

    table: dict[str, dict] = {}
    for e in notes:
        if e.name.startswith(SPAN_PREFIX) and w0 <= e.start_ns < w1:
            row = table.setdefault(e.name, {"count": 0, "total_s": 0.0,
                                            "self_s": 0.0, "stats": {}})
            row["count"] += 1
            row["total_s"] += e.dur_ns * 1e-9
            for k, v in e.stats.items():
                row["stats"][k] = row["stats"].get(k, 0) + v
    for s, e, name in pieces:
        if name in table:
            row = table[name]
            row["self_s"] += max(0.0, min(e, w1) - max(s, w0)) * 1e-9
    for row in table.values():
        row["stats"].pop("call", None)      # a sequence number, not a count

    named = _name_gaps(_gaps(events, w0, w1), pieces)
    by_host: dict[str, float] = {}
    for name, dur, _ in named:
        by_host[name] = by_host.get(name, 0.0) + dur
    longest = sorted(named, key=lambda g: -g[1])[:top]
    out["idle_by_host"] = sorted(((k, v * 1e-9) for k, v in by_host.items()),
                                 key=lambda kv: -kv[1])
    out["longest_gaps"] = [[n, d * 1e-9] for n, d, _ in longest]
    out["longest_gap_starts_s"] = [(s - w0) * 1e-9 for _, _, s in longest]
    out["spans"] = table
    return out


def per_call(trace: dict | None, span: str,
             stat: str | None = None) -> float | None:
    """A span's self seconds (or the sum of one of its stats) in the
    window, per `snn.run_batch` call; None where the trace holds neither
    (a program without the spans)."""
    table = (trace or {}).get("spans") or {}
    calls = table.get(CALL_SPAN, {}).get("count", 0)
    row = table.get(span)
    if not calls or row is None:
        return None
    value = row["self_s"] if stat is None else row["stats"].get(stat)
    return None if value is None else value / calls
