"""The serve engine's own spans (`serve.*`), as the per-layer metrics
and a kept trace read them.

The engine opens each span as a `jax.profiler.TraceAnnotation` and adds
its host seconds and count to `ServeReport.spans`; it counts its
blocking device-to-host reads in `ServeReport.host_syncs`. The metric
readers read those totals over the window's waves (`totals`): the
traced window's profile is reduced and dropped before any reader runs,
and a program without them (an older engine) gives None.

A kept profile holds the same spans on the device's timeline:
`load` returns `trace_reduce.load`'s events with the `serve.*` spans
among the host spans, so that `trace_reduce.reduce` puts each idle gap
down to the innermost engine span, and `span_time` sums one span over
the window.
"""
from __future__ import annotations

from benchmarks.chip import trace_reduce

ENGINE_PREFIX = "serve."


def totals(run):
    """{span: (seconds, count)} summed over the window's waves, or None
    where the engine keeps no span totals."""
    out = {}
    for w in run.cell.waves:
        spans = getattr(w.report, "spans", None)
        if spans is None:
            return None
        for name, (s, n) in spans.items():
            t = out.get(name, (0.0, 0))
            out[name] = (t[0] + s, t[1] + n)
    return out


def load(path: str) -> list:
    """`trace_reduce.load`'s events and the engine's host spans."""
    from jax.profiler import ProfileData
    events = trace_reduce.load(path)
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.is_device_plane(plane.name):
            continue
        for line in plane.lines:
            events += [trace_reduce.Event(plane.name, line.name, e.name,
                                          float(e.start_ns),
                                          float(e.duration_ns))
                       for e in line.events
                       if e.name.startswith(ENGINE_PREFIX)]
    return events


def span_time(events, name: str) -> tuple:
    """(seconds, count) of the spans called `name` that start inside
    the `bench.window` span."""
    wins = [e for e in events if e.name == trace_reduce.WINDOW_SPAN]
    lo = min(e.start_ns for e in wins)
    hi = max(e.end_ns for e in wins)
    inside = [e for e in events if e.name == name
              and lo <= e.start_ns < hi
              and not trace_reduce.is_device_plane(e.plane)]
    return sum(e.dur_ns for e in inside) * 1e-9, len(inside)
