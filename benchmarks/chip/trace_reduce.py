"""Reduction of a JAX profiler trace (`.xplane.pb`) to the numbers the
per-layer metrics read: device busy time, time per compiled program and
per kernel, the device operations that took most time, and the idle
gaps, each put down to the benchmark span the host was in.

The trace is read with `jax.profiler.ProfileData`. Device planes are
named `/device:<KIND>:<n>`; on them the line `XLA Modules` holds one
event per program run (`jit_step_sample(17)`), and `XLA Ops` one per
operation, named by its HLO text (`%gqa_decode_paged.8 = f32[...]
custom-call(...)`), so an op is known by the instruction name left of
` = `; a consumer's text names its operands too, and must not count as
them. A loop op (`%while.2`) spans the ops of its body on the same line:
it counts towards busy time (a union) but not among the top ops. Host
spans are the `jax.profiler.TraceAnnotation`s the benchmark opens, all
named with the prefix `bench.`; `bench.window` marks the traced window.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
#: characters of an event's name kept (an op's HLO text can run long)
NAME_CHARS = 160
#: ops that contain other ops of the same line
CONTAINER_OPS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op(self) -> str:
        """The HLO instruction name of an op event (`gqa_decode_paged.8`)."""
        return self.name.split(" = ", 1)[0].lstrip("%")


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith(
        "/device:CPU")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> list:
    """Device events of every line, and the host's benchmark spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name,
                                     e.name[:NAME_CHARS],
                                     float(e.start_ns),
                                     float(e.duration_ns)))
    return out


def merge(intervals) -> list:
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def program_base(name: str) -> str:
    """`jit_step_sample(17)` -> `jit_step_sample`."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclasses.dataclass
class Summary:
    window_ns: float
    n_devices: int
    busy_ns: float                       # mean over devices
    programs: dict                       # base name -> [total ns, runs]
    ops: list                            # device op events in the window
    idle_by_span: list                   # [(span, seconds)], longest first
    top_ops: list                        # [(op name, seconds)]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def program_time(self, prefix: str) -> tuple:
        """(seconds, runs) of the programs whose base name starts with
        `prefix`; (0, 0) when none ran."""
        ns, runs = 0.0, 0
        for name, (t, n) in self.programs.items():
            if name.startswith(prefix):
                ns += t
                runs += n
        return ns * 1e-9, runs

    def kernel_time(self, kernel: str) -> tuple:
        """(seconds, events) of the device ops that are calls of
        `kernel`."""
        ns, n = 0.0, 0
        for e in self.ops:
            if e.op.startswith(kernel):
                ns += e.dur_ns
                n += 1
        return ns * 1e-9, n


def reduce(events, top: int = 10) -> Summary:
    """Reduce `load`'s events over the `bench.window` span."""
    wins = [e for e in events if e.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo = min(e.start_ns for e in wins)
    hi = max(e.end_ns for e in wins)
    dev = [e for e in events if is_device_plane(e.plane)
           and e.start_ns < hi and e.end_ns > lo]
    spans = [e for e in events if not is_device_plane(e.plane)]
    planes = sorted({e.plane for e in dev})
    ops = [e for e in dev if e.line == OPS_LINE]
    mods = [e for e in dev if e.line == MODULES_LINE]
    busy_source = ops or mods
    busy_total = 0.0
    first_merged = []
    for i, p in enumerate(planes):
        merged = merge(_clip([(e.start_ns, e.end_ns) for e in busy_source
                              if e.plane == p], lo, hi))
        busy_total += sum(e - s for s, e in merged)
        if i == 0:
            first_merged = merged
    n_dev = max(1, len(planes))
    programs = collections.defaultdict(lambda: [0.0, 0])
    for e in mods:
        if planes and e.plane == planes[0]:
            rec = programs[program_base(e.name)]
            rec[0] += e.dur_ns
            rec[1] += 1
    by_op = collections.Counter()
    for e in ops:
        if planes and e.plane == planes[0] \
                and not e.op.startswith(CONTAINER_OPS):
            by_op[e.op] += e.dur_ns
    gaps = list(_gaps(first_merged, lo, hi))
    idle = collections.Counter()
    for (s, e), name in zip(gaps, _innermost(spans, [(s + e) / 2.0
                                                     for s, e in gaps])):
        idle[name] += e - s
    return Summary(
        window_ns=hi - lo, n_devices=n_dev, busy_ns=busy_total / n_dev,
        programs=dict(programs), ops=ops,
        idle_by_span=[(k, v * 1e-9) for k, v in idle.most_common(top)],
        top_ops=[(k, v * 1e-9) for k, v in by_op.most_common(top)])


def _gaps(merged, lo, hi):
    t = lo
    for s, e in merged:
        if s > t:
            yield t, s
        t = max(t, e)
    if hi > t:
        yield t, hi


def _innermost(spans, times) -> list:
    """For each of the ascending `times`, the name of the shortest
    benchmark span (other than the window) that covers it."""
    spans = sorted((e for e in spans if e.name != WINDOW_SPAN),
                   key=lambda e: e.start_ns)
    out, active, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i].start_ns <= t:
            active.append(spans[i])
            i += 1
        active = [e for e in active if e.end_ns > t]
        best = min(active, key=lambda e: e.dur_ns) if active else None
        out.append(best.name if best else "outside any inner span")
    return out
