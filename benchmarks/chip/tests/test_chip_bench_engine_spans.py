"""The engine's spans as the benchmark reads them, on the CPU: summed and
counted over the window, idle gaps put down to the innermost engine
span, a trace with the benchmark's spans alone reduced as before, and
the traced rehearsal of each cell reporting every metric that reads
them."""
from __future__ import annotations

import pytest

from benchmarks.chip import engine_spans, trace_reduce
from benchmarks.chip.trace_reduce import Event
from test_chip_bench_cells import (SPEC, _run,  # noqa: F401 (fixtures)
                                   cpu_as_chip, no_kernel_check)

DEV = "/device:TPU:0"
HOST = "/host:CPU"
ENGINE_METRICS = ("cycle_host_ms", "admit_ms", "sample_keys_ms",
                  "radio_ms", "host_syncs_per_cycle")


def _ev(name, s, d):
    return Event(HOST, "python", name, float(s), float(d))


def _dev(line, name, s, d):
    return Event(DEV, line, name, float(s), float(d))


def _engine_trace():
    """A wave of two cycles inside the window, a span that starts
    before it, and device ops between the host's waits."""
    return [
        _ev("serve.cycle", 0, 90),               # before the window
        _ev("bench.window", 100, 900),
        _ev("bench.wave", 100, 900),
        _ev("serve.cycle", 100, 400),
        _ev("serve.admit", 110, 150),
        _ev("serve.uplink", 130, 50),
        _ev("serve.prefill.wait", 300, 150),
        _ev("serve.cycle", 500, 400),
        _ev("serve.keys", 520, 60),
        _ev("serve.decode.wait", 700, 150),
        _dev("XLA Modules", "jit_prefill_sample(1)", 300, 150),
        _dev("XLA Ops", "%fusion.1 = f32[8] fusion(%p)", 300, 150),
        _dev("XLA Ops", "%fusion.2 = f32[8] fusion(%p)", 700, 150),
    ]


def test_span_time_sums_and_counts_inside_the_window():
    ev = _engine_trace()
    assert engine_spans.span_time(ev, "serve.cycle") == \
        pytest.approx((800e-9, 2))
    assert engine_spans.span_time(ev, "serve.keys") == \
        pytest.approx((60e-9, 1))
    assert engine_spans.span_time(ev, "serve.downlink") == (0.0, 0)


def test_idle_gaps_go_to_the_innermost_engine_span():
    s = trace_reduce.reduce(_engine_trace())
    # gaps [100,300) mid 200: uplink ends 180, admit 260 -> admit;
    # [450,700) mid 575: keys [520,580); [850,1000) mid 925: the wave
    assert dict(s.idle_by_span) == pytest.approx(
        {"serve.admit": 200e-9, "serve.keys": 250e-9,
         "bench.wave": 150e-9})
    assert s.busy_ns == 300


def test_recorded_trace_keeps_engine_spans_by_name(tmp_path):
    """A recorded CPU trace: the engine's spans come back under their
    own names (the request id is a stat, not part of the name), and the
    benchmark's own spans and the reduction are as `trace_reduce`
    reads them alone."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.wave"):
                for rid in range(3):
                    with jax.profiler.TraceAnnotation("serve.admit",
                                                      rid=rid):
                        f(x).block_until_ready()
    path = trace_reduce.find_xplane(str(tmp_path))
    bench_only = trace_reduce.load(path)
    events = engine_spans.load(path)
    assert events[:len(bench_only)] == bench_only
    assert {e.name for e in events[len(bench_only):]} == {"serve.admit"}
    assert engine_spans.span_time(events, "serve.admit")[1] == 3
    a, b = trace_reduce.reduce(bench_only), trace_reduce.reduce(events)
    assert (a.window_ns, a.busy_ns, a.programs, a.ops, a.top_ops) == \
        (b.window_ns, b.busy_ns, b.programs, b.ops, b.top_ops)
    assert {k for k, _ in b.idle_by_span} <= {"serve.admit", "bench.wave"}


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_traced_rehearsal_reads_the_engine_spans(cpu_as_chip, capsys,
                                                  cell):
    rc, res, _ = _run(capsys, cell, trace=1)
    assert rc == 0 and res["correct"] is True
    want = {m["name"] for m in SPEC["per_layer"]
            if m["name"] in ENGINE_METRICS
            and cell in m.get("workloads", [cell])}
    assert want and want <= set(res["metrics"])
    assert all(res["metrics"][m]["value"] > 0 for m in want)
