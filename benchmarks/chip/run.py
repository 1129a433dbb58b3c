"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout. Everything is found by name from
`BENCHMARK.json`: the cell names its configuration and its traffic mix;
`configs/<config>.json` holds the sizes as run and `configs/<config>.py`
the weights and the plain reference; `traffic/<mix>.json` holds the
mix's parameters and names its driver, `drivers/<driver>.py`;
`cells/<cell>.json` the limits of the cell's check; `metrics/<metric>.py`
the reader of each metric, end-to-end and per-layer.

A run checks the device first (a TPU, as many chips as the cell asks
for, kernels compiled, not interpreted) and exits 2 without a result
otherwise. Set-up (`setup_s`) runs from the start of the process to the
start of the window: imports, weights, compiling (from the persistent
cache in the checkout's `.jax_cache/` after the first run) and one warm
wave. The window then runs for `--seconds`; with `--trace 1` it runs
under the profiler, for at most TRACE_SECONDS, and the per-layer metrics
are read from that trace. Then the program's state is freed and what the
window served is checked against the reference. The last lines on
standard error are the numbers compared, each beside its limit; the last
line on standard output is the result, as JSON.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: the longest window a traced run records
TRACE_SECONDS = 10.0
#: the compile requests JAX makes while its persistent cache is on
COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """The benchmark's files, found by name."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, "benchmarks", "chip")

    def cell(self, name: str) -> dict:
        for c in self.spec["workloads"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def conf(self, cell: dict) -> dict:
        return load_json(os.path.join(self.dir, "configs",
                                      cell["config"] + ".json"))

    def model(self, cell: dict):
        return load_module(os.path.join(self.dir, "configs",
                                        cell["config"] + ".py"),
                           "bench_config_" + cell["config"])

    def mix(self, cell: dict) -> dict:
        return load_json(os.path.join(self.dir, "traffic",
                                      cell["traffic"] + ".json"))

    def limits(self, cell: dict) -> dict:
        return load_json(os.path.join(self.dir, "cells",
                                      cell["name"] + ".json"))["limits"]

    def metrics(self, cell: dict, kind: str) -> list:
        return [m for m in self.spec[kind]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, metric: str):
        return load_module(os.path.join(self.dir, "metrics",
                                        metric + ".py"),
                           "bench_metric_" + metric.replace(".", "_"))

    def driver(self, mix: dict):
        return importlib.import_module("benchmarks.chip.drivers."
                                       + mix["driver"])


def device_check(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise NoDevice(f"no TPU: JAX's default device is "
                       f"{dev['platform']!r}")
    if dev["count"] < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds "
                       f"{dev['count']}")
    from repro.kernels import resolve_interpret
    if resolve_interpret():
        raise NoDevice("Pallas kernels would be interpreted")
    return dev


def enable_cache(root: str) -> str:
    """The persistent compilation cache, at the checkout's fixed
    `.jax_cache/`, through the program's own switch."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                           ".jax_cache")
    from repro.launch.compile_cache import enable_persistent_cache
    return enable_persistent_cache()


class CompileCounter:
    """Counts the programs JAX builds (compiled or read from the
    persistent cache) while `on`."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, name: str, **kw) -> None:
        if self.on and name == COMPILE_EVENT:
            self.n += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_listener(self._event)


class Run:
    """What a metric reader reads: the driver's cell after its window,
    the window's length, the set-up time, the trace summary (traced
    runs), the peak."""

    def __init__(self, cell, conf, mix, window_s, setup_s, peak,
                 summary=None):
        self.cell, self.conf, self.mix = cell, conf, mix
        self.window_s, self.setup_s = window_s, setup_s
        self.peak, self.summary = peak, summary


def traced_window(cell, seconds: float):
    """The window under the profiler; returns (seconds, trace summary).
    Python calls are not traced, so only the benchmark's spans stand for
    the host."""
    import jax

    from benchmarks.chip import trace_reduce
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(tmp, profiler_options=opts):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                window_s = cell.window(seconds)
        events = trace_reduce.load(trace_reduce.find_xplane(tmp))
        return window_s, trace_reduce.reduce(events)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    bench = Bench()
    wl = bench.cell(args.workload)
    conf, mix = bench.conf(wl), bench.mix(wl)
    try:
        dev = device_check(wl["chips"])
    except NoDevice as e:
        print(f"[bench] no result: {e}", file=sys.stderr, flush=True)
        return 2
    import jax

    from benchmarks.chip import peaks
    peak = peaks.peak_for(dev["kind"])
    enable_cache(bench.root)
    compiles = CompileCounter()
    cell = bench.driver(mix).Cell(conf, bench.model(wl), mix)
    cell.setup(args.seed)
    setup_s = time.perf_counter() - T_START

    compiles.on = True
    if args.trace:
        window_s, summary = traced_window(
            cell, min(args.seconds, TRACE_SECONDS))
    else:
        window_s, summary = cell.window(args.seconds), None
    compiles.on = False
    compiles.close()
    used = jax.devices()[:wl["chips"]]
    dev["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in used)
    run = Run(cell, conf, mix, window_s, setup_s, peak, summary)

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in bench.metrics(wl, kind):
        v = bench.reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if args.trace:
        dev["busy_s"] = summary.busy_ns * 1e-9
        dev["window_s"] = summary.window_ns * 1e-9

    counts = cell.counts()
    cell.release()
    checks = cell.checks(bench.limits(wl))
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    print(f"[bench] compiles in window {compiles.n}; window {window_s!r} "
          f"s; set-up {setup_s!r} s {cell.setup_phases}", file=sys.stderr)
    for name, c in checks.items():
        print(f"[bench] check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out = {"correct": correct, "attempted": counts["attempted"],
           "failed": counts["failed"], "metrics": metrics, "device": dev,
           "compiles_in_window": compiles.n}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary.top_ops,
                            "idle_gaps": summary.idle_by_span}
    out["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
