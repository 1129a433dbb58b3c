"""CPU rehearsals of the benchmark's command at tiny sizes: each cell's
driver and metric readers through `run.main` with the look for a chip
skipped, the refusal of a CPU device, the check catching a served token
altered where it is produced, and the float8 control reading above the
program."""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

import pytest

from benchmarks.chip import run as R
from benchmarks.chip.drivers import serve_waves as SW

ROOT = R.ROOT
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [c["name"] for c in SPEC["workloads"]]
METRICS = [m["name"] for kind in ("end_to_end", "per_layer")
           for m in SPEC[kind]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


class Small(R.Bench):
    """The benchmark's own files, at sizes a CPU test holds."""

    def conf(self, cell):
        c = super().conf(cell)
        if c["name"] == "qwen1.5-0.5b":
            c.update(num_hidden_layers=2, hidden_size=256,
                     num_attention_heads=4, num_key_value_heads=4,
                     intermediate_size=512, vocab_size=1024)
        return c

    def mix(self, cell):
        m = super().mix(cell)
        m.update(wave=8, check=4)
        m["engine"].update(n_slots=4, chunk_size=16)
        m["prompt"].update(min=4, max=min(40, m["prompt"]["max"]))
        if "median" in m["prompt"]:
            m["prompt"]["median"] = 20
        m["output"].update(min=min(2, m["output"]["min"]),
                           max=min(6, m["output"]["max"]))
        return m


@pytest.fixture
def no_kernel_check(monkeypatch):
    """The CPU's programs hold no Pallas calls: report the kernels the
    configurations name as found."""
    monkeypatch.setattr(SW, "pallas_op_names",
                        lambda c: ["gqa_decode_paged gqa_prefill_paged"])


@pytest.fixture
def cpu_as_chip(monkeypatch, no_kernel_check):
    """Skip the look for a chip, the compile cache and the kernel check;
    everything else runs as on the chip."""
    monkeypatch.setattr(R, "Bench", Small)
    monkeypatch.setattr(R, "device_check", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(R, "enable_cache", lambda root: "")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_by_name(cell):
    bench = R.Bench()
    wl = bench.cell(cell)
    conf, mix = bench.conf(wl), bench.mix(wl)
    assert conf["name"] == wl["config"]
    assert callable(bench.driver(mix).Cell)
    model = bench.model(wl)
    assert all(callable(getattr(model, f)) for f in (
        "make_weights", "to_program", "program_config", "Reference"))
    assert set(bench.limits(wl)) == {"max_logit_gap"}


@pytest.mark.parametrize("metric", METRICS)
def test_every_metric_has_a_reader(metric):
    assert callable(R.Bench().reader(metric).read)


def _run(capsys, cell, trace=0, seed=2 ** 31 + 7):
    rc = R.main(["--workload", cell, "--seed", str(seed), "--seconds",
                 "0.5", "--trace", str(trace)])
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cpu_as_chip, capsys, cell, trace):
    rc, res, err = _run(capsys, cell, trace)
    assert rc == 0 and RESULT_KEYS <= set(res)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 8
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in SPEC[kind]
            if cell in m.get("workloads", [cell])}
    got = set(res["metrics"])
    assert got <= want
    if not trace:
        assert got == want and all(m["value"] > 0
                                   for m in res["metrics"].values())
    else:
        # no device plane on the CPU: readers of device time are silent
        assert "serve_cycle_ms" in got and "serve_idle_share" in got
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    last = err.strip().splitlines()[-3:]
    assert all(line.startswith("[bench] check ") for line in last)


def test_command_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def _alter_served_tokens(monkeypatch):
    """Every token the engine's prefill and decode programs produce is
    moved to the next id, where it is produced."""
    setup = SW.Cell.setup

    def broken(self, seed):
        setup(self, seed)
        built = self.eng._compiled[max(8, self.S)]
        vocab = self.eng.out_vocab

        def alter(f, *a, **kw):
            tok, cache = f(*a, **kw)
            return (tok + 1) % vocab, cache

        for k in ("prefill_sample", "decode"):
            built[k] = functools.partial(alter, built[k])

    monkeypatch.setattr(SW.Cell, "setup", broken)


@pytest.mark.parametrize("cell", CELLS)
def test_altered_tokens_are_not_correct(cpu_as_chip, monkeypatch, capsys,
                                        cell):
    _alter_served_tokens(monkeypatch)
    rc, res, err = _run(capsys, cell)
    assert rc == 0 and res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def _readings(bench, cell, seed, seconds):
    wl = bench.cell(cell)
    conf, mix, model = bench.conf(wl), bench.mix(wl), bench.model(wl)
    c = SW.Cell(conf, model, mix)
    c.setup(seed)
    c.window(seconds)
    gap, (ctl,) = c.gaps(model.Reference(conf), c.sample(),
                         [model.Reference(conf, control=True)])
    return gap, ctl, json.load(open(os.path.join(
        bench.dir, "cells", cell + ".json")))["limits"]["max_logit_gap"]


def test_float8_control_fails_the_classifier_cell(no_kernel_check):
    """The paper's classifier at its full size: the float8 control, put
    in the program's place, reads over the cell's limit; the program
    (exact float32 on the CPU) reads under it."""
    gap, ctl, limit = _readings(R.Bench(), "tinylstm-serve-classify",
                                seed=5, seconds=2.0)
    assert gap <= limit < ctl


class SmallCold(Small):
    """Every request compared, sampled at T = 0.1: the tiny model's
    logits are too flat for its float8 errors to move a pick that
    Gumbel noise at T = 1 decides."""

    def mix(self, cell):
        m = super().mix(cell)
        m["check"] = 0
        m["engine"]["temperature"] = 0.1
        return m


@pytest.mark.parametrize("cell", ["qwen05b-serve-decode"])
def test_float8_control_reads_above_the_program(no_kernel_check, cell):
    gap, ctl, _ = _readings(SmallCold(), cell, seed=11, seconds=1.0)
    assert ctl > 0.0 and ctl >= 3 * gap
