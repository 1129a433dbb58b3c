"""CPU tests of what the cell `dsv2lite-ep8-serve-decode` adds: its run
through `run.main` at test size (see conftest.py) with its new metrics
in the result line, each new reader on hand-made runs (and silent on a
program without held-expert counters), the latent-attention MoE work
counts against hand counts for one request, and the float8 control
reading above the program."""
from __future__ import annotations

import json
import types

import pytest

from benchmarks.chip import counts_mla_moe as cm
from benchmarks.chip import peaks
from benchmarks.chip import run as R
from benchmarks.chip.drivers import serve_waves as SW

CELL = "dsv2lite-ep8-serve-decode"
#: the cell's new per-layer metrics -> the end-to-end metric each moves
NEW = {"moe_gmm_roofline": "serve_tok_s", "mla_decode_roofline": "serve_tok_s",
       "moe_serve_mfu": "serve_tok_s", "expert_imbalance": "serve_tok_s",
       "mla_prefill_roofline": "ttft_p90_s"}


def published_conf() -> dict:
    return R.load_json(f"{R.Bench().dir}/configs/deepseek-v2-lite.json")


@pytest.fixture
def cpu_as_chip(monkeypatch):
    monkeypatch.setattr(R, "device_check", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(R, "enable_cache", lambda root: "")


class Small(R.Bench):
    """Two waves' worth of short requests on 4 slots."""

    def mix(self, cell):
        m = super().mix(cell)
        m.update(wave=8, check=0)
        m["engine"].update(n_slots=4, chunk_size=16)
        m["prompt"].update(min=4, max=20)
        m["output"].update(min=2, max=6)
        return m


def test_cell_reports_its_new_metrics(cpu_as_chip, monkeypatch, capsys):
    monkeypatch.setattr(R, "Bench", Small)
    rc = R.main(["--workload", CELL, "--seed", str(2 ** 31 + 99),
                 "--seconds", "0.5", "--trace", "1"])
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    got = res["metrics"]
    # no device plane on the CPU: the kernel rooflines are silent
    for kernel_metric in ("moe_gmm_roofline", "mla_decode_roofline",
                          "mla_prefill_roofline"):
        assert kernel_metric not in got
    assert got["moe_serve_mfu"]["value"] > 0
    assert 1.0 <= got["expert_imbalance"]["value"] <= 4.0
    assert got["host_syncs_per_cycle"]["value"] > 0


def test_new_metrics_are_listed_for_the_cell_only():
    spec = R.Bench().spec
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name, moves in NEW.items():
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == moves
    for name in ("serve_mfu", "prefill_mfu", "attn_decode_roofline",
                 "attn_prefill_roofline"):
        assert CELL not in per_layer[name]["workloads"]


class Summary:
    """A trace summary with one kernel's time."""

    def __init__(self, kernel, seconds, n=10):
        self.kernel, self.seconds, self.n = kernel, seconds, n

    def kernel_time(self, name):
        return (self.seconds, self.n) if name == self.kernel else (0.0, 0)


def fake_run(conf, report=None, summary=None, window_s=2.0):
    """One request of prompt 3 and 3 tokens, served in a window; chunk 64."""
    q = types.SimpleNamespace(prompt_len=3, max_new_tokens=3)
    r = types.SimpleNamespace(tokens=(1, 2, 3))
    waves = [types.SimpleNamespace(report=report or types.SimpleNamespace())]
    cell = types.SimpleNamespace(waves=waves, results=lambda: [(0, q, r)])
    return types.SimpleNamespace(
        conf=conf, cell=cell, mix={"engine": {"chunk_size": 64}},
        summary=summary, window_s=window_s,
        peak=peaks.peak_for("TPU v5 lite"))


def counters(rows=12, busiest=4, groups=5):
    return types.SimpleNamespace(expert_rows=rows, expert_rows_max=busiest,
                                 expert_groups=groups)


def test_work_counts_by_hand():
    """Published widths, one request of 3 prompt tokens and 3 generated:
    attention weights per layer 2048*3072 + 2048*576 + 512*4096 +
    2048*2048 = 13,762,560; the dense FFN 3*2048*10944 = 67,239,936; an
    MoE layer's router and shared experts 2048*64 + 3*2048*2816 =
    17,432,576; a held expert's row 3*2048*1408 = 8,650,752; the head
    2048*102400 = 209,715,200. Decode attends 4 and 5 keys; the chunk's
    causal triangle holds 6; a key costs 2*16*(576 + 512) = 34,816."""
    m = cm.Dims.of(published_conf())
    assert (m.layers, m.dense_layers, m.moe_layers, m.held, m.experts,
            m.row) == (27, 1, 26, 8, 64, 576)
    assert cm.token_flops(m) == 2 * (27 * 13762560 + 67239936
                                     + 26 * 17432576) == 1784152064
    assert cm.attn_decode_work(m, [4, 5]) == (
        34816 * 9 * 27, 27 * (9 * 576 * 2 + 2 * 16 * 576 * (2 + 4)))
    assert cm.attn_prefill_work(m, [(0, 3)]) == (
        34816 * 6 * 27, 27 * (3 * 576 * 2 + 3 * 16 * 576 * (2 + 4)))
    from benchmarks.chip import counts
    w = counts.serve_work([(3, 3)], 64)
    assert cm.serve_flops(m, w, 12) == (
        1784152064 * 5 + 2 * 8650752 * 12 + 2 * 209715200 * 3
        + 34816 * 6 * 27 + 34816 * 9 * 27) == 10400770048
    assert cm.expert_bytes(m, 12, 5) == 5 * 8650752 * 2 + 12 * (
        2 * 2048 * 2 + 2 * 1408 * 4 + 1408 * 2 + 2048 * 4) == 86873088


def test_readers_by_hand():
    conf, peak = published_conf(), peaks.peak_for("TPU v5 lite")
    m = cm.Dims.of(conf)
    run = fake_run(conf, counters(), Summary("moe_gmm", 1e-3))
    want = 100 * max(2 * 8650752 * 12 / peak.flops_bf16,
                     86873088 / peak.hbm_bytes_s) / 1e-3
    assert R.Bench().reader("moe_gmm_roofline").read(run) == \
        pytest.approx(want)
    run = fake_run(conf, counters(), Summary("mla_decode_paged", 1e-4))
    f, b = cm.attn_decode_work(m, [4, 5])
    want = 100 * max(f / peak.flops_bf16, b / peak.hbm_bytes_s) / 1e-4
    assert R.Bench().reader("mla_decode_roofline").read(run) == \
        pytest.approx(want)
    run = fake_run(conf, counters(), Summary("mla_prefill_paged", 1e-4))
    f, b = cm.attn_prefill_work(m, [(0, 3)])
    want = 100 * max(f / peak.flops_bf16, b / peak.hbm_bytes_s) / 1e-4
    assert R.Bench().reader("mla_prefill_roofline").read(run) == \
        pytest.approx(want)
    run = fake_run(conf, counters(), window_s=2.0)
    assert R.Bench().reader("moe_serve_mfu").read(run) == pytest.approx(
        100 * 10400770048 / 2.0 / peak.flops_bf16)
    # busiest 4 against a mean of 12 / 8 held experts
    assert R.Bench().reader("expert_imbalance").read(run) == \
        pytest.approx(8 * 4 / 12)


@pytest.mark.parametrize("metric", NEW)
def test_readers_are_silent_without_counters_or_kernel(metric):
    """A program without the held-expert counters (the parent of the
    change that adds them) and a trace without the kernel read nothing
    and raise nothing."""
    conf = published_conf()
    run = fake_run(conf, None, Summary("other_kernel", 1.0))
    assert R.Bench().reader(metric).read(run) is None


def test_float8_control_reads_above_the_program(monkeypatch):
    """At test size, every request compared at T = 0.1 (see the qwen
    cell's test): the float8 control's picks lie further below the
    reference's best than the program's."""
    monkeypatch.setattr(SW, "pallas_op_names", lambda c: [])

    class Cold(Small):
        def mix(self, cell):
            m = super().mix(cell)
            m["engine"]["temperature"] = 0.1
            return m

    bench = Cold()
    wl = bench.cell(CELL)
    conf, mix, model = bench.conf(wl), bench.mix(wl), bench.model(wl)
    c = SW.Cell(conf, model, mix)
    c.setup(13)
    c.window(1.0)
    gap, (ctl,) = c.gaps(model.Reference(conf), c.sample(),
                         [model.Reference(conf, control=True)])
    assert ctl > 0.0 and ctl >= 3 * gap
