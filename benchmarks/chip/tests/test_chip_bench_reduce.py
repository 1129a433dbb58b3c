"""The benchmark's yardstick on the CPU: the peak table, the work counts
against hand counts, the trace reduction on hand-made and recorded
traces, and the traffic generator's fixed shapes."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmarks.chip import counts, peaks, trace_reduce, traffic
from benchmarks.chip.trace_reduce import Event

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = peaks.peak_for("TPU v5 lite")


# ------------------------------------------------------------------ peaks
def test_v5e_peak_is_the_published_one():
    assert V5E.flops_bf16 == 197e12 and V5E.hbm_bytes_s == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peak_for(kind)


# ----------------------------------------------------------------- counts
SMALL = counts.Dims(layers=2, d=8, heads=2, kv_heads=1, hd=4, ff=16,
                    vocab=32)


def test_matmul_and_head_flops_by_hand():
    # per layer: q 8*8 + k,v 2*8*4 + o 8*8 + mlp 3*8*16 = 576 weights
    assert counts.matmul_flops_per_token(SMALL) == 2 * 2 * 576
    assert counts.lm_head_flops(SMALL) == 2 * 8 * 32


def test_attention_work_by_hand():
    # decode rows of context 3 and 5: 4*H*hd*8 per layer
    f, b = counts.attn_decode_work(SMALL, [3, 5])
    assert f == 4 * 2 * 4 * 8 * 2
    assert b == 2 * (2 * 8 * 1 * 4 * 2 + 2 * 2 * 2 * 4 * 2)
    # one chunk at start 2 of 3 tokens: queries see 3, 4, 5 keys
    f, b = counts.attn_prefill_work(SMALL, [(2, 3)])
    assert f == 4 * 2 * 4 * 12 * 2
    assert b == 2 * (2 * 5 * 1 * 4 * 2 + 2 * 3 * 2 * 4 * 2)


def test_serve_work_reconstructs_chunks_and_contexts():
    w = counts.serve_work([(300, 3), (5, 1)], chunk=128)
    assert w.chunks == [(0, 128), (128, 128), (256, 44), (0, 5)]
    assert w.decode_contexts == [301, 302]
    assert (w.prefill_tokens, w.decode_tokens) == (305, 2)
    assert (w.first_tokens, w.decode_sampled) == (2, 2)


@pytest.mark.parametrize("flops,byts,bound", [(197e12, 1.0, "compute"),
                                              (1.0, 819e9, "memory")])
def test_share_at_the_bound_time_reads_100(flops, byts, bound):
    share, which = counts.roofline_share(flops, byts, 1.0, V5E)
    assert share == pytest.approx(100.0) and which == bound
    share, _ = counts.roofline_share(flops, byts, 4.0, V5E)
    assert share == pytest.approx(25.0)


def test_real_widths_flops_per_token():
    conf = json.load(open(os.path.join(HERE, "..", "configs",
                                       "qwen1.5-0.5b.json")))
    m = counts.Dims.of(conf)
    assert counts.matmul_flops_per_token(m) == 2 * 24 * 12_845_056
    assert counts.lm_head_flops(m) == 2 * 1024 * 151_936


# ------------------------------------------------------------ trace_reduce
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(line, name, s, d, plane=DEV):
    return Event(plane, line, name, float(s), float(d))


def _synthetic():
    return [
        _ev("python", "bench.window", 100, 900, HOST),
        _ev("python", "bench.wave", 100, 900, HOST),
        _ev("python", "bench.radio", 150, 100, HOST),
        _ev("XLA Modules", "jit_step_sample(3)", 300, 200),
        _ev("XLA Modules", "jit_prefill_sample(4)", 600, 100),
        _ev("XLA Modules", "jit_step_sample(3)", 800, 100),
        _ev("XLA Ops", "%while.4 = (s32[]) while(%tuple.1)", 300, 200),
        _ev("XLA Ops", "%fusion.1 = bf16[8] fusion(%p)", 300, 120),
        _ev("XLA Ops", "%gqa_decode_paged.2 = f32[8] custom-call(%q)",
            400, 100),
        # a consumer names the kernel's output, and is no kernel call
        _ev("XLA Ops", "%fusion.1 = bf16[8] fusion(%gqa_decode_paged.2)",
            450, 30),
        _ev("XLA Ops", "%fusion.3 = f32[8] fusion(%r)", 600, 100),
        _ev("XLA Ops", "%gqa_decode_paged.2 = f32[8] custom-call(%q)",
            800, 50),
        _ev("XLA Ops", "%fusion.1 = bf16[8] fusion(%p)", 50, 100),
    ]


def test_reduce_busy_union_programs_kernels_and_gaps():
    s = trace_reduce.reduce(_synthetic())
    assert s.window_ns == 900 and s.n_devices == 1
    # ops: [100,150) clipped, [300,500), [600,700), [800,850)
    assert s.busy_ns == 50 + 200 + 100 + 50
    assert s.idle_share == pytest.approx(1 - 400 / 900)
    assert s.program_time("jit_step_sample") == pytest.approx((300e-9, 2))
    assert s.program_time("jit_prefill_sample") == pytest.approx((1e-7, 1))
    assert s.program_time("jit_nothing") == (0.0, 0)
    assert s.kernel_time("gqa_decode_paged") == pytest.approx((150e-9, 2))
    assert s.kernel_time("gqa_prefill_paged") == (0.0, 0)
    # gaps: [150,300) mid 225 in radio; [500,600), [700,800), [850,1000)
    # in the wave only
    assert dict(s.idle_by_span) == pytest.approx(
        {"bench.radio": 150e-9, "bench.wave": 350e-9})
    assert [k for k, _ in s.top_ops] == ["fusion.1", "gqa_decode_paged.2",
                                         "fusion.3"]


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        trace_reduce.reduce([e for e in _synthetic()
                             if e.name != "bench.window"])


def test_merge_is_a_union():
    assert trace_reduce.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == \
        [[1, 4], [5, 8]]


def test_recorded_tpu_trace_excerpt():
    """One decode step of qwen1.5-0.5b at 16 slots, recorded on a v5e:
    the program's time, the 24 calls of the decode kernel (and not the
    ops that consume its output) and the busy union, against readings
    taken from the same events by plain filters when it was recorded."""
    with open(os.path.join(HERE, "tpu_trace_excerpt.json")) as f:
        rec = json.load(f)
    events = [Event(*e) for e in rec["events"]]
    s = trace_reduce.reduce(events)
    assert s.busy_ns == pytest.approx(rec["busy_ns"])
    for prog, (ns, runs) in rec["programs"].items():
        assert s.program_time(prog) == pytest.approx((ns * 1e-9, runs))
    for kernel, (ns, n) in rec["kernels"].items():
        assert s.kernel_time(kernel) == pytest.approx((ns * 1e-9, n))
    assert 0.0 < s.idle_share < 1.0


def test_loader_reads_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.wave"):
                f(x).block_until_ready()
    events = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    names = {e.name for e in events}
    assert {"bench.window", "bench.wave"} <= names
    s = trace_reduce.reduce(events)
    assert s.window_ns > 0 and s.busy_ns == 0      # no device plane on CPU


# ---------------------------------------------------------------- traffic
def test_quantiles_include_both_ends():
    q = traffic.quantiles({"dist": "uniform", "min": 5, "max": 30}, 26)
    assert q.tolist() == list(range(5, 31))
    q = traffic.quantiles({"dist": "lognormal", "median": 256,
                           "sigma": 0.8, "min": 64, "max": 1024}, 256)
    assert q[0] == 64 and q[-1] == 1024 and np.all(np.diff(q) >= 0)
    assert 200 <= np.median(q) <= 300


MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "..", "traffic"))
               if f.endswith(".json"))


@pytest.mark.parametrize("mix", MIXES)
def test_every_wave_serves_the_quantiles_in_one_order(mix):
    m = traffic.load_mix(os.path.join(HERE, "..", "traffic",
                                      mix + ".json"))
    a = traffic.wave_pairs(m)
    assert len(a) == m["wave"]
    assert sorted(p for p, _ in a) == traffic.quantiles(m["prompt"],
                                                        m["wave"]).tolist()
    assert sorted(n for _, n in a) == traffic.quantiles(m["output"],
                                                        m["wave"]).tolist()
    assert a == traffic.wave_pairs(m)


def test_alpaca_lengths_keep_the_published_means():
    """vLLM paper, Fig. 11(b): Alpaca's mean input 19.31, output 58.45."""
    m = traffic.load_mix(os.path.join(HERE, "..", "traffic",
                                      "alpaca-replies.json"))
    a = traffic.wave_pairs(m)
    assert abs(np.mean([p for p, _ in a]) - 19.31) < 0.5
    assert abs(np.mean([n for _, n in a]) - 58.45) < 0.5


def test_derived_seeds_are_31_bit_and_distinct():
    s = {traffic.derived_seed(2 ** 33, traffic.WAVES, w) for w in range(50)}
    assert len(s) == 50 and all(0 <= x < 2 ** 31 for x in s)


def test_sample_keeps_the_longest():
    idx = traffic.sample_indices(100, 8, [97], seed=3)
    assert 97 in idx and len(idx) == 8
    assert traffic.sample_indices(5, 0, [1], seed=3) == list(range(5))
