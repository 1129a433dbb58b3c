"""Sizes at which a CPU test runs a configuration that the CPU cannot
hold at its published sizes. Every test here that looks a configuration
up through `run.Bench.conf` gets the test size; the widths, layer kinds,
router and share keep their structure: a dense layer and two MoE layers,
4 of 8 experts held, small latent and rope widths."""
from __future__ import annotations

import pytest

from benchmarks.chip import run as R

#: configuration name -> keys replaced at test size. The CPU's programs
#: hold no Pallas call, so no kernel is looked for in them.
TEST_SIZES = {
    "deepseek-v2-lite": {
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 4, "published": {"n_routed_experts": 8},
        "num_experts_per_tok": 3, "vocab_size": 512, "kernels": {},
    },
}


def test_size(conf: dict) -> dict:
    return dict(conf, **TEST_SIZES.get(conf["name"], {}))


@pytest.fixture(autouse=True)
def cpu_test_sizes(monkeypatch):
    conf = R.Bench.conf
    monkeypatch.setattr(R.Bench, "conf",
                        lambda self, cell: test_size(conf(self, cell)))
