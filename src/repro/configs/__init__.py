"""Importing this package populates the architecture registry."""
from repro.configs.base import (ArchConfig, ShapeConfig, WirelessConfig,
                                SHAPES, get_arch, list_archs)
from repro.configs import (stablelm_12b, command_r_plus_104b, internvl2_76b,
                           zamba2_1_2b, xlstm_350m, qwen1_5_0_5b,
                           seamless_m4t_medium, chatglm3_6b,
                           llama4_scout_17b_a16e, qwen3_moe_235b_a22b,
                           paper_tinylstm, deepseek_v2_lite)

ASSIGNED = [
    "stablelm-12b", "command-r-plus-104b", "internvl2-76b", "zamba2-1.2b",
    "xlstm-350m", "qwen1.5-0.5b", "seamless-m4t-medium", "chatglm3-6b",
    "llama4-scout-17b-a16e", "qwen3-moe-235b-a22b",
]
