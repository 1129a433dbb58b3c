"""Config dataclasses + registry for architectures, input shapes, and the
paper-technique (wireless SL/FL/CL) knobs."""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp

_REGISTRY: dict[str, "ArchConfig"] = {}


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN RoPE scaling (arXiv 2309.00071), as DeepSeek-V2 configures it:
    frequencies ramp from interpolated (`factor`) to original between
    the correction dims of `beta_fast` and `beta_slow` rotations over
    `original_max_len` positions; the attention scale gains
    `mscale(mscale_all_dim)**2` and cos/sin the ratio
    `mscale(mscale) / mscale(mscale_all_dim)`."""
    factor: float
    original_max_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | vlm | audio | tiny
    citation: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    shared_experts: int = 0      # shared SwiGLU of width shared_experts * moe_d_ff
    moe_chunk: int = 0           # token-chunked dispatch (0 = auto 16k)
    norm_topk_prob: bool = True  # renormalise the top-k gates (Qwen/Mixtral)
    # the experts this chip holds, [lo, hi); () = all of them
    experts_held: tuple = ()
    first_dense: int = 0         # leading dense (non-MoE) layers, of d_ff
    # latent attention (MLA, DeepSeek-V2): kv_lora_rank > 0 switches it on
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # SSM / hybrid
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    attn_every: int = 0          # hybrid: shared attn block every k ssm blocks
    slstm_every: int = 0         # xlstm: one sLSTM per this many mLSTM blocks
    # attention flavour
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0   # chatglm applies RoPE to half the head dim
    rope_scaling: Optional[RopeScaling] = None
    norm: str = "rmsnorm"        # rmsnorm | layernorm
    norm_eps: float = 1e-5
    tie_embed: bool = True       # LM head = the embedding table
    parallel_block: bool = False # command-r style parallel attn+mlp
    # long context
    sliding_window: int = 0      # 0 = full attention (train); decode long ctx
    # enc-dec
    enc_layers: int = 0          # >0 => encoder-decoder (seamless)
    # multimodal frontends (stubbed per assignment)
    frontend: str = ""           # "" | "vision" | "audio"
    n_frontend_tokens: int = 0
    # numerics
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # grad-accumulation microbatch SIZE for training (0 = one sample per
    # data shard). Large-d_model archs set 8 to halve remat residuals;
    # see EXPERIMENTS.md §Perf A2/B3 for the collective/memory trade.
    microbatch_size: int = 0
    remat: bool = True
    # attention chunking for train/prefill (memory-bounded softmax)
    attn_chunk: int = 512

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def held(self) -> tuple:
        """[lo, hi) of the experts this chip holds."""
        return tuple(self.experts_held) or (0, self.n_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held
        return hi - lo

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: <=2 layers, d_model<=512, <=4 experts."""
        d = min(self.d_model, 256)
        heads = min(self.n_heads, 4)
        kv = min(self.n_kv_heads, heads)
        return dataclasses.replace(
            self,
            n_layers=2, d_model=d, n_heads=heads, n_kv_heads=kv,
            head_dim=d // heads,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            moe_d_ff=min(self.moe_d_ff, 256) if self.moe_d_ff else 0,
            vocab_size=min(self.vocab_size, 1024),
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            attn_every=min(self.attn_every, 1) if self.attn_every else 0,
            slstm_every=min(self.slstm_every, 2) if self.slstm_every else 0,
            enc_layers=2 if self.enc_layers else 0,
            n_frontend_tokens=min(self.n_frontend_tokens, 16) if self.n_frontend_tokens else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=min(self.ssm_head_dim, 32),
            experts_held=(),
            kv_lora_rank=min(self.kv_lora_rank, 64),
            qk_nope_dim=min(self.qk_nope_dim, 32),
            qk_rope_dim=min(self.qk_rope_dim, 16),
            v_head_dim=min(self.v_head_dim, 32),
            attn_chunk=64,
            dtype=jnp.float32,
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode
    microbatch: int = 0          # 0 = auto


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class WirelessConfig:
    """Paper Table I knobs (the paper's technique, first-class)."""
    mode: str = "cl"             # cl | fl | sl
    snr_db: float = 20.0
    fading: bool = True
    quant_bits: int = 8
    split_layer: int = 2         # SL cut point (user-side layer count)
    compress_factor: int = 4     # semantic encoder compression
    grad_clip: float = 0.5       # tau
    local_steps: int = 5         # J (FL)
    n_users: int = 3             # N (FL)
    comm_cycles: int = 7         # K (FL) / 50 for SL-CL
    bandwidth_hz: float = 100e3  # B
    tx_power_w: float = 1e-3     # P
    perfect_channel: bool = False
    # beyond-paper: link-layer ARQ — redraw deep fades (|f|^2 < min) up
    # to `attempts` times; 1 = paper-faithful no-ARQ
    arq_attempts: int = 1
    arq_min_f2: float = 0.25
    # beyond-paper: BOUNDED ARQ — cap the link layer at `arq_max_tx`
    # transmissions per packet; a packet still in outage after the cap
    # is an ERASURE (delivered as zeros, billed as erased_bits). 0 keeps
    # the legacy semantics: `arq_attempts` draws, last one delivered
    # no matter how deep the fade (a crossing can never fail).
    arq_max_tx: int = 0
    # beyond-paper: Gilbert-Elliott burst outages — a two-state Markov
    # link (good/bad) layered over the Rayleigh fades; every ARQ attempt
    # of a packet sent in the bad state fails. p(good->bad) per packet
    # slot; 0.0 = process off (no RNG drawn, goldens bitwise intact).
    ge_p_gb: float = 0.0
    ge_p_bg: float = 0.5
    # beyond-paper: exponential backoff between ARQ retries, billed in
    # TIME (Delivery.outage_s), not bits: retry k waits base * 2^(k-1).
    # 0.0 = retries are back-to-back (no outage time).
    arq_backoff_s: float = 0.0
    # beyond-paper: codeword rounding — "nearest" (paper Eq. 2) or
    # "stochastic" (unbiased E[q] = x/S; tames the pod-mesh FL
    # quant-drift flips where a one-ulp reduction-order difference
    # flips a deterministic round). Packed jnp wire path only.
    rounding: str = "nearest"
    # beyond-paper: server aggregation — "mean" (paper FedAvg, Eq. 3) or
    # "median" (coordinate-wise; robust to a single user's deep-fade
    # MSB flips at zero extra bits)
    aggregate: str = "mean"
    # beyond-paper: FL round scheduling — "barrier" (paper/PR 5: the
    # sync's aggregate is consumed by the same round) or "delayed"
    # (DiLoCo-style async, one-round staleness: round k trains against
    # round k-1's aggregate while round k-1's upload syncs — the
    # collective overlaps the next local phase). Billing is identical:
    # the same fold_in(key, 999) draw covers both.
    sync: str = "barrier"
    # on-wire codeword container — "float32" (abstract b-bit symbols,
    # bills quant_bits), "int8" (byte codewords, Q<=8, bills 8) or
    # "int4" (two codewords per byte, Q<=4, bills 4). Packed/kernel
    # wire paths only; see wire.wire_width.
    wire_dtype: str = "float32"
    # route wire crossings through the Pallas kernel; in FL this also
    # fuses quantize->channel->dequantize->FedAvg into ONE launch
    # (wire.transmit_stacked_mean — allclose, not bitwise, to the
    # default dequant-then-mean path, hence opt-in)
    use_kernel: bool = False


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    import repro.configs  # noqa: F401  (populates registry)
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> list[str]:
    import repro.configs  # noqa: F401
    return sorted(_REGISTRY)
