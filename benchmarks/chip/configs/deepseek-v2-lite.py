"""DeepSeek-V2-Lite (arXiv 2405.04434) as one chip of an 8-way
expert-parallel deployment: the benchmark's weights and its plain
reference, independent of the program.

The layer, as published: x += o(MLA(n1(x))); x += FFN(n2(x)), RMSNorm
n1, n2. MLA: q = x Wq split per head into q_nope (128) and q_pe (64);
[c | k_pe] = x Wkva, c = RMSNorm(c) (512); [k_nope | v] = c Wkvb per
head; YaRN RoPE (arXiv 2309.00071; factor 40 over 4096 positions,
beta 32/1, mscale = mscale_all_dim = 0.707) rotates q_pe and the one
shared k_pe, in the published weights' interleaved layout (the
published code de-interleaves, then rotates halves); softmax over
q.k at (128 + 64)^-1/2 * m^2, m = 0.1 * 0.707 * ln 40 + 1, causal.
FFN: layer 0 dense SwiGLU (10944); layers 1-26 DeepSeekMoE: softmax
router over all 64 experts, greedy top-6, gates not renormalised,
scaled by 1; the experts held here (the first n_routed_experts of the
file) add gate x SwiGLU(1408), the others add nothing (they live on
other chips); 2 shared experts are one SwiGLU of 2816. Untied LM head.

The reference computes this in float32 at `Precision.HIGHEST`, one
request at a time, in the naive (published) attention form, with no
cache and no kernel; the bfloat16 weights are upcast one layer at a
time inside the scan over layers, so that the reference fits beside
them. Its control rounds every matmul operand to float8 e4m3
(activations scaled per row, weights per matrix), the step below the
bfloat16 the configuration computes in.

Weights are drawn on the device from the seed in one jitted call, in
bfloat16 (the published checkpoint's dtype), already in the layout the
program reads, so `to_program` hands it the same arrays without a copy.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
ATTN = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo", "ln1", "ln2")


def dims(conf: dict) -> dict:
    return {
        "L": conf["num_hidden_layers"], "d": conf["hidden_size"],
        "H": conf["num_attention_heads"], "r": conf["kv_lora_rank"],
        "dn": conf["qk_nope_head_dim"], "dr": conf["qk_rope_head_dim"],
        "dv": conf["v_head_dim"], "ff": conf["intermediate_size"],
        "eff": conf["moe_intermediate_size"],
        "E": conf["published"]["n_routed_experts"],
        "Eh": conf["n_routed_experts"], "k": conf["num_experts_per_tok"],
        "sh": conf["n_shared_experts"] * conf["moe_intermediate_size"],
        "V": conf["vocab_size"], "n_dense": conf["first_k_dense_replace"],
    }


def make_weights(conf: dict, key) -> dict:
    """Random bfloat16 weights, [in, out] per matrix, made on the device
    from `key` in one call; the n_dense leading layers under `dense`, the
    MoE layers under `moe`, each stacked on a leading layer axis."""
    m = dims(conf)
    d, H, r, dn, dr, dv = m["d"], m["H"], m["r"], m["dn"], m["dr"], m["dv"]
    n_moe = m["L"] - m["n_dense"]
    bf16 = jnp.bfloat16

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 32))

        def n(shape, std, mean=0.0):
            # one layer at a time, so that no full-size temporary lives
            k = next(ks)
            if len(shape) < 3:
                return (mean + std * jax.random.normal(k, shape)).astype(bf16)
            return jax.lax.map(
                lambda kk: (mean + std * jax.random.normal(
                    kk, shape[1:])).astype(bf16),
                jax.random.split(k, shape[0]))

        def attn(nl):
            return {
                "wq": n((nl, d, H * (dn + dr)), d ** -0.5),
                "wkv_a": n((nl, d, r + dr), d ** -0.5),
                "kv_norm": n((nl, r), 0.05, 1.0),
                "wkv_b": n((nl, r, H * (dn + dv)), r ** -0.5),
                "wo": n((nl, H * dv, d), (H * dv) ** -0.5),
                "ln1": n((nl, d), 0.05, 1.0), "ln2": n((nl, d), 0.05, 1.0),
            }

        dense = attn(m["n_dense"])
        dense.update(w_gate=n((m["n_dense"], d, m["ff"]), d ** -0.5),
                     w_up=n((m["n_dense"], d, m["ff"]), d ** -0.5),
                     w_down=n((m["n_dense"], m["ff"], d), m["ff"] ** -0.5))
        moe = attn(n_moe)
        Eh, eff, sh = m["Eh"], m["eff"], m["sh"]
        moe.update(
            router=n((n_moe, d, m["E"]), d ** -0.5),
            e_gate=n((n_moe, Eh, d, eff), d ** -0.5),
            e_up=n((n_moe, Eh, d, eff), d ** -0.5),
            e_down=n((n_moe, Eh, eff, d), eff ** -0.5),
            s_gate=n((n_moe, d, sh), d ** -0.5),
            s_up=n((n_moe, d, sh), d ** -0.5),
            s_down=n((n_moe, sh, d), sh ** -0.5))
        return {"embed": n((m["V"], d), 0.02), "dense": dense, "moe": moe,
                "ln_f": n((d,), 0.05, 1.0), "lm_head": n((m["V"], d), 0.02)}

    return make(key)


def to_program(conf: dict, w: dict) -> dict:
    """The program's parameter tree over the same arrays (no copy)."""
    def attn(g):
        return {"wq": {"w": g["wq"]}, "wkv_a": {"w": g["wkv_a"]},
                "kv_norm": {"scale": g["kv_norm"]},
                "wkv_b": {"w": g["wkv_b"]}, "wo": {"w": g["wo"]}}

    def block(g):
        return {"ln_attn": {"scale": g["ln1"]}, "attn": attn(g),
                "ln_mlp": {"scale": g["ln2"]}}

    dn, mo = w["dense"], w["moe"]
    return {
        "embed": {"table": w["embed"]},
        "dense_layers": dict(block(dn), mlp={
            "wi": {"w": dn["w_up"]}, "wg": {"w": dn["w_gate"]},
            "wo": {"w": dn["w_down"]}}),
        "layers": dict(block(mo), moe={
            "router": {"w": mo["router"]}, "wi": mo["e_up"],
            "wg": mo["e_gate"], "wo": mo["e_down"],
            "shared": {"wi": {"w": mo["s_up"]}, "wg": {"w": mo["s_gate"]},
                       "wo": {"w": mo["s_down"]}}}),
        "ln_f": {"scale": w["ln_f"]},
        "lm_head": {"table": w["lm_head"]},
    }


def yarn(conf: dict, S: int) -> tuple:
    """(cos, sin) [S, dr] of the published YaRN rotary embedding (cos
    and sin of [freqs, freqs]) and the softmax scale, in numpy float64."""
    rs, dr = conf["rope_scaling"], conf["qk_rope_head_dim"]
    base, f = float(conf["rope_theta"]), float(rs["factor"])
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return dr * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    def mscale(mm):
        return 0.1 * mm * math.log(f) + 1.0 if f > 1 else 1.0

    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), dr - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = np.clip((np.arange(dr // 2) - lo) / (hi - lo), 0.0, 1.0)
    pos_freq = base ** (np.arange(0, dr, 2) / dr)
    inv = (1.0 / (f * pos_freq)) * ramp + (1.0 / pos_freq) * (1.0 - ramp)
    ang = np.arange(S)[:, None] * inv[None, :]
    emb = np.concatenate([ang, ang], -1)
    cs = mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"])
    scale = (conf["qk_nope_head_dim"] + dr) ** -0.5 \
        * mscale(rs["mscale_all_dim"]) ** 2
    return np.cos(emb) * cs, np.sin(emb) * cs, scale


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


class Reference:
    """Logits of the plain forward at chosen positions of each sequence.
    `control=True` is the float8 control."""

    def __init__(self, conf: dict, control: bool = False):
        self.conf = conf
        self.control = control
        self._fn = jax.jit(self._logits)

    def _mm(self, a, b):
        if self.control:
            a, b = _fp8(a, -1), _fp8(b, (-2, -1))
        return jnp.matmul(a, b, precision=HIGHEST)

    def _logits(self, w, tokens, positions):
        conf, m = self.conf, dims(self.conf)
        H, r, dn, dr, dv = m["H"], m["r"], m["dn"], m["dr"], m["dv"]
        eps = conf["rms_norm_eps"]
        S = tokens.shape[0]
        cos, sin, scale = yarn(conf, S)
        cos = jnp.asarray(cos, jnp.float32)[:, None, :]
        sin = jnp.asarray(sin, jnp.float32)[:, None, :]
        causal = jnp.tril(jnp.ones((S, S), bool))
        f32 = jnp.float32

        def rms(x, g):
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + eps) * g

        def rope(x):                                  # [S, n, dr]
            x = x.reshape(S, -1, dr // 2, 2).swapaxes(-1, -2).reshape(
                S, -1, dr)                            # de-interleave
            x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
            return x * cos + jnp.concatenate([-x2, x1], -1) * sin

        def swiglu(h, g, u, dwn):
            return self._mm(jax.nn.silu(self._mm(h, g)) * self._mm(h, u),
                            dwn)

        def attention(x, lw):
            h = rms(x, lw["ln1"])
            q = self._mm(h, lw["wq"]).reshape(S, H, dn + dr)
            kva = self._mm(h, lw["wkv_a"])
            c = rms(kva[:, :r], lw["kv_norm"])
            kv = self._mm(c, lw["wkv_b"]).reshape(S, H, dn + dv)
            q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
            k_pe = jnp.broadcast_to(rope(kva[:, None, r:]), (S, H, dr))
            k = jnp.concatenate([kv[..., :dn], k_pe], -1)
            s = self._mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) * scale
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            a = self._mm(p, kv[..., dn:].transpose(1, 0, 2)).transpose(1, 0, 2)
            return x + self._mm(a.reshape(S, H * dv), lw["wo"])

        def dense_layer(x, lw):
            lw = jax.tree.map(lambda a: a.astype(f32), lw)
            x = attention(x, lw)
            h = rms(x, lw["ln2"])
            return x + swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"]), None

        def moe_layer(x, lw):
            lw = jax.tree.map(lambda a: a.astype(f32), lw)
            x = attention(x, lw)
            h = rms(x, lw["ln2"])
            probs = jax.nn.softmax(self._mm(h, lw["router"]), -1)  # [S, E]
            gate, idx = jax.lax.top_k(probs, m["k"])
            if conf["norm_topk_prob"]:
                gate = gate / gate.sum(-1, keepdims=True)
            gate = gate * conf["routed_scaling_factor"]
            y = swiglu(h, lw["s_gate"], lw["s_up"], lw["s_down"])
            for e in range(m["Eh"]):                  # the held experts
                ge = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)   # [S]
                y = y + ge[:, None] * swiglu(h, lw["e_gate"][e],
                                             lw["e_up"][e], lw["e_down"][e])
            return x + y, None

        x = w["embed"][tokens].astype(f32)
        x, _ = jax.lax.scan(dense_layer, x, w["dense"])
        x, _ = jax.lax.scan(moe_layer, x, w["moe"])
        x = rms(x, w["ln_f"].astype(f32))[positions]
        return self._mm(x, w["lm_head"].astype(f32).T)

    def logits(self, w: dict, requests, s_pad: int, n_pad: int) -> list:
        """`requests`: (tokens, positions) pairs -> [len(positions), V]
        float32 arrays. Every sequence is padded at the end to `s_pad`
        tokens (causal attention never looks ahead, so the padding
        changes nothing before it) and its positions to `n_pad`, so that
        one program serves the whole cell."""
        out = []
        for toks, posn in requests:
            t = np.zeros(s_pad, np.int32)
            t[:len(toks)] = toks
            p = np.zeros(n_pad, np.int32)
            p[:len(posn)] = posn
            lg = self._fn(w, jnp.asarray(t), jnp.asarray(p))
            out.append(np.asarray(lg)[:len(posn)])
        return out


def program_config(conf: dict):
    """The program's configuration of this model, at the file's sizes:
    the held experts are the first `n_routed_experts` of the published
    count, and the weights are stored in bfloat16. The program scales
    no routed output, so the file's `routed_scaling_factor` is 1."""
    import dataclasses

    from repro.configs import get_arch
    from repro.configs.base import RopeScaling
    if conf["routed_scaling_factor"] != 1:
        raise ValueError("routed_scaling_factor must be 1")
    m, rs = dims(conf), conf["rope_scaling"]
    return dataclasses.replace(
        get_arch(conf["registry"]), n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=conf["num_key_value_heads"],
        d_ff=m["ff"], moe_d_ff=m["eff"], vocab_size=m["V"],
        n_experts=m["E"], top_k=m["k"], experts_held=(0, m["Eh"]),
        shared_experts=conf["n_shared_experts"],
        norm_topk_prob=bool(conf["norm_topk_prob"]),
        first_dense=m["n_dense"], kv_lora_rank=m["r"], qk_nope_dim=m["dn"],
        qk_rope_dim=m["dr"], v_head_dim=m["dv"],
        rope_theta=float(conf["rope_theta"]),
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_len=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embed=bool(conf["tie_word_embeddings"]),
        param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
