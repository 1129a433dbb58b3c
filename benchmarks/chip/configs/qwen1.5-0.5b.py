"""Qwen1.5-0.5B (the Qwen2 architecture): the benchmark's weights and its
plain reference, independent of the program.

The layer, as published: x += o(attn(rope(q(n1(x))), rope(k(n1(x))),
v(n1(x)))); x += down(silu(gate(n2(x))) * up(n2(x))), with RMSNorm
n1, n2, q/k/v biases, rotate-half RoPE, causal softmax attention, and an
LM head tied to the embedding. The reference computes it in float32 at
`Precision.HIGHEST`, one request at a time, with no cache and no kernel.
Its control rounds every matmul operand to float8 e4m3 (activations
scaled per row, weights per matrix), the step below the bfloat16 the
configuration computes in.

Weights are drawn on the device from the seed in one jitted call, in
the published layout. `to_program` hands the program the same arrays
under its own names; the program rotates interleaved pairs where the
published model rotates halves, so its q and k columns are permuted to
match, as any loader of published weights into it must.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def dims(conf: dict) -> tuple:
    h = conf["num_attention_heads"]
    return (conf["num_hidden_layers"], conf["hidden_size"], h,
            conf["num_key_value_heads"], conf["hidden_size"] // h,
            conf["intermediate_size"], conf["vocab_size"])


def make_weights(conf: dict, key) -> dict:
    """Random float32 weights in the published layout, made on the
    device from `key` in one call."""
    L, d, H, Hkv, hd, ff, V = dims(conf)

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 16))

        def n(shape, std):
            return std * jax.random.normal(next(ks), shape, jnp.float32)

        return {
            "embed": n((V, d), 0.02),
            "wq": n((L, d, H * hd), d ** -0.5), "bq": n((L, H * hd), 0.02),
            "wk": n((L, d, Hkv * hd), d ** -0.5),
            "bk": n((L, Hkv * hd), 0.02),
            "wv": n((L, d, Hkv * hd), d ** -0.5),
            "bv": n((L, Hkv * hd), 0.02),
            "wo": n((L, H * hd, d), (H * hd) ** -0.5),
            "w_gate": n((L, d, ff), d ** -0.5),
            "w_up": n((L, d, ff), d ** -0.5),
            "w_down": n((L, ff, d), ff ** -0.5),
            "ln1": 1.0 + n((L, d), 0.05), "ln2": 1.0 + n((L, d), 0.05),
            "ln_f": 1.0 + n((d,), 0.05),
        }

    return make(key)


def _pair_columns(x, n_heads: int, hd: int):
    """Columns of each head reordered from halves to interleaved pairs:
    program column 2j takes column j, and 2j + 1 takes j + hd/2."""
    perm = np.empty(hd, np.int32)
    perm[0::2] = np.arange(hd // 2)
    perm[1::2] = np.arange(hd // 2) + hd // 2
    idx = (np.arange(n_heads)[:, None] * hd + perm[None, :]).reshape(-1)
    return x[..., idx]


def to_program(conf: dict, w: dict) -> dict:
    """The program's parameter tree over the same weights."""
    L, d, H, Hkv, hd, ff, V = dims(conf)
    return {
        "embed": {"table": w["embed"]},
        "layers": {
            "ln_attn": {"scale": w["ln1"]},
            "attn": {
                "wq": {"w": _pair_columns(w["wq"], H, hd),
                       "b": _pair_columns(w["bq"], H, hd)},
                "wk": {"w": _pair_columns(w["wk"], Hkv, hd),
                       "b": _pair_columns(w["bk"], Hkv, hd)},
                "wv": {"w": w["wv"], "b": w["bv"]},
                "wo": {"w": w["wo"]},
            },
            "ln_mlp": {"scale": w["ln2"]},
            "mlp": {"wi": {"w": w["w_up"]}, "wg": {"w": w["w_gate"]},
                    "wo": {"w": w["w_down"]}},
        },
        "ln_f": {"scale": w["ln_f"]},
    }


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
        conf = self.conf
        L, d, H, Hkv, hd, ff, V = dims(conf)
        eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
        S = tokens.shape[0]
        pos = jnp.arange(S, dtype=jnp.float32)
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = pos[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        causal = jnp.tril(jnp.ones((S, S), bool))

        def rms(x, g):
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + eps) * g

        def rope(x):                                   # [S, n, hd]
            x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], -1)

        def layer(x, lw):
            h = rms(x, lw["ln1"])
            q = rope((self._mm(h, lw["wq"]) + lw["bq"]).reshape(S, H, hd))
            k = rope((self._mm(h, lw["wk"]) + lw["bk"]).reshape(S, Hkv, hd))
            v = (self._mm(h, lw["wv"]) + lw["bv"]).reshape(S, Hkv, hd)
            k = jnp.repeat(k, H // Hkv, axis=1)
            v = jnp.repeat(v, H // Hkv, axis=1)
            s = self._mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) \
                / np.sqrt(hd)
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            a = self._mm(p, v.transpose(1, 0, 2)).transpose(1, 0, 2)
            x = x + self._mm(a.reshape(S, H * hd), lw["wo"])
            h = rms(x, lw["ln2"])
            m = jax.nn.silu(self._mm(h, lw["w_gate"])) \
                * self._mm(h, lw["w_up"])
            return x + self._mm(m, lw["w_down"]), None

        layers = {k: w[k] for k in ("wq", "bq", "wk", "bk", "wv", "bv",
                                    "wo", "w_gate", "w_up", "w_down",
                                    "ln1", "ln2")}
        x = w["embed"][tokens]
        x, _ = jax.lax.scan(layer, x, layers)
        x = rms(x, w["ln_f"])[positions]
        return self._mm(x, w["embed"].T)

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
    """The program's configuration of this model, at the file's sizes."""
    import dataclasses

    from repro.configs import get_arch
    L, d, H, Hkv, hd, ff, V = dims(conf)
    return dataclasses.replace(
        get_arch(conf["registry"]), n_layers=L, d_model=d, n_heads=H,
        n_kv_heads=Hkv, head_dim=0, d_ff=ff, vocab_size=V,
        qkv_bias=bool(conf["attention_bias"]),
        rope_theta=float(conf["rope_theta"]))
