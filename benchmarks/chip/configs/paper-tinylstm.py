"""The paper's 89,673-parameter sentiment classifier (arXiv 2411.06291,
Sec. III-A): the benchmark's weights and its plain reference,
independent of the program.

Embedding(10,001 x 8) -> Conv1D(32 filters, width 3, valid) + ReLU ->
MaxPool1D(2) -> LSTM(32; gates i, f, g, o) -> Dense(16, ReLU) ->
Dense(1). The served label is the argmax of the logits [0, z], i.e. the
sigmoid head's decision. The reference runs a batch of prompts of up to
`seq_len` tokens in float32 at `Precision.HIGHEST`, masking each row's
LSTM steps to its own length. Its control rounds every matmul operand
to float8 e4m3 (scaled per row and per matrix), the step below the
bfloat16 operands of the TPU's default matmul precision that the
program runs at.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
BLOCK = 512          # rows per reference call


def make_weights(conf: dict, key) -> dict:
    """Random float32 weights, made on the device from `key` in one
    call; the forget-gate bias starts at 1 as in Keras. The label head is
    balanced as a trained one would be: its bias puts the median logit
    over 1,024 random prompts (also from `key`) at 0."""
    V, E = conf["vocab_size"], conf["embedding_dim"]
    F, K = conf["conv_filters"], conf["conv_kernel"]
    H, D = conf["lstm_units"], conf["dense_units"]

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 12))

        def n(shape, std):
            return std * jax.random.normal(next(ks), shape, jnp.float32)

        forget = jnp.zeros(4 * H).at[H:2 * H].set(1.0)
        w = {
            "embed": n((V, E), 0.5),
            "conv_w": n((K, E, F), (K * E) ** -0.5), "conv_b": n((F,), 0.05),
            "lstm_wx": n((F, 4 * H), F ** -0.5),
            "lstm_wh": n((H, 4 * H), H ** -0.5),
            "lstm_b": forget + n((4 * H,), 0.05),
            "dense": {"w": n((H, D), H ** -0.5), "b": n((D,), 0.05)},
            "out": {"w": n((D, 1), D ** -0.5), "b": jnp.zeros((1,))},
        }
        kt, kl = jax.random.split(next(ks))
        toks = jax.random.randint(kt, (1024, conf["seq_len"]), 1, V)
        lens = jax.random.randint(kl, (1024,), K + 1, conf["seq_len"] + 1)
        z = label_logits(w, toks, lens, conf, _matmul)[:, 1]
        w["out"]["b"] = -jnp.median(z)[None]
        return w

    return make(key)


def to_program(conf: dict, w: dict) -> dict:
    """The program names the leaves as the paper's layers are named
    here."""
    return w


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _fp8_matmul(a, b):
    return jnp.matmul(_fp8(a, -1), _fp8(b, (-2, -1)), precision=HIGHEST)


def label_logits(w, tokens, lengths, conf, mm):
    """[n, 2] logits [0, z] of prompts `tokens` [n, S] of `lengths` [n],
    with every matmul done by `mm`."""
    S = tokens.shape[1]
    K, H = conf["conv_kernel"], conf["lstm_units"]
    x = w["embed"][tokens]                                    # [n, S, E]
    conv = sum(mm(x[:, k:S - K + 1 + k], w["conv_w"][k])
               for k in range(K)) + w["conv_b"]
    conv = jax.nn.relu(conv)                                  # [n, S-2, F]
    T = conv.shape[1] // 2
    pooled = jnp.maximum(conv[:, 0:2 * T:2], conv[:, 1:2 * T:2])
    steps = (lengths - (K - 1)) // 2                          # pooled rows

    def cell(carry, inp):
        h, c = carry
        xt, t = inp
        g = mm(xt, w["lstm_wx"]) + mm(h, w["lstm_wh"]) + w["lstm_b"]
        gi, gf, gg, go = jnp.split(g, 4, axis=-1)
        c2 = jax.nn.sigmoid(gf) * c + jax.nn.sigmoid(gi) * jnp.tanh(gg)
        h2 = jax.nn.sigmoid(go) * jnp.tanh(c2)
        live = (t < steps)[:, None]
        return (jnp.where(live, h2, h), jnp.where(live, c2, c)), None

    h0 = jnp.zeros((tokens.shape[0], H), jnp.float32)
    (h, _), _ = jax.lax.scan(cell, (h0, h0),
                             (pooled.swapaxes(0, 1), jnp.arange(T)))
    d = jax.nn.relu(mm(h, w["dense"]["w"]) + w["dense"]["b"])
    z = mm(d, w["out"]["w"]) + w["out"]["b"]                  # [n, 1]
    return jnp.concatenate([jnp.zeros_like(z), z], axis=-1)


class Reference:
    """The label logits [0, z] of each prompt. `control=True` is the
    float8 control."""

    def __init__(self, conf: dict, control: bool = False):
        self.conf = conf
        mm = _fp8_matmul if control else _matmul
        self._fn = jax.jit(lambda w, t, n: label_logits(w, t, n, conf, mm))

    def logits(self, w: dict, requests, s_pad: int, n_pad: int) -> list:
        """`requests`: (tokens, positions) pairs, each asking for the
        logits after its last token -> [1, 2] float32 arrays."""
        S = self.conf["seq_len"]
        out = []
        for b in range(0, len(requests), BLOCK):
            block = requests[b:b + BLOCK]
            toks = np.zeros((BLOCK, S), np.int32)
            lens = np.full(BLOCK, S, np.int32)
            for i, (t, posn) in enumerate(block):
                if list(posn) != [len(t) - 1]:
                    raise ValueError("the classifier has one logit row, "
                                     "after the last prompt token")
                toks[i, :len(t)] = t
                lens[i] = len(t)
            lg = np.asarray(self._fn(w, jnp.asarray(toks),
                                     jnp.asarray(lens)))
            out += [lg[i:i + 1] for i in range(len(block))]
        return out


def program_config(conf: dict):
    """The program's configuration of this model, at the file's sizes."""
    import dataclasses

    from repro.configs import get_arch
    return dataclasses.replace(get_arch(conf["registry"]),
                               vocab_size=conf["vocab_size"])
