"""The serve driver: closed-loop waves of offline batches through the
program's `ServeEngine` (continuous batching, chunked prefill, paged KV)
on a perfect radio link, which is noiseless but billed.

One wave is one `ServeEngine.serve` call on a trace whose requests all
arrive at cycle 0; `serve` returns when the last of them has finished
and its tokens have been read back to the host. Waves start until the
window's seconds have passed, so the window is whole waves.

The check compares what the window served with the configuration's
plain reference, on a sample of the window's requests drawn from the
seed, the longest always among them: the reference runs each prompt
followed by its served tokens, and the number compared is the widest
gap by which a served token's score lies below the reference's best
score at that position. The score is the logit under greedy decoding;
under sampling at temperature T it is logit / T plus the Gumbel noise
of the request's sampling key, so that the reference picks, at each
position, the token the engine's `jax.random.categorical` would have
picked from the reference's logits. Prompt contents and sampling keys
follow the engine's documented RNG streams: under `PRNGKey(seed + 13)`,
request `rid` folds `rid`, then 3 for its prompt (uniform ids in
[1, vocab)) and 9, t for its t-th sampled token. Bills are held
exactly: uplink = prompt tokens x codeword bits, downlink = served
tokens x codeword bits, bits = uplink + downlink, nothing erased.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import traffic

#: the engine's serving RNG stream offset and its folds
SERVE_STREAM, PROMPT_FOLD, SAMPLE_FOLD = 13, 3, 9


def codeword_bits(vocab: int) -> int:
    """Fixed-width codeword of one token id out of `vocab`."""
    return max(1, (int(vocab) - 1).bit_length())


def prompt_tokens(trace_seed: int, rid: int, n: int, vocab: int):
    kreq = jax.random.fold_in(jax.random.PRNGKey(trace_seed + SERVE_STREAM),
                              rid)
    return np.asarray(jax.random.randint(
        jax.random.fold_in(kreq, PROMPT_FOLD), (n,), 1, vocab, jnp.int32))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _gumbel(trace_seed, rid, n: int, vocab: int):
    kreq = jax.random.fold_in(jax.random.PRNGKey(trace_seed + SERVE_STREAM),
                              rid)
    ks = jax.random.fold_in(kreq, SAMPLE_FOLD)
    return jax.vmap(lambda t: jax.random.gumbel(
        jax.random.fold_in(ks, t), (vocab,), jnp.float32))(jnp.arange(n))


def gumbel_noise(trace_seed: int, rid: int, n: int, n_pad: int,
                 vocab: int):
    """[n, vocab] Gumbel noise of the request's first n sampled tokens
    (drawn n_pad at a time, so that one program serves every request)."""
    return np.asarray(_gumbel(trace_seed, rid, n_pad, vocab))[:n]


def spanned_radio():
    """A perfect, non-fading `Radio` whose token sends are benchmark
    spans (`bench.radio`), so that the trace can put idle device time
    down to billing."""
    from repro.schemes.radio import Radio

    @dataclasses.dataclass(frozen=True)
    class SpannedRadio(Radio):
        def send_tokens(self, *a, **kw):
            with jax.profiler.TraceAnnotation("bench.radio"):
                return super().send_tokens(*a, **kw)

    return SpannedRadio(perfect=True, fading=False)


@dataclasses.dataclass
class Wave:
    trace_seed: int
    trace: object           # RequestTrace
    report: object          # ServeReport


class Cell:
    """One configuration under one serve mix: `setup`, then `window`,
    then `checks`."""

    def __init__(self, conf: dict, model, mix: dict):
        self.conf, self.model, self.mix = conf, model, mix
        self.eng = None
        self.waves: list = []

    # ------------------------------------------------------------ setup
    def setup(self, seed: int) -> None:
        """Weights from the seed, the engine, every program compiled (and
        the attention kernels found in them), and one warm wave."""
        from repro.serve.engine import ServeEngine
        t0 = time.perf_counter()
        self.seed = seed
        self.cfg = self.model.program_config(self.conf)
        self.pairs = traffic.wave_pairs(self.mix)
        self.S = traffic.max_seq_len(self.pairs)
        self.set_weights(seed)
        e = self.mix["engine"]
        self.eng = ServeEngine(
            self.cfg, self.params, n_slots=e["n_slots"],
            radio=spanned_radio(), greedy=bool(e["greedy"]),
            temperature=float(e.get("temperature", 1.0)),
            prefill="chunked", kv="paged", chunk_size=e["chunk_size"],
            page_size=e.get("page_size", 16))
        t1 = time.perf_counter()
        compiled = {k: low.compile()
                    for k, low in self.eng.lower(self.S).items()}
        t2 = time.perf_counter()
        for kind, kernel in self.conf.get("kernels", {}).items():
            progs = [c for k, c in compiled.items() if k.startswith(kind)]
            if not progs or not all(kernel in " ".join(pallas_op_names(c))
                                    for c in progs):
                raise RuntimeError(f"{kind} programs hold no Pallas call "
                                   f"from {kernel}")
        t3 = time.perf_counter()
        self.serve_wave(0)
        self.setup_phases = {"weights": t1 - t0, "compile": t2 - t1,
                             "kernel_check": t3 - t2,
                             "warm_wave": time.perf_counter() - t3}

    def set_weights(self, seed: int) -> None:
        """The benchmark's weights for `seed`, and the program's view of
        them (on the engine too, when there is one)."""
        key = jax.random.PRNGKey(traffic.derived_seed(seed, traffic.WEIGHTS))
        self.weights = self.model.make_weights(self.conf, key)
        self.params = self.model.to_program(self.conf, self.weights)
        jax.block_until_ready(self.params)
        check_tree(self.params, self.cfg)
        if self.eng is not None:
            self.eng.params = self.params

    def serve_wave(self, w: int) -> Wave:
        from repro.serve.trace import Request, RequestTrace
        ts = traffic.derived_seed(self.seed, traffic.WAVES, w)
        trace = RequestTrace(ts, tuple(
            Request(rid, 0, p, n) for rid, (p, n) in enumerate(self.pairs)))
        with jax.profiler.TraceAnnotation("bench.wave"):
            return Wave(ts, trace, self.eng.serve(trace))

    # ----------------------------------------------------------- window
    def window(self, seconds: float) -> float:
        """Serve waves until `seconds` have passed; returns the window's
        length in seconds, from the start of the first wave to the end
        of the last."""
        self.waves = []
        t0 = time.perf_counter()
        w = 1
        while True:
            self.waves.append(self.serve_wave(w))
            w += 1
            if time.perf_counter() - t0 >= seconds:
                return time.perf_counter() - t0

    def results(self):
        """(trace seed, request, result) of every request of the
        window."""
        out = []
        for wave in self.waves:
            reqs = {r.rid: r for r in wave.trace.requests}
            out += [(wave.trace_seed, reqs[r.rid], r)
                    for r in wave.report.results]
        return out

    def release(self) -> None:
        """Drop the program's state; the benchmark's weights stay for
        the reference."""
        self.eng = None
        self.params = None

    # ------------------------------------------------------------ check
    def counts(self) -> dict:
        """Requests attempted and failed (not served whole), and bills
        that are not exact."""
        out_vocab = self.conf.get("num_labels", self.conf["vocab_size"])
        up_w = codeword_bits(self.conf["vocab_size"])
        down_w = codeword_bits(out_vocab)
        res = self.results()
        failed = sum(1 for _, q, r in res if r.status != "ok"
                     or len(r.tokens) != q.max_new_tokens
                     or not all(0 <= t < out_vocab for t in r.tokens))
        bad_bill = sum(1 for _, q, r in res if not (
            r.uplink_bits == q.prompt_len * up_w
            and r.downlink_bits == len(r.tokens) * down_w
            and r.bits == r.uplink_bits + r.downlink_bits
            and r.erased_bits == 0.0))
        return {"attempted": len(res), "failed": failed,
                "bill_errors": bad_bill}

    def checks(self, limits: dict) -> dict:
        """The numbers compared, each beside its limit: the widest gap of
        the sampled served tokens below the reference, requests not
        served whole, and bills that are not exact."""
        c = self.counts()
        t0 = time.perf_counter()
        gap, _ = self.gaps(self.model.Reference(self.conf), self.sample())
        print(f"[bench] reference {time.perf_counter() - t0!r} s",
              file=sys.stderr)
        return {
            "max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]},
            "failed_requests": {"value": c["failed"], "limit": 0},
            "bill_errors": {"value": c["bill_errors"], "limit": 0},
        }

    def sample(self) -> list:
        res = self.results()
        longest = max(range(len(res)), key=lambda i: res[i][1].prompt_len
                      + res[i][1].max_new_tokens)
        idx = traffic.sample_indices(len(res), int(self.mix["check"]),
                                     [longest], self.seed)
        return [res[i] for i in idx if res[i][2].status == "ok"]

    def gaps(self, reference, sample, controls=()) -> tuple:
        """Widest gap of the served tokens below the reference's best
        score, and of the tokens each control reference puts first."""
        vocab = self.conf["vocab_size"]
        e = self.mix["engine"]
        greedy, temp = bool(e["greedy"]), float(e.get("temperature", 1.0))
        reqs = []
        for ts, q, r in sample:
            toks = np.concatenate([prompt_tokens(ts, q.rid, q.prompt_len,
                                                 vocab),
                                   np.asarray(r.tokens[:-1], np.int32)])
            reqs.append((toks, list(range(q.prompt_len - 1, len(toks)))))
        s_pad = 64 * -(-self.S // 64)
        n_pad = 8 * -(-max(n for _, n in self.pairs) // 8)
        ref = reference.logits(self.weights, reqs, s_pad, n_pad)
        ctl = [c.logits(self.weights, reqs, s_pad, n_pad) for c in controls]
        served_gap, ctl_gaps = 0.0, [0.0] * len(controls)
        for i, (ts, q, r) in enumerate(sample):
            score = ref[i]
            if not greedy:
                g = gumbel_noise(ts, q.rid, len(r.tokens), n_pad,
                                 score.shape[1])
                score = score / temp + g
            best = score.max(axis=1)
            rows = np.arange(len(r.tokens))
            served_gap = max(served_gap, float(
                (best - score[rows, np.asarray(r.tokens)]).max()))
            for j, c in enumerate(ctl):
                cs = c[i] if greedy else c[i] / temp + g
                pick = cs.argmax(axis=1)
                ctl_gaps[j] = max(ctl_gaps[j],
                                  float((best - score[rows, pick]).max()))
        return served_gap, ctl_gaps


def check_tree(params, cfg) -> None:
    """The weights handed to the program have the tree and shapes of
    the program's own parameters."""
    from repro.models.api import param_specs
    from repro.nn import shapes_tree
    want = shapes_tree(param_specs(cfg))
    if jax.tree.structure(want) != jax.tree.structure(params):
        raise ValueError("weights do not match the program's parameter "
                         "tree")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params)):
        if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
            raise ValueError(f"weight {b.shape} {b.dtype} where the "
                             f"program has {a.shape} {a.dtype}")


def pallas_op_names(compiled) -> list:
    """op_name metadata of every Pallas TPU custom call in a compiled
    program."""
    import re
    names = []
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names += re.findall(r'op_name="([^"]*)"', line) or ["?"]
    return names
