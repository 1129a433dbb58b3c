"""Serving driver: many users over the semantic link, billed per user.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --reduced --batch 4 --prompt-len 32 --new-tokens 16 \
        --engine continuous --snr-db 10

Thin front-end over `repro.serve.ServeEngine`: requests come from a
`RequestTrace` (`--trace file.json` to replay, `--requests N` for a
synthetic arrival process, else a uniform all-at-once trace matching
the legacy demo), every prompt uplink and generated-token downlink
crosses the per-user `Radio` (`Radio.send_tokens`), and the run prints
the exact Delivery bill next to the throughput numbers. `--engine
continuous` (default) admits a queued request the moment a slot frees;
`--engine static` re-admits only when the whole batch drains.

Families without a per-slot decode path (ssm / hybrid / audio) fall
back to the legacy single-batch loop — still billed: the prompt batch
rides ONE uplink and the generated tokens ONE downlink through the
same Radio, closing the old drive-the-model-for-free gap.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh
from repro.models import api as M
from repro.nn import init_params, use_mesh
from repro.runtime.serve_step import make_decode_step
from repro.schemes.radio import Radio
from repro.serve import (RequestTrace, ServeEngine, SLOT_FAMILIES,
                         make_trace, uniform_trace)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", default="continuous",
                    choices=["continuous", "static"])
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (engine) / batch rows (legacy)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=0,
                    help=">0: synthetic arrival trace of this many "
                         "requests instead of the uniform demo trace")
    ap.add_argument("--trace", default=None,
                    help="replay a RequestTrace JSON file")
    ap.add_argument("--snr-db", type=float, default=None,
                    help="base link SNR; omit for an ideal noiseless "
                         "link (still billed)")
    ap.add_argument("--arq-max-tx", type=int, default=0,
                    help=">0: bounded ARQ — exhausted uplinks are "
                         "erased and the request abandoned")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="max prompt tokens absorbed per cycle "
                         "(chunked prefill)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged-KV page length in tokens")
    ap.add_argument("--page-budget", type=int, default=0,
                    help=">0: cap the shared page pool at this many "
                         "pages (0 = batch * ceil(S/page-size))")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--mesh", default="none", choices=["none", "test"],
                    help="test: a one-device (data, model) mesh")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--aot-warmup", action="store_true",
                    help="compile the decode step AND every prefill "
                         "bucket before admitting requests and print "
                         "aot_warmup_compile_wall_s= (near-zero on a "
                         "warm persistent cache)")
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="skip the persistent XLA compile cache "
                         "(launch/compile_cache.py)")
    return ap.parse_args(argv)


def make_radio(args) -> Radio:
    if args.snr_db is None:
        return Radio(perfect=True, fading=False,
                     arq_max_tx=args.arq_max_tx)
    return Radio(snr_db=args.snr_db, fading=True,
                 arq_max_tx=args.arq_max_tx,
                 arq_attempts=2 if args.arq_max_tx else 1)


def resolve_trace(args, snr_db: float) -> RequestTrace:
    if args.trace:
        return RequestTrace.load(args.trace)
    if args.requests > 0:
        return make_trace(args.seed, args.requests)
    return uniform_trace(args.seed, args.batch, args.prompt_len,
                         args.new_tokens, snr_db)


def gen_matrix(report, n_new: int) -> np.ndarray:
    """Per-request generated ids as a padded [n_requests, n_new] matrix
    (abandoned requests are all-pad rows)."""
    gen = np.zeros((len(report.results), n_new), np.int32)
    for i, r in enumerate(report.results):
        row = np.asarray(r.tokens[:n_new], np.int32)
        gen[i, :len(row)] = row
    return gen


def sample(key, logits, temperature: float, greedy: bool):
    if greedy or temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


def legacy_main(args, cfg, mesh) -> dict:
    """Single static batch, token-by-token — the only decode path for
    scalar-index families. Prompt uplink + token downlink are billed
    through the same Radio the engine uses."""
    model = M.get_model(cfg)
    if model.decode_step is None:
        raise SystemExit(f"{args.arch} has no decode step (encoder-only)")
    B, P, N = args.batch, args.prompt_len, args.new_tokens
    total = P + N
    key = jax.random.PRNGKey(args.seed)
    radio = make_radio(args)
    bits = energy = erased = 0.0

    with use_mesh(mesh):
        params = init_params(key, M.param_specs(cfg))
        cache = model.init_cache(cfg, B, total)
        if cfg.family == "audio":
            from repro.models import encdec
            frames = 0.1 * jnp.ones((B, encdec.src_len(cfg, total),
                                     cfg.d_model))
            cache = encdec.prefill_cross(params, frames, cfg, cache)
        shape = ShapeConfig("serve", total, B, "decode")
        step = jax.jit(make_decode_step(cfg, shape))

        prompt = jax.random.randint(jax.random.fold_in(key, 1), (B, P), 1,
                                    cfg.vocab_size, jnp.int32)
        # uplink: the users' prompts cross the radio BEFORE the server
        # sees them — the server decodes what was received
        d = radio.send_tokens(jax.random.fold_in(key, 4), prompt,
                              cfg.vocab_size)
        bits += d.bits; energy += d.energy_j; erased += d.erased_bits
        prompt = jnp.asarray(d.payload)
        t0 = time.time()
        logits = None
        for i in range(P):
            logits, cache = step(params, cache, prompt[:, i:i + 1],
                                 jnp.int32(i))
        t_prefill = time.time() - t0

        out = []
        tok = sample(jax.random.fold_in(key, 2), logits[:, 0],
                     args.temperature, args.greedy)[:, None]
        t0 = time.time()
        for j in range(N):
            out.append(np.asarray(tok))
            logits, cache = step(params, cache, tok, jnp.int32(P + j))
            tok = sample(jax.random.fold_in(key, 3 + j), logits[:, 0],
                         args.temperature, args.greedy)[:, None]
        t_decode = time.time() - t0

    gen = np.concatenate(out, axis=1)
    # downlink: generated ids return to the users over the same radio
    d = radio.send_tokens(jax.random.fold_in(key, 5),
                          jnp.asarray(gen), cfg.vocab_size)
    bits += d.bits; energy += d.energy_j; erased += d.erased_bits
    print(f"prefill {P} toks: {t_prefill:.2f}s | decode {N} toks: "
          f"{t_decode:.2f}s ({t_decode / N * 1e3:.1f} ms/tok)")
    print(f"radio: {bits:.0f} bits ({erased:.0f} erased), "
          f"{energy * 1e3:.3f} mJ")
    assert gen.shape == (B, N)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    return {"generated": gen, "t_prefill_s": t_prefill,
            "t_decode_s": t_decode, "bits": bits, "erased_bits": erased,
            "energy_j": energy}


def main(argv=None) -> dict:
    args = parse_args(argv)
    if not args.no_compile_cache:
        from repro.launch.compile_cache import enable_persistent_cache
        enable_persistent_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_mesh((1, 1), ("data", "model")) \
        if args.mesh == "test" else None
    if cfg.family not in SLOT_FAMILIES:
        print(f"{cfg.family}: scalar-index decode only — legacy loop")
        return legacy_main(args, cfg, mesh)

    radio = make_radio(args)
    trace = resolve_trace(args, args.snr_db if args.snr_db is not None
                          else 20.0)
    with use_mesh(mesh):
        params = init_params(jax.random.PRNGKey(args.seed),
                             M.param_specs(cfg))
        engine = ServeEngine(cfg, params, n_slots=args.batch, radio=radio,
                             temperature=args.temperature,
                             greedy=args.greedy, chunk_size=args.chunk_size,
                             page_size=args.page_size,
                             page_budget=args.page_budget)
        if args.aot_warmup:
            wall = engine.warmup_compile(trace.max_seq_len())
            print(f"aot_warmup_compile_wall_s={wall:.3f}", flush=True)
        report = engine.serve(trace, args.engine)

    d = report.to_dict()
    print(f"{args.engine}: {trace.n_requests} requests on "
          f"{args.batch} slots -> {d['cycles']} cycles, "
          f"{d['generated_tokens']} tokens "
          f"({d['tokens_per_s']:.1f} tok/s) | statuses {d['statuses']}")
    print(f"latency p50 {d['p50_latency_cycles']:.0f} / "
          f"p99 {d['p99_latency_cycles']:.0f} cycles | ttft p50 "
          f"{d['p50_ttft_cycles']:.0f} / p99 {d['p99_ttft_cycles']:.0f} "
          f"cycles | radio {d['bits']:.0f} bits "
          f"({d['erased_bits']:.0f} erased), "
          f"{d['energy_j'] * 1e3:.3f} mJ")
    if d["kv"] == "paged":
        print(f"paged kv: {d['peak_pages']}/{d['n_pages']} peak pages "
              f"({args.page_size} tokens each)")
    assert abs(d["delivered_bits"] + d["erased_bits"] - d["bits"]) < 1e-6
    return {"generated": gen_matrix(report, args.new_tokens),
            "report": d, "results": report.results}


if __name__ == "__main__":
    main()
