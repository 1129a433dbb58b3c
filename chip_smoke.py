"""Smoke run of the system's main paths on a TPU, through the entry points
a user calls. Weights are random, made from `--seed`; every input is
generated in-process. Lines starting with `[smoke]` are smoke output:
the times in them are set-up and sanity readings, not benchmark numbers.

    python chip_smoke.py               # one chip: phases `serve` and `train`
    python chip_smoke.py --four-chips  # only the pod-mesh FL round on 4 chips,
                                       # against its one-chip reference

* serve — qwen1.5-0.5b at its published widths through `ServeEngine`
  (continuous batching, chunked prefill, paged KV) on a perfect Radio;
  checks that the compiled decode and prefill programs hold the Pallas
  attention kernels, and compares kernel and plain-jnp attention logits.
* train — (a) the paper's 89,673-parameter classifier, FL through
  `build_scheme` + `Experiment` with the compiled packed-wire kernel;
  (b) qwen1.5-0.5b at published widths, the scaled SL scheme.
* --four-chips — the pod-mesh FL round (user axis on `pod`) against the
  same round on one chip: cycle-1 loss within rtol 2e-4, bills equal.

The device check comes first: without a TPU (or with fewer than 4 for
`--four-chips`) the script exits nonzero before any phase. The last line
printed on success is the JSON object
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}`.
Any failed check raises, and the script exits nonzero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: environment switches the script refuses: the paths it checks are chosen
#: by arguments and by the device, never by the environment
STALE_SWITCHES = ("REPRO_SERVE_KERNEL", "REPRO_PREFILL_IMPL")
#: Bound on ||kernel - jnp|| / ||jnp|| over the last-position logits.
#: Both routes compute in bfloat16 (ulp 2^-8 ~ 3.9e-3) and round at
#: different points (the kernels keep float32 softmax state and output),
#: so they part by a few ulps: 1.86e-2 at the published 24 layers in the
#: Pallas interpreter on CPU. Wrong masking or paging moves whole
#: attention rows, which is far above this bound.
LOGIT_RTOL = 5e-2
#: cycle-1 loss tolerance of the pod-mesh FL round against one chip (the
#: bound tests/dist_checks.py holds the same round to)
POD_LOSS_RTOL = 2e-4
#: qwen1.5-0.5b as cut for the four-chip round, whose one-chip reference
#: holds all four users' weights, momentum and sync buffers. The sync's
#: packed float32 buffer and uint32 rand words scale with the whole
#: model, embedding included: at the published vocabulary the reference
#: does not fit a 16 GB v5e even at one layer (the compiler asks for
#: 16.95 of 15.75 GiB), so the vocabulary is cut as well as the depth.
POD_LAYERS = 4
POD_VOCAB = 65_536


class SmokeError(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def say(*parts) -> None:
    print("[smoke]", *parts, flush=True)


# ------------------------------------------------------------ device
def device_check(min_count: int = 1) -> dict:
    """The TPU the run is on, as JAX reports it; raises SmokeError when
    the default device is not a TPU or there are fewer than `min_count`
    devices. There is no CPU fallback."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say(f"device platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    check(dev["platform"] == "tpu",
          f"no TPU: JAX's default device is {dev['platform']!r}")
    check(dev["count"] >= min_count,
          f"needs {min_count} TPU devices, found {dev['count']}")
    return dev


def pallas_op_names(compiled) -> list:
    """op_name metadata of every Pallas TPU custom call in a compiled
    program (empty when the program holds none)."""
    names = []
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names += re.findall(r'op_name="([^"]*)"', line) or ["?"]
    return names


def require_kernel(compiled, kernel: str, program: str) -> None:
    names = pallas_op_names(compiled)
    check(any(kernel in n for n in names),
          f"{program}: no tpu_custom_call from {kernel} (Pallas calls: "
          f"{names})")


def _finite(x) -> bool:
    import numpy as np
    return bool(np.isfinite(np.asarray(x, np.float64)).all())


# ------------------------------------------------------------- serve
def compare_attention_routes(cfg, params, *, n_slots: int = 8,
                             chunk: int = 32, page_size: int = 16,
                             seed: int = 0) -> dict:
    """Two prompt chunks, then one decode step, once through the Pallas
    paged kernels (`kernel=True`) and once through the plain
    prefill_attention_jnp / decode_attention_jnp route (`kernel=False`)
    on the same weights and tokens. The second chunk attends the first
    chunk's cache across page boundaries, with a different valid length
    per slot. Returns {"prefill_err", "decode_err"}, the relative error
    ||kernel - jnp|| / ||jnp|| of the last-position logits, and the
    same as max|kernel - jnp| / max|jnp| ("*_max_err")."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import ShapeConfig
    from repro.models import transformer
    from repro.runtime.serve_step import (make_paged_decode_step,
                                          make_paged_prefill_step)

    B, C = n_slots, chunk
    S = 2 * C + 8
    n_lp = -(-S // page_size)
    sc = ShapeConfig("smoke", S, B, "decode")
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (3, B, C)), jnp.int32)
    tables = jnp.arange(B * n_lp, dtype=jnp.int32).reshape(B, n_lp)
    full = jnp.full((B,), C, jnp.int32)
    nv2 = jnp.asarray(np.maximum(C - 3 * np.arange(B), 1), jnp.int32)
    out = {}
    for kernel in (True, False):
        pf = jax.jit(make_paged_prefill_step(cfg, sc, page_size,
                                             kernel=kernel))
        dec = jax.jit(make_paged_decode_step(cfg, sc, page_size,
                                             kernel=kernel))
        cache = transformer.init_paged_cache(cfg, B * n_lp, page_size)
        _, cache = pf(params, cache, toks[0], jnp.zeros((B,), jnp.int32),
                      full, tables)
        lg_pre, cache = pf(params, cache, toks[1], full, nv2, tables)
        lg_dec, _ = dec(params, cache, toks[2][:, :1], C + nv2, tables,
                        jnp.ones((B,), bool))
        out[kernel] = (np.asarray(lg_pre, np.float32),
                       np.asarray(lg_dec[:, 0], np.float32))
    errs = {}
    for i, name in enumerate(("prefill", "decode")):
        k, j = out[True][i], out[False][i]
        check(_finite(k) and _finite(j), f"{name} logits are not finite")
        errs[f"{name}_err"] = float(np.linalg.norm(k - j)
                                    / max(np.linalg.norm(j), 1e-30))
        errs[f"{name}_max_err"] = float(np.abs(k - j).max()
                                        / max(np.abs(j).max(), 1e-30))
    return errs


def phase_serve(cfg, *, n_requests: int = 16, n_slots: int = 8,
                new_tokens: int = 16, prompt_lens=(8, 100),
                require_kernels: bool = False, seed: int = 0) -> dict:
    """Serve `n_requests` requests of `make_trace(seed, ...)` through a
    continuous-batching `ServeEngine` (chunked prefill, paged KV, greedy,
    perfect Radio) on random weights. The first pass compiles and warms
    every program; the second is the timed one and must replay it
    token for token. With `require_kernels`, the compiled decode and
    prefill programs must hold the Pallas paged-attention kernels."""
    import jax
    import numpy as np

    from repro.models.api import param_specs
    from repro.nn import init_params
    from repro.serve.engine import ServeEngine
    from repro.serve.trace import make_trace

    params = init_params(jax.random.PRNGKey(seed), param_specs(cfg))
    eng = ServeEngine(cfg, params, n_slots=n_slots, greedy=True)
    trace = make_trace(seed, n_requests, prompt_lens=prompt_lens,
                       new_tokens=(new_tokens, new_tokens),
                       snr_dbs=(20.0,))
    lowered = eng.lower(trace.max_seq_len())
    t0 = time.perf_counter()
    compiled = {name: low.compile() for name, low in lowered.items()}
    compile_s = time.perf_counter() - t0
    if require_kernels:
        require_kernel(compiled["decode"], "gqa_decode_paged",
                       "serve decode")
        for name, c in compiled.items():
            if name.startswith("prefill_"):
                require_kernel(c, "gqa_prefill_paged", f"serve {name}")
    say(f"serve: compiled {len(compiled)} programs in {compile_s:.3f} s "
        "(smoke reading, not a benchmark number)")

    first = eng.serve(trace)
    rep = eng.serve(trace)
    for a, r in zip(first.results, rep.results):
        check(a.tokens == r.tokens, f"request {r.rid}: replay differs")
    for r in rep.results:
        check(r.status == "ok", f"request {r.rid}: status {r.status}")
        check(len(r.tokens) == new_tokens,
              f"request {r.rid}: {len(r.tokens)} tokens, "
              f"expected {new_tokens}")
        check(all(0 <= t < cfg.vocab_size for t in r.tokens),
              f"request {r.rid}: token outside the vocabulary")
        check(r.bits > 0 and r.bits == r.uplink_bits + r.downlink_bits,
              f"request {r.rid}: bits {r.bits} != uplink + downlink")
        check(r.erased_bits == 0.0, f"request {r.rid}: erased bits on a "
              "perfect link")
    check(rep.delivered_bits + rep.erased_bits == rep.bits,
          "serve: delivered + erased != bits")
    errs = compare_attention_routes(cfg, params, n_slots=n_slots,
                                    seed=seed)
    for name in ("prefill_err", "decode_err"):
        check(errs[name] <= LOGIT_RTOL, f"serve: kernel vs jnp {name} "
              f"{errs[name]:.3e} > {LOGIT_RTOL}")
    out = {"requests": len(rep.results), "tokens": rep.generated_tokens,
           "prompt_lens": sorted({r.prompt_len for r in rep.results}),
           "cycles": rep.cycles, "peak_pages": rep.peak_pages,
           "bits": rep.bits, "compile_s": compile_s,
           "tokens_per_s": rep.tokens_per_s(), **errs}
    say(f"serve: {out['requests']} requests ok, {out['tokens']} tokens in "
        f"{rep.cycles} cycles, peak {rep.peak_pages} pages, "
        f"bits {rep.bits}")
    say(f"serve: kernel vs jnp logits, relative error prefill "
        f"{errs['prefill_err']:.3e} decode {errs['decode_err']:.3e} "
        f"(bound {LOGIT_RTOL}); max-relative prefill "
        f"{errs['prefill_max_err']:.3e} decode {errs['decode_max_err']:.3e}")
    say(f"serve: steady {out['tokens_per_s']:.3f} tokens/s "
        f"(second pass, {rep.wall_s:.3f} s; smoke reading, not a "
        "benchmark number)")
    return out


# ------------------------------------------------------------- train
def _check_rounds(exp, res, name: str) -> None:
    """Finite losses, and every cycle billed and delivered whole. No link
    of the smoke run has bounded ARQ (`arq_max_tx` 0), so nothing may be
    erased: delivered = bits - erased = bits. The phases then hold
    `bits` to the payload they sent."""
    check(all(math.isfinite(x) for x in res.loss),
          f"{name}: loss not finite: {res.loss}")
    check(exp.scheme.radio.arq_max_tx == 0,
          f"{name}: the smoke run's links have no bounded ARQ")
    for c, rep in enumerate(exp.reports):
        check(rep.bits > 0, f"{name} cycle {c}: nothing billed")
        check(rep.erased_bits == 0.0,
              f"{name} cycle {c}: {rep.erased_bits} of {rep.bits} bits "
              "erased without bounded ARQ")


def phase_fl_paper(wcfg, *, cycles: int = 2, n_train: int = 24_576,
                   n_test: int = 2_560, require_kernels: bool = False,
                   seed: int = 0) -> dict:
    """The paper's classifier, FL through `build_scheme` + `Experiment`
    (`wcfg.use_kernel` routes the stacked upload through the packed-wire
    kernel). Each cycle bills n_users x params x quant_bits. With
    `require_kernels`, the upload program `Radio.send_stacked` runs,
    compiled with the scheme's own radio arguments, must hold the
    packed-wire kernel."""
    import jax

    from repro.core import wire as W
    from repro.schemes import Experiment, build_scheme

    scheme = build_scheme(wcfg)
    exp = Experiment(scheme, cycles=cycles, seed=seed, n_train=n_train,
                     n_test=n_test)
    t0 = time.perf_counter()
    res = exp.run()
    wall = time.perf_counter() - t0
    _check_rounds(exp, res, "train fl")
    model = exp.final_state.train.trainable["model"]
    n_users = wcfg.n_users
    n_params = sum(int(l.size) for l in jax.tree.leaves(model)) // n_users
    n_leaves = len(jax.tree.leaves(model))
    for c, rep in enumerate(exp.reports):
        check(rep.bits == n_users * n_params * wcfg.quant_bits
              and rep.n_tx == n_users * n_leaves,
              f"train fl cycle {c}: bill {rep.bits} bits / {rep.n_tx} tx, "
              f"expected {n_users * n_params * wcfg.quant_bits} / "
              f"{n_users * n_leaves}")
    if require_kernels:
        r = scheme.radio
        upload = jax.jit(lambda k, t: W.transmit_stacked(
            k, t, r.quant_bits, r.snr_db, return_diag=True,
            **r.wire_kwargs()))
        require_kernel(upload.lower(jax.random.PRNGKey(0), model).compile(),
                       "_transmit_stacked_planned", "fl upload")
    out = {"params": n_params, "users": n_users, "loss": res.loss,
           "accuracy": res.accuracy, "bits": [r.bits for r in exp.reports],
           "wall_s": wall}
    say(f"train fl (paper model, {n_params} params, {n_users} users, "
        f"wire kernel={wcfg.use_kernel}): loss {res.loss}, "
        f"accuracy {res.accuracy}, bits/cycle {out['bits']}, "
        f"{wall:.3f} s with compiles")
    return out


def phase_sl_scaled(cfg, shape, *, cycles: int = 2, steps_per_cycle: int = 2,
                    seed: int = 0) -> dict:
    """The scaled SL scheme (the paper's headline paradigm, Q16 link)
    on `cfg` through `build_scheme` + `Experiment`: `cycles` x
    `steps_per_cycle` fused split steps. On a fault-free link each step
    bills one uplink and one downlink leg of `crossing_elems` x 16 bits."""
    from repro.configs.base import WirelessConfig
    from repro.core.split import crossing_elems
    from repro.schemes import Experiment, build_scheme

    wcfg = WirelessConfig(mode="sl", quant_bits=16)
    scheme = build_scheme(wcfg, cfg=cfg, shape=shape,
                          steps_per_cycle=steps_per_cycle)
    exp = Experiment(scheme, cycles=cycles, seed=seed,
                     n_train=4 * shape.global_batch,
                     n_test=shape.global_batch)
    t0 = time.perf_counter()
    res = exp.run()
    wall = time.perf_counter() - t0
    _check_rounds(exp, res, "train sl")
    leg = crossing_elems(cfg, shape, wcfg)
    for c, rep in enumerate(exp.reports):
        check(rep.bits == 2 * steps_per_cycle * leg * 16,
              f"train sl cycle {c}: {rep.bits} bits, expected "
              f"{2 * steps_per_cycle * leg * 16}")
    out = {"loss": res.loss, "bits": [r.bits for r in exp.reports],
           "wall_s": wall}
    say(f"train sl ({cfg.name}, {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, vocab {cfg.vocab_size}, batch "
        f"{shape.global_batch} x seq {shape.seq_len}): loss {res.loss}, "
        f"bits/cycle {out['bits']}, {wall:.3f} s with compiles")
    return out


# --------------------------------------------------------------- pod
def phase_pod_fl(cfg, shape, mesh, *, cycles: int = 2,
                 local_steps: int = 2, seed: int = 0) -> dict:
    """The scaled FL round with the user axis sharded over `mesh`'s `pod`
    axis (one user per pod slot) against the same round on no mesh,
    in one process: cycle-1 loss within POD_LOSS_RTOL, bills equal."""
    from repro.configs.base import WirelessConfig
    from repro.nn import use_mesh
    from repro.schemes import Experiment, build_scheme

    wcfg = WirelessConfig(mode="fl", quant_bits=8,
                          local_steps=local_steps,
                          n_users=mesh.shape["pod"])

    def run(m, name):
        """-> (RunResult, RoundReports, wall s); the trained state is
        dropped so that the next run has the device to itself."""
        with use_mesh(m):
            exp = Experiment(build_scheme(wcfg, cfg=cfg, shape=shape),
                             cycles=cycles, seed=seed,
                             n_train=4 * wcfg.n_users * shape.global_batch,
                             n_test=shape.global_batch,
                             lr_schedule=lambda e: 1e-3)
            t0 = time.perf_counter()
            res = exp.run()
            wall = time.perf_counter() - t0
        _check_rounds(exp, res, name)
        return res, exp.reports, wall

    res_m, reps_m, wall_m = run(mesh, "pod fl")
    res_1, reps_1, wall_1 = run(None, "one-chip fl")
    check(abs(res_m.loss[0] - res_1.loss[0])
          <= POD_LOSS_RTOL * abs(res_1.loss[0]),
          f"pod fl: cycle-1 loss {res_m.loss[0]} vs {res_1.loss[0]} on "
          f"one chip (rtol {POD_LOSS_RTOL})")
    for c, (a, b) in enumerate(zip(reps_m, reps_1)):
        for f in ("bits", "n_tx", "energy_j", "erased_bits", "outage_s"):
            check(getattr(a, f) == getattr(b, f),
                  f"pod fl cycle {c}: {f} {getattr(a, f)!r} != "
                  f"{getattr(b, f)!r} on one chip")
    out = {"mesh": dict(mesh.shape), "loss": res_m.loss,
           "loss_one_chip": res_1.loss,
           "bits": [r.bits for r in reps_m],
           "wall_s": wall_m, "wall_one_chip_s": wall_1}
    say(f"pod fl ({cfg.name} cut to {cfg.n_layers} layers, vocab "
        f"{cfg.vocab_size}, "
        f"{wcfg.n_users} users on mesh {out['mesh']}): loss {res_m.loss} "
        f"vs one chip {res_1.loss} (cycle-1 rtol {POD_LOSS_RTOL}), "
        f"bits/cycle {out['bits']} on both")
    return out


# -------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pod-mesh FL round on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        stale = [v for v in STALE_SWITCHES if v in os.environ]
        check(not stale, f"unset {stale}: the smoke run takes no "
              "path switches from the environment")
        dev = device_check(4 if args.four_chips else 1)

        from repro.configs import get_arch
        from repro.configs.base import ShapeConfig, WirelessConfig
        from repro.kernels import resolve_interpret
        from repro.launch.compile_cache import enable_persistent_cache

        say(f"compile cache: {enable_persistent_cache()}")
        check(not resolve_interpret(), "Pallas kernels would be "
              "interpreted on this device")
        qwen = get_arch("qwen1.5-0.5b")
        if args.four_chips:
            from repro.launch.mesh import make_mesh
            cfg = dataclasses.replace(qwen, n_layers=POD_LAYERS,
                                      vocab_size=POD_VOCAB)
            say(f"pod fl: cut {qwen.n_layers} -> {POD_LAYERS} layers and "
                f"vocab {qwen.vocab_size} -> {POD_VOCAB} so the one-chip "
                "reference holds all four users")
            phase_pod_fl(cfg, ShapeConfig("smoke", 128, 4, "train"),
                         make_mesh((4, 1, 1), ("pod", "data", "model")),
                         seed=args.seed)
        else:
            phase_serve(qwen, require_kernels=True, seed=args.seed)
            phase_fl_paper(WirelessConfig(mode="fl", use_kernel=True,
                                          snr_db=20.0),
                           require_kernels=True, seed=args.seed)
            phase_sl_scaled(qwen, ShapeConfig("smoke", 128, 8, "train"),
                            seed=args.seed)
    except SmokeError as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
