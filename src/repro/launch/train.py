"""Unified training driver: every arch, every paradigm, ONE loop.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --mode fl --steps 20 --reduced --batch 8 --seq 128
    PYTHONPATH=src python -m repro.launch.train --arch paper-tinylstm \
        --mode fl --steps 2

Both the paper's tiny model and the scaled assigned architectures run
through `build_scheme(...)` + `Experiment` (src/repro/schemes/): the
tiny model gets the parity-pinned CL/FL/SL schemes on the sentiment
corpus with the paper's lr schedule; any other arch gets the scaled
schemes (schemes/scaled.py — fused CL/SL train steps, the pod-mesh FL
cycle) on a synthetic Zipf LM corpus at a constant `--lr`. Every
communication cycle is billed into a `RoundReport` (bits / n_tx /
energy), printed per cycle and summarized at exit. On real TPU the
same driver shards over the production mesh; on CPU a 1-device mesh
degrades every sharding rule to replication — same code path.

`--steps` is the target TOTAL optimizer steps (per client); the driver
runs enough communication cycles to reach it (tiny CL/SL cycle = one
corpus epoch; tiny FL cycle = J local epochs; scaled CL/SL cycle =
`--cycle-steps`; scaled FL cycle = `local_steps`). Checkpointing is
`Experiment`'s crash-consistent path (checkpoint/ckpt.py experiment
snapshots): `--ckpt-dir` snapshots the whole run — train pytree,
data-rng state, cycle index, accumulated billing — every
`--ckpt-every` cycles, and a restart with the same `--ckpt-dir`
resumes from the latest snapshot, reproducing the uninterrupted run's
trajectory and billing bit-for-bit (tests/test_resume.py).
"""
from __future__ import annotations

import argparse
import math
import os
import time

import numpy as np

from repro.checkpoint.ckpt import latest_experiment_cycle
from repro.configs import get_arch
from repro.configs.base import ShapeConfig, WirelessConfig
from repro.launch.mesh import make_mesh
from repro.nn import use_mesh
from repro.schemes import BATCH, Experiment, build_scheme


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", default="cl", choices=["cl", "fl", "sl"])
    ap.add_argument("--steps", type=int, default=20,
                    help="target total optimizer steps (per client)")
    ap.add_argument("--cycle-steps", type=int, default=5,
                    help="scaled CL/SL: optimizer steps per cycle")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant (CPU-friendly)")
    ap.add_argument("--optimizer", default=None, choices=["adamw", "sgd"],
                    help="scaled cl/sl optimizer (default adamw); the "
                         "pod-FL cycle and the paper schemes are "
                         "SGD-momentum by construction")
    ap.add_argument("--lr", type=float, default=None,
                    help="constant lr (default: 3e-4 scaled; the paper "
                         "schedule for paper-tinylstm)")
    ap.add_argument("--snr-db", type=float, default=20.0)
    ap.add_argument("--quant-bits", type=int, default=8)
    ap.add_argument("--split-layer", type=int, default=2)
    ap.add_argument("--n-users", type=int, default=3, help="FL users N")
    ap.add_argument("--local-steps", type=int, default=5,
                    help="FL local steps/epochs J")
    ap.add_argument("--sync", default="barrier",
                    choices=["barrier", "delayed"],
                    help="FL round scheduling: barrier (paper) or "
                         "delayed (async, one-round staleness — the "
                         "sync overlaps the next local phase)")
    ap.add_argument("--wire-dtype", default="float32",
                    choices=["float32", "int8", "int4"],
                    help="FL sync codeword container (int4: two "
                         "codewords/byte, needs --quant-bits<=4)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="FL: fuse quantize->channel->dequantize->"
                         "FedAvg into one Pallas launch")
    ap.add_argument("--aot-warmup", action="store_true",
                    help="compile the round program ahead of the first "
                         "cycle and print aot_warmup_compile_wall_s= "
                         "(pairs with the persistent compile cache: "
                         "second runs report near-zero wall)")
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="skip the persistent XLA compile cache "
                         "(launch/compile_cache.py)")
    ap.add_argument("--fleet-size", type=int, default=0,
                    help="run an N-client fleet of the paper tiny model "
                         "instead of the single-link schemes (one cycle "
                         "per --steps step)")
    ap.add_argument("--fleet-engine", default="synthetic",
                    choices=["auto", "loop", "fleet", "synthetic"],
                    help="fleet engine: loop = per-client "
                         "PopulationScheme, fleet = struct-of-arrays "
                         "FleetScheme on the same ClientSpecs (bills "
                         "bit-identical to loop), synthetic = a "
                         "ClientBatch with NO per-client Python objects "
                         "(billing plane, scales to 10^5+), auto = loop")
    ap.add_argument("--fleet-sl-frac", type=float, default=0.0,
                    help="fraction of fleet clients on the SL paradigm")
    ap.add_argument("--fleet-sample", type=int, default=8,
                    help="uniform-k participation per round (0 = all)")
    ap.add_argument("--n-train", type=int, default=0,
                    help="corpus rows (0 = 3072 tiny / 512 scaled)")
    ap.add_argument("--n-test", type=int, default=0,
                    help="held-out rows (0 = 512 tiny / 128 scaled)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10,
                    help="checkpoint every k cycles")
    ap.add_argument("--log-every", type=int, default=1,
                    help="print every k cycles")
    ap.add_argument("--mesh", default="none", choices=["none", "test"],
                    help="test: a one-device (data, model) mesh")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build_wcfg(args) -> WirelessConfig | None:
    if args.mode == "cl":
        return None           # ideal link; the corpus crossing still bills
    if args.mode == "fl":
        return WirelessConfig(mode="fl", snr_db=args.snr_db,
                              quant_bits=args.quant_bits,
                              local_steps=args.local_steps,
                              n_users=args.n_users,
                              sync=args.sync,
                              wire_dtype=args.wire_dtype,
                              use_kernel=args.use_kernel)
    return WirelessConfig(mode="sl", snr_db=args.snr_db,
                          quant_bits=args.quant_bits,
                          split_layer=args.split_layer)


def main(argv=None) -> dict:
    args = parse_args(argv)
    if not args.no_compile_cache:
        from repro.launch.compile_cache import enable_persistent_cache
        enable_persistent_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tiny = cfg.family == "tiny"
    wcfg = build_wcfg(args)
    n_train = args.n_train or (3072 if tiny else 512)
    n_test = args.n_test or (512 if tiny else 128)
    mesh = make_mesh((1, 1), ("data", "model")) \
        if args.mesh == "test" else None

    data = None
    if args.fleet_size > 0:
        if not tiny:
            raise SystemExit("--fleet-size runs the paper tiny model; "
                             "drop --arch or use paper-tinylstm")
        from repro.schemes import (ClientBatch, ClientSpec,
                                   ParticipationPolicy, corpus)
        data = corpus(n_train, n_test, args.seed)
        kwargs = {}
        if args.fleet_sample > 0:
            kwargs["policy"] = ParticipationPolicy.uniform(
                min(args.fleet_sample, args.fleet_size))
        base = WirelessConfig(mode="fl", snr_db=args.snr_db,
                              quant_bits=args.quant_bits)
        if args.fleet_engine == "synthetic":
            batch = ClientBatch.synthetic(args.fleet_size,
                                          seed=args.seed,
                                          quant_bits=args.quant_bits,
                                          sl_frac=args.fleet_sl_frac)
            scheme = build_scheme(base, clients=batch, **kwargs)
        else:
            # loop-expressible specs: one shared shard per client, so
            # the corpus bounds the shard, not the fleet size
            (xtr, ytr), _ = data
            shard = (xtr[:BATCH], ytr[:BATCH])
            n_sl = int(round(args.fleet_size * args.fleet_sl_frac))
            specs = [(ClientSpec.sl(base, shard=shard, quant_bits=16,
                                    name=f"sl{i}") if i < n_sl else
                      ClientSpec.fl(base, shard=shard, name=f"fl{i}"))
                     for i in range(args.fleet_size)]
            scheme = build_scheme(base, clients=specs,
                                  engine=args.fleet_engine, **kwargs)
        spc = 1                  # one communication cycle per step
        lr_schedule = (lambda e: args.lr) if args.lr is not None else None
    elif tiny:
        scheme = build_scheme(wcfg)
        if args.mode == "fl":
            spc = args.local_steps * (n_train // args.n_users // BATCH)
        else:
            spc = n_train // BATCH
        # the paper's lr schedule unless an explicit --lr pins a constant
        lr_schedule = (lambda e: args.lr) if args.lr is not None else None
    else:
        shape = ShapeConfig("cli", args.seq, args.batch, "train",
                            microbatch=args.batch)
        if args.mode == "fl":
            # pod FL is SGD-momentum by construction; refuse rather
            # than silently train a different optimizer than requested
            if args.optimizer not in (None, "sgd"):
                raise SystemExit(
                    f"--mode fl runs SGD-momentum local steps; "
                    f"--optimizer {args.optimizer} is not supported")
            kwargs = {}
        else:
            kwargs = {"optimizer": args.optimizer or "adamw"}
        # build UNDER the mesh: the scaled FL scheme binds explicit
        # in/out shardings to its executable at construction
        with use_mesh(mesh):
            scheme = build_scheme(wcfg, cfg=cfg, shape=shape,
                                  steps_per_cycle=args.cycle_steps,
                                  **kwargs)
        spc = args.local_steps if args.mode == "fl" else args.cycle_steps
        lr = args.lr if args.lr is not None else 3e-4
        lr_schedule = lambda e: lr               # noqa: E731
    cycles = max(1, math.ceil(args.steps / max(spc, 1)))

    if args.aot_warmup:
        from repro.launch.compile_cache import warmup
        with use_mesh(mesh):
            wall = warmup(scheme)
        print(f"aot_warmup_compile_wall_s={wall:.3f}", flush=True)

    history = []
    t0 = time.time()

    def on_cycle(cyc, acc, rep):
        if cyc % args.log_every == 0 or cyc == cycles - 1:
            dt = (time.time() - t0) / (cyc + 1)
            extra = ""
            if "fleet" in rep.metrics:   # streamed fleet summaries
                counts = rep.metrics["fleet"]["status_counts"]
                extra = "  [" + " ".join(
                    f"{k}={v}" for k, v in sorted(counts.items())) + "]"
            print(f"cycle {cyc:4d}  loss {rep.loss:.4f}  acc {acc:.3f}  "
                  f"bits {rep.bits:.3e}  n_tx {rep.n_tx:.0f}  "
                  f"energy {rep.energy_j:.3e} J  ({dt:.2f}s/cycle)"
                  f"{extra}", flush=True)
            history.append({"cycle": cyc, "loss": rep.loss, "acc": acc,
                            "bits": rep.bits})
            assert np.isfinite(rep.loss), f"loss diverged at cycle {cyc}"

    resume = None
    if args.ckpt_dir and latest_experiment_cycle(args.ckpt_dir) is not None:
        resume = args.ckpt_dir
        print(f"resuming from cycle "
              f"{latest_experiment_cycle(args.ckpt_dir)} "
              f"({os.path.abspath(args.ckpt_dir)})")

    with use_mesh(mesh):
        exp = Experiment(scheme, cycles=cycles, seed=args.seed,
                         n_train=n_train, n_test=n_test, data=data,
                         lr_schedule=lr_schedule, on_cycle=on_cycle,
                         checkpoint_dir=args.ckpt_dir or None,
                         checkpoint_every=(args.ckpt_every
                                           if args.ckpt_dir else 0),
                         resume_from=resume)
        res = exp.run()

    init_bits = exp.init_delivery.bits if exp.init_delivery else 0.0
    print(f"done: {cycles} cycles, final acc {res.final_accuracy:.3f}, "
          f"total bits {res.total_bits:.3e} "
          f"(init {init_bits:.3e}), "
          f"energy {sum(r.energy_j for r in exp.reports):.3e} J")
    final_loss = (history[-1]["loss"] if history
                  else (res.loss[-1] if res.loss else 0.0))
    return {"history": history, "final_loss": final_loss, "result": res}


if __name__ == "__main__":
    main()
