"""Scaled-architecture schemes: the pod-mesh FL step and the fused
CL/SL train steps behind the SAME `Scheme` protocol the paper model
uses — one `Experiment` driver for every scale.

The repo used to carry two parallel stacks: `schemes/` + `Experiment`
for the paper's tiny model, and bespoke loops in `launch/train.py` /
`runtime/fl_runtime.py` for the sharded assigned architectures. These
three classes collapse the second stack into the first:

* `ScaledCentralizedScheme` — wraps `make_train_step` (no radio in the
  step); the synthetic corpus crosses the radio ONCE at `init`
  (`Radio.send_tokens`, the tiny CL convention — bit errors corrupt
  token ids, a perfect link is noiseless but still billed);
* `ScaledFederatedScheme` — wraps `make_fl_train_step`: one `round` is
  one whole communication cycle as ONE XLA program (J pod-local SGD
  steps per user + the quantized stacked sync, the program's only
  cross-pod collective). The sync's crossings live inside the jit, so
  the scheme bills them by replaying the fade/ARQ draw on the same
  channel key (`wire.drawn_stacked_tx` at `fold_in(key, 999)`) —
  exactly how the fused SL path has always been billed;
* `ScaledSplitScheme` — wraps `make_train_step` with an SL
  `WirelessConfig` (the split forward + `channel_crossing` fused into
  the train step); per-step activation/gradient legs are billed at the
  DRAWN ARQ counts via the same outside-the-jit key replay
  (`split.crossing_elems` x quant_bits per leg).

All three run mesh-sharded when built under `use_mesh` (nn/sharding.py
resolves the logical axes; the FL user axis maps onto the `pod` mesh
axis via the "users" rule) and expose `lower_step(mesh)` so
`launch/dryrun.py` lowers the identical step the `Experiment` trains.

RNG contract (pinned by tests/test_scheme_parity.py against inline
legacy loops): CL/SL rounds fold per-step keys from the CUMULATIVE step
counter off `PRNGKey(seed)` — the exact stream the deleted
`launch/train.py` loop consumed (`fold_in(PRNGKey(seed), step)`); FL
rounds use `fold_in(PRNGKey(seed + 3), cycle)`, the tiny
`FederatedScheme` convention. Data is drawn from the one experiment rng
(`seed + 1`) by with-replacement sampling, so any corpus size feeds any
batch shape.

The paper model keeps its own parity-pinned schemes; `build_scheme`
routes non-tiny `cfg`s here. FLOPs accounting comes from XLA's
pre-compile cost analysis of the SAME jitted round program the scheme
executes (`_step_cost_flops`), apportioned user/server per paradigm —
no hand-derived formula to drift from the model code.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig, WirelessConfig
from repro.core import split as SPLIT
from repro.core import wire as W
from repro.data.pipeline import synthetic_corpus
from repro.models import api as M
from repro.models import encdec
from repro.runtime.fl_runtime import SYNC_KEY_FOLD, make_fl_train_step
from repro.runtime.train_step import (auto_microbatch, init_train_state,
                                      key_sds, make_train_step,
                                      train_state_sds_and_shardings,
                                      window_for)
from repro.schemes.base import RoundReport, SchemeState, train_cycle
from repro.schemes.radio import Radio

DEFAULT_SHAPE = ShapeConfig("scaled", 128, 8, "train", microbatch=8)


class _ScaledScheme:
    """Shared plumbing: synthetic-corpus contract, with-replacement batch
    sampling off the experiment rng, next-token-accuracy eval."""
    epochs_per_cycle = 1
    bits_normalizer = 1.0

    def __init__(self, cfg, shape: Optional[ShapeConfig] = None,
                 wcfg=None, capture: bool = False,
                 optimizer: str = "adamw", steps_per_cycle: int = 4,
                 n_data_shards: int = 16):
        if capture:
            raise ValueError("capture=True is a tiny-scheme privacy-eval "
                             "feature; the scaled schemes do not observe")
        if cfg.family == "tiny":
            raise ValueError("the paper model runs the parity-pinned tiny "
                             "schemes; build_scheme routes it there")
        self.cfg = cfg
        self.shape = shape or DEFAULT_SHAPE
        self.wcfg = wcfg
        self.optimizer = optimizer
        self.steps_per_cycle = int(steps_per_cycle)
        self.n_data_shards = n_data_shards
        self.radio = Radio.from_wcfg(wcfg)
        self.captures: dict = {}
        self._eval_exe = None
        self._cost_flops: Optional[float] = None

    # ------------------------------------------------------------- data
    def default_data(self, n_train: int, n_test: int, seed: int):
        """The corpus `Experiment` feeds this scheme when none is given:
        finite synthetic Zipf LM rows (labels = tokens)."""
        x, y = synthetic_corpus(self.cfg, n_train + n_test,
                                self.shape.seq_len, seed)
        return (x[:n_train], y[:n_train]), (x[n_train:], y[n_train:])

    def _check_corpus(self, xtr):
        xtr = np.asarray(xtr)
        if xtr.ndim != 2 or xtr.shape[1] != self.shape.seq_len:
            raise ValueError(
                f"scaled scheme expects a [n, seq_len={self.shape.seq_len}]"
                f" token corpus, got {xtr.shape} — pass data="
                "synthetic_corpus(cfg, n, seq_len) (or let Experiment use "
                "the scheme's default_data)")
        if int(xtr.max(initial=0)) >= self.cfg.vocab_size:
            raise ValueError(
                f"corpus token ids exceed vocab_size={self.cfg.vocab_size}")
        return xtr

    def _frontend_extras(self, rng, b: int) -> dict:
        """Random frontend inputs for the stubbed multimodal families,
        drawn from the SAME rng stream as the token sampling (mirrors
        data/pipeline.synthetic_lm_batches)."""
        cfg, extras = self.cfg, {}
        if cfg.frontend == "vision":
            extras["patch_embeds"] = rng.standard_normal(
                (b, cfg.n_frontend_tokens, cfg.d_model)
            ).astype(np.float32) * 0.1
        if cfg.family == "audio":
            extras["frames"] = rng.standard_normal(
                (b, encdec.src_len(cfg, self.shape.seq_len), cfg.d_model)
            ).astype(np.float32) * 0.1
        return extras

    def _sample_batch(self, x, y, rng, b: int) -> dict:
        idx = rng.integers(0, len(x), b)
        batch = {"tokens": jnp.asarray(x[idx]),
                 "labels": jnp.asarray(y[idx])}
        for k, v in self._frontend_extras(rng, b).items():
            batch[k] = jnp.asarray(v)
        return batch

    # ------------------------------------------------------------- eval
    def _eval_wcfg(self):
        return None      # CL/FL deploy the plain forward

    def _eval_fn(self):
        if self._eval_exe is None:
            cfg, wcfg = self.cfg, self._eval_wcfg()
            window = window_for(cfg, self.shape)
            from repro.runtime.train_step import _forward

            @jax.jit
            def ev(trainable, batch, key):
                logits, _ = _forward(trainable, batch, cfg, wcfg, key,
                                     window)
                labels = batch["labels"]
                logits = logits[:, -labels.shape[1]:][:, :-1]
                targets = labels[:, 1:]
                hit = (jnp.argmax(logits, axis=-1) == targets)
                mask = (targets != 0).astype(jnp.float32)
                return jnp.sum(hit * mask) / jnp.maximum(jnp.sum(mask), 1.)
            self._eval_exe = ev
        return self._eval_exe

    def _evaluate_trainable(self, trainable, xte, yte) -> float:
        """Next-token accuracy of the deployed function on full batches
        of the held-out rows; fixed eval keys `PRNGKey(999 + start)` (the
        SL eval convention — CL/FL ignore the key)."""
        ev = self._eval_fn()
        b = self.shape.global_batch
        rng = np.random.default_rng(999)       # frontend extras only
        accs = []
        for i in range(0, max(len(xte) - b + 1, 1), b):
            batch = {"tokens": jnp.asarray(np.asarray(xte[i:i + b])),
                     "labels": jnp.asarray(np.asarray(yte[i:i + b]))}
            n = batch["tokens"].shape[0]
            for k, v in self._frontend_extras(rng, n).items():
                batch[k] = jnp.asarray(v)
            accs.append(float(ev(trainable, batch,
                                 jax.random.PRNGKey(999 + i))))
        return float(np.mean(accs))

    def default_lr_schedule(self, epoch: int) -> float:
        """Constant 3e-4 when the Experiment pins no schedule — the
        paper's 0.1 step-decay is tuned for the 89k-param tiny model
        and diverges the scaled archs."""
        return 3e-4

    def _lower_for_cost(self):
        """Lower ONE round program on abstract inputs — subclasses bind
        the concrete state/batch ShapeDtypeStructs."""
        raise NotImplementedError

    def _step_cost_flops(self) -> float:
        """FLOPs of one compiled round program, from XLA's cost analysis
        of the SAME jitted step the rounds execute — no hand-derived
        formula to drift from the model code. Lowered on abstract
        inputs (no device memory); the TPU backend has no pre-compile
        cost model (`Lowered.cost_analysis()` is None), so there the
        compiled program is asked. Cached per scheme."""
        if self._cost_flops is None:
            lowered = self._lower_for_cost()
            cost = lowered.cost_analysis()
            if cost is None:
                cost = lowered.compile().cost_analysis()
            self._cost_flops = float(cost["flops"])
        return self._cost_flops

    def flops(self, steps_total: int):
        """Compiled-program FLOPs x executed steps; the user/server
        split is each paradigm's (see subclass overrides)."""
        return 0.0, self._step_cost_flops() * steps_total

    def warmup_compile(self) -> float:
        """Ahead-of-time compile of the round program (the `--aot-warmup`
        flag): lower on abstract inputs and compile NOW, returning the
        wall seconds it took. With the persistent compile cache enabled
        (launch/compile_cache.py) the first run pays the real XLA wall
        here and seeds the cache; every later process gets a cache hit —
        near-zero compile wall — at the same call."""
        import time
        lowered = self._lower_for_cost()   # tracing wall, never cached
        t0 = time.perf_counter()
        lowered.compile()
        return time.perf_counter() - t0


# ------------------------------------------------------------------- CL
class ScaledCentralizedScheme(_ScaledScheme):
    """CL for the assigned archs: the corpus crosses the radio once at
    `init` (billed, possibly corrupted), then `make_train_step` runs
    radio-silent server epochs — `steps_per_cycle` optimizer steps per
    communication cycle."""
    mode = "cl"

    def __init__(self, cfg, shape=None, wcfg=None, **kw):
        super().__init__(cfg, shape, wcfg, **kw)
        # the train state is donated, as in `lower_step`: each step
        # updates it in place, so a cycle holds one state, not one per
        # step (`round` never reads the state it passed in again)
        self._exe = jax.jit(make_train_step(
            cfg, self.shape, None, optimizer=self.optimizer,
            n_data_shards=self.n_data_shards), donate_argnums=(0,))

    def _step_wcfg(self):
        return None

    def _lower_for_cost(self):
        state_sds = jax.eval_shape(
            lambda k: init_train_state(k, self.cfg, self._step_wcfg(),
                                       self.optimizer), key_sds())
        return self._exe.lower(state_sds,
                               M.input_specs(self.cfg, self.shape),
                               key_sds(), 3e-4)

    def init(self, seed: int, xtr, ytr):
        xtr = self._check_corpus(xtr)
        dlv = self.radio.send_tokens(jax.random.PRNGKey(seed + 7),
                                     jnp.asarray(xtr), self.cfg.vocab_size)
        x_rx = np.asarray(dlv.payload)
        state = init_train_state(jax.random.PRNGKey(seed), self.cfg,
                                 self._step_wcfg(), self.optimizer)
        # the server trains on what ARRIVED: labels are the received
        # tokens themselves (next-token objective)
        return SchemeState(train=state, data=(x_rx, x_rx)), dlv

    def cycle_batches(self, state, rng, cycle):
        x, y = state.data
        return [self._sample_batch(x, y, rng, self.shape.global_batch)
                for _ in range(self.steps_per_cycle)]

    def round_key(self, seed: int, cycle: int):
        # the legacy launch/train.py stream: fold_in(PRNGKey(seed), step)
        return jax.random.PRNGKey(seed)

    def round(self, state, batch, key, lr):
        step = lambda st, b, k: self._exe(st, b, k, lr)   # noqa: E731
        st, m, steps = train_cycle(step, state.train, batch, key,
                                   state.steps)
        new = SchemeState(st, state.data, steps, state.epoch + 1)
        # the corpus upload was billed at init; rounds are radio-silent
        return new, RoundReport(loss=float(m["loss"]),
                                steps=steps - state.steps)

    def evaluate(self, state, xte, yte) -> float:
        return self._evaluate_trainable(state.train.trainable, xte, yte)

    # ----------------------------------------------------------- dryrun
    def lower_step(self, mesh, n_data_shards: Optional[int] = None):
        """Lower the round's train step with explicit state/batch
        shardings for `mesh` — what launch/dryrun.py compiles."""
        nd = n_data_shards or self.n_data_shards
        wcfg = self._step_wcfg()
        state_sds, state_sh = train_state_sds_and_shardings(
            self.cfg, wcfg, mesh, self.optimizer)
        batch_sds = M.input_specs(self.cfg, self.shape)
        from repro.runtime.train_step import axes_to_shardings
        batch_sh = axes_to_shardings(batch_sds,
                                     M.input_axes(self.cfg, self.shape),
                                     mesh)
        step = make_train_step(self.cfg, self.shape, wcfg,
                               optimizer=self.optimizer, n_data_shards=nd)
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh, None),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
        return fn.lower(state_sds, batch_sds, key_sds())


# ------------------------------------------------------------------- SL
class ScaledSplitScheme(ScaledCentralizedScheme):
    """SL for the assigned archs: `make_train_step` with an SL
    WirelessConfig fuses the split forward + `channel_crossing` into the
    train step; each optimizer step pushes the encoded activation up and
    the tau-clipped gradient down through the radio, billed at the DRAWN
    ARQ transmission counts replayed outside the jit (each of the step's
    `n_micro` microbatches crosses once per leg)."""
    mode = "sl"

    def __init__(self, cfg, shape=None, wcfg=None, perfect_eval=False,
                 **kw):
        wcfg = wcfg or WirelessConfig(mode="sl", quant_bits=16)
        _ScaledScheme.__init__(self, cfg, shape, wcfg, **kw)
        self.perfect_eval = perfect_eval
        self._exe = jax.jit(make_train_step(
            cfg, self.shape, wcfg, optimizer=self.optimizer,
            n_data_shards=self.n_data_shards), donate_argnums=(0,))
        self._n_micro = auto_microbatch(cfg, self.shape,
                                        self.n_data_shards)
        # one leg's payload per optimizer step (all microbatches)
        self._leg_elems = SPLIT.crossing_elems(cfg, self.shape, wcfg)

    def _step_wcfg(self):
        return self.wcfg

    def _eval_wcfg(self):
        if self.perfect_eval:
            return dataclasses.replace(self.wcfg, perfect_channel=True)
        return self.wcfg

    def init(self, seed: int, xtr, ytr):
        xtr = self._check_corpus(xtr)
        state = init_train_state(jax.random.PRNGKey(seed), self.cfg,
                                 self.wcfg, self.optimizer)
        return SchemeState(train=state,
                           data=(np.asarray(xtr), np.asarray(xtr))), None

    def _drawn_leg_diag(self, key, start: int, n_steps: int):
        """DRAWN link-leg diagnostics of `n_steps` fused steps starting
        at cumulative step `start` -> (n_tx, n_erased_legs,
        backoff_units): the train step folds the microbatch index onto
        the step key before `_link`, the gradient leg folds 1 on top
        (core/channel.py `_cc_bwd`) — same replay contract as
        split.sl_cycle_drawn_diag, generalized to n_micro > 1. On a
        fault-free link this is identically (2 x n_micro x n_steps,
        0, 0) with no RNG touched."""
        radio = self.radio
        if n_steps <= 0:
            return 0.0, 0.0, 0.0
        if W.fault_free(radio.fading, radio.perfect, radio.arq_attempts,
                        radio.arq_min_f2, radio.arq_max_tx,
                        radio.ge_p_gb):
            return float(2 * self._n_micro * n_steps), 0.0, 0.0
        kw = dict(fading=radio.fading, perfect=False,
                  arq_attempts=radio.arq_attempts,
                  arq_min_f2=radio.arq_min_f2,
                  arq_max_tx=radio.arq_max_tx,
                  ge_p_gb=radio.ge_p_gb, ge_p_bg=radio.ge_p_bg)

        def one(s, i):
            ck = jax.random.fold_in(jax.random.fold_in(key, s), i)
            up = W.drawn_tree_diag(ck, 1, **kw)
            down = W.drawn_tree_diag(jax.random.fold_in(ck, 1), 1, **kw)
            return (up[0] + down[0], up[1] + down[1], up[2] + down[2])

        steps = jnp.repeat(jnp.arange(start, start + n_steps),
                           self._n_micro)
        micros = jnp.tile(jnp.arange(self._n_micro), n_steps)
        tx, er, bo = jax.vmap(one)(steps, micros)
        return float(tx.sum()), float(er.sum()), float(bo.sum())

    def _drawn_leg_tx(self, key, start: int, n_steps: int) -> float:
        """Back-compat alias: just the transmission count."""
        return self._drawn_leg_diag(key, start, n_steps)[0]

    def round(self, state, batch, key, lr):
        step = lambda st, b, k: self._exe(st, b, k, lr)   # noqa: E731
        st, m, steps = train_cycle(step, state.train, batch, key,
                                   state.steps)
        n = steps - state.steps
        n_tx, n_er, bo = self._drawn_leg_diag(key, state.steps, n)
        # each microbatch leg carries leg_elems / n_micro elements
        leg_bits = (self._leg_elems / self._n_micro) \
            * float(self.radio.quant_bits)
        bits = n_tx * leg_bits
        new = SchemeState(st, state.data, steps, state.epoch + 1)
        return new, RoundReport(
            loss=float(m["loss"]), steps=n, bits=bits, n_tx=n_tx,
            energy_j=self.radio.energy_j(bits),
            erased_bits=n_er * self.radio.arq_max_tx * leg_bits,
            outage_s=bo * self.radio.arq_backoff_s)

    def flops(self, steps_total: int):
        """One fused program covers BOTH sides of the cut; apportion by
        layer share — `split_layer` of `n_layers` runs on-device
        (plus its gradient), the rest server-side."""
        total = self._step_cost_flops() * steps_total
        cut = max(1, min(self.wcfg.split_layer, self.cfg.n_layers - 1))
        ufrac = cut / float(self.cfg.n_layers)
        return total * ufrac, total * (1.0 - ufrac)


# ------------------------------------------------------------------- FL
class ScaledFederatedScheme(_ScaledScheme):
    """The pod-mesh FL step behind the Scheme protocol: one `round` runs
    `make_fl_train_step`'s whole communication cycle (J pod-local SGD
    steps per user + the quantized stacked sync) as one XLA program;
    the sync is billed by replaying its fade/ARQ draw outside the jit
    on the same `fold_in(key, 999)` channel key. Reports the paper's
    per-user bits convention (`bits_normalizer = n_users`).

    `wcfg.sync="delayed"` runs the one-round-staleness async schedule
    (see make_fl_train_step): the scheme state becomes the carry
    {"state": TrainState, "agg": stacked model tree}; billing is
    UNCHANGED (same key fold, same draw — a delayed round puts the same
    packets on the air as a barrier round). `evaluate` deploys the
    aggregate view (the server's weights), not the in-flight locals.

    Built under `use_mesh`, the round executable is jitted with
    EXPLICIT in/out shardings (the same trees lower_step declares) and
    `init` commits the state to them — otherwise cycle 0 (uncommitted
    init arrays) and cycle 1 (jit-committed outputs) present different
    arg shardings and XLA compiles the whole program twice (the 10.9 s
    "steady-state" BENCH_scaled artifact was really this second compile
    wall landing on the single post-compile sample)."""
    mode = "fl"

    def __init__(self, cfg, shape=None, wcfg=None, **kw):
        kw.pop("steps_per_cycle", None)   # one cycle IS local_steps steps
        if kw.get("optimizer", "sgd") != "sgd":
            # the pod FL step is SGD-momentum by construction (DiLoCo-
            # style local SGD); silently training a different optimizer
            # than requested would be worse than refusing
            raise ValueError("ScaledFederatedScheme runs SGD-momentum "
                             f"local steps; optimizer="
                             f"{kw['optimizer']!r} is not supported")
        kw.setdefault("optimizer", "sgd")
        wcfg = wcfg or WirelessConfig(mode="fl")
        super().__init__(cfg, shape, wcfg, **kw)
        self.n_users = wcfg.n_users
        self.local_steps = wcfg.local_steps
        self.sync = str(getattr(wcfg, "sync", "barrier"))
        self.bits_normalizer = float(self.n_users)
        step = make_fl_train_step(cfg, self.shape, wcfg,
                                  n_users=self.n_users)
        from repro.nn import current_mesh
        self._mesh = current_mesh()
        self._train_sh = None
        # the train state is donated, as in `lower_step`
        if self._mesh is None:
            self._exe = jax.jit(step, donate_argnums=(0,))
        else:
            state_sh = train_state_sds_and_shardings(
                cfg, None, self._mesh, "sgd", n_users=self.n_users)[1]
            batch_sh = self._batch_shardings(self._mesh)
            self._train_sh = self._as_train(state_sh)
            self._exe = jax.jit(
                step, in_shardings=(self._train_sh, batch_sh, None, None),
                out_shardings=(self._train_sh, None), donate_argnums=(0,))
        # per-packet payload of the stacked sync: one packet per
        # (user, model leaf), sized by the per-user leaf
        specs = M.param_specs(cfg)
        from repro.nn import shapes_tree
        self._packet_sizes = np.asarray(
            [int(np.prod(s.shape)) for s in
             jax.tree.leaves(shapes_tree(specs))], np.float64)

    def _as_train(self, state_tree):
        """The scheme-state train tree for one user-stacked TrainState
        tree (works on arrays, ShapeDtypeStructs and shardings alike):
        the state itself under barrier sync, the delayed-sync carry —
        state + last aggregate (seeded with the same broadcast model)
        — otherwise."""
        if self.sync != "delayed":
            return state_tree
        return {"state": state_tree, "agg": state_tree.trainable["model"]}

    def _batch_sds(self):
        return {k: jax.ShapeDtypeStruct((self.n_users,) + v.shape,
                                        v.dtype)
                for k, v in M.input_specs(self.cfg, self.shape).items()}

    def _batch_shardings(self, mesh):
        batch_ax = {k: ("users",) + ax for k, ax in
                    M.input_axes(self.cfg, self.shape).items()}
        from repro.runtime.train_step import axes_to_shardings
        return axes_to_shardings(self._batch_sds(), batch_ax, mesh)

    def init(self, seed: int, xtr, ytr):
        xtr = self._check_corpus(xtr)
        ytr = np.asarray(ytr)
        state0 = init_train_state(jax.random.PRNGKey(seed), self.cfg,
                                  None, "sgd")
        user_states = jax.tree.map(
            lambda p: jnp.broadcast_to(p, (self.n_users,) + p.shape),
            state0)
        train = self._as_train(user_states)
        if self.sync == "delayed":
            # the aggregate gets buffers of its own: the round donates
            # the whole carry, and no buffer can be donated twice
            train["agg"] = jax.tree.map(jnp.copy, train["agg"])
        if self._train_sh is not None:
            # commit to the executable's declared input shardings so
            # round 0 presents the same arg signature as every later
            # round — one compile for the whole run
            train = jax.device_put(train, self._train_sh)
        per = len(xtr) // self.n_users
        shards = [(xtr[u * per:(u + 1) * per], ytr[u * per:(u + 1) * per])
                  for u in range(self.n_users)]
        return SchemeState(train=train, data=shards), None

    def cycle_batches(self, state, rng, cycle):
        b = self.shape.global_batch
        per_user = [self._sample_batch(xs, ys, rng, b)
                    for xs, ys in state.data]
        return {k: jnp.stack([u[k] for u in per_user])
                for k in per_user[0]}

    def round_key(self, seed: int, cycle: int):
        return jax.random.fold_in(jax.random.PRNGKey(seed + 3), cycle)

    def round(self, state, batch, key, lr):
        st, metrics = self._exe(state.train, batch, key, lr)
        r = self.radio
        out = W.drawn_stacked_tx(
            jax.random.fold_in(key, SYNC_KEY_FOLD), self.n_users,
            len(self._packet_sizes), fading=r.fading, perfect=r.perfect,
            arq_attempts=r.arq_attempts, arq_min_f2=r.arq_min_f2,
            arq_max_tx=r.arq_max_tx, ge_p_gb=r.ge_p_gb,
            ge_p_bg=r.ge_p_bg, with_erased=(r.arq_max_tx > 0))
        erased_bits = 0.0
        if r.arq_max_tx > 0:
            # the fused program's in-jit erasure-aware FedAvg saw the
            # SAME draw; replaying it here is what lets the host bill
            # the wasted air time of exhausted uploads
            n_tx, erased = out
            erased_bits = float(r.wire_width()) * float(
                (self._packet_sizes[None, :] * n_tx * erased).sum())
        else:
            n_tx = out
        # billed at the ON-WIRE width: quant_bits for abstract float32
        # symbols, the container width for int8/int4 packed codewords
        bits = float(r.wire_width()) * float(
            (self._packet_sizes[None, :] * n_tx).sum())
        new = SchemeState(st, state.data,
                          state.steps + self.local_steps,
                          state.epoch + 1)
        return new, RoundReport(
            loss=float(metrics["loss"]), steps=self.local_steps,
            bits=bits, n_tx=float(n_tx.sum()),
            energy_j=r.energy_j(bits), erased_bits=erased_bits,
            outage_s=float(W.backoff_s(n_tx, r.arq_backoff_s)))

    def _lower_for_cost(self):
        def mk(k):
            s0 = init_train_state(k, self.cfg, None, "sgd")
            return jax.tree.map(lambda p: jnp.broadcast_to(
                p, (self.n_users,) + p.shape), s0)
        train_sds = self._as_train(jax.eval_shape(mk, key_sds()))
        return self._exe.lower(train_sds, self._batch_sds(),
                               key_sds(), 3e-4)

    def flops(self, steps_total: int):
        """One program IS a whole communication cycle of user-side local
        SGD (the server only averages): all FLOPs are the users'."""
        cycles = steps_total / float(max(self.local_steps, 1))
        return self._step_cost_flops() * cycles, 0.0

    def evaluate(self, state, xte, yte) -> float:
        if self.sync == "delayed":
            # deploy the SERVER's view: the last synced aggregate, with
            # the non-model trainables (if any) from the local state
            st = state.train["state"]
            trainable = jax.tree.map(
                lambda p: p[0],
                dict(st.trainable, model=state.train["agg"]))
        else:
            trainable = jax.tree.map(lambda p: p[0],
                                     state.train.trainable)
        return self._evaluate_trainable(trainable, xte, yte)

    # ----------------------------------------------------------- dryrun
    def lower_step(self, mesh, n_data_shards: Optional[int] = None):
        """Lower the fused FL cycle with the user axis sharded onto the
        mesh's `pod` axis (the "users" rule in nn/sharding.py)."""
        state_sds, state_sh = train_state_sds_and_shardings(
            self.cfg, None, mesh, "sgd", n_users=self.n_users)
        train_sds = self._as_train(state_sds)
        train_sh = self._as_train(state_sh)
        batch_sds = self._batch_sds()
        batch_sh = self._batch_shardings(mesh)
        step = make_fl_train_step(self.cfg, self.shape, self.wcfg,
                                  n_users=self.n_users)
        fn = jax.jit(step, in_shardings=(train_sh, batch_sh, None),
                     out_shardings=(train_sh, None), donate_argnums=(0,))
        return fn.lower(train_sds, batch_sds, key_sds())
