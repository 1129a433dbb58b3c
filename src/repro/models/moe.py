"""Expert-parallel Mixture-of-Experts layer.

Three dispatch strategies, picked automatically:

1. `_moe_ep` (shard_map expert parallelism) — when a mesh with the
   expert axis is active. Each (data, model) device routes its LOCAL
   tokens, dispatches only to the E/n_shards experts IT owns, and the
   partial outputs are combined with ONE psum over the expert axis per
   layer. The baseline pjit scatter (below) made XLA all-reduce the full
   [T*k, d] dispatch buffer across data shards — ~30 TB/device/step for
   qwen3 train_4k; this form moves ~100x less (EXPERIMENTS.md §Perf-2).
2. `_moe_core` token-chunked scatter/gather — no-mesh fallback and the
   path the adversarial tests exercise; chunking bounds the dispatch
   buffers (a 1M-token prefill otherwise materializes ~268 GiB/device).
3. Both share capacity-based dispatch: the [T, E, C] one-hot never
   materializes — tokens scatter into a compact [E, C, d] buffer.

Returns (y, aux) where aux carries the Switch-style load-balance loss and
router stats.

Serving (`decode_step`/`prefill_step`) takes a fourth path, `moe_held`:
dropless, over the experts this chip holds (`cfg.experts_held`), as one
grouped matmul per weight (`kernels/moe_gmm`) with no capacity.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.moe_gmm.ref import gmm_ref
from repro.nn import Spec, constrain
from repro.nn.sharding import current_mesh
from repro.models.layers import linear_specs, linear, mlp_specs, apply_mlp


def moe_specs(cfg) -> dict:
    d, ff, E, Eh = cfg.d_model, cfg.expert_ff, cfg.n_experts, cfg.n_held
    s = {
        "router": linear_specs(d, E, ("embed", None)),
        "wi": Spec((Eh, d, ff), ("experts", "embed", "expert_mlp"), init="fan_in"),
        "wg": Spec((Eh, d, ff), ("experts", "embed", "expert_mlp"), init="fan_in"),
        "wo": Spec((Eh, ff, d), ("experts", "expert_mlp", "embed"), init="fan_in"),
    }
    if cfg.shared_experts:
        s["shared"] = mlp_specs(cfg, ff * cfg.shared_experts)
    return s


def route(p: dict, xf: jax.Array, cfg) -> tuple:
    """Softmax over all E experts, greedy top-k: (gates [T, k] fp32,
    expert ids [T, k], probs [T, E]). Gates are renormalised over the
    k when `cfg.norm_topk_prob`."""
    logits = linear(p["router"], xf.astype(jnp.float32))          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)                   # [T, k]
    if cfg.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return gate, idx, probs


#: the counters `moe_held` returns: rows the held experts computed, the
#: busiest held expert's rows, held experts with at least one row
N_STATS = 3


def moe_held(p: dict, x: jax.Array, cfg, layer, valid=None,
             kernel: bool = False) -> tuple:
    """Dropless MoE over the held experts [lo, hi) = `cfg.held`: route
    every token over all E experts (`route`), keep the assignments that
    fall in the held window (and, with `valid` [B, S] bool, only valid
    tokens'), sort them by expert and run the SwiGLU as three grouped
    matmuls over the held experts' weights, combine with the gates and
    add the shared experts once. `p["wi"/"wg"/"wo"]` hold only the held
    experts, stacked over the layers, [L, n_held, ...]; the kernel reads
    layer `layer` of them in place (a slice would be a copy). No
    capacity: a token is never dropped. What the absent experts would
    add is left out, as on a chip of an expert-parallel deployment.
    `kernel=True` runs the Pallas grouped matmul, False its jnp
    reference. Returns (y [B, S, d], counters int32 [N_STATS])."""
    B, S, d = x.shape
    T, k = B * S, cfg.top_k
    lo, hi = cfg.held
    Eh = hi - lo
    M = T * min(k, Eh)            # top-k ids are distinct: a bound on rows
    xf = x.reshape(T, d)
    with jax.named_scope("moe.route"):
        gate, idx, _ = route(p, xf, cfg)
        e = idx - lo
        keep = (e >= 0) & (e < Eh)
        if valid is not None:
            keep &= valid.reshape(T, 1)
        key = jnp.where(keep, e, Eh).reshape(T * k)
        order = jnp.argsort(key, stable=True)[:M]
        sizes = jnp.bincount(key, length=Eh + 1)[:Eh].astype(jnp.int32)
        n = sizes.sum()
        rows = order // k
        g = gate.reshape(T * k)[order]
    if kernel:
        def gmm(a, w):
            return moe_gmm(a, w, sizes, layer)
    else:
        def gmm(a, w):
            return gmm_ref(a, w[layer].astype(x.dtype), sizes)
    with jax.named_scope("moe.experts"):
        xs = jnp.take(xf, rows, axis=0)                           # [M, d]
        h = jax.nn.silu(gmm(xs, p["wg"])) * gmm(xs, p["wi"])
        out = gmm(h.astype(x.dtype), p["wo"])                     # [M, d]
        live = (jnp.arange(M) < n)[:, None]
        out = jnp.where(live, out * g[:, None], 0.0)
        y = jnp.zeros((T, d), jnp.float32).at[rows].add(out)
    y = y.reshape(B, S, d).astype(x.dtype)
    if cfg.shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + apply_mlp(p["shared"], x)
    stats = jnp.stack([n, sizes.max(), (sizes > 0).sum()]).astype(jnp.int32)
    return y, stats


def capacity(n_tokens: int, cfg) -> int:
    c = int(math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8 (lane-friendly)


def auto_chunk(T: int, cfg) -> int:
    """Largest power-of-two-friendly token chunk <= moe_chunk that divides
    T. Chunked dispatch bounds the [chunk*k, d] scatter rows and the
    router cumsum — without it a 1M-token prefill materializes hundreds
    of GiB of dispatch state (EXPERIMENTS.md §Perf-2)."""
    target = cfg.moe_chunk or 16_384
    c = min(T, target)
    while T % c:
        c -= 1
    return c


EP_MIN_TOKENS = 2048    # below this the psum-per-layer costs more than
                        # the scatter it replaces (decode: §Perf-B5)


def apply_moe(p: dict, x: jax.Array, cfg) -> tuple[jax.Array, dict]:
    """Dispatch strategy selection — see module docstring."""
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.shape \
            and cfg.n_experts % mesh.shape["model"] == 0 \
            and x.shape[0] * x.shape[1] >= EP_MIN_TOKENS:
        return _moe_ep(p, x, cfg, mesh)
    return _moe_chunked(p, x, cfg)


def _moe_chunked(p: dict, x: jax.Array, cfg) -> tuple[jax.Array, dict]:
    """Token-chunked expert dispatch: scan over chunks of the flattened
    token dim; each chunk routes/dispatches/combines independently (the
    router is token-local, so chunking is exact, not an approximation —
    only the capacity limit becomes per-chunk)."""
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    chunk = auto_chunk(T, cfg)
    if chunk == T:
        return _single(p, xf, cfg, B, S, d)

    def body(_, xc):
        y, aux = _moe_core(p, xc, cfg)
        return None, (y, aux["lb_loss"], aux["dropped_frac"])

    _, (ys, lb, dropped) = jax.lax.scan(body, None,
                                        xf.reshape(T // chunk, chunk, d))
    y = ys.reshape(B, S, d)
    if cfg.shared_experts:
        y = y + apply_mlp(p["shared"], x)
    return constrain(y, "batch", "seq", "act_embed"), {
        "lb_loss": jnp.mean(lb), "dropped_frac": jnp.mean(dropped)}


def _single(p, xf, cfg, B, S, d):
    y, aux = _moe_core(p, xf, cfg)
    y = y.reshape(B, S, d)
    if cfg.shared_experts:
        y = y + apply_mlp(p["shared"], xf.reshape(B, S, d))
    return constrain(y, "batch", "seq", "act_embed"), aux


def _moe_core(p: dict, xf: jax.Array, cfg, e_lo=0,
              n_local: int = 0) -> tuple[jax.Array, dict]:
    """Capacity dispatch over the expert window [e_lo, e_lo + n_local).
    Routing (router/top-k/gates) always spans all E experts; only the
    dispatch is windowed, so an expert-parallel caller can pass its local
    weight slice plus its window and psum the partial outputs."""
    T, d = xf.shape
    E, k = cfg.n_experts, cfg.top_k
    if not n_local:                 # the experts this chip holds
        e_lo, n_local = cfg.held[0], cfg.n_held
    El = n_local
    C = capacity(T, cfg)

    gate, idx, probs = route(p, xf, cfg)                          # [T, k]

    # position of each (token, slot) within its expert, in flat arrival order
    eflat = idx.reshape(T * k) - e_lo                             # window-rel
    in_win = (eflat >= 0) & (eflat < El)
    e_loc = jnp.where(in_win, eflat, El)
    onehot = jax.nn.one_hot(e_loc, El, dtype=jnp.int32)           # [T*k, El]
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1       # [T*k]
    keep = in_win & (pos < C)
    dest = jnp.where(keep, e_loc * C + jnp.clip(pos, 0, C - 1), El * C)

    rows = jnp.repeat(xf, k, axis=0)                              # [T*k, d]
    buf = jnp.zeros((El * C, d), xf.dtype).at[dest].set(rows, mode="drop")
    buf = constrain(buf.reshape(El, C, d), "experts", None, None)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(xf.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(xf.dtype))
    h = constrain(h, "experts", None, "expert_mlp")
    out = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(xf.dtype))
    out = constrain(out, "experts", None, None).reshape(El * C, d)

    gathered = jnp.take(out, jnp.clip(dest, 0, El * C - 1), axis=0)
    gathered = gathered * keep[:, None].astype(xf.dtype)
    y = (gathered.reshape(T, k, d) * gate[..., None].astype(xf.dtype)).sum(1)

    # Switch-style load-balance loss: E * sum_e f_e * P_e
    frac = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    lb_loss = E * jnp.sum(frac * prob_mean)
    n_win = jnp.maximum(jnp.sum(in_win.astype(jnp.float32)), 1.0)
    dropped = 1.0 - jnp.sum(keep.astype(jnp.float32)) / n_win
    return y, {"lb_loss": lb_loss, "dropped_frac": dropped}


# ------------------------------------------------- expert parallelism
def _moe_ep(p: dict, x: jax.Array, cfg, mesh) -> tuple[jax.Array, dict]:
    """shard_map expert parallelism (§Perf-2): every device routes its
    local tokens, dispatches only to the experts it owns, and partial
    outputs combine with one psum over the expert axis. Collective cost
    per layer = one [T_local, d] all-reduce (+ the small replicated
    router weights), instead of resharding the full dispatch buffers."""
    axis = "model"
    n_sh = mesh.shape[axis]
    El = cfg.n_experts // n_sh
    B, S, d = x.shape
    bax = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if bax and B % math.prod(mesh.shape[a] for a in bax):
        bax = ()                       # batch not divisible: replicate

    def f(rw, wi, wg, wo, xl):
        j = jax.lax.axis_index(axis)
        Bl, Sl, dl = xl.shape
        xf = xl.reshape(Bl * Sl, dl)
        chunk = auto_chunk(Bl * Sl, cfg)
        pl = {"router": {"w": rw}, "wi": wi, "wg": wg, "wo": wo}

        def body(_, xc):
            y, aux = _moe_core(pl, xc, cfg, e_lo=j * El, n_local=El)
            return None, (y, aux["lb_loss"], aux["dropped_frac"])

        _, (ys, lb, dr) = jax.lax.scan(
            body, None, xf.reshape(-1, chunk, dl))
        y = jax.lax.psum(ys.reshape(Bl, Sl, dl), axis)
        # scalars must be identical on every device for out_spec P()
        lb = jax.lax.pmean(jnp.mean(lb), bax + (axis,))
        dr = jax.lax.pmean(jnp.mean(dr), bax + (axis,))
        return y, lb, dr

    y, lb, dr = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(None, None), P(axis, None, None), P(axis, None, None),
                  P(axis, None, None), P(bax if bax else None, None, None)),
        out_specs=(P(bax if bax else None, None, None), P(), P()),
        check_vma=False,
    )(p["router"]["w"], p["wi"], p["wg"], p["wo"], x)
    if cfg.shared_experts:
        y = y + apply_mlp(p["shared"], x)
    return constrain(y, "batch", "seq", "act_embed"), {
        "lb_loss": lb, "dropped_frac": dr}
