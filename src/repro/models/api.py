"""Unified model API: dispatch by family, input specs per shape, losses.

Every family exposes:
    specs(cfg)                         -> param Spec tree
    forward(params, batch, cfg, window)-> (logits, aux)
    cache_shapes(cfg, B, S) / init_cache / decode_step   (decoder families)
    prefill_step, paged_cache_shapes   (where the family has them)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import transformer, xlstm, hybrid, encdec, lstm_tiny
from repro.nn import axes_tree as _axes_tree, is_spec


@dataclasses.dataclass(frozen=True)
class ModelApi:
    specs: Callable
    forward: Callable
    cache_shapes: Optional[Callable] = None
    init_cache: Optional[Callable] = None
    decode_step: Optional[Callable] = None
    # vectorized whole-chunk prefill (serving admission); families
    # without one fall back to runtime/serve_step.py's exact scan
    prefill_step: Optional[Callable] = None
    # the shared-pool paged cache (cfg, n_pages, page_size) -> {name:
    # (shape, axes, dtype)}; None where the cache cannot be paged
    # (recurrent O(1) state has nothing to page)
    paged_cache_shapes: Optional[Callable] = None


_TRANSFORMER = ModelApi(transformer.model_specs, transformer.forward,
                        transformer.init_cache_shapes, transformer.init_cache,
                        transformer.decode_step, transformer.prefill_step,
                        transformer.paged_cache_shapes)

_FAMILIES = {
    "dense": _TRANSFORMER,
    "moe": _TRANSFORMER,
    "vlm": _TRANSFORMER,
    "ssm": ModelApi(xlstm.model_specs, xlstm.forward,
                    xlstm.cache_shapes, xlstm.init_cache, xlstm.decode_step),
    "hybrid": ModelApi(hybrid.model_specs, hybrid.forward,
                       hybrid.cache_shapes, hybrid.init_cache,
                       hybrid.decode_step),
    "audio": ModelApi(encdec.model_specs, encdec.forward,
                      encdec.cache_shapes, encdec.init_cache,
                      encdec.decode_step),
    "tiny": ModelApi(lstm_tiny.model_specs, lstm_tiny.forward,
                     lstm_tiny.cache_shapes, lstm_tiny.init_cache,
                     lstm_tiny.decode_step),
}


def get_model(cfg) -> ModelApi:
    return _FAMILIES[cfg.family]


def paged_families() -> tuple:
    """Families whose cache can live in the shared page pool."""
    return tuple(f for f, m in _FAMILIES.items()
                 if m.paged_cache_shapes is not None)


def param_specs(cfg):
    return get_model(cfg).specs(cfg)


def param_axes(cfg):
    return _axes_tree(param_specs(cfg))


# ------------------------------------------------------------- inputs
def input_specs(cfg, shape_cfg) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of one step —
    weak-type-correct, shardable, no device allocation."""
    B, S = shape_cfg.global_batch, shape_cfg.seq_len
    i32 = jnp.int32
    if shape_cfg.kind in ("train", "prefill"):
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                 "labels": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.frontend == "vision":
            batch["patch_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
        if cfg.family == "audio":
            batch["frames"] = jax.ShapeDtypeStruct(
                (B, encdec.src_len(cfg, S), cfg.d_model), jnp.float32)
        return batch
    # decode: ONE new token against a seq_len cache
    return {"token": jax.ShapeDtypeStruct((B, 1), i32),
            "index": jax.ShapeDtypeStruct((), i32)}


def input_axes(cfg, shape_cfg) -> dict:
    if shape_cfg.kind in ("train", "prefill"):
        ax = {"tokens": ("batch", None), "labels": ("batch", None)}
        if cfg.frontend == "vision":
            ax["patch_embeds"] = ("batch", None, None)
        if cfg.family == "audio":
            ax["frames"] = ("batch", None, None)
        return ax
    return {"token": ("batch", None), "index": ()}


# ------------------------------------------------------------- losses
def lm_loss(logits: jax.Array, batch: dict, cfg) -> jax.Array:
    """Next-token CE. VLM prefix tokens (patch embeds) carry no loss."""
    labels = batch["labels"]
    S = labels.shape[1]
    logits = logits[:, -S:]                      # drop multimodal prefix
    logits = logits[:, :-1]
    targets = labels[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = (targets != 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
