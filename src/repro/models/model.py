"""Unified model API: init / loss / prefill / decode for every arch."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.transformer import (
    ShardCtx,
    decode_step,
    forward,
    init_cache,
    init_params,
)

IGNORE_LABEL = -1
LB_COEF = 0.01     # load-balance aux coefficient (Switch/OLMoE-style)
Z_COEF = 0.001     # router z-loss coefficient


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Masked mean CE. logits (B,S,V) any dtype; labels (B,S) with -1 ignored."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    nll = lse - label_logit
    mask = (labels != IGNORE_LABEL).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)


def loss_fn(
    params: dict,
    batch: dict,
    cfg: ModelConfig,
    *,
    ctx: ShardCtx = ShardCtx(),
) -> tuple[jax.Array, dict]:
    logits, aux = forward(
        params,
        batch["tokens"],
        cfg,
        extra_embeds=batch.get("vision_embeds"),
        encoder_frames=batch.get("audio_frames"),
        ctx=ctx,
        mode="train",
    )
    if cfg.vision_tokens:
        logits = logits[:, cfg.vision_tokens :, :]
    with jax.named_scope("model.head"):
        ce = cross_entropy(logits, batch["labels"])
    loss = ce
    metrics = {"ce": ce}
    if cfg.num_experts:
        loss = loss + LB_COEF * aux["load_balance"] + Z_COEF * aux["z_loss"]
        metrics.update(aux)
    metrics["loss"] = loss
    return loss, metrics


def prefill(params, batch, cfg, *, ctx: ShardCtx = ShardCtx()):
    """Full-sequence prefill: returns (logits, aux, cache)."""
    return forward(
        params,
        batch["tokens"],
        cfg,
        extra_embeds=batch.get("vision_embeds"),
        encoder_frames=batch.get("audio_frames"),
        ctx=ctx,
        mode="prefill",
    )


__all__ = [
    "ShardCtx",
    "cross_entropy",
    "decode_step",
    "forward",
    "init_cache",
    "init_params",
    "loss_fn",
    "prefill",
]
