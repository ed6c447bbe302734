"""Decoder LM assembly: dense / MoE / SSM / hybrid layer stacks.

Layers are *stacked* over the leading dim and applied with ``lax.scan`` —
essential for compile time at 512 SPMD partitions (one layer body instead of
42 unrolled) and it subsumes the paper's ZeRO layerwise optimizer-state
sharding (state tensors carry the layer dim; each device owns its
model-parallel shard of every layer).

Modes:
  train/eval  : full-sequence forward, no cache
  prefill     : full-sequence forward, returns the KV/SSM cache
  decode      : one token against a cache at position ``pos``

Embeddings are vocab-parallel (Megatron-style); logits stay vocab-sharded
into the loss (logsumexp + label-gather need only tiny collectives).

Each layer of the step runs under a ``jax.named_scope`` that a profiler
trace reads back through the HLO ``op_name`` metadata: ``model.embed``
(the lookup), ``model.attn`` and ``model.mlp`` (each sublayer with its
norms and residual add) and ``model.head`` (final norm and logits; the
loss adds the cross-entropy). SSM, cross-attention and encoder layers
carry no scope.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    attention_block,
    scan_unroll,
    geglu,
    rms_norm,
    rope_frequencies,
    softcap,
    swiglu,
)
from repro.models.moe import moe_block

NO_WINDOW = jnp.int32(2**30)


class ShardCtx(NamedTuple):
    """Distribution context threaded through model code (None on CPU tests)."""

    mesh: Any = None
    data_axes: tuple = ()
    model_axis: Optional[str] = None
    q_layout: str = "head"    # 'head' | 'hd' (see layers.split_heads)
    kv_layout: str = "head"
    batch_axes: tuple = ()    # mesh axes sharding the batch dim of this run
    flash_block_k: int = 1024  # KV block of the jnp attention scan (fp32 score memory)



# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _norm_init(cfg, shape, dtype):
    return jnp.ones(shape, dtype)


def _dense_init(key, shape, dtype, scale=0.02):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_layer_params(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    """Stacked (num_layers, ...) parameters for the decoder stack."""
    L, D, F = cfg.num_layers, cfg.d_model, cfg.d_ff
    keys = iter(jax.random.split(key, 32))
    params: dict = {"norms": {}}

    has_attn = cfg.arch_type in ("dense", "moe", "vlm", "audio", "hybrid")
    has_mlp = cfg.arch_type in ("dense", "vlm", "audio", "hybrid")
    has_moe = cfg.arch_type == "moe"
    has_ssm = cfg.arch_type in ("ssm", "hybrid")

    if has_attn:
        params["attn"] = {
            "wq": _dense_init(next(keys), (L, D, cfg.q_dim), dtype),
            "wk": _dense_init(next(keys), (L, D, cfg.kv_dim), dtype),
            "wv": _dense_init(next(keys), (L, D, cfg.kv_dim), dtype),
            "wo": _dense_init(next(keys), (L, cfg.q_dim, D), dtype),
        }
        params["norms"]["attn_norm"] = _norm_init(cfg, (L, D), dtype)
        if cfg.use_post_norms:
            params["norms"]["post_attn_norm"] = _norm_init(cfg, (L, D), dtype)
    if has_mlp:
        mlp = {
            "wi": _dense_init(next(keys), (L, D, F), dtype),
            "wo": _dense_init(next(keys), (L, F, D), dtype),
        }
        if cfg.mlp_act in ("swiglu", "geglu"):
            mlp["wg"] = _dense_init(next(keys), (L, D, F), dtype)
        params["mlp"] = mlp
        params["norms"]["mlp_norm"] = _norm_init(cfg, (L, D), dtype)
        if cfg.use_post_norms:
            params["norms"]["post_mlp_norm"] = _norm_init(cfg, (L, D), dtype)
    if has_moe:
        E = cfg.num_experts
        params["moe"] = {
            "router": _dense_init(next(keys), (L, D, E), dtype),
            "wi": _dense_init(next(keys), (L, E, D, F), dtype),
            "wg": _dense_init(next(keys), (L, E, D, F), dtype),
            "wo": _dense_init(next(keys), (L, E, F, D), dtype),
        }
        params["norms"]["mlp_norm"] = _norm_init(cfg, (L, D), dtype)
    if has_ssm:
        dims = ssm_dims(cfg)
        stacked = [
            ssm_lib.init_ssm_params(k, dims, dtype)
            for k in jax.random.split(next(keys), L)
        ]
        params["ssm"] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)
        params["norms"]["ssm_norm"] = _norm_init(cfg, (L, D), dtype)
    if cfg.arch_type == "hybrid":
        params["hybrid"] = {
            "attn_scale": jnp.ones((L, D), dtype),
            "ssm_scale": jnp.ones((L, D), dtype),
        }
    return params


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    k_embed, k_layers, k_head, k_enc = jax.random.split(key, 4)
    Vp, D = cfg.padded_vocab, cfg.d_model
    params = {
        "embed": _dense_init(k_embed, (Vp, D), dtype),
        "layers": init_layer_params(k_layers, cfg, dtype),
        "final_norm": _norm_init(cfg, (D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(k_head, (D, Vp), dtype)
    if cfg.arch_type == "audio":
        from repro.models.encdec import init_encoder_params  # cycle-free

        params["encoder"] = init_encoder_params(k_enc, cfg, dtype)
        # decoder cross-attention (stacked per decoder layer)
        L = cfg.num_layers
        kc = jax.random.split(k_enc, 5)
        params["layers"]["cross"] = {
            "wq": _dense_init(kc[0], (L, D, cfg.q_dim), dtype),
            "wk": _dense_init(kc[1], (L, D, cfg.kv_dim), dtype),
            "wv": _dense_init(kc[2], (L, D, cfg.kv_dim), dtype),
            "wo": _dense_init(kc[3], (L, cfg.q_dim, D), dtype),
        }
        params["layers"]["norms"]["cross_norm"] = _norm_init(cfg, (L, D), dtype)
    return params


def ssm_dims(cfg: ModelConfig) -> ssm_lib.SSMDims:
    return ssm_lib.make_dims(
        cfg.d_model, cfg.ssm_state, head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand
    )


def window_flags(cfg: ModelConfig) -> jax.Array:
    """Per-layer attention window (NO_WINDOW = global)."""
    L = cfg.num_layers
    if cfg.attention_pattern == "swa":
        return jnp.full((L,), cfg.window_size, jnp.int32)
    if cfg.attention_pattern == "alternating":
        return jnp.where(
            jnp.arange(L) % 2 == 0, jnp.int32(cfg.window_size), NO_WINDOW
        )
    return jnp.full((L,), NO_WINDOW, jnp.int32)


def _seq_shard(x: jax.Array, ctx: ShardCtx) -> jax.Array:
    """Megatron-style sequence parallelism for the residual stream.

    Between layers the (B, S, D) residual is sharded over the ``model`` axis
    on S — activation checkpoints then occupy 1/model_size of HBM; GSPMD
    inserts the all-gather (fwd) / reduce-scatter (bwd) at each layer's
    attention/MLP entry exactly like Megatron-LM sequence parallelism.
    """
    if ctx.mesh is None or ctx.model_axis is None:
        return x
    msize = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))[ctx.model_axis]
    if x.shape[1] % msize or x.shape[1] == 1:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(ctx.batch_axes if ctx.batch_axes else None, ctx.model_axis, None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------

def _mlp_apply(x, mlp, cfg):
    if cfg.mlp_act == "swiglu":
        return swiglu(x, mlp["wi"], mlp["wg"], mlp["wo"])
    if cfg.mlp_act == "geglu":
        return geglu(x, mlp["wi"], mlp["wg"], mlp["wo"])
    return jax.nn.gelu(x @ mlp["wi"], approximate=True) @ mlp["wo"]


def _attn_kwargs(cfg, inv_freq, ctx: ShardCtx = ShardCtx()):
    return dict(
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        inv_freq=inv_freq,
        attn_softcap=cfg.attn_softcap,
        q_layout=ctx.q_layout,
        kv_layout=ctx.kv_layout,
        block_k=ctx.flash_block_k,
        mesh=ctx.mesh,
        batch_axes=ctx.batch_axes,
        model_axis=ctx.model_axis,
    )


def decoder_layer(
    x: jax.Array,
    layer: dict,
    cfg: ModelConfig,
    *,
    window: jax.Array,
    positions: jax.Array,
    inv_freq,
    mode: str,
    kv_cache=None,
    ssm_state=None,
    cache_index=None,
    kv_len=None,
    ring: bool = False,
    ctx: ShardCtx = ShardCtx(),
    cross_kv: jax.Array | None = None,
):
    """One decoder layer. Returns (x, new_kv_cache, new_ssm_state, aux)."""
    norms = layer["norms"]
    new_kv = None
    new_ssm = None
    aux = jnp.zeros((2,), jnp.float32)  # (load_balance, z_loss)

    has_attn = "attn" in layer
    has_ssm = "ssm" in layer
    hybrid = cfg.arch_type == "hybrid"
    # A global layer's scanned NO_WINDOW masks nothing; pass None, which
    # tells attention that the layer is global.
    if ring or cfg.attention_pattern == "full":
        window = None

    if has_attn and has_ssm and hybrid:
        with jax.named_scope("model.attn"):
            h = rms_norm(x, norms["attn_norm"])
            attn_out, new_kv = attention_block(
                h, layer["attn"], positions=positions,
                window=window,
                kv_cache=kv_cache, cache_index=cache_index, kv_len=kv_len,
                causal=not ring,
                **_attn_kwargs(cfg, inv_freq, ctx),
            )
        if mode == "decode":
            ssm_out, new_ssm = ssm_lib.ssm_decode_step(
                h, ssm_state, layer["ssm"], ssm_dims(cfg)
            )
        elif mode == "prefill":
            ssm_out, new_ssm = ssm_lib.ssm_forward(
                h, layer["ssm"], ssm_dims(cfg), return_state=True
            )
        else:
            ssm_out = ssm_lib.ssm_forward(h, layer["ssm"], ssm_dims(cfg))
        combined = 0.5 * (
            attn_out * layer["hybrid"]["attn_scale"]
            + ssm_out * layer["hybrid"]["ssm_scale"]
        )
        x = x + combined
    elif has_attn:
        with jax.named_scope("model.attn"):
            h = rms_norm(x, norms["attn_norm"])
            attn_out, new_kv = attention_block(
                h, layer["attn"], positions=positions,
                window=window,
                kv_cache=kv_cache, cache_index=cache_index, kv_len=kv_len,
                causal=(cfg.arch_type != "encoder") and not ring,
                **_attn_kwargs(cfg, inv_freq, ctx),
            )
            if cfg.use_post_norms:
                attn_out = rms_norm(attn_out, norms["post_attn_norm"])
            x = x + attn_out
    elif has_ssm:  # pure SSM (mamba2)
        h = rms_norm(x, norms["ssm_norm"])
        if mode == "decode":
            ssm_out, new_ssm = ssm_lib.ssm_decode_step(
                h, ssm_state, layer["ssm"], ssm_dims(cfg)
            )
        elif mode == "prefill":
            ssm_out, new_ssm = ssm_lib.ssm_forward(
                h, layer["ssm"], ssm_dims(cfg), return_state=True
            )
        else:
            ssm_out = ssm_lib.ssm_forward(h, layer["ssm"], ssm_dims(cfg))
        x = x + ssm_out

    if cross_kv is not None:
        h = rms_norm(x, norms["cross_norm"])
        cross_out, _ = attention_block(
            h, layer["cross"], positions=positions, cross_kv=cross_kv,
            **_attn_kwargs(cfg, None, ctx),
        )
        x = x + cross_out

    if "moe" in layer:
        with jax.named_scope("model.mlp"):
            h = rms_norm(x, norms["mlp_norm"])
            out = moe_block(
                h, layer["moe"],
                top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor,
                router_style=cfg.router_style,
                mesh=ctx.mesh,
                data_axes=ctx.batch_axes,
                model_axis=ctx.model_axis,
            )
            aux = jnp.stack([out.load_balance_loss, out.router_z_loss])
            x = x + out.y
    elif "mlp" in layer:
        with jax.named_scope("model.mlp"):
            h = rms_norm(x, norms["mlp_norm"])
            mlp_out = _mlp_apply(h, layer["mlp"], cfg)
            if cfg.use_post_norms:
                mlp_out = rms_norm(mlp_out, norms["post_mlp_norm"])
            x = x + mlp_out

    return x, new_kv, new_ssm, aux


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    return x


def _logits(params, x, cfg):
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    )
    logits = x @ head
    if cfg.final_softcap is not None:
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits


def _scan_layers(fn, x, layers, flags, extra_xs=None):
    """Scan ``fn`` over stacked layers; returns (x, stacked outputs)."""
    xs = (layers, flags) if extra_xs is None else (layers, flags, extra_xs)
    return jax.lax.scan(fn, x, xs)


def forward(
    params: dict,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    extra_embeds: jax.Array | None = None,
    encoder_frames: jax.Array | None = None,
    ctx: ShardCtx = ShardCtx(),
    mode: str = "train",
    remat: bool = True,
):
    """Full-sequence forward. Returns (logits, aux_losses[, cache]).

    extra_embeds: (B, V_tok, D) VLM patch embeddings prepended to the text.
    encoder_frames: (B, S_enc, D) whisper frame embeddings (audio arch).
    With mode='prefill', also returns the cache pytree for decode.
    """
    with jax.named_scope("model.embed"):
        x = _embed(params, tokens, cfg)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    seq = x.shape[1]
    positions = jnp.arange(seq)
    inv_freq = (
        rope_frequencies(cfg.head_dim, cfg.rope_theta)
        if cfg.arch_type != "audio" and cfg.num_heads
        else None
    )
    flags = window_flags(cfg)

    cross_kv = None
    if cfg.arch_type == "audio":
        from repro.models.encdec import encode

        if encoder_frames is None:
            raise ValueError("audio arch requires encoder_frames")
        cross_kv = encode(params["encoder"], encoder_frames, cfg, ctx)
        from repro.models.layers import sinusoidal_positions

        x = x + sinusoidal_positions(seq, cfg.d_model).astype(x.dtype)[None]

    layers = params["layers"]
    prefill = mode == "prefill"

    def body(x, sl):
        layer, window = sl
        x = _seq_shard(x, ctx)
        x, new_kv, new_ssm, aux = decoder_layer(
            x, layer, cfg,
            window=window, positions=positions, inv_freq=inv_freq,
            mode="prefill" if prefill else "train",
            ctx=ctx,
            cross_kv=cross_kv if "cross" in layer else None,
        )
        outs = {"aux": aux}
        if prefill:
            if new_kv is not None:
                outs["kv"] = new_kv
            if new_ssm is not None:
                outs["ssm"] = new_ssm
        return x, outs

    if mode == "train" and remat:
        # Activation checkpointing: save only the (sequence-sharded) residual
        # between layers, recompute everything else in the backward pass.
        body = jax.checkpoint(body, policy=None)

    x, outs = jax.lax.scan(
        body, x, (layers, flags), unroll=True if scan_unroll() else 1
    )
    x = _seq_shard(x, ctx)
    with jax.named_scope("model.head"):
        x = rms_norm(x, params["final_norm"])
        logits = _logits(params, x, cfg)
    aux = {"load_balance": outs["aux"][:, 0].sum(), "z_loss": outs["aux"][:, 1].sum()}
    if prefill:
        cache = {k: v for k, v in outs.items() if k != "aux"}
        return logits, aux, cache
    return logits, aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    """Empty decode cache: KV buffers and/or SSM states, stacked over layers."""
    cache: dict = {}
    L = cfg.num_layers
    if cfg.num_heads and cfg.arch_type != "ssm":
        kv_shape = (L, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        cache["kv"] = (jnp.zeros(kv_shape, dtype), jnp.zeros(kv_shape, dtype))
    if cfg.arch_type in ("ssm", "hybrid"):
        dims = ssm_dims(cfg)
        kk = dims.conv_kernel - 1
        cache["ssm"] = {
            "h": jnp.zeros(
                (L, batch, dims.num_heads, dims.head_dim, dims.state_size),
                jnp.float32,
            ),
            "conv_x": jnp.zeros((L, batch, kk, dims.d_inner), dtype),
            "conv_b": jnp.zeros((L, batch, kk, dims.state_size), dtype),
            "conv_c": jnp.zeros((L, batch, kk, dims.state_size), dtype),
        }
    return cache


def decode_step(
    params: dict,
    token: jax.Array,        # (B, 1) int32
    cache: dict,
    pos: jax.Array,          # scalar int32: current length of the cache
    cfg: ModelConfig,
    *,
    ctx: ShardCtx = ShardCtx(),
    encoder_out: jax.Array | None = None,
    ring_cache: bool = False,
):
    """One decode step. Returns (logits (B,1,V), new cache).

    ``ring_cache=True`` (uniform sliding-window archs only): the KV buffer
    holds just ``window_size`` slots written at ``pos % window``; since RoPE
    is applied at absolute positions before caching, attention over the ring
    needs only a fill-level mask — the window constraint is implied by
    eviction. Cuts decode cache memory from O(seq_len) to O(window):
    long_500k on mixtral is 128x. (See EXPERIMENTS.md Perf.)
    """
    x = _embed(params, token, cfg)
    positions = pos + jnp.arange(1)
    inv_freq = (
        rope_frequencies(cfg.head_dim, cfg.rope_theta)
        if cfg.arch_type != "audio" and cfg.num_heads
        else None
    )
    if cfg.arch_type == "audio":
        from repro.models.layers import sinusoidal_positions

        # position embedding for the current slot
        table = sinusoidal_positions(cache["kv"][0].shape[2], cfg.d_model)
        x = x + jax.lax.dynamic_slice_in_dim(table, pos, 1, 0)[None].astype(x.dtype)
    flags = window_flags(cfg)
    layers = params["layers"]

    write_idx, fill = pos, None
    if ring_cache:
        if cfg.attention_pattern != "swa":
            raise ValueError("ring_cache requires a uniform sliding-window arch")
        cache_len = cache["kv"][0].shape[2]
        write_idx = pos % cache_len
        fill = jnp.minimum(pos + 1, cache_len)

    def body(x, sl):
        layer, window, cache_sl = sl
        x, new_kv, new_ssm, _ = decoder_layer(
            x, layer, cfg,
            window=window, positions=positions, inv_freq=inv_freq,
            mode="decode",
            kv_cache=cache_sl.get("kv"),
            ssm_state=cache_sl.get("ssm"),
            cache_index=write_idx,
            kv_len=fill,
            ring=ring_cache,
            ctx=ctx,
            cross_kv=encoder_out if "cross" in layer else None,
        )
        new_sl = {}
        if new_kv is not None:
            new_sl["kv"] = new_kv
        if new_ssm is not None:
            new_sl["ssm"] = new_ssm
        return x, new_sl

    x, new_cache = jax.lax.scan(
        body, x, (layers, flags, cache), unroll=True if scan_unroll() else 1
    )
    x = rms_norm(x, params["final_norm"])
    logits = _logits(params, x, cfg)
    return logits, new_cache
