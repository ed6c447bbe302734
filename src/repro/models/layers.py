"""Shared model layers: RMSNorm, RoPE, GQA attention, gated MLPs.

Attention takes one of two paths (``flash_attention``):

* Full-sequence causal self-attention on a TPU (training, and prefill
  without a cache) runs on the Pallas flash kernel of
  ``repro.kernels.attention``: bf16 operands, fp32 softmax, blocks above
  the diagonal skipped. It applies only where the call shows it: no
  window, no KV-length mask, no soft cap, query offset the literal 0,
  Sq == Skv, a head size that is a multiple of 128, a sequence the
  kernel's blocks divide, and under a mesh the ``head`` layout with heads
  and batch that the mesh divides.
* Every other call runs a pure-JAX flash/online-softmax scan (lax.scan
  over KV blocks, fp32 accumulators), which never materializes the O(S^2)
  scores: sliding windows (mixtral/gemma2/hymba), attention logit
  softcapping (gemma2), cross-attention and the non-causal encoder
  (whisper), prefill into a cache at an offset, KV-length masking (decode
  with a partially filled cache), and every backend but the TPU. Decode
  (Sq==1) uses a direct path — one token's scores over the cache are
  cheap and GSPMD shards them cleanly.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import attention as attention_kernel

NEG_INF = -1e30


def scan_unroll():
    """Full-unroll switch for cost calibration (see launch/dryrun.py).

    XLA's cost_analysis counts a while-loop body ONCE regardless of trip
    count; the dry-run sets REPRO_UNROLL_SCANS=1 on small-L variants to get
    fully-counted FLOPs/bytes/collectives and extrapolates to the real L.
    """
    return os.environ.get("REPRO_UNROLL_SCANS", "") not in ("", "0")


# ---------------------------------------------------------------------------
# Basic blocks
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def swiglu(x: jax.Array, wi: jax.Array, wg: jax.Array, wo: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ wg) * (x @ wi)
    return h @ wo


def geglu(x: jax.Array, wi: jax.Array, wg: jax.Array, wo: jax.Array) -> jax.Array:
    h = jax.nn.gelu(x @ wg, approximate=True) * (x @ wi)
    return h @ wo


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array) -> jax.Array:
    """Interleaved-pair RoPE. x: (B, S, H, hd); positions: (S,) or (B, S).

    Interleaved (GPT-NeoX original) rather than rotate-half: rotation pairs
    are *adjacent* channels (2i, 2i+1), so when head_dim is sharded over the
    ``model`` axis (the kv-heads < TP-degree fallback, see sharding/specs.py)
    both members of a pair live on the same device and RoPE needs no
    cross-device traffic. Mathematically equivalent up to a fixed channel
    permutation (init is iid random, so the permutation is immaterial).
    """
    dtype = x.dtype
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x = x.astype(jnp.float32)
    shape = x.shape
    x = x.reshape(*shape[:-1], shape[-1] // 2, 2)
    x1, x2 = x[..., 0], x[..., 1]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    out = jnp.stack([r1, r2], axis=-1).reshape(shape)
    return out.astype(dtype)


def sinusoidal_positions(num_positions: int, dim: int) -> jax.Array:
    """Whisper-style sinusoidal absolute position embeddings."""
    pos = jnp.arange(num_positions, dtype=jnp.float32)[:, None]
    inv = jnp.exp(
        -jnp.log(10000.0) * jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    )[None, :]
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _mask_scores(
    s: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    *,
    causal: bool,
    window: jax.Array | int | None,
    kv_len: jax.Array | int | None,
) -> jax.Array:
    """s: (..., Sq, Tb); q_pos: (Sq,); k_pos: (Tb,)."""
    valid = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        valid &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        # Attend to at most `window` previous positions (inclusive of self).
        valid &= k_pos[None, :] > q_pos[:, None] - window
    if kv_len is not None:
        valid &= (k_pos < kv_len)[None, :]
    return jnp.where(valid, s, NEG_INF)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: jax.Array | int | None = None,
    attn_softcap: float | None = None,
    q_offset: jax.Array | int = 0,
    kv_len: jax.Array | int | None = None,
    block_k: int = 1024,
    mesh: jax.sharding.Mesh | None = None,
    batch_axes: tuple[str, ...] = (),
    model_axis: str | None = None,
    q_layout: str = "head",
    kv_layout: str = "head",
) -> jax.Array:
    """Online-softmax attention.

    q: (B, Sq, Hq, hd); k, v: (B, Skv, Hkv, hd) with Hq % Hkv == 0 (GQA).
    Returns (B, Sq, Hq, hd) in q.dtype. Scores/accumulators are fp32.
    Where ``attention_kernel.use_kernel`` holds, the Pallas kernel computes
    it (under ``mesh``, sharded over ``batch_axes`` and ``model_axis``);
    otherwise the scan below, whose blocks are ``block_k`` keys.
    """
    batch, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv
    scale = hd ** -0.5

    if attention_kernel.use_kernel(
        q.shape, k.shape, causal=causal, window=window, attn_softcap=attn_softcap,
        q_offset=q_offset, kv_len=kv_len, mesh=mesh, batch_axes=batch_axes,
        model_axis=model_axis, q_layout=q_layout, kv_layout=kv_layout,
    ):
        return attention_kernel.causal_attention(
            q, k, v, mesh=mesh, batch_axes=batch_axes, model_axis=model_axis)

    if sq == 1:
        return _decode_attention(
            q, k, v, causal=causal, window=window, attn_softcap=attn_softcap,
            q_offset=q_offset, kv_len=kv_len,
        )

    block_k = min(block_k, skv)
    if skv % block_k:
        # Pad KV to a block multiple; padded keys are masked via kv_len.
        pad = block_k - skv % block_k
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_len = jnp.minimum(jnp.asarray(kv_len if kv_len is not None else skv), skv)
        skv = k.shape[1]
    nblk = skv // block_k

    qg = q.reshape(batch, sq, hkv, groups, hd)
    qg = jnp.moveaxis(qg, 1, 3).astype(jnp.float32)            # (B,Hkv,G,Sq,hd)
    kb = jnp.moveaxis(k.reshape(batch, nblk, block_k, hkv, hd), 3, 2)
    vb = jnp.moveaxis(v.reshape(batch, nblk, block_k, hkv, hd), 3, 2)
    kb = jnp.moveaxis(kb, 1, 0)                             # (nblk,B,Hkv,Tb,hd)
    vb = jnp.moveaxis(vb, 1, 0)

    q_pos = jnp.asarray(q_offset) + jnp.arange(sq)

    def body(carry, inputs):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, blk_idx = inputs                          # (B,Hkv,Tb,hd)
        s = jnp.einsum(
            "bkgqd,bktd->bkgqt", qg, k_blk.astype(jnp.float32)
        ) * scale
        if attn_softcap is not None:
            s = softcap(s, attn_softcap)
        k_pos = blk_idx * block_k + jnp.arange(block_k)
        s = _mask_scores(
            s, q_pos, k_pos, causal=causal, window=window, kv_len=kv_len
        )
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqt,bktd->bkgqd", p, v_blk.astype(jnp.float32)
        )
        return (m_new, l_new, acc), None

    m0 = jnp.full((batch, hkv, groups, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((batch, hkv, groups, sq), jnp.float32)
    acc0 = jnp.zeros((batch, hkv, groups, sq, hd), jnp.float32)
    (m_f, l_f, acc_f), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kb, vb, jnp.arange(nblk)),
        unroll=True if scan_unroll() else 1,
    )
    out = acc_f / jnp.maximum(l_f, 1e-30)[..., None]            # (B,Hkv,G,Sq,hd)
    out = jnp.moveaxis(out, 3, 1).reshape(batch, sq, hq, hd)
    return out.astype(q.dtype)


def _decode_attention(
    q, k, v, *, causal, window, attn_softcap, q_offset, kv_len
) -> jax.Array:
    """Direct attention for a single query position (Sq == 1)."""
    batch, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv
    scale = hd ** -0.5
    qg = q.reshape(batch, sq, hkv, groups, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k.astype(jnp.float32)) * scale
    if attn_softcap is not None:
        s = softcap(s, attn_softcap)
    q_pos = jnp.asarray(q_offset) + jnp.arange(sq)
    k_pos = jnp.arange(skv)
    s = _mask_scores(s, q_pos, k_pos, causal=causal, window=window, kv_len=kv_len)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqt,btkd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(batch, sq, hq, hd).astype(q.dtype)


def split_heads(t: jax.Array, n_heads: int, head_dim: int, layout: str) -> jax.Array:
    """(B, S, n*hd) -> (B, S, n, hd).

    layout='head': columns are head-major (standard). layout='hd': columns
    are head_dim-major — used when n_heads doesn't divide the model axis but
    head_dim does, so the projection's column sharding propagates to the
    head_dim factor of the reshape (see sharding/specs.py).
    """
    b, s, _ = t.shape
    if layout == "hd":
        return jnp.swapaxes(t.reshape(b, s, head_dim, n_heads), 2, 3)
    return t.reshape(b, s, n_heads, head_dim)


def merge_heads(t: jax.Array, layout: str) -> jax.Array:
    b, s, h, hd = t.shape
    if layout == "hd":
        return jnp.swapaxes(t, 2, 3).reshape(b, s, hd * h)
    return t.reshape(b, s, h * hd)


def attention_block(
    x: jax.Array,
    params: dict,
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    positions: jax.Array,
    inv_freq: jax.Array | None,
    causal: bool = True,
    window: jax.Array | int | None = None,
    attn_softcap: float | None = None,
    kv_cache: tuple[jax.Array, jax.Array] | None = None,
    cache_index: jax.Array | None = None,
    kv_len: jax.Array | int | None = None,
    cross_kv: jax.Array | None = None,
    block_k: int = 1024,
    q_layout: str = "head",
    kv_layout: str = "head",
    mesh: jax.sharding.Mesh | None = None,
    batch_axes: tuple[str, ...] = (),
    model_axis: str | None = None,
):
    """Full attention sub-block: projections + rope + attention + out-proj.

    Returns (out, new_kv_cache). With ``kv_cache`` given, the fresh K/V are
    written at ``cache_index`` and attention runs over the whole cache.
    With ``cross_kv`` (B, S_enc, D) this is cross-attention (no cache/rope).
    ``mesh``, ``batch_axes`` and ``model_axis`` say how the kernel path of
    ``flash_attention`` shards.
    """
    batch, sq, _ = x.shape
    kv_src = cross_kv if cross_kv is not None else x
    q = split_heads(x @ params["wq"], num_heads, head_dim, q_layout)
    k = split_heads(kv_src @ params["wk"], num_kv_heads, head_dim, kv_layout)
    v = split_heads(kv_src @ params["wv"], num_kv_heads, head_dim, kv_layout)

    if inv_freq is not None and cross_kv is None:
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)

    # The post-RoPE K/V are the cache content: return them even without a
    # pre-allocated buffer (prefill builds its cache from these).
    new_cache = (k, v)
    q_offset = 0
    if kv_cache is not None:
        ck, cv = kv_cache
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_index, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_index, 0, 0))
        k, v = ck, cv
        new_cache = (ck, cv)
        q_offset = cache_index
        kv_len = cache_index + sq if kv_len is None else kv_len

    out = flash_attention(
        q, k, v,
        causal=causal and cross_kv is None,
        window=window,
        attn_softcap=attn_softcap,
        q_offset=q_offset,
        kv_len=kv_len,
        block_k=block_k,
        mesh=mesh,
        batch_axes=batch_axes,
        model_axis=model_axis,
        q_layout=q_layout,
        kv_layout=kv_layout,
    )
    out = merge_heads(out, q_layout) @ params["wo"]
    return out, new_cache
