"""Causal self-attention on the installed Pallas flash kernel (splash).

``layers.flash_attention`` sends a call here when ``use_kernel`` says the
kernel applies: full-sequence causal self-attention on a TPU, with no
window, no KV-length mask, no soft cap and no cache offset, a head size
that is a multiple of 128 and a sequence that is a multiple of the
kernel's blocks. Every other call keeps the jnp scan.

The kernel is JAX's ``splash_attention``: grouped-query heads (query head
``h`` reads KV head ``h // G``, as the scan does), blocks above the
diagonal skipped, operands in the compute dtype and the scores, row max,
row sum and accumulators in fp32, with its own backward kernel (dq, dk
and dv in one fused pass) under a ``custom_vjp``. q is scaled by
``head_dim ** -0.5`` before the call.

Under a mesh the kernel runs inside ``jax.shard_map`` with the batch over
the batch axes and the heads over the model axis: a ``pallas_call`` left
to GSPMD would be replicated.
"""

from __future__ import annotations

import functools
import math

import jax
from jax.sharding import PartitionSpec as P

# Sequence blocks the rule picks from, largest first. At 1 x 4096 with
# 24/8 heads of 128 on a v5e, blocks of 1024 with the fused backward ran
# forward and backward fastest (PERF.md, PR 15).
BLOCKS = (1024, 512, 256, 128)


def block_size(seq: int, head_dim: int) -> int | None:
    """The q and kv block, forward and backward, for a sequence of ``seq``
    at ``head_dim``: the largest of ``BLOCKS`` that divides ``seq`` (halved
    above a head of 128, whose tiles take twice the fast memory); None
    where none divides."""
    for b in BLOCKS:
        b = b if head_dim <= 128 else max(b // 2, 128)
        if seq % b == 0:
            return b
    return None


def use_kernel(q_shape, kv_shape, *, causal, window, attn_softcap, q_offset, kv_len,
               mesh=None, batch_axes=(), model_axis=None, q_layout="head",
               kv_layout="head", backend=None) -> bool:
    """Whether the call can be seen to be plain causal self-attention that
    the kernel computes; ``backend`` defaults to JAX's."""
    batch, sq, hq, hd = q_shape
    skv, hkv = kv_shape[1], kv_shape[2]
    if (backend or jax.default_backend()) != "tpu":
        return False
    if not causal or window is not None or attn_softcap is not None or kv_len is not None:
        return False
    # A traced offset (prefill into a cache) may be anything but 0.
    if not (isinstance(q_offset, int) and q_offset == 0):
        return False
    if sq != skv or hd % 128 or block_size(sq, hd) is None:
        return False
    if mesh is None:
        return True
    if q_layout != "head" or kv_layout != "head":
        return False
    heads = mesh.shape[model_axis] if model_axis else 1
    rows = math.prod(mesh.shape[a] for a in batch_axes)
    return hq % heads == 0 and hkv % heads == 0 and batch % rows == 0


@functools.lru_cache(maxsize=None)
def _kernel(seq: int, q_heads: int, head_dim: int, interpret: bool):
    # Imported on first use, as the NS kernels are: Pallas takes over a
    # second to import, which calls that keep the scan never need.
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    b = block_size(seq, head_dim)
    # One fused backward kernel computes dq with dk and dv.
    blocks = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        use_fused_bwd_kernel=True)
    mask = splash.MultiHeadMask([splash.CausalMask((seq, seq))] * q_heads)
    # The kernel holds its block masks as arrays: make them concrete, so
    # that a kernel built while tracing can serve later traces.
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(mask, block_sizes=blocks, head_shards=1,
                                      q_seq_shards=1, interpret=interpret)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, mesh=None,
                     batch_axes=(), model_axis=None, interpret: bool = False) -> jax.Array:
    """q: (B, S, Hq, hd); k, v: (B, S, Hkv, hd). Returns (B, S, Hq, hd) in
    q.dtype. ``interpret`` runs the kernel in Pallas interpret mode (CPU
    tests)."""
    hd = q.shape[-1]
    q = (q * hd ** -0.5).astype(q.dtype)
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))   # (B, H, S, hd)

    def local(q, k, v):
        kernel = _kernel(q.shape[2], q.shape[1], hd, interpret)
        return jax.vmap(kernel)(q, k, v)

    if mesh is not None:
        spec = P(batch_axes or None, model_axis, None, None)
        local = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                              check_vma=False)
    return local(qt, kt, vt).swapaxes(1, 2)
