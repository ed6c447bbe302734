"""Device time of the attention sublayer per step, in ms: ops under the
``model.attn`` scope (pre-norm, QKV/O projections, RoPE, the flash-attention
scan, residual add) in the forward, its recomputation and the backward."""

from bench.layer_scopes import layer_ms


def read(r):
    return layer_ms(r, "attn_ms")
