"""Device time of the MLP sublayer per step, in ms: ops under the
``model.mlp`` scope (pre-norm, the MLP or MoE block, residual add) in the
forward, its recomputation and the backward."""

from bench.layer_scopes import layer_ms


def read(r):
    return layer_ms(r, "mlp_ms")
