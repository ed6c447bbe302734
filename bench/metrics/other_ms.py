"""Device time per step, in ms, of the step programs' innermost ops that
no layer scope (``model.embed``, ``model.attn``, ``model.mlp``,
``model.head``, ``opt``) claims: the tracing's coverage gauge. With
``attn_ms``, ``mlp_ms``, ``head_ms`` and ``opt_ms`` it adds up to the
step's busy time."""

from bench.layer_scopes import other_ms


def read(r):
    return other_ms(r)
