"""Device time of the vocabulary layers per step, in ms: ops under the
``model.embed`` scope (the token lookup and, in the backward, its
scatter-add into the embedding) or ``model.head`` (final norm, logits,
cross-entropy), forward and backward."""

from bench.layer_scopes import layer_ms


def read(r):
    return layer_ms(r, "head_ms")
