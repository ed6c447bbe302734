"""Device time of the optimizer per step, in ms: ops under the ``opt``
scope (update, ZeRO-1 constraint, parameter update). The comm engine's
``muonbp.*`` stages nest inside it, so it holds ``ns_ms``'s ops and
``opt_ms - ns_ms`` is the optimizer's work outside the Newton-Schulz."""

from bench.layer_scopes import layer_ms


def read(r):
    return layer_ms(r, "opt_ms")
