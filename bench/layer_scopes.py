"""The train step's layers as the program names them, and the device time
each takes per step.

The program runs each layer under a ``jax.named_scope`` (``model.embed``,
``model.attn``, ``model.mlp``, ``model.head`` in ``models/``, ``opt`` in
``training/train_step.py``). A scope ``S`` reaches an op's ``op_name`` as
one component of its name stack, wrapped in JAX's transform names where
the op was differentiated outside a loop: ``.../closed_call/S/dot_general``
in the forward scan, ``.../checkpoint/rematted_computation/S/...`` where
the backward recomputes it, ``.../checkpoint/S/...`` in the backward, and
``jvp(S)/...`` or ``transpose(jvp(S))/...`` outside the scan. ``pattern``
matches ``S`` as such a whole component, so ``opt`` never matches
``optimizer`` or ``adopt``.
"""

from __future__ import annotations

import re

from bench.trace import in_window, leaf_ops, phase_modules, scoped_ops, union_ns

# Metric -> the program's scopes it reads.
LAYERS = {
    "attn_ms": ("model.attn",),
    "mlp_ms": ("model.mlp",),
    "head_ms": ("model.embed", "model.head"),
    "opt_ms": ("opt",),
}


def pattern(scopes) -> str:
    """A regex that finds any of ``scopes`` as a whole name-scope component."""
    names = "|".join(re.escape(s) for s in scopes)
    return rf"(^|/|\()({names})(\)|/|$)"


ANY_LAYER = re.compile(pattern([s for scopes in LAYERS.values() for s in scopes]))


def layer_ms(r, metric: str):
    """Device time under the metric's scopes per traced step, in ms: the
    union of the matching ops' intervals (a loop op and its body ops both
    match), over the number of steps; None where no op matches."""
    rx = pattern(LAYERS[metric])

    def per_device(d):
        ops = scoped_ops(r.trace, d, rx, r.lo, r.hi)
        return union_ns(ops, r.lo, r.hi) / len(r.phases) / 1e6 if ops else None
    return r.per_device_mean(per_device)


def other_ms(r):
    """Device time per traced step, in ms, of the step programs' innermost
    ops that no layer scope claims: ops with an empty scope (copies the
    compiler inserts), the layer scan's own slicing, the gradient norm.
    A claimed op's interval is taken out, so an unclaimed op inside a
    claimed loop is not counted twice. None where the program names no
    layer at all (then every op would be unclaimed, and the number would
    gauge nothing)."""
    def per_device(d):
        ops = in_window(r.trace.ops.get(d, []), r.lo, r.hi)
        claimed = [op for op in ops if ANY_LAYER.search(op[3])]
        if not claimed:
            return None
        unclaimed = [op for op in leaf_ops(ops) if not ANY_LAYER.search(op[3])]
        total = 0.0
        for _, (start, dur, _name) in phase_modules(r.trace, d, r.phases, r.lo, r.hi):
            end = start + dur
            total += (union_ns(unclaimed + claimed, start, end)
                      - union_ns(claimed, start, end))
        return total / len(r.phases) / 1e6
    return r.per_device_mean(per_device)
