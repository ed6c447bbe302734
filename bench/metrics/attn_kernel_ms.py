"""Device time of the attention kernel per step, in ms: the Pallas flash
kernel's forward, its recomputation and its backward. 0.0 where the
attention sublayer runs and none of its ops is the kernel (the jnp scan);
None where the program names no layers.

A kernel op is one under the ``model.attn`` scope whose ``op_name`` ends
in a ``pallas_call`` component, or one whose instruction is named after
the splash kernel (``splash_mha_fwd_residuals.3``): a Mosaic custom call's
HLO text spans several lines (its kernel metadata is printed with line
breaks), so the trace may give it no scope at all."""

import re

from bench.layer_scopes import LAYERS, pattern
from bench.trace import in_window, union_ns

ATTN = re.compile(pattern(LAYERS["attn_ms"]))
PALLAS = re.compile(r"(^|/)pallas_call(/|$)")
SPLASH = re.compile(r"^splash_m[hq]a_(fwd|dq|dkv)")


def _is_kernel(op) -> bool:
    return bool(SPLASH.match(op[2]) or (ATTN.search(op[3]) and PALLAS.search(op[3])))


def read(r):
    def per_device(d):
        ops = in_window(r.trace.ops.get(d, []), r.lo, r.hi)
        if not any(ATTN.search(op[3]) for op in ops):
            return None
        return union_ns([op for op in ops if _is_kernel(op)], r.lo, r.hi) / len(r.phases) / 1e6
    return r.per_device_mean(per_device)
