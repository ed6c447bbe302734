"""The part of ``gather_ms.full`` during which no other op runs on the
device, in ms per full step: gather time the pipelined schedule did not
hide behind compute. "Other ops" are the innermost non-collective ops (a
loop op spans its body and would hide everything)."""

from bench.trace import COLLECTIVE, in_window, leaf_ops, scoped_ops, union_ns

GATHER_SCOPE = r"muonbp\.full(\.s\d+)?\.gather/"


def read(r):
    n_full = r.phases.count("full")
    if not n_full:
        return None

    def per_device(d):
        gathers = [op for op in scoped_ops(r.trace, d, GATHER_SCOPE, r.lo, r.hi, async_ops=True)
                   if COLLECTIVE.match(op[2])]
        if not gathers:
            return None
        others = [op for op in leaf_ops(in_window(r.trace.ops.get(d, []), r.lo, r.hi))
                  if not COLLECTIVE.match(op[2])]
        merged, total = [], 0.0
        for s, e in sorted((g[0], g[0] + g[1]) for g in gathers):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        for s, e in merged:
            total += (e - s) - union_ns(others, s, e)
        return total / n_full / 1e6
    return r.per_device_mean(per_device)
