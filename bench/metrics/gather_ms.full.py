"""Device time of the full step's optimizer gathers, in ms per full step:
the time covered by collective ops (synchronous, or async from start to
done) under the comm engine's ``muonbp.full[.s<i>].gather`` scopes. A
one-chip cell has none and reports nothing."""

from bench.trace import COLLECTIVE, scoped_ops, union_ns

GATHER_SCOPE = r"muonbp\.full(\.s\d+)?\.gather/"


def read(r):
    n_full = r.phases.count("full")
    if not n_full:
        return None

    def per_device(d):
        ops = [op for op in scoped_ops(r.trace, d, GATHER_SCOPE, r.lo, r.hi, async_ops=True)
               if COLLECTIVE.match(op[2])]
        return union_ns(ops, r.lo, r.hi) / n_full / 1e6 if ops else None
    return r.per_device_mean(per_device)
