"""Device time of the optimizer's Newton-Schulz ops per step, in ms: the
time covered by ops under the comm engine's ``muonbp.<phase>[.s<i>].ns``
name scopes over the traced steps, over the number of steps."""

from bench.trace import scoped_ops, union_ns

NS_SCOPE = r"muonbp\.[a-z0-9]+(\.s\d+)?\.ns/"


def read(r):
    def per_device(d):
        ops = scoped_ops(r.trace, d, NS_SCOPE, r.lo, r.hi)
        return union_ns(ops, r.lo, r.hi) / len(r.phases) / 1e6 if ops else None
    return r.per_device_mean(per_device)
