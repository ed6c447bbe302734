"""The Newton-Schulz ops' share of their roofline, in %: the least time the
chip needs for the iterations' operations and bytes (``bench/flops.py``,
from the units each traced step orthogonalizes on one device) over the
measured device time under the ``.ns`` scopes (``ns_ms``'s numerator). The
compute bound (bf16 peak) is the larger of the two at these shapes."""

from bench import flops
from bench.trace import scoped_ops, union_ns

NS_SCOPE = r"muonbp\.[a-z0-9]+(\.s\d+)?\.ns/"


def read(r):
    need_flops = sum(flops.ns_flops(r.cfg, ph) for ph in r.phases)
    need_bytes = sum(flops.ns_bytes(r.cfg, ph) for ph in r.phases)
    least_s = max(need_flops / r.peak["bf16_flops_per_s"],
                  need_bytes / r.peak["hbm_bytes_per_s"])

    def per_device(d):
        busy = union_ns(scoped_ops(r.trace, d, NS_SCOPE, r.lo, r.hi), r.lo, r.hi) / 1e9
        return 100.0 * least_s / busy if busy > 0 else None
    return r.per_device_mean(per_device)
