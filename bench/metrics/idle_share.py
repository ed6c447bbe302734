"""Share of the traced window in which no op runs on the device, in %,
averaged over the cell's devices. The window runs from the first traced
step program's start to the last one's end."""

from bench.trace import union_ns


def read(r):
    span = r.hi - r.lo
    return r.per_device_mean(
        lambda d: 100.0 * (1.0 - union_ns(r.trace.ops.get(d, []), r.lo, r.hi) / span))
