"""The whole step's share of the chip's bf16 peak, in %: model operations
per step on one chip (``bench/flops.py``; recomputation and optimizer
operations not counted) over the mean device time of the traced step
programs."""

from bench import flops
from bench.trace import phase_modules


def read(r):
    chips = r.cfg["mesh"].get("data", 1) * r.cfg["mesh"].get("model", 1)
    tokens = r.traffic["batch"] * r.traffic["seq"]
    per_chip = flops.model_flops_per_token(r.cfg, r.traffic["seq"]) * tokens / chips
    durs = [m[1] for d in r.devices()
            for _, m in phase_modules(r.trace, d, r.phases, r.lo, r.hi)]
    if not durs:
        return None
    step_s = sum(durs) / len(durs) / 1e9
    return 100.0 * per_chip / (step_s * r.peak["bf16_flops_per_s"])
