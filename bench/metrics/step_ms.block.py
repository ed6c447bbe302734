"""Device time of one block-phase step program, in ms (AdamW's only phase)."""

from bench.trace import phase_modules


def read(r):
    durs = [m[1] for d in r.devices()
            for ph, m in phase_modules(r.trace, d, r.phases, r.lo, r.hi) if ph == "block"]
    return sum(durs) / len(durs) / 1e6 if durs else None
