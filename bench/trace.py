"""Reading a profiler trace of a cell's traced window.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps what the per-layer metrics read: for every TPU plane its op events
(the synchronous op line and the async op line), its step program
executions, and the benchmark's own host annotations. A TPU op event names
its HLO instruction and carries no name scope, so ``attach_scopes`` looks
each instruction up in the ``op_name`` metadata of the compiled program of
the step it ran in (``hlo_scopes``). ``Trace`` holds all this as plain
lists, so a small recorded trace can be kept as JSON and read back without
a chip (``to_json`` / ``from_json``).

Times are nanoseconds. Device planes share one clock; the host's spans are
aligned to it only to about a millisecond, so windows are taken from the
device's own events.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

# Host annotations the benchmark writes around its own calls.
HOST_SPANS = ("dispatch", "block", "batch", "traced_window")
# Collective operations, as XLA names them (async pairs included).
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")


@dataclasses.dataclass
class Trace:
    """Per device: op events ``[start_ns, dur_ns, name, scope]`` of the
    synchronous op line and of the async op line (an async copy or
    collective from its start to its done), and step program executions
    ``[start_ns, dur_ns, name]``; host spans ``[start_ns, dur_ns, name]``.
    ``name`` is the HLO instruction's name, ``scope`` its ``op_name``
    metadata (the JAX name scopes it was traced under)."""

    ops: dict[int, list]
    async_ops: dict[int, list]
    modules: dict[int, list]
    host: list

    def to_json(self, path: str) -> None:
        data = dataclasses.asdict(self)
        with gzip.open(path, "wt") as f:
            json.dump(data, f)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        return cls(**{k: ({int(d): v for d, v in data[k].items()} if k != "host" else data[k])
                      for k in ("ops", "async_ops", "modules", "host")})

    def window(self) -> tuple[float, float]:
        """(start_ns, end_ns) of the traced steps: from the first step
        program's start to the last one's end, over all devices. (The
        host's spans sit on the same clock only to about a millisecond.)"""
        starts = [m[0][0] for m in self.modules.values() if m]
        ends = [m[-1][0] + m[-1][1] for m in self.modules.values() if m]
        if not starts:
            raise ValueError("trace has no step program executions")
        return min(starts), max(ends)

    def attach_scopes(self, phases: list, scopes: dict) -> None:
        """Give each op the name scope of its instruction in the program
        of the step it ran in: ``scopes[phase][instruction name]``."""
        for dev, mods in self.modules.items():
            mods = mods[-len(phases):] if len(mods) >= len(phases) else mods
            for events in (self.ops.get(dev, []), self.async_ops.get(dev, [])):
                i = 0
                for ev in events:
                    while i < len(mods) and ev[0] >= mods[i][0] + mods[i][1]:
                        i += 1
                    if i < len(mods) and ev[0] >= mods[i][0]:
                        ev[3] = scopes.get(phases[i], {}).get(ev[2], "")


def _device_id(plane_name: str):
    m = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def _instruction(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` metadata, from a compiled program's
    HLO text."""
    return {name: scope for name, scope in re.findall(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"', hlo_text, re.M)}


def load(profile_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    tr = Trace(ops={}, async_ops={}, modules={}, host=[])
    lines = {"XLA Ops": tr.ops, "Async XLA Ops": tr.async_ops}
    for plane in data.planes:
        dev = _device_id(plane.name)
        if dev is None:
            if plane.name.startswith("/host:"):
                tr.host += [[ev.start_ns, ev.duration_ns, ev.name]
                            for line in plane.lines for ev in line.events
                            if ev.name in HOST_SPANS]
            continue
        for line in plane.lines:
            if line.name in lines:
                lines[line.name][dev] = sorted(
                    ([ev.start_ns, ev.duration_ns, _instruction(ev.name), ""]
                     for ev in line.events), key=lambda e: (e[0], -e[1]))
            elif line.name == "XLA Modules":
                tr.modules[dev] = sorted([ev.start_ns, ev.duration_ns, ev.name]
                                         for ev in line.events)
    tr.host.sort()
    return tr


# ---------------------------------------------------------------------------
# Shared reductions
# ---------------------------------------------------------------------------

def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``[start, start + dur]`` clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, d in sorted((iv[0], iv[1]) for iv in intervals):
        s, e = max(s, lo), min(s + d, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals ``(start, end)`` between the busy ones in [lo, hi]."""
    out, t = [], lo
    for s, d in sorted((iv[0], iv[1]) for iv in intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, s + d)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def in_window(events, lo: float, hi: float) -> list:
    return [e for e in events if e[0] >= lo and e[0] + e[1] <= hi]


def phase_modules(trace: Trace, device: int, phases: list, lo: float, hi: float):
    """The step program executions in the window, paired with the phases
    the benchmark dispatched, in order. Programs other than the step (a
    counter update, a copy) are the short ones and are dropped: the step
    programs are the ``len(phases)`` longest executions."""
    mods = in_window(trace.modules.get(device, []), lo, hi)
    if len(mods) < len(phases):
        return []
    longest = sorted(mods, key=lambda m: -m[1])[:len(phases)]
    steps = sorted(longest)
    return list(zip(phases, steps))


def scoped_ops(trace: Trace, device: int, pattern: str, lo: float, hi: float,
               async_ops: bool = False):
    """Ops in the window whose name scope matches ``pattern`` (a loop op
    and the ops of its body both match: take their union, not their sum)."""
    rx = re.compile(pattern)
    events = trace.ops.get(device, []) + (trace.async_ops.get(device, []) if async_ops else [])
    return [op for op in in_window(events, lo, hi) if rx.search(op[3])]


def leaf_ops(events) -> list:
    """The ops that contain no other op (a loop's body ops, not the loop):
    their durations add up without counting anything twice."""
    out = []
    for i, op in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt[0] >= op[0] + op[1]:
            out.append(op)
    return out


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reads: the trace of the traced window, the
    phase of each step dispatched in it, the window's bounds, the cell's
    configuration and traffic files, and the device's peaks."""

    trace: Trace
    phases: list
    lo: float
    hi: float
    cfg: dict
    traffic: dict
    peak: dict

    def devices(self) -> list[int]:
        return sorted(self.trace.ops)

    def per_device_mean(self, fn):
        """Mean over devices of ``fn(device)``, skipping devices where it
        finds nothing (None)."""
        vals = [v for v in (fn(d) for d in self.devices()) if v is not None]
        return sum(vals) / len(vals) if vals else None
