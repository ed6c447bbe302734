"""Span-based timing: host-side timers that also mark the profiler trace.

:func:`span` is a host-side context manager timing step / phase /
checkpoint-save / resume / data regions with ``time.perf_counter``. Spans
nest via a thread-local stack; a child records its parent's name so
``scripts/obs_report.py`` can attribute e.g. ``checkpoint.save`` time
inside a ``step`` span. Each span emits one ``{"event": "span"}`` record
on exit, and holds a ``jax.profiler.TraceAnnotation`` of its name open
while it times, so a ``--profile-steps`` capture shows the host's spans
beside the device ops on the trace's clock (with no trace running the
annotation records nothing). Host wall times include device time only up
to dispatch — pass a ``sync`` callable (e.g. ``jax.block_until_ready``
over the step outputs, ``train.py --obs-block``) when accurate per-step
device wall times are wanted; by default nothing is synchronized and
instrumentation adds no device round-trips.

Device-side names are ``jax.named_scope`` calls in the program itself: the
model's layers (``models/transformer.py``), the optimizer apply
(``training/train_step.py``) and the comm engine's stages
(``distributed/engine.py``); docs/observability.md lists them.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator

import jax

from repro.obs import bus as bus_lib

_local = threading.local()


def _stack() -> list["Span"]:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


def current_span() -> "Span | None":
    stack = _stack()
    return stack[-1] if stack else None


@dataclass
class Span:
    """One timed region; ``dur_s`` is populated when the context exits."""

    name: str
    attrs: dict[str, Any] = field(default_factory=dict)
    parent: "Span | None" = None
    dur_s: float | None = None

    def set(self, **attrs: Any) -> None:
        """Attach attributes after entry (e.g. the phase chosen mid-step)."""
        self.attrs.update(attrs)


@contextlib.contextmanager
def span(
    bus: bus_lib.Bus | None,
    name: str,
    sync: Callable[[], Any] | None = None,
    **attrs: Any,
) -> Iterator[Span]:
    """Time a region, mark it in the profiler trace, and emit a ``span``
    record on exit.

    ``sync`` (if given) runs inside the timed region just before the clock
    stops — the hook for ``jax.block_until_ready`` when the caller wants
    device completion included. The emitted record is
    ``{"event": "span", "name": ..., "dur_s": ..., **attrs}`` plus
    ``"parent"`` when nested.
    """
    sp = Span(name=name, attrs=dict(attrs), parent=current_span())
    _stack().append(sp)
    try:
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield sp
            finally:
                if sync is not None:
                    sync()
                sp.dur_s = time.perf_counter() - t0
    finally:
        _stack().pop()
        if bus is not None:
            rec: dict[str, Any] = {"event": "span", "name": name, "dur_s": round(sp.dur_s, 6)}
            if sp.parent is not None:
                rec["parent"] = sp.parent.name
            rec.update(sp.attrs)
            bus.emit(rec)


def record_span(bus: bus_lib.Bus | None, name: str, dur_s: float, **attrs: Any) -> None:
    """Emit a span record for a duration measured elsewhere (e.g. dryrun's
    lower/compile timings, which are produced by library code)."""
    if bus is None:
        return
    bus.emit({"event": "span", "name": name, "dur_s": round(float(dur_s), 6), **attrs})


def parse_profile_window(spec: str) -> tuple[int, int]:
    """Parse ``--profile-steps A:B`` into an inclusive-exclusive window."""
    try:
        a_s, b_s = spec.split(":")
        a, b = int(a_s), int(b_s)
    except ValueError:
        raise ValueError(f"--profile-steps expects A:B (got {spec!r})") from None
    if a < 0 or b <= a:
        raise ValueError(f"--profile-steps window must satisfy 0 <= A < B (got {spec!r})")
    return a, b


def percentiles(values, qs=(50, 95, 99)) -> dict[str, float]:
    """Nearest-rank percentiles, keyed ``p50``/``p95``/... Empty input → {}."""
    import math

    vals = sorted(float(v) for v in values)
    if not vals:
        return {}
    out = {}
    for q in qs:
        idx = min(len(vals) - 1, max(0, math.ceil(q / 100.0 * len(vals)) - 1))
        out[f"p{q}"] = vals[idx]
    return out
