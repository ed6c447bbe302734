"""Structured observability: event bus, span timing, plan-vs-runtime drift.

Three small modules, threaded through the launchers, the distributed
engine, and the benchmarks:

* :mod:`repro.obs.bus` — the event/metric bus. Every telemetry record is
  one flat JSON object; sinks decide where it goes (crash-safe append-mode
  JSONL, stdout in the legacy ``{"event": ...}`` wire format, an in-memory
  list for tests). Counters (guard skips, escalations, checkpoint
  fallbacks, NS kernel launches) accumulate on the bus and ride out in the
  ``run_end`` record.
* :mod:`repro.obs.spans` — host-side span timers (step / data /
  checkpoint-save / resume, nested with parent attribution), each also a
  ``jax.profiler.TraceAnnotation``, so a captured profiler trace shows
  them beside the device ops (whose layer and stage names are
  ``jax.named_scope`` calls in the program: docs/observability.md).
* :mod:`repro.obs.drift` — the plan-vs-runtime drift monitor: joins
  ``CommPlan.predicted_by_link`` (and, when available, the pipeline
  schedule's exposed bytes) against measured block/full step wall times,
  derives achieved bytes/s per link class, and emits a ``drift`` event
  when the modeled rate constants (``plan.MODELED_LINK_BYTES_PER_S``)
  disagree with observation beyond a threshold.
  :class:`~repro.obs.drift.ResidueDriftMonitor` is the staggered-schedule
  variant: per-residue wall EMAs checked against the plan's per-residue
  byte bills (the full-minus-block delta the synchronous monitor prices
  does not exist under ``--full-schedule staggered``).

``scripts/obs_report.py`` aggregates a run's JSONL into percentiles,
per-phase breakdowns, comm-rate summaries, and an incident timeline.
Schema + flag documentation: docs/observability.md.
"""

from repro.obs.bus import (  # noqa: F401
    Bus,
    EVENT_FIELDS,
    JsonlSink,
    MemorySink,
    QUIET_EVENTS,
    StdoutSink,
    event_type,
    get_bus,
    set_bus,
    validate_record,
)
from repro.obs.drift import (  # noqa: F401
    DriftConfig,
    DriftMonitor,
    ResidueDriftMonitor,
    exposed_by_link,
)
from repro.obs.spans import (  # noqa: F401
    Span,
    percentiles,
    span,
)
