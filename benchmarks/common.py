"""Shared benchmark utilities."""

from __future__ import annotations

import time

import jax


def timeit(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time per call in microseconds (blocks on results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def timeit_stats(fn, *args, warmup: int = 2, iters: int = 5, bus=None,
                 name: str = "bench") -> dict:
    """Per-iteration timing through the ``repro.obs`` span layer.

    Each iteration runs inside a ``span`` (device completion blocked inside
    the clock), so BENCH snapshots and run telemetry share one schema: the
    returned ``median_us``/``p50_us``/``p95_us`` come from the same span
    records a training run would emit. Pass ``bus`` to forward the
    per-iteration span records to an external sink (the optional telemetry
    pass-through); by default they stay in-memory.
    """
    from repro.obs import Bus, MemorySink
    from repro.obs.spans import percentiles, span

    mem = MemorySink()
    local = Bus([mem])
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    for i in range(iters):
        with span(local, name, iter=i):
            jax.block_until_ready(fn(*args))
    if bus is not None:
        for r in mem.records:
            bus.emit(r)
    durs = sorted(r["dur_s"] for r in mem.records)
    pcts = percentiles(durs, (50, 95))
    return {
        "median_us": durs[len(durs) // 2] * 1e6,
        "p50_us": pcts["p50"] * 1e6,
        "p95_us": pcts["p95"] * 1e6,
    }


def one_device_engine(params):
    """shard_map engine over a 1-device ('data','model') mesh.

    Every gather is a no-op (axis size 1), so a staggered-schedule
    optimizer built on it is numerically an A/B of the *schedule* alone —
    exactly what the loss benchmarks need to compare synchronous vs
    staggered at matched periods and stepsizes.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed import make_engine
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    pspecs = jax.tree.map(lambda p: P(*([None] * p.ndim)), params)
    return make_engine(params, pspecs, mesh)


COLUMNS = (
    "name", "us_per_call", "derived", "backend", "bucketing",
    "engine", "predicted_bytes", "measured_collectives", "schedule",
    "p50_us", "p95_us",
)


def row(
    name: str, us: float, derived: str, backend: str = "-", bucketing: str = "-",
    engine: str = "-", predicted_bytes: str = "-", measured_collectives: str = "-",
    schedule: str = "-", p50_us: str = "-", p95_us: str = "-",
) -> str:
    """CSV row; ``backend``/``bucketing`` identify the NS engine variant
    measured ("jnp"/"pallas", "on"/"off"); ``engine`` names the optimizer
    comm engine ("gspmd"/"shard_map"); ``predicted_bytes`` is the CommPlan
    prediction and ``measured_collectives`` the post-SPMD HLO count for the
    same compile; ``schedule`` names the engine full-step schedule
    ("barrier"/"pipelined"); ``p50_us``/``p95_us`` are span-layer
    percentiles (``timeit_stats``) — "-" where not applicable."""
    return (
        f"{name},{us:.1f},{derived},{backend},{bucketing},"
        f"{engine},{predicted_bytes},{measured_collectives},{schedule},"
        f"{p50_us},{p95_us}"
    )
