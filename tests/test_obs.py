"""Tests for the observability subsystem (repro.obs).

Covers: sink durability (fsync'd append JSONL, torn-final-line recovery,
reopen-append), stdout wire-format compatibility, span nesting and
attribution, the plan-vs-runtime drift detector (fires on synthetic rate
mismatch, silent on plan-exact timings), schema validation, and — the
acceptance-critical one — that bus instrumentation with counters only
leaves optimizer steps BITWISE-identical and never syncs the hot path
(fast 1-device check in-process; 8-device engine run in a slow
subprocess)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adamw, combine, label_tree, muon
from repro.core.combine import apply_updates
from repro.kernels import dispatch
from repro.obs import (
    Bus,
    DriftConfig,
    DriftMonitor,
    JsonlSink,
    MemorySink,
    QUIET_EVENTS,
    StdoutSink,
    event_type,
    exposed_by_link,
    set_bus,
    span,
    validate_record,
)
from repro.obs.bus import read_jsonl
from repro.obs.spans import current_span, parse_profile_window, percentiles


# ---------------------------------------------------------------------------
# Bus + sinks
# ---------------------------------------------------------------------------

def test_jsonl_sink_appends_and_fsyncs_each_record(tmp_path):
    path = str(tmp_path / "t.jsonl")
    sink = JsonlSink(path)
    sink.emit({"event": "checkpoint", "step": 1, "path": "/x"})
    # Durable BEFORE close: a SIGKILL now must not lose the record.
    on_disk = read_jsonl(path)
    assert len(on_disk) == 1 and on_disk[0]["step"] == 1
    assert "ts" in on_disk[0]
    sink.emit({"step": 2, "loss": 1.5, "phase": "block"})
    sink.close()
    assert len(read_jsonl(path)) == 2


def test_jsonl_sink_reopen_appends(tmp_path):
    path = str(tmp_path / "t.jsonl")
    s1 = JsonlSink(path)
    s1.emit({"event": "resume", "step": 0, "snapshot": None})
    s1.close()
    s2 = JsonlSink(path)  # a resumed launch extends the same trail
    s2.emit({"event": "resume", "step": 5, "snapshot": "/snap"})
    s2.close()
    recs = read_jsonl(path)
    assert [r["step"] for r in recs] == [0, 5]


def test_read_jsonl_tolerates_exactly_one_torn_final_line(tmp_path):
    path = str(tmp_path / "t.jsonl")
    sink = JsonlSink(path)
    for i in range(3):
        sink.emit({"step": i, "loss": 1.0, "phase": "block"})
    sink.close()
    # Simulate a SIGKILL mid-write: truncate into the last record.
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 7)
    torn = []
    recs = read_jsonl(path, on_torn=lambda n, line: torn.append(n))
    assert [r["step"] for r in recs] == [0, 1]
    assert len(torn) == 1


def test_read_jsonl_rejects_midfile_corruption(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write('{"step": 0, "loss": 1.0}\n')
        f.write('{"step": 1, "lo\n')  # torn NOT at the end: corruption
        f.write('{"step": 2, "loss": 1.0}\n')
    with pytest.raises(ValueError, match="mid-file"):
        read_jsonl(path)


def test_stdout_sink_wire_format_and_quiet_events(capsys):
    sink = StdoutSink()
    rec = {"event": "checkpoint", "step": 3, "path": "/snap/step_3"}
    sink.emit(rec)
    sink.emit({"event": "span", "name": "step", "dur_s": 0.1})  # quiet
    sink.emit({"step": 3, "loss": 2.5, "phase": "full"})
    out = capsys.readouterr().out.splitlines()
    # Byte-identical to the legacy print(json.dumps(...)) lines.
    assert out[0] == json.dumps(rec)
    assert out[1] == json.dumps({"step": 3, "loss": 2.5, "phase": "full"})
    assert len(out) == 2
    assert "span" in QUIET_EVENTS and "run_start" in QUIET_EVENTS


def test_bus_sink_order_and_counters(tmp_path, capsys):
    path = str(tmp_path / "t.jsonl")
    bus = Bus([JsonlSink(path), StdoutSink()])
    bus.event("resume", step=0, snapshot=None)
    bus.inc("guard.skipped_steps")
    bus.inc("guard.skipped_steps", 2)
    assert bus.counters == {"guard.skipped_steps": 3}
    # Everything stdout showed is already on disk (JSONL sink runs first).
    stdout_lines = [l for l in capsys.readouterr().out.splitlines()
                    if l.startswith("{")]
    disk = read_jsonl(path)
    assert len(stdout_lines) == 1 and len(disk) == 1
    assert json.loads(stdout_lines[0])["event"] == "resume"
    bus.close()


def test_event_type_and_schema_validation():
    assert event_type({"event": "drift", "step": 1}) == "drift"
    assert event_type({"step": 1, "loss": 2.0}) == "step"
    assert event_type({"foo": 1}) is None
    ok = {"event": "checkpoint", "step": 1, "path": "/x"}
    assert validate_record(ok) == []
    assert validate_record({"event": "checkpoint", "step": 1})  # missing path
    assert validate_record({"event": "not_a_thing"})  # unknown type
    assert validate_record({"foo": 1})  # unrecognized shape


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_attribution():
    mem = MemorySink()
    bus = Bus([mem])
    with span(bus, "step", step=7, phase="full") as outer:
        assert current_span() is outer
        with span(bus, "checkpoint.save", step=7):
            pass
    assert current_span() is None
    assert outer.dur_s is not None and outer.dur_s >= 0
    inner_rec, outer_rec = mem.records  # inner exits (and emits) first
    assert inner_rec["name"] == "checkpoint.save"
    assert inner_rec["parent"] == "step"
    assert outer_rec["name"] == "step"
    assert "parent" not in outer_rec
    assert outer_rec["step"] == 7 and outer_rec["phase"] == "full"
    assert outer_rec["dur_s"] >= inner_rec["dur_s"]


def test_span_sync_runs_inside_clock():
    calls = []
    with span(None, "step", sync=lambda: calls.append(1)) as sp:
        pass
    assert calls == [1] and sp.dur_s is not None


@pytest.fixture
def annotations(monkeypatch):
    """Names passed to ``jax.profiler.TraceAnnotation``, in order."""
    names = []

    class Record:
        def __init__(self, name, **kwargs):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Record)
    return names


def test_span_marks_the_profiler_trace(annotations):
    with span(None, "step", step=0):
        with span(None, "checkpoint.save"):
            pass
    assert annotations == ["step", "checkpoint.save"]


def test_launcher_annotates_step_and_data_spans(annotations, monkeypatch, tmp_path):
    """A 2-step CPU run of the launcher writes its ``step`` spans and the
    ``data`` spans around each batch's preparation to the profiler trace."""
    from repro.launch import compile_cache, train

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    run = train.main(["--arch", "muonbp-960m", "--reduced", "--steps", "2", "--batch", "2",
                      "--seq", "32", "--optimizer", "muonbp", "--period", "2",
                      "--drift-threshold", "0"])
    assert run.status == "ok"
    assert annotations.count("step") == 2 and annotations.count("data") == 2
    assert annotations.index("data") < annotations.index("step")


def test_percentiles_nearest_rank():
    vals = list(range(1, 101))  # 1..100
    p = percentiles(vals)
    assert p["p50"] == 50 and p["p95"] == 95 and p["p99"] == 99
    assert percentiles([]) == {}
    assert percentiles([42.0]) == {"p50": 42.0, "p95": 42.0, "p99": 42.0}


def test_parse_profile_window():
    assert parse_profile_window("3:6") == (3, 6)
    with pytest.raises(ValueError):
        parse_profile_window("6:3")
    with pytest.raises(ValueError):
        parse_profile_window("abc")


# ---------------------------------------------------------------------------
# Drift monitor
# ---------------------------------------------------------------------------

RATE = 100e6  # 100 MB/s synthetic link
BYTES = {"ici": 50 * 2 ** 20}  # -> modeled extra = 0.524s


def _feed(mon, full_extra_s, n=6, base=0.10):
    for i in range(n):
        mon.observe(2 * i, "block", base)
        mon.observe(2 * i + 1, "full", base + full_extra_s)


def test_drift_silent_on_plan_exact_timings():
    bus = Bus([MemorySink()])
    mon = DriftMonitor(comm_bytes_by_link=BYTES, rates={"ici": RATE},
                       cfg=DriftConfig(), bus=bus)
    _feed(mon, mon.modeled_extra_s)  # measured == modeled exactly
    assert mon.drift_events == 0
    rep = mon.report()
    # Achieved rate reproduces the modeled constant (EMAs converge exactly
    # on constant inputs).
    assert rep["achieved_bytes_per_s"]["ici"] == pytest.approx(RATE, rel=0.01)
    assert rep["drift_events"] == 0


def test_drift_fires_on_rate_mismatch():
    mem = MemorySink()
    bus = Bus([mem])
    mon = DriftMonitor(comm_bytes_by_link=BYTES, rates={"ici": RATE},
                       cfg=DriftConfig(threshold=2.0), bus=bus)
    _feed(mon, 10 * mon.modeled_extra_s)  # link 10x slower than modeled
    assert mon.drift_events >= 1
    drifts = [r for r in mem.records if r.get("event") == "drift"]
    assert drifts and drifts[0]["ratio"] > 2.0
    # Achieved rate ~ RATE/10, reported per link.
    assert drifts[0]["achieved_bytes_per_s"]["ici"] < RATE / 5
    # Cooldown: persistent drift must not fire every full step.
    assert mon.drift_events < mon.full_n


def test_drift_fires_on_faster_than_modeled_too():
    mon = DriftMonitor(comm_bytes_by_link=BYTES, rates={"ici": RATE},
                       cfg=DriftConfig(threshold=2.0))
    _feed(mon, mon.modeled_extra_s / 10)  # comm mostly hidden / link faster
    assert mon.drift_events >= 1


def test_drift_silent_with_zero_planned_bytes():
    # The 1-device CI case: no full-step comm delta -> nothing to judge.
    mon = DriftMonitor(comm_bytes_by_link={"ici": 0, "dcn": 0},
                       rates={"ici": RATE, "dcn": RATE})
    _feed(mon, 0.5)  # even a huge full-step delta is not drift
    assert mon.drift_events == 0
    rep = mon.report()
    assert rep["achieved_bytes_per_s"] == {}


def test_drift_respects_warmup():
    mon = DriftMonitor(comm_bytes_by_link=BYTES, rates={"ici": RATE},
                       cfg=DriftConfig(warmup=3))
    mon.observe(0, "block", 0.1)
    mon.observe(1, "full", 0.1 + 10 * mon.modeled_extra_s)
    assert mon.drift_events == 0  # one obs each < warmup


def test_exposed_by_link_from_schedule():
    class FakeSchedule:
        exposed_bytes = 1000
        exposed_dcn_bytes = 300

    assert exposed_by_link(FakeSchedule()) == {"ici": 700, "dcn": 300}


# ---------------------------------------------------------------------------
# Bitwise parity: instrumentation must not perturb or sync the hot path
# ---------------------------------------------------------------------------

def _tiny_setup():
    key = jax.random.PRNGKey(0)
    params = {
        "w": jax.random.normal(key, (4, 16, 16)),
        "bias": jax.random.normal(key, (16,)),
    }
    labels = label_tree(params)
    opt = combine({"muon": muon(1e-2, 1e-2, period=2), "adamw": adamw(1e-3)},
                  labels)
    grads = jax.tree.map(lambda p: 0.1 * jnp.ones_like(p), params)
    return params, opt, grads


def _make_step(opt):
    import functools

    @functools.partial(jax.jit, static_argnames=("phase",))
    def step(g, s, p, phase):
        u, ns = opt.update(g, s, p, phase)
        return apply_updates(p, u), ns

    return step


def _run_steps(params, opt, grads, step, steps=4, bus=None):
    state = opt.init(params)
    for i in range(steps):
        phase = "full" if i % 2 == 0 else "block"
        if bus is not None:
            with span(bus, "step", step=i, phase=phase):
                params, state = step(grads, state, params, phase)
            bus.inc("steps")
        else:
            params, state = step(grads, state, params, phase)
    return params, state


def test_instrumented_steps_bitwise_identical_no_sync(monkeypatch, tmp_path):
    """Counters + spans + the NS launch hook leave the update bitwise
    unchanged AND never call device_get/block_until_ready on the hot path
    (guarded by raising patches during the instrumented executed steps)."""
    params, opt, grads = _tiny_setup()
    step = _make_step(opt)
    p_ref, s_ref = _run_steps(params, opt, grads, step)  # uninstrumented

    launches = []
    mem = MemorySink()
    bus = Bus([mem, JsonlSink(str(tmp_path / "t.jsonl"))])
    dispatch.set_launch_hook(
        lambda backend, strategy, shape: launches.append((backend, shape)))
    try:
        # Fresh jit wrapper so the instrumented path retraces with the
        # launch hook installed; the warmup compiles both phases BEFORE
        # the sync guards go in (tracing may legitimately inspect values).
        step_obs = _make_step(opt)
        _run_steps(params, opt, grads, step_obs, steps=2, bus=bus)

        def _banned(*a, **k):
            raise AssertionError("obs instrumentation synced the hot path")

        monkeypatch.setattr(jax, "device_get", _banned)
        monkeypatch.setattr(jax, "block_until_ready", _banned)
        p_obs, s_obs = _run_steps(params, opt, grads, step_obs, bus=bus)
    finally:
        dispatch.set_launch_hook(None)
        monkeypatch.undo()

    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_obs)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_ref), jax.tree.leaves(s_obs)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # The hook fired at trace time and counted per-backend launches.
    assert launches and all(b == "jnp" for b, _ in launches)
    assert bus.counters["steps"] == 6  # 2 warmup + 4 measured
    step_spans = [r for r in mem.records if r.get("name") == "step"]
    assert len(step_spans) == 6  # 2 warmup + 4 measured
    assert {r["phase"] for r in step_spans} == {"block", "full"}


def test_null_bus_swallows_everything(capsys):
    prev = set_bus(None)
    try:
        from repro.obs import get_bus

        get_bus().event("checkpoint", step=1, path="/x")
        get_bus().inc("n")
        assert capsys.readouterr().out == ""
    finally:
        set_bus(prev)


# ---------------------------------------------------------------------------
# 8-device subprocess: engine-path bitwise parity with instrumentation on
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import adamw, combine, label_tree, muon
from repro.core.blocking import BlockSpec2D
from repro.core.combine import apply_updates
from repro.distributed import make_engine
from repro.kernels import dispatch
from repro.obs import Bus, JsonlSink, MemorySink, span

mesh = make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
params = {
    "stack_col": jax.random.normal(key, (8, 16, 32)),
    "stack_row": jax.random.normal(key, (8, 32, 16)),
    "bias": jax.random.normal(key, (32,)),
}
pspecs = {
    "stack_col": P(None, None, "model"),
    "stack_row": P(None, "model", None),
    "bias": P(None),
}
params = jax.tree.map(
    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, pspecs)
labels = label_tree(params)
bspecs = {"stack_col": BlockSpec2D(1, 4), "stack_row": BlockSpec2D(4, 1), "bias": None}
bspecs = jax.tree.map(lambda l, b: b if l == "muon" else None, labels, bspecs,
                      is_leaf=lambda x: x is None or isinstance(x, BlockSpec2D))
comm = make_engine(params, pspecs, mesh, zero1=True)
opt = combine({"muon": muon(1e-2, 1e-2, period=2, block_specs=bspecs, comm=comm),
               "adamw": adamw(1e-3)}, labels)
grads = jax.tree.map(lambda p: 0.1 * jnp.ones_like(p), params)

def run(bus):
    import functools
    from repro.distributed import zero1 as z1
    state = opt.init(params)
    state = z1.shard_state(state, params, mesh, pspecs=pspecs)
    p = params

    @functools.partial(jax.jit, static_argnames=("phase",))
    def step(g, s, pp, phase):
        u, ns = opt.update(g, s, pp, phase)
        return apply_updates(pp, u), ns

    for i in range(4):
        phase = "full" if i % 2 == 0 else "block"
        if bus is not None:
            with span(bus, "step", step=i, phase=phase):
                p, state = step(grads, state, p, phase)
            bus.inc("steps")
        else:
            p, state = step(grads, state, p, phase)
    return p, state

p_ref, s_ref = run(None)
mem = MemorySink()
bus = Bus([mem, JsonlSink("/tmp/repro_obs_test/sub.jsonl")])
dispatch.set_launch_hook(lambda b, s, sh: bus.inc(f"ns_launch.{b}.{s or 'auto'}"))
p_obs, s_obs = run(bus)
dispatch.set_launch_hook(None)

out = {
    "params_equal": all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_obs))),
    "opt_equal": all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(s_ref), jax.tree.leaves(s_obs))),
    "n_step_spans": sum(1 for r in mem.records if r.get("name") == "step"),
    "counters": bus.counters,
}
print("RESULT " + json.dumps(out))
"""


# slow: spawns an 8-forced-device subprocess compiling the engine programs.
@pytest.fixture(scope="module")
def obs_dist_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_instrumented_engine_steps_bitwise_identical(obs_dist_result):
    """Bus + spans + launch counters around shard_map-engine steps on the
    2x4 mesh (ZeRO-1, pipelined full schedule) change NOTHING: params and
    optimizer state bitwise-equal to the uninstrumented run."""
    r = obs_dist_result
    assert r["params_equal"], r
    assert r["opt_equal"], r
    assert r["n_step_spans"] == 4, r
    assert r["counters"]["steps"] == 4, r
    assert any(k.startswith("ns_launch.") for k in r["counters"]), r
