"""The layer readers (``attn_ms``, ``mlp_ms``, ``head_ms``, ``opt_ms``,
``other_ms``): the name forms a scope takes in ``op_name``, loops against
their bodies, disjointness and coverage, on hand-made events."""

import json
import re
from pathlib import Path

import pytest

from bench import layer_scopes, run, trace

ROOT = Path(__file__).resolve().parents[2]
LAYER_METRICS = ("attn_ms", "mlp_ms", "head_ms", "opt_ms", "other_ms")
J = "jit(train_step)"
SCAN = f"{J}/transpose(jvp())/while/body/closed_call"


def _reading(op_list, modules, phases, hi=2000):
    tr = trace.Trace(ops={0: sorted(op_list, key=lambda e: (e[0], -e[1]))},
                     async_ops={0: []}, modules={0: modules},
                     host=[[0, hi, "traced_window"]])
    cfg = json.loads((ROOT / "bench" / "configs" / "phi4-mini.1chip.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / "muonbp_p5.b1s4096.json").read_text())
    return trace.Reading(trace=tr, phases=phases, lo=0, hi=hi, cfg=cfg,
                         traffic=traffic, peak=run.load_peak("TPU v5 lite"))


# One step program from 0 to 1000 ns: every scope in each name form, a loop
# op spanning its body, an op with an empty scope and the scan's slicing.
STEP = [
    [0, 40, "fusion.1", f"{J}/jvp(model.embed)/jit(_take)/gather"],
    # The layer scan (unclaimed loop op) and its body.
    [40, 500, "while.2", f"{J}/jvp()/while"],
    [40, 10, "dynamic-slice.3", f"{J}/jvp()/while/body/dynamic_slice"],
    [50, 100, "fusion.4", f"{J}/jvp()/while/body/closed_call/model.attn/dot_general"],
    # The flash-attention loop (claimed) and its body ops: union, not sum.
    [150, 80, "while.5", f"{SCAN}/checkpoint/rematted_computation/model.attn/while"],
    [150, 30, "fusion.6",
     f"{SCAN}/checkpoint/rematted_computation/model.attn/while/body/closed_call/dot_general"],
    [180, 50, "fusion.7", f"{SCAN}/checkpoint/model.attn/while/body/closed_call/mul"],
    [230, 70, "fusion.8", f"{SCAN}/checkpoint/model.mlp/dot_general"],
    [300, 20, "fusion.9", f"{SCAN}/checkpoint/rematted_computation/model.mlp/jit(silu)/mul"],
    [320, 20, "dynamic-update-slice.10", f"{SCAN}/dynamic_update_slice"],
    [340, 200, "fusion.11", f"{J}/transpose(jvp(model.head))/dot_general"],
    [540, 30, "copy.12", ""],
    [570, 50, "fusion.13", f"{J}/transpose(jvp(model.embed))/scatter-add"],
    [620, 300, "fusion.14", f"{J}/opt/muonbp.block.ns/dot_general"],
    [920, 40, "fusion.15", f"{J}/opt/add"],
    [960, 40, "fusion.16", f"{J}/reduce_sum"],
]
EXPECT_NS = {"attn_ms": 100 + 80, "mlp_ms": 70 + 20, "head_ms": 40 + 200 + 50,
             "opt_ms": 300 + 40, "other_ms": 10 + 20 + 30 + 40}


def test_layer_readers_on_hand_made_events():
    r = _reading(STEP, [[0, 1000, "jit_train_step"]], ["block"])
    for name, ns in EXPECT_NS.items():
        assert run.load_metric(name).read(r) == pytest.approx(ns / 1e6), name


def test_layers_are_disjoint_and_add_up_to_the_step():
    """Two steps, each per-step value the mean of the two; the five add up
    to the step programs' busy time, which here is the whole step."""
    second = [[s + 1000, d, n, sc] for s, d, n, sc in STEP]
    r = _reading(STEP + second, [[0, 1000, "jit_train_step"], [1000, 1000, "jit_train_step"]],
                 ["full", "block"])
    values = {name: run.load_metric(name).read(r) for name in LAYER_METRICS}
    for name, ns in EXPECT_NS.items():
        assert values[name] == pytest.approx(ns / 1e6), name
    step = (run.load_metric("step_ms.full").read(r) + run.load_metric("step_ms.block").read(r)) / 2
    assert sum(values.values()) == pytest.approx(step)


def test_unclaimed_op_inside_a_claimed_loop_is_counted_once():
    ops = [[0, 100, "while.1", f"{J}/opt/while"],
           [10, 30, "copy.2", ""],
           [40, 60, "fusion.3", f"{J}/opt/while/body/mul"],
           [100, 50, "copy.4", ""]]
    r = _reading(ops, [[0, 150, "jit_train_step"]], ["block"], hi=150)
    assert run.load_metric("opt_ms").read(r) == pytest.approx(100 / 1e6)
    assert run.load_metric("other_ms").read(r) == pytest.approx(50 / 1e6)


def test_other_ms_leaves_out_ops_between_step_programs():
    ops = [[0, 100, "fusion.1", f"{J}/opt/add"], [110, 20, "copy.2", ""],
           [200, 100, "fusion.3", f"{J}/opt/add"]]
    r = _reading(ops, [[0, 100, "jit_train_step"], [105, 30, "jit_counter"],
                       [200, 100, "jit_train_step"]], ["block", "block"], hi=300)
    assert run.load_metric("other_ms").read(r) == 0.0


@pytest.mark.parametrize("name", LAYER_METRICS)
def test_layer_readers_find_nothing_where_the_program_names_no_layer(name):
    """A program that does not name its layers reports no layer metric."""
    r = _reading([[0, 100, "fusion.1", f"{J}/transpose(jvp())/dot_general"],
                  [100, 100, "fusion.2", f"{J}/muonbp.block.ns/dot_general"],
                  [200, 10, "copy.3", ""]],
                 [[0, 210, "jit_train_step"]], ["block"], hi=210)
    assert run.load_metric(name).read(r) is None


@pytest.mark.parametrize("name,scope", [
    ("attn_ms", "model.attn"), ("mlp_ms", "model.mlp"), ("head_ms", "model.embed"),
    ("head_ms", "model.head"), ("opt_ms", "opt")])
def test_scope_matches_only_whole_components(name, scope):
    rx = layer_scopes.pattern(layer_scopes.LAYERS[name])
    for form in (f"{J}/{scope}/x", f"{J}/jvp({scope})/x", f"{J}/transpose(jvp({scope}))/x",
                 f"{SCAN}/checkpoint/rematted_computation/{scope}/x", f"{J}/a/{scope}"):
        assert re.search(rx, form), form
    for near in (f"{J}/{scope}x/y", f"{J}/x{scope}/y", f"{J}/jvp({scope}.s0)/y"):
        assert not re.search(rx, near), near


def test_unscoped_recorded_trace_reports_no_layer():
    """The trace recorded before the program named its layers: the layer
    readers find nothing and raise nothing there."""
    tr = trace.Trace.from_json(
        str(ROOT / "bench" / "testdata" / "phi4-mini.1chip.muonbp.trace.json.gz"))
    lo, hi = tr.window()
    r = trace.Reading(trace=tr, phases=["full"] + ["block"] * 4 + ["full"] + ["block"] * 4,
                      lo=lo, hi=hi, cfg={}, traffic={}, peak={})
    for name in LAYER_METRICS:
        assert run.load_metric(name).read(r) is None, name


def test_readers_on_a_recorded_scoped_chip_trace():
    """Two periods of phi4-mini.1chip.muonbp traced on a v5e with the
    program naming its layers: every reader gives what the chip run
    reported, the five layers add up to the step, and the optimizer's time
    holds the NS."""
    data = ROOT / "bench" / "testdata"
    expect = json.loads((data / "phi4-mini.1chip.muonbp.scoped.trace.expect.json").read_text())
    tr = trace.Trace.from_json(str(data / "phi4-mini.1chip.muonbp.scoped.trace.json.gz"))
    lo, hi = tr.window()
    cfg = json.loads((ROOT / "bench" / "configs" / "phi4-mini.1chip.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / "muonbp_p5.b1s4096.json").read_text())
    r = trace.Reading(trace=tr, phases=expect["phases"], lo=lo, hi=hi, cfg=cfg,
                      traffic=traffic, peak=run.load_peak("TPU v5 lite"))
    values = {name: run.load_metric(name).read(r) for name in expect["metrics"]}
    for name, value in expect["metrics"].items():
        assert values[name] == pytest.approx(value, rel=1e-9), name
    assert set(LAYER_METRICS) <= set(values)
    assert (hi - lo) / 1e9 == pytest.approx(expect["window_s"], rel=1e-9)
    n_full = expect["phases"].count("full")
    step = (n_full * values["step_ms.full"]
            + (len(expect["phases"]) - n_full) * values["step_ms.block"]) / len(expect["phases"])
    assert sum(values[m] for m in LAYER_METRICS) == pytest.approx(step, rel=0.02)
    assert values["opt_ms"] >= values["ns_ms"]
    assert all(values[m] > 0 for m in ("attn_ms", "mlp_ms", "head_ms", "opt_ms"))
