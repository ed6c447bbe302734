"""The trace reduction: interval arithmetic on hand-made events, and every
per-layer reader on a small trace recorded on a TPU v5e."""

import json
from pathlib import Path

import pytest

from bench import run, trace

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "bench" / "testdata"


def test_union_and_gaps():
    ops = [[0, 10], [5, 10], [30, 5], [50, 100]]
    assert trace.union_ns(ops, 0, 100) == 15 + 5 + 50
    assert trace.gaps(ops, 0, 100) == [(15, 30), (35, 50)]
    assert trace.union_ns(ops, 8, 12) == 4


def _reading(op_list, modules, phases):
    tr = trace.Trace(ops={0: op_list}, async_ops={0: []}, modules={0: modules},
                     host=[[0, 1000, "traced_window"]])
    cfg = json.loads((ROOT / "bench" / "configs" / "phi4-mini.1chip.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / "muonbp_p5.b1s4096.json").read_text())
    return trace.Reading(trace=tr, phases=phases, lo=0, hi=1000, cfg=cfg,
                         traffic=traffic, peak=run.load_peak("TPU v5 lite"))


def test_readers_on_hand_made_events():
    ops = [[0, 100, "fusion.1", "jit(train_step)/muonbp.full.s0.ns/dot_general"],
           [100, 50, "all-gather.2", "jit(train_step)/muonbp.full.s1.gather/all_gather"],
           [120, 10, "fusion.3", "jit(train_step)/muonbp.full.s0.ns/mul"],
           [200, 300, "fusion.4", "jit(train_step)/transpose(jvp(forward))/dot_general"],
           [600, 100, "fusion.5", "jit(train_step)/muonbp.block.ns/dot_general"]]
    modules = [[0, 500, "jit_train_step"], [550, 300, "jit_train_step"]]
    r = _reading(ops, modules, ["full", "block"])
    assert run.load_metric("step_ms.full").read(r) == pytest.approx(500 / 1e6)
    assert run.load_metric("step_ms.block").read(r) == pytest.approx(300 / 1e6)
    assert run.load_metric("ns_ms").read(r) == pytest.approx(210 / 2 / 1e6)
    assert run.load_metric("gather_ms.full").read(r) == pytest.approx(50 / 1e6)
    # 10 of the gather's 50 ns overlap the ns op at 120-130.
    assert run.load_metric("gather_exposed_ms.full").read(r) == pytest.approx(40 / 1e6)
    busy = 100 + 50 + 300 + 100
    assert run.load_metric("idle_share").read(r) == pytest.approx(100 * (1 - busy / 1000))


def test_readers_find_nothing_where_nothing_is():
    r = _reading([[0, 100, "fusion.1", "jit(train_step)/dot_general"]],
                 [[0, 100, "jit_train_step"]], ["block"])
    for name in ("ns_ms", "ns_roofline", "gather_ms.full", "gather_exposed_ms.full",
                 "step_ms.full"):
        assert run.load_metric(name).read(r) is None, name


def test_readers_on_a_recorded_chip_trace():
    """Two periods of phi4-mini.1chip.muonbp traced on a v5e: every reader
    gives what the chip run reported, and the shares stay under 100%."""
    expect = json.loads((DATA / "phi4-mini.1chip.muonbp.trace.expect.json").read_text())
    tr = trace.Trace.from_json(str(DATA / "phi4-mini.1chip.muonbp.trace.json.gz"))
    lo, hi = tr.window()
    cfg = json.loads((ROOT / "bench" / "configs" / "phi4-mini.1chip.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / "muonbp_p5.b1s4096.json").read_text())
    r = trace.Reading(trace=tr, phases=expect["phases"], lo=lo, hi=hi, cfg=cfg,
                      traffic=traffic, peak=run.load_peak("TPU v5 lite"))
    for name, value in expect["metrics"].items():
        assert run.load_metric(name).read(r) == pytest.approx(value, rel=1e-9), name
    assert (hi - lo) / 1e9 == pytest.approx(expect["window_s"], rel=1e-9)
    assert 0 < run.load_metric("ns_roofline").read(r) <= 100
    assert 0 < run.load_metric("step_mfu").read(r) <= 100
    # The NS is the largest layer of this cell's step.
    assert run.load_metric("ns_ms").read(r) > 0.4 * run.load_metric("step_ms.block").read(r)
