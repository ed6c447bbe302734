"""``attn_kernel_ms``: the attention kernel's device time per step, on
hand-made events in the forms a TPU trace gives the Pallas kernel's ops
(``.../model.attn/.../splash_mha_*/pallas_call``, or no scope at all where
the custom call's HLO text spans several lines), and on the recorded trace
of a program that runs the jnp scan."""

import json
from pathlib import Path

import pytest

from bench import run, trace

ROOT = Path(__file__).resolve().parents[2]
J = "jit(train_step)"
SCAN = f"{J}/transpose(jvp())/while/body/closed_call"
SPLASH = "vmap(jit(_splash_attention))"


def _read(op_list, modules, phases, hi):
    tr = trace.Trace(ops={0: sorted(op_list, key=lambda e: (e[0], -e[1]))},
                     async_ops={0: []}, modules={0: modules}, host=[])
    r = trace.Reading(trace=tr, phases=phases, lo=0, hi=hi, cfg={}, traffic={},
                      peak=run.load_peak("TPU v5 lite"))
    return run.load_metric("attn_kernel_ms").read(r)


# One step from 0 to 1000 ns: the kernel's forward, recomputed forward and
# backward (under model.attn, or with no scope), the sublayer's other ops,
# and a Pallas kernel outside the sublayer (the optimizer's), which is not
# attention's.
STEP = [
    [0, 100, "fusion.1", f"{J}/jvp()/while/body/closed_call/model.attn/dot_general"],
    [100, 60, "splash_mha_fwd_residuals.1",
     f"{J}/jvp()/while/body/closed_call/model.attn/{SPLASH}/splash_mha_fwd_residuals/"
     "splash_mha_fwd_residuals/pallas_call"],
    [160, 40, "copy.2", ""],
    [200, 60, "splash_mha_fwd_residuals.2",
     f"{SCAN}/checkpoint/rematted_computation/model.attn/{SPLASH}/splash_mha_fwd_residuals/"
     "splash_mha_fwd_residuals/pallas_call"],
    [260, 70, "splash_mha_dq_no_residuals.1",
     f"{SCAN}/checkpoint/transpose(jvp(model.attn))/{SPLASH}/splash_mha_dq_no_residuals/"
     "splash_mha_dq_no_residuals/pallas_call"],
    # As a v5e trace gives it: the instruction found no op_name.
    [330, 90, "splash_mha_dkv_no_residuals.1", ""],
    [420, 80, "fusion.3", f"{SCAN}/checkpoint/model.attn/transpose/dot_general"],
    [500, 300, "ns_kernel.1", f"{J}/opt/muonbp.block.ns/fused_chain/pallas_call"],
    [800, 200, "fusion.4", f"{J}/transpose(jvp(model.mlp))/dot_general"],
]


def test_kernel_ops_under_the_attention_scope():
    assert _read(STEP, [[0, 1000, "jit_train_step"]], ["block"], 1000) == pytest.approx(
        (60 + 60 + 70 + 90) / 1e6)


def test_per_step_over_two_steps():
    second = [[s + 1000, d, n, sc] for s, d, n, sc in STEP]
    got = _read(STEP + second, [[0, 1000, "jit_train_step"], [1000, 1000, "jit_train_step"]],
                ["full", "block"], 2000)
    assert got == pytest.approx(280 / 1e6)


def test_kernel_ops_with_no_scope_are_found_by_name():
    ops = [[0, 100, "fusion.1", f"{J}/jvp()/while/body/closed_call/model.attn/dot_general"],
           [100, 50, "splash_mha_fwd_residuals.18", ""],
           [150, 20, "copy.2", ""]]
    assert _read(ops, [[0, 170, "jit_train_step"]], ["block"], 170) == pytest.approx(50 / 1e6)


def test_scan_program_reads_zero():
    """The sublayer runs, on the jnp scan: the kernel took no time."""
    ops = [op for op in STEP if "splash" not in op[2]]
    assert _read(ops, [[0, 1000, "jit_train_step"]], ["block"], 1000) == 0.0


def test_program_without_layer_names_reads_nothing():
    ops = [[0, 100, "fusion.1", f"{J}/jvp()/dot_general"],
           [100, 50, "custom-call.1", f"{J}/jvp()/pallas_call"]]
    assert _read(ops, [[0, 150, "jit_train_step"]], ["block"], 150) is None


def test_recorded_scan_trace_reads_zero():
    """phi4-mini.1chip.muonbp traced on a v5e with the layers named and
    attention on the jnp scan."""
    data = ROOT / "bench" / "testdata"
    expect = json.loads((data / "phi4-mini.1chip.muonbp.scoped.trace.expect.json").read_text())
    tr = trace.Trace.from_json(str(data / "phi4-mini.1chip.muonbp.scoped.trace.json.gz"))
    lo, hi = tr.window()
    r = trace.Reading(trace=tr, phases=expect["phases"], lo=lo, hi=hi, cfg={}, traffic={},
                      peak={})
    assert run.load_metric("attn_kernel_ms").read(r) == 0.0
