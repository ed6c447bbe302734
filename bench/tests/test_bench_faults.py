"""A run whose timed path is broken underneath comes out not correct, and
so does the control: the comparison fails what it has to fail.

Each test skips only the look for a chip and drives the rest of a run at a
CPU size, judged by the limits of the benchmark cell it stands for."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import control, run
from bench.tests.cells import PEAK, tiny_cell

SEED = 3_000_000_029
ROOT = Path(__file__).resolve().parents[2]


def _state_unchanged(fns, phase_of):
    """Every step computes its loss but returns the state it was given."""
    def wrap(fn):
        inner = jax.jit(fn.__wrapped__)
        return lambda state, batch: (state, inner(state, batch)[1])
    return {ph: wrap(fn) for ph, fn in fns.items()}


def _half_batch(fns, phase_of):
    """The loss is the mean over the first half of the batch's tokens."""
    @jax.jit
    def drop(labels):
        flat = labels.reshape(-1)
        keep = jnp.arange(flat.size) < flat.size // 2
        return jnp.where(keep, flat, -1).reshape(labels.shape)

    def wrap(fn):
        return lambda state, batch: fn(state, {**batch, "labels": drop(batch["labels"])})
    return {ph: wrap(fn) for ph, fn in fns.items()}


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("optimizer", ["muonbp", "adamw"])
def test_fault_is_not_correct(fault, optimizer):
    cell = tiny_cell(optimizer, limits_of=f"phi4-mini.1chip.{optimizer}")
    result = run.run_cell(cell, SEED, 0.2, False, peak=PEAK, fault=fault)
    assert not result["correct"], result["check"]


@pytest.mark.parametrize("optimizer", ["muonbp", "adamw"])
def test_control_is_not_correct(optimizer):
    cell = tiny_cell(optimizer, limits_of=f"phi4-mini.1chip.{optimizer}")
    gaps = control.readings(cell, SEED, ["control"], jax.devices()[:1])[0]
    ok, check = run.judge({k: gaps[k] for k in ("loss_gap", "grad_gap", "update_gap")},
                          cell.limits)
    assert not ok, check


FOUR_DEVICES = r'''
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
from bench import run
from bench.tests.cells import PEAK, tiny_cell

def no_gather(fns, phase_of):
    return {{**fns, "full": fns["block"]}}

cell = tiny_cell("muonbp", data=2, model=2)
out = {{}}
for name, fault in [("clean", None), ("no_gather", no_gather)]:
    out[name] = run.run_cell(cell, {seed}, 0.2, False, peak=PEAK, fault=fault)
print(json.dumps({{k: [v["correct"], v["check"]] for k, v in out.items()}}))
'''


def test_four_device_run_and_exchange_left_out():
    """On a data=2 x model=2 mesh (four CPU devices in a child process):
    the clean run is correct; with the full step's gathers left out (every
    full step runs as a block step) it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES.format(root=str(ROOT), seed=SEED)],
        capture_output=True, text=True, env=env, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["clean"][0], out["clean"][1]
    assert not out["no_gather"][0], out["no_gather"][1]
