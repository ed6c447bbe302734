"""The plain reference agrees with the program at a small size, and a
whole run (set-up, window, check) comes out correct on the CPU."""

import jax
import numpy as np
import pytest

from bench import reference, run
from bench.tests.cells import PEAK, tiny_cell

SEED = 3_000_000_017   # larger than 31 bits, as a benchmark seed may be


def test_reference_init_is_the_programs():
    from repro.models.model import init_params

    cell = tiny_cell()
    cfg = run._model_config(cell)
    key = jax.random.PRNGKey(SEED % 2 ** 32)
    mine = reference.init_params(key, cell.config)
    theirs = init_params(key, cfg)
    flat_m = dict(zip(reference.leaf_paths(mine), jax.tree.leaves(mine)))
    flat_t = dict(zip(reference.leaf_paths(theirs), jax.tree.leaves(theirs)))
    assert flat_m.keys() == flat_t.keys()
    for k in flat_m:
        np.testing.assert_array_equal(np.asarray(flat_m[k]), np.asarray(flat_t[k]), k)


@pytest.mark.parametrize("optimizer", ["muonbp", "adamw"])
def test_run_is_correct_on_cpu(optimizer):
    limits_of = f"phi4-mini.1chip.{optimizer}"
    cell = tiny_cell(optimizer, limits_of=limits_of)
    result = run.run_cell(cell, SEED, 0.2, False, peak=PEAK)
    assert result["correct"], result["check"]
    assert result["attempted"] > 0 and result["attempted"] % cell.traffic["period"] == 0
    assert result["failed"] == 0
    assert list(result["check"]) == ["loss_gap", "grad_gap", "update_gap"]
    assert set(result["metrics"]) == {"tokens_per_s", "mfu", "peak_hbm_gib", "setup_s"}


def test_block_grid_matters():
    """The reference's block step differs from its full step when the grid
    has more than one block, so the grid it is given is checked."""
    cell = tiny_cell()
    batches = [{"tokens": np.arange(128).reshape(2, 64) % 512,
                "labels": (np.arange(128).reshape(2, 64) + 1) % 512}] * 2
    grid = {"mlp/wi": [1, 2]}
    full = reference.run(cell.config, cell.traffic, grid, 7, batches, ["full", "full"])
    block = reference.run(cell.config, cell.traffic, grid, 7, batches, ["full", "block"])
    assert block["delta_norms"]["layers/mlp/wi"] != full["delta_norms"]["layers/mlp/wi"]
    assert block["delta_norms"]["layers/mlp/wg"] == full["delta_norms"]["layers/mlp/wg"]
