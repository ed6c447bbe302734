"""Checkpoint round-trip for ZeRO-1-sharded optimizer state on an 8-device
host-platform mesh (subprocess): save -> restore must be bitwise identical
AND land the momentum back in its data-axis shards when ``opt_shardings``
(from ``distributed.zero1``) is passed to ``checkpoint.restore``."""

import json
import os
import subprocess
import sys

import pytest

# slow: spawns an 8-forced-device subprocess; ci.sh's multi-device smoke
# step (and the full tier-1 `pytest -x -q`) runs it.
pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, tempfile
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import adamw, combine, label_tree, muon
from repro.core.blocking import BlockSpec2D
from repro.distributed import make_engine
from repro.distributed import zero1 as z1
from repro.training import checkpoint

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
opt = combine({"muon": muon(1e-2, block_specs=bspecs, comm=comm),
               "adamw": adamw(1e-3)}, labels)

state = opt.init(params)
state = z1.shard_state(state, params, mesh, pspecs=pspecs)
grads = jax.tree.map(lambda p: 0.1 * jnp.ones_like(p), params)
# one real update so the momentum is nonzero (and stays sharded)
_, state = jax.jit(lambda g, s, p: opt.update(g, s, p, "block"))(grads, state, params)
saved_spec = str(state.inner["muon"].momentum["stack_col"].sharding.spec)

ckpt_dir = tempfile.mkdtemp()
checkpoint.save(ckpt_dir, params, state, step=7)

a_params = jax.tree.map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), params)
a_opt = jax.eval_shape(opt.init, a_params)
param_sh = jax.tree.map(lambda x: x.sharding, a_params)
opt_sh = z1.opt_shardings(a_opt, a_params, mesh, zero1=True)
r_params, r_state, step = checkpoint.restore(
    ckpt_dir, a_params, a_opt, shardings=param_sh, opt_shardings=opt_sh)

out = {"step": step, "saved_spec": saved_spec}
out["restored_spec"] = str(r_state.inner["muon"].momentum["stack_col"].sharding.spec)
out["restored_devices"] = len(r_state.inner["muon"].momentum["stack_col"].sharding.device_set)
out["params_equal"] = all(
    np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(r_params)))
out["opt_equal"] = all(
    np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(r_state)))
# the SDS-leaf form (zero1.attach output) must also be accepted as shardings
r2_params, r2_state, _ = checkpoint.restore(
    ckpt_dir, a_params, a_opt, shardings=a_params,
    opt_shardings=z1.attach(a_opt, a_params, mesh, zero1=True))
out["sds_spec"] = str(r2_state.inner["muon"].momentum["stack_col"].sharding.spec)
# without opt_shardings the state restores replicated (documented behavior)
_, r3_state, _ = checkpoint.restore(ckpt_dir, a_params, a_opt)
out["unsharded_ok"] = bool(np.array_equal(
    np.asarray(r3_state.inner["muon"].momentum["stack_col"]),
    np.asarray(state.inner["muon"].momentum["stack_col"])))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_roundtrip_bitwise(result):
    assert result["step"] == 7
    assert result["params_equal"]
    assert result["opt_equal"]


def test_restore_reapplies_zero1_shards(result):
    assert "data" in result["saved_spec"]
    assert result["restored_spec"] == result["saved_spec"]
    assert result["restored_devices"] == 8
    assert result["sds_spec"] == result["saved_spec"]


def test_restore_without_shardings_still_correct(result):
    assert result["unsharded_ok"]
