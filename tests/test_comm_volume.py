"""The paper's core systems claim, verified from post-SPMD HLO on 8 host
devices: MuonBP block steps add (almost) no optimizer communication, full
steps pay the Muon all-gather. Runs in a subprocess so the forced device
count can't leak into other tests."""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, functools
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.launch.dryrun import parse_collectives
from repro.models.model import init_params
from repro.sharding import specs as sh
from repro.core import adamw, combine, label_tree, muon
from repro.training.train_step import TrainState, train_step
from jax.sharding import NamedSharding, PartitionSpec as P
import dataclasses

cfg = get_config("granite-8b").reduced()
cfg = dataclasses.replace(cfg, d_model=256, d_ff=512, vocab_size=512, num_layers=2)
mesh = make_mesh((2, 4), ("data", "model"))
ctx = sh.make_ctx(cfg, mesh, global_batch=4)

a_params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
pspecs = sh.param_specs(a_params, cfg, mesh)
a_params = jax.tree.map(
    lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
    a_params, pspecs)
labels = label_tree(a_params)
bspecs = sh.block_specs_for(a_params, pspecs, mesh)
bspecs = jax.tree.map(lambda l, b: b if l == "muon" else None, labels, bspecs)
opt = combine({"muon": muon(1e-3, block_specs=bspecs), "adamw": adamw(1e-3)}, labels)
a_opt = jax.eval_shape(opt.init, a_params)
from repro.launch.dryrun import _attach_opt_shardings
a_opt = _attach_opt_shardings(a_opt, a_params, mesh)
state = TrainState(a_params, a_opt, jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())))
batch = {
    "tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32, sharding=NamedSharding(mesh, P("data", None))),
    "labels": jax.ShapeDtypeStruct((4, 64), jnp.int32, sharding=NamedSharding(mesh, P("data", None))),
}
out = {}
for phase in ("block", "full"):
    fn = functools.partial(train_step, cfg=cfg, optimizer=opt, ctx=ctx, phase=phase)
    compiled = jax.jit(fn).lower(state, batch).compile()
    out[phase] = parse_collectives(compiled.as_text())
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_block_phase_has_less_optimizer_comm():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    result = json.loads(line[len("RESULT "):])
    block_bytes = sum(v["bytes"] for v in result["block"].values())
    full_bytes = sum(v["bytes"] for v in result["full"].values())
    # full orthogonalization must move strictly more bytes (the Muon gather)
    assert full_bytes > 1.2 * block_bytes, result
    # and block steps must not all-gather the big momentum matrices:
    ag_block = result["block"].get("all-gather", {}).get("bytes", 0)
    ag_full = result["full"].get("all-gather", {}).get("bytes", 0)
    assert ag_full > ag_block, result
