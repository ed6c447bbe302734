"""8-device engine coverage for the optimizer variants.

One subprocess (16 host-platform devices) drives every registered variant
through the shard_map engine: ZeRO-1 state sharding is bitwise-equivalent
to unsharded state per variant, NorMuon's second-moment rows survive the
36-layer/16-way flatten-and-shard fallback, block phases audit to zero
optimizer gathers, full phases gather exactly what CommPlan prices, and
the Dion factor program moves no parameter-sized bytes on either phase.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import build_variant, muon
from repro.core.blocking import BlockSpec2D
from repro.distributed import audit_optimizer, make_engine, plan_comm
from repro.distributed import zero1 as z1

GATHER_OPS = ("all-gather", "reduce-scatter", "all-to-all")
mesh = make_mesh((2, 4), ("data", "model"), devices=jax.devices()[:8])
layout = {
    "wq":    ((64, 128),    P(None, "model"),       BlockSpec2D(1, 4)),
    "wo":    ((128, 64),    P("model", None),       BlockSpec2D(4, 1)),
    "stack": ((4, 32, 64),  P(None, None, "model"), BlockSpec2D(1, 4)),
    "local": ((24, 24),     P(None, None),          None),
}
pspecs = {k: sp for k, (s, sp, b) in layout.items()}
blocks = {k: b for k, (s, sp, b) in layout.items()}
params = {
    k: jax.device_put(jax.random.normal(jax.random.PRNGKey(i), s),
                      NamedSharding(mesh, sp))
    for i, (k, (s, sp, b)) in enumerate(layout.items())
}
grads = jax.tree.map(lambda p: 0.1 * p, params)
labels = {k: "muon" for k in layout}
a_params = jax.tree.map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), params)
plan = plan_comm(a_params, pspecs, mesh, labels=labels, block_specs=blocks)

out = {"predicted_full": plan.predicted_bytes("full")}

# ---- muon-family variants: zero1 parity + per-phase comm audits --------
for vname in ("muon", "turbo_muon", "normuon"):
    eng0 = make_engine(params, pspecs, mesh)
    engz = make_engine(params, pspecs, mesh, zero1=True)
    o0 = muon(0.02, block_specs=blocks, comm=eng0, variant=vname)
    oz = muon(0.02, block_specs=blocks, comm=engz, variant=vname)
    s0 = o0.init(params)
    sz = z1.shard_state(oz.init(params), params, mesh, pspecs=pspecs)
    rec = {}
    for phase in ("block", "full"):
        u0, n0 = o0.update(grads, s0, params, phase)
        uz, nz = oz.update(grads, sz, params, phase)
        rec[phase + "_updates_bitwise"] = all(
            bool(jnp.all(a == b))
            for a, b in zip(jax.tree.leaves(u0), jax.tree.leaves(uz)))
        rec[phase + "_updates_rel_err"] = max(
            float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a)))
            for a, b in zip(jax.tree.leaves(u0), jax.tree.leaves(uz)))
        if vname == "normuon" and phase == "full":
            rec["v_bitwise"] = all(
                bool(jnp.all(a == b))
                for a, b in zip(jax.tree.leaves(n0.second_moment),
                                jax.tree.leaves(nz.second_moment)))
            rec["vcount_one"] = all(
                int(c) == 1 for c in jax.tree.leaves(nz.vcount))
    if vname == "normuon":
        rec["v_stack_spec"] = str(sz.second_moment["stack"].sharding.spec)
    a_opt = z1.attach(jax.eval_shape(o0.init, a_params), a_params, mesh)
    res_b = audit_optimizer(o0, a_params, a_opt, phase="block")
    res_f = audit_optimizer(o0, a_params, a_opt, phase="full")
    rec["block_gather_bytes"] = sum(res_b.bytes_of(op) for op in GATHER_OPS)
    rec["block_collectives"] = res_b.collectives
    rec["full_gather_bytes"] = res_f.bytes_of("all-gather")
    out[vname] = rec

# ---- NorMuon extra state under the 36-layer/16-way flatten fallback ----
mesh16 = make_mesh((16, 1), ("data", "model"))
tree = {"layers": jax.random.normal(jax.random.PRNGKey(9), (36, 8, 16))}
tree = jax.device_put(tree, NamedSharding(mesh16, P(None, None, None)))
grads16 = jax.tree.map(lambda p: 0.1 * p, tree)
pspecs16 = {"layers": P(None, None, None)}
blocks16 = {"layers": None}
o0 = muon(0.02, block_specs=blocks16,
          comm=make_engine(tree, pspecs16, mesh16), variant="normuon")
of = muon(0.02, block_specs=blocks16,
          comm=make_engine(tree, pspecs16, mesh16, zero1=True,
                           zero1_flatten=True),
          variant="normuon")
s0 = o0.init(tree)
sf = z1.shard_state(of.init(tree), tree, mesh16, pspecs=pspecs16)
g = {
    "m_padded": list(sf.momentum["layers"].shape),
    "v_padded": list(sf.second_moment["layers"].shape),
    "v_spec": str(sf.second_moment["layers"].sharding.spec),
}
for phase in ("block", "full"):
    u0, n0 = o0.update(grads16, s0, tree, phase)
    uf, nf = of.update(grads16, sf, tree, phase)
    g[phase + "_updates_bitwise"] = bool(jnp.all(u0["layers"] == uf["layers"]))
    g[phase + "_v_head_bitwise"] = bool(jnp.all(
        n0.second_moment["layers"]
        == np.asarray(nf.second_moment["layers"])[:36]))
    g[phase + "_v_pad_zero"] = bool(jnp.all(
        np.asarray(nf.second_moment["layers"])[36:] == 0))
out["granite36_normuon"] = g

# ---- Dion: factor program moves no parameter-sized bytes ---------------
od = build_variant("dion", 0.02, rank=8,
                   comm=make_engine(params, pspecs, mesh))
sd = od.init(params)
ub, _ = od.update(grads, sd, params, "block")
uf, _ = od.update(grads, sd, params, "full")
drec = {
    "block_eq_full": all(
        bool(jnp.all(a == b))
        for a, b in zip(jax.tree.leaves(ub), jax.tree.leaves(uf))),
    "finite": all(bool(jnp.all(jnp.isfinite(u))) for u in jax.tree.leaves(ub)),
}
replicate = lambda t: jax.tree.map(
    lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=NamedSharding(mesh, P(*(None,) * x.ndim))),
    t)
# Dion's own layout: replicated fp32 state + post-allreduce (replicated)
# grads — auditing with model-sharded grads would measure the gather XLA
# inserts to re-replicate b = m + g, a layout artifact, not program comm.
a_rep = replicate(a_params)
a_opt_d = replicate(jax.eval_shape(od.init, a_params))
for phase in ("block", "full"):
    res = audit_optimizer(od, a_rep, a_opt_d, phase=phase)
    drec[phase + "_gather_bytes"] = res.bytes_of("all-gather")
    drec[phase + "_collectives"] = res.collectives
out["dion"] = drec
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("REPRO_FULL_SCHEDULE", None)
    env.pop("REPRO_OPTIMIZER_VARIANT", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
@pytest.mark.parametrize("vname", ["muon", "turbo_muon", "normuon"])
def test_zero1_bitwise_parity_per_variant(result, vname):
    """ZeRO-1 state sharding never changes a variant's numerics: both
    phases produce bitwise-identical updates to the unsharded engine.

    One exception, and only in summation order: NorMuon's full-phase
    RMS-preserving rescale takes a global mean over the whole leaf. ZeRO-1
    shards the state's lead dim over 'data', so the partitioner sums that
    mean's per-shard partials in a different association than the
    unsharded layout, moving the rescale scalar by about one fp32 ulp. The
    row statistics themselves stay bitwise (see the second-moment test);
    the update agrees to a few ulps of its largest entry."""
    rec = result[vname]
    assert rec["block_updates_bitwise"], vname
    if vname == "normuon":
        assert rec["full_updates_rel_err"] <= 1e-6, rec
    else:
        assert rec["full_updates_bitwise"], vname


@pytest.mark.slow
def test_normuon_second_moment_sharded_and_bitwise(result):
    """NorMuon's extra state flows through ZeRO-1: the row stats live
    sharded on the lead dim and the full-phase refresh is bitwise-equal to
    the unsharded refresh; the counter advanced exactly once."""
    rec = result["normuon"]
    assert "data" in rec["v_stack_spec"]
    assert rec["v_bitwise"]
    assert rec["vcount_one"]


@pytest.mark.slow
@pytest.mark.parametrize("vname", ["muon", "turbo_muon", "normuon"])
def test_block_phase_zero_optimizer_gathers(result, vname):
    """Acceptance: block phases move ZERO gather-class optimizer bytes for
    every variant (NorMuon's epilogue reductions are all-reduces of row
    scalars, never parameter gathers; Turbo's pre-scale is local)."""
    assert result[vname]["block_gather_bytes"] == 0, result[vname]
    if vname != "normuon":
        # without an epilogue the block step has no collectives at all
        assert result[vname]["block_collectives"] == {}, result[vname]


@pytest.mark.slow
@pytest.mark.parametrize("vname", ["muon", "turbo_muon", "normuon"])
def test_full_phase_gathers_plan_exact_per_variant(result, vname):
    """Acceptance: full-phase all-gather bytes equal CommPlan's prediction
    exactly for every variant — the variant stages change kernels, never
    the comm schedule."""
    assert result[vname]["full_gather_bytes"] \
        == result["predicted_full"] > 0, result[vname]


@pytest.mark.slow
def test_normuon_granite36_flatten_fallback(result):
    """The 36-layer/16-way flatten fallback pads NorMuon's momentum AND
    second moment to 48 lead rows, keeps both phases bitwise-equal to
    unsharded state, refreshes only the 36 real rows, and leaves the pad
    rows zero."""
    g = result["granite36_normuon"]
    assert g["m_padded"] == [48, 8, 16]
    assert g["v_padded"] == [48, 8, 1]
    assert "data" in g["v_spec"]
    for phase in ("block", "full"):
        assert g[phase + "_updates_bitwise"], phase
        assert g[phase + "_v_head_bitwise"], phase
        assert g[phase + "_v_pad_zero"], phase


@pytest.mark.slow
def test_dion_engine_moves_no_parameter_bytes(result):
    """Dion through the engine: phases identical, updates finite, and NO
    all-gathers on either phase — the factor program's 0 B prediction holds
    in the compiled HLO."""
    d = result["dion"]
    assert d["block_eq_full"]
    assert d["finite"]
    assert d["block_gather_bytes"] == 0, d
    assert d["full_gather_bytes"] == 0, d
