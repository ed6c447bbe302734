"""Hierarchical ('pod','data','model') mesh support + ZeRO-1 flatten fallback.

Host-side sections (no devices, fake meshes): per-link plan accounting,
flatten-and-shard pricing (including the paper-scale granite 36-layer /
16-way shape), DCN-first pipeline ordering, replica-group parsing and
mesh-axis attribution.

Device sections (subprocess, forced host devices, marked slow): on a
simulated (2,2,2) mesh block steps audit to ZERO inter-pod collective
bytes, full-step pod-local gathers match ``CommPlan.predicted_bytes`` per
axis exactly, and the ZeRO-1 flatten fallback is bitwise-equivalent to
unsharded optimizer state — including the 36-layer/16-way-data granite
shape — with ``CommPlan.predicted_bytes('apply')`` matching the audited
gather-class bytes.
"""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import LeafSpec, compile_program
from repro.distributed import (
    AuditResult,
    DCN_AXES,
    bytes_by_axes,
    bytes_by_link,
    collective_axes,
    link_class,
    overlappable_ns_bytes,
    parse_collective_events,
    plan_comm,
)
from repro.distributed.audit import _parse_replica_groups
from repro.sharding import specs as sh


def fake_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    devs = np.array(jax.devices() * int(np.prod(shape)))[: int(np.prod(shape))]
    return Mesh(devs.reshape(shape), axes)


SIZES = {"pod": 2, "data": 2, "model": 2}


# ------------------------------------------------------------ link model

def test_link_class():
    assert link_class(("model",)) == "ici"
    assert link_class(("data", "model")) == "ici"
    assert link_class(("pod",)) == "dcn"
    assert link_class(("pod", "data")) == "dcn"  # slowest link wins
    assert "pod" in DCN_AXES


def test_overlappable_ns_bytes_per_link():
    ici = overlappable_ns_bytes((8, 64, 128), 5, link="ici")
    dcn = overlappable_ns_bytes((8, 64, 128), 5, link="dcn")
    assert 8 * dcn == pytest.approx(ici, abs=8)  # modeled DCN rate is ICI/8
    assert overlappable_ns_bytes((8, 64, 128), 5) == ici  # default is ici
    with pytest.raises(ValueError, match="link"):
        overlappable_ns_bytes((8, 64, 128), 5, link="pcie")


def test_zero1_axes_resolution():
    assert sh.zero1_axes(SIZES) == ("pod", "data")
    assert sh.zero1_axes({"data": 4, "model": 2}) == ("data",)
    assert sh.zero1_axes(SIZES, "data") == ("data",)
    assert sh.zero1_axes(SIZES, ("pod", "data")) == ("pod", "data")


def test_momentum_spec_tuple_axes():
    # multi-pod ZeRO-1: lead dim shards over ('pod','data') when divisible
    assert sh.momentum_spec(P(None, None, "model"), (8, 4, 6), SIZES,
                            zero1=True, zero1_axis=None) \
        == P(("pod", "data"), None, "model")
    # indivisible by the combined extent (4) but divisible by data (2):
    # fall back to the largest dividing axis SUFFIX, never silently
    # replicate (the flat-mesh behavior is preserved across pods)
    assert sh.momentum_spec(P(None, None, "model"), (6, 4, 6), SIZES,
                            zero1=True, zero1_axis=None) \
        == P("data", None, "model")
    # indivisible by every suffix: untouched
    assert sh.momentum_spec(P(None, None, "model"), (3, 4, 6), SIZES,
                            zero1=True, zero1_axis=None) \
        == P(None, None, "model")
    # production-shaped case: 48 layers on (pod=2, data=16) -> data alone
    assert sh.momentum_spec(P(None, None, "model"),
                            (48, 4, 6), {"pod": 2, "data": 16, "model": 16},
                            zero1=True, zero1_axis=None) \
        == P("data", None, "model")
    # single-axis tuples normalize to the scalar entry (flat-mesh behavior)
    assert sh.momentum_spec(P(None, None, "model"), (8, 4, 6), SIZES,
                            zero1=True, zero1_axis=("data",)) \
        == P("data", None, "model")


# ------------------------------------------------- flatten-and-shard rules

def test_zero1_flatten_info_rules():
    # engages: muon stack, unsharded lead, indivisible by pod*data = 4
    fl = sh.zero1_flatten_info(P(None, None, "model"), (3, 4, 6), SIZES,
                               zero1_axis=None)
    assert fl is not None
    assert (fl.axes, fl.factor, fl.lead, fl.padded_lead) \
        == (("pod", "data"), 4, 3, 4)
    assert fl.pad == 1 and fl.padded_shape((3, 4, 6)) == (4, 4, 6)
    # divisible lead: standard ZeRO-1 applies, no fallback
    assert sh.zero1_flatten_info(P(None, None, "model"), (8, 4, 6), SIZES,
                                 zero1_axis=None) is None
    # 2-D muon leaf: trailing dims are the block grid, never split
    assert sh.zero1_flatten_info(P(None, "model"), (3, 6), SIZES,
                                 zero1_axis=None) is None
    # already-sharded lead dim: not ours to re-shard
    assert sh.zero1_flatten_info(P("model", None, None), (3, 4, 6), SIZES,
                                 zero1_axis=None) is None
    # spec for the padded shape
    fl = sh.zero1_flatten_info(P(None, None, "model"), (3, 4, 6), SIZES,
                               zero1_axis=None)
    assert sh.flatten_momentum_spec(P(None, None, "model"), (3, 4, 6), fl) \
        == P(("pod", "data"), None, "model")


def test_flatten_plan_prices_apply_per_axis():
    mesh = fake_mesh()
    params = {"w": jax.ShapeDtypeStruct((3, 8, 16), jnp.float32)}
    pspecs = {"w": P(None, None, "model")}
    plan = plan_comm(params, pspecs, mesh, labels={"w": "muon"},
                     zero1=True, zero1_flatten=True)
    (leaf,) = plan.leaves
    assert leaf.flatten is not None and leaf.zero1_factor == 4
    # block steps stay shard-local; full gathers only the model axis
    assert plan.predicted_bytes("block") == 0
    assert plan.predicted_by_axes("full") == {("model",): 1 * 8 * 16 * 4}
    # apply: per-axis writeback gathers, minor ('data') first, result bytes
    # growing as the padded lead dim fills in (trailing stays model-sharded)
    from repro.distributed import Collective

    assert leaf.apply == (
        Collective("all-gather", ("data",), 2 * 8 * 8 * 4),
        Collective("all-gather", ("pod",), 4 * 8 * 8 * 4),
    )
    assert plan.predicted_by_link("apply") == {
        "ici": 2 * 8 * 8 * 4, "dcn": 4 * 8 * 8 * 4,
    }
    # without the opt-in the fallback must not engage (documented no-op)
    base = plan_comm(params, pspecs, mesh, labels={"w": "muon"}, zero1=True)
    assert base.leaves[0].zero1_factor == 1
    assert base.predicted_bytes("apply") == 0


def test_granite_36_layer_16_way_flatten_plan():
    """The acceptance shape: granite's 36 layers on the 16-way production
    data axis. Standard ZeRO-1 no-ops (36 % 16 != 0); the fallback pads to
    48 and prices the writeback gather in 'apply'."""
    from repro.configs import get_config
    from repro.core import label_tree
    from repro.models.model import init_params

    cfg = get_config("granite-8b")
    assert cfg.num_layers == 36
    mesh = fake_mesh((16, 16), ("data", "model"))
    a_params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    pspecs = sh.param_specs(a_params, cfg, mesh)
    labels = label_tree(a_params)
    base = plan_comm(a_params, pspecs, mesh, labels=labels, zero1=True)
    plan = plan_comm(a_params, pspecs, mesh, labels=labels, zero1=True,
                     zero1_flatten=True)
    flat_labels = dict(zip((l.path for l in plan.leaves), jax.tree.leaves(labels)))
    muon_stacks = [
        l for l in plan.leaves
        if flat_labels[l.path] == "muon" and len(l.shape) >= 3
    ]
    assert muon_stacks
    sizes = sh.mesh_axis_sizes(mesh)
    for leaf, b_leaf in zip(plan.leaves, base.leaves):
        if leaf not in muon_stacks:
            continue
        # without the fallback ZeRO-1 silently no-ops on these leaves
        assert b_leaf.zero1_factor == 1 and b_leaf.predicted_bytes("apply") == 0
        assert leaf.flatten is not None
        assert leaf.flatten.lead == 36 and leaf.flatten.padded_lead == 48
        assert leaf.zero1_factor == 16
        # full-step gathers shrink by the ZeRO factor (each rank gathers
        # only its own 3 padded layers)
        if b_leaf.full:
            assert leaf.predicted_bytes("full") * 16 \
                == b_leaf.predicted_bytes("full") // 36 * 48
        # one writeback gather over 'data', padded stack, model-sharded trailing
        r = sh.spec_entry_size(list(leaf.spec)[-2], sizes)
        c = sh.spec_entry_size(list(leaf.spec)[-1], sizes)
        per_layer = int(np.prod(leaf.shape[1:]))
        (ap,) = leaf.apply
        assert ap.axes == ("data",)
        assert ap.bytes == 48 * per_layer // (r * c) * 4
    assert plan.predicted_bytes("apply") > 0
    assert plan.predicted_bytes("block") == 0  # block steps stay shard-local


def test_flatten_program_compiles_apply_commops():
    """Engine-mode programs for flatten leaves carry the writeback 'apply'
    CommOp, the param-layout out_spec, and the unpadded lead."""

    class FlattenEngine:
        axis_sizes = dict(SIZES)

        def spec_for(self, key, ndim):
            return P(("pod", "data"), *([None] * (ndim - 2)), "model")

        def flatten_for(self, key):
            return sh.FlattenSpec(axes=("pod", "data"), factor=4, lead=3,
                                  padded_lead=4)

        def state_shape_for(self, key, shape):
            return (4, *shape[1:])

    # the program sees the PADDED shape (muon.update pads the NS input)
    ls = LeafSpec(key=("w",), shape=(4, 8, 16), dtype="float32", block=None)
    prog = compile_program((ls,), backend="jnp", engine=FlattenEngine())
    for phase in ("block", "full"):
        (le,) = prog.phase(phase).leaf_execs
        assert le.apply is not None and le.apply.kind == "apply"
        assert le.apply.collectives == (
            ("all-gather", ("data",), 2 * 8 * 8 * 4),
            ("all-gather", ("pod",), 4 * 8 * 8 * 4),
        )
        assert le.out_spec == P(None, None, "model")
        assert le.lead == 3
        assert prog.phase(phase).predicted_apply_bytes() == (2 + 4) * 8 * 8 * 4
    assert "zero1 apply" in prog.summary()
    # unpadded shapes are rejected loudly
    bad = LeafSpec(key=("w",), shape=(3, 8, 16), dtype="float32", block=None)
    with pytest.raises(ValueError, match="padded"):
        compile_program((bad,), backend="jnp", engine=FlattenEngine())


def test_pipeline_schedule_orders_dcn_first():
    """A bucket whose gather traverses the inter-pod link issues first even
    when an intra-pod bucket moves more bytes, and stage pricing carries
    the per-link split."""

    class PodShardedEngine:
        axis_sizes = dict(SIZES)

        def spec_for(self, key, ndim):
            if key == ("pod_leaf",):
                return P(*([None] * (ndim - 1)), ("pod", "model"))
            if key == ("big_ici",):
                return P(*([None] * (ndim - 1)), "model")
            return P(*(None,) * ndim)

    leaf_specs = (
        # bigger ICI gather...
        LeafSpec(key=("big_ici",), shape=(8, 64, 128), dtype="float32"),
        # ...but this one crosses the pod boundary -> must issue first
        LeafSpec(key=("pod_leaf",), shape=(32, 64), dtype="float32"),
        LeafSpec(key=("local",), shape=(24, 24), dtype="float32"),
    )
    prog = compile_program(leaf_specs, backend="jnp",
                           engine=PodShardedEngine())
    full = prog.phase("full")
    sched = full.schedule
    assert sched is not None
    first_op = full.ops[sched.order[0]]
    assert first_op.leaves[0].index == 1  # the pod-sharded leaf
    assert sched.dcn_gather_bytes > 0
    s0 = sched.stages[0]
    # the pod_leaf bucket's 'pod'-axis gather is the DCN portion; its
    # intra-pod 'model' gather stays ICI
    assert 0 < s0.dcn_gather_bytes < s0.gather_bytes
    assert s0.exposed_bytes == s0.gather_bytes  # nothing to hide behind
    for s in sched.stages:
        assert 0 <= s.dcn_gather_bytes <= s.gather_bytes
        if s.compute is not None:
            assert s.dcn_overlap_bytes * 8 == pytest.approx(s.overlap_bytes, abs=8)
    # flat-mesh programs price zero DCN everywhere
    assert sched.exposed_dcn_bytes <= sched.dcn_gather_bytes


def test_pipeline_vmem_budget_per_link():
    from repro.kernels import dispatch

    assert dispatch.pipeline_vmem_budget("dcn") \
        == dispatch.pipeline_vmem_budget("ici") - dispatch.PIPELINE_VMEM_RESERVE_BYTES
    with pytest.raises(ValueError, match="link"):
        dispatch.pipeline_vmem_budget("nvlink")


# ------------------------------------------ replica-group axis attribution

def test_parse_replica_groups_forms():
    # explicit list form
    assert _parse_replica_groups(
        "x = f32[2] all-gather(y), replica_groups={{0,1},{2,3}}, dim=0"
    ) == ((0, 1), (2, 3))
    # iota v2 form: [groups,size]<=[dims]
    assert _parse_replica_groups(
        "x = f32[2] all-gather(y), replica_groups=[4,2]<=[8]"
    ) == ((0, 1), (2, 3), (4, 5), (6, 7))
    # iota with transpose: groups stride over the major axis
    assert _parse_replica_groups(
        "x = f32[2] all-gather(y), replica_groups=[2,4]<=[4,2]T(1,0)"
    ) == ((0, 2, 4, 6), (1, 3, 5, 7))
    assert _parse_replica_groups("x = f32[2] add(y, z)") is None


def test_collective_axes_attribution():
    # plain-int device array stands in for the mesh (2,2,2) = pod,data,model
    mesh = types.SimpleNamespace(
        devices=np.arange(8).reshape(2, 2, 2),
        axis_names=("pod", "data", "model"),
    )
    # groups varying only in the last coordinate -> model axis
    assert collective_axes(((0, 1), (2, 3), (4, 5), (6, 7)), mesh) == ("model",)
    # groups pairing across pods (0 vs 4) -> pod axis
    assert collective_axes(((0, 4), (1, 5), (2, 6), (3, 7)), mesh) == ("pod",)
    # one group spanning everything
    assert collective_axes((tuple(range(8)),), mesh) \
        == ("data", "model", "pod")
    # degenerate/empty groups attribute to nothing
    assert collective_axes(((3,),), mesh) == ()
    assert collective_axes(None, mesh) == ()


def test_bytes_by_axes_and_link_from_hlo_text():
    hlo = "\n".join([
        "ENTRY %main {",
        "  %p = f32[4,8]{1,0} parameter(0)",
        "  %ag = f32[8,8]{1,0} all-gather(f32[4,8]{1,0} %p),"
        " replica_groups={{0,1},{2,3},{4,5},{6,7}}, dimensions={0}",
        "  %ar = f32[8,8]{1,0} all-reduce(f32[8,8]{1,0} %ag),"
        " replica_groups=[2,4]<=[4,2]T(1,0), to_apply=%add",
        "  %cp = f32[2,8]{1,0} collective-permute(f32[2,8]{1,0} %p),"
        " source_target_pairs={{0,4}}",
        "}",
    ])
    events = parse_collective_events(hlo)
    assert [(e.op, e.bytes) for e in events] \
        == [("all-gather", 256), ("all-reduce", 256), ("collective-permute", 64)]
    result = AuditResult(collectives={}, events=(), collective_events=tuple(events))
    mesh = types.SimpleNamespace(
        devices=np.arange(8).reshape(2, 2, 2),
        axis_names=("pod", "data", "model"),
    )
    by_axes = bytes_by_axes(result, mesh)
    # {{0,1},...} varies model; [2,4]<=[4,2]T(1,0) groups (0,2,4,6) vary
    # pod+data; the permute has no replica_groups -> visible under ('?',)
    assert by_axes == {("model",): 256, ("data", "pod"): 256, ("?",): 64}
    # fail-closed: unattributable bytes count as 'dcn', so the inter-pod
    # gate trips on anything the parser cannot place
    assert bytes_by_link(result, mesh) == {"ici": 256, "dcn": 256 + 64}


# ------------------------------------- devices: (2,2,2) + granite 36/16

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import LeafSpec, compile_program, muon
from repro.core.blocking import BlockSpec2D
from repro.distributed import (
    assert_matches_plan_by_axes, assert_no_inter_pod,
    assert_pipelined_matches_plan, audit_optimizer, bytes_by_axes,
    bytes_by_link, inter_pod_bytes, make_engine, plan_comm,
)
from repro.distributed import zero1 as z1

out = {}

# ---------------- (2,2,2) hierarchical mesh over 8 of the devices --------
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"),
                     devices=jax.devices()[:8])
layout = {
    # 3 layers over pod*data=4 -> flatten fallback engages under zero1
    "stack": ((3, 16, 32), P(None, None, "model"), BlockSpec2D(1, 2)),
    "wq":    ((16, 32),    P(None, "model"),       BlockSpec2D(1, 2)),
    # "genuinely sharded across pods": trailing dim over ('pod','model')
    "podw":  ((16, 64),    P(None, ("pod", "model")), BlockSpec2D(1, 4)),
    "local": ((12, 12),    P(None, None),          None),
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
plan_f = plan_comm(a_params, pspecs, mesh, labels=labels, block_specs=blocks,
                   zero1=True, zero1_flatten=True)
out["plan"] = {
    "full_by_link": plan.predicted_by_link("full"),
    "apply_by_link_flatten": plan_f.predicted_by_link("apply"),
}

# --- no-zero1 engine: block steps move ZERO inter-pod (and zero) bytes ---
eng = make_engine(params, pspecs, mesh)
opt = muon(0.02, block_specs=blocks, comm=eng)
a_opt = jax.eval_shape(opt.init, a_params)
a_opt = z1.attach(a_opt, a_params, mesh)
res_b = audit_optimizer(opt, a_params, a_opt, phase="block")
assert_no_inter_pod(res_b, mesh)
out["block"] = {
    "collectives": res_b.collectives,
    "inter_pod": inter_pod_bytes(res_b, mesh),
}

# --- full step: per-axis gathers match the plan EXACTLY; only the
# pod-sharded leaf's gather crosses the pod boundary ---------------------
res_f = audit_optimizer(opt, a_params, a_opt, phase="full")
by_axes = assert_matches_plan_by_axes(res_f, plan, "full", mesh)
out["full"] = {
    "by_axes": {"/".join(k): v for k, v in by_axes.items()},
    "by_link": bytes_by_link(res_f, mesh),
    "plan_by_link": plan.predicted_by_link("full"),
}

# --- pipelined schedule: DCN bucket first; stage attribution exact ------
leaf_specs = tuple(
    LeafSpec(key=(k,), shape=s, dtype="float32", block=b)
    for k, (s, sp, b) in layout.items()
)
prog = compile_program(leaf_specs, backend="jnp", engine=eng)
sched = prog.phase("full").schedule
first = prog.phase("full").ops[sched.order[0]]
out["sched"] = {
    "first_leaf": list(prog.leaf_specs[first.leaves[0].index].key),
    "dcn_bytes": sched.dcn_gather_bytes,
}
try:
    attributed = assert_pipelined_matches_plan(res_f, prog.phase("full"), plan)
    out["sched"]["attribution"] = "ok"
    out["sched"]["stages"] = {str(k): v for k, v in attributed.items()}
except AssertionError as e:
    out["sched"]["attribution"] = str(e)

# --- ZeRO-1 flatten fallback: bitwise parity + audited apply bytes ------
s0 = opt.init(params)
eng_f = make_engine(params, pspecs, mesh, zero1=True, zero1_flatten=True)
opt_f = muon(0.02, block_specs=blocks, comm=eng_f)
s_f = z1.shard_state(opt_f.init(params), params, mesh, pspecs=pspecs)
out["flatten"] = {
    "padded_shape": list(s_f.momentum["stack"].shape),
    "momentum_spec": str(s_f.momentum["stack"].sharding.spec),
}
parity = {}
for phase in ("block", "full"):
    u0, ns0 = opt.update(grads, s0, params, phase)
    uf, nsf = opt_f.update(grads, s_f, params, phase)
    parity[phase + "_updates"] = all(
        bool(jnp.all(a == b))
        for a, b in zip(jax.tree.leaves(u0), jax.tree.leaves(uf))
    )
    # state parity: the fallback's real layers == unsharded momentum bitwise
    parity[phase + "_momentum"] = all(
        bool(jnp.all(a == np.asarray(b)[: a.shape[0]]))
        for a, b in zip(jax.tree.leaves(ns0.momentum),
                        jax.tree.leaves(nsf.momentum))
    )
out["flatten"]["parity"] = parity

a_opt_f = jax.eval_shape(opt_f.init, a_params)
a_opt_f = z1.attach(a_opt_f, a_params, mesh, zero1=True)
GATHER_OPS = ("all-gather", "reduce-scatter", "all-to-all")
audits = {}
for phase in ("block", "full"):
    res = audit_optimizer(opt_f, a_params, a_opt_f, phase=phase)
    assert_matches_plan_by_axes(res, plan_f, (phase, "apply"), mesh)
    audits[phase] = {
        "gather_bytes": sum(res.bytes_of(op) for op in GATHER_OPS),
        "predicted_phase": plan_f.predicted_bytes(phase),
        "predicted_apply": plan_f.predicted_bytes("apply"),
    }
out["flatten"]["audits"] = audits

# ---------------- granite shape: 36 layers / 16-way data axis -----------
mesh16 = make_mesh((16, 1), ("data", "model"), devices=jax.devices())
tree = {"layers": jax.random.normal(jax.random.PRNGKey(9), (36, 8, 16))}
tree = jax.device_put(tree, NamedSharding(mesh16, P(None, None, None)))
grads16 = jax.tree.map(lambda p: 0.1 * p, tree)
pspecs16 = {"layers": P(None, None, None)}
blocks16 = {"layers": None}
eng16_0 = make_engine(tree, pspecs16, mesh16)
opt16_0 = muon(0.02, block_specs=blocks16, comm=eng16_0)
eng16 = make_engine(tree, pspecs16, mesh16, zero1=True, zero1_flatten=True)
opt16 = muon(0.02, block_specs=blocks16, comm=eng16)
s16_0 = opt16_0.init(tree)
s16 = z1.shard_state(opt16.init(tree), tree, mesh16, pspecs=pspecs16)
g36 = {}
g36["padded"] = list(s16.momentum["layers"].shape)
g36["spec"] = str(s16.momentum["layers"].sharding.spec)
for phase in ("block", "full"):
    u0, ns0 = opt16_0.update(grads16, s16_0, tree, phase)
    uf, nsf = opt16.update(grads16, s16, tree, phase)
    g36[phase + "_updates_bitwise"] = bool(
        jnp.all(u0["layers"] == uf["layers"]))
    g36[phase + "_momentum_bitwise"] = bool(
        jnp.all(ns0.momentum["layers"]
                == np.asarray(nsf.momentum["layers"])[:36]))
a16 = jax.tree.map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), tree)
plan16 = plan_comm(a16, pspecs16, mesh16, labels={"layers": "muon"},
                   block_specs=blocks16, zero1=True, zero1_flatten=True)
a_opt16 = z1.attach(jax.eval_shape(opt16.init, a16), a16, mesh16, zero1=True)
res16 = audit_optimizer(opt16, a16, a_opt16, phase="block")
g36["audited_gather_bytes"] = sum(res16.bytes_of(op) for op in GATHER_OPS)
g36["predicted_apply"] = plan16.predicted_bytes("apply")
out["granite36"] = g36
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("REPRO_FULL_SCHEDULE", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


pytestmark_slow = pytest.mark.slow


@pytest.mark.slow
def test_block_steps_zero_inter_pod_bytes(result):
    """Acceptance: on the (2,2,2) mesh, block steps audit to zero inter-pod
    collective bytes (assert_no_inter_pod ran in-subprocess; re-assert the
    reported numbers)."""
    assert result["block"]["inter_pod"] == 0
    # and in fact zero optimizer collectives at all on this layout
    assert result["block"]["collectives"] == {}


@pytest.mark.slow
def test_full_step_pod_local_gathers_match_plan_per_axis(result):
    """Acceptance: full-step gathers match CommPlan per axis exactly —
    intra-pod ('model') for ordinarily sharded leaves; only the leaf
    genuinely sharded across pods pays a DCN gather."""
    full = result["full"]
    assert full["by_link"] == full["plan_by_link"]
    assert full["by_link"]["dcn"] == result["plan"]["full_by_link"]["dcn"] > 0
    assert "model" in full["by_axes"]
    # the pod-crossing bytes come only from the pod-sharded leaf's axis set
    dcn_keys = [k for k in full["by_axes"] if "pod" in k.split("/")]
    assert dcn_keys and sum(full["by_axes"][k] for k in dcn_keys) \
        == full["by_link"]["dcn"]


@pytest.mark.slow
def test_pipelined_schedule_dcn_first_and_attributed(result):
    """The pipelined full step issues the inter-pod bucket first and every
    measured gather attributes to exactly one stage."""
    assert result["sched"]["first_leaf"] == ["podw"]
    assert result["sched"]["dcn_bytes"] > 0
    assert result["sched"]["attribution"] == "ok", result["sched"]
    assert sum(result["sched"]["stages"].values()) \
        == sum(result["full"]["by_axes"].values())


@pytest.mark.slow
def test_flatten_fallback_bitwise_and_priced(result):
    """Acceptance: the ZeRO-1 flatten fallback is bitwise-equivalent to
    unsharded state, its momentum actually lives sharded+padded, and the
    audited gather-class bytes equal phase + 'apply' predictions."""
    fl = result["flatten"]
    assert fl["padded_shape"] == [4, 16, 32]
    assert "'pod', 'data'" in fl["momentum_spec"]
    for name, ok in fl["parity"].items():
        assert ok, name
    for phase, rec in fl["audits"].items():
        assert rec["predicted_apply"] > 0
        assert rec["gather_bytes"] \
            == rec["predicted_phase"] + rec["predicted_apply"], (phase, rec)


@pytest.mark.slow
def test_granite_36_16_flatten_bitwise(result):
    """Acceptance: the 36-layer/16-way granite shape — fallback pads to 48,
    both phases bitwise-equal to unsharded state, audited bytes ==
    CommPlan.predicted_bytes('apply')."""
    g = result["granite36"]
    assert g["padded"] == [48, 8, 16]
    assert "data" in g["spec"]
    for phase in ("block", "full"):
        assert g[phase + "_updates_bitwise"], phase
        assert g[phase + "_momentum_bitwise"], phase
    assert g["audited_gather_bytes"] == g["predicted_apply"] > 0
