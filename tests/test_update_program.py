"""Compiled UpdateProgram: numerical equivalence with the seed per-leaf
path, program structure (buckets, kernel plans, comm ops), comm pricing
against CommPlan, and ``phase_for_step`` edge cases.

The reference below is a direct port of the seed optimizer's per-leaf
update (nesterov momentum -> per-leaf block/full orthogonalization ->
RMS-matched scale -> weight decay); every program configuration — bucketed,
degenerate per-leaf, layer_shard, and the single-device shard_map engine —
must reproduce it (bitwise for the degenerate program, <= 1e-6 otherwise;
the 8-device engine parity + zero-collective block-step HLO audit live in
tests/test_distributed_engine.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import (
    BlockSpec2D,
    LeafSpec,
    compile_program,
    muon,
    orthogonalize,
    partition_blocks,
    phase_for_step,
    unpartition_blocks,
)
from repro.core import program as program_lib
from repro.kernels import dispatch
from repro.launch.mesh import make_mesh


# --------------------------------------------------------------- reference

MU = 0.9
LR = 0.02
WD = 0.1
RMS_TARGET = 0.2


def reference_update(grads, params, *, phase, block_specs, rms_match=True,
                     weight_decay=WD, nesterov=True):
    """Seed per-leaf update math, first step (zero momentum)."""

    def leaf(path, g, p):
        bs = _lookup(block_specs, path)
        m = g.astype(jnp.float32)  # momentum after step 1 == fp32 grad
        u = g.astype(jnp.float32) + MU * m if nesterov else m
        mdim, ndim = int(u.shape[-2]), int(u.shape[-1])
        if phase == "full" or bs is None or bs.num_blocks == 1:
            o = orthogonalize(u, steps=5)
            m_eff, n_eff = mdim, ndim
        else:
            o = unpartition_blocks(orthogonalize(partition_blocks(u, bs), steps=5), bs)
            m_eff, n_eff = mdim // bs.r, ndim // bs.c
        scale = RMS_TARGET * float(max(m_eff, n_eff)) ** 0.5 if rms_match else 1.0
        upd = -LR * scale * o
        if weight_decay:
            upd = upd - LR * weight_decay * p.astype(jnp.float32)
        return upd.astype(p.dtype)

    return jax.tree_util.tree_map_with_path(leaf, grads, params)


def _lookup(tree, path):
    node = tree
    for k in path:
        node = node[getattr(k, "key", getattr(k, "idx", None))]
    return node


def make_tree(dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    params = {
        "attn": {
            "wq": jax.random.normal(ks[0], (16, 32), dtype),
            "wo": jax.random.normal(ks[1], (32, 16), dtype),
        },
        "layers": {"w": jax.random.normal(ks[2], (3, 16, 32), dtype)},
        "mlp": {"wi": jax.random.normal(ks[3], (16, 32), dtype)},  # wq's bucket
        "odd": jax.random.normal(ks[4], (24, 24), dtype),          # unblocked
    }
    grads = jax.tree.map(
        lambda p, k=ks[5]: 0.1 * jax.random.normal(k, p.shape, p.dtype), params
    )
    blocks = {
        "attn": {"wq": BlockSpec2D(2, 4), "wo": BlockSpec2D(4, 2)},
        "layers": {"w": BlockSpec2D(2, 4)},
        "mlp": {"wi": BlockSpec2D(2, 4)},
        "odd": None,
    }
    return params, grads, blocks


# ------------------------------------------------- equivalence (property)

@pytest.mark.parametrize("phase", ["block", "full"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bucketing", [True, False])
def test_program_matches_seed_per_leaf(phase, dtype, bucketing):
    params, grads, blocks = make_tree(dtype)
    opt = muon(LR, momentum=MU, weight_decay=WD, block_specs=blocks,
               bucketing=bucketing)
    upd, _ = opt.update(grads, opt.init(params), params, phase)
    expect = reference_update(grads, params, phase=phase, block_specs=blocks)
    for a, b, path in zip(
        jax.tree.leaves(upd), jax.tree.leaves(expect),
        [p for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]],
    ):
        assert a.dtype == b.dtype, path
        if not bucketing:
            # degenerate program == the seed path op-for-op -> bitwise
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path))
        else:
            atol = 1e-6 if dtype == jnp.float32 else 1e-4
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=0, atol=atol, err_msg=str(path),
            )


@pytest.mark.parametrize("phase", ["block", "full"])
def test_layer_shard_program_matches_seed(phase, key):
    """The layer_shard CommOp changes placement, never numerics."""
    mesh = make_mesh((1,), ("data",))
    params, grads, blocks = make_tree(jnp.float32)
    opt = muon(LR, momentum=MU, weight_decay=WD, block_specs=blocks,
               layer_shard=(mesh, "data"))
    upd, _ = opt.update(grads, opt.init(params), params, phase)
    expect = reference_update(grads, params, phase=phase, block_specs=blocks)
    for a, b in zip(jax.tree.leaves(upd), jax.tree.leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-6)


@pytest.mark.parametrize("phase", ["block", "full"])
@pytest.mark.parametrize("bucketing", [True, False])
def test_shard_map_engine_program_matches_seed(phase, bucketing):
    """In-process engine-mode program (1x1 mesh: every gather degenerates,
    the shard_map region still executes). The 8-device version of this
    assertion — plus the zero-collective block HLO audit — runs in
    tests/test_distributed_engine.py."""
    from repro.distributed import make_engine

    mesh = make_mesh((1, 1), ("data", "model"))
    params, grads, blocks = make_tree(jnp.float32)
    pspecs = jax.tree.map(lambda p: P(*(None,) * p.ndim), params)
    engine = make_engine(params, pspecs, mesh)
    opt = muon(LR, momentum=MU, weight_decay=WD, block_specs=blocks,
               comm=engine, bucketing=bucketing)
    upd, _ = opt.update(grads, opt.init(params), params, phase)
    expect = reference_update(grads, params, phase=phase, block_specs=blocks)
    for a, b in zip(jax.tree.leaves(upd), jax.tree.leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-6)


# ----------------------------------------------------- phase_for_step edges

def test_phase_for_step_edge_cases():
    # period None (BlockMuon): block forever, including step 0
    assert [phase_for_step(t, None) for t in (0, 1, 7)] == ["block"] * 3
    # period 1 (Muon): full every step
    assert [phase_for_step(t, 1) for t in (0, 1, 7)] == ["full"] * 3
    # period <= 1 degenerates to Muon rather than dividing by zero
    assert phase_for_step(0, 0) == "full"
    # period P: step 0 is a full step (t % P == 0), then P-1 blocks
    assert phase_for_step(0, 5) == "full"
    assert [phase_for_step(t, 5) for t in range(1, 5)] == ["block"] * 4
    assert phase_for_step(5, 5) == "full"
    # invalid phases are rejected by the interpreter
    opt = muon(LR)
    g = {"w": jnp.ones((4, 4))}
    with pytest.raises(ValueError, match="phase"):
        opt.update(g, opt.init(g), g, "warmup")


# -------------------------------------------------------- program structure

def _leaf_specs(params, blocks):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return tuple(
        LeafSpec(
            key=tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path),
            shape=tuple(leaf.shape),
            dtype="float32",
            block=_lookup(blocks, path),
        )
        for path, leaf in flat
    )


def test_gspmd_program_buckets_and_modes():
    params, _, blocks = make_tree(jnp.float32)
    prog = compile_program(_leaf_specs(params, blocks), backend="jnp")
    block, full = prog.phase("block"), prog.phase("full")
    # block: stack mode; wq, wo and wi all block to (8, 8, 8) and share one
    # bucket (orientations merge after blocking); layers/w carries an extra
    # stack dim and odd is unblocked -> 3 ops
    assert all(op.mode == "stack" for op in block.ops)
    assert len(block.ops) == 3
    assert sorted(len(op.leaves) for op in block.ops) == [1, 1, 3]
    # full: concat mode; wq/wi/layers-w all flatten to (., 16, 32) units
    assert all(op.mode == "concat" for op in full.ops)
    assert len(full.ops) == 3
    fat = max(full.ops, key=lambda op: len(op.leaves))
    assert fat.packed_shape == (5, 16, 32)  # 1 + 1 + 3 stacked layers
    # zero predicted communication in GSPMD mode
    assert block.predicted_comm_bytes() == 0
    assert full.predicted_comm_bytes() == 0
    # the interpreter must cover every leaf exactly once per phase
    for prog_phase in (block, full):
        covered = sorted(le.index for op in prog_phase.ops for le in op.leaves)
        assert covered == list(range(len(prog.leaf_specs)))


def test_degenerate_program_is_per_leaf():
    params, _, blocks = make_tree(jnp.float32)
    specs = _leaf_specs(params, blocks)
    prog = compile_program(specs, bucketing=False, backend="jnp")
    for phase in ("block", "full"):
        assert len(prog.phase(phase).ops) == len(specs)
        assert all(len(op.leaves) == 1 for op in prog.phase(phase).ops)


def test_kernel_plans_follow_vmem_fit():
    small = LeafSpec(key=("w",), shape=(16, 32), dtype="float32",
                     block=BlockSpec2D(2, 4))
    huge = LeafSpec(key=("h",), shape=(2, 16384, 16384), dtype="float32", block=None)
    prog = compile_program((small, huge), backend="pallas")
    by_key = {op.leaves[0].index: op for op in prog.phase("full").ops}
    assert by_key[0].kernel == program_lib.KernelPlan(
        "pallas", "fused_chain", ns_steps=5)
    assert by_key[1].kernel == program_lib.KernelPlan(
        "pallas", "tiled", ns_steps=5)
    # jnp backend never plans kernels
    prog_jnp = compile_program((small, huge), backend="jnp")
    assert all(op.kernel.strategy == "jnp" for op in prog_jnp.phase("full").ops)
    # explicit strategy pin wins over the shape-derived plan
    prog_pin = compile_program((small,), backend="pallas", strategy="fused_iter")
    assert all(op.kernel.strategy == "fused_iter" for op in prog_pin.phase("block").ops)


def test_engine_layer_shard_fold():
    """layer_shard composes with the engine as the explicit fold: a
    full-step stack gets one priced all-gather CommOp (slice is local) and
    the kernel plans on the per-rank share; unknown axes are rejected."""
    from repro.distributed.plan import layer_shard_collectives

    class FakeEngine:
        axis_sizes = {"data": 4}

        def spec_for(self, key, ndim):
            return P(*(None,) * ndim)

    stack = LeafSpec(key=("w",), shape=(6, 16, 32), dtype="float32", block=None)
    mat = LeafSpec(key=("v",), shape=(24, 24), dtype="float32", block=None)
    prog = compile_program((stack, mat), backend="jnp", engine=FakeEngine(),
                           layer_shard=(object(), "data"))
    full_ops = {op.leaves[0].index: op for op in prog.phase("full").ops}
    op = full_ops[0]
    assert op.comm is not None and op.comm.kind == "layer_shard"
    assert op.comm.collectives == layer_shard_collectives(
        (6, 16, 32), "data", 4, mode="engine")
    # 6 layers pad to 8 over 4 ranks -> each rank orthogonalizes 2
    assert op.packed_shape == (2, 16, 32)
    # a single 2D matrix has no layer dim to split
    assert full_ops[1].comm is None
    # block phase never layer-shards
    assert all(o.comm is None for o in prog.phase("block").ops)
    with pytest.raises(ValueError, match="axis"):
        compile_program((stack,), engine=FakeEngine(), layer_shard=(object(), "pod"))


def test_engine_layer_shard_skips_zero1_sharded_leaves():
    """A leaf whose lead dim is already data-sharded (ZeRO-1) owns its
    layers outright — the fold would double-count, so it is skipped."""

    class Zero1Engine:
        axis_sizes = {"data": 2}

        def spec_for(self, key, ndim):
            return P("data", *(None,) * (ndim - 1))

    stack = LeafSpec(key=("w",), shape=(4, 16, 32), dtype="float32", block=None)
    prog = compile_program((stack,), backend="jnp", engine=Zero1Engine(),
                           layer_shard=(object(), "data"))
    assert all(op.comm is None for op in prog.phase("full").ops)


# ------------------------------------------------- pipeline schedule artifact

def _engine_for(params, pspecs, mesh):
    from repro.distributed import make_engine

    return make_engine(params, pspecs, mesh)


def _sharded_specs():
    shapes = {
        "big": ((8, 64, 128), P(None, None, "model")),
        "mid": ((64, 128), P(None, "model")),
        "local": ((24, 24), P(None, None)),
    }
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, (s, _) in shapes.items()}
    pspecs = {k: sp for k, (_, sp) in shapes.items()}
    leaf_specs = tuple(
        LeafSpec(key=(k,), shape=s, dtype="float32", block=None)
        for k, (s, _) in shapes.items()
    )
    return params, pspecs, leaf_specs


def test_pipelined_schedule_structure():
    """The compiled PipelineSchedule: full phase only, largest gathers
    first, stage s = gather order[s] / NS order[s-1] / writeback order[s-2],
    every op computed and every leaf written back exactly once."""
    mesh = fake_mesh()
    params, pspecs, leaf_specs = _sharded_specs()
    engine = _engine_for(params, pspecs, mesh)
    prog = compile_program(leaf_specs, backend="jnp", engine=engine)
    full = prog.phase("full")
    sched = full.schedule
    assert sched is not None
    assert prog.phase("block").schedule is None  # block steps stay barrier
    n = len(full.ops)
    assert len(sched.stages) == n + 2
    # descending gather bytes: 'big' (8x64x128) before 'mid' before 'local'
    gb = [sum(le.gather.predicted_bytes for le in full.ops[i].leaves if le.gather)
          for i in sched.order]
    assert gb == sorted(gb, reverse=True)
    computed = [s.compute for s in sched.stages if s.compute is not None]
    assert computed == list(sched.order)
    written = sorted(i for s in sched.stages for i in s.writeback)
    assert written == sorted(le.index for op in full.ops for le in op.leaves)
    for k, stage in enumerate(sched.stages):
        assert stage.index == k
        if stage.gathers:
            assert k < n and set(stage.gathers) <= {
                le.index for le in full.ops[sched.order[k]].leaves
            }
        if stage.compute is not None:
            assert stage.compute == sched.order[k - 1]
    # summary renders the schedule
    assert "pipelined:" in prog.summary() and "exposed" in prog.summary()


def test_pipelined_schedule_pricing_and_toggles():
    """Exposed bytes follow plan.overlappable_ns_bytes; barrier and GSPMD
    programs compile without a schedule; bad names are rejected."""
    from repro.distributed import overlappable_ns_bytes

    mesh = fake_mesh()
    params, pspecs, leaf_specs = _sharded_specs()
    engine = _engine_for(params, pspecs, mesh)
    prog = compile_program(leaf_specs, backend="jnp", engine=engine,
                           full_schedule="pipelined", ns_steps=5)
    full = prog.phase("full")
    sched = full.schedule
    for stage in sched.stages:
        expect_overlap = (
            overlappable_ns_bytes(full.ops[stage.compute].packed_shape, 5)
            if stage.compute is not None else 0
        )
        assert stage.overlap_bytes == expect_overlap
        assert stage.exposed_bytes == max(0, stage.gather_bytes - stage.overlap_bytes)
    assert sched.gather_bytes == full.predicted_comm_bytes()
    assert 0 < sched.exposed_bytes <= sched.gather_bytes
    # prologue gather is fully exposed (nothing to hide behind)
    assert sched.stages[0].exposed_bytes == sched.stages[0].gather_bytes > 0
    # toggles
    barrier = compile_program(leaf_specs, backend="jnp", engine=engine,
                              full_schedule="barrier")
    assert barrier.phase("full").schedule is None
    assert barrier.phase("full").predicted_comm_bytes() == full.predicted_comm_bytes()
    gspmd = compile_program(leaf_specs, backend="jnp")
    assert gspmd.phase("full").schedule is None
    with pytest.raises(ValueError, match="full_schedule"):
        compile_program(leaf_specs, backend="jnp", engine=engine,
                        full_schedule="eager")
    with pytest.raises(ValueError, match="full_schedule"):
        muon(LR, full_schedule="eager")


# ------------------------------------------- engine mode: comm ops == plan

def fake_mesh(shape=(2, 4), axes=("data", "model")):
    devs = np.array(jax.devices() * int(np.prod(shape)))[: int(np.prod(shape))]
    return Mesh(devs.reshape(shape), axes)


@pytest.fixture(scope="module")
def granite_muon():
    from repro.configs import get_config
    from repro.core import label_tree
    from repro.models.model import init_params
    from repro.sharding import specs as sh

    cfg = get_config("granite-8b")
    mesh = fake_mesh()
    a_params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    pspecs = sh.param_specs(a_params, cfg, mesh)
    labels = label_tree(a_params)
    bspecs = sh.block_specs_for(a_params, pspecs, mesh)
    bspecs = jax.tree.map(lambda l, b: b if l == "muon" else None, labels, bspecs)
    return mesh, a_params, pspecs, labels, bspecs


def test_engine_program_comm_matches_comm_plan(granite_muon):
    """The engine-mode program's gather CommOps are priced byte-for-byte
    like CommPlan (whose full-step prediction the HLO audit has measured
    exact) — program and plan are two views of one schedule."""
    from repro.distributed import make_engine, plan_comm

    mesh, a_params, pspecs, labels, bspecs = granite_muon
    engine = make_engine(a_params, pspecs, mesh)
    plan = plan_comm(a_params, pspecs, mesh, labels=labels, block_specs=bspecs)

    # muon-masked leaf specs, in the optimizer's flat order
    flat = jax.tree_util.tree_flatten_with_path(a_params)[0]
    flat_labels = jax.tree.leaves(labels)
    flat_blocks = jax.tree_util.tree_flatten(
        bspecs, is_leaf=lambda x: x is None or isinstance(x, BlockSpec2D)
    )[0]
    specs = tuple(
        LeafSpec(
            key=tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path),
            shape=tuple(leaf.shape), dtype="float32", block=bs,
        )
        for (path, leaf), lab, bs in zip(flat, flat_labels, flat_blocks)
        if lab == "muon"
    )
    prog = compile_program(specs, backend="jnp", engine=engine)
    assert prog.phase("full").predicted_comm_bytes() == plan.predicted_bytes("full") > 0
    assert prog.phase("block").predicted_comm_bytes() == plan.predicted_bytes("block") == 0

    # structure: on block steps no blocked leaf gathers; on full steps every
    # model-sharded leaf gathers exactly its plan bytes
    by_path = {l.path: l for l in plan.leaves}
    for le, ls in zip(prog.phase("full").leaf_execs, specs):
        planned = by_path["/".join(ls.key)].predicted_bytes("full")
        got = le.gather.predicted_bytes if le.gather else 0
        assert got == planned, ls.key

    # inside the body everything is local -> concat packing, fewer ops than leaves
    assert all(op.mode == "concat" for op in prog.phase("block").ops)
    assert len(prog.phase("block").ops) < len(specs)


def test_engine_program_block_step_unblocked_sharded_leaf_gathers(granite_muon):
    """A sharded muon leaf WITHOUT a usable block grid pays its gathers on
    block steps too (the plan's documented exception)."""
    from repro.distributed import make_engine

    mesh, a_params, pspecs, *_ = granite_muon
    engine = make_engine(a_params, pspecs, mesh)
    ls = LeafSpec(key=("layers", "mlp", "wi"),
                  shape=(36, 4096, 12800), dtype="float32", block=None)
    prog = compile_program((ls,), backend="jnp", engine=engine)
    le = prog.phase("block").leaf_execs[0]
    assert le.gather is not None and le.gather.predicted_bytes > 0
    # with its block grid the same leaf is local on block steps
    ls_b = LeafSpec(key=ls.key, shape=ls.shape, dtype="float32",
                    block=BlockSpec2D(1, 4))
    prog_b = compile_program((ls_b,), backend="jnp", engine=engine)
    assert prog_b.phase("block").leaf_execs[0].gather is None
    assert prog_b.phase("block").predicted_comm_bytes() == 0


def test_program_summary_renders():
    params, _, blocks = make_tree(jnp.float32)
    prog = compile_program(_leaf_specs(params, blocks), backend="jnp")
    text = prog.summary()
    assert "block:" in text and "full:" in text and "concat" in text
    assert "schedule: barrier" in text  # GSPMD full steps have no pipeline


# --------------------------- 8-device: pipelined parity + schedule audit

_PIPELINE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import LeafSpec, compile_program, muon
from repro.core.blocking import BlockSpec2D
from repro.distributed import (
    assert_pipelined_matches_plan, audit_optimizer, make_engine, plan_comm,
)
from repro.distributed import zero1 as z1

mesh = make_mesh((2, 4), ("data", "model"))
layout = {
    "wq":    ((64, 128),    P(None, "model"),       BlockSpec2D(1, 4)),
    "wo":    ((128, 64),    P("model", None),       BlockSpec2D(4, 1)),
    "stack": ((4, 32, 64),  P(None, None, "model"), BlockSpec2D(1, 4)),
    "local": ((24, 24),     P(None, None),          None),
}
pspecs = {k: sp for k, (s, sp, b) in layout.items()}
blocks = {k: b for k, (s, sp, b) in layout.items()}
params = {
    k: jax.device_put(
        jax.random.normal(jax.random.PRNGKey(i), s),
        NamedSharding(mesh, sp))
    for i, (k, (s, sp, b)) in enumerate(layout.items())
}
grads = jax.tree.map(lambda p: 0.1 * p, params)
labels = {k: "muon" for k in layout}

out = {"parity": {}, "audit": {}}

# --- bitwise parity: pipelined == barrier, phases x zero1 x bucketing ---
for zero1 in (False, True):
    eng = make_engine(params, pspecs, mesh, zero1=zero1)
    for bucketing in (True, False):
        for phase in ("block", "full"):
            upd = {}
            for sched in ("pipelined", "barrier"):
                opt = muon(0.02, block_specs=blocks, comm=eng,
                           bucketing=bucketing, full_schedule=sched)
                state = opt.init(params)
                if zero1:
                    state = z1.shard_state(state, params, mesh, pspecs=pspecs)
                upd[sched], _ = opt.update(grads, state, params, phase)
            bitwise = all(
                bool(jnp.all(a == b))
                for a, b in zip(jax.tree.leaves(upd["pipelined"]),
                                jax.tree.leaves(upd["barrier"]))
            )
            out["parity"][f"z{int(zero1)}_b{int(bucketing)}_{phase}"] = bitwise

# --- HLO audit: per-bucket gathers, total == CommPlan, stage attribution ---
a_params = jax.tree.map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), params)
plan = plan_comm(a_params, pspecs, mesh, labels=labels, block_specs=blocks)
eng = make_engine(params, pspecs, mesh)
leaf_specs = tuple(
    LeafSpec(key=(k,), shape=s, dtype="float32", block=b)
    for k, (s, sp, b) in layout.items()
)
prog = compile_program(leaf_specs, backend="jnp", engine=eng)
opt = muon(0.02, block_specs=blocks, comm=eng, full_schedule="pipelined")
a_opt = jax.eval_shape(opt.init, a_params)
a_opt = z1.attach(a_opt, a_params, mesh)
res = audit_optimizer(opt, a_params, a_opt, phase="full")
try:
    attributed = assert_pipelined_matches_plan(res, prog.phase("full"), plan)
    out["audit"]["full"] = {
        "ok": True,
        "stages": {str(k): v for k, v in attributed.items()},
        "gather_events": res.count_of("all-gather"),
        "gather_bytes": res.bytes_of("all-gather"),
        "predicted": plan.predicted_bytes("full"),
    }
except AssertionError as e:
    out["audit"]["full"] = {"ok": False, "error": str(e)}

# --- engine layer_shard fold: exact comm, parity with the plain engine ---
o_plain = muon(0.02, block_specs=blocks, comm=eng)
o_ls = muon(0.02, block_specs=blocks, comm=eng, layer_shard=(mesh, "data"))
u0, _ = o_plain.update(grads, o_plain.init(params), params, "full")
u1, _ = o_ls.update(grads, o_ls.init(params), params, "full")
out["layer_shard_err"] = max(
    float(jnp.max(jnp.abs(a - b)))
    for a, b in zip(jax.tree.leaves(u0), jax.tree.leaves(u1))
)
prog_ls = compile_program(leaf_specs, backend="jnp", engine=eng,
                          layer_shard=(mesh, "data"))
res_ls = audit_optimizer(o_ls, a_params, a_opt, phase="full")
out["layer_shard_audit"] = {
    "measured": res_ls.bytes_of("all-gather"),
    "predicted": prog_ls.phase("full").predicted_comm_bytes(),
}
# the stage-attribution helper must handle the fold's in-compute gathers
try:
    assert_pipelined_matches_plan(res_ls, prog_ls.phase("full"), plan)
    out["layer_shard_audit"]["attribution"] = "ok"
except AssertionError as e:
    out["layer_shard_audit"]["attribution"] = str(e)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def pipeline_result():
    import json as _json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("REPRO_FULL_SCHEDULE", None)  # schedules are explicit in-script
    proc = subprocess.run(
        [sys.executable, "-c", _PIPELINE_SCRIPT], capture_output=True,
        text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    return _json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_pipelined_bitwise_parity_8dev(pipeline_result):
    """Pipelined == barrier BITWISE on the 8-device mesh, across phases x
    zero1 x bucketing (the pipeline only reorders ops; optimization_barrier
    is value-identity)."""
    assert pipeline_result["parity"], "no parity cases ran"
    for name, bitwise in pipeline_result["parity"].items():
        assert bitwise, name


@pytest.mark.slow
def test_pipelined_full_step_audit_8dev(pipeline_result):
    """The pipelined full step issues per-bucket (not monolithic) gathers
    whose total equals CommPlan.predicted_bytes exactly, and every HLO
    gather attributes to exactly one pipeline stage (no duplicates)."""
    audit = pipeline_result["audit"]["full"]
    assert audit.get("ok"), audit.get("error")
    assert audit["gather_bytes"] == audit["predicted"] > 0
    assert audit["gather_events"] >= 2  # per-bucket, not one monolithic op
    assert sum(audit["stages"].values()) == audit["predicted"]


@pytest.mark.slow
def test_engine_layer_shard_8dev(pipeline_result):
    """The engine layer_shard fold is numerically exact and its one
    all-gather per stacked bucket is priced exactly."""
    assert pipeline_result["layer_shard_err"] == 0.0
    ls = pipeline_result["layer_shard_audit"]
    assert ls["measured"] == ls["predicted"] > 0
    assert ls["attribution"] == "ok", ls["attribution"]
