"""Batched/fused NS execution engine: fused kernel, bucketing, dispatch.

Acceptance coverage for the engine PR:
  * fused single-launch kernel parity vs ref.py (batched, non-square,
    non-tile-multiple, bf16) in interpret mode
  * shape bucketing round-trip: bucketed vs per-leaf optimizer updates are
    bitwise-close on a real param pytree
  * optimizer-step NS dispatch count == number of shape buckets
  * backend registry selection (argument / override / env var)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BlockSpec2D,
    adamw,
    bucketed_orthogonalize,
    combine,
    label_tree,
    muon,
    plan_buckets,
)
from repro.core import newton_schulz
from repro.core.newton_schulz import PAPER_COEFFS, orthogonalize, orthogonalize_jnp
from repro.kernels import dispatch
from repro.kernels.newton_schulz import fused, ref

from conftest import tiny_cfg


# ---------------------------------------------------------------- fused kernel

FUSED_SHAPES = [
    (1, 64, 64),     # single square matrix
    (3, 64, 96),     # batched, non-square
    (2, 100, 36),    # tall units (kernel path transposes), ragged dims
    (5, 17, 130),    # non-tile-multiple rows AND cols (exercises padding)
    (4, 8, 8),       # tiny blocks, way below one tile
]


@pytest.mark.parametrize("shape", FUSED_SHAPES)
def test_fused_iteration_matches_ref(shape):
    x = jax.random.normal(jax.random.PRNGKey(shape[1]), shape)
    x = x / jnp.linalg.norm(x, axis=(-2, -1), keepdims=True)
    out = fused.ns_iteration_batched(x, PAPER_COEFFS, interpret=True)
    expect = ref.batched_ns_iteration_ref(x, PAPER_COEFFS)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("shape", FUSED_SHAPES)
@pytest.mark.parametrize("steps", [1, 5])
def test_fused_orthogonalize_matches_ref(shape, steps):
    g = jax.random.normal(jax.random.PRNGKey(steps), shape)
    out = fused.orthogonalize(g, steps=steps, interpret=True)
    expect = ref.batched_newton_schulz_ref(g, steps, PAPER_COEFFS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)
    # and against the jnp engine, which is the optimizer's default
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(orthogonalize_jnp(g, steps=steps)), atol=1e-5
    )


def test_fused_bf16_input():
    g = jax.random.normal(jax.random.PRNGKey(7), (2, 48, 72), jnp.bfloat16)
    out = fused.orthogonalize(g, steps=5, interpret=True)
    assert out.dtype == jnp.bfloat16
    expect = ref.batched_newton_schulz_ref(g, 5, PAPER_COEFFS)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_fused_leading_dims_and_2d():
    g = jax.random.normal(jax.random.PRNGKey(9), (2, 3, 32, 48))
    out = fused.orthogonalize(g, steps=3, interpret=True)
    assert out.shape == g.shape
    g2 = g[0, 0]
    out2 = fused.orthogonalize(g2, steps=3, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0, 0]), np.asarray(out2), atol=1e-6)


def test_fits_vmem_gate():
    assert fused.fits_vmem((64, 256, 256))
    assert fused.fits_vmem((2048, 128))          # skinny: small side bounds Gram
    assert not fused.fits_vmem((8192, 8192))     # Gram alone is 256 MiB


# -------------------------------------------------------------- fused chain

@pytest.mark.parametrize("shape", [(3, 64, 96), (5, 17, 130), (2, 100, 36)])
def test_fused_chain_matches_per_iteration(shape):
    """Acceptance: the whole-chain kernel (one launch for all K iterations)
    is parity with the per-iteration kernel and the ref oracle to 1e-5."""
    g = jax.random.normal(jax.random.PRNGKey(shape[-1]), shape)
    chain = fused.orthogonalize(g, steps=5, interpret=True, chain=True)
    iter_ = fused.orthogonalize(g, steps=5, interpret=True, chain=False)
    np.testing.assert_allclose(np.asarray(chain), np.asarray(iter_), atol=1e-5)
    expect = ref.batched_newton_schulz_ref(g, 5, PAPER_COEFFS)
    np.testing.assert_allclose(np.asarray(chain), np.asarray(expect), atol=1e-5)


def test_fused_chain_is_one_launch():
    """K iterations -> ONE pallas_call (vs K per-iteration launches). Fresh
    shapes force fresh traces so the module's launch counter delta is exact."""
    g = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 88))
    before = fused.launch_count()
    fused.orthogonalize(g, steps=5, interpret=True, chain=True)
    assert fused.launch_count() - before == 1
    g2 = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 88))
    before = fused.launch_count()
    fused.orthogonalize(g2, steps=5, interpret=True, chain=False)
    assert fused.launch_count() - before == 5


def test_tiled_batched_fallback_matches_jnp():
    """Oversized stacks route through the tiled 3-launch path per matrix
    (ROADMAP: previously a silent jnp fallback). Forced via the strategy pin
    so the test doesn't need an actually-VMEM-overflowing array."""
    g = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 24, 40))
    out = orthogonalize(g, steps=3, backend="pallas", strategy="tiled")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(orthogonalize_jnp(g, steps=3)), atol=1e-5
    )
    with pytest.raises(ValueError, match="stacked"):
        from repro.kernels.newton_schulz import ops

        ops.orthogonalize_batched(g[0, 0], steps=3)


def test_plan_strategy_decides_per_shape(monkeypatch):
    monkeypatch.delenv(dispatch.STRATEGY_ENV_VAR, raising=False)
    assert dispatch.plan_strategy((4, 64, 128), "jnp") == "jnp"
    assert dispatch.plan_strategy((4, 64, 128), "pallas") == "fused_chain"
    assert dispatch.plan_strategy((8192, 8192), "pallas") == "tiled"
    monkeypatch.setenv(dispatch.STRATEGY_ENV_VAR, "fused_iter")
    assert dispatch.plan_strategy((4, 64, 128), "pallas") == "fused_iter"
    monkeypatch.setenv(dispatch.STRATEGY_ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        dispatch.plan_strategy((4, 64, 128), "pallas")


# (128, n): counted fused VMEM = 2048*n_p + 320 KiB — chosen to fit the full
# 16 MiB budget but NOT the pipeline-reserved one (14 MiB).
_EDGE_SHAPE = (128, 7936)


def test_plan_strategy_pipeline_vmem_budget(monkeypatch):
    """A pipelined stage plans against the reduced VMEM budget: a shape
    that fused-chains under the full budget falls back to tiled when the
    in-flight gather's double buffers are reserved."""
    monkeypatch.delenv(dispatch.STRATEGY_ENV_VAR, raising=False)
    assert fused.fits_vmem(_EDGE_SHAPE)
    assert not fused.fits_vmem(_EDGE_SHAPE, budget=dispatch.pipeline_vmem_budget())
    assert dispatch.plan_strategy(_EDGE_SHAPE, "pallas") == "fused_chain"
    assert dispatch.plan_strategy(
        _EDGE_SHAPE, "pallas", vmem_budget=dispatch.pipeline_vmem_budget()
    ) == "tiled"


def test_pipelined_program_respects_vmem_reserve(monkeypatch):
    """End-to-end: the engine-mode pipelined full phase plans the edge
    shape as tiled while the barrier program keeps the fused chain."""
    from jax.sharding import PartitionSpec as P

    from repro.core import LeafSpec, compile_program

    monkeypatch.delenv(dispatch.STRATEGY_ENV_VAR, raising=False)

    class FakeEngine:
        axis_sizes = {"model": 4}

        def spec_for(self, key, ndim):
            return P(*([None] * (ndim - 1) + ["model"]))

    spec = LeafSpec(key=("w",), shape=_EDGE_SHAPE, dtype="float32", block=None)
    pipelined = compile_program((spec,), backend="pallas", engine=FakeEngine(),
                                full_schedule="pipelined")
    barrier = compile_program((spec,), backend="pallas", engine=FakeEngine(),
                              full_schedule="barrier")
    assert pipelined.phase("full").ops[0].kernel.strategy == "tiled"
    assert barrier.phase("full").ops[0].kernel.strategy == "fused_chain"
    # the reserve is a full-phase concern; block steps keep the full budget
    assert pipelined.phase("block").ops[0].kernel.strategy == "fused_chain"


# ------------------------------------------------------------------- bucketing

def test_plan_buckets_groups_by_unit_shape():
    leaves = [
        jax.ShapeDtypeStruct((32, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 32), jnp.float32),   # own-orientation bucket
        jax.ShapeDtypeStruct((2, 32, 64), jnp.float32),  # stacked layers
        jax.ShapeDtypeStruct((16, 16), jnp.float32),
    ]
    specs = [None, None, None, None]
    buckets = plan_buckets(leaves, specs)
    assert list(buckets) == [
        (32, 64, "float32"), (64, 32, "float32"), (16, 16, "float32")
    ]
    assert buckets[(32, 64, "float32")] == [0, 2]

    # blocking changes the unit shape: a (2,2)-blocked 16x16 is 4 8x8 units
    buckets = plan_buckets(leaves, [None, None, None, BlockSpec2D(2, 2)])
    assert (8, 8, "float32") in buckets


def test_bucketed_orthogonalize_one_call_per_bucket():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    leaves = [
        jax.random.normal(keys[0], (32, 64)),
        jax.random.normal(keys[1], (64, 32)),
        jax.random.normal(keys[2], (2, 32, 64)),
        jax.random.normal(keys[3], (16, 16)),
    ]
    specs = [None, None, None, BlockSpec2D(2, 2)]
    calls = []

    def orth(x):
        calls.append(x.shape)
        return orthogonalize_jnp(x, steps=5)

    outs = bucketed_orthogonalize(leaves, specs, orth)
    assert len(calls) == len(plan_buckets(leaves, specs)) == 3
    assert calls[0] == (3, 32, 64)  # 1 + 2 stacked units share the bucket
    for leaf, out, spec in zip(leaves, outs, specs):
        assert out.shape == leaf.shape and out.dtype == leaf.dtype
        if spec is None:
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(orthogonalize_jnp(leaf, steps=5)),
                atol=1e-6,
            )


def test_stack_mode_buckets_by_blocked_shape():
    """Stack packing: strict per-shape buckets via a new leading axis."""
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    leaves = [
        jax.random.normal(keys[0], (16, 32)),
        jax.random.normal(keys[1], (16, 32)),
        jax.random.normal(keys[2], (2, 16, 32)),  # extra lead dim: own bucket
    ]
    specs = [BlockSpec2D(1, 2), BlockSpec2D(1, 2), BlockSpec2D(1, 2)]
    calls = []

    def orth(x):
        calls.append(x.shape)
        return orthogonalize_jnp(x, steps=5)

    outs = bucketed_orthogonalize(leaves, specs, orth, mode="stack")
    assert calls == [(2, 2, 16, 16), (2, 2, 16, 16)]
    assert len(plan_buckets(leaves, specs, mode="stack")) == 2
    # parity with the concat packing on identical inputs
    outs_c = bucketed_orthogonalize(leaves, specs, orth, mode="concat")
    for a, b in zip(outs, outs_c):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _real_param_setup():
    from repro.models.model import init_params

    cfg = tiny_cfg("muonbp-960m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    grads = jax.tree.map(lambda p: 0.01 * jnp.ones_like(p), params)
    labels = label_tree(params)
    blocks = jax.tree.map(
        lambda p: BlockSpec2D(1, 4)
        if p.ndim >= 2 and p.shape[-1] % 4 == 0
        else None,
        params,
    )
    blocks = jax.tree.map(
        lambda b, l: b if l == "muon" else None, blocks, labels,
        is_leaf=lambda x: x is None or isinstance(x, BlockSpec2D),
    )
    return params, grads, labels, blocks


@pytest.mark.parametrize("phase", ["block", "full"])
def test_bucketed_update_matches_per_leaf_on_real_pytree(phase):
    """Acceptance: bucketed vs per-leaf optimizer updates bitwise-close."""
    params, grads, labels, blocks = _real_param_setup()

    def build(bucketing):
        matrix = muon(1e-3, block_specs=blocks, bucketing=bucketing)
        return combine({"muon": matrix, "adamw": adamw(1e-3)}, labels)

    on, off = build(True), build(False)
    u_on, _ = on.update(grads, on.init(params), params, phase)
    u_off, _ = off.update(grads, off.init(params), params, phase)
    flat_on = jax.tree.leaves(u_on)
    flat_off = jax.tree.leaves(u_off)
    assert len(flat_on) == len(flat_off)
    for a, b in zip(flat_on, flat_off):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0, atol=1e-7,
        )


@pytest.mark.parametrize("phase", ["block", "full"])
def test_ns_dispatch_count_equals_bucket_count(phase, monkeypatch):
    """Acceptance: one NS chain per shape bucket, not per parameter leaf."""
    params, grads, labels, blocks = _real_param_setup()
    matrix = muon(1e-3, block_specs=blocks, bucketing=True)
    opt = combine({"muon": matrix, "adamw": adamw(1e-3)}, labels)
    state = opt.init(params)

    calls = []
    real = newton_schulz.orthogonalize
    monkeypatch.setattr(
        newton_schulz, "orthogonalize",
        lambda g, *a, **kw: (calls.append(g.shape), real(g, *a, **kw))[1],
    )
    opt.update(grads, state, params, phase)

    flat_labels = jax.tree.leaves(labels)
    flat_params = jax.tree.leaves(params)
    flat_blocks = jax.tree_util.tree_flatten(
        blocks, is_leaf=lambda x: x is None or isinstance(x, BlockSpec2D)
    )[0]
    leaves, specs = [], []
    for p, b, l in zip(flat_params, flat_blocks, flat_labels):
        if l != "muon":
            continue
        leaves.append(jax.ShapeDtypeStruct(p.shape, jnp.float32))
        specs.append(b if phase == "block" else None)
    specs = [s if (s is not None and s.num_blocks > 1) else None for s in specs]
    mode = "stack" if phase == "block" else "concat"
    expected = len(plan_buckets(leaves, specs, mode=mode))

    n_muon_leaves = len(leaves)
    assert len(calls) == expected
    assert expected < n_muon_leaves  # bucketing actually coalesced dispatches


# ------------------------------------------------- cross-bucket launch sharing

def test_shared_launch_groups_merges_dtypes():
    groups = dispatch.shared_launch_groups([
        (16, 32, "float32"), (16, 32, "bfloat16"), (64, 64, "float32"),
    ])
    assert groups[(16, 32)] == ("float32", ("bfloat16", "float32"))
    assert groups[(64, 64)] == ("float32", ())  # single dtype: no epilogue


def test_cross_bucket_launch_sharing_in_program():
    """Buckets with the same unit shape but different dtypes share ONE
    launch with a cast epilogue (ROADMAP item): the merge is recorded in
    the compiled KernelPlan and the numerics match per-dtype launches
    exactly (every NS kernel computes in fp32 internally)."""
    from repro.core import LeafSpec, compile_program
    from repro.core.program import execute_ops

    specs = (
        LeafSpec(key=("a",), shape=(16, 32), dtype="float32", block=None),
        LeafSpec(key=("b",), shape=(3, 16, 32), dtype="bfloat16", block=None),
        LeafSpec(key=("c",), shape=(16, 16), dtype="float32", block=None),
    )
    prog = compile_program(specs, backend="jnp")
    full = prog.phase("full")
    assert len(full.ops) == 2  # (16,32) f32+bf16 merged; (16,16) alone
    merged = next(op for op in full.ops if len(op.leaves) == 2)
    assert merged.compute_dtype == "float32"
    assert merged.kernel.merged_dtypes == ("bfloat16", "float32")
    assert merged.packed_shape == (4, 16, 32)
    assert "merge=bfloat16+float32" in prog.summary()
    solo = next(op for op in full.ops if len(op.leaves) == 1)
    assert solo.compute_dtype is None and solo.kernel.merged_dtypes == ()

    # numerics: merged launch == per-dtype launches, leaf dtypes preserved
    leaves = [
        jax.random.normal(jax.random.PRNGKey(0), (16, 32), jnp.float32),
        jax.random.normal(jax.random.PRNGKey(1), (3, 16, 32), jnp.bfloat16),
        jax.random.normal(jax.random.PRNGKey(2), (16, 16), jnp.float32),
    ]
    calls = []

    def orth(x, strategy=None):
        calls.append(x.shape)
        return orthogonalize_jnp(x, steps=5)

    outs = execute_ops(full.ops, leaves, orth)
    assert len(calls) == 2  # one launch for the merged bucket
    for leaf, out in zip(leaves, outs):
        assert out.dtype == leaf.dtype and out.shape == leaf.shape
        expect = orthogonalize_jnp(leaf.astype(jnp.float32), steps=5)
        atol = 1e-2 if leaf.dtype == jnp.bfloat16 else 1e-6
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(expect.astype(leaf.dtype), np.float32),
            rtol=0, atol=atol, err_msg=str(leaf.shape),
        )

    # the degenerate per-leaf program never merges
    prog_pl = compile_program(specs, backend="jnp", bucketing=False)
    assert all(op.compute_dtype is None for op in prog_pl.phase("full").ops)
    assert len(prog_pl.phase("full").ops) == 3


def test_stack_mode_never_merges_dtypes():
    """GSPMD block steps stack-pack to keep operand shardings intact; a
    cross-dtype cast there would change the moved bytes, so dtypes stay in
    their own buckets."""
    from repro.core import LeafSpec, compile_program

    specs = (
        LeafSpec(key=("a",), shape=(16, 32), dtype="float32",
                 block=BlockSpec2D(2, 4)),
        LeafSpec(key=("b",), shape=(16, 32), dtype="bfloat16",
                 block=BlockSpec2D(2, 4)),
    )
    prog = compile_program(specs, backend="jnp")
    assert len(prog.phase("block").ops) == 2
    assert all(op.compute_dtype is None for op in prog.phase("block").ops)
    # the same two leaves merge on the (concat) full phase
    assert len(prog.phase("full").ops) == 1


# -------------------------------------------------------------------- dispatch

def test_backend_selection_precedence(monkeypatch):
    assert set(dispatch.available_backends()) >= {"jnp", "pallas"}
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    assert dispatch.get_backend() == "jnp"
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    assert dispatch.get_backend() == "pallas"
    with dispatch.use_backend("jnp"):
        assert dispatch.get_backend() == "jnp"
    assert dispatch.get_backend() == "pallas"
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    with pytest.raises(ValueError):
        dispatch.set_backend("nope")
    with pytest.raises(ValueError):
        dispatch.orthogonalize(
            jnp.ones((4, 4)), steps=1, coeffs=PAPER_COEFFS, eps=1e-7,
            backend="nope",
        )


@pytest.mark.parametrize("shape", [(32, 64), (3, 24, 40)])
def test_pallas_backend_matches_jnp(shape):
    g = jax.random.normal(jax.random.PRNGKey(11), shape)
    a = orthogonalize(g, steps=5, backend="jnp")
    b = orthogonalize(g, steps=5, backend="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_env_var_routes_optimizer(monkeypatch):
    """REPRO_NS_BACKEND flips the engine under the public entry point."""
    g = jax.random.normal(jax.random.PRNGKey(13), (16, 24))
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    out = orthogonalize(g, steps=3)
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(orthogonalize_jnp(g, steps=3)), atol=1e-5
    )
