"""Optimizer-variant programs (``core/variants.py``).

Per-variant numerical parity against a per-leaf jnp reference (phases x
dtypes x bucketing), Turbo-Muon's strictly reduced NS launch count,
bitwise Pallas-vs-jnp agreement for the NorMuon epilogue kernel, the
revived Dion program, and property-style invariants for the kernel plans
and cross-bucket launch groups under variant K / precondition / epilogue
stages (hypothesis when available, deterministic parametrization
otherwise, per the test_blocking convention)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import (
    BlockSpec2D,
    LeafSpec,
    VARIANTS,
    VariantSpec,
    build_variant,
    compile_program,
    get_variant,
    muon,
    orthogonalize,
    partition_blocks,
    spectral_norm_est,
    unpartition_blocks,
    variant_names,
)
from repro.core.dion import DionState, _FactorEngineView
from repro.core.muon import SPECTRAL_MARGIN
from repro.kernels import dispatch
from repro.kernels import normuon as normuon_lib


MU = 0.9
LR = 0.02
WD = 0.1
RMS_TARGET = 0.2


# --------------------------------------------------------------- references

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
        "mlp": {"wi": jax.random.normal(ks[3], (16, 32), dtype)},
        "odd": jax.random.normal(ks[4], (24, 24), dtype),
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


def _blocked_input(g, bs, phase):
    """First-step NS input + effective dims, per the seed per-leaf path."""
    m = g.astype(jnp.float32)           # momentum after step 1 == fp32 grad
    u = g.astype(jnp.float32) + MU * m  # nesterov
    mdim, ndim = int(u.shape[-2]), int(u.shape[-1])
    if phase == "full" or bs is None or bs.num_blocks == 1:
        return u, None, mdim, ndim
    return partition_blocks(u, bs), bs, mdim // bs.r, ndim // bs.c


def _scale_and_decay(o, p, m_eff, n_eff):
    scale = RMS_TARGET * float(max(m_eff, n_eff)) ** 0.5
    upd = -LR * scale * o - LR * WD * p.astype(jnp.float32)
    return upd.astype(p.dtype)


def turbo_reference(grads, params, *, phase, block_specs, ns_steps=3):
    """Per-leaf Turbo-Muon: spectral pre-scale, then a K-2 chain with the
    kernels' entry Frobenius normalization disabled."""

    def leaf(path, g, p):
        ub, bs, m_eff, n_eff = _blocked_input(g, _lookup(block_specs, path), phase)
        sigma = spectral_norm_est(ub).astype(ub.dtype)
        o = orthogonalize(ub / (sigma * SPECTRAL_MARGIN + 1e-7),
                          steps=ns_steps, normalize=False)
        if bs is not None:
            o = unpartition_blocks(o, bs)
        return _scale_and_decay(o, p, m_eff, n_eff)

    return jax.tree_util.tree_map_with_path(leaf, grads, params)


def normuon_reference(grads, params, *, phase, block_specs):
    """Per-leaf NorMuon: seed K=5 orthogonalization, then the leaf-level
    neuron-norm epilogue on fresh (zero) statistics."""

    def leaf(path, g, p):
        ub, bs, m_eff, n_eff = _blocked_input(g, _lookup(block_specs, path), phase)
        o = orthogonalize(ub, steps=5)
        if bs is not None:
            o = unpartition_blocks(o, bs)
        v0 = jnp.zeros(o.shape[:-1] + (1,), jnp.float32)
        c0 = jnp.zeros((), jnp.int32)
        o, v, c = normuon_lib.apply_neuron_norm(
            o, v0, c0, beta2=0.95, eps=1e-8,
            refresh=phase == "full", backend="jnp",
        )
        return _scale_and_decay(o, p, m_eff, n_eff), v, c

    out = jax.tree_util.tree_map_with_path(leaf, grads, params)
    upd = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    v = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    c = jax.tree.map(lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple))
    return upd, v, c


# ------------------------------------------------------------------ registry

def test_registry_names_and_get():
    assert variant_names() == ("muon", "turbo_muon", "normuon", "dion")
    assert get_variant(None) is VARIANTS["muon"]
    spec = VariantSpec(name="custom", ns_steps_delta=-1)
    assert get_variant(spec) is spec
    assert get_variant("turbo_muon").ns_steps_delta == -2
    assert get_variant("turbo_muon").precondition == "spectral_scale"
    assert get_variant("normuon").epilogue == "neuron_norm"
    assert get_variant("dion").low_rank
    with pytest.raises(ValueError, match="unknown optimizer variant"):
        get_variant("muonx")


def test_muon_rejects_low_rank_variant():
    with pytest.raises(ValueError, match="low-rank"):
        muon(LR, variant="dion")


def test_build_variant_routes():
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (24, 16))}
    grads = jax.tree.map(lambda p: 0.1 * p, params)
    opt = build_variant("dion", LR, rank=4, weight_decay=WD,
                        bucketing=False, ns_strategy="jnp")
    state = opt.init(params)
    assert isinstance(state, DionState)
    upd, _ = opt.update(grads, state, params, "block")
    assert upd["w"].shape == (24, 16)
    # muon-family routing passes the spec through
    opt_t = build_variant("turbo_muon", LR, momentum=MU, weight_decay=WD)
    upd_t, _ = opt_t.update(grads, opt_t.init(params), params, "full")
    expect = turbo_reference(grads, params, phase="full", block_specs={"w": None})
    np.testing.assert_allclose(np.asarray(upd_t["w"]), np.asarray(expect["w"]),
                               rtol=0, atol=1e-6)


def test_engine_config_variant_env(monkeypatch):
    from repro.configs.base import NSEngineConfig

    assert NSEngineConfig().variant == "muon"
    monkeypatch.setenv("REPRO_OPTIMIZER_VARIANT", "normuon")
    assert NSEngineConfig.from_env().variant == "normuon"


# -------------------------------------------- per-leaf parity (tentpole gate)

@pytest.mark.parametrize("phase", ["block", "full"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bucketing", [True, False])
def test_turbo_muon_matches_per_leaf_reference(phase, dtype, bucketing):
    params, grads, blocks = make_tree(dtype)
    opt = muon(LR, momentum=MU, weight_decay=WD, block_specs=blocks,
               bucketing=bucketing, variant="turbo_muon")
    upd, _ = opt.update(grads, opt.init(params), params, phase)
    expect = turbo_reference(grads, params, phase=phase, block_specs=blocks)
    atol = 1e-6 if dtype == jnp.float32 else 1e-4
    for a, b, path in zip(
        jax.tree.leaves(upd), jax.tree.leaves(expect),
        [p for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]],
    ):
        assert a.dtype == b.dtype, path
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0, atol=atol, err_msg=str(path),
        )


@pytest.mark.parametrize("phase", ["block", "full"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bucketing", [True, False])
def test_normuon_matches_per_leaf_reference(phase, dtype, bucketing):
    params, grads, blocks = make_tree(dtype)
    opt = muon(LR, momentum=MU, weight_decay=WD, block_specs=blocks,
               bucketing=bucketing, variant="normuon")
    state = opt.init(params)
    upd, new_state = opt.update(grads, state, params, phase)
    expect, v_ref, c_ref = normuon_reference(grads, params, phase=phase,
                                             block_specs=blocks)
    atol = 1e-6 if dtype == jnp.float32 else 1e-4
    for a, b, path in zip(
        jax.tree.leaves(upd), jax.tree.leaves(expect),
        [p for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]],
    ):
        assert a.dtype == b.dtype, path
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0, atol=atol, err_msg=str(path),
        )
    # the second-moment state matches the per-leaf refresh exactly
    for v, vr in zip(jax.tree.leaves(new_state.second_moment),
                     jax.tree.leaves(v_ref)):
        np.testing.assert_allclose(np.asarray(v), np.asarray(vr),
                                   rtol=0, atol=atol)
    for c, cr in zip(jax.tree.leaves(new_state.vcount), jax.tree.leaves(c_ref)):
        assert int(c) == int(cr) == (1 if phase == "full" else 0)


def test_normuon_state_allocation_and_block_passthrough():
    """Init allocates (..., 1) row stats + int32 counters; with zero
    statistics a block step is EXACTLY the baseline muon update (the
    first-steps guard passes the raw update through)."""
    params, grads, blocks = make_tree(jnp.float32)
    opt = muon(LR, momentum=MU, weight_decay=WD, block_specs=blocks,
               variant="normuon")
    state = opt.init(params)
    for p, v in zip(jax.tree.leaves(params), jax.tree.leaves(state.second_moment)):
        assert v.shape == p.shape[:-1] + (1,)
        assert v.dtype == jnp.float32
        assert float(jnp.sum(jnp.abs(v))) == 0.0
    for c in jax.tree.leaves(state.vcount):
        assert c.dtype == jnp.int32 and int(c) == 0

    base = muon(LR, momentum=MU, weight_decay=WD, block_specs=blocks)
    upd_n, _ = opt.update(grads, state, params, "block")
    upd_b, _ = base.update(grads, base.init(params), params, "block")
    for a, b in zip(jax.tree.leaves(upd_n), jax.tree.leaves(upd_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_baseline_state_has_no_variant_leaves():
    """The 4-field OptState is leaf-compatible with the seed 2-field one
    for every non-NorMuon variant (checkpoints and sharding unchanged)."""
    params, grads, blocks = make_tree(jnp.float32)
    for variant in (None, "muon", "turbo_muon"):
        opt = muon(LR, block_specs=blocks, variant=variant)
        state = opt.init(params)
        assert state.second_moment is None and state.vcount is None
        n_param_leaves = len(jax.tree.leaves(params))
        assert len(jax.tree.leaves(state)) == n_param_leaves + 1  # + count


# ----------------------------------------------- Turbo-Muon launch reduction

def test_turbo_muon_fewer_ns_launches():
    """fused_iter issues one launch per NS iteration, so the launch-count
    delta across a fresh trace IS the compiled chain length: Turbo-Muon's
    must be strictly below the baseline's (K-2 < K)."""
    from repro.kernels.newton_schulz import fused

    def launches(opt, shape, seed):
        params = {"w": jax.random.normal(jax.random.PRNGKey(seed), shape)}
        grads = jax.tree.map(lambda p: 0.1 * p, params)
        before = fused.launch_count()
        opt.update(grads, opt.init(params), params, "block")
        return fused.launch_count() - before

    # distinct shapes force fresh traces (jit caches are shape-keyed)
    base = muon(LR, ns_backend="pallas", ns_strategy="fused_iter")
    turbo = muon(LR, ns_backend="pallas", ns_strategy="fused_iter",
                 variant="turbo_muon")
    d_base = launches(base, (168, 88), seed=11)
    d_turbo = launches(turbo, (104, 184), seed=12)
    assert d_base == 5
    assert d_turbo == 3
    assert d_turbo < d_base


def test_turbo_muon_reduced_k_orthogonalizes_as_well():
    """The point of the spectral pre-scale: K=3 with it reaches (at least)
    the orthogonality the baseline needs K=5 for."""
    from repro.core import orthogonality_error

    x = jax.random.normal(jax.random.PRNGKey(7), (96, 128))
    base = orthogonalize(x, steps=5)
    sigma = spectral_norm_est(x).astype(x.dtype)
    turbo = orthogonalize(x / (sigma * SPECTRAL_MARGIN + 1e-7), steps=3,
                          normalize=False)
    assert float(orthogonality_error(turbo)) <= float(orthogonality_error(base)) * 1.05


# ------------------------------------------ NorMuon kernel: bitwise parity

@pytest.mark.parametrize("refresh", [True, False])
@pytest.mark.parametrize("shape", [
    (1, 8, 128), (2, 10, 17), (3, 16, 130),
    (1, 200, 6144),   # three row tiles of 72 (normuon.row_tile), last padded
    (2, 30, 16384),   # two row tiles of 16, the last one padded
])
def test_normuon_kernel_bitwise_vs_reference(refresh, shape):
    """Interpret-mode Pallas kernel == jnp reference BIT FOR BIT: both run
    the same fp32 math on identically padded operands, whatever the row
    tiling of the kernel's grid."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, shape, jnp.float32)
    v = jnp.abs(jax.random.normal(k2, (*shape[:-1], 1), jnp.float32))
    corr = jnp.float32(1.0 - 0.95 ** 3)
    y_k, v_k = normuon_lib.neuron_norm(x, v, corr, beta2=0.95, eps=1e-8,
                                       refresh=refresh, interpret=True)
    y_r, v_r = normuon_lib.neuron_norm_reference(x, v, corr, beta2=0.95,
                                                 eps=1e-8, refresh=refresh)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))
    if not refresh:
        np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v))


def test_apply_neuron_norm_lead_padded_state():
    """ZeRO-1 flatten fallback: state rows beyond the true lead dim are
    pad — the epilogue normalizes the head and restores zero pad rows."""
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 8, 16))
    v = jnp.concatenate([jnp.ones((3, 8, 1)), jnp.zeros((1, 8, 1))])  # lead 4
    c = jnp.asarray(2, jnp.int32)
    y, v_new, c_new = normuon_lib.apply_neuron_norm(
        x, v, c, beta2=0.95, eps=1e-8, refresh=True, backend="jnp")
    assert y.shape == x.shape
    assert v_new.shape == (4, 8, 1)
    assert int(c_new) == 3
    np.testing.assert_array_equal(np.asarray(v_new[3:]), 0.0)
    # RMS preserved globally
    np.testing.assert_allclose(
        float(jnp.sqrt(jnp.mean(jnp.square(y)))),
        float(jnp.sqrt(jnp.mean(jnp.square(x)))), rtol=1e-5)


# ------------------------------------------------------- revived Dion program

def test_dion_block_equals_full():
    """Dion has no block-periodic structure: both phases compile to the
    same work and produce the same update."""
    params = {"w": jax.random.normal(jax.random.PRNGKey(1), (32, 48)),
              "s": jax.random.normal(jax.random.PRNGKey(2), (2, 24, 16))}
    grads = jax.tree.map(lambda p: 0.1 * p, params)
    opt = build_variant("dion", 0.1, rank=8)
    state = opt.init(params)
    u_b, s_b = opt.update(grads, state, params, "block")
    u_f, s_f = opt.update(grads, state, params, "full")
    for a, b in zip(jax.tree.leaves(u_b), jax.tree.leaves(u_f)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_b), jax.tree.leaves(s_f)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dion_rejects_staggered_and_bad_phase():
    with pytest.raises(ValueError, match="stagger"):
        build_variant("dion", 0.1, full_schedule="staggered")
    opt = build_variant("dion", 0.1)
    params = {"w": jnp.ones((8, 8))}
    with pytest.raises(ValueError, match="phase"):
        opt.update(params, opt.init(params), params, "stagger:0")


def test_dion_factor_program_predicts_zero_comm():
    """The Dion program compiles against the factor engine view: P factors
    are replicated, so the compiled program prices 0 B on every phase —
    Dion's selling point in MuonBP's own accounting."""

    class FakeInner:
        axis_sizes = {"data": 2, "model": 4}
        mesh = object()

    view = _FactorEngineView(FakeInner())
    specs = (
        LeafSpec(key=("wq",), shape=(64, 8), dtype="float32", block=None),
        LeafSpec(key=("stack",), shape=(3, 32, 8), dtype="float32", block=None),
    )
    prog = compile_program(specs, backend="jnp", engine=view)
    for phase in ("block", "full"):
        assert prog.phase(phase).predicted_comm_bytes() == 0
        assert all(le.gather is None for le in prog.phase(phase).leaf_execs)


def test_dion_polar_is_orthonormal_in_training():
    """Error feedback compounds any orthonormality deficit, so assert the
    NS polar factor stays QR-grade through real update dynamics."""
    params = {"w": jax.random.normal(jax.random.PRNGKey(9), (48, 32))}
    opt = build_variant("dion", 0.05, rank=8, momentum=0.9)
    state = opt.init(params)
    w = params["w"]
    for t in range(5):
        g = 0.3 * jax.random.normal(jax.random.PRNGKey(100 + t), w.shape)
        upd, state = opt.update({"w": g}, state, {"w": w}, "block")
        w = w + upd["w"]
        # rank-r update with orthonormal left factor: upd = -lr*s*Q V^T,
        # V column-normalized => upd^T upd has V^T V's structure; check Q
        # via the basis invariant instead: columns of V stay unit-norm.
        norms = jnp.linalg.norm(state.basis["w"], axis=-2)
        np.testing.assert_allclose(np.asarray(norms), 1.0, atol=1e-4)


# ------------------- kernel plans / launch groups under variant stages
# (property-style: hypothesis when available, deterministic otherwise)

_PLAN_CASES = [
    ((16, 32), "pallas", -2, "spectral_scale", None),
    ((2, 64, 64), "pallas", 0, None, "neuron_norm"),
    ((128, 96), "jnp", -2, "spectral_scale", None),
    ((8, 16, 16), "jnp", 0, None, "neuron_norm"),
    ((16384, 16384), "pallas", -2, "spectral_scale", None),
]


def _check_plan_invariants(shape, backend, delta, precondition, epilogue):
    """Variant stage fields ANNOTATE the plan; they never change the
    strategy choice, which must match dispatch.plan_strategy on the packed
    shape. Every bucket of one program carries the same K/stage fields."""
    k = max(1, 5 + delta)
    spec = LeafSpec(key=("w",), shape=tuple(shape), dtype="float32", block=None)
    base = compile_program((spec,), backend=backend)
    prog = compile_program((spec,), backend=backend, ns_steps=k,
                           precondition=precondition, epilogue=epilogue)
    for phase in ("block", "full"):
        ops = prog.phase(phase).ops
        base_ops = base.phase(phase).ops
        for op, bop in zip(ops, base_ops):
            assert op.kernel.strategy == bop.kernel.strategy
            assert op.kernel.strategy == dispatch.plan_strategy(
                op.packed_shape, backend)
            assert op.kernel.ns_steps == k
            assert op.kernel.precondition == precondition
            assert op.kernel.epilogue == epilogue
    text = prog.summary()
    assert f"K={k}" in text
    if precondition:
        assert f"pre={precondition}" in text
    if epilogue:
        assert f"epi={epilogue}" in text


if HAVE_HYPOTHESIS:

    @hypothesis.settings(deadline=None, max_examples=25)
    @hypothesis.given(
        m=st.sampled_from([8, 16, 64, 1024, 16384]),
        n=st.sampled_from([8, 32, 96, 16384]),
        lead=st.integers(0, 2),
        backend=st.sampled_from(["jnp", "pallas"]),
        variant=st.sampled_from(["turbo_muon", "normuon"]),
    )
    def test_variant_kernel_plan_invariants(m, n, lead, backend, variant):
        vs = VARIANTS[variant]
        shape = (2,) * lead + (m, n)
        _check_plan_invariants(shape, backend, vs.ns_steps_delta,
                               vs.precondition, vs.epilogue)

else:

    @pytest.mark.parametrize("shape,backend,delta,pre,epi", _PLAN_CASES)
    def test_variant_kernel_plan_invariants(shape, backend, delta, pre, epi):
        _check_plan_invariants(shape, backend, delta, pre, epi)


def _check_launch_groups(keys):
    """shared_launch_groups invariants: groups partition the keys by
    (m, n); the compute dtype is the promotion of the members; single-dtype
    groups carry no cast epilogue. Variant stages never enter the keys, so
    grouping is identical for every variant program."""
    groups = dispatch.shared_launch_groups(keys)
    seen = set()
    for (m, n), (compute, members) in groups.items():
        dts = [dt for (km, kn, dt) in keys if (km, kn) == (m, n)]
        assert set(dts) != set()
        if len(set(dts)) == 1:
            assert members == ()
        else:
            assert set(members) == set(dts)
            assert jnp.dtype(compute) == jnp.promote_types(*set(dts)) or all(
                jnp.promote_types(compute, d) == jnp.dtype(compute) for d in dts
            )
        seen.add((m, n))
    assert seen == {(m, n) for (m, n, _) in keys}


if HAVE_HYPOTHESIS:

    @hypothesis.settings(deadline=None, max_examples=25)
    @hypothesis.given(
        st.lists(
            st.tuples(
                st.sampled_from([8, 16, 64]),
                st.sampled_from([8, 32]),
                st.sampled_from(["float32", "bfloat16"]),
            ),
            min_size=1, max_size=6, unique=True,
        )
    )
    def test_shared_launch_group_invariants(keys):
        _check_launch_groups(keys)

else:

    @pytest.mark.parametrize(
        "keys",
        [
            [(16, 32, "float32")],
            [(16, 32, "float32"), (16, 32, "bfloat16")],
            [(16, 32, "float32"), (64, 8, "bfloat16"), (64, 8, "float32")],
        ],
    )
    def test_shared_launch_group_invariants(keys):
        _check_launch_groups(keys)
