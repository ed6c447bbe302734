"""Guarded train step + escalation ladder + fault injection.

The resilience contract, tested at three levels:

* unit — health predicate, EMA debias/fold, escalator ladder, fault-plan
  parsing, guard-state checkpoint round-trip;
* single-device integration — guard enabled with no faults is *bitwise*
  identical to the unguarded step; injected NaN/Inf/spike steps are skipped
  (params AND momentum untouched) while the same fault unguarded poisons the
  params;
* 8-device subprocess (slow) — the same bitwise-parity claim under the
  shard_map engine with ZeRO-1, plus the HLO audit: the lax.cond guard must
  not reintroduce optimizer collectives into the block phase.
"""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch, tiny_cfg
from repro.core import adamw, combine, label_tree, muon
from repro.core import variants as variants_lib
from repro.models.model import init_params
from repro.models.transformer import ShardCtx
from repro.training import resilience
from repro.training.faults import Fault, FaultPlan
from repro.training.resilience import (
    EscalationPolicy,
    Escalator,
    GuardConfig,
    GuardState,
    apply_backoff,
    debiased_ema,
    fold_observation,
    guard_from_meta,
    guard_to_meta,
    health_check,
    init_guard_state,
)
from repro.training.train_step import init_train_state, make_train_step_fns


# ---------------------------------------------------------------------------
# Unit: health predicate + EMA
# ---------------------------------------------------------------------------

def _gstate(ema_loss=5.0, ema_count=100, skipped=0, lr_scale=1.0):
    return GuardState(
        ema_loss=jnp.float32(ema_loss),
        ema_count=jnp.int32(ema_count),
        skipped=jnp.int32(skipped),
        lr_scale=jnp.float32(lr_scale),
    )


def test_health_check_finiteness():
    cfg = GuardConfig()
    g = init_guard_state()
    ok = jnp.float32(2.0)
    assert bool(health_check(cfg, ok, ok, g))
    assert not bool(health_check(cfg, jnp.float32(np.nan), ok, g))
    assert not bool(health_check(cfg, ok, jnp.float32(np.inf), g))
    assert not bool(health_check(cfg, jnp.float32(-np.inf), ok, g))


def test_health_check_spike_after_warmup_only():
    cfg = GuardConfig(spike_factor=3.0, ema_beta=0.9, warmup_steps=10)
    # Saturated EMA near 5.0 -> a 50.0 loss is a spike...
    warm = _gstate(ema_loss=5.0 * (1 - 0.9 ** 100), ema_count=100)
    assert not bool(health_check(cfg, jnp.float32(50.0), jnp.float32(1.0), warm))
    assert bool(health_check(cfg, jnp.float32(10.0), jnp.float32(1.0), warm))
    # ...but the same loss during warmup is allowed (init transients).
    cold = _gstate(ema_loss=0.5, ema_count=3)
    assert bool(health_check(cfg, jnp.float32(50.0), jnp.float32(1.0), cold))


def test_debiased_ema_matches_first_sample():
    cfg = GuardConfig(ema_beta=0.98)
    g = fold_observation(cfg, init_guard_state(), jnp.float32(7.5), jnp.bool_(True))
    # Adam-style debias: after one sample the EMA estimate IS that sample.
    assert float(debiased_ema(cfg, g)) == pytest.approx(7.5, rel=1e-6)
    assert int(g.ema_count) == 1 and int(g.skipped) == 0


def test_fold_observation_unhealthy_freezes_ema():
    cfg = GuardConfig()
    g0 = _gstate(ema_loss=1.25, ema_count=7, skipped=2)
    g1 = fold_observation(cfg, g0, jnp.float32(np.nan), jnp.bool_(False))
    assert float(g1.ema_loss) == 1.25      # NaN must not poison the baseline
    assert int(g1.ema_count) == 7
    assert int(g1.skipped) == 3
    assert float(g1.lr_scale) == 1.0


# ---------------------------------------------------------------------------
# Unit: escalation ladder
# ---------------------------------------------------------------------------

def test_escalator_walks_the_ladder():
    esc = Escalator(EscalationPolicy(force_full_after=1, backoff_after=3,
                                     abort_after=6))
    total, actions = 0, []
    for step in range(7):
        total += 1  # one new skip every step
        actions.append(esc.observe(step, total))
    assert actions == ["force_full", "force_full", "backoff", "backoff",
                       "backoff", "abort", "abort"]
    assert esc.history[0] == (0, "force_full")


def test_escalator_healthy_step_resets_streak():
    esc = Escalator(EscalationPolicy(force_full_after=1, backoff_after=2,
                                     abort_after=4))
    assert esc.observe(0, 1) == "force_full"
    assert esc.observe(1, 2) == "backoff"
    assert esc.observe(2, 2) == "none"      # no new skips -> streak reset
    assert esc.consecutive == 0
    assert esc.observe(3, 3) == "force_full"  # ladder restarts from rung 1


def test_escalator_disabled_rungs():
    esc = Escalator(EscalationPolicy(force_full_after=0, backoff_after=0,
                                     abort_after=2))
    assert esc.observe(0, 1) == "none"
    assert esc.observe(1, 2) == "abort"


def test_escalator_resume_seeding():
    """After restore the launcher seeds _last_total from the checkpointed skip
    counter so pre-preemption skips don't re-escalate."""
    esc = Escalator(EscalationPolicy(force_full_after=1))
    esc._last_total = 5
    assert esc.observe(10, 5) == "none"
    assert esc.observe(11, 6) == "force_full"


# ---------------------------------------------------------------------------
# Unit: guard-state checkpoint round-trip + fault plans
# ---------------------------------------------------------------------------

def test_guard_meta_roundtrip():
    g = _gstate(ema_loss=1.5, ema_count=42, skipped=3, lr_scale=0.25)
    meta = json.loads(json.dumps(guard_to_meta(g)))  # must be JSON-safe
    g2 = guard_from_meta(meta)
    assert float(g2.ema_loss) == pytest.approx(1.5)
    assert int(g2.ema_count) == 42
    assert int(g2.skipped) == 3
    assert float(g2.lr_scale) == 0.25
    assert guard_to_meta(None) is None
    assert int(guard_from_meta(None).skipped) == 0  # fresh state fallback


def test_fault_plan_parse_roundtrip():
    spec = "nan_grads@7,spike_loss@9x8,kill_in_save@12"
    plan = FaultPlan.parse(spec)
    assert plan.spec() == spec
    assert plan.grad_fault(7) == Fault("nan_grads", 7)
    assert plan.grad_fault(9).scale == 8.0
    assert plan.grad_fault(12) is None  # kills are not in-graph faults
    assert plan.without_kills().spec() == "nan_grads@7,spike_loss@9x8"
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.parse("meteor_strike@3")
    with pytest.raises(ValueError, match="bad fault spec"):
        FaultPlan.parse("nan_grads")


def test_fault_plan_kill_fires_once_at_or_after_step():
    plan = FaultPlan.parse("kill_in_save@12")
    assert not plan.take_kill("checkpoint.pre_finalize", 10)
    assert not plan.take_kill("checkpoint.mid_write", 14)  # wrong point
    assert plan.take_kill("checkpoint.pre_finalize", 14)   # first save >= 12
    assert not plan.take_kill("checkpoint.pre_finalize", 16)  # fired already


# ---------------------------------------------------------------------------
# Integration (single device): bitwise parity + fault handling
# ---------------------------------------------------------------------------

def _setup(key, guard=None, fault=None, variant=None):
    cfg = tiny_cfg("granite-8b")
    params = init_params(key, cfg)
    if variant is not None and variants_lib.get(variant).low_rank:
        matrix_opt = variants_lib.build_variant(variant, 0.02, rank=8)
    else:
        matrix_opt = muon(0.02, 0.02, period=3, variant=variant)
    opt = combine({"muon": matrix_opt, "adamw": adamw(0.01)},
                  label_tree(params))
    fns = make_train_step_fns(cfg, opt, ShardCtx(), donate=False, guard=guard,
                              fault=fault)
    state = init_train_state(params, opt, guard=guard is not None)
    return cfg, state, fns


def _leaves_equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def test_guarded_step_bitwise_identical_when_healthy(key):
    cfg, state_u, fns_u = _setup(key)
    _, state_g, fns_g = _setup(key, guard=GuardConfig())
    batch = make_batch(cfg)
    for t in range(6):
        phase = "full" if t % 3 == 0 else "block"
        state_u, _ = fns_u[phase](state_u, batch)
        state_g, m = fns_g[phase](state_g, batch)
    assert _leaves_equal(state_u.params, state_g.params)
    assert _leaves_equal(state_u.opt_state, state_g.opt_state)
    assert int(m["skipped"]) == 0 and int(m["healthy"]) == 1
    assert float(m["lr_scale"]) == 1.0
    assert int(state_g.guard.ema_count) == 6


@pytest.mark.parametrize("variant", ["turbo_muon", "normuon", "dion"])
def test_guarded_step_bitwise_identical_per_variant(key, variant):
    """The guard's lax.cond identity branch must stay bitwise-transparent
    for every optimizer variant — including NorMuon's extra second-moment
    state and Dion's basis, which ride through the same skip machinery."""
    cfg, state_u, fns_u = _setup(key, variant=variant)
    _, state_g, fns_g = _setup(key, guard=GuardConfig(), variant=variant)
    batch = make_batch(cfg)
    for t in range(4):
        phase = "full" if t % 3 == 0 else "block"
        state_u, _ = fns_u[phase](state_u, batch)
        state_g, m = fns_g[phase](state_g, batch)
    assert _leaves_equal(state_u.params, state_g.params)
    assert _leaves_equal(state_u.opt_state, state_g.opt_state)
    assert int(m["skipped"]) == 0 and int(m["healthy"]) == 1


@pytest.mark.parametrize("variant", ["normuon", "dion"])
def test_guard_skip_leaves_variant_state_untouched(key, variant):
    """A skipped (NaN-grad) step must not advance variant-specific state:
    NorMuon's second moment / vcount and Dion's basis stay bitwise-put."""
    cfg, state, fns = _setup(key, guard=GuardConfig(), variant=variant)
    _, _, fault_fns = _setup(key, guard=GuardConfig(),
                             fault=Fault("nan_grads", 0), variant=variant)
    batch = make_batch(cfg)
    state, _ = fns["full"](state, batch)  # populate the variant state
    before = state.opt_state
    state, m = fault_fns["block"](state, batch)
    assert int(m["skipped"]) == 1
    assert _leaves_equal(before, state.opt_state)


@pytest.mark.parametrize("kind", ["nan_grads", "inf_grads"])
def test_guard_skips_nonfinite_step(key, kind):
    cfg, state, fns = _setup(key, guard=GuardConfig())
    _, _, fault_fns = _setup(key, guard=GuardConfig(), fault=Fault(kind, 0))
    batch = make_batch(cfg)
    for phase in ("full", "block"):
        state, _ = fns[phase](state, batch)
    before_p, before_o = state.params, state.opt_state
    state, m = fault_fns["block"](state, batch)
    assert int(m["healthy"]) == 0 and int(m["skipped"]) == 1
    assert _leaves_equal(before_p, state.params)      # identity branch:
    assert _leaves_equal(before_o, state.opt_state)   # momentum untouched too
    # the guard state itself still advances (counter, frozen EMA)
    assert int(state.guard.skipped) == 1
    # ...and the next clean step recovers normally
    state, m = fns["block"](state, batch)
    assert int(m["healthy"]) == 1
    assert math.isfinite(float(m["loss"]))
    assert not _leaves_equal(before_p, state.params)


def test_unguarded_nonfinite_step_poisons_params(key):
    """The contrast case: without the guard a single NaN gradient corrupts
    the params irrecoverably — this is what the guard exists to prevent."""
    cfg, state, _ = _setup(key)
    _, _, fault_fns = _setup(key, fault=Fault("nan_grads", 0))
    state, _ = fault_fns["block"](state, make_batch(cfg))
    leaf = np.asarray(jax.tree.leaves(state.params)[0])
    assert np.isnan(leaf).any()


def test_guard_skips_loss_spike_after_warmup(key):
    gcfg = GuardConfig(spike_factor=3.0, warmup_steps=2)
    cfg, state, fns = _setup(key, guard=gcfg)
    _, _, spike_fns = _setup(key, guard=gcfg, fault=Fault("spike_loss", 0, scale=50.0))
    batch = make_batch(cfg)
    for _ in range(3):  # past warmup
        state, _ = fns["block"](state, batch)
    before = state.params
    state, m = spike_fns["block"](state, batch)
    assert int(m["healthy"]) == 0 and int(m["skipped"]) == 1
    assert _leaves_equal(before, state.params)
    # the spiked loss is finite — this is the EMA detector, not the NaN check
    assert math.isfinite(float(m["loss"]))


def test_spike_during_warmup_is_not_skipped(key):
    gcfg = GuardConfig(spike_factor=3.0, warmup_steps=10)
    cfg, state, _ = _setup(key, guard=gcfg)
    _, _, spike_fns = _setup(key, guard=gcfg, fault=Fault("spike_loss", 0, scale=50.0))
    state, m = spike_fns["block"](state, make_batch(cfg))
    assert int(m["healthy"]) == 1 and int(m["skipped"]) == 0


def test_backoff_scales_update_exactly(key):
    """lr_scale is folded into the compiled step: halving it via
    apply_backoff halves the param delta bitwise-exactly (linear update)."""
    cfg, state, fns = _setup(key, guard=GuardConfig())
    batch = make_batch(cfg)
    state, _ = fns["full"](state, batch)  # warm momentum
    base = state
    s1, m1 = fns["block"](base, batch)
    s2, m2 = fns["block"](apply_backoff(base, 0.5), batch)
    assert float(m1["lr_scale"]) == 1.0 and float(m2["lr_scale"]) == 0.5
    d1 = np.asarray(jax.tree.leaves(s1.params)[0]) - np.asarray(jax.tree.leaves(base.params)[0])
    d2 = np.asarray(jax.tree.leaves(s2.params)[0]) - np.asarray(jax.tree.leaves(base.params)[0])
    np.testing.assert_allclose(d2, 0.5 * d1, rtol=1e-5, atol=1e-8)
    # momentum is NOT scaled — backoff damps the applied update only
    assert _leaves_equal(s1.opt_state, s2.opt_state)


# ---------------------------------------------------------------------------
# 8-device subprocess: engine/ZeRO-1 parity + HLO audit of the guarded step
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import adamw, combine, label_tree, muon
from repro.core.blocking import BlockSpec2D
from repro.core.combine import apply_updates
from repro.distributed import (
    assert_matches_plan, audit_guarded_optimizer, make_engine, plan_comm)
from repro.distributed import zero1 as z1
from repro.training.resilience import GuardConfig, guarded_update, init_guard_state

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
gcfg = GuardConfig()

state = opt.init(params)
state = z1.shard_state(state, params, mesh, pspecs=pspecs)
grads = jax.tree.map(lambda p: 0.1 * jnp.ones_like(p), params)
scalar = NamedSharding(mesh, P())
loss = jax.device_put(jnp.float32(2.0), scalar)
gstate = jax.device_put(init_guard_state(), scalar)

out = {"parity": {}}
for phase in ("block", "full"):
    def unguarded(g, s, p):
        u, ns = opt.update(g, s, p, phase)
        return apply_updates(p, u), ns
    def guarded(g, s, p, l, gs):
        gsq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                  for x in jax.tree.leaves(g))
        np_, no_, ng_, h = guarded_update(opt, gcfg, g, s, p, gs, l, gsq, phase)
        return np_, no_, ng_, h
    pu, su = jax.jit(unguarded)(grads, state, params)
    pg, sg, ng, healthy = jax.jit(guarded)(grads, state, params, loss, gstate)
    out["parity"][phase] = {
        "params_equal": all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(pu), jax.tree.leaves(pg))),
        "opt_equal": all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(su), jax.tree.leaves(sg))),
        "healthy": int(healthy),
        "skipped": int(ng.skipped),
    }

# HLO audit: the lax.cond guard must not change the collective schedule.
a_params = jax.tree.map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), params)
a_opt = jax.eval_shape(opt.init, a_params)
a_opt = z1.attach(a_opt, a_params, mesh, zero1=True)
upd_sh = jax.tree.map(
    lambda x: x.sharding, z1.attach(a_params, a_params, mesh, zero1=True))
plan = plan_comm(a_params, pspecs, mesh, labels=labels, block_specs=bspecs,
                 zero1=True)
GATHER_OPS = ("all-gather", "reduce-scatter", "all-to-all")
out["audit"] = {}
for phase in ("block", "full"):
    res = audit_guarded_optimizer(opt, gcfg, a_params, a_opt, phase=phase,
                                  update_shardings=upd_sh)
    assert_matches_plan(res, plan, phase)
    out["audit"][phase] = {
        "gather_bytes": sum(res.bytes_of(op) for op in GATHER_OPS),
        "predicted": plan.predicted_bytes(phase),
        "plan_match": "ok",
    }
print("RESULT " + json.dumps(out))
"""

# slow: spawns an 8-forced-device subprocess compiling several XLA programs.
@pytest.fixture(scope="module")
def dist_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_guard_parity_under_engine_zero1(dist_result):
    """Guarded apply == unguarded apply bitwise on the 2x4 mesh with the
    shard_map engine and ZeRO-1 state, both phases."""
    for phase, rec in dist_result["parity"].items():
        assert rec["params_equal"], (phase, rec)
        assert rec["opt_equal"], (phase, rec)
        assert rec["healthy"] == 1 and rec["skipped"] == 0, (phase, rec)


@pytest.mark.slow
def test_guard_keeps_block_phase_collective_free(dist_result):
    """ISSUE acceptance: the block-phase HLO audit still reports zero
    optimizer gather/scatter bytes with the guard compiled in; the full
    phase still matches the CommPlan byte-for-byte."""
    blk = dist_result["audit"]["block"]
    assert blk["gather_bytes"] == 0 and blk["predicted"] == 0, blk
    full = dist_result["audit"]["full"]
    assert full["plan_match"] == "ok" and full["predicted"] > 0, full
    assert full["gather_bytes"] == full["predicted"], full
