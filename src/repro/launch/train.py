"""Training launcher: config-driven MuonBP pretraining.

Runs on whatever devices exist (CPU: 1-device mesh; TPU slice: pass
``--mesh pod=2,data=2,model=2``-style specs — or the legacy ``--mesh-model``
— to match it). The MuonBP phase schedule is driven here: two compiled step
functions, ``step % P == 0`` picks 'full'; ``--full-schedule staggered``
replaces the synchronous pair with one mixed-phase step per step-residue
(bucket i goes full when ``step % P == offset_i``, offsets balanced over
DCN bytes), flattening the p-step DCN burst into a per-step trickle with
the two-stepsize rule applied per bucket. The optimizer runs through the
explicit shard_map comm engine by default (its schedule is asserted against
CommPlan; ``--comm-engine gspmd`` keeps the implicit partitioner path for
A/Bs). ``--zero1`` shards optimizer state over the mesh's data axes
(``('pod', 'data')`` on a hierarchical mesh); ``--zero1-flatten`` adds the
flatten-and-shard fallback for layer counts that don't divide them.

Resilience: ``--guard`` wraps the optimizer apply in the in-graph health
check (skip on NaN/Inf or loss spike) and drives the escalation ladder from
here — skip -> force an early 'full'-phase step (both phase functions are
already compiled, so that is a dispatch decision) -> LR backoff ->
checkpoint-and-abort. ``--checkpoint-every`` writes atomic, checksummed
snapshots (always including the final step) and ``--resume`` auto-resumes
from the newest *valid* one, including optimizer shards, the data-stream
position, and the guard counters. ``--fault-plan`` injects deterministic
faults for chaos testing (scripts/chaos_run.py).

Telemetry flows through ``repro.obs``: every record (per-step lines, the
checkpoint/resume/abort/skip_snapshot events, spans, drift reports,
counters) goes to the event bus — stdout keeps the exact legacy wire
format, and ``--log-file`` append-streams fsync'd JSONL so a SIGKILL
mid-run (preemption, ``--fault-plan`` kills) preserves every record up to
the kill. ``scripts/obs_report.py`` aggregates the JSONL; the
plan-vs-runtime drift monitor (``--drift-threshold``) compares measured
full-minus-block step wall time against ``CommPlan``-predicted comm cost;
``--profile-steps A:B`` captures a profiler trace whose stage names match
``UpdateProgram.summary()``. See docs/observability.md.

See docs/operators-guide.md for flag-by-flag guidance.

Example (CPU-scale):
  PYTHONPATH=src python -m repro.launch.train \
      --arch granite-8b --reduced --steps 200 --batch 8 --seq 128 \
      --optimizer muonbp --period 5 --lr 0.02
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import NSEngineConfig
from repro.core import adamw, block_muon, combine, dion, label_tree, muon, muon_full
from repro.core import variants as variants_lib
from repro.core.muon import StaggerSchedule
from repro.core.schedule import cosine, wsd
from repro.data.pipeline import SyntheticLM
from repro.kernels import dispatch
from repro.launch import compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.model import init_params
from repro.obs import (
    Bus,
    DriftConfig,
    DriftMonitor,
    JsonlSink,
    ResidueDriftMonitor,
    StdoutSink,
    set_bus,
    span,
)
from repro.obs.spans import parse_profile_window
from repro.sharding import specs as sh
from repro.training import checkpoint, resilience
from repro.training import faults as faults_lib
from repro.training.train_step import init_train_state, make_train_step_fns


def build_optimizer(name, params, *, lr, adam_lr, period, schedule_fn=None,
                    block_specs=None, rank=64, weight_decay=0.1, engine=None,
                    comm=None, variant=None):
    labels = label_tree(params)
    lr_s = schedule_fn(lr) if schedule_fn else lr
    adam_s = schedule_fn(adam_lr) if schedule_fn else adam_lr
    engine = engine if engine is not None else NSEngineConfig.from_env()
    vspec = variants_lib.get(variant if variant is not None else engine.variant)
    ns_kw = dict(bucketing=engine.bucketing, ns_backend=engine.backend,
                 ns_strategy=engine.strategy, comm=comm,
                 full_schedule=engine.full_schedule)
    if name == "adamw":
        return combine({"adamw": adamw(adam_s, weight_decay=weight_decay)},
                       jax.tree.map(lambda _: "adamw", labels)), None
    if name == "dion" or vspec.low_rank:
        # Legacy ``--optimizer dion`` and ``--optimizer-variant dion`` build
        # the same revived low-rank program (core/dion.py through
        # compile_program; comm wraps in the factor engine view).
        matrix_opt = variants_lib.build_variant(
            "dion", lr_s, rank=rank,
            weight_decay=weight_decay, period=period, **ns_kw)
        name = "dion"
    elif name == "muon":
        matrix_opt = muon_full(lr_s, weight_decay=weight_decay,
                               block_specs=block_specs, variant=vspec, **ns_kw)
    elif name == "blockmuon":
        matrix_opt = block_muon(lr_s, weight_decay=weight_decay,
                                block_specs=block_specs, variant=vspec, **ns_kw)
    elif name == "muonbp":
        matrix_opt = muon(lr_s, lr_s, period=period, weight_decay=weight_decay,
                          block_specs=block_specs, variant=vspec, **ns_kw)
    else:
        raise ValueError(name)
    period_eff = {"muon": 1, "blockmuon": None, "dion": 1, "muonbp": period}[name]
    return combine({"muon": matrix_opt, "adamw": adamw(adam_s, weight_decay=weight_decay)},
                   labels), period_eff


@dataclasses.dataclass
class TrainResult:
    """What a run returns to an in-process caller (e.g. ``chip_smoke.py``).

    Per executed step: loss, phase, wall seconds of the step span (device
    completion included only with ``--obs-block``) and the seconds JAX
    spent compiling inside it. ``device`` is what JAX reports for the
    first device. ``state``/``step_fns``/``batch`` are the final train
    state, the jitted step per phase and the last batch, so a caller can
    lower or compile a step after the run; drop the result to free them.
    """

    losses: list
    phases: list
    step_s: list
    compile_s: list
    device: dict
    status: str
    state: Any = None
    step_fns: dict = None
    batch: dict = None


def device_info() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> TrainResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="muonbp-960m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--optimizer", default="muonbp",
                    choices=["muonbp", "muon", "blockmuon", "adamw", "dion"])
    ap.add_argument("--optimizer-variant", default=None,
                    choices=list(variants_lib.names()),
                    help="optimizer-variant program (core/variants.py): "
                         "'muon' baseline, 'turbo_muon' spectral "
                         "preconditioning + reduced NS K, 'normuon' "
                         "neuron-wise second-moment epilogue, 'dion' "
                         "low-rank (default: REPRO_OPTIMIZER_VARIANT or "
                         "muon); composes with --optimizer muonbp/muon/"
                         "blockmuon — 'dion' overrides the matrix "
                         "optimizer entirely")
    ap.add_argument("--period", type=int, default=5)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--adam-lr", type=float, default=0.008)
    ap.add_argument("--schedule", default="wsd", choices=["wsd", "cosine", "const"])
    ap.add_argument("--ns-backend", default=None, choices=["jnp", "pallas"],
                    help="NS execution backend (default: REPRO_NS_BACKEND or jnp)")
    ap.add_argument("--ns-strategy", default=None,
                    choices=["auto", "jnp", "fused_chain", "fused_iter", "tiled"],
                    help="pin the per-bucket NS kernel strategy (default: auto "
                         "— the UpdateProgram picks per bucket)")
    ap.add_argument("--no-ns-bucketing", action="store_true",
                    help="disable shape-bucketed batched NS dispatch")
    ap.add_argument("--comm-engine", default="shard_map",
                    choices=["shard_map", "gspmd"],
                    help="optimizer comm engine (default: the explicit "
                         "shard_map engine, repro.distributed; 'gspmd' keeps "
                         "the implicit partitioner path for A/Bs)")
    ap.add_argument("--full-schedule", default=None,
                    choices=["pipelined", "barrier", "staggered"],
                    help="engine-mode full-step schedule (default: pipelined "
                         "— per-bucket gathers overlapped with NS of "
                         "already-resident buckets; 'barrier' keeps the "
                         "gather-all/NS-all/slice-all A/B; 'staggered' "
                         "spreads each bucket's full step across the period "
                         "— bucket i goes full on steps where step %% P == "
                         "offset_i, flattening the p-step DCN burst into a "
                         "per-step trickle; GSPMD always runs barrier-style)")
    ap.add_argument("--zero1", action="store_true",
                    help="shard optimizer state over the mesh's data axes "
                         "(ZeRO-1; ('pod','data') on a multi-pod mesh)")
    ap.add_argument("--zero1-flatten", action="store_true",
                    help="with --zero1: flatten-and-shard fallback for "
                         "leaves whose layer count does not divide the "
                         "ZeRO axes (pads the lead dim; writeback gathers "
                         "priced in the plan's 'apply' phase)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None,
                    help="mesh spec, e.g. 'pod=2,data=2,model=2' or '4,2' "
                         "(data,model); overrides --mesh-model")
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--keep-checkpoints", type=int, default=3,
                    help="snapshot retention: keep the newest k step_* dirs "
                         "under --checkpoint-dir")
    ap.add_argument("--resume", action="store_true",
                    help="auto-resume from the newest VALID snapshot under "
                         "--checkpoint-dir (corrupt ones are skipped; run "
                         "metadata is verified); starts fresh when none "
                         "exists")
    ap.add_argument("--guard", action="store_true",
                    help="guarded train step: in-graph health check "
                         "(all-finite loss/grads + EMA loss-spike detector) "
                         "skips unstable updates and drives the escalation "
                         "ladder (skip -> forced full step -> LR backoff -> "
                         "checkpoint-and-abort)")
    ap.add_argument("--guard-spike-factor", type=float, default=3.0,
                    help="skip the step when loss > factor * EMA(loss)")
    ap.add_argument("--guard-ema-beta", type=float, default=0.98,
                    help="EMA decay of the loss-spike detector")
    ap.add_argument("--guard-warmup", type=int, default=10,
                    help="healthy steps before spike detection engages")
    ap.add_argument("--guard-force-full-after", type=int, default=1,
                    help="consecutive skips before forcing an early "
                         "'full'-phase step (the paper's stabilizer); 0 "
                         "disables the rung")
    ap.add_argument("--guard-backoff-after", type=int, default=3,
                    help="consecutive skips before LR backoff; 0 disables")
    ap.add_argument("--guard-backoff-factor", type=float, default=0.5,
                    help="multiplier applied to the guard lr_scale per "
                         "backoff")
    ap.add_argument("--guard-abort-after", type=int, default=6,
                    help="consecutive skips before checkpoint-and-abort "
                         "(exit 3); 0 disables")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic fault injection spec, e.g. "
                         "'nan_grads@7,spike_loss@9x8,kill_in_save@12' "
                         "(repro.training.faults; chaos testing only)")
    ap.add_argument("--log-file", default=None,
                    help="append-stream every telemetry record (steps, spans, "
                         "events, counters) as fsync'd JSONL; crash-safe — a "
                         "kill loses at most the record being written. Read "
                         "with scripts/obs_report.py")
    ap.add_argument("--obs-block", action="store_true",
                    help="block_until_ready inside each step span so wall "
                         "times include device completion (adds one host "
                         "sync per step; required for meaningful drift "
                         "monitoring)")
    ap.add_argument("--drift-threshold", type=float, default=2.0,
                    help="emit a 'drift' event when measured full-minus-block "
                         "step time disagrees with the CommPlan-modeled comm "
                         "cost by more than this factor (either direction); "
                         "0 disables the monitor")
    ap.add_argument("--profile-steps", default=None,
                    help="capture a jax profiler trace over steps A:B "
                         "(half-open window), e.g. '3:6'; stage regions are "
                         "named muonbp.<phase>.s<stage>.<gather|ns|writeback>")
    ap.add_argument("--profile-dir", default="/tmp/repro_profile",
                    help="output dir for the --profile-steps trace")
    args = ap.parse_args(argv)
    compile_cache.enable()

    variant_name = (args.optimizer_variant
                    if args.optimizer_variant is not None
                    else NSEngineConfig.from_env().variant)
    if args.full_schedule == "staggered":
        # Staggering is an engine-mode schedule over the per-leaf gathers of
        # a periodic optimizer: GSPMD has no explicit gathers to stagger and
        # the non-periodic optimizers have no full step to spread. The
        # muon-family variants (turbo_muon/normuon) keep the periodic
        # structure and stagger fine; the dion variant has no per-leaf
        # full-step gathers at all.
        if args.comm_engine != "shard_map":
            ap.error("--full-schedule staggered requires --comm-engine shard_map")
        if args.optimizer != "muonbp":
            ap.error("--full-schedule staggered requires --optimizer muonbp "
                     f"(got {args.optimizer!r})")
        if variant_name == "dion" or args.optimizer == "dion":
            ap.error("--full-schedule staggered is incompatible with the "
                     "dion variant (a low-rank update has no per-leaf "
                     "full-step gathers to stagger)")
        if args.period < 2:
            ap.error("--full-schedule staggered requires --period >= 2 "
                     f"(got {args.period})")

    # Telemetry bus. Sink order matters: the durable JSONL sink comes
    # FIRST, so every record a stdout parser (chaos_run) observes is
    # already fsync'd on disk — the containment invariant the chaos drill
    # asserts after each kill.
    sinks: list = []
    if args.log_file:
        sinks.append(JsonlSink(args.log_file))
    sinks.append(StdoutSink())
    bus = Bus(sinks)
    set_bus(bus)
    device = device_info()
    bus.event("run_start", argv=sys.argv[1:] if argv is None else list(argv),
              args=vars(args), device=device)
    # NS launch counters: fires at trace time (per jit specialization),
    # never per executed step — zero hot-path cost.
    dispatch.set_launch_hook(
        lambda backend, strategy, shape: bus.inc(
            f"ns_launch.{backend}.{strategy or 'auto'}"))
    prof_window = (parse_profile_window(args.profile_steps)
                   if args.profile_steps else None)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    if args.mesh:
        from repro.launch.mesh import make_mesh_from_spec

        mesh = make_mesh_from_spec(args.mesh)
    else:
        mesh = make_local_mesh(model=args.mesh_model)
    ctx = sh.make_ctx(cfg, mesh)

    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    pspecs = sh.param_specs(params, cfg, mesh)
    params = jax.device_put(params, sh.named(mesh, pspecs))
    bspecs = sh.block_specs_for(params, pspecs, mesh)
    labels = label_tree(params)
    bspecs = jax.tree.map(lambda b, l: b if l == "muon" else None, bspecs, labels)

    sched = {"wsd": lambda peak: wsd(peak, args.steps),
             "cosine": lambda peak: cosine(peak, args.steps),
             "const": lambda peak: peak}[args.schedule]
    engine = NSEngineConfig.from_env()
    if args.ns_backend:
        engine = dataclasses.replace(engine, backend=args.ns_backend)
    if args.ns_strategy:
        engine = dataclasses.replace(engine, strategy=args.ns_strategy)
    if args.no_ns_bucketing:
        engine = dataclasses.replace(engine, bucketing=False)
    if args.full_schedule:
        engine = dataclasses.replace(engine, full_schedule=args.full_schedule)
    if args.optimizer_variant:
        engine = dataclasses.replace(engine, variant=args.optimizer_variant)
    from repro.distributed import make_engine
    from repro.distributed import zero1 as zero1_lib

    comm = (
        make_engine(params, pspecs, mesh, zero1=args.zero1,
                    zero1_flatten=args.zero1_flatten)
        if args.comm_engine == "shard_map" else None
    )
    optimizer, period = build_optimizer(
        args.optimizer, params, lr=args.lr, adam_lr=args.adam_lr,
        period=args.period, schedule_fn=sched, block_specs=bspecs,
        engine=engine, comm=comm, variant=variant_name,
    )

    # Step-phase schedule. Synchronous: every muon bucket goes full on the
    # same step (step % P == 0). Staggered: bucket i goes full on steps
    # where step % P == offset_i, with offsets assigned (by the program
    # compiler AND the comm plan, identically) to balance per-step DCN
    # bytes — the p-step burst becomes a per-step trickle.
    staggered = args.full_schedule == "staggered"
    schedule = StaggerSchedule(period, "staggered" if staggered else "synchronous")

    # One comm plan serves both the stagger bookkeeping (offsets into
    # run_meta, per-residue due counts) and the drift monitor.
    comm_plan = None
    if period is not None and args.optimizer != "adamw" and (
            staggered or args.drift_threshold > 0):
        from repro.distributed.plan import plan_comm

        comm_plan = plan_comm(
            params, pspecs, mesh, labels=labels, block_specs=bspecs,
            zero1=args.zero1, zero1_flatten=args.zero1_flatten)

    # Stagger bookkeeping: the offset map (leaf path -> due residue) and
    # per-residue due counts, persisted in run metadata so a resume under a
    # different schedule fails the named-field check instead of silently
    # re-phasing the buckets.
    stagger_offsets = None
    due_by_residue = None
    n_muon_matrices = sum(
        1 for lab, p in zip(jax.tree.leaves(labels), jax.tree.leaves(params))
        if lab == "muon" and p.ndim >= 2
    )
    if staggered:
        stagger_offsets = comm_plan.stagger_offsets(period)
        due_by_residue = [0] * period
        for r in stagger_offsets.values():
            due_by_residue[r] += 1
    bus.event("schedule",
              mode=schedule.mode, period=period,
              offsets=stagger_offsets,
              max_staggered_dcn_bytes=(
                  comm_plan.max_staggered_dcn_bytes(period) if staggered else None),
              full_dcn_bytes=(
                  comm_plan.predicted_bytes("full", "dcn") if comm_plan else None))

    # Plan-vs-runtime drift monitor. Synchronous: block steps are the
    # compute baseline, so the full-minus-block wall-time delta prices
    # exactly the extra full-step collectives — the per-link byte delta
    # from the same CommPlan the HLO audit checks (apply-phase bytes cancel
    # in the difference). Staggered: that delta is erased by design, so the
    # monitor compares per-residue wall EMAs against the plan's per-residue
    # bills instead. On a 1-device mesh the deltas are zero bytes and both
    # monitors are silent by construction.
    drift_mon = None
    if args.drift_threshold > 0 and comm_plan is not None:
        from repro.distributed.plan import LINKS

        if staggered:
            drift_mon = ResidueDriftMonitor(
                comm_bytes_by_residue=tuple(
                    {ln: comm_plan.predicted_bytes(
                        "staggered", ln, period=period, residue=r)
                     for ln in LINKS}
                    for r in range(period)
                ),
                cfg=DriftConfig(threshold=args.drift_threshold),
                bus=bus,
            )
        else:
            full_b = comm_plan.predicted_by_link("full")
            block_b = comm_plan.predicted_by_link("block")
            drift_mon = DriftMonitor(
                comm_bytes_by_link={
                    k: max(full_b.get(k, 0) - block_b.get(k, 0), 0) for k in full_b
                },
                cfg=DriftConfig(threshold=args.drift_threshold),
                bus=bus,
            )

    guard_cfg = (
        resilience.GuardConfig(
            spike_factor=args.guard_spike_factor,
            ema_beta=args.guard_ema_beta,
            warmup_steps=args.guard_warmup,
        )
        if args.guard else None
    )
    state = init_train_state(params, optimizer, guard=args.guard)
    # Place the whole initial state on the mesh, in the layout the step
    # returns (optimizer state pinned to ZeRO-1 shards or the param layout,
    # counters replicated): a first step fed differently placed inputs than
    # every later one would compile its phase twice.
    opt_shardings = zero1_lib.opt_shardings(
        state.opt_state, params, mesh, pspecs=pspecs, zero1=args.zero1)
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    state = state._replace(
        opt_state=jax.device_put(state.opt_state, opt_shardings),
        step=jax.device_put(state.step, replicated),
        guard=jax.device_put(state.guard, replicated))
    # One jitted step per phase name. Under staggered that is one mixed
    # phase per step-residue (stagger:0..P-1); 'block' and 'full' ride
    # along (jit is lazy, unused variants never compile) so the guard's
    # forced-full escalation keeps its synchronous 'full' variant.
    phases = tuple(dict.fromkeys((*schedule.phases(), "block", "full")))
    param_shardings = sh.named(mesh, pspecs)
    fns = make_train_step_fns(cfg, optimizer, ctx, opt_shardings=opt_shardings,
                              param_shardings=param_shardings,
                              guard=guard_cfg, phases=phases)
    pipe_src = SyntheticLM(cfg, args.batch, args.seq, seed=args.seed)
    pipe = iter(pipe_src)

    plan = faults_lib.FaultPlan.parse(args.fault_plan) if args.fault_plan else None
    if plan:
        faults_lib.set_active(plan)
    fault_fns: dict = {}

    def step_fn(phase, fault):
        """Clean steps use the pre-built fns; a scheduled in-graph fault
        dispatches a separately-compiled variant (built lazily, never
        touching the clean functions)."""
        if fault is None:
            return fns[phase]
        key = (phase, fault)
        if key not in fault_fns:
            fault_fns[key] = make_train_step_fns(
                cfg, optimizer, ctx, opt_shardings=opt_shardings,
                param_shardings=param_shardings, guard=guard_cfg,
                fault=fault, phases=phases)[phase]
        return fault_fns[key]

    # Run metadata: verified on resume so a wrong-arch/optimizer/mesh resume
    # fails with a named mismatch instead of a shape error.
    run_meta = {
        "arch": cfg.name,
        "optimizer": args.optimizer,
        "variant": variant_name,
        "period": period,
        "mesh": {k: int(v) for k, v in zip(mesh.axis_names, mesh.devices.shape)},
        "zero1": bool(args.zero1),
        "seed": args.seed,
        # Schedule mode + per-bucket offsets: a resume that would re-phase
        # the staggered buckets (different mode, period, or offset map)
        # fails the named-field check. Step-residue alignment itself needs
        # no extra state — TrainState.step is restored bit-exactly and the
        # phase is a pure function of (step, schedule).
        "schedule": {
            "mode": schedule.mode,
            "period": period,
            "offsets": stagger_offsets,
        },
    }

    def save_ckpt(step):
        extra = {
            "run": run_meta,
            "args": vars(args),
            "data_state": pipe_src.state(),
            "guard": resilience.guard_to_meta(state.guard),
        }
        with span(bus, "checkpoint.save", step=step):
            path = checkpoint.save_snapshot(
                args.checkpoint_dir, state.params, state.opt_state, step=step,
                extra=extra, keep=args.keep_checkpoints)
        bus.inc("checkpoint.saves")
        bus.emit({"event": "checkpoint", "step": step, "path": path})

    def on_skip_snapshot(p, why):
        bus.inc("checkpoint.fallbacks")
        bus.emit({"event": "skip_snapshot", "path": p, "why": why})

    start_step = 0
    if args.resume:
        with span(bus, "resume"):
            found = checkpoint.latest_valid(
                args.checkpoint_dir, expect_run=run_meta,
                on_skip=on_skip_snapshot)
            if found is not None:
                ck_path, meta = found
                r_params, r_opt, saved_step = checkpoint.restore(
                    ck_path, state.params, state.opt_state,
                    shardings=param_shardings, opt_shardings=opt_shardings,
                    verify_checksums=False)  # latest_valid already verified
                state = state._replace(
                    params=r_params, opt_state=r_opt,
                    step=jnp.asarray(saved_step + 1, jnp.int32),
                    guard=(resilience.guard_from_meta(meta.get("guard"))
                           if args.guard else None))
                if meta.get("data_state"):
                    pipe_src.set_state(meta["data_state"])
                start_step = saved_step + 1
        if found is not None:
            bus.inc("resumes")
            bus.emit({"event": "resume", "step": start_step,
                      "snapshot": ck_path})
        else:
            bus.emit({"event": "resume", "step": 0, "snapshot": None})

    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M optimizer={args.optimizer} "
          f"variant={variant_name} period={period} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    escalator = (
        resilience.Escalator(resilience.EscalationPolicy(
            force_full_after=args.guard_force_full_after,
            backoff_after=args.guard_backoff_after,
            backoff_factor=args.guard_backoff_factor,
            abort_after=args.guard_abort_after,
        ))
        if args.guard else None
    )
    if escalator is not None and start_step:
        # The cumulative skip counter survives the resume; don't re-escalate
        # on skips that happened before the preemption.
        escalator._last_total = int(state.guard.skipped)

    losses, phases_run, step_s, compile_s = [], [], [], []

    def finish(status):
        clock.stop()
        if drift_mon is not None:
            drift_mon.report()
        if prof_window is not None and profiling[0]:
            jax.profiler.stop_trace()
            profiling[0] = False
        bus.event("run_end", steps=args.steps - start_step,
                  wall_s=round(time.time() - t0, 1), status=status,
                  counters=dict(bus.counters))
        bus.close()
        return TrainResult(
            losses=[float(v) for v in jax.device_get(losses)],
            phases=phases_run, step_s=step_s, compile_s=compile_s,
            device=device, status=status, state=state, step_fns=fns,
            batch=batch)

    clock = compile_cache.CompileClock().start()
    batch = None
    t0 = time.time()
    forced_full = False
    profiling = [False]
    for step in range(start_step, args.steps):
        if prof_window is not None and step == prof_window[0]:
            jax.profiler.start_trace(args.profile_dir)
            profiling[0] = True
        batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
        phase = schedule.phase_for(step) if args.optimizer != "adamw" else "block"
        if forced_full and args.optimizer != "adamw":
            phase = "full"
        forced_full = False
        # Step-residue telemetry: residue is the step's position in the
        # period; due counts the muon buckets running their full path this
        # step (the residue's offset group under staggered, the whole set
        # on a synchronous full step).
        residue = step % period if period else 0
        if due_by_residue is not None and phase.startswith("stagger:"):
            due = due_by_residue[residue]
        else:
            due = n_muon_matrices if phase == "full" else 0
        fault = plan.grad_fault(step) if plan else None
        # The step span times dispatch only unless --obs-block pulls device
        # completion inside the clock; either way no extra device fetch
        # happens here, so instrumented steps stay bitwise-identical.
        with span(bus, "step",
                  sync=((lambda: jax.block_until_ready(state))
                        if args.obs_block else None),
                  step=step, phase=phase, residue=residue, due=due) as sp:
            compiled_before = clock.seconds
            state, metrics = step_fn(phase, fault)(state, batch)
        losses.append(metrics["loss"])
        phases_run.append(phase)
        step_s.append(sp.dur_s)
        compile_s.append(clock.seconds - compiled_before)
        if drift_mon is not None:
            drift_mon.observe(step, phase, sp.dur_s)
        if prof_window is not None and profiling[0] and step == prof_window[1] - 1:
            jax.profiler.stop_trace()
            profiling[0] = False
        action = "none"
        skipped = healthy = None
        if escalator is not None:
            skipped = int(metrics["skipped"])
            healthy = int(metrics["healthy"])
            if not healthy:
                bus.inc("guard.skipped_steps")
            action = escalator.observe(step, skipped)
            if action != "none":
                bus.inc(f"escalation.{action}")
                bus.event("escalation", step=step, action=action)
            if action == "force_full":
                forced_full = True
            elif action == "backoff":
                state = resilience.apply_backoff(state, args.guard_backoff_factor)
        if (step % args.log_every == 0 or step == args.steps - 1
                or (healthy is not None and not healthy)):
            loss = float(metrics["loss"])
            rec = {"step": step, "loss": round(loss, 4), "phase": phase,
                   "residue": residue, "due": due,
                   "wall_s": round(time.time() - t0, 1)}
            if escalator is not None:
                rec.update(healthy=healthy, skipped=skipped,
                           escalation=action,
                           lr_scale=round(float(metrics["lr_scale"]), 4))
            bus.emit(rec)
        if args.checkpoint_every and (
                (step and step % args.checkpoint_every == 0)
                or step == args.steps - 1):
            save_ckpt(step)
        if action == "abort":
            save_ckpt(step)
            bus.emit({"event": "abort", "step": step,
                      "consecutive_skips": escalator.consecutive})
            finish("abort")
            sys.exit(3)
    return finish("ok")


if __name__ == "__main__":
    main()
