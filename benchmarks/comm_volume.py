"""Paper Table 4 analogue: optimizer-step communication volume & modeled
throughput, from post-SPMD HLO on 8 forced host devices (subprocess so the
device-count override can't leak into this process).

Two measurement families:

  * Train-step collectives per optimizer (Muon / BlockMuon / MuonBP@P=5 /
    AdamW) — the original Table-4 rows (full pass only; fwd/bwd comm
    included, AdamW row is the baseline to subtract).
  * Optimizer-isolated audits (``--quick`` covers these): the update alone
    is compiled per (engine x phase x zero1) and its post-SPMD collective
    schedule is reported next to ``distributed.plan.CommPlan``'s prediction
    — rows carry the ``engine``/``predicted_bytes``/``measured_collectives``
    columns for eyeballing drift, and the ``schedule`` column A/Bs the
    shard_map full step's barrier vs pipelined execution (same bytes by
    contract — the pipeline reorders communication, never adds to it). The
    *enforced* plan-vs-HLO gate lives in tests/test_distributed_engine.py
    and tests/test_update_program.py (run by ci.sh's multi-device smoke
    step); this module is the measurement/reporting surface. A
    bucketing=off row keeps the ROADMAP "bucketing x sharding" A/B visible.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import row

ICI_BYTES_PER_S = 50e9

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
quick = os.environ.get("REPRO_COMM_QUICK") == "1"
import json, functools, dataclasses
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.distributed import audit as audit_lib
from repro.distributed import make_engine, plan_comm
from repro.distributed import zero1 as z1
from repro.models.model import init_params
from repro.sharding import specs as sh
from repro.core import adamw, combine, label_tree, muon, muon_full, block_muon
from repro.training.train_step import TrainState, train_step

cfg = get_config("muonbp-960m")
# keep compile cheap; per-layer comm scales linearly
cfg = dataclasses.replace(cfg, num_layers=2 if quick else 4)
mesh = make_mesh((2, 4), ("data", "model"))
ctx = sh.make_ctx(cfg, mesh, global_batch=8)

a_params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
pspecs = sh.param_specs(a_params, cfg, mesh)
a_params = jax.tree.map(
    lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
    a_params, pspecs)
labels = label_tree(a_params)
bspecs = sh.block_specs_for(a_params, pspecs, mesh)
bspecs = jax.tree.map(lambda l, b: b if l == "muon" else None, labels, bspecs)

def opt_for(engine="gspmd", zero1=False, bucketing=True, matrix=muon,
            full_schedule=None):
    comm = make_engine(a_params, pspecs, mesh, zero1=zero1) if engine == "shard_map" else None
    m = matrix(1e-3, block_specs=bspecs, comm=comm, bucketing=bucketing,
               full_schedule=full_schedule)
    return combine({"muon": m, "adamw": adamw(1e-3)}, labels)

def measure_train(matrix_opt, phase):
    if matrix_opt is None:
        opt = combine({"adamw": adamw(1e-3)}, jax.tree.map(lambda _: "adamw", labels))
    else:
        opt = combine({"muon": matrix_opt, "adamw": adamw(1e-3)}, labels)
    a_opt = jax.eval_shape(opt.init, a_params)
    a_opt = z1.attach(a_opt, a_params, mesh)
    state = TrainState(a_params, a_opt, jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())))
    batch = {
        "tokens": jax.ShapeDtypeStruct((8, 256), jnp.int32, sharding=NamedSharding(mesh, P("data", None))),
        "labels": jax.ShapeDtypeStruct((8, 256), jnp.int32, sharding=NamedSharding(mesh, P("data", None))),
    }
    fn = functools.partial(train_step, cfg=cfg, optimizer=opt, ctx=ctx, phase=phase)
    compiled = jax.jit(fn).lower(state, batch).compile()
    coll = audit_lib.parse_collectives(compiled.as_text())
    return sum(v["bytes"] for v in coll.values())

def measure_update(engine, phase, zero1=False, bucketing=True, full_schedule=None):
    opt = opt_for(engine, zero1=zero1, bucketing=bucketing,
                  full_schedule=full_schedule)
    a_opt = jax.eval_shape(opt.init, a_params)
    a_opt = z1.attach(a_opt, a_params, mesh, zero1=zero1)
    upd_sh = jax.tree.map(
        lambda x: x.sharding, z1.attach(a_params, a_params, mesh, zero1=zero1))
    res = audit_lib.audit_optimizer(opt, a_params, a_opt, phase=phase,
                                    update_shardings=upd_sh)
    gather_ops = ("all-gather", "reduce-scatter", "all-to-all")
    return {"bytes": sum(res.bytes_of(op) for op in gather_ops),
            "count": res.total_count}

plan = plan_comm(a_params, pspecs, mesh, labels=labels, block_specs=bspecs)
plan_z = plan_comm(a_params, pspecs, mesh, labels=labels, block_specs=bspecs, zero1=True)
out = {"plan": {ph: plan.predicted_bytes(ph) for ph in ("block", "full", "apply")},
       "plan_zero1": {ph: plan_z.predicted_bytes(ph) for ph in ("block", "full", "apply")},
       "update": {}}
for engine in ("gspmd", "shard_map"):
    for phase in ("block", "full"):
        out["update"][f"{engine}_{phase}"] = measure_update(engine, phase)
# the full-step schedule A/B: pipelined (the shard_map_full default above)
# must move exactly the bytes the barrier body does — just reordered.
out["update"]["shard_map_full_barrier"] = measure_update(
    "shard_map", "full", full_schedule="barrier")
out["update"]["shard_map_block_zero1"] = measure_update("shard_map", "block", zero1=True)
out["update"]["shard_map_full_zero1"] = measure_update("shard_map", "full", zero1=True)
out["update"]["gspmd_block_nobucket"] = measure_update("gspmd", "block", bucketing=False)

if not quick:
    out["train"] = {
        "adamw": measure_train(None, "block"),
        "muon": measure_train(muon_full(1e-3, block_specs=bspecs), "full"),
        "blockmuon": measure_train(block_muon(1e-3, block_specs=bspecs), "block"),
        "muonbp_block": measure_train(muon(1e-3, block_specs=bspecs), "block"),
        "muonbp_full": measure_train(muon(1e-3, block_specs=bspecs), "full"),
    }
print("RESULT " + json.dumps(out))
"""


def run(quick: bool = False) -> list[str]:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["REPRO_COMM_QUICK"] = "1" if quick else "0"
    # The child measures HLO bytes on 8 host devices; it must never try to
    # take an accelerator that this process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True, env=env,
        timeout=1800,
    )
    if proc.returncode != 0:
        return [row("comm_volume_error", 0.0, proc.stderr.strip().replace("\n", ";")[-200:])]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    r = json.loads(line[len("RESULT "):])

    rows = []
    # Optimizer-isolated audit rows: measured (derived) vs plan (predicted).
    plan_for = {
        "gspmd_block": ("plan", "block"), "gspmd_full": ("plan", "full"),
        "shard_map_block": ("plan", "block"), "shard_map_full": ("plan", "full"),
        "shard_map_full_barrier": ("plan", "full"),
        "shard_map_block_zero1": ("plan_zero1", "block"),
        "shard_map_full_zero1": ("plan_zero1", "full"),
        "gspmd_block_nobucket": ("plan", "block"),
    }
    for name, rec in r["update"].items():
        plan_key, phase = plan_for[name]
        engine = "shard_map" if name.startswith("shard_map") else "gspmd"
        schedule = "-"
        if engine == "shard_map" and phase == "full":
            schedule = "barrier" if name.endswith("barrier") else "pipelined"
        rows.append(row(
            f"comm_opt_update_{name}", 0.0, f"{rec['bytes']}B",
            bucketing="off" if name.endswith("nobucket") else "on",
            engine=engine,
            predicted_bytes=str(r[plan_key][phase]),
            measured_collectives=str(rec["count"]),
            schedule=schedule,
        ))
    # The ZeRO-1 apply-time gather is priced by the plan but sits outside
    # optimizer.update — surface it so the trade stays visible.
    rows.append(row("comm_opt_zero1_apply_gather", 0.0, "plan_only",
                    engine="shard_map",
                    predicted_bytes=str(r["plan_zero1"]["apply"])))

    if "train" in r:
        t = r["train"]
        p = 5
        muonbp_avg = (t["muonbp_full"] + (p - 1) * t["muonbp_block"]) / p
        rows += [
            row("comm_bytes_adamw", 0.0, str(t["adamw"]), engine="gspmd"),
            row("comm_bytes_muon", 0.0, str(t["muon"]), engine="gspmd"),
            row("comm_bytes_blockmuon", 0.0, str(t["blockmuon"]), engine="gspmd"),
            row("comm_bytes_muonbp_block_phase", 0.0, str(t["muonbp_block"]), engine="gspmd"),
            row("comm_bytes_muonbp_full_phase", 0.0, str(t["muonbp_full"]), engine="gspmd"),
            row("comm_bytes_muonbp_amortized_P5", 0.0, f"{muonbp_avg:.0f}", engine="gspmd"),
        ]
        # optimizer-attributable comm = total - adamw baseline (fwd/bwd comm)
        opt_muon = max(t["muon"] - t["adamw"], 1)
        opt_muonbp = max(muonbp_avg - t["adamw"], 1)
        opt_block = max(t["blockmuon"] - t["adamw"], 0)
        rows.append(row("comm_optimizer_reduction_muonbp_vs_muon", 0.0,
                        f"x{opt_muon/opt_muonbp:.2f}_paper_claims_~{p}x"))
        rows.append(row("comm_optimizer_blockmuon_bytes", 0.0,
                        f"{opt_block}_paper_claims_~0"))
        # modeled throughput: step time = compute (fixed) + comm/ICI_BW; take
        # compute from the paper's 8%-overhead observation scaled by our ratio.
        t_comm_muon = t["muon"] / ICI_BYTES_PER_S
        t_comm_muonbp = muonbp_avg / ICI_BYTES_PER_S
        rows.append(row("comm_modeled_step_saving", 0.0,
                        f"{(t_comm_muon - t_comm_muonbp)*1e3:.2f}ms/step_at_50GBps"))
    return rows
