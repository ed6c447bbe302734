"""Optimizer-step microbenchmark (paper Sec 2.2 'Computational costs').

Times a full optimizer update over a realistic param set for AdamW / Muon /
BlockMuon / MuonBP / Dion. The Muon-family rows are measured twice — with
the shape-bucketed batched NS engine (bucketing=on, the default: one NS
chain per distinct unit shape) and with per-leaf dispatch (bucketing=off) —
so the engine win shows up as a column-wise A/B on identical numerics. The
backend column records the NS execution backend (jnp on CPU; the pallas
interpret path is a correctness artifact benchmarked in ns_cost).

The shard_map-engine full step is additionally measured once per execution
schedule (``schedule`` column: barrier vs pipelined) on the local 1-device
mesh — identical numerics and zero collectives at this scale, so the row
pair isolates the pipeline body's dispatch overhead; the multi-device
byte-level A/B lives in comm_volume."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import row, timeit_stats
from repro.configs import get_config
from repro.core import adamw, block_muon, combine, dion, label_tree, muon, muon_full
from repro.core.blocking import BlockSpec2D
from repro.launch.mesh import make_mesh
from repro.models.model import init_params


def run(quick: bool = False) -> list[str]:
    cfg = get_config("muonbp-960m").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    grads = jax.tree.map(lambda p: jnp.ones_like(p) * 0.01, params)
    labels = label_tree(params)
    blocks = jax.tree.map(
        lambda p: BlockSpec2D(1, 4 if p.ndim >= 2 and p.shape[-1] % 4 == 0 else 1)
        if p.ndim >= 2 else None,
        params,
    )

    rows = []
    n_params = sum(int(p.size) for p in jax.tree.leaves(params))
    variants = [
        ("adamw", None, "block", "-", "-"),
        ("muon_full", lambda b: muon_full(1e-3, bucketing=b, ns_backend="jnp"),
         "full", "jnp", None),
        ("blockmuon", lambda b: block_muon(1e-3, block_specs=blocks, bucketing=b,
                                           ns_backend="jnp"), "block", "jnp", None),
        ("muonbp_block_phase", lambda b: muon(1e-3, block_specs=blocks, bucketing=b,
                                              ns_backend="jnp"), "block", "jnp", None),
        ("dion_r32", lambda b: dion(1e-3, rank=32), "block", "-", "-"),
    ]
    for name, make, phase, backend, bucket_col in variants:
        bucket_modes = (
            [(bucket_col, None)]
            if bucket_col is not None
            else [("on", True), ("off", False)]
        )
        for bucket_label, bucketing in bucket_modes:
            if make is None:
                opt = combine(
                    {"adamw": adamw(1e-3)}, jax.tree.map(lambda _: "adamw", labels)
                )
            else:
                matrix_opt = make(bucketing) if bucketing is not None else make(True)
                opt = combine({"muon": matrix_opt, "adamw": adamw(1e-3)}, labels)
            state = opt.init(params)

            @jax.jit
            def step(g, s, p):
                return opt.update(g, s, p, phase)

            st = timeit_stats(step, grads, state, params, warmup=1, iters=3,
                              name=f"opt_step_{name}")
            rows.append(
                row(f"opt_step_{name}", st["median_us"],
                    f"{n_params/1e6:.1f}M_params",
                    backend=backend, bucketing=bucket_label,
                    p50_us=f"{st['p50_us']:.1f}", p95_us=f"{st['p95_us']:.1f}")
            )

    # shard_map engine full step, once per schedule (barrier vs pipelined).
    from jax.sharding import PartitionSpec as P

    from repro.distributed import make_engine

    mesh = make_mesh((1, 1), ("data", "model"))
    pspecs = jax.tree.map(lambda p: P(*(None,) * p.ndim), params)
    for sched in ("barrier", "pipelined"):
        engine = make_engine(params, pspecs, mesh)
        matrix_opt = muon(1e-3, block_specs=blocks, comm=engine,
                          ns_backend="jnp", full_schedule=sched)
        opt = combine({"muon": matrix_opt, "adamw": adamw(1e-3)}, labels)
        state = opt.init(params)

        @jax.jit
        def estep(g, s, p, _opt=opt):
            return _opt.update(g, s, p, "full")

        st = timeit_stats(estep, grads, state, params, warmup=1, iters=3,
                          name="opt_step_muonbp_full_engine")
        rows.append(
            row("opt_step_muonbp_full_engine", st["median_us"],
                f"{n_params/1e6:.1f}M_params",
                backend="jnp", bucketing="on", engine="shard_map",
                schedule=sched,
                p50_us=f"{st['p50_us']:.1f}", p95_us=f"{st['p95_us']:.1f}")
        )
    return rows
