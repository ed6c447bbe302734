"""Chip smoke test: MuonBP training of muonbp-960m on a TPU, end to end.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chips  # data=2 x model=2 ZeRO-1 vs one device

Both paths train through ``repro.launch.train.main`` — the entry point
``python -m repro.launch.train`` calls — at muonbp-960m's published widths
(12 layers, d_model 1536, 16/4 heads, d_ff 6144, vocab 128256; random
weights from ``--seed``), with MuonBP at period 2 so block and full steps
both run. One process drives everything: it never starts a child.

One chip: the same run twice, NS on the jnp backend and then on the Pallas
kernels. It checks that every loss is finite, that the two backends' losses
agree per step within ``LOSS_TOL``, and that the compiled Pallas step holds
``tpu_custom_call`` (the kernels were compiled, not interpreted). A third
run with the Muon update zeroed (``--lr 0``) is the control: its losses
must differ from the Pallas run's by more than ``LOSS_TOL``, or the
comparison could not tell a kernel that returns zeros from a working one.

Four chips: the run on a ``data=2,model=2`` mesh with ZeRO-1, the shard_map
engine and the default pipelined full step, then the same steps and
batches on a one-device mesh whose optimizer blocks are the four-chip
mesh's shards (``sharding.specs.block_specs_for``), so both block and full
steps have an exact single-device counterpart. The control is that
reference with every step a block step, what a full step that skipped its
gathers would compute; it must differ from the four-chip run by more than
``LOSS_TOL``.

Step times printed here are first readings of one run, not a benchmark.
Without a TPU the script exits non-zero before any work and prints no
result; the last line of a passing run is the JSON result object.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

import jax

ARCH = "muonbp-960m"
PERIOD = 2
# Six steps at period 2: full, block, full, block, full, block. Steps 0 and
# 1 compile the two phases; later steps that compiled nothing are readings.
STEPS = 6
WARMUP = 2
# One chip: the longest sequence (<= the paper's 8192) whose step compiles
# for a described v5e inside 16 GiB with ~10% headroom at batch 1. Measured
# with memory_analysis(): 8192 needs 20.3 GiB; 4096 needs 14.8 GiB (7%
# headroom); 3584 needs 14.0 GiB (12%); both backends alike.
ONE_CHIP = dict(batch=1, seq=3584)
# Four chips: batch 2 (one row per data shard) at half the sequence, so the
# one-device reference holds the same 3584 tokens as the one-chip run.
FOUR_CHIPS = dict(batch=2, seq=1792)
# Muon at the launcher's default LR; AdamW (embedding, head, norms) at a
# tenth of its default 0.008. At the default AdamW LR the loss rises by
# step 4 at either Muon LR, with or without a 3-step warmup (12.06 -> 14.10
# at 0.02/0.008, 15.43 with the warmup, 12.12 at 0.002/0.008), and stays
# flat at 0.02/0.0008 (on a v5e; PERF.md, Findings).
LR, ADAM_LR = 0.02, 0.0008
# Per-step |loss difference| allowed between the compared runs, set from
# readings on a v5e at these LRs: jnp vs Pallas differ by at most 1.5e-3
# (step 0 runs no update, so it is equal); the controls differ by up to
# 1.7e-2 (Muon update zeroed) and 7.5e-3 (all block steps against the
# four-chip blocking). The tolerance sits between, and each run checks
# that its control still falls outside it.
LOSS_TOL = 4e-3


class SmokeFailure(RuntimeError):
    """A phase of the smoke test produced a wrong or missing result."""


def _check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def _train_args(shape: dict, *extra: str) -> list:
    return ["--arch", ARCH, "--optimizer", "muonbp", "--period", str(PERIOD),
            "--steps", str(STEPS), "--batch", str(shape["batch"]),
            "--seq", str(shape["seq"]), "--seed", "0", "--obs-block",
            "--lr", str(LR), "--adam-lr", str(ADAM_LR),
            "--log-every", str(STEPS), "--drift-threshold", "0", *extra]


def _report(label: str, run) -> dict:
    """Print compile and step times of a run; returns its summary."""
    _check(run.status == "ok", f"{label}: run ended with status {run.status!r}")
    bad = [i for i, v in enumerate(run.losses) if not math.isfinite(v)]
    _check(not bad, f"{label}: non-finite loss at steps {bad}: {run.losses}")
    for i, ph in enumerate(run.phases):
        if run.compile_s[i] > 0:
            print(f"{label}: step {i} ({ph}) compiled for {run.compile_s[i]:.3f} s "
                  f"of its {run.step_s[i]:.3f} s wall")
    for ph in sorted(set(run.phases)):
        times = [run.step_s[i] for i, p in enumerate(run.phases)
                 if p == ph and i >= WARMUP and run.compile_s[i] == 0]
        print(f"{label}: {ph} step wall s {times} "
              "(first chip reading, not a benchmark)")
        _check(bool(times), f"{label}: no {ph} step ran without compiling")
    print(f"{label}: losses {run.losses}")
    return {"losses": run.losses, "phases": run.phases}


def _max_diff(label: str, a: dict, b: dict) -> float:
    diffs = [abs(x - y) for x, y in zip(a["losses"], b["losses"])]
    print(f"{label}: per-step |loss diff| {diffs} (tolerance {LOSS_TOL})")
    return max(diffs)


def _compare(label: str, a: dict, b: dict) -> None:
    _check(a["phases"] == b["phases"], f"{label}: phases {a['phases']} vs {b['phases']}")
    _check(_max_diff(label, a, b) <= LOSS_TOL, f"{label}: losses disagree")


def _control(label: str, run: dict, control: dict) -> None:
    """The comparison must fail on a control that computes a wrong update."""
    _check(_max_diff(label, run, control) > LOSS_TOL,
           f"{label}: the tolerance cannot tell the control from the run")


def _peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def _kernel_calls(run, phase: str = "full") -> int:
    """``tpu_custom_call`` ops in the compiled step of ``phase``; prints the
    step's compile-time HBM plan (``peak_bytes_in_use`` leaves out the
    program's temporaries)."""
    compiled = run.step_fns[phase].lower(run.state, run.batch).compile()
    mem = compiled.memory_analysis()
    if mem is not None:
        print(f"{phase} step memory_analysis: arguments "
              f"{mem.argument_size_in_bytes} temp {mem.temp_size_in_bytes} "
              f"output {mem.output_size_in_bytes} aliased {mem.alias_size_in_bytes}")
    return compiled.as_text().count("tpu_custom_call")


def one_chip() -> None:
    from repro.launch import train

    args = _train_args(ONE_CHIP)
    _print_shape(ONE_CHIP)
    runs = {}
    for backend in ("jnp", "pallas"):
        run = train.main(args + ["--ns-backend", backend])
        runs[backend] = _report(backend, run)
        if backend == "pallas":
            n_calls = _kernel_calls(run)
            print(f"pallas: compiled full step holds {n_calls} tpu_custom_call")
            _check(n_calls > 0, "pallas: step compiled without its kernels")
        del run
        print(f"{backend}: peak_bytes_in_use so far {_peak_bytes(jax.devices()[:1])}")
    _compare("jnp vs pallas", runs["jnp"], runs["pallas"])
    run = train.main(args + ["--ns-backend", "jnp", "--lr", "0"])
    control = _report("control (Muon update zeroed)", run)
    del run
    _control("pallas vs control", runs["pallas"], control)


def _print_shape(shape: dict) -> None:
    from repro.configs import get_config

    cfg = get_config(ARCH)
    print(f"shape: {ARCH} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} batch={shape['batch']} seq={shape['seq']} "
          f"period={PERIOD} steps={STEPS}")


def one_device_reference(shape: dict, mesh_shape=(2, 2)) -> tuple:
    """The four-chip run's steps on device 0 alone, with the optimizer
    blocked as the ``data x model`` mesh shards the parameters; returns
    that run and the control that takes a block step every step."""
    from repro.configs import get_config
    from repro.core import label_tree
    from repro.core.schedule import wsd
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_optimizer
    from repro.models.model import init_params
    from repro.sharding import specs as sh
    from repro.training.train_step import init_train_state, make_train_step_fns

    cfg = get_config(ARCH)
    axes = ("data", "model")
    mesh_n = make_mesh(mesh_shape, axes,
                       devices=jax.devices()[:math.prod(mesh_shape)])
    mesh_1 = make_mesh((1, 1), axes, devices=jax.devices()[:1])
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    labels = label_tree(params)
    bspecs = sh.block_specs_for(params, sh.param_specs(params, cfg, mesh_n), mesh_n)
    bspecs = jax.tree.map(lambda b, l: b if l == "muon" else None, bspecs, labels)
    placement = sh.named(mesh_1, sh.param_specs(params, cfg, mesh_1))
    # The run's LRs and the launcher's wsd schedule; the implicit (GSPMD)
    # path partitions each matrix into the given blocks itself.
    optimizer, period = build_optimizer(
        "muonbp", params, lr=LR, adam_lr=ADAM_LR, period=PERIOD,
        schedule_fn=lambda peak: wsd(peak, STEPS), block_specs=bspecs,
        variant="muon")
    fns = make_train_step_fns(cfg, optimizer, sh.make_ctx(cfg, mesh_1))
    runs = []
    for phase_of in (lambda step: "full" if step % period == 0 else "block",
                     lambda step: "block"):
        # The step donates its state: each run starts from a fresh init.
        params = jax.device_put(init_params(jax.random.PRNGKey(0), cfg), placement)
        state = init_train_state(params, optimizer)
        del params
        data = iter(SyntheticLM(cfg, shape["batch"], shape["seq"], seed=0))
        losses, phases = [], []
        for step in range(STEPS):
            state, metrics = fns[phase_of(step)](state, next(data))
            losses.append(metrics["loss"])
            phases.append(phase_of(step))
        del state
        runs.append({"losses": [float(v) for v in jax.device_get(losses)],
                     "phases": phases})
    return tuple(runs)


def four_chips() -> None:
    from repro.launch import train

    devices = jax.devices()
    _check(len(devices) >= 4, f"--four-chips needs 4 devices, have {len(devices)}")
    _print_shape(FOUR_CHIPS)
    run = train.main(_train_args(FOUR_CHIPS, "--mesh", "data=2,model=2", "--zero1"))
    holders = set()
    for p in jax.tree.leaves(run.state.params):
        holders |= set(p.sharding.device_set)
    _check(holders == set(devices[:4]), f"four-chip: params on {holders}")
    print(f"four-chip: params on {len(holders)} devices")
    sharded = _report("four-chip", run)
    del run
    print(f"four-chip: peak_bytes_in_use per device {_peak_bytes(devices[:4])}")
    ref, control = one_device_reference(FOUR_CHIPS)
    print(f"one-device reference: losses {ref['losses']}")
    print(f"control (all block steps): losses {control['losses']}")
    _compare("four-chip vs one-device", sharded, ref)
    _control("four-chip vs control", sharded, control)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data=2,model=2 ZeRO-1 phase and its "
                         "one-device reference")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch import compile_cache

    compile_cache.enable()
    four_chips() if args.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
