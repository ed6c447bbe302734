"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload W --seed N --seconds S --trace 0|1

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
(``bench/configs/<config>.json``), a traffic file
(``bench/traffic/<traffic>.json``) and a limits file
(``bench/limits/<workload>.json``); per-layer metrics are readers in
``bench/metrics/<metric>.py``. A new cell or metric is new files and
entries, read by name; this file does not change.

Set-up builds the training run through the launcher's in-process entry
(``repro.launch.train.main`` with ``--steps 0``: mesh, sharding, comm
engine, ZeRO-1, one jitted step per phase), makes a pool of batches from
the seed and places it as the step takes it, then drives that same step
and state through the warm-up: the first three steps are the ones the
plain reference follows (``bench/reference.py``). The window then calls
the step per the launcher's phase rule, a whole period at a time, never
waiting inside a period, and ends at the first period boundary after
``--seconds``. ``--trace 1`` traces two whole periods of a steady window
instead and reports the per-layer metrics.

Once the window is over and the device's peak memory read, the program's
state is freed and the reference runs; the result's ``correct`` says
whether the three steps agree with it within the cell's limits. The last
line of standard output is the result as one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# Run as a script, Python puts bench/ first on the path, where trace.py
# would shadow the standard library module of that name.
if sys.path and Path(sys.path[0]).resolve() == Path(__file__).resolve().parent:
    sys.path.pop(0)
GiB = 2 ** 30
TRACE_DIR = ROOT / ".bench_trace"
# Reference gradients under this share of the median leaf's are round-off,
# not a signal: such leaves are left out of the update comparison.
NEGLIGIBLE_GRAD = 1e-3
# Model keys of a configuration file and the ModelConfig field each must equal.
MODEL_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
              "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
              "head_dim": "head_dim", "num_hidden_layers": "num_layers",
              "vocab_size": "vocab_size", "tie_word_embeddings": "tie_embeddings",
              "rope_theta": "rope_theta"}


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic: dict
    chips: int
    limits: dict
    end_to_end: list
    per_layer: list


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload, config_name=w["config"],
        config=json.loads((root / configs[w["config"]]["file"]).read_text()),
        traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        chips=w["chips"],
        limits=json.loads((root / "bench" / "limits" / f"{workload}.json").read_text()),
        end_to_end=[m for m in manifest["end_to_end"] if applies(m)],
        per_layer=[m for m in manifest["per_layer"] if applies(m)])


def load_peak(kind: str, root: Path = ROOT) -> dict:
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(peaks)})")
    return peaks[kind]


def load_metric(name: str, root: Path = ROOT):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", root / "bench" / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------

def compare(program: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``.

    ``loss_gap``: the largest |program - reference| loss over the compared
    steps. ``grad_gap``: over leaves, the largest gap between the program's
    and the reference's norm of the first gradient, over the reference's
    norm of that leaf or of the median leaf, whichever is larger.
    ``update_gap``: the same for the parameters' change over the compared
    steps, leaving out leaves whose reference gradient is under
    ``NEGLIGIBLE_GRAD`` of the median leaf's.
    """
    def worst(p: dict, r: dict, keys) -> float:
        med = statistics.median(r[k] for k in keys)
        return max(abs(p[k] - r[k]) / max(r[k], med, 1e-30) for k in keys)

    keys = sorted(ref["grad_norms"])
    g_med = statistics.median(ref["grad_norms"].values())
    moved = [k for k in keys if ref["grad_norms"][k] >= NEGLIGIBLE_GRAD * g_med]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(program["losses"], ref["losses"])),
        "grad_gap": worst(program["grad_norms"], ref["grad_norms"], keys),
        "update_gap": worst(program["delta_norms"], ref["delta_norms"], moved),
    }


def judge(gaps: dict, limits: dict) -> tuple[bool, dict]:
    check = {k: {"value": v, "limit": limits[k]} for k, v in gaps.items()}
    ok = all(math.isfinite(v) and v <= limits[k] for k, v in gaps.items())
    return ok, check


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def _model_config(cell: Cell):
    from repro.configs import ARCHS, get_config

    cfg = dataclasses.replace(get_config(cell.config["arch"]), name=cell.config_name,
                              **cell.config["overrides"])
    for key, field in MODEL_KEYS.items():
        if getattr(cfg, field) != cell.config[key]:
            raise ValueError(f"{cell.config_name}: {key} is {cell.config[key]} in the "
                             f"file but {getattr(cfg, field)} as run")
    ARCHS[cfg.name] = cfg
    return cfg


def _launcher_argv(cell: Cell, cfg, seed: int) -> list:
    t, c = cell.traffic, cell.config
    mesh = ",".join(f"{k}={v}" for k, v in c["mesh"].items())
    argv = ["--arch", cfg.name, "--optimizer", t["optimizer"],
            "--period", str(t["period"]), "--steps", "0",
            "--batch", str(t["batch"]), "--seq", str(t["seq"]),
            "--lr", str(t["lr"]), "--adam-lr", str(t["adam_lr"]),
            "--schedule", "const", "--drift-threshold", "0",
            "--seed", str(seed), "--mesh", mesh,
            "--ns-backend", t["ns_backend"], "--full-schedule", t["full_schedule"],
            "--log-every", "1000000"]
    return argv + (["--zero1"] if c["zero1"] else [])


def _phase_rule(traffic: dict):
    """The launcher's phase for a step (``StaggerSchedule.phase_for``);
    AdamW runs its one compiled phase, ``block``, as the launcher does."""
    from repro.core.muon import StaggerSchedule

    if traffic["optimizer"] == "adamw":
        return lambda step: "block"
    mode = "staggered" if traffic["full_schedule"] == "staggered" else "synchronous"
    return StaggerSchedule(traffic["period"], mode).phase_for


def _grad_norms(opt_state) -> dict:
    """Per-leaf norm of the first gradient as the optimizer got it, from its
    state after one step: Muon's momentum is the gradient itself after one
    step; AdamW's first moment is (1 - b1) times the clipped gradient."""
    import jax
    import jax.numpy as jnp

    inner = opt_state.inner
    out = {}
    trees = []
    if "muon" in inner:
        trees.append((inner["muon"].momentum, 1.0))
    if "adamw" in inner:
        trees.append((inner["adamw"].mu, 1.0 / (1.0 - 0.9)))
    for tree, scale in trees:
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x))) for x in xs])(
            [x for _, x in flat])
        for (path, _), n in zip(flat, jax.device_get(norms)):
            out[_path(path)] = float(n) * scale
    return out


def _path(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _delta_norms(params_host: dict, cfg, seed: int) -> dict:
    """Per-leaf norm of the program's parameter change: the parameters after
    the compared steps against the program's own initial weights."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import init_params

    init = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(seed))
    flat = jax.tree_util.tree_flatten_with_path(init)[0]
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    out = {}
    for path, leaf in flat:
        key = _path(path)
        out[key] = float(diff(jnp.asarray(params_host[key]), leaf))
    del init, flat
    return out


class _Compiles:
    """Counts JAX's backend compile events (a persistent-cache load is timed
    inside the same event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, peak: dict,
             fault=None, save_trace: str | None = None) -> dict:
    """Set up, run the window, check against the reference; the result.

    ``fault(step_fns, phase_rule)`` may replace the step functions before
    the first step (tests plant faults through it)."""
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import reference
    from bench.traffic import markov
    from repro.launch import train

    compiles = _Compiles()
    devices = jax.devices()[:cell.chips]
    traffic, period = cell.traffic, cell.traffic["period"]
    # The launcher and the reference take the seed as a JAX key (32 bits).
    key_seed = seed % 2 ** 32

    cfg = _model_config(cell)
    res = train.main(_launcher_argv(cell, cfg, key_seed))
    state, programs = res.state, dict(res.step_fns)
    del res
    phase_of = _phase_rule(traffic)
    fns = fault(programs, phase_of) if fault is not None else programs

    mesh = jax.tree.leaves(state.params)[0].sharding.mesh
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    batch_sharding = NamedSharding(mesh, P(data_axes or None, None))
    host_pool = markov.batches(traffic, cell.config["vocab_size"], seed)
    pool = [{k: jax.device_put(v, batch_sharding) for k, v in b.items()}
            for b in host_pool]

    # Warm-up: both phases compile and run; the first three steps are the
    # ones the reference follows.
    n_check = 3
    warm = traffic["warmup_periods"] * period
    program = {"losses": []}
    capture_s = 0.0
    step = 0
    for step in range(warm):
        state, metrics = fns[phase_of(step)](state, pool[step % len(pool)])
        if step < n_check:
            program["losses"].append(metrics["loss"])
        if step == 0:
            t = time.perf_counter()
            program["grad_norms"] = _grad_norms(state.opt_state)
            capture_s += time.perf_counter() - t
        if step == n_check - 1:
            t = time.perf_counter()
            program["params"] = {_path(p): np.asarray(x) for p, x in
                                 jax.tree_util.tree_flatten_with_path(
                                     jax.device_get(state.params))[0]}
            capture_s += time.perf_counter() - t
    jax.block_until_ready(state)
    program["losses"] = [float(x) for x in jax.device_get(program["losses"])]

    compiles_before = compiles.count
    step = warm
    window_losses, traced_phases = [], []
    profile_dir = TRACE_DIR / cell.name
    setup_s = time.perf_counter() - T_START - capture_s

    def dispatch_period():
        nonlocal state, step
        for _ in range(period):
            phase = phase_of(step)
            with jax.profiler.TraceAnnotation("batch"):
                batch = pool[step % len(pool)]
            with jax.profiler.TraceAnnotation("dispatch"):
                state, metrics = fns[phase](state, batch)
            window_losses.append(metrics["loss"])
            step += 1

    if not trace:
        t0 = time.perf_counter()
        waiting = None
        while True:
            dispatch_period()
            # Keep one period queued on the device: wait for the previous
            # period's last step, never for one inside the period in flight.
            if waiting is not None:
                with jax.profiler.TraceAnnotation("block"):
                    waiting.block_until_ready()
            waiting = window_losses[-1]
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    else:
        dispatch_period()                     # a steady period before the trace
        jax.block_until_ready(state)
        shutil.rmtree(profile_dir, ignore_errors=True)
        jax.profiler.start_trace(str(profile_dir))
        first = step
        with jax.profiler.TraceAnnotation("traced_window"):
            for _ in range(2):
                dispatch_period()
            with jax.profiler.TraceAnnotation("block"):
                jax.block_until_ready(state)
        jax.profiler.stop_trace()
        traced_phases = [phase_of(s) for s in range(first, step)]
        window_s = None
    window_compiles = compiles.count - compiles_before
    compiles.close()
    t_after = time.perf_counter()
    n_window = len(window_losses)
    losses = np.asarray(jax.device_get(window_losses), np.float64)
    failed = int(np.sum(~np.isfinite(losses)))

    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(st.get("peak_bytes_in_use", 0) for st in stats)
    print(f"memory_stats device 0: {stats[0]}", flush=True)
    scopes = {}
    for phase in sorted(set(phase_of(s) for s in range(period))):
        compiled = programs[phase].lower(state, pool[0]).compile()
        ma = compiled.memory_analysis()
        print(f"memory_analysis {phase}: arguments {ma.argument_size_in_bytes} "
              f"temp {ma.temp_size_in_bytes} output {ma.output_size_in_bytes} "
              f"alias {ma.alias_size_in_bytes} total(arguments+temp) "
              f"{ma.argument_size_in_bytes + ma.temp_size_in_bytes} bytes; "
              f"peak_bytes_in_use {peak_bytes}", flush=True)
        if trace:
            from bench import trace as trace_lib

            scopes[phase] = trace_lib.hlo_scopes(compiled.as_text())
        del compiled

    t_ref_start = time.perf_counter()
    # Free the program before the reference runs.
    del state, fns, programs, pool, metrics, window_losses
    gc.collect()
    jax.clear_caches()

    t_ref = time.perf_counter()
    program["delta_norms"] = _delta_norms(program.pop("params"), cfg, key_seed)
    shard = _reference_sharding(devices) if len(devices) > 1 else None
    ref = reference.run(cell.config, traffic, cell.config.get("blocks", {}), key_seed,
                        host_pool[:n_check], [phase_of(s) for s in range(n_check)],
                        shard=shard)
    t_ref = time.perf_counter() - t_ref
    gaps = compare(program, ref)
    correct, check = judge(gaps, cell.limits)
    if window_compiles:
        correct = False
        check["window_compiles"] = {"value": window_compiles, "limit": 0}

    print(f"timing: setup_s {setup_s:.3f} (capture for the check {capture_s:.3f} left "
          f"out), window {window_s}, memory readings {t_ref_start - t_after:.3f}, "
          f"reference and comparison {t_ref:.3f}", file=sys.stderr, flush=True)
    dev = devices[0]
    result = {"correct": correct, "attempted": n_window, "failed": failed,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": int(peak_bytes)}}
    if not trace:
        tokens = n_window * traffic["batch"] * traffic["seq"]
        tokens_per_s = tokens / window_s
        from bench import flops

        mfu = (100.0 * flops.model_flops_per_token(cell.config, traffic["seq"])
               * tokens_per_s / (len(devices) * peak["bf16_flops_per_s"]))
        values = {"tokens_per_s": tokens_per_s, "mfu": mfu,
                  "peak_hbm_gib": peak_bytes / GiB, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        from bench import trace as trace_lib

        tr = trace_lib.load(str(profile_dir))
        tr.attach_scopes(traced_phases, scopes)
        if save_trace:
            tr.to_json(save_trace)
        shutil.rmtree(profile_dir, ignore_errors=True)
        lo, hi = tr.window()
        reading = trace_lib.Reading(trace=tr, phases=traced_phases, lo=lo, hi=hi,
                                    cfg=cell.config, traffic=traffic, peak=peak)
        metrics_out = {}
        for m in cell.per_layer:
            value = load_metric(m["name"]).read(reading)
            if value is not None:
                metrics_out[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics_out
        busy = reading.per_device_mean(lambda d: trace_lib.union_ns(tr.ops[d], lo, hi))
        result["device"]["busy_s"] = (busy or 0.0) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = _breakdown(tr, lo, hi)
    result["check"] = check
    return result


def _reference_sharding(devices):
    """Place each reference array over all the cell's devices, split on its
    largest dimension that divides evenly (replicated when none does)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("r",))
    n = len(devices)

    def shard(x):
        dims = [i for i, d in enumerate(x.shape) if d % n == 0]
        spec = [None] * x.ndim
        if dims:
            spec[max(dims, key=lambda i: (x.shape[i], i))] = "r"
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))
    return shard


def _op_label(op) -> str:
    """``<stage>/<op>``: the optimizer's ``muonbp`` stage, else backward or
    forward (JAX's ``transpose(jvp(...))`` and ``jvp(...)`` scopes), with
    an einsum's subscripts where the op has them; an op with no scope is
    named by its HLO instruction kind."""
    if not op[3]:
        return op[2].rstrip("0123456789.")
    parts = op[3].split("/")
    muon = [re.sub(r"\.s\d+", "", p) for p in parts if p.startswith("muonbp.")]
    if muon:
        stage = muon[0]
    elif any(p.startswith("transpose(") for p in parts):
        stage = "backward"
    elif any(p.startswith("jvp(") for p in parts):
        stage = "forward"
    else:
        stage = "other"
    einsum = [p for p in parts if "->" in p]
    return "/".join([stage] + einsum[-1:] + parts[-1:])


def _breakdown(tr, lo, hi) -> dict:
    """The device ops that took most time (innermost ops, summed by name
    scope and op), and the longest idle gaps with the benchmark's host span
    open in each, on the first device. Host spans align to the device clock
    only to about a millisecond, so a short gap's label is approximate."""
    from bench import trace as trace_lib

    dev = min(tr.ops)
    by_label: dict = {}
    for op in trace_lib.leaf_ops(trace_lib.in_window(tr.ops[dev], lo, hi)):
        label = _op_label(op)
        by_label[label] = by_label.get(label, 0.0) + op[1] / 1e9
    top = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(trace_lib.gaps(tr.ops[dev], lo, hi), key=lambda g: g[0] - g[1])[:10]

    def host_at(t):
        open_ = [h[2] for h in tr.host
                 if h[0] <= t <= h[0] + h[1] and h[2] != "traced_window"]
        return open_[-1] if open_ else "none"
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[host_at((s + e) / 2), (e - s) / 1e9] for s, e in idle]}


def _cache_settings(jax) -> None:
    """Every program of the run goes to the cache, the small ones too, so
    that a second run of the cell compiles nothing; no size limit, so no
    entry is evicted (an environment that sets one turns on JAX's LRU
    bookkeeping, which failed every write on the chip's machine)."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--save-trace", default=None,
                    help="also write the reduced trace as gzipped JSON here")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    # JAX's persistent compilation cache lives in the checkout, at a fixed
    # path; the launcher takes the directory from this variable.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    import jax

    _cache_settings(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)", file=sys.stderr)
        return 2
    try:
        peak = load_peak(devices[0].device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), peak=peak,
                      save_trace=args.save_trace)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
