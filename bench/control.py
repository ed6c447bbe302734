"""Readings that set a cell's limits: the control and the planted faults.

    python3 bench/control.py --workload W --seeds 11,12,13 --variants control,half_batch

Each variant is the plain reference (``bench/reference.py``) put in the
program's place, on the cell's own batches and sizes, compared with the
clean reference by the same numbers that decide ``correct``
(``bench/run.py: compare``):

- ``control``: one precision step below the configuration's (float8 model
  matmul inputs for bfloat16, bfloat16 Newton-Schulz for float32);
- ``half_batch``: the loss taken over the first half of each batch's
  tokens, the rest left out;
- ``no_gather``: the exchange between chips left out: every full step
  orthogonalizes the shard-local blocks, as a block step does.

One JSON line per seed. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
# Run as a script, Python puts bench/ first on the path, where trace.py
# would shadow the standard library module of that name.
if sys.path and Path(sys.path[0]).resolve() == Path(__file__).resolve().parent:
    sys.path.pop(0)
VARIANTS = ("control", "half_batch", "no_gather")


def readings(cell, seed: int, variants, devices) -> list[dict]:
    """Each variant against the clean reference, on one seed's batches."""
    from bench import reference, run
    from bench.traffic import markov

    traffic, n_check = cell.traffic, 3
    phase_of = run._phase_rule(traffic)
    phases = [phase_of(s) for s in range(n_check)]
    batches = markov.batches(traffic, cell.config["vocab_size"], seed)[:n_check]
    key_seed = seed % 2 ** 32
    shard = run._reference_sharding(devices) if len(devices) > 1 else None
    blocks = cell.config.get("blocks", {})
    ref = reference.run(cell.config, traffic, blocks, key_seed, batches, phases,
                        shard=shard)
    out = []
    for variant in variants:
        kw = {"control": {"precision": "control"}, "half_batch": {"half_batch": True},
              "no_gather": {}}[variant]
        var_phases = ["block"] * n_check if variant == "no_gather" else phases
        var = reference.run(cell.config, traffic, blocks, key_seed, batches, var_phases,
                            shard=shard, **kw)
        out.append({"seed": seed, "variant": variant, **run.compare(var, ref),
                    "losses": var["losses"], "reference_losses": ref["losses"]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--variants", required=True,
                    help=f"comma-separated, of {', '.join(VARIANTS)}")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import run

    cell = run.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    import jax

    run._cache_settings(jax)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: {args.workload} needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 2
    variants = args.variants.split(",")
    if not set(variants) <= set(VARIANTS):
        ap.error(f"unknown variant in {variants}")
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in readings(cell, seed, variants, devices[:cell.chips]):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
