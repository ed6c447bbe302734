"""A cell at a size a CPU test run holds: the same model family and
optimizer as the benchmark's cells, with tiny widths."""

import json
import sys
from pathlib import Path

from bench import run

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))
BLOCKS_2X2 = {"attn/wq": [1, 2], "attn/wk": [1, 2], "attn/wv": [1, 2], "attn/wo": [2, 1],
              "mlp/wi": [1, 2], "mlp/wg": [1, 2], "mlp/wo": [2, 1]}


def tiny_cell(optimizer: str = "muonbp", data: int = 1, model: int = 1,
              limits_of: str = "phi4-mini.1chip.muonbp") -> run.Cell:
    """A 2-layer phi4-mini-family cell; ``limits_of`` names the benchmark
    cell whose limits it is judged by."""
    config = {
        "name": "tiny", "arch": "phi4-mini-3.8b",
        "overrides": {"num_layers": 2, "vocab_size": 512, "d_model": 128,
                      "num_heads": 4, "num_kv_heads": 2, "head_dim": 32, "d_ff": 256},
        "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
        "vocab_size": 512, "tie_word_embeddings": True, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "mesh": {"data": data, "model": model},
        "zero1": data > 1, "blocks": BLOCKS_2X2 if model > 1 else {}}
    traffic = {"optimizer": optimizer, "period": 2, "full_schedule": "pipelined",
               "ns_backend": "jnp", "lr": 0.02, "adam_lr": 0.0008, "batch": 2, "seq": 64,
               "pool_batches": 4, "table_seed": 0, "branching": 8, "warmup_periods": 2}
    e2e = [{"name": n, "unit": u} for n, u in
           [("tokens_per_s", "tokens/s"), ("mfu", "%"), ("peak_hbm_gib", "GiB"),
            ("setup_s", "s")]]
    limits = json.loads((ROOT / "bench" / "limits" / f"{limits_of}.json").read_text())
    return run.Cell("tiny.cell", "tiny", config, traffic, data * model,
                    {k: v for k, v in limits.items() if k in ("loss_gap", "grad_gap", "update_gap")},
                    e2e, [])


PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
