"""Operation counts of a training cell, from its configuration file.

``model_flops_per_token``: what the forward and backward passes require per
token, the usual 6 x (matmul parameters) plus causal attention: the head
matmul counts over the padded vocabulary it is computed on, the embedding
lookup counts nothing, and activation recomputation is not counted.

``ns_flops``: the matmul operations of one optimizer step's Newton-Schulz
iterations on one device, from the shapes of the units it orthogonalizes:
on a full step each layer's whole matrix, on a block step each block of the
cell's block grid; under ZeRO-1 a device orthogonalizes only the layers of
its own data shard. Each iteration on an m x n unit (m <= n after the
smaller-side transpose) costs X X^T (2 m^2 n), A^2 (2 m^3) and B X
(2 m^2 n). ``ns_bytes`` is the least HBM traffic of those iterations (each
matmul reads its two operands and writes its result once, in float32).
"""

from __future__ import annotations

NS_STEPS = 5
VOCAB_PAD = 256


def _dims(cfg: dict) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    v = cfg["vocab_size"]
    return {"L": cfg["num_hidden_layers"], "D": d, "F": cfg["intermediate_size"],
            "q": cfg["num_attention_heads"] * hd,
            "kv": cfg["num_key_value_heads"] * hd,
            "V": (v + VOCAB_PAD - 1) // VOCAB_PAD * VOCAB_PAD}


def matrices(cfg: dict) -> dict:
    """Per-layer matrix shapes (rows, cols) of the stacked layer leaves."""
    n = _dims(cfg)
    D, F = n["D"], n["F"]
    return {"attn/wq": (D, n["q"]), "attn/wk": (D, n["kv"]),
            "attn/wv": (D, n["kv"]), "attn/wo": (n["q"], D),
            "mlp/wi": (D, F), "mlp/wg": (D, F), "mlp/wo": (F, D)}


def matmul_params(cfg: dict) -> int:
    n = _dims(cfg)
    per_layer = sum(r * c for r, c in matrices(cfg).values())
    return n["L"] * per_layer + n["D"] * n["V"]


def model_flops_per_token(cfg: dict, seq: int) -> float:
    n = _dims(cfg)
    return 6.0 * matmul_params(cfg) + 6.0 * n["L"] * seq * n["q"]


def _ns_unit(m: int, n: int) -> tuple[float, float]:
    """(flops, bytes) of NS_STEPS iterations on one m x n unit."""
    m, n = min(m, n), max(m, n)
    flops = NS_STEPS * (4.0 * m * m * n + 2.0 * m ** 3)
    # X X^T: read X, write A; A A: read A, write A2; B X: read B and X,
    # write X -- float32, once each.
    bytes_ = NS_STEPS * 4.0 * (2 * m * n + m * m + 2 * m * m + m * m + m * m + 2 * m * n)
    return flops, bytes_


def _ns_units(cfg: dict, phase: str) -> list[tuple[int, int, int]]:
    """(count, m, n) of the units one device orthogonalizes in ``phase``."""
    n = _dims(cfg)
    data = cfg["mesh"].get("data", 1) if cfg.get("zero1") else 1
    layers = n["L"] // data if n["L"] % data == 0 else n["L"]
    model = cfg["mesh"].get("model", 1)
    units = []
    for key, (r, c) in matrices(cfg).items():
        br, bc = cfg.get("blocks", {}).get(key, (1, 1))
        if phase == "full":
            units.append((layers, r, c))
        else:
            # A device holds one block of its own layers' matrices: the
            # block grid is the model-parallel sharding.
            units.append((layers * br * bc // model if br * bc >= model else layers,
                          r // br, c // bc))
    # The stacked norm gains (L, D) have rank 2 and are orthogonalized as one
    # L x D matrix on every device.
    units.append((2, n["L"], n["D"]))
    return units


def ns_flops(cfg: dict, phase: str) -> float:
    return sum(k * _ns_unit(m, n)[0] for k, m, n in _ns_units(cfg, phase))


def ns_bytes(cfg: dict, phase: str) -> float:
    return sum(k * _ns_unit(m, n)[1] for k, m, n in _ns_units(cfg, phase))
