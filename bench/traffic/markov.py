"""Token rows for a training cell: a seeded first-order Markov chain.

The chain's transition table comes from ``table_seed`` (the same "language"
for every run) and the sampled rows from the run's seed, so every seed gets
the same sizes and a different stream. Each token moves to one of
``branching`` successors with fixed probabilities, so a model can learn it
and a working update lowers the loss where a broken one does not.

This is a copy of ``repro.data.pipeline.SyntheticLM``'s sampler, kept with
the benchmark so that no change to the program changes its inputs.
"""

from __future__ import annotations

import numpy as np


def sample_rows(vocab: int, rows: int, length: int, *, seed: int,
                table_seed: int = 0, branching: int = 8) -> np.ndarray:
    """``(rows, length)`` int32 tokens; all rows are drawn in one pass."""
    table = np.random.default_rng(table_seed)
    successors = table.integers(0, vocab, size=(vocab, branching))
    raw = table.random((vocab, branching)) ** 2
    cum_p = np.cumsum(raw / raw.sum(axis=1, keepdims=True), axis=1)
    rng = np.random.default_rng(seed)
    out = np.empty((rows, length), np.int32)
    state = rng.integers(0, vocab, size=rows)
    out[:, 0] = state
    for t in range(1, length):
        choice = (rng.random(rows)[:, None] > cum_p[state]).sum(axis=1)
        state = successors[state, np.minimum(choice, branching - 1)]
        out[:, t] = state
    return out


def batches(traffic: dict, vocab: int, seed: int) -> list[dict]:
    """The cell's pool: ``pool_batches`` batches of next-token pairs."""
    b, s, n = traffic["batch"], traffic["seq"], traffic["pool_batches"]
    rows = sample_rows(vocab, n * b, s + 1, seed=seed,
                       table_seed=traffic["table_seed"],
                       branching=traffic["branching"])
    return [{"tokens": rows[i * b:(i + 1) * b, :-1],
             "labels": rows[i * b:(i + 1) * b, 1:].copy()} for i in range(n)]
