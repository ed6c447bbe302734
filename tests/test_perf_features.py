"""Beyond-paper performance features: correctness guarantees.

Each optimization in EXPERIMENTS.md Perf must not change semantics:
  * ring-buffer SWA decode cache == full-cache decode == teacher forcing
  * gradient accumulation == single-batch gradients
  * distributed full-NS == replicated full-NS (single-device: same math)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.configs import get_config
from repro.core import muon, muon_full
from repro.launch.mesh import make_mesh
from repro.models.model import decode_step, init_cache, init_params, loss_fn
from repro.models.transformer import forward
from repro.training.train_step import TrainState, init_train_state, train_step


def test_ring_cache_matches_forward(key):
    cfg = tiny_cfg("mixtral-8x7b", capacity_factor=100.0, window_size=6)
    params = init_params(key, cfg)
    B, S = 1, 20
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    logits_full, _ = forward(params, tokens, cfg)
    cache = init_cache(cfg, B, cfg.window_size, dtype=jnp.float32)
    outs = []
    for t in range(S):
        lg, cache = decode_step(
            params, tokens[:, t : t + 1], cache, jnp.int32(t), cfg, ring_cache=True
        )
        outs.append(lg)
    err = float(jnp.max(jnp.abs(logits_full - jnp.concatenate(outs, 1))))
    assert err < 1e-4, err


def test_ring_cache_rejects_full_attention(key):
    cfg = tiny_cfg("granite-8b")
    params = init_params(key, cfg)
    cache = init_cache(cfg, 1, 8, dtype=jnp.float32)
    with pytest.raises(ValueError, match="sliding-window"):
        decode_step(params, jnp.zeros((1, 1), jnp.int32), cache, jnp.int32(0),
                    cfg, ring_cache=True)


def test_grad_accumulation_matches(key):
    cfg = tiny_cfg("granite-8b")
    params = init_params(key, cfg)
    tokens = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens,
             "labels": jnp.concatenate([tokens[:, 1:], -jnp.ones((4, 1), jnp.int32)], 1)}
    g_full = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)
    halves = [jax.tree.map(lambda x: x[i * 2 : (i + 1) * 2], batch) for i in range(2)]
    gs = [jax.grad(lambda p: loss_fn(p, b, cfg)[0])(params) for b in halves]
    g_acc = jax.tree.map(lambda a, b: (a + b) / 2, *gs)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_acc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_train_step_accum_runs(key):
    cfg = tiny_cfg("granite-8b")
    params = init_params(key, cfg)
    from repro.core import adamw, combine, label_tree

    opt = combine({"muon": muon(0.02), "adamw": adamw(0.01)}, label_tree(params))
    st = init_train_state(params, opt)
    tokens = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens,
             "labels": jnp.concatenate([tokens[:, 1:], -jnp.ones((4, 1), jnp.int32)], 1)}
    st2, m = train_step(st, batch, cfg=cfg, optimizer=opt, phase="block", accum_steps=2)
    assert bool(jnp.isfinite(m["loss"]))


def test_layer_shard_full_ns_single_device_math(key):
    """The layer_shard program CommOp on a 1-device mesh must equal the
    plain full step (padding + resharding are numerically inert)."""
    mesh = make_mesh((1,), ("data",))
    g = jax.random.normal(key, (3, 16, 24))  # stacked "layers"
    plain = muon_full(0.1, rms_match=False)
    dist = muon(0.1, 0.1, period=1, rms_match=False, layer_shard=(mesh, "data"))
    s1, s2 = plain.init({"w": g}), dist.init({"w": g})
    u1, _ = plain.update({"w": g}, s1, {"w": jnp.zeros_like(g)}, "full")
    u2, _ = dist.update({"w": g}, s2, {"w": jnp.zeros_like(g)}, "full")
    np.testing.assert_allclose(np.asarray(u1["w"]), np.asarray(u2["w"]), atol=1e-5)
