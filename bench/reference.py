"""Plain float32 reference of a training cell's first steps.

Straight ``jax.numpy`` at ``highest`` matmul precision, written from the
model's published description and the optimizer's stated rules, importing
nothing of the program under test. It makes its own weights from the seed
with the same recipe the model documents (normal(0, 0.02) matrices, unit
norm gains, vocabulary padded to a multiple of 256), runs the cell's first
three steps on the cell's own batches, and returns per step the loss, per
leaf the first gradient as the optimizer receives it, and per leaf the
parameters' change after the three steps.

The model: token embedding; per layer RMSNorm -> GQA attention with
interleaved-pair RoPE over the whole head, causal -> residual; RMSNorm ->
SwiGLU MLP -> residual; final RMSNorm; logits over the padded vocabulary
(tied to the embedding or a separate head); mean cross-entropy.

The optimizer: MuonBP (period P, Nesterov momentum 0.95 with ``m = 0.95 m +
g``, 5 cubic Newton-Schulz steps (2, -1.5, 0.5) on the Frobenius-normalized
matrix, iterating on its smaller side; on a full step the whole matrix, on
a block step each block of the cell's block grid; update scaled by
``0.2 sqrt(max(m, n))`` of the matrix or block it orthogonalized, times the
LR, plus decoupled weight decay 0.1) for every parameter of rank >= 2 other
than the embedding and the head; AdamW (b1 0.9, b2 0.95, eps 1e-8,
weight decay 0.1, global-norm clip 1.0 over its own parameters) for the
rest, or for everything when the cell's optimizer is AdamW.

``precision="control"`` computes the same steps one precision step lower
than the configuration states: every model matmul takes float8 (e4m3)
inputs where the program takes bfloat16, and the Newton-Schulz iteration
is carried in bfloat16 where the program carries float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NS_COEFFS = (2.0, -1.5, 0.5)
NS_STEPS = 5
MOMENTUM = 0.95
MUON_RMS = 0.2
WEIGHT_DECAY = 0.1
ADAM_B1, ADAM_B2, ADAM_EPS, ADAM_CLIP = 0.9, 0.95, 1e-8, 1.0
VOCAB_PAD = 256
INIT_STD = 0.02
# Query rows per attention block: the scores of one block are kept, not
# the whole (S, S) matrix.
Q_BLOCK = 512


def padded_vocab(model: dict) -> int:
    v = model["vocab_size"]
    return (v + VOCAB_PAD - 1) // VOCAB_PAD * VOCAB_PAD


def dims(model: dict) -> dict:
    d, h = model["hidden_size"], model["head_dim"]
    return {"L": model["num_hidden_layers"], "D": d, "F": model["intermediate_size"],
            "H": model["num_attention_heads"], "K": model["num_key_value_heads"],
            "hd": h, "q": model["num_attention_heads"] * h,
            "kv": model["num_key_value_heads"] * h, "V": padded_vocab(model)}


def init_params(key, model: dict) -> dict:
    """Weights from ``PRNGKey(seed)``: the documented recipe and key order."""
    n = dims(model)
    L, D, F = n["L"], n["D"], n["F"]
    k_embed, k_layers, k_head, _ = jax.random.split(key, 4)
    keys = jax.random.split(k_layers, 32)

    def normal(key, shape):
        return INIT_STD * jax.random.normal(key, shape, jnp.float32)

    params = {
        "embed": normal(k_embed, (n["V"], D)),
        "final_norm": jnp.ones((D,), jnp.float32),
        "layers": {
            "attn": {"wq": normal(keys[0], (L, D, n["q"])),
                     "wk": normal(keys[1], (L, D, n["kv"])),
                     "wv": normal(keys[2], (L, D, n["kv"])),
                     "wo": normal(keys[3], (L, n["q"], D))},
            "mlp": {"wi": normal(keys[4], (L, D, F)),
                    "wo": normal(keys[5], (L, F, D)),
                    "wg": normal(keys[6], (L, D, F))},
            "norms": {"attn_norm": jnp.ones((L, D), jnp.float32),
                      "mlp_norm": jnp.ones((L, D), jnp.float32)},
        },
    }
    if not model["tie_word_embeddings"]:
        params["lm_head"] = normal(k_head, (D, n["V"]))
    return params


def leaf_paths(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def is_muon_leaf(path: str, ndim: int, optimizer: str) -> bool:
    if optimizer == "adamw" or ndim < 2:
        return False
    return not any(t in path.lower() for t in ("embed", "lm_head"))


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

def _rounding(precision: str):
    """What a matmul input goes through: nothing, or float8 and back. The
    rounding passes gradients straight through: the backward's cotangents
    are not rounded (float8 would flush them to zero without scaling)."""
    if precision == "control":
        def rnd(t):
            low = t.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            return t + jax.lax.stop_gradient(low - t)
        return rnd
    return lambda t: t


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Interleaved pairs (2i, 2i+1) rotated by position * theta^(-2i/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv        # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


def _attention(q, k, v, rnd):
    """Causal GQA softmax attention, one block of query rows at a time."""
    b, s, hq, hd = q.shape
    hk = k.shape[2]
    qb = min(Q_BLOCK, s)
    q = q.reshape(b, s // qb, qb, hk, hq // hk, hd)
    k, v = rnd(k), rnd(v)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qi, i = args                                              # (b,qb,hk,g,hd)
        sc = jnp.einsum("bqkgd,btkd->bkgqt", rnd(qi), k) * hd ** -0.5
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bkgqt,btkd->bqkgd", rnd(p), v)

    out = jax.lax.map(block, (jnp.moveaxis(q, 1, 0), jnp.arange(s // qb)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, hq, hd)


def loss_fn(params, tokens, labels, mask, model: dict, precision: str = "reference"):
    """Cross-entropy averaged over the tokens where ``mask`` is 1."""
    n = dims(model)
    rnd = _rounding(precision)
    mm = lambda a, w: rnd(a) @ rnd(w)
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    b, s = tokens.shape
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, p):
        h = _rms_norm(x, p["norms"]["attn_norm"], eps)
        q = _rope(mm(h, p["attn"]["wq"]).reshape(b, s, n["H"], n["hd"]), theta)
        k = _rope(mm(h, p["attn"]["wk"]).reshape(b, s, n["K"], n["hd"]), theta)
        v = mm(h, p["attn"]["wv"]).reshape(b, s, n["K"], n["hd"])
        x = x + mm(_attention(q, k, v, rnd).reshape(b, s, n["q"]), p["attn"]["wo"])
        h = _rms_norm(x, p["norms"]["mlp_norm"], eps)
        gate = jax.nn.silu(mm(h, p["mlp"]["wg"])) * mm(h, p["mlp"]["wi"])
        return x + mm(gate, p["mlp"]["wo"]), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms_norm(x, params["final_norm"], eps)
    head = params["embed"].T if model["tie_word_embeddings"] else params["lm_head"]
    logits = mm(x, head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - picked) * mask) / jnp.sum(mask)


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

def newton_schulz(x, dtype=jnp.float32):
    """Orthogonalize the trailing two dims (batched over the rest)."""
    x = x.astype(dtype)
    if x.shape[-2] > x.shape[-1]:
        return jnp.swapaxes(newton_schulz(jnp.swapaxes(x, -1, -2), dtype), -1, -2)
    x32 = x.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(x32 * x32, axis=(-2, -1), keepdims=True))
    x = (x32 / (norm + 1e-7)).astype(dtype)
    a, b, c = NS_COEFFS
    for _ in range(NS_STEPS):
        gram = x @ jnp.swapaxes(x, -1, -2)
        x = a * x + (b * gram + c * (gram @ gram)) @ x
    return x.astype(jnp.float32)


def orthogonalize_blocks(u, grid, dtype=jnp.float32):
    """Newton-Schulz on each block of an ``r x c`` grid of the trailing dims;
    returns the update and the (rows, cols) of one block."""
    r, c = grid
    *lead, m, n = u.shape
    x = u.reshape(*lead, r, m // r, c, n // c)
    x = jnp.moveaxis(x, -2, -3)                                   # (..., r, c, m/r, n/c)
    o = newton_schulz(x, dtype)
    o = jnp.moveaxis(o, -3, -2).reshape(u.shape)
    return o, (m // r, n // c)


def make_step(model: dict, traffic: dict, blocks: dict, phase: str,
              precision: str = "reference"):
    """One reference step: (params, opt, tokens, labels, mask) ->
    (params, opt, loss, per-leaf norms of the gradient the optimizer got)."""
    optimizer = traffic["optimizer"]
    lr, adam_lr = traffic["lr"], traffic["adam_lr"]
    ns_dtype = jnp.bfloat16 if precision == "control" else jnp.float32

    def step(params, opt, tokens, labels, mask):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, labels, mask, model, precision)
        paths = leaf_paths(params)
        p_l, g_l = jax.tree.leaves(params), jax.tree.leaves(grads)
        muon = [is_muon_leaf(k, p.ndim, optimizer) for k, p in zip(paths, p_l)]
        adam_sq = sum(jnp.sum(g * g) for g, is_m in zip(g_l, muon) if not is_m)
        clip = jnp.minimum(1.0, ADAM_CLIP / (jnp.sqrt(adam_sq) + 1e-12))
        count = opt["count"] + 1
        new_p, new_m, new_v, seen = [], [], [], []
        for k, p, g, is_m, m, v in zip(paths, p_l, g_l, muon, opt["m"], opt["v"]):
            if is_m:
                m = MOMENTUM * m + g
                u = g + MOMENTUM * m
                grid = (1, 1) if phase == "full" else tuple(
                    blocks.get(k.removeprefix("layers/"), (1, 1)))
                o, (mb, nb) = orthogonalize_blocks(u, grid, ns_dtype)
                upd = -lr * MUON_RMS * math.sqrt(max(mb, nb)) * o - lr * WEIGHT_DECAY * p
            else:
                g = g * clip
                m = ADAM_B1 * m + (1 - ADAM_B1) * g
                v = ADAM_B2 * v + (1 - ADAM_B2) * g * g
                mhat = m / (1 - ADAM_B1 ** count)
                vhat = v / (1 - ADAM_B2 ** count)
                upd = (-adam_lr * mhat / (jnp.sqrt(vhat) + ADAM_EPS)
                       - adam_lr * WEIGHT_DECAY * p)
            seen.append(jnp.sqrt(jnp.sum(g * g)))
            new_p.append(p + upd)
            new_m.append(m)
            new_v.append(v)
        return (jax.tree.unflatten(jax.tree.structure(params), new_p),
                {"m": new_m, "v": new_v, "count": count}, loss, jnp.stack(seen))

    return step


def run(model: dict, traffic: dict, blocks: dict, seed: int, batches: list,
        phases: list, precision: str = "reference", half_batch: bool = False,
        shard=None) -> dict:
    """The cell's first steps; ``phases[i]`` is step i's phase.

    ``half_batch`` takes the loss over the first half of each batch's
    tokens only (a fault a check has to catch). ``shard(x)`` places an
    array across the devices (GSPMD decides the rest); without it
    everything stays on the default device. Returns the losses, the first
    step's per-leaf gradient norms and the per-leaf norms of the
    parameters' change over all the steps, keyed by leaf path.
    """
    place = shard or (lambda x: x)
    key = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(place, jax.jit(lambda k: init_params(k, model))(key))
        paths = leaf_paths(params)
        leaves = jax.tree.leaves(params)
        adam = [not is_muon_leaf(k, p.ndim, traffic["optimizer"])
                for k, p in zip(paths, leaves)]
        opt = {"m": [place(jnp.zeros_like(p)) for p in leaves],
               "v": [place(jnp.zeros_like(p)) if a else None
                     for p, a in zip(leaves, adam)],
               "count": jnp.zeros((), jnp.int32)}
        del leaves
        steps = {ph: jax.jit(make_step(model, traffic, blocks, ph, precision),
                             donate_argnums=(0, 1))
                 for ph in set(phases)}
        losses, grad_norms = [], None
        for ph, batch in zip(phases, batches):
            tokens = np.asarray(batch["tokens"])
            mask = np.ones(tokens.shape, np.float32)
            if half_batch:
                mask.reshape(-1)[tokens.size // 2:] = 0.0
            params, opt, loss, norms = steps[ph](
                params, opt, place(jnp.asarray(tokens)),
                place(jnp.asarray(batch["labels"])), place(jnp.asarray(mask)))
            losses.append(loss)
            if grad_norms is None:
                grad_norms = norms
        del opt
        delta = jax.jit(lambda a, k: jnp.stack(
            [jnp.sqrt(jnp.sum((x - y) ** 2)) for x, y in
             zip(jax.tree.leaves(a), jax.tree.leaves(init_params(k, model)))]))(params, key)
        del params
    return {"losses": [float(x) for x in jax.device_get(losses)],
            "grad_norms": dict(zip(paths, map(float, np.asarray(grad_norms)))),
            "delta_norms": dict(zip(paths, map(float, np.asarray(delta))))}
