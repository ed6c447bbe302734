"""Pallas TPU kernels for Newton-Schulz orthogonalization.

The NS iteration (paper Algorithm 2) is the optimizer's compute hot-spot:
per matrix it is three chained matmuls (A = X X^T; P = bA + cA^2; Y = aX +
P X). On TPU these map to the MXU with 128x128 tiling; this module provides

  * ``matmul``      — general tiled matmul, fp32 VMEM accumulator
  * ``fma_matmul``  — fused ``alpha*C + beta*(A@B)`` (epilogue add reads the
    C tile once while the accumulator is still in VMEM — saves one HBM
    round-trip per NS polynomial step vs composing matmul + add)

Tiling: grid (M/bm, N/bn, K/bk) with the K dimension innermost ("arbitrary"
semantics) accumulating into a VMEM scratch tile; block shapes default to
128x128x512 — MXU-aligned and, at bf16, a (128x512 + 512x128 + 128x128 fp32)
working set of ~320 KiB, comfortably inside Mosaic's default scoped-VMEM
limit (16 MiB on v5e) with double-buffering.

On a TPU the kernels lower to Mosaic; ``tests/test_chip_compile.py``
compiles them for a described v5e. Elsewhere they run in interpret mode
(``pl.pallas_call(..., interpret=True)`` executes the kernel body with
XLA ops), which is how the CPU tests check them against ``ref.py``.

These tiled kernels remain the fallback path for matrices whose fused
working set exceeds VMEM; the default kernel path is the single-launch
fused iteration in ``fused.py`` (selected via ``kernels/dispatch.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _matmul_kernel(x_ref, y_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        y_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _fma_matmul_kernel(x_ref, y_ref, c_ref, out_ref, acc_ref, *, n_k: int, alpha: float, beta: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        y_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _():
        out_ref[...] = (
            alpha * c_ref[...].astype(jnp.float32) + beta * acc_ref[...]
        ).astype(out_ref.dtype)


def round_up(v: int, mult: int) -> int:
    """Smallest multiple of ``mult`` >= ``v``."""
    return -(-v // mult) * mult


def _pad_to(x, m_mult, n_mult):
    m, n = x.shape
    pm = round_up(m, m_mult) - m
    pn = round_up(n, n_mult) - n
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(
    x: jax.Array,
    y: jax.Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """x (M,K) @ y (K,N) with fp32 accumulation; output in x.dtype."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    xp = _pad_to(x, bm_, bk_)
    yp = _pad_to(y, bk_, bn_)
    mp, kp = xp.shape
    np_ = yp.shape[1]
    n_k = kp // bk_
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=(mp // bm_, np_ // bn_, n_k),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xp, yp)
    return out[:m, :n]


@functools.partial(
    jax.jit, static_argnames=("alpha", "beta", "bm", "bn", "bk", "interpret")
)
def fma_matmul(
    x: jax.Array,
    y: jax.Array,
    c: jax.Array,
    *,
    alpha: float,
    beta: float,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """alpha * c + beta * (x @ y), fused epilogue in the output tile."""
    m, k = x.shape
    _, n = y.shape
    assert c.shape == (m, n), (c.shape, m, n)
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    xp = _pad_to(x, bm_, bk_)
    yp = _pad_to(y, bk_, bn_)
    cp = _pad_to(c, bm_, bn_)
    mp, kp = xp.shape
    np_ = yp.shape[1]
    n_k = kp // bk_
    out = pl.pallas_call(
        functools.partial(_fma_matmul_kernel, n_k=n_k, alpha=alpha, beta=beta),
        grid=(mp // bm_, np_ // bn_, n_k),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xp, yp, cp)
    return out[:m, :n]
