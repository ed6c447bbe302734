"""NorMuon neuron-wise second-moment normalization — fused NS epilogue.

NorMuon keeps one second-moment statistic per output neuron (row) of each
matrix leaf and divides the orthogonalized update by the bias-corrected
root — Adam-style variance reduction at row granularity, cheap enough to
ride along with Muon's matrix update. Under MuonBP's schedule the statistic
*refresh* (an EMA of row mean-squares, which needs the full row) happens
only on full/due steps — block-periodic, like the orthogonalization itself
— so block steps stay collective-free: applying the standing statistics is
an elementwise broadcast divide over rows each rank already owns.

Two equivalent executions of the same padded math:

  * :func:`neuron_norm` — the fused Pallas kernel: grid over the stack and
    over tiles of rows (the statistics are per row, so a tile of whole rows
    is self-contained), one ``(1, rows, n_p)`` block in VMEM per step, row
    statistics + EMA + normalization in one launch, fp32 internally. The
    row tile is sized from the width (:func:`row_tile`), so the kernel
    compiles at any realistic width. Lanes pad to multiples of 128 and rows
    to the tile; row mean-squares are computed as ``sum(x*x) * (1/n_true)``
    so zero-padding is exact.
  * :func:`neuron_norm_reference` — pure jnp on the SAME padded shapes and
    op order, bitwise-identical to the kernel in interpret mode (asserted
    in tests/test_variants.py) and the partitioner-friendly path for
    multi-device jnp-backend runs.

:func:`apply_neuron_norm` is the leaf-level epilogue ``muon.update`` calls:
it handles lead-padded ZeRO-1 flatten-fallback state (apply on the head,
pad the refreshed statistics back), the bias correction, a first-steps
guard (before any refresh the statistics are zero — the raw update passes
through), and a global RMS-preserving rescale so the normalized update
keeps the magnitude the two-stepsize rule was tuned for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.newton_schulz.fused import VMEM_LIMIT_BYTES
from repro.kernels.newton_schulz.newton_schulz import round_up

# Lane width of the statistics blocks: v logically has a single column, but
# VMEM blocks want a 128-multiple last dim, so the kernel carries the stats
# in column 0 of a 128-lane block (the wrapper slices it back to (..., 1)).
STAT_LANES = 128

# Additive guard for the RMS-preserving rescale's means (exact-zero updates).
_TINY = 1e-30



def _vmem_bytes_per_row(n_p: int) -> int:
    """Scoped VMEM one row of a tile takes, counted as ``fused.fits_vmem``
    counts: Pallas double-buffers the fp32 in and out blocks (4 rows of
    ``n_p`` and 4 of ``STAT_LANES``), and Mosaic keeps up to four more
    row-sized fp32 temporaries (``x``, ``x * x``, the scaled output and a
    relayout copy)."""
    return 4 * (8 * n_p + 4 * STAT_LANES)


def row_tile(m: int, n: int) -> int:
    """Rows per grid step for an ``(m, n)`` matrix: a multiple of 8 whose
    tile fits ``fused.VMEM_LIMIT_BYTES``, spread evenly over the fewest
    tiles so the row padding stays under 8 per tile."""
    cap = max(8, VMEM_LIMIT_BYTES // _vmem_bytes_per_row(round_up(n, 128)) // 8 * 8)
    m8 = round_up(m, 8)
    tiles = -(-m8 // cap)
    return round_up(-(-m8 // tiles), 8)


def _norm_math(x, v0, corr, *, beta2, eps, inv_n, refresh):
    """The shared padded math on fp32 VALUES: (m_p, n_p) x (m_p, 1) -> same.

    Kernel body and jnp reference both call exactly this, on identically
    padded operands, so interpret-mode outputs match bit for bit.
    """
    if refresh:
        row = jnp.sum(x * x, axis=-1, keepdims=True) * inv_n
        v = beta2 * v0 + (1.0 - beta2) * row
    else:
        v = v0
    denom = jnp.sqrt(v / corr) + eps
    return x / denom, v


def _neuron_norm_kernel(x_ref, v_ref, corr_ref, out_ref, vout_ref, *,
                        beta2, eps, inv_n, refresh):
    """One tile of rows of one stacked matrix per grid step, in VMEM."""
    x = x_ref[0].astype(jnp.float32)
    v0 = v_ref[0][:, :1].astype(jnp.float32)
    y, v = _norm_math(x, v0, corr_ref[0, 0], beta2=beta2, eps=eps,
                      inv_n=inv_n, refresh=refresh)
    out_ref[0] = y.astype(out_ref.dtype)
    vout_ref[0] = jnp.broadcast_to(v, vout_ref.shape[1:]).astype(vout_ref.dtype)


def _pad_operands(x: jax.Array, v: jax.Array, rows: int):
    """Tile-align ``(B, m, n)``/``(B, m, 1)`` to ``(B, m_p, n_p)``/``(B, m_p, LANES)``.

    ``m_p`` is a multiple of the row tile ``rows``. Zero-padding is exact:
    pad rows carry zero statistics and produce zero outputs (``0 / eps``),
    and pad columns contribute nothing to the row sums because the mean
    divides by the TRUE column count.
    """
    _, m, n = x.shape
    mp, np_ = round_up(m, rows), round_up(n, 128)
    if (mp, np_) != (m, n):
        x = jnp.pad(x, ((0, 0), (0, mp - m), (0, np_ - n)))
    v = jnp.pad(v, ((0, 0), (0, mp - m), (0, STAT_LANES - 1)))
    return x, v, mp, np_


@functools.partial(
    jax.jit, static_argnames=("beta2", "eps", "refresh", "interpret")
)
def neuron_norm(
    x: jax.Array,
    v: jax.Array,
    corr: jax.Array,
    *,
    beta2: float,
    eps: float,
    refresh: bool,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused Pallas neuron normalization of a stack ``(B, m, n)``.

    ``v`` is the standing row second moments ``(B, m, 1)``; ``corr`` the
    bias-correction scalar ``1 - beta2**count`` (computed by the caller —
    it depends on the traced refresh counter). Returns ``(y, v_new)`` with
    ``v_new == v`` when ``refresh=False``.
    """
    if x.ndim != 3 or v.shape != (*x.shape[:-1], 1):
        raise ValueError(f"expected (B, m, n) + (B, m, 1), got {x.shape}/{v.shape}")
    bsz, m, n = x.shape
    tr = row_tile(m, n)
    xp, vp, mp, np_ = _pad_operands(x.astype(jnp.float32), v.astype(jnp.float32), tr)
    corr2 = jnp.asarray(corr, jnp.float32).reshape(1, 1)
    y, v_new = pl.pallas_call(
        functools.partial(
            _neuron_norm_kernel, beta2=float(beta2), eps=float(eps),
            inv_n=1.0 / float(n), refresh=refresh,
        ),
        grid=(bsz, mp // tr),
        in_specs=[
            pl.BlockSpec((1, tr, np_), lambda i, r: (i, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tr, STAT_LANES), lambda i, r: (i, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, tr, np_), lambda i, r: (i, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tr, STAT_LANES), lambda i, r: (i, r, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, mp, np_), jnp.float32),
            jax.ShapeDtypeStruct((bsz, mp, STAT_LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(xp, vp, corr2)
    return y[:, :m, :n], v_new[:, :m, :1]


@functools.partial(jax.jit, static_argnames=("beta2", "eps", "refresh"))
def neuron_norm_reference(
    x: jax.Array,
    v: jax.Array,
    corr: jax.Array,
    *,
    beta2: float,
    eps: float,
    refresh: bool,
) -> tuple[jax.Array, jax.Array]:
    """Pure-jnp twin of :func:`neuron_norm` — same padded shapes, same ops.

    Runs :func:`_norm_math` per stacked matrix on the identically padded
    operands. Every row is computed on its own, so the kernel's row tiles
    give the same bits (asserted in interpret mode).
    """
    if x.ndim != 3 or v.shape != (*x.shape[:-1], 1):
        raise ValueError(f"expected (B, m, n) + (B, m, 1), got {x.shape}/{v.shape}")
    bsz, m, n = x.shape
    xp, vp, _, _ = _pad_operands(x.astype(jnp.float32), v.astype(jnp.float32),
                                 row_tile(m, n))
    corr_f = jnp.asarray(corr, jnp.float32).reshape(1, 1)[0, 0]
    ys, vs = [], []
    for i in range(bsz):
        y, v_new = _norm_math(
            xp[i], vp[i][:, :1], corr_f, beta2=float(beta2), eps=float(eps),
            inv_n=1.0 / float(n), refresh=refresh,
        )
        ys.append(y)
        vs.append(v_new)
    return jnp.stack(ys)[:, :m, :n], jnp.stack(vs)[:, :m, :1]


def apply_neuron_norm(
    o: jax.Array,
    v: jax.Array,
    count: jax.Array,
    *,
    beta2: float,
    eps: float,
    refresh: bool,
    backend: str = "jnp",
    interpret: bool = None,
):
    """Leaf-level NorMuon epilogue: ``(o, v, count) -> (o', v', count')``.

    ``o`` is the orthogonalized update (any leading dims); ``v`` its row
    second moments — possibly lead-padded (ZeRO-1 flatten fallback, where
    the update re-entered the PARAM layout while the state keeps the
    padded stack): the head rows are normalized/refreshed and the zero pad
    rows are restored untouched. ``backend='pallas'`` runs the fused
    kernel (interpret mode off-TPU); anything else the jnp reference —
    the partitioner-friendly choice for multi-device jnp-backend runs.
    """
    orig_dtype = o.dtype
    x = o.astype(jnp.float32)
    lead_pad = v.shape[0] - x.shape[0]
    head = v[: x.shape[0]] if lead_pad else v
    new_count = count + 1 if refresh else count
    corr = jnp.maximum(
        1.0 - jnp.float32(beta2) ** new_count.astype(jnp.float32),
        jnp.float32(1e-12),
    )
    m, n = x.shape[-2], x.shape[-1]
    x3 = x.reshape(-1, m, n)
    v3 = head.astype(jnp.float32).reshape(-1, m, 1)
    if backend == "pallas":
        interp = (jax.default_backend() != "tpu") if interpret is None else interpret
        y3, vn3 = neuron_norm(x3, v3, corr, beta2=beta2, eps=eps,
                              refresh=refresh, interpret=interp)
    else:
        y3, vn3 = neuron_norm_reference(x3, v3, corr, beta2=beta2, eps=eps,
                                        refresh=refresh)
    y = y3.reshape(x.shape)
    if refresh:
        head_n = vn3.reshape(head.shape)
        v_new = (
            jnp.pad(head_n, [(0, lead_pad)] + [(0, 0)] * (head_n.ndim - 1))
            if lead_pad else head_n
        )
    else:
        v_new = v
    # RMS-preserving rescale: per-row division changes the update magnitude
    # the two-stepsize rule was tuned for, so restore the leaf's global RMS
    # (direction reweighted across neurons, norm preserved).
    num = jnp.mean(jnp.square(x)) + _TINY
    den = jnp.mean(jnp.square(y)) + _TINY
    y = y * jnp.sqrt(num / den)
    # First-steps guard: before any refresh the statistics are all zero and
    # the divide would be 1/eps — pass the raw update through instead.
    y = jnp.where(new_count > 0, y, x)
    return y.astype(orig_dtype), v_new, new_count
