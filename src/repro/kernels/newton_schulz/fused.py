"""Fused, batched Newton-Schulz: whole chains (or iterations) in one launch.

The tiled kernels in ``newton_schulz.py`` execute one NS iteration as three
chained launches (``matmul`` for the Gram matrix, two ``fma_matmul`` for the
polynomial and the update), bouncing every intermediate through HBM. This
module fuses the whole iteration

    A = X X^T;  P = bA + cA^2;  Y = aX + P X

into a single kernel: per grid step, one stacked matrix is read from HBM
into VMEM once, the Gram matrix lives in an fp32 VMEM scratch accumulator,
and only the final ``Y`` is written back — one HBM read and one HBM write
per NS iteration instead of six round-trips.

``orthogonalize(..., chain=True)`` goes one level further and runs **all K
iterations inside ONE launch** (the ``fused_chain`` dispatch strategy): X
stays resident in VMEM for the entire chain, so the K-step orthogonalization
costs one HBM read and one HBM write *total* instead of per iteration —
the per-iteration kernel round-trips X through HBM K-1 more times than
necessary whenever the block fits VMEM for the whole chain (which is the
same VMEM working set: the chain reuses the iteration's buffers in place).
The per-iteration launcher (``chain=False`` / strategy ``"fused_iter"``)
remains the A/B comparison point; ``benchmarks/ns_cost.py`` reports the
launch-count and wall-time delta.

Two structural optimizations:

  * **Batched grid.** The grid is the leading stack dimension, so one launch
    covers a whole shape bucket (see ``core/bucketing.py``) — stacked layers
    or blocks of identical shape run as a single kernel with no per-matrix
    dispatch overhead.
  * **Gram symmetry.** ``A = X X^T`` is symmetric, so the Gram stage only
    computes the upper-triangular (i <= j) tile pairs on the MXU and mirrors
    the transpose into the lower triangle — ~2x fewer Gram-stage MXU tiles.

Sizing: both kernels ask Mosaic for ``VMEM_LIMIT_BYTES`` of scoped VMEM,
and ``fits_vmem`` counts what Mosaic allocates against that same limit
(double-buffered X/Y blocks, the Gram scratch and the fp32 Gram-sized
temporaries), so dispatch never picks a kernel the compiler refuses;
oversized matrices fall back to the tiled/jnp paths.
``tests/test_chip_compile.py`` holds the gate to the compiler for a
described v5e.

Off-TPU the kernels run in interpret mode (``interpret=True``), which is
how the CPU tests check them against ``ref.py``; on TPU the same code
lowers to Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.newton_schulz.newton_schulz import round_up

# Gram-stage tile (rows of X per MXU dot). 128 matches the MXU systolic array.
DEFAULT_GRAM_TILE = 128

# Scoped VMEM each fused launch asks Mosaic for, and the budget
# ``fits_vmem`` counts against (pipelined stages plan against less, see
# ``dispatch.pipeline_vmem_budget``). v5e has 128 MiB of VMEM per core but
# Mosaic's default scoped limit there is 16 MiB; passing the limit
# explicitly makes the compiler and the gate use one number on every TPU
# generation.
VMEM_LIMIT_BYTES = 16 * 2**20

# Trace-time Pallas launch counter: every pallas_call this module issues
# bumps it once per trace. Benchmarks/tests read the delta across a fresh
# trace to demonstrate fused-chain (1 launch) vs per-iteration (K launches)
# without parsing HLO.
_launches = 0


def launch_count() -> int:
    return _launches


def _count_launch() -> None:
    global _launches
    _launches += 1


def _ns_step(x: jax.Array, gram_ref, *, a, b, c, tm, nt) -> jax.Array:
    """One NS iteration on an fp32 VMEM-resident (m_p, n_p) value.

    ``gram_ref`` is the fp32 VMEM accumulator for ``A = X X^T``; only
    upper-triangular tile pairs hit the MXU, the rest is mirrored.
    """
    for i in range(nt):
        xi = x[i * tm : (i + 1) * tm, :]
        for j in range(i, nt):
            xj = x[j * tm : (j + 1) * tm, :]
            tile = jnp.dot(xi, xj.T, preferred_element_type=jnp.float32)
            gram_ref[i * tm : (i + 1) * tm, j * tm : (j + 1) * tm] = tile
            if j > i:
                gram_ref[j * tm : (j + 1) * tm, i * tm : (i + 1) * tm] = tile.T
    gram = gram_ref[...]
    poly = b * gram + c * jnp.dot(gram, gram, preferred_element_type=jnp.float32)
    return a * x + jnp.dot(poly, x, preferred_element_type=jnp.float32)


def _fused_ns_kernel(x_ref, out_ref, gram_ref, *, a, b, c, tm, nt):
    """One full NS iteration on the (1, m_p, n_p) block in VMEM."""
    y = _ns_step(x_ref[0].astype(jnp.float32), gram_ref, a=a, b=b, c=c, tm=tm, nt=nt)
    out_ref[0] = y.astype(out_ref.dtype)


def _fused_ns_chain_kernel(x_ref, out_ref, gram_ref, *, a, b, c, tm, nt, steps):
    """ALL ``steps`` NS iterations on the (1, m_p, n_p) block, one launch.

    X never leaves VMEM between iterations — the unrolled chain reuses the
    same Gram scratch, so the whole orthogonalization is one HBM read and
    one HBM write per stacked matrix.
    """
    x = x_ref[0].astype(jnp.float32)
    for _ in range(steps):
        x = _ns_step(x, gram_ref, a=a, b=b, c=c, tm=tm, nt=nt)
    out_ref[0] = x.astype(out_ref.dtype)


def _padded_dims(m: int, n: int, tm: int) -> tuple[int, int, int]:
    """(tile, m_p, n_p): Gram tile clamped to the matrix, TPU-aligned pads."""
    tm_ = min(tm, round_up(m, 8))
    return tm_, round_up(m, tm_), round_up(n, 128)


def fits_vmem(shape, *, tm: int = DEFAULT_GRAM_TILE, budget: int = VMEM_LIMIT_BYTES) -> bool:
    """Whether the fused kernel compiles for ``shape`` within ``budget``.

    Counts an upper bound of the scoped VMEM Mosaic allocates: Pallas
    double-buffers the fp32 in and out blocks across grid steps
    (4 x ``m_p x n_p``); the Gram accumulator is one ``m_p x m_p`` scratch,
    and Mosaic keeps up to four more fp32 Gram-sized temporaries (the
    loaded Gram, ``A @ A``, the polynomial and relayout copies). The v5e
    compiler's reported use stays within this count from (128, 1536) to
    (768, 768) and (128, 6144). ``m`` is the post-transpose small side.
    """
    m, n = int(shape[-2]), int(shape[-1])
    m, n = min(m, n), max(m, n)
    _, mp, np_ = _padded_dims(m, n, tm)
    return 4 * (4 * mp * np_ + 5 * mp * mp) <= budget


def _ns_iteration_padded(
    xp: jax.Array, a: float, b: float, c: float, tm: int, interpret: bool
) -> jax.Array:
    """Launch the fused kernel on an already tile-aligned ``(B, m_p, n_p)``."""
    bsz, mp, np_ = xp.shape
    _count_launch()
    return pl.pallas_call(
        functools.partial(_fused_ns_kernel, a=a, b=b, c=c, tm=tm, nt=mp // tm),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, mp, np_), lambda i: (i, 0, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec(
            (1, mp, np_), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, mp, np_), xp.dtype),
        scratch_shapes=[pltpu.VMEM((mp, mp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(xp)


def _ns_chain_padded(
    xp: jax.Array, a: float, b: float, c: float, tm: int, steps: int,
    interpret: bool,
) -> jax.Array:
    """Launch the whole K-iteration chain on a tile-aligned ``(B, m_p, n_p)``.

    One ``pallas_call`` total — the same VMEM working set as the single
    iteration (X/Y blocks + Gram scratch + Gram-sized temporaries), so the
    ``fits_vmem`` gate applies unchanged.
    """
    bsz, mp, np_ = xp.shape
    _count_launch()
    return pl.pallas_call(
        functools.partial(
            _fused_ns_chain_kernel, a=a, b=b, c=c, tm=tm, nt=mp // tm,
            steps=steps,
        ),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, mp, np_), lambda i: (i, 0, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec(
            (1, mp, np_), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, mp, np_), xp.dtype),
        scratch_shapes=[pltpu.VMEM((mp, mp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(xp)


def _pad_stack(x: jax.Array, mp: int, np_: int) -> jax.Array:
    """Zero-pad the trailing dims of ``(B, m, n)`` to ``(B, m_p, n_p)``.

    Zero-padding is exact for NS: padded rows/cols of X produce zero
    rows/cols in A and in ``(bA + cA^2) X``, and ``aX`` keeps the pad zero,
    so slicing the output back recovers the unpadded result.
    """
    _, m, n = x.shape
    if (mp, np_) == (m, n):
        return x
    return jnp.pad(x, ((0, 0), (0, mp - m), (0, np_ - n)))


@functools.partial(jax.jit, static_argnames=("coeffs", "tm", "interpret"))
def ns_iteration_batched(
    x: jax.Array,
    coeffs,
    *,
    tm: int = DEFAULT_GRAM_TILE,
    interpret: bool = False,
) -> jax.Array:
    """One fused NS iteration over a stack ``(B, m, n)`` — one launch total."""
    if x.ndim != 3:
        raise ValueError(f"fused kernel expects (stack, m, n), got {x.shape}")
    a, b, c = (float(v) for v in coeffs)
    _, m, n = x.shape
    tm_, mp, np_ = _padded_dims(m, n, tm)
    out = _ns_iteration_padded(
        _pad_stack(x, mp, np_), a, b, c, tm_, interpret
    )
    return out[:, :m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("steps", "coeffs", "eps", "tm", "interpret", "chain",
                     "normalize"),
)
def orthogonalize(
    g: jax.Array,
    steps: int = 5,
    coeffs=(2.0, -1.5, 0.5),
    *,
    eps: float = 1e-7,
    tm: int = DEFAULT_GRAM_TILE,
    interpret: bool = False,
    chain: bool = False,
    normalize: bool = True,
) -> jax.Array:
    """Fused-kernel NS orthogonalization over the trailing two dims.

    Accepts arbitrary leading (stack) dims; matches
    ``core.newton_schulz.orthogonalize`` numerics — iterate on the smaller
    side, fro-normalize, fp32 internally, cast back at the end.
    ``normalize=False`` skips the entry normalization for pre-scaled inputs
    (the Turbo-Muon preconditioner path).

    ``chain=True`` runs all ``steps`` iterations inside ONE Pallas launch
    (X stays in VMEM for the whole chain); ``chain=False`` launches once
    per iteration — same numerics, K-1 extra HBM round-trips of X.
    """
    if g.ndim < 2:
        raise ValueError(f"orthogonalize expects a matrix, got shape {g.shape}")
    orig_dtype = g.dtype
    orig_shape = g.shape
    *lead, m, n = g.shape
    x = g.astype(jnp.float32).reshape(-1, m, n)
    transpose = m > n
    if transpose:
        x = jnp.swapaxes(x, -1, -2)
        m, n = n, m
    if normalize:
        norm = jnp.linalg.norm(x, axis=(-2, -1), keepdims=True)
        x = x / (norm + eps)
    # Pad once for the whole chain (zero-pad is NS-exact, see _pad_stack) so
    # each iteration is exactly one launch with no pad/slice copies between.
    a, b, c = (float(v) for v in coeffs)
    tm_, mp, np_ = _padded_dims(m, n, tm)
    x = _pad_stack(x, mp, np_)
    if chain:
        x = _ns_chain_padded(x, a, b, c, tm_, steps, interpret)
    else:
        for _ in range(steps):
            x = _ns_iteration_padded(x, a, b, c, tm_, interpret)
    x = x[:, :m, :n]
    if transpose:
        x = jnp.swapaxes(x, -1, -2)
    return x.reshape(orig_shape).astype(orig_dtype)
