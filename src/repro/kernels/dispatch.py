"""Newton-Schulz backend registry: route ``orthogonalize`` to an engine.

``core.newton_schulz.orthogonalize`` — the single entry point the optimizer,
benchmarks, and tests all use — resolves its execution engine here, so the
same model/optimizer code can be A/B'd across backends:

  * ``"jnp"``    — the pure-jnp chain (XLA fuses it; the right default on
    CPU and the numerics oracle everywhere).
  * ``"pallas"`` — the Pallas kernels: the fused-chain kernel (all K NS
    iterations in ONE launch) when the working set fits VMEM, else the
    tiled 3-launch streaming path (2D matrices AND batched stacks).
    Interpret mode is selected automatically off-TPU, so the pallas path
    is correct (if slow) on CPU.

Selection has two static levels:

  * **backend** — registry name. Precedence: explicit ``backend=`` argument
    > ``set_backend()`` / ``use_backend()`` override > ``REPRO_NS_BACKEND``
    env var > ``"jnp"``.
  * **strategy** — which kernel within the backend (:data:`STRATEGIES`).
    ``plan_strategy(shape, backend)`` derives the default from the shape at
    compile time; the compiled :class:`repro.core.program.UpdateProgram`
    records one strategy per bucket so the hot path never re-derives VMEM
    fits. ``REPRO_NS_STRATEGY`` / an explicit ``strategy=`` pin it for A/Bs
    (``fused_iter`` keeps the one-launch-per-iteration kernel reachable as
    the fused-chain comparison point).

Backend/strategy resolution happens at trace time (the names are static),
so switching retriggers jit specialization as expected.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable, Optional

import jax

ENV_VAR = "REPRO_NS_BACKEND"
STRATEGY_ENV_VAR = "REPRO_NS_STRATEGY"

# Kernel strategies within a backend. "auto" defers to plan_strategy.
STRATEGIES = ("auto", "jnp", "fused_chain", "fused_iter", "tiled")

# VMEM headroom a *pipelined* stage reserves before choosing fused_chain:
# while bucket i orthogonalizes, bucket i+1's gather is in flight and the
# async collective's landing/streaming buffers double-buffer through VMEM.
# A stage that would fill the whole budget with its own working set would
# stall the overlap the schedule exists to create, so pipelined kernel
# planning runs against ``pipeline_vmem_budget()`` instead of the full
# budget (see core/program.py's compiler). The reserve is per LINK CLASS:
# an inter-pod (DCN) gather drains ~8x slower than an intra-pod (ICI) one
# (distributed/plan.py's modeled rates), so its landing buffers stay live
# across more NS chains and the stage reserves proportionally more.
PIPELINE_VMEM_RESERVE_BYTES = 2 * 2 ** 20
PIPELINE_VMEM_RESERVE_BY_LINK = {
    "ici": PIPELINE_VMEM_RESERVE_BYTES,
    "dcn": 2 * PIPELINE_VMEM_RESERVE_BYTES,
}

_REGISTRY: dict[str, Callable] = {}
_override: Optional[str] = None

# Trace-time launch observer. ``repro.obs`` sets this (via
# ``set_launch_hook``) to count NS dispatches per backend/strategy/shape —
# dispatch stays import-clean of the obs layer. The hook fires when a call
# is TRACED (once per jit specialization), not per device execution, so it
# adds nothing to the compiled program and cannot sync the hot path.
_launch_hook: Optional[Callable[[str, Optional[str], tuple], None]] = None


def set_launch_hook(
    fn: Optional[Callable[[str, Optional[str], tuple], None]],
) -> None:
    """Install (or with None, clear) the NS launch observer.

    ``fn(backend, strategy, shape)`` is invoked from :func:`orthogonalize`
    at trace time; exceptions propagate (a broken observer should fail
    loudly in tests, not silently drop counts).
    """
    global _launch_hook
    _launch_hook = fn


def register_backend(name: str, fn: Callable) -> None:
    """Register ``fn(g, steps, coeffs, eps, strategy, normalize) -> array``
    under ``name``."""
    _REGISTRY[name] = fn


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_backend() -> str:
    """Resolve the active backend name (override > env var > 'jnp')."""
    name = _override if _override is not None else os.environ.get(ENV_VAR, "jnp")
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown NS backend {name!r}; available: {available_backends()}"
        )
    return name


def set_backend(name: Optional[str]) -> None:
    """Set (or with None, clear) the process-wide backend override."""
    global _override
    if name is not None and name not in _REGISTRY:
        raise ValueError(
            f"unknown NS backend {name!r}; available: {available_backends()}"
        )
    _override = name


@contextlib.contextmanager
def use_backend(name: str):
    """Scoped backend override (used by benchmarks to A/B engines)."""
    prev = _override
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def pipeline_vmem_budget(link: str = "ici") -> int:
    """VMEM budget for kernel planning inside a pipelined full-step stage.

    ``link`` is the class of the in-flight gather's slowest mesh axis
    ('ici' intra-pod, 'dcn' inter-pod) — DCN stages reserve twice the
    headroom because their collective buffers stay live ~8x longer.
    """
    from repro.kernels.newton_schulz import fused

    try:
        reserve = PIPELINE_VMEM_RESERVE_BY_LINK[link]
    except KeyError:
        raise ValueError(
            f"link must be one of {tuple(PIPELINE_VMEM_RESERVE_BY_LINK)}, "
            f"got {link!r}"
        ) from None
    return fused.VMEM_LIMIT_BYTES - reserve


def plan_strategy(shape, backend: str, *, vmem_budget: Optional[int] = None) -> str:
    """Static kernel plan for a (stacked) matrix shape under a backend.

    This is the compile-time decision the UpdateProgram records per bucket:

      * jnp backend       -> ``"jnp"`` (XLA fuses the chain itself)
      * fits VMEM         -> ``"fused_chain"`` (all K iterations, ONE launch)
      * oversized         -> ``"tiled"`` (3-launch HBM streaming; batched
                             stacks loop the 2D path per matrix)

    ``vmem_budget`` overrides the fused kernel's default working-set budget
    — pipelined stages plan against :func:`pipeline_vmem_budget` so a stage
    never picks a fused_chain that would crowd out the in-flight gather's
    double buffers. ``REPRO_NS_STRATEGY`` overrides the shape-derived
    choice for A/Bs.
    """
    env = os.environ.get(STRATEGY_ENV_VAR)
    if env and env != "auto":
        if env not in STRATEGIES:
            raise ValueError(
                f"unknown NS strategy {env!r}; available: {STRATEGIES}"
            )
        return env
    if backend != "pallas":
        return "jnp"
    from repro.kernels.newton_schulz import fused

    budget = vmem_budget if vmem_budget is not None else fused.VMEM_LIMIT_BYTES
    if fused.fits_vmem(shape, budget=budget):
        return "fused_chain"
    return "tiled"


def shared_launch_groups(keys) -> dict:
    """Plan cross-bucket launch sharing over concat-mode bucket keys.

    ``keys`` are ``(m, n, dtype)`` bucket keys. Buckets that differ only in
    dtype share one batched launch: members are cast to the promoted compute
    dtype on pack, the NS chain runs once over the fatter stack, and a cast
    epilogue restores each member's dtype on unpack (exact — every NS kernel
    computes in fp32 internally, so casting up-front reproduces the
    separate-launch numerics bit-for-bit). Returns
    ``{(m, n): (compute_dtype, (dtype, ...))}`` per shared group; groups
    with a single dtype map to ``(dtype, ())`` — no epilogue.
    """
    import jax.numpy as jnp

    by_shape: dict = {}
    for m, n, dt in keys:
        by_shape.setdefault((m, n), set()).add(dt)
    out = {}
    for shape_key, dtypes in by_shape.items():
        if len(dtypes) == 1:
            out[shape_key] = (next(iter(dtypes)), ())
        else:
            compute = str(
                functools.reduce(jnp.promote_types, sorted(dtypes))
            )
            out[shape_key] = (compute, tuple(sorted(dtypes)))
    return out


def orthogonalize(
    g, *, steps, coeffs, eps, backend: Optional[str] = None,
    strategy: Optional[str] = None, normalize: bool = True,
):
    """Dispatch ``Orth(g)`` to the selected backend/strategy.

    ``normalize=False`` skips the kernels' entry Frobenius normalization
    (the caller pre-scaled the input into the NS convergence basin — the
    Turbo-Muon preconditioner path).
    """
    name = backend if backend is not None else get_backend()
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown NS backend {name!r}; available: {available_backends()}"
        )
    if strategy is not None and strategy not in STRATEGIES:
        raise ValueError(
            f"unknown NS strategy {strategy!r}; available: {STRATEGIES}"
        )
    if _launch_hook is not None:
        _launch_hook(name, strategy, tuple(g.shape))
    return _REGISTRY[name](g, steps, coeffs, eps, strategy, normalize)


def _jnp_backend(g, steps, coeffs, eps, strategy=None, normalize=True):
    from repro.core.newton_schulz import orthogonalize_jnp

    return orthogonalize_jnp(g, steps=steps, coeffs=coeffs, eps=eps,
                             normalize=normalize)


def _pallas_backend(g, steps, coeffs, eps, strategy=None, normalize=True):
    from repro.core.newton_schulz import orthogonalize_jnp
    from repro.kernels.newton_schulz import fused, ops

    if strategy is None or strategy == "auto":
        strategy = plan_strategy(g.shape, "pallas")
    interpret = jax.default_backend() != "tpu"
    if strategy == "jnp":
        return orthogonalize_jnp(g, steps=steps, coeffs=coeffs, eps=eps,
                                 normalize=normalize)
    if strategy in ("fused_chain", "fused_iter"):
        return fused.orthogonalize(
            g, steps=steps, coeffs=coeffs, eps=eps, interpret=interpret,
            chain=strategy == "fused_chain", normalize=normalize,
        )
    if strategy == "tiled":
        if g.ndim == 2:
            return ops.orthogonalize(
                g, steps=steps, coeffs=coeffs, eps=eps, interpret=interpret,
                normalize=normalize,
            )
        # Oversized stacks stream each matrix through the tiled 3-launch
        # path (ROADMAP item: previously they silently fell back to jnp).
        return ops.orthogonalize_batched(
            g, steps=steps, coeffs=coeffs, eps=eps, interpret=interpret,
            normalize=normalize,
        )
    raise ValueError(f"unknown NS strategy {strategy!r}")


register_backend("jnp", _jnp_backend)
register_backend("pallas", _pallas_backend)
