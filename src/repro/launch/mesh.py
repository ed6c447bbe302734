"""Mesh construction: production pod meshes + `--mesh` spec parsing.

``make_mesh`` is the one place a device mesh is built. Every axis is
``AxisType.Auto``: the sharding code here relies on the GSPMD partitioner
(``jax.make_mesh`` alone defaults to ``Explicit`` axes, under which the
partitioner's implicit resharding is a type error).

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). The dry-run launcher forces 512 host platform devices
*before* importing anything from repro (see launch/dryrun.py lines 1-2).

``parse_mesh_spec`` / ``make_mesh_from_spec`` back the launchers' ``--mesh``
flag: ``"pod=2,data=2,model=2"`` (explicit axis=size pairs, any subset of
pod/data/model in that order) or the positional shorthand ``"2,2,2"``
(pod,data,model) / ``"4,2"`` (data,model).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

MESH_AXES = ("pod", "data", "model")


def make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-partitioned)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def parse_mesh_spec(spec: str) -> tuple[tuple[str, ...], tuple[int, ...]]:
    """Parse a ``--mesh`` string into ``(axis_names, shape)``.

    Accepts ``"pod=2,data=2,model=2"`` (named; axes must be a subset of
    ``('pod', 'data', 'model')`` and are reordered major-to-minor) or the
    positional shorthand ``"2,2,2"`` -> pod,data,model / ``"4,2"`` ->
    data,model / ``"8"`` -> data.
    """
    parts = [p.strip() for p in spec.split(",") if p.strip()]
    if not parts:
        raise ValueError(f"empty mesh spec {spec!r}")
    if any("=" in p for p in parts):
        by_axis: dict[str, int] = {}
        for p in parts:
            name, _, size = p.partition("=")
            name = name.strip()
            if name not in MESH_AXES:
                raise ValueError(
                    f"unknown mesh axis {name!r} in {spec!r}; "
                    f"axes are {MESH_AXES}"
                )
            if name in by_axis:
                raise ValueError(f"duplicate mesh axis {name!r} in {spec!r}")
            by_axis[name] = int(size)
        axes = tuple(a for a in MESH_AXES if a in by_axis)
        return axes, tuple(by_axis[a] for a in axes)
    sizes = tuple(int(p) for p in parts)
    if len(sizes) > len(MESH_AXES):
        raise ValueError(
            f"mesh spec {spec!r} has {len(sizes)} entries; max is "
            f"{len(MESH_AXES)} ({MESH_AXES})"
        )
    # positional: the LAST axes of (pod, data, model) — "4,2" is data,model
    axes = MESH_AXES[len(MESH_AXES) - len(sizes):]
    return axes, sizes


def make_mesh_from_spec(spec: str) -> jax.sharding.Mesh:
    """Build a mesh from a ``--mesh`` spec over the available devices."""
    axes, shape = parse_mesh_spec(spec)
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {dict(zip(axes, shape))} needs {n} devices, have "
            f"{len(devices)} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} for a host smoke)"
        )
    return make_mesh(shape, axes, devices=devices[:n])


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """TPU v5e, 256 chips/pod, (data=16, model=16) per pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before jax initializes"
        )
    return make_mesh(shape, axes, devices=devices[:n])


def make_local_mesh(model: int | None = None, data: int | None = None,
                    pod: int | None = None) -> jax.sharding.Mesh:
    """Best-effort mesh over whatever devices exist (CPU tests, small runs).

    With ``pod`` the mesh is hierarchical ``('pod', 'data', 'model')``;
    otherwise the flat ``('data', 'model')``.
    """
    n = len(jax.devices())
    if model is None:
        model = 1
    if pod:
        if data is None:
            data = n // (model * pod)
        return make_mesh(
            (pod, data, model), ("pod", "data", "model"),
            devices=jax.devices()[: pod * data * model],
        )
    if data is None:
        data = n // model
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[: data * model])
