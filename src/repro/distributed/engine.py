"""Explicitly-scheduled distributed execution of the MuonBP update.

The GSPMD path in ``core/muon.py`` expresses distribution implicitly: block
steps rely on the compiler noticing that logical blocks coincide with
shards, and full steps rely on it inserting the momentum gather somewhere
sensible. That works, but the communication schedule is an emergent property
of the partitioner — it cannot be asserted, priced, or overlapped. This
module is the explicit alternative: a ``jax.shard_map`` region per update in
which every collective is written out by hand, scheduled to match
``distributed/plan.py`` exactly:

  * **block phase** — the shard-local array on each device *is* the MuonBP
    block (paper Sec 3: "block = the shard on one device"). The body runs
    Newton-Schulz directly on it. Zero collectives by construction, not by
    compiler fortune. Leaves whose block grid is coarser than their shard
    grid (e.g. replicated params carrying a logical block spec) are blocked
    by the residual factor locally, so numerics match the GSPMD path
    bit-for-bit in every configuration.
  * **full phase** — per sharded leaf: ``lax.all_gather`` the momentum
    shards over the trailing-dim model axes (tiled), run the full NS
    redundantly on every rank, and ``dynamic_slice`` the local shard back
    out. One gather per sharded leaf, nothing else. By default the gathers
    are *pipelined* (the program's compiled :class:`PipelineSchedule`):
    bucket i+1's gathers issue while bucket i orthogonalizes and bucket
    i−1 slices back, double-buffered with ``lax.optimization_barrier`` so
    at most two buckets' gathered momentum is ever live. The barrier body
    (gather everything, NS everything, slice everything) remains as the
    ``full_schedule='barrier'`` A/B.

All of those decisions are made at *compile* time: ``core/program.py``
builds the engine-mode :class:`UpdateProgram` from this engine's momentum
PartitionSpecs (gather CommOps, residual block grids, device-local bucket
plans, per-bucket kernel strategies), and :meth:`ShardMapEngine.run_program`
merely executes one phase of it inside a single ``shard_map`` region —
leaf gathers, the shared bucket interpreter (``program.execute_ops``),
leaf slices. Inside the body everything is device-local, so buckets
concat-pack into one batched NS chain per distinct local shape and run on
the ``kernels/dispatch.py`` backend (fused-chain Pallas kernel when the
bucket fits VMEM) — even block steps get maximum batching (the GSPMD
program must stack-pack to avoid resharding; the shard_map body has no such
constraint).

ZeRO-1 composes transparently: the engine's in specs are the *momentum*
specs (``sharding.specs.momentum_spec``), so a data-sharded leading stack
dim simply makes the local NS batch smaller — full-step gathers move
1/data_size of the bytes and each rank orthogonalizes only its own layers.
On a hierarchical ``('pod', 'data', 'model')`` mesh the ZeRO axes default
to ``('pod', 'data')`` and, because every collective here is written
against a *named* axis, gathers only ever traverse the axes a leaf's spec
names: trailing-dim (model) gathers stay intra-pod by construction, and
the only inter-pod collectives are the ones the plan prices as such.

When ``num_layers`` does not divide the ZeRO axes (granite: 36 vs 16) the
*flatten-and-shard fallback* (``zero1_flatten=True``) stores the momentum
with its lead dim ceil-padded to a multiple of the axes and sharded —
block/full steps run unchanged on each rank's own (padded) layers, and the
one extra cost is the writeback: per-axis all-gathers restore the padded
update stack and a local slice drops the pad, so updates leave the region
in the PARAM layout (priced in the plan's 'apply' phase).

``core.muon.muon(..., comm=engine)`` compiles the update program against
this engine. ``muon(layer_shard=...)`` composes with it as the explicit
in-body fold (and remains the GSPMD re-shard without an engine).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import program as program_lib
from repro.sharding import specs as sh
from repro.sharding.specs import spec_entry_names as _names
from repro.sharding.specs import spec_entry_size as _factor

PathKey = tuple[str, ...]


def path_key(path) -> PathKey:
    return tuple(sh.path_names(path))


def _entries(spec: P, ndim: int) -> list:
    ent = list(spec)
    return ent + [None] * (ndim - len(ent))


def _gather_trailing(x: jax.Array, spec: P, sizes: dict[str, int]) -> jax.Array:
    """Tiled all-gather of the trailing (matrix) dims, dim -2 then -1.

    Tuple spec entries gather minor axis first so the concatenation order
    reproduces PartitionSpec's major-to-minor global layout.
    """
    entries = _entries(spec, x.ndim)
    for dim, entry in ((x.ndim - 2, entries[-2]), (x.ndim - 1, entries[-1])):
        for name in reversed(_names(entry)):
            if sizes.get(name, 1) > 1:
                x = jax.lax.all_gather(x, name, axis=dim, tiled=True)
    return x


def _slice_trailing(x: jax.Array, spec: P, sizes: dict[str, int]) -> jax.Array:
    """Inverse of :func:`_gather_trailing`: take this rank's shard (local)."""
    entries = _entries(spec, x.ndim)
    for dim, entry in ((x.ndim - 2, entries[-2]), (x.ndim - 1, entries[-1])):
        factor = _factor(entry, sizes)
        if factor == 1:
            continue
        idx = jnp.zeros((), jnp.int32)
        for name in _names(entry):  # major-to-minor linear index
            idx = idx * sizes.get(name, 1) + jax.lax.axis_index(name)
        local = x.shape[dim] // factor
        x = jax.lax.dynamic_slice_in_dim(x, idx * local, local, axis=dim)
    return x


@dataclasses.dataclass(frozen=True)
class ShardMapEngine:
    """shard_map executor for compiled MuonBP update programs on one mesh.

    ``uspec_by_path`` maps param-tree path keys to the *momentum* spec of
    that leaf (param spec, plus the ZeRO-1 lead-dim data sharding when
    enabled) — the sharding the NS input ``u = g + mu*m`` arrives in and
    (except for flatten-fallback leaves, which leave in the param layout)
    the sharding the orthogonalized update leaves in. The program compiler
    reads it via :meth:`spec_for` to plan gathers and device-local bucket
    shapes.

    ``flatten_by_path`` records the ZeRO-1 flatten-and-shard fallback
    (``sharding.specs.FlattenSpec``) for leaves whose lead dim does not
    divide the ZeRO axes: their momentum is stored lead-padded + sharded
    (:meth:`state_shape_for` tells ``muon.init``/``muon.update`` the
    padded shape) and the program attaches the writeback 'apply' CommOp.
    """

    mesh: Mesh
    uspec_by_path: dict
    flatten_by_path: dict = dataclasses.field(default_factory=dict)

    @property
    def axis_sizes(self) -> dict[str, int]:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def spec_for(self, key: PathKey, ndim: int) -> P:
        spec = self.uspec_by_path.get(key)
        if spec is None:
            return P(*(None,) * ndim)
        return P(*_entries(spec, ndim)[:ndim])

    def flatten_for(self, key: PathKey):
        """FlattenSpec of a ZeRO-1 flatten-fallback leaf, or None."""
        return self.flatten_by_path.get(key)

    def state_shape_for(self, key: PathKey, shape: tuple) -> tuple:
        """Momentum/NS-input shape for a leaf — lead-padded under the
        flatten fallback, the param shape otherwise."""
        fl = self.flatten_by_path.get(key)
        if fl is None:
            return tuple(shape)
        return fl.padded_shape(shape)

    def _layer_shard_apply(self, sizes: dict[str, int]) -> Callable:
        """Explicit in-body layer_shard: local slice -> NS share -> all-gather.

        The packed stack is replicated over the layer_shard axis once the
        trailing-dim gathers have run, so each rank's slice is free; the
        one collective is the tiled all-gather that restores the full stack
        after NS — exactly what ``plan.layer_shard_collectives('engine')``
        prices.
        """

        def apply(packed: jax.Array, op: program_lib.BucketOp):
            from repro.distributed.plan import layer_shard_dims

            axis = op.comm.axes[0]
            d = sizes.get(axis, 1)
            lead = packed.shape[:-2]
            stack, stack_p, m, n = layer_shard_dims(packed.shape, d)
            x2 = packed.reshape(stack, m, n)
            if stack_p > stack:
                x2 = jnp.concatenate(
                    [x2, jnp.zeros((stack_p - stack, m, n), x2.dtype)], axis=0
                )
            shard = stack_p // d
            idx = jax.lax.axis_index(axis) if d > 1 else jnp.zeros((), jnp.int32)
            x_local = jax.lax.dynamic_slice_in_dim(x2, idx * shard, shard, axis=0)

            def undo(o: jax.Array) -> jax.Array:
                if d > 1:
                    o = jax.lax.all_gather(o, axis, axis=0, tiled=True)
                if stack_p > stack:
                    o = o[:stack]
                return o.reshape(*lead, m, n)

            return x_local, undo

        return apply

    def run_program(
        self,
        prog: program_lib.PhaseProgram,
        u_leaves: Sequence[jax.Array],
        orth: Callable,
    ) -> list[jax.Array]:
        """Execute one compiled phase inside a single shard_map region.

        The program's leaf records carry this engine's momentum specs and
        gather CommOps. With a compiled :class:`program.PipelineSchedule`
        (full steps, ``full_schedule='pipelined'``) the body walks the
        stages — issue bucket i+1's gathers, orthogonalize bucket i, slice
        bucket i−1 back to shard layout — double-buffered: a stage's
        gathers are gated on the NS output from two stages back with
        ``lax.optimization_barrier`` (identity on values), so at most two
        buckets' gathered momentum is live and the compiler cannot hoist
        every gather to the top. Without a schedule the body is the
        barrier reference: gather all, interpret all BucketOps, slice all.
        """
        if not u_leaves:
            return []
        sizes = self.axis_sizes
        leaf_execs = prog.leaf_execs
        specs = tuple(le.spec for le in leaf_execs)
        # Flatten-fallback leaves leave the region in the PARAM layout (the
        # writeback gathered their padded lead dim); everything else keeps
        # its momentum spec.
        out_specs = tuple(
            le.out_spec if le.out_spec is not None else le.spec
            for le in leaf_execs
        )
        ls_apply = self._layer_shard_apply(sizes)

        def writeback(o, le):
            """Slice the trailing shard back out, then (flatten fallback
            only) gather the padded lead dim per ZeRO axis — minor axis
            first, mirroring the trailing-dim gathers — and drop the pad
            (local slice)."""
            if le.gather is not None:
                o = _slice_trailing(o, le.spec, sizes)
            if le.apply is not None:
                for name in reversed(le.apply.axes):
                    if sizes.get(name, 1) > 1:
                        o = jax.lax.all_gather(o, name, axis=0, tiled=True)
                if le.lead is not None and o.shape[0] != le.lead:
                    o = jax.lax.slice_in_dim(o, 0, le.lead, axis=0)
            return o

        # Trace annotations: named_scope only attaches names to the traced
        # ops (HLO metadata / profiler TraceAnnotation rows keyed
        # ``muonbp.<phase>.s<stage>.<gather|ns|writeback>``), so a profiler
        # capture reads against PipelineSchedule.describe() stage indices
        # while the compiled program stays bitwise-identical. Staggered
        # phase names carry a ':' ("stagger:3"), which named_scope rejects;
        # the scope drops it ("stagger3").
        scope = prog.phase.replace(":", "")

        def barrier_body(*xs):
            with jax.named_scope(f"muonbp.{scope}.gather"):
                ins = [
                    _gather_trailing(x, le.spec, sizes) if le.gather is not None else x
                    for x, le in zip(xs, leaf_execs)
                ]
            with jax.named_scope(f"muonbp.{scope}.ns"):
                outs = program_lib.execute_ops(
                    prog.ops, ins, orth, layer_shard_apply=ls_apply
                )
            with jax.named_scope(f"muonbp.{scope}.writeback"):
                return tuple(
                    writeback(o, le) for o, le in zip(outs, leaf_execs)
                )

        def pipelined_body(*xs):
            results: list = [None] * len(xs)
            pending: dict = {}   # leaf index -> NS output awaiting writeback
            gathered: dict = {}  # leaf index -> gathered (global-trailing) input
            gate = None          # NS output from the previous stage's compute
            for stage in prog.schedule.stages:
                with jax.named_scope(f"muonbp.{scope}.s{stage.index}.gather"):
                    for li in stage.gathers:
                        x = xs[li]
                        if gate is not None:
                            # Double-buffer gate: this gather may not issue
                            # before the NS two computes back has retired.
                            x, _ = jax.lax.optimization_barrier((x, gate))
                        gathered[li] = _gather_trailing(
                            x, leaf_execs[li].spec, sizes
                        )
                if stage.compute is not None:
                    op = prog.ops[stage.compute]
                    ins = list(xs)
                    for le in op.leaves:
                        if le.index in gathered:
                            ins[le.index] = gathered.pop(le.index)
                    with jax.named_scope(f"muonbp.{scope}.s{stage.index}.ns"):
                        for idx, out in program_lib.execute_op(
                            op, ins, orth, layer_shard_apply=ls_apply
                        ):
                            pending[idx] = out
                            gate = out
                with jax.named_scope(f"muonbp.{scope}.s{stage.index}.writeback"):
                    for li in stage.writeback:
                        results[li] = writeback(pending.pop(li), leaf_execs[li])
            assert not pending and all(r is not None for r in results), (
                "pipeline schedule left leaves unwritten"
            )
            return tuple(results)

        body = barrier_body if prog.schedule is None else pipelined_body
        fn = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=specs,
            out_specs=out_specs,
            check_vma=False,
        )
        return list(fn(*u_leaves))


def make_engine(params: Any, pspecs: Any, mesh: Mesh, *, zero1: bool = False,
                zero1_axis=None, zero1_flatten: bool = False) -> ShardMapEngine:
    """Build a :class:`ShardMapEngine` from the param tree + PartitionSpecs.

    ``params`` may be arrays or ShapeDtypeStructs (shapes only are read).
    With ``zero1`` the engine's update specs carry the ZeRO-1 lead-dim data
    sharding from ``sharding.specs.momentum_spec`` — pair it with
    ``distributed.zero1`` so the momentum actually lives in those shards.
    ``zero1_axis`` may be an axis name, a tuple of names, or None for the
    mesh's data axes (``('pod', 'data')`` on a hierarchical mesh). With
    ``zero1_flatten``, leaves whose lead dim does not divide the ZeRO axes
    engage the flatten-and-shard fallback (padded lead dim, recorded in
    ``flatten_by_path``) instead of silently no-opping.
    """
    sizes = sh.mesh_axis_sizes(mesh)
    axes = sh.zero1_axes(sizes, zero1_axis)
    uspecs: dict[PathKey, P] = {}
    flatten: dict[PathKey, Any] = {}
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    spec_leaves = jax.tree.flatten(pspecs, is_leaf=lambda x: isinstance(x, P))[0]
    if len(flat_p) != len(spec_leaves):
        raise ValueError(
            f"params/pspecs leaf counts differ: {len(flat_p)}/{len(spec_leaves)}"
        )
    for (path, leaf), spec in zip(flat_p, spec_leaves):
        key = path_key(path)
        shape = tuple(leaf.shape)
        fl = (
            sh.zero1_flatten_info(spec, shape, sizes, zero1_axis=axes)
            if zero1 and zero1_flatten else None
        )
        if fl is not None:
            flatten[key] = fl
            uspecs[key] = sh.flatten_momentum_spec(spec, shape, fl)
        else:
            uspecs[key] = sh.momentum_spec(
                spec, shape, sizes, zero1=zero1, zero1_axis=axes
            )
    return ShardMapEngine(mesh=mesh, uspec_by_path=uspecs,
                          flatten_by_path=flatten)
