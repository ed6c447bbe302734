"""The Pallas kernels compile for a described TPU v5e, at real widths.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached, and refuses what the chip would refuse (VMEM
overuse, misaligned tiles). muonbp-960m's optimizer buckets are the
shapes: K/V stacks (12, 384, 1536), Q/O stacks (12, 1536, 1536) and MLP
stacks (12, 1536, 6144). The boundary test holds ``fused.fits_vmem`` to the
compiler: every shape the dispatcher hands to ``fused_chain`` compiles,
and a shape the compiler refuses is one the gate refuses too.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch, normuon
from repro.kernels.newton_schulz import fused, ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent cache
        # but cannot be read back without the chip: keep the cache off.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, shape, sharding):
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    return jax.jit(fn).lower(x).compile().as_text()


def _fused_chain(g):
    return fused.orthogonalize(g, steps=5, chain=True)


def _normuon(x):
    v = jnp.zeros((*x.shape[:-1], 1), jnp.float32)
    return normuon.neuron_norm(x, v, jnp.float32(0.5), beta2=0.95, eps=1e-8,
                               refresh=True)


@pytest.mark.parametrize("fn,shape", [
    pytest.param(_fused_chain, (12, 384, 1536), id="fused_chain_kv"),
    pytest.param(lambda g: ops.orthogonalize(g, steps=5), (1536, 6144),
                 id="tiled_mlp"),
    pytest.param(lambda g: ops.orthogonalize_batched(g, steps=5),
                 (12, 1536, 1536), id="tiled_batched_qo"),
    pytest.param(_normuon, (12, 384, 1536), id="normuon_kv"),
    pytest.param(_normuon, (12, 1536, 6144), id="normuon_mlp"),
    pytest.param(_normuon, (4, 256, 16384), id="normuon_wide"),
])
def test_kernel_compiles_at_960m_widths(one_chip, fn, shape):
    assert "tpu_custom_call" in _compile_text(fn, shape, one_chip)


def _gate_edge_shapes():
    """For each small side, the widest (2, m, n) the gate still gives to
    ``fused_chain`` under the full and the pipelined budget."""
    shapes = []
    for budget in (fused.VMEM_LIMIT_BYTES, dispatch.pipeline_vmem_budget()):
        for m in (128, 256, 384, 512, 640):
            widest = None
            for n in range(m, 16384 + 1, 128):
                if fused.fits_vmem((m, n), budget=budget):
                    widest = n
            if widest is not None:
                shapes.append((budget, (2, m, widest)))
    return shapes


@pytest.mark.parametrize(
    "budget,shape", _gate_edge_shapes(),
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else f"{v >> 20}MiB")
def test_fused_chain_gate_shapes_compile(one_chip, budget, shape):
    """Every shape the dispatcher assigns to ``fused_chain`` compiles."""
    assert dispatch.plan_strategy(shape, "pallas", vmem_budget=budget) == "fused_chain"
    assert "tpu_custom_call" in _compile_text(_fused_chain, shape, one_chip)


@pytest.mark.parametrize("shape,compiles", [
    pytest.param((2, 640, 1536), False, id="2x640x1536"),  # Mosaic needs 17.7 MiB
    pytest.param((4, 576, 1536), True, id="4x576x1536"),
])
def test_fused_gate_agrees_with_compiler(one_chip, shape, compiles):
    """Where the compiler refuses the fused kernel, the gate refuses it
    too and the dispatcher plans the tiled path instead."""
    try:
        _compile_text(_fused_chain, shape, one_chip)
        ok = True
    except Exception as e:
        assert re.search(r"vmem", str(e), re.I), e
        ok = False
    assert ok == compiles
    if not compiles:
        assert not fused.fits_vmem(shape)
        assert dispatch.plan_strategy(shape, "pallas") == "tiled"
