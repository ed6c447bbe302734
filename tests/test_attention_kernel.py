"""The Pallas flash-attention path (``repro.kernels.attention``) against
the jnp scan of ``layers.flash_attention``, with the kernel in Pallas
interpret mode on the CPU: forward and gradients at grouped-query shapes,
inside ``jax.checkpoint``, and on a 2x2 mesh through the ``shard_map``
wrapper; and the rule that decides which calls take the kernel.

Tolerances are set from the dtype: fp32 operands agree to 1e-5 of the
largest reference value (the two sum in other orders); bf16 operands to
2**-5 of it (a few bf16 ulps: the kernel rounds q after scaling and the
probabilities before the PV matmul, the scan does neither)."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import attention as attention_kernel
from repro.models.layers import flash_attention

TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -5}


def _inputs(batch, seq, hq, hkv, dtype, hd=128):
    ks = jax.random.split(jax.random.PRNGKey(seq + 7 * hq + batch), 4)
    q = jax.random.normal(ks[0], (batch, seq, hq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (batch, seq, hkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (batch, seq, hkv, hd), jnp.float32).astype(dtype)
    ct = jax.random.normal(ks[3], (batch, seq, hq, hd), jnp.float32)
    return q, k, v, ct


def _kernel(q, k, v):
    return attention_kernel.causal_attention(q, k, v, interpret=True)


def _scan(q, k, v):
    return flash_attention(q, k, v, causal=True, block_k=128)


def _value_and_grads(fn, q, k, v, ct, remat):
    f = jax.checkpoint(fn) if remat else fn

    def loss(q, k, v):
        return jnp.sum(f(q, k, v).astype(jnp.float32) * ct)
    out = jax.jit(f)(q, k, v)
    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    return [out, *grads]


def _assert_close(got, want, tol, name):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale, err_msg=name)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "checkpoint"])
@pytest.mark.parametrize("batch,seq,hq,hkv,dtype", [
    (1, 256, 4, 2, jnp.float32),
    (2, 256, 6, 2, jnp.float32),
    (1, 512, 6, 2, jnp.float32),
    (2, 512, 4, 2, jnp.float32),
    (1, 256, 4, 2, jnp.bfloat16),
    (2, 512, 6, 2, jnp.bfloat16),
], ids=lambda p: getattr(p, "__name__", str(p)))
def test_kernel_matches_scan(batch, seq, hq, hkv, dtype, remat):
    q, k, v, ct = _inputs(batch, seq, hq, hkv, dtype)
    got = _value_and_grads(_kernel, q, k, v, ct, remat)
    want = _value_and_grads(_scan, q, k, v, ct, remat)
    assert got[0].dtype == q.dtype and got[0].shape == q.shape
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        _assert_close(g, w, TOL[dtype], name)


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.kernels import attention as attention_kernel
from repro.models.layers import flash_attention

mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
ks = jax.random.split(jax.random.PRNGKey(0), 4)
B, S, HQ, HKV, HD = 2, 256, 4, 2, 128
spec = NamedSharding(mesh, P("data", None, "model", None))
q = jax.device_put(jax.random.normal(ks[0], (B, S, HQ, HD)), spec)
k = jax.device_put(jax.random.normal(ks[1], (B, S, HKV, HD)), spec)
v = jax.device_put(jax.random.normal(ks[2], (B, S, HKV, HD)), spec)
ct = jax.random.normal(ks[3], (B, S, HQ, HD))

def kernel(q, k, v):
    return attention_kernel.causal_attention(
        q, k, v, mesh=mesh, batch_axes=("data",), model_axis="model", interpret=True)

def scan(q, k, v):
    return flash_attention(q, k, v, causal=True, block_k=128)

def run(fn):
    loss = lambda q, k, v: jnp.sum(jax.checkpoint(fn)(q, k, v) * ct)
    return [jax.jit(fn)(q, k, v), *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)]

got, want = run(kernel), run(scan)
hlo = jax.jit(kernel).lower(q, k, v).as_text()
print(json.dumps({
    "gaps": [float(jnp.abs(g - w).max() / jnp.abs(w).max()) for g, w in zip(got, want)],
    "out_sharding": str(got[0].sharding.spec),
    "shard_map": "shard_map" in hlo or "sdy.manual_computation" in hlo,
}))
"""


def test_kernel_on_a_2x2_mesh_through_shard_map():
    """Batch over ``data``, heads over ``model``: each device runs the
    kernel on its own heads of its own rows, and the result and its
    gradients match the scan on the whole arrays."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["shard_map"]
    assert max(res["gaps"]) < TOL[jnp.float32], res["gaps"]


MESH = types.SimpleNamespace(shape={"data": 2, "model": 2})
Q = (2, 4096, 24, 128)
KV = (2, 4096, 8, 128)
FULL = dict(causal=True, window=None, attn_softcap=None, q_offset=0, kv_len=None)


@pytest.mark.parametrize("q_shape,kv_shape,changes,kernel", [
    (Q, KV, {}, True),                                   # causal full self-attention
    (Q, KV, dict(mesh=MESH, batch_axes=("data",), model_axis="model"), True),
    ((2, 1, 24, 128), KV, dict(q_offset=jnp.int32(7), kv_len=jnp.int32(8)), False),  # decode
    ((2, 1, 24, 128), KV, {}, False),                    # Sq == 1
    (Q, KV, dict(q_offset=jnp.int32(0)), False),         # prefill into a cache
    (Q, KV, dict(q_offset=16), False),
    (Q, KV, dict(kv_len=4096), False),
    (Q, KV, dict(window=4096), False),
    (Q, KV, dict(window=jnp.int32(2 ** 30)), False),     # a scanned window flag
    (Q, KV, dict(attn_softcap=50.0), False),
    ((2, 448, 24, 128), (2, 1536, 8, 128), dict(causal=False), False),  # cross-attention
    ((2, 512, 24, 128), (2, 1536, 8, 128), {}, False),   # Sq != Skv
    (Q, KV, dict(causal=False), False),                  # encoder
    ((2, 4096, 24, 64), (2, 4096, 8, 64), {}, False),    # head size 64
    ((2, 4000, 24, 128), (2, 4000, 8, 128), {}, False),  # ragged S
    (Q, KV, dict(backend="cpu"), False),
    (Q, KV, dict(mesh=MESH, batch_axes=("data",), model_axis="model",
                 q_layout="hd", kv_layout="hd"), False),
    ((2, 4096, 24, 128), (2, 4096, 3, 128),
     dict(mesh=MESH, batch_axes=("data",), model_axis="model"), False),  # KV heads over 2
    ((1, 4096, 24, 128), (1, 4096, 8, 128),
     dict(mesh=MESH, batch_axes=("data",), model_axis="model"), False),  # batch over 2
])
def test_dispatch_rule(q_shape, kv_shape, changes, kernel):
    kw = {**FULL, "backend": "tpu", **changes}
    assert attention_kernel.use_kernel(q_shape, kv_shape, **kw) is kernel


@pytest.mark.parametrize("seq,head_dim,block", [
    (4096, 128, 1024), (2560, 128, 512), (256, 128, 256), (384, 128, 128), (4096, 256, 512),
    (4000, 128, None), (64, 128, None)])
def test_block_size_rule(seq, head_dim, block):
    assert attention_kernel.block_size(seq, head_dim) == block


def test_cpu_calls_take_the_scan():
    """Off the TPU ``flash_attention`` never reaches the kernel."""
    q, k, v, _ = _inputs(1, 256, 4, 2, jnp.float32)
    hlo = jax.jit(lambda q, k, v: flash_attention(q, k, v)).lower(q, k, v).as_text()
    assert "pallas" not in hlo and "while" in hlo
