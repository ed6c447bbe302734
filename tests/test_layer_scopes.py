"""The train step names its layers: the compiled block and full MuonBP
steps and the AdamW step of a tiny phi4-mini-family model carry each
layer scope in their ``op_name`` metadata, where a profiler trace reads
it (``bench/layer_scopes.py``)."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_config
from repro.kernels import attention as attention_kernel
from repro.launch import compile_cache, train

SCOPES = ("model.embed", "model.attn", "model.mlp", "model.head", "opt")
BATCH, SEQ = 2, 64


@pytest.fixture
def no_persistent_cache():
    """JAX's persistent cache key leaves out ``op_name`` metadata, so a
    cached executable of the same step compiled without the scopes would
    come back with its own names: compile afresh."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def tiny_arch():
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b"), name="tiny-scopes",
                              num_layers=2, vocab_size=512, d_model=128, num_heads=4,
                              num_kv_heads=2, head_dim=32, d_ff=256)
    ARCHS[cfg.name] = cfg
    yield cfg.name
    del ARCHS[cfg.name]


def _op_names(arch, optimizer, phase):
    res = train.main(["--arch", arch, "--optimizer", optimizer, "--period", "2",
                      "--steps", "0", "--batch", str(BATCH), "--seq", str(SEQ),
                      "--schedule", "const", "--drift-threshold", "0",
                      "--mesh", "data=1,model=1", "--ns-backend", "jnp"])
    batch = {"tokens": jnp.zeros((BATCH, SEQ), jnp.int32),
             "labels": jnp.zeros((BATCH, SEQ), jnp.int32)}
    hlo = res.step_fns[phase].lower(res.state, batch).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.mark.parametrize("optimizer,phase", [("muonbp", "block"), ("muonbp", "full"),
                                             ("adamw", "block")])
def test_compiled_step_names_every_layer(tiny_arch, optimizer, phase, no_persistent_cache,
                                        monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    names = _op_names(tiny_arch, optimizer, phase)
    for scope in SCOPES:
        rx = re.compile(rf"(^|/|\(){re.escape(scope)}(\)|/|$)")
        assert any(rx.search(n) for n in names), scope
    # Both passes of each sublayer: the forward scan and the backward.
    for scope in ("model.attn", "model.mlp"):
        assert any(f"closed_call/{scope}/" in n and "transpose" not in n for n in names)
        assert any(f"checkpoint/{scope}/" in n for n in names)
    if optimizer == "muonbp":
        # The comm engine's stage scopes nest inside the optimizer's.
        assert any(re.search(rf"/opt/muonbp\.{phase}(\.s\d+)?\.ns/", n) for n in names)


def test_attention_kernel_ops_carry_the_attention_scope(no_persistent_cache, monkeypatch,
                                                        tmp_path):
    """With the Pallas flash kernel on the path (steered here: interpret
    mode, as the CPU has no TPU), its forward, recomputed forward and
    fused backward kernel all run under ``model.attn``."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    use_kernel = attention_kernel.use_kernel
    monkeypatch.setattr(attention_kernel, "use_kernel",
                        lambda *a, **kw: use_kernel(*a, **{**kw, "backend": "tpu"}))
    monkeypatch.setattr(attention_kernel, "causal_attention",
                        functools.partial(attention_kernel.causal_attention, interpret=True))
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b"), name="tiny-attn-kernel",
                              num_layers=2, vocab_size=512, d_model=256, num_heads=2,
                              num_kv_heads=1, head_dim=128, d_ff=256)
    ARCHS[cfg.name] = cfg
    try:
        res = train.main(["--arch", cfg.name, "--optimizer", "adamw", "--steps", "0",
                          "--batch", "2", "--seq", "128", "--schedule", "const",
                          "--drift-threshold", "0", "--mesh", "data=1,model=1"])
    finally:
        del ARCHS[cfg.name]
    batch = {"tokens": jnp.zeros((2, 128), jnp.int32),
             "labels": jnp.zeros((2, 128), jnp.int32)}
    hlo = res.step_fns["block"].lower(res.state, batch).compile().as_text()
    kernel = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
              if re.search(r"/splash_mha_\w+/", n)]
    attn = re.compile(r"(^|/|\()model\.attn(\)|/|$)")
    assert kernel and all(attn.search(n) for n in kernel)
    for part in ("/model.attn/", "rematted_computation/model.attn/"):
        assert any(part in n and "splash_mha_fwd" in n for n in kernel), part
    assert any("splash_mha_dkv" in n for n in kernel)
