"""The training launcher as an in-process entry point, and its compile cache."""

import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.launch import compile_cache, train


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_fixed_inside_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    repo = Path(__file__).resolve().parents[1]
    assert compile_cache.CHECKOUT_CACHE_DIR == repo / ".jax_cache"


def test_cache_dir_env_var_left_to_jax(monkeypatch, tmp_path, cache_dir_restored):
    """With the variable set, the code sets nothing: JAX reads it itself."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_main_returns_per_step_result(monkeypatch, tmp_path, cache_dir_restored):
    """``main(argv)`` runs in-process and returns per-step losses, phases,
    wall and compile seconds; each phase compiles once — the initial state
    sits where every later step's state sits, so no phase compiles twice."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    run = train.main([
        "--arch", "muonbp-960m", "--reduced", "--steps", "4", "--batch", "2",
        "--seq", "32", "--optimizer", "muonbp", "--period", "2",
        "--obs-block", "--log-every", "10", "--drift-threshold", "0",
    ])
    assert run.status == "ok"
    assert run.phases == ["full", "block", "full", "block"]
    assert len(run.losses) == len(run.step_s) == len(run.compile_s) == 4
    assert all(math.isfinite(v) for v in run.losses)
    assert run.compile_s[0] > 0 and run.compile_s[1] > 0
    assert run.compile_s[2:] == [0.0, 0.0]
    assert all(c <= s for c, s in zip(run.compile_s, run.step_s))
    dev = jax.devices()[0]
    assert run.device == {"platform": dev.platform, "kind": dev.device_kind,
                          "count": len(jax.devices())}
    assert set(run.step_fns) >= {"block", "full"}


@pytest.fixture
def fresh_cache(tmp_path, cache_dir_restored):
    """A persistent cache of its own in ``tmp_path`` that keeps every
    executable; the process's cache settings come back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
    cc.reset_cache()


def test_compile_clock_counts_a_cache_load_once(monkeypatch, fresh_cache):
    """A persistent-cache hit is one compile event: the clock never counts
    more compile time than the call that loaded it took."""
    from jax._src import compiler

    def f(x):
        return jnp.sin(x) @ x.T

    x = jnp.ones((64, 64))
    jax.jit(f)(x).block_until_ready()  # compiled and written to the cache
    read = compiler._cache_read

    def slow_read(*args, **kwargs):
        time.sleep(0.5)
        return read(*args, **kwargs)

    hits = []

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    monkeypatch.setattr(compiler, "_cache_read", slow_read)
    jax.monitoring.register_event_listener(on_event)
    jax.clear_caches()
    clock = compile_cache.CompileClock().start()
    try:
        t0 = time.perf_counter()
        jax.jit(f)(x).block_until_ready()
        wall = time.perf_counter() - t0
    finally:
        clock.stop()
        jax.monitoring.unregister_event_listener(on_event)
    assert hits
    assert 0.5 <= clock.seconds <= wall


_ZERO1_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
from repro.configs import get_config
from repro.launch import train
from repro.sharding import specs as sh

run = train.main([
    "--arch", "muonbp-960m", "--reduced", "--steps", "4", "--batch", "2",
    "--seq", "32", "--optimizer", "muonbp", "--period", "2",
    "--mesh", "data=2,model=2", "--zero1", "--log-every", "10",
    "--drift-threshold", "0",
])
params = run.state.params
mesh = jax.tree.leaves(params)[0].sharding.mesh
want = sh.param_specs(params, get_config("muonbp-960m").reduced(), mesh)
print(json.dumps({
    "compile_s": run.compile_s,
    "specs_kept": all(
        p.sharding.is_equivalent_to(s, p.ndim)
        for p, s in zip(jax.tree.leaves(params),
                        jax.tree.leaves(sh.named(mesh, want)))),
}))
"""


def test_zero1_params_keep_layout_across_steps(tmp_path):
    """On a data=2,model=2 ZeRO-1 mesh the update is computed on data
    shards; the new params still leave each step in their own layout, so
    every phase compiles once and the forward never gathers them."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    proc = subprocess.run([sys.executable, "-c", _ZERO1_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["specs_kept"]
    assert out["compile_s"][0] > 0 and out["compile_s"][1] > 0
    assert out["compile_s"][2:] == [0.0, 0.0]
