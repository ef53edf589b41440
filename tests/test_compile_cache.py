"""Where the persistent compile cache lives (ops.compile_cache_dir): the
directory JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache —
for this process and for every child the driver spawns."""

import os
from contextlib import contextmanager

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache as cc

from corda_tpu import ops
from corda_tpu.testing.driver import _node_env

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextmanager
def _cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_include_full_tracebacks_in_locations")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _compile_something() -> None:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    jax.jit(lambda x: x * 7 - 3)(jnp.arange(11)).block_until_ready()


def test_env_cache_dir_wins_here_and_in_children(monkeypatch, tmp_path):
    where = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(where))
    assert ops.compile_cache_dir() == str(where)
    assert ops.compile_cache_dir(cpu=False) == str(where)
    assert _node_env("accelerator")["JAX_COMPILATION_CACHE_DIR"] == str(where)
    assert _node_env("cpu")["JAX_COMPILATION_CACHE_DIR"] == str(where)
    with _cache_config():
        ops.enable_persistent_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(where)
        _compile_something()
        assert any(where.iterdir())  # written there, and only there


def test_default_cache_dir_is_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.join(CHECKOUT, ".jax_cache")
    assert ops.CHECKOUT_ROOT == CHECKOUT
    assert ops.compile_cache_dir(cpu=False) == root
    # An accelerator child gets the device directory even from a parent
    # pinned to the CPU.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert _node_env("accelerator")["JAX_COMPILATION_CACHE_DIR"] == root
    with _cache_config():
        ops.enable_persistent_compile_cache()
        assert jax.config.jax_compilation_cache_dir.startswith(root)


def test_cpu_entries_are_partitioned_by_host_signature(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    sig = ops.host_cpu_signature()
    assert len(sig) == 8 and sig == ops.host_cpu_signature()
    int(sig, 16)  # hex
    assert ops.compile_cache_dir() == os.path.join(
        CHECKOUT, ".jax_cache", f"cpu-{sig}")
    assert _node_env("cpu").get("JAX_PLATFORMS") == "cpu"
