"""Compile cache placement (utils/compile_cache.py): the environment decides
where it lives; the program only fills in a fixed in-checkout default."""

import os
import subprocess
import sys

import jax

from deepfm_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import jax, jax.numpy as jnp
{extra_import}
from deepfm_tpu.utils import compile_cache
print(compile_cache.configure())
print(jax.config.jax_compilation_cache_dir)
print(jax._src.xla_bridge.backends_are_initialized())
jax.jit(lambda x: jnp.tanh(x) + 1)(jnp.ones((8, 8))).block_until_ready()
"""


def _child(cwd, extra_import="", **env):
    base = {k: v for k, v in os.environ.items()
            if k != compile_cache.ENV_VAR}
    p = subprocess.run(
        [sys.executable, "-c", _CHILD.format(extra_import=extra_import)],
        cwd=cwd, capture_output=True,
        text=True, timeout=120,
        env=dict(base, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO, **env))
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout.strip().splitlines()


def _entries(directory):
    return ({n for n in os.listdir(directory) if n.endswith("-cache")}
            if os.path.isdir(directory) else set())


def test_env_set_names_the_directory_and_code_sets_none(tmp_path):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself: configure() reports it and
    leaves the config value to JAX. The sub-second program the child compiles
    must land there (the 1 s default write threshold would skip it)."""
    cache = tmp_path / "cache"
    default_before = _entries(compile_cache.default_dir())
    reported, config_value, _ = _child(
        str(tmp_path), **{compile_cache.ENV_VAR: str(cache)})
    assert reported == config_value == str(cache)
    assert compile_cache.entry_count(str(cache)) >= 1
    # (by name, not by count: other test workers compile into the default
    # directory meanwhile, and none of them compiles the child's program)
    new_in_default = _entries(compile_cache.default_dir()) - default_before
    assert not new_in_default & _entries(str(cache))


def test_env_unset_uses_the_fixed_in_checkout_path(tmp_path):
    """Unset: <checkout>/.jax_cache, derived from the package path — the
    same from a process started in another directory as from this one."""
    want = os.path.join(_REPO, ".jax_cache")
    assert compile_cache.default_dir() == want
    reported, config_value, backend_started = _child(
        str(tmp_path), extra_import="import deepfm_tpu.launch")
    assert reported == config_value == want
    # Importing the launcher and placing the cache is everything that runs
    # before bootstrap: none of it may start the XLA backend, or
    # jax.distributed.initialize() refuses and no multi-process job starts
    # (a module-level jnp scalar did exactly that).
    assert backend_started == "False"


def test_in_process_env_set_leaves_config_alone(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    try:
        assert compile_cache.configure() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          threshold)
