"""Persistent XLA compilation cache, placed from outside the program.

A cold start compiles the scanned train step, the eval/predict programs and
the serving engine's whole bucket ladder; a machine that is thrown away after
each run pays that every time unless the compiled programs outlive the
process. Where the cache lives is the deployment's decision:

  * ``JAX_COMPILATION_CACHE_DIR`` set  -> JAX reads it itself; this module
    sets NO directory in code (an orchestrator that mounts a cache volume
    must not be overridden by the program it runs).
  * unset -> ``<checkout>/.jax_cache``, derived from this package's own path.
    Fixed on purpose: a directory that moves between runs (tempfile, pid,
    timestamp) never hits.

Either way the write threshold drops to zero: JAX only persists programs that
took >= 1 s to compile by default, which would skip every sub-second serving
bucket program — exactly the ones a warm start wants.
"""

from __future__ import annotations

import os

from ..obs import startup

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_dir() -> str:
    """The in-checkout cache path used when the environment names none."""
    return os.path.join(_CHECKOUT, ".jax_cache")


def configure() -> str:
    """Point JAX at the persistent cache; returns the directory in effect.

    Must run before the process's first compile: JAX decides once, at the
    first compilation, whether a cache is in use. From here on JAX's own
    compile timings are ``compile.*`` spans (``obs.startup``): whether a
    program was compiled or fetched is read there."""
    with startup.importing("jax"):
        import jax  # noqa: PLC0415 (entry points call this before touching jax)

    startup.listen_to_jax()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get(ENV_VAR, "")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", default_dir())
    return default_dir()


def entry_count(directory: str) -> int:
    """Cached programs under ``directory`` (0 when it does not exist yet)."""
    if not os.path.isdir(directory):
        return 0
    return sum(1 for name in os.listdir(directory) if name.endswith("-cache"))
