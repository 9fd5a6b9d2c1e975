"""Process bootstrap: where XLA's persistent compilation cache lives,
and the virtual CPU mesh the tests run on.

JAX honours ``JAX_PLATFORMS`` itself, so platform choice needs no code
here. Deliberately NOT an import side effect of a library module: entry
points (server, CLI, ``chip_smoke.py``) call ``bootstrap()``
before first backend use.
"""

from __future__ import annotations

import os
import sys

# Fixed path inside the checkout: the path is part of a cache entry's
# key, so a directory that moves (home, a temp name, a pid) never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# JAX caches only programs that took at least this long to compile, 1 s
# by default. Most programs here compile faster, and a floor near their
# compile time makes a second run add entries the first one timed under
# it (my chip run, PR 21: 0.2 s let a second run add 5 entries to 14), so
# every program is cached.
_MIN_COMPILE_SECS_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
_MIN_COMPILE_SECS = "0"


def bootstrap() -> str:
    """The one call every entry point makes before first backend use.
    Returns the compilation cache directory."""
    return enable_compilation_cache()


def force_cpu_mesh(n_devices: int = 8) -> None:
    """Give this process ``n_devices`` virtual CPU devices, whether or
    not ``jax`` was imported first. Must run before the first backend
    initialisation (XLA reads XLA_FLAGS once)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    jax = sys.modules.get("jax")
    if jax is not None:
        # an imported jax has already read JAX_PLATFORMS
        jax.config.update("jax_platforms", "cpu")


def enable_compilation_cache() -> str:
    """Persist XLA compilations across processes; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX uses it and this sets
    no directory. Otherwise the cache is ``DEFAULT_CACHE_DIR``, exported
    through the same variable so a later ``import jax`` and every child
    process resolve the same directory. ``JAX_ENABLE_COMPILATION_CACHE=0``
    turns the cache off (JAX's own switch). A directory that cannot be
    created raises: a cache that cannot be set up is a start-up error.
    """
    d = os.environ.get(_CACHE_DIR_ENV)
    chosen_here = not d
    if chosen_here:
        d = DEFAULT_CACHE_DIR
        os.makedirs(d, exist_ok=True)
        os.environ[_CACHE_DIR_ENV] = d
    floor_here = _MIN_COMPILE_SECS_ENV not in os.environ
    if floor_here:
        os.environ[_MIN_COMPILE_SECS_ENV] = _MIN_COMPILE_SECS
    jax = sys.modules.get("jax")
    if jax is not None:
        # an imported jax has already read its environment
        if chosen_here:
            jax.config.update("jax_compilation_cache_dir", d)
        if floor_here:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                float(_MIN_COMPILE_SECS),
            )
    return d
