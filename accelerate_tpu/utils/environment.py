"""Environment-variable parsing and patching helpers.

TPU-native re-design of the reference's ``utils/environment.py``
(/root/reference/src/accelerate/utils/environment.py:59-92 for parsers,
:382-452 for the patch/clear context managers). GPU/NUMA introspection from
the reference is replaced by TPU/JAX device introspection.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Any

# the checkout: what the program builds or caches at run time lives under it
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_TRUE = {"1", "true", "yes", "on", "y", "t"}
_FALSE = {"0", "false", "no", "off", "n", "f", ""}


def default_compile_cache_dir() -> str:
    """Where the persistent JAX compile cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache`` at the root of
    this checkout.

    The path is part of every cache key, so it has to be the same in every
    process and every run: no home directory, no temp name, no uid, pid or
    time. A machine that is built fresh for each call keeps nothing outside
    the checkout anyway, and a caller that wants the cache elsewhere (a disk
    that survives, a directory other users cannot write: entries are
    deserialized into compiled executables) names it in the variable."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO_ROOT, ".jax_cache"
    )


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on at :func:`default_compile_cache_dir`
    — the one place in the repository that sets the path. Returns it."""
    import jax

    path = default_compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def str_to_bool(value: str) -> int:
    """Convert a string to 1/0 (raises on unrecognized), mirroring
    reference utils/environment.py:59-74."""
    value = value.lower().strip()
    if value in _TRUE:
        return 1
    if value in _FALSE:
        return 0
    raise ValueError(f"invalid truth value {value!r}")


def get_int_from_env(env_keys, default: int) -> int:
    """First set env var among ``env_keys`` parsed as int, else default."""
    for k in env_keys:
        val = os.environ.get(k, None)
        if val is not None and val != "":
            return int(val)
    return default


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key, None)
    if value is None:
        return default
    return bool(str_to_bool(value))


def parse_choice_from_env(key: str, default: str = "no") -> str:
    return os.environ.get(key, default)


def are_libraries_initialized(*library_names: str) -> list[str]:
    """Return the sublist of libraries already imported in this process."""
    import sys

    return [name for name in library_names if name in sys.modules]


@contextmanager
def clear_environment():
    """Temporarily wipe os.environ (reference utils/environment.py:382-415)."""
    backup = os.environ.copy()
    os.environ.clear()
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(backup)


@contextmanager
def patch_environment(**kwargs: Any):
    """Temporarily set env vars (upper-cased keys); restores previous values
    on exit. Mirrors reference utils/environment.py:417-451."""
    existing = {}
    for key, value in kwargs.items():
        key = key.upper()
        if key in os.environ:
            existing[key] = os.environ[key]
        os.environ[key] = str(value)
    try:
        yield
    finally:
        for key in kwargs:
            key = key.upper()
            if key in existing:
                os.environ[key] = existing[key]
            else:
                os.environ.pop(key, None)


def purge_accelerate_environment(func):
    """Test decorator: run ``func`` with all ACCELERATE_*/MITA_* env vars
    removed, restoring them afterwards (reference utils/environment.py:453+)."""
    import functools

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        backup = os.environ.copy()
        for key in list(os.environ):
            if key.startswith(("ACCELERATE_", "MITA_", "FSDP_", "PARALLELISM_CONFIG_")):
                del os.environ[key]
        try:
            return func(*args, **kwargs)
        finally:
            os.environ.clear()
            os.environ.update(backup)

    return wrapper
