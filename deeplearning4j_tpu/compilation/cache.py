"""Persistent XLA compilation-cache wiring (tentpole layer 1).

Every process used to pay full cold XLA compilation on its first batch.
JAX's persistent compilation cache makes the *backend compile* of a
previously-seen program a disk read instead of an XLA invocation — across
process restarts, across jobs sharing the directory. (Layer 2, the AOT
executable store in `store.py`, additionally skips tracing/lowering; this
layer alone already removes the dominant cost.)

Where the cache lives is decided from outside the program:

- ``JAX_COMPILATION_CACHE_DIR`` set -> JAX reads it itself at import and
  this module sets no ``jax_compilation_cache_dir``; the AOT store goes
  beside JAX's entries, under ``<that dir>/aot``;
- unset -> the fixed ``<checkout>/.dl4j_compile_cache`` (gitignored):
  JAX's entries under ``xla/``, the AOT store under ``aot/``. The path is
  part of JAX's cache key, so it never moves with the user, the process or
  the time.

``DL4J_TPU_COMPILE_CACHE=off`` (or ``0``/``false``/``none``/empty)
disables both layers; it is an off switch, not a place.

Configuration happens once at package import (``deeplearning4j_tpu/
__init__.py``): the engines compile a flock of small helper programs
during ``net.init()`` — before any `_get_jit` — and a warm process should
replay those from disk too, not just the big training programs.
Concurrent processes are safe: jax writes cache entries via tmp-file +
atomic rename, and the AOT store does the same (`store.py`), so readers
never observe a half-written artifact.
"""

from __future__ import annotations

import os
import threading
import warnings
from typing import Optional

ENV_KNOB = "DL4J_TPU_COMPILE_CACHE"
JAX_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
_OFF_VALUES = {"", "0", "false", "off", "none", "disabled"}

# The cache's place when JAX_COMPILATION_CACHE_DIR does not name one:
# beside the package, at the root of the checkout. Listed in .gitignore.
LOCAL_DIRNAME = ".dl4j_compile_cache"

_lock = threading.Lock()
_configured = False
_configured_root: Optional[str] = None


def checkout_cache_dir() -> str:
    """``<checkout>/.dl4j_compile_cache``: fixed for a given checkout."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), LOCAL_DIRNAME)


def cache_root() -> Optional[str]:
    """The cache root (None = caching disabled). Reads the environment on
    every call so tests can re-point it; `configure_persistent_cache`
    latches the first answer for the jax side."""
    raw = os.environ.get(ENV_KNOB)
    if raw is not None:
        if raw.strip().lower() in _OFF_VALUES:
            return None
        raise ValueError(
            f"{ENV_KNOB}={raw!r}: this variable only switches the compile "
            f"cache off ({sorted(_OFF_VALUES - {''})}); to place the cache "
            f"set {JAX_ENV_DIR}")
    placed = os.environ.get(JAX_ENV_DIR)
    if placed:
        return os.path.abspath(placed)
    return checkout_cache_dir()


def configure_persistent_cache() -> Optional[str]:
    """Turn on jax's persistent compilation cache under `cache_root()`
    (idempotent; first call wins). Returns the active root, or None when
    caching is disabled or the directory is unusable.

    The size/time floors are dropped to "cache everything": the default
    min-compile-time floor (1s) would skip exactly the many small programs
    an engine run compiles (per-shape train steps, superstep tails), and
    entry dedup across processes is the whole point of the directory.
    """
    global _configured, _configured_root
    with _lock:
        if _configured:
            return _configured_root
        _configured = True
        root = cache_root()
        if root is None:
            return None
        try:
            os.makedirs(root, exist_ok=True)
        except OSError as e:
            warnings.warn(
                f"compile cache dir {root!r} cannot be created ({e}); the "
                f"AOT executable store is disabled for this process")
            return None
        import jax

        if not os.environ.get(JAX_ENV_DIR):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(root, "xla"))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        _configured_root = root
        return root


def reset_for_tests() -> None:
    """Drop the latched configuration (and jax's in-memory cache handle) so
    a test can re-point the cache at a fresh tmpdir."""
    global _configured, _configured_root
    with _lock:
        _configured, _configured_root = False, None
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
