"""Persistent compile cache + AOT warmup: kill cold-start XLA compilation.

Three layers (PERF.md §14):

1. `cache.py`    — turns on jax's persistent compilation cache where
                   ``JAX_COMPILATION_CACHE_DIR`` says, else at the fixed
                   ``<checkout>/.dl4j_compile_cache``
                   (``DL4J_TPU_COMPILE_CACHE=off`` opts out): the backend
                   compile of a seen program becomes a disk read.
2. `store.py` /
   `program.py`  — the framework-level AOT executable store: whole
                   compiled executables serialized under a fingerprint of
                   (model config, batch signature, jit kind/static, mesh
                   context, versions, backend); a hit skips tracing and
                   lowering entirely. Hooks into both engines through
                   `nn/jit_cache.py`.
3. `warmup.py`   — `net.warmup()` / `ParallelWrapper.warmup()` /
                   `InferenceServer(warmup=True)` / the
                   ``python -m deeplearning4j_tpu.compilation.warmup`` CLI:
                   pre-compile expected programs before traffic.

Observability: `dl4j_compile_cache_hits_total` /
`dl4j_compile_cache_misses_total` and the `dl4j_compile_seconds`
histogram, all labeled ``source=trace|persistent|aot``.
"""

from deeplearning4j_tpu.compilation.cache import (
    ENV_KNOB, cache_root, checkout_cache_dir, configure_persistent_cache)
from deeplearning4j_tpu.compilation.program import (
    CachedProgram, get_store, wrap_program)
from deeplearning4j_tpu.compilation.store import (
    AOTStore, build_fingerprint_doc, fingerprint, tree_signature)
from deeplearning4j_tpu.compilation.warmup import (
    infer_feature_shape, synthetic_dataset, warmup_net)

__all__ = [
    "ENV_KNOB", "cache_root", "checkout_cache_dir",
    "configure_persistent_cache", "CachedProgram", "get_store", "wrap_program",
    "AOTStore", "build_fingerprint_doc", "fingerprint", "tree_signature",
    "infer_feature_shape", "synthetic_dataset", "warmup_net", "reset",
]


def reset() -> None:
    """Test hook: drop the latched cache configuration, the store
    singleton, and jax's in-memory persistent-cache handle so the next use
    re-reads the environment."""
    from deeplearning4j_tpu.compilation import program as _program

    _program.reset_for_tests()
