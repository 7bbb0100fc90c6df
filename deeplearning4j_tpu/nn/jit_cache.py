"""The engine's jit-program cache.

`Engine._get_jit` (`nn/engine.py`) delegates the cache-key construction and
lookup here, where the compile-cache store (`compilation/`) hooks in.

The cache key is ``(kind, sorted static args, context_cache_key(),
kernels.config_key())``: the active `ParallelContext` selects which
program a layer traces (ring vs flash attention, expert-sharded vs local
MoE), and the kernel-registry env config selects which implementation
each dispatch seam resolves (Pallas vs XLA fallback, `kernels/
registry.py`), so both are part of the program identity — the same net
can train sharded and unsharded, or fused and fallback, in one process
without stale programs. Folding the kernel config in HERE is also the
"hoist to signature level" fix: a restacked superstep block with an
already-seen signature is a cache hit, so kernel resolution (and its
`is_available` probes) never re-runs per block. Superstep `k`/`scan` arrive through
`static`, so each distinct block length is its own cached program (the
StepProfiler's jit-cache-growth heuristic relies on that to classify a
tail block's first call as compile).

When the compile cache is enabled (on unless
`DL4J_TPU_COMPILE_CACHE=off`) each freshly built program is wrapped in a
`compilation.CachedProgram`, which consults the fingerprinted AOT
executable store before the first trace and writes back on miss; when
disabled, the raw jitted callable is cached — byte-for-byte the old
behavior.
"""

from __future__ import annotations

from deeplearning4j_tpu import compilation as _compilation
from deeplearning4j_tpu.kernels import registry as _kernels_registry
from deeplearning4j_tpu.parallel.context import context_cache_key


def get_jit(net, hit_metric, miss_metric, kind: str, **static):
    """Cached program lookup for one engine instance (see module
    docstring). `hit_metric`/`miss_metric` are the engine's labeled
    jit-cache counters."""
    key = (kind, tuple(sorted(static.items())), context_cache_key(),
           _kernels_registry.config_key())
    fn = net._jit_cache.get(key)
    if fn is not None:
        hit_metric.inc()
        return fn
    miss_metric.inc()
    fn = _compilation.wrap_program(net._build_jit(kind, **static),
                                   net, kind, static)
    net._jit_cache[key] = fn
    return fn
