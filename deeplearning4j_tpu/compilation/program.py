"""CachedProgram: the store-aware wrapper the engines' jit cache holds.

`nn/jit_cache.py` wraps every program built by `_build_jit` in a
`CachedProgram` (when the compile cache is enabled). The wrapper keys each
call on the ABSTRACT signature of its arguments — shapes/dtypes/structure/
shardings, the same identity jit itself dispatches on — and on the first
call of each signature:

1. fingerprints (model config, signature, kind/static, mesh context,
   versions — `store.build_fingerprint_doc`) and consults the AOT store;
2. on a hit, uses the deserialized executable: no trace, no lowering, no
   XLA — the cold-start cost is one disk read;
3. on a miss, compiles via ``fn.lower(*args).compile()`` (same cost as the
   jit call would have paid), writes the artifact back, and uses the
   compiled executable from then on.

Only the store may degrade: an artifact that cannot be read is a miss and
one that cannot be written stays in memory, each with a warning. The
compile itself never does — what the compiler refuses (a Pallas kernel the
chip rejects, a program that does not fit) raises out of the first call and
out of `warm`, so a warmed server is a server whose programs compiled.
`warm(*args)` does step 1-3 *without executing* the program —
donation-safe pre-compilation for the warmup API. `lower(*args)` delegates
to the underlying jit fn (the profiler's cost-analysis probe relies on
it).
"""

from __future__ import annotations

import threading
import time
import warnings
from typing import Any, Dict, Optional, Tuple

from deeplearning4j_tpu.compilation import cache as _cache
from deeplearning4j_tpu.compilation import store as _store
from deeplearning4j_tpu.observability import memory as _obsmem

_store_lock = threading.Lock()
_store_singleton: Optional[_store.AOTStore] = None
_store_root: Optional[str] = None

# Persistent-cache hits seen on this thread (jax fires the event inside
# `compile()`, on the compiling thread). An executable that jax loaded from
# its persistent cache must not be written to the AOT store: under jaxlib
# 0.9.0 the CPU backend serializes a deserialized executable without its
# compiled functions, and the artifact then loads but dies at its first
# call ("Function ... not found").
_persistent_hits = threading.local()
_hit_listener_installed = False


def _on_jax_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _persistent_hits.n = getattr(_persistent_hits, "n", 0) + 1


def get_store() -> Optional[_store.AOTStore]:
    """Process-wide `AOTStore` under the configured cache root (configures
    the persistent XLA cache as a side effect of first use). None when
    caching is disabled."""
    global _store_singleton, _store_root, _hit_listener_installed
    root = _cache.configure_persistent_cache()
    if root is None:
        return None
    with _store_lock:
        if not _hit_listener_installed:
            from jax import monitoring

            monitoring.register_event_listener(_on_jax_event)
            _hit_listener_installed = True
        if _store_singleton is None or _store_root != root:
            _store_singleton = _store.AOTStore(root)
            _store_root = root
        return _store_singleton


def reset_for_tests() -> None:
    global _store_singleton, _store_root
    with _store_lock:
        _store_singleton, _store_root = None, None
    _cache.reset_for_tests()


def wrap_program(fn, net, kind: str, static: Dict[str, Any]):
    """Wrap a freshly built jit program for the executable store; returns
    `fn` unchanged when the compile cache is disabled (zero overhead)."""
    if _cache.configure_persistent_cache() is None:
        return fn
    return CachedProgram(fn, net, kind, static)


class CachedProgram:
    """See module docstring. One instance per engine jit-cache entry, so
    the (kind, static, context) identity is fixed; per-call identity is the
    argument signature."""

    def __init__(self, fn, net, kind: str, static: Dict[str, Any]):
        self._fn = fn
        self._net = net
        self.kind = kind
        self.static = dict(static)
        self._entries: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()
        self._fallback_warned = False

    # ------------------------------------------------------------ identity

    def _signature(self, args) -> Tuple:
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(args)
        descs = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            if shape is None:
                descs.append((type(leaf).__name__,))
                continue
            descs.append((
                tuple(shape), str(getattr(leaf, "dtype", "?")),
                bool(getattr(leaf, "weak_type", False)),
                getattr(leaf, "sharding", None),
            ))
        return (treedef, tuple(descs))

    # ------------------------------------------------------------ dispatch

    def __call__(self, *args):
        return self._entry_for(args)(*args)

    def _entry_for(self, args):
        sig = self._signature(args)
        entry = self._entries.get(sig)
        if entry is not None:
            return entry
        with self._lock:
            entry = self._entries.get(sig)
            if entry is None:
                entry, _ = self._acquire(args)
                self._entries[sig] = entry
            return entry

    def _acquire(self, args):
        """`(callable, origin)` for one argument signature; origin is
        'aot' (store hit), 'compiled' (live compile + write-back) or 'jit'
        (no store: the program traces on its first call)."""
        store = get_store()
        if store is None:
            return self._fn, "jit"
        try:
            doc = _store.build_fingerprint_doc(self._net, self.kind,
                                               self.static, args)
            fp = _store.fingerprint(doc)
        except Exception as e:
            self._warn_fallback("fingerprinting failed", e)
            return self._fn, "jit"
        loaded = store.load(fp)
        if loaded is not None:
            _store._M_HITS_AOT.inc()
            self._record_memory(loaded)
            return loaded, "aot"
        _store._M_MISSES_AOT.inc()
        hits0 = getattr(_persistent_hits, "n", 0)
        t0 = time.perf_counter()
        # A compile error propagates: the plain jit path would only compile
        # the same program again, and fail again at the first request.
        compiled = self._fn.lower(*args).compile()
        # dl4j_compile_seconds{source=trace|persistent} for the backend
        # part is observed by the jax.monitoring hook; this histogram
        # entry is intentionally NOT duplicated here.
        dt = time.perf_counter() - t0
        if getattr(_persistent_hits, "n", 0) == hits0:
            store.save(fp, compiled, dict(doc, compile_seconds=dt))
        self._record_memory(compiled)
        return compiled, "compiled"

    def _record_memory(self, compiled) -> None:
        """Static HBM accounting: every executable that materializes here
        (AOT hit or live compile) reports its memory_analysis() into
        `dl4j_program_hbm_bytes{program,kind}`. Never raises."""
        _obsmem.record_program_memory(
            _obsmem.program_label(self.kind, self.static), compiled,
            net=self._net)

    def _warn_fallback(self, what: str, e: Exception) -> None:
        if not self._fallback_warned:
            self._fallback_warned = True
            warnings.warn(
                f"{what} for program {self.kind!r} "
                f"({type(e).__name__}: {e}); using the plain jit path for "
                f"this program")

    # ------------------------------------------------------------- warmup

    def warm(self, *args) -> str:
        """Ensure an executable exists for this argument signature WITHOUT
        running it (safe with donated buffers). Returns where it came
        from: 'ready' (already warm), 'aot' (store hit), 'compiled'
        (live compile + write-back), or 'jit' (store unavailable — the
        program will trace on first call). Raises what the compiler
        raises."""
        sig = self._signature(args)
        with self._lock:
            if sig in self._entries:
                return "ready"
            self._entries[sig], origin = self._acquire(args)
            return origin

    # ----------------------------------------------------------- plumbing

    def executables(self):
        """The executable held for each argument signature seen so far
        (`jax.stages.Compiled`; the plain jit callable where a fingerprint
        failed) — for inspection: `as_text()`, `memory_analysis()`."""
        with self._lock:
            return list(self._entries.values())

    def lower(self, *args, **kwargs):
        """Delegate to the underlying jit fn (cost-analysis probes)."""
        return self._fn.lower(*args, **kwargs)

    def __repr__(self) -> str:
        return (f"CachedProgram({self.kind!r}, static={self.static}, "
                f"entries={len(self._entries)})")
