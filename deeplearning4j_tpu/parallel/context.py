"""Active parallelism context: how a DSL-built model reaches the mesh.

The reference's only parallelism is data-parallel parameter averaging wired
through wrapper objects (`parallelism/ParallelWrapper.java:322`); its config
DSL never needs to know about devices. On TPU the interesting axes —
sequence/context (`parallel/sequence.py`), expert (`parallel/expert.py`),
tensor (`parallel/mesh.py`) — change how a LAYER's forward is computed, so
layer implementations need to see the mesh at trace time. This module is
that bridge: a process-wide `ParallelContext` naming the mesh and the role
of each axis. Engines/wrappers install it (e.g. `ParallelWrapper(...,
seq_axis="seq")`) around their jitted-step tracing; layer impls
(`nn/layers/attention.py`, `nn/layers/moe.py`) consult it and pick the
sharded collective path when the relevant axis exists. The context is
read at TRACE time only (it selects which program to build — never a
traced value), so each engine folds `cache_key()` into its jit-cache key:
the same net can train sharded and unsharded in one process without stale
programs.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from typing import Optional

from jax.sharding import Mesh


@dataclass(frozen=True)
class ParallelContext:
    """Names the mesh axes by role. Any axis may be absent (None)."""

    mesh: Mesh
    data_axis: Optional[str] = "data"
    model_axis: Optional[str] = None
    seq_axis: Optional[str] = None
    expert_axis: Optional[str] = None
    pipe_axis: Optional[str] = None

    def __post_init__(self):
        for role in ("data_axis", "model_axis", "seq_axis", "expert_axis",
                     "pipe_axis"):
            name = getattr(self, role)
            if name is not None and name not in self.mesh.shape:
                raise ValueError(
                    f"{role}={name!r} is not an axis of the mesh "
                    f"(axes: {tuple(self.mesh.shape)})")

    def axis_size(self, role: str) -> int:
        """Mesh size of the axis filling `role` ('seq', 'expert', ...); 1 if
        the role is unassigned."""
        name = getattr(self, role + "_axis")
        return int(self.mesh.shape[name]) if name is not None else 1

    def cache_key(self):
        """Hashable description for engine jit-cache keys. The Mesh object
        itself is part of the key (it hashes by device identity), so two
        same-topology meshes over DIFFERENT devices never share a traced
        program whose sharding constraints are bound to the wrong devices."""
        return (
            self.mesh,
            self.data_axis, self.model_axis, self.seq_axis,
            self.expert_axis, self.pipe_axis,
        )


_state = threading.local()


def current_context() -> Optional[ParallelContext]:
    return getattr(_state, "ctx", None)


def context_if_any(ctx: Optional[ParallelContext]):
    """`parallel_context(ctx)` for a sharded owner, a no-op for an unsharded
    one (`ctx` None) — which leaves an externally installed context alone.
    What every dispatcher that may own a mesh wraps its traces in (decode
    steppers, the serving batcher)."""
    return parallel_context(ctx) if ctx is not None else nullcontext()


@contextmanager
def parallel_context(ctx: Optional[ParallelContext]):
    """Install `ctx` as the active parallelism context for the block."""
    prev = current_context()
    _state.ctx = ctx
    try:
        yield ctx
    finally:
        _state.ctx = prev


def context_cache_key():
    """The active context's cache key (None when no context is active) —
    engines mix this into their jit-cache keys."""
    ctx = current_context()
    return None if ctx is None else ctx.cache_key()
