"""Superstep program-body helpers of the engine (`nn/engine.py`).

The fused K-iteration train program can iterate two ways — same math, same
RNG/clock threading, ONE device dispatch either way:

- `lax.scan` (the default): trace/compile time O(1) in K; the body lowers
  once, exactly like the per-batch program, so the result is bit-for-bit
  identical to K sequential per-batch steps on every backend.
- unrolled (`DL4J_TPU_SUPERSTEP_SCAN=0`): a CPU perf escape hatch. XLA:CPU
  cannot route convolutions inside a `while` loop (what scan lowers to)
  through its optimized Eigen kernels — a conv body inside scan runs ~13x
  slower than the same body at top level (measured: 132 ms vs 10 ms per
  iteration for LeNet's first conv, single-core CPU; TPU is unaffected).
  Unrolling restores the fast kernels at O(K) trace time — but XLA then
  optimizes ACROSS iterations (fusion/reassociation), so results are
  float-close, not bit-identical, to the per-batch loop. Hence opt-in.

The choice is a STATIC part of the program (it changes the lowered HLO), so
the engine passes it into the `_get_jit` cache key alongside `k` — and
alongside `kernel_config()`, the kernel-registry selection under which the
superstep body (LSTM cells, norm+act, the fused optimizer update carried
through `(params, state, opt_state, clock)`) traces its dispatch seams.
Resolution is hoisted to SIGNATURE level: a restacked block with an
already-seen `(k, scan, kernels, shapes)` identity is a jit-cache hit, so
`kernels.registry` never re-runs its `is_available` probes per block
(`registry.probe_count()` holds the line in tests/test_kernels.py).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp


def kernel_config():
    """The kernel-registry selection this superstep program traces under
    — passed by the engine as a `_get_jit` static so the fused-vs-
    fallback choice is explicit program identity (also folded in globally
    by `nn/jit_cache.py`; here it additionally lands in the AOT
    fingerprint's `static` list and the StepProfiler's program key)."""
    from deeplearning4j_tpu.kernels import registry

    return registry.config_key()


def use_scan() -> bool:
    """Loop shape for the superstep program: scan unless
    `DL4J_TPU_SUPERSTEP_SCAN=0` opts into the unrolled shape (CPU conv
    speed over bit-exactness — see module docstring)."""
    env = os.environ.get("DL4J_TPU_SUPERSTEP_SCAN")
    if env:
        return env not in ("0", "false", "False")
    return True


def superstep_loop(body, carry, xs, k: int, scan: bool):
    """Run `body` over the leading [K] axis of the `xs` pytree and return
    `(carry, losses)` with `losses` a `[K]` vector — `lax.scan` when `scan`,
    else a K-step unrolled loop with identical carry threading. `None`
    leaves in `xs` (absent masks) are empty pytrees in both shapes: scan
    passes them through untouched, and the unrolled indexer never sees
    them."""
    if scan:
        return jax.lax.scan(body, carry, xs)
    losses = []
    for i in range(k):
        inp = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, loss = body(carry, inp)
        losses.append(loss)
    return carry, jnp.stack(losses)
