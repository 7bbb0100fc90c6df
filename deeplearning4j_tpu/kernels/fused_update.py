"""Fused optimizer update: one elementwise kernel over a layer's small leaves.

The engine applies Adam/Nesterov/RMSProp once per LAYER
(`Engine._apply_updates`: ResNet-50's step makes 107 dispatches), and each
dispatch reaches this seam with that layer's gradient and state trees. Two
bodies compute the same update:

- The XLA bodies below are the LITERAL pre-registry `ops/updaters.py`
  code moved here verbatim (bit-exactness contract): one `tree_map` per
  state field, the same bias-correction branch, so `DL4J_TPU_KERNELS=xla`
  (and auto off-TPU) trains bit-identically to the pre-PR engines. XLA
  fuses each leaf's chain with the gradient's cast before it and the
  engine's `p - delta` after it, in place on the donated buffers.
- The Pallas body ravels the gradient/state trees into single flat vectors
  (`ravel_pytree`), pads to an (8, 128) tile multiple, runs ONE elementwise
  kernel producing the new state vectors and the delta vector, and
  unravels them back to the tree. It is for dispatches of SMALL leaves
  (a BatchNorm's gamma and beta, a 1x1 convolution), which alone never
  fill a lane: on ResNet-50's 72 such dispatches it is 0.2% of the step
  faster than XLA's per-leaf fusions (PERF.md §6, PR 29).

Under `auto` on a TPU the leaves' sizes decide (`_RAVEL_LIMIT`): a leaf of a
grid block or more fills the lanes by itself, and the ravel, the tile
reshape and the unravel around the custom call are whole passes over HBM
that XLA must materialise: for `keye_vl2_30b_a3b`'s 465 M parameters about
50 GB a step around a 16 ms kernel, invisible to every `*_time_share`
(PERF.md §6, PR 29). `DL4J_TPU_KERNEL_FUSED_UPDATE=pallas` still forces the
body (parity tests, `chip_smoke.py`). The superstep carry
(`nn/superstep.py`) threads through this exact seam.

Hyperparameters stay Python floats baked into the trace; `lr`/`step` may
be traced scalars and are passed into the kernel as a (3,) SMEM operand.

Scope: `adam`, `nesterovs`, `rmsprop` (the issue's set). Other updaters
never enter the seam. Mixed-dtype or non-float32 trees fall back.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from deeplearning4j_tpu.kernels import registry

_KINDS = ("adam", "nesterovs", "rmsprop")
_TILE = 8 * 128
# Rows of the [rows, 128] f32 view per grid step: 512 KiB per operand
# block, so Adam's 3 inputs + 3 outputs, double-buffered, hold 6 MiB of
# the chip's 16 MiB default scoped VMEM.
_BLOCK_ROWS = 1024
# Under `auto` the body is for dispatches of small leaves. Once a leaf
# holds a whole grid block a per-leaf XLA fusion fills the lanes as well,
# and runs in place on the donated buffers; the ravel here does not. A
# constant of the kernel, decided on the chip (PERF.md §6, PR 29).
_RAVEL_LIMIT = _BLOCK_ROWS * 128
_RAVEL_AUTO_REFUSAL = (
    "the largest leaf holds {largest} elements (>= one grid block of "
    f"{_RAVEL_LIMIT}): the body wants every leaf raveled into one flat "
    "vector, and for a leaf this size the ravel, the tile reshape and the "
    "unravel are whole passes over HBM that XLA's per-leaf fusion (cast, "
    "moments, delta, p - delta, in place) never makes "
    "(DL4J_TPU_KERNEL_FUSED_UPDATE=pallas forces it; PERF.md PR 29)")


def _pallas_available(backend, shapes, dtypes, meta=(), forced=False):
    m = dict(meta)
    kind = m.get("kind")
    if kind is None and backend == "tpu":
        # Generic (shapeless) probe, e.g. the CLI: the fused path exists
        # for the _KINDS set; per-signature probes decide per updater.
        return True, f"TPU fused update for {'/'.join(_KINDS)}"
    if kind not in _KINDS:
        return False, f"updater {kind!r} has no fused kernel (fused: {_KINDS})"
    if shapes == () and dtypes == ():
        return False, "empty gradient tree"
    if dtypes and any(d != "float32" for d in set(dtypes)):
        return False, f"non-float32 leaves {sorted(set(dtypes))}"
    if forced:
        return True, ("forced" + ("" if backend == "tpu"
                                  else " (interpret mode off-TPU)"))
    if backend != "tpu":
        return False, (f"Pallas fused update needs the TPU backend, have "
                       f"{backend} (DL4J_TPU_KERNEL_FUSED_UPDATE=pallas "
                       "forces interpret mode)")
    largest = max((math.prod(s) for s in shapes), default=0)
    if largest >= _RAVEL_LIMIT:
        return False, _RAVEL_AUTO_REFUSAL.format(largest=largest)
    return True, "TPU fused elementwise update over stacked flat leaves"


def _xla_available(backend, shapes, dtypes, meta=(), forced=False):
    return True, "per-leaf tree_map (bit-identical to the pre-registry code)"


registry.register("fused_update", [
    registry.KernelImpl("pallas", _pallas_available),
    registry.KernelImpl("xla", _xla_available),
])


# ------------------------------------------------------- XLA fallbacks
# Moved VERBATIM from ops/updaters.py — the op order is the bit-exactness
# contract with the pre-registry engines.


def adam_xla(state, grads, lr, step, beta1, beta2, eps):
    t = step + 1
    m = jax.tree_util.tree_map(lambda m0, g: beta1 * m0 + (1 - beta1) * g, state["m"], grads)
    v = jax.tree_util.tree_map(lambda v0, g: beta2 * v0 + (1 - beta2) * g * g, state["v"], grads)
    bc1 = 1.0 - beta1 ** t.astype(jnp.float32) if hasattr(t, "astype") else 1.0 - beta1 ** t
    bc2 = 1.0 - beta2 ** t.astype(jnp.float32) if hasattr(t, "astype") else 1.0 - beta2 ** t
    deltas = jax.tree_util.tree_map(
        lambda m1, v1: lr * (m1 / bc1) / (jnp.sqrt(v1 / bc2) + eps), m, v
    )
    return {"m": m, "v": v}, deltas


def nesterovs_xla(state, grads, lr, step, momentum):
    v_prev = state["v"]
    v = jax.tree_util.tree_map(lambda v0, g: momentum * v0 - lr * g, v_prev, grads)
    # ND4J semantics: applied update = -(mu*vPrev) + (1+mu)*v, negated here
    # because the caller subtracts deltas.
    deltas = jax.tree_util.tree_map(
        lambda v0, v1: momentum * v0 - (1.0 + momentum) * v1, v_prev, v
    )
    return {"v": v}, deltas


def rmsprop_xla(state, grads, lr, step, decay, eps):
    g2 = jax.tree_util.tree_map(lambda a, g: decay * a + (1 - decay) * g * g, state["g2"], grads)
    deltas = jax.tree_util.tree_map(lambda a, g: lr * g / jnp.sqrt(a + eps), g2, grads)
    return {"g2": g2}, deltas


# -------------------------------------------------------- Pallas path


def _adam_kernel(beta1, beta2, eps, m_ref, v_ref, g_ref, s_ref, mo, vo, do):
    lr = s_ref[0]
    bc1 = s_ref[1]
    bc2 = s_ref[2]
    g = g_ref[...]
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * g * g
    mo[...] = m
    vo[...] = v
    do[...] = lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)


def _nesterovs_kernel(momentum, v_ref, g_ref, s_ref, vo, do):
    lr = s_ref[0]
    v0 = v_ref[...]
    v = momentum * v0 - lr * g_ref[...]
    vo[...] = v
    do[...] = momentum * v0 - (1.0 + momentum) * v


def _rmsprop_kernel(decay, eps, a_ref, g_ref, s_ref, ao, do):
    lr = s_ref[0]
    a = decay * a_ref[...] + (1.0 - decay) * g_ref[...] * g_ref[...]
    ao[...] = a
    do[...] = lr * g_ref[...] / jnp.sqrt(a + eps)


@functools.lru_cache(maxsize=64)
def _flat_call(kind: str, rows: int, hyper: tuple, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    body = {
        "adam": functools.partial(_adam_kernel, *hyper),
        "nesterovs": functools.partial(_nesterovs_kernel, *hyper),
        "rmsprop": functools.partial(_rmsprop_kernel, *hyper),
    }[kind]
    # Tiled operands in = tiled operands out: state fields + gradient in,
    # state fields + delta out.
    n_out = {"adam": 3, "nesterovs": 2, "rmsprop": 2}[kind]
    out = jax.ShapeDtypeStruct((rows, 128), jnp.float32)
    block = min(rows, _BLOCK_ROWS)
    # A ragged last block is fine for an elementwise pass: rows past the
    # end read unspecified values and their writes are dropped.
    tile = pl.BlockSpec((block, 128), lambda i: (i, 0))
    return pl.pallas_call(
        body, out_shape=(out,) * n_out, grid=(pl.cdiv(rows, block),),
        in_specs=[tile] * n_out + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=(tile,) * n_out, interpret=interpret,
        name=f"fused_update_{kind}")


def _to_tiles(vec):
    n = vec.shape[0]
    pad = (-n) % _TILE
    if pad:
        vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
    return vec.reshape(-1, 128)


def _scalars(lr, step, kind, hyper):
    lr = jnp.asarray(lr, jnp.float32)
    if kind == "adam":
        beta1, beta2, _ = hyper
        t = jnp.asarray(step, jnp.float32) + 1.0
        bc1 = 1.0 - beta1 ** t
        bc2 = 1.0 - beta2 ** t
        return jnp.stack([lr, bc1, bc2])
    return jnp.stack([lr, lr, lr])


def pallas_update(kind, state, grads, lr, step, hyper):
    """Fused update over the raveled trees; returns `(new_state, deltas)`
    with the same tree structure as the XLA fallbacks."""
    gflat, unravel = ravel_pytree(grads)
    n = gflat.shape[0]
    fields = {"adam": ("m", "v"), "nesterovs": ("v",), "rmsprop": ("g2",)}[kind]
    sflat = [ravel_pytree(state[f])[0] for f in fields]
    tiles = _to_tiles(gflat)
    call = _flat_call(kind, tiles.shape[0], hyper,
                      interpret=registry.interpret_mode())
    outs = call(*[_to_tiles(s) for s in sflat], tiles,
                _scalars(lr, step, kind, hyper))
    outs = [o.reshape(-1)[:n] for o in outs]
    new_state = {f: unravel(outs[i]) for i, f in enumerate(fields)}
    return new_state, unravel(outs[-1])


# ------------------------------------------------------- dispatch seam


def dispatch(kind, state, grads, lr, step, hyper):
    """`ops/updaters.py`'s seam: `hyper` is the positional hyperparameter
    tuple of the kind's XLA fallback (Python floats — part of the trace,
    and of the resolution memo key)."""
    leaves = jax.tree_util.tree_leaves(grads)
    res = registry.resolve(
        "fused_update",
        shapes=tuple(tuple(l.shape) for l in leaves),
        dtypes=tuple(str(l.dtype) for l in leaves),
        meta=(("kind", kind), ("hyper", tuple(hyper))))
    if res.impl == "pallas":
        return pallas_update(kind, state, grads, lr, step, tuple(hyper))
    if kind == "adam":
        return adam_xla(state, grads, lr, step, *hyper)
    if kind == "nesterovs":
        return nesterovs_xla(state, grads, lr, step, *hyper)
    return rmsprop_xla(state, grads, lr, step, *hyper)
