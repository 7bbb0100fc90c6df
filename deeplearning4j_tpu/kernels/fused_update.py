"""Fused optimizer update: one elementwise kernel over stacked leaves.

`ops/updaters.py` applies Adam/Nesterov/RMSProp with one
`jax.tree_util.tree_map` per state field — per-leaf ops that XLA mostly
fuses, but each leaf is its own kernel launch chain and small leaves
(biases, norm scales) never saturate a lane. The Pallas path ravels the
gradient/state pytrees into single flat vectors (`ravel_pytree`), pads to
an (8, 128) tile multiple, and runs ONE elementwise kernel producing the
new state vectors and the delta vector, which is then unraveled back to
the param tree — the superstep carry (`nn/superstep.py`) threads through
this exact seam, so all K fused iterations share one update kernel per
step.

The XLA fallbacks below are the LITERAL pre-registry `ops/updaters.py`
bodies moved here verbatim (bit-exactness contract): same tree_maps, same
bias-correction branch, so `DL4J_TPU_KERNELS=xla` (and auto off-TPU)
trains bit-identically to the pre-PR engines. Hyperparameters stay
Python floats baked into the trace; `lr`/`step` may be traced scalars and
are passed into the kernel as a (3,) SMEM operand.

Scope: `adam`, `nesterovs`, `rmsprop` (the issue's set). Other updaters
never enter the seam. Mixed-dtype or non-float32 trees fall back.
"""

from __future__ import annotations

import functools

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
