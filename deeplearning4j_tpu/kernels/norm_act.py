"""Normalize + affine + activation for BatchNorm / LayerNorm, behind the seam.

`nn/layers/normalization.py` hands both layers' tails to this module.

LayerNorm: per-row statistics, normalize, scale/shift and activation run
as one Pallas pass over the `[rows, features]` view, row-tiled so a grid
step holds one `_BLOCK_BYTES` block in VMEM whatever the row count.
Arithmetic is f32 inside the kernel for either operand dtype (a v5e has
no bf16 `sqrt`/`rsqrt`/`tanh` unit); the store casts back.

BatchNorm: the layer computes the batch statistics itself (a reduction XLA
already does well, and whose single-pass form is part of the bit-
exactness contract), so what reaches the seam is an elementwise chain, the
one thing XLA fuses into its producer and consumers for free. A custom call
there is a fusion barrier: the convolution writes its whole output, the
call reads and writes it again, the next convolution reads the result, and
the backward (`_diff.pallas_fwd_ref_bwd`) recomputes the chain apart from
BN's own reductions. `auto` therefore resolves BatchNorm to the XLA
expression on every backend (`_BATCHNORM_AUTO_REFUSAL`); the Pallas body
(`_bn_kernel`, mean/var as operands) runs only when forced.

The XLA fallbacks are the LITERAL pre-registry expressions moved here
verbatim — same ops, same order — so `DL4J_TPU_KERNELS=xla` (and auto
off-TPU, and auto for BatchNorm anywhere) produces bit-identical jaxprs to
the pre-PR layers.

Availability (auto): LayerNorm only — TPU backend, float32 or bfloat16,
activation in the in-kernel set, feature dim a lane (128) multiple no
wider than `_MAX_FEATS` and row count a sublane (8) multiple. Forced
`pallas` takes either operation, keeps the structural constraints and runs
interpret mode off-TPU (the CPU parity tests' path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels import registry

_ACTS = {
    "identity": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
}


# Why `auto` never picks the Pallas body for BatchNorm. Decided by the layer
# type, not by a shape: the body's best case (one read and one write of x)
# is XLA's worst case for the same chain.
_BATCHNORM_AUTO_REFUSAL = (
    "BatchNorm's statistics are XLA's already and what is left is an "
    "elementwise chain: a custom call there is a fusion barrier between "
    "the convolution, BN's reductions and the activation (ResNet-50 at "
    "batch 256 on a v5e: 116.5 GB and ~143 ms a step with it, 81.1 GB and "
    "~101 ms without; ledger PR 24, PERF.md §6 PR 21); "
    "DL4J_TPU_KERNEL_NORM_ACT=pallas forces the body")


def _pallas_available(backend, shapes, dtypes, meta=(), forced=False):
    m = dict(meta)
    act = m.get("act")
    if act is not None and act not in _ACTS:
        return False, f"activation {act!r} not expressible in-kernel"
    if dtypes and not set(dtypes) <= {"float32", "bfloat16"}:
        return False, f"dtype {sorted(set(dtypes))} not in (float32, bfloat16)"
    if not forced and m.get("op") == "batchnorm":
        return False, _BATCHNORM_AUTO_REFUSAL
    if forced and backend != "tpu":
        return True, "forced (interpret mode off-TPU)"
    if backend != "tpu":
        return False, (f"Pallas norm+act needs the TPU backend, have "
                       f"{backend} (DL4J_TPU_KERNEL_NORM_ACT=pallas forces "
                       "interpret mode)")
    if not shapes:
        return True, ("TPU backend (shapes unknown: LayerNorm assumed, "
                      "tile-aligned; BatchNorm resolves to xla)")
    rows, feats = shapes
    if feats % 128 or rows % 8:
        return False, (f"rows={rows}, features={feats} not tile-aligned "
                       "(need features % 128 == 0 and rows % 8 == 0)")
    if feats > _MAX_FEATS:
        return False, (f"features={feats} > {_MAX_FEATS}: the smallest "
                       "(32-row) block, double-buffered in and out, exceeds "
                       "the 16 MiB scoped VMEM limit (the v5e compiler's "
                       "RESOURCE_EXHAUSTED at f32 [1024, 32768])")
    return True, ("forced (TPU, tile-aligned)" if forced
                  else "TPU fused normalize+affine+activation")


def _xla_available(backend, shapes, dtypes, meta=(), forced=False):
    return True, "XLA elementwise chain (bit-identical to the pre-registry layers)"


registry.register("norm_act", [
    registry.KernelImpl("pallas", _pallas_available),
    registry.KernelImpl("xla", _xla_available),
])


# ------------------------------------------------------- XLA fallbacks
# Moved VERBATIM from nn/layers/normalization.py (bit-exactness contract).


def batchnorm_xla(x, mean, var, gamma, beta, eps, activation):
    from deeplearning4j_tpu.nn import activations

    xhat = (x - mean) / jnp.sqrt(var + eps)
    out = gamma * xhat + beta
    return activations.resolve(activation)(out)


def layernorm_xla(x, gamma, beta, eps, activation):
    from deeplearning4j_tpu.nn import activations

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    out = out * gamma + beta
    return activations.resolve(activation)(out)


# -------------------------------------------------------- Pallas path


def _f32(ref):
    return ref[...].astype(jnp.float32)


def _bn_kernel(eps, act_name, x_ref, mu_ref, var_ref, g_ref, b_ref, o_ref):
    xhat = (_f32(x_ref) - _f32(mu_ref)) / jnp.sqrt(_f32(var_ref) + eps)
    out = _ACTS[act_name](_f32(g_ref) * xhat + _f32(b_ref))
    o_ref[...] = out.astype(o_ref.dtype)


def _ln_kernel(eps, act_name, x_ref, g_ref, b_ref, o_ref):
    x = _f32(x_ref)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    out = _ACTS[act_name](out * _f32(g_ref) + _f32(b_ref))
    o_ref[...] = out.astype(o_ref.dtype)


# Widest feature dim whose smallest block the chip's compiler accepts.
_MAX_FEATS = 16384

# f32 bytes of one [block_rows, features] block. The kernel keeps the
# input and output blocks double-buffered plus a few f32 temporaries of
# the same extent, inside the chip's 16 MiB default scoped VMEM.
_BLOCK_BYTES = 1024 * 1024


def _block_rows(rows: int, feats: int) -> int:
    """Rows per grid step: a multiple of 32 (whole sublane tiles for f32
    and packed bf16 alike), or all rows when they fit one block."""
    fit = max(32, _BLOCK_BYTES // (4 * feats) // 32 * 32)
    return rows if rows <= fit else fit


@functools.lru_cache(maxsize=64)
def _norm_call(op: str, rows: int, feats: int, eps: float, act_name: str,
               dtype: str, interpret: bool):
    from jax.experimental import pallas as pl

    body = functools.partial(
        _bn_kernel if op == "batchnorm" else _ln_kernel, eps, act_name)
    block = _block_rows(rows, feats)
    tile = pl.BlockSpec((block, feats), lambda i: (i, 0))
    vec = pl.BlockSpec((1, feats), lambda i: (0, 0))
    n_vec = 4 if op == "batchnorm" else 2
    # A ragged last block is safe: both bodies are row-independent, rows
    # past the end read unspecified values and their writes are dropped.
    return pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((rows, feats), jnp.dtype(dtype)),
        grid=(pl.cdiv(rows, block),), in_specs=[tile] + [vec] * n_vec,
        out_specs=tile, interpret=interpret, name=f"norm_act_{op}")


def _row_view(a):
    """Feature-last tensors of any rank as [rows, features]."""
    return a.reshape(-1, a.shape[-1])


def _vec(v, feats, dtype):
    """gamma/beta/mean/var as a broadcastable [1, features] row — scalars
    (the `lock_gamma_beta` constants) are materialized."""
    return jnp.broadcast_to(jnp.asarray(v, dtype), (feats,)).reshape(1, feats)


def _signature(op, x, activation):
    rows = 1
    for d in x.shape[:-1]:
        rows *= int(d)
    return dict(shapes=(rows, int(x.shape[-1])), dtypes=(str(x.dtype),),
                meta=(("op", op), ("act", str(activation))))


def batchnorm_norm_act(x, mean, var, gamma, beta, eps, activation):
    """`nn/layers/normalization.py::batchnorm_apply`'s seam: normalize
    with the given (already-reduced) statistics, apply scale/shift, then
    the conf activation."""
    res = registry.resolve("norm_act", **_signature("batchnorm", x, activation))
    if res.impl != "pallas":
        return batchnorm_xla(x, mean, var, gamma, beta, eps, activation)
    from deeplearning4j_tpu.kernels import _diff

    feats = x.shape[-1]
    call = _norm_call("batchnorm", _row_view(x).shape[0], int(feats),
                      float(eps), str(activation), str(x.dtype),
                      interpret=registry.interpret_mode())
    # Pallas forward, XLA-reference backward: the seam sits inside the
    # engines' value_and_grad (kernels/_diff.py).
    f = _diff.pallas_fwd_ref_bwd(
        call, lambda xv, mu, vr, g, b: batchnorm_xla(xv, mu, vr, g, b,
                                                     eps, activation))
    out = f(_row_view(x), _vec(mean, feats, x.dtype),
            _vec(var, feats, x.dtype), _vec(gamma, feats, x.dtype),
            _vec(beta, feats, x.dtype))
    return out.reshape(x.shape)


def layernorm_norm_act(x, gamma, beta, eps, activation):
    """`nn/layers/normalization.py::layernorm_apply`'s seam: per-row stats
    + normalize + affine + activation."""
    res = registry.resolve("norm_act", **_signature("layernorm", x, activation))
    if res.impl != "pallas":
        return layernorm_xla(x, gamma, beta, eps, activation)
    from deeplearning4j_tpu.kernels import _diff

    feats = x.shape[-1]
    call = _norm_call("layernorm", _row_view(x).shape[0], int(feats),
                      float(eps), str(activation), str(x.dtype),
                      interpret=registry.interpret_mode())
    f = _diff.pallas_fwd_ref_bwd(
        call, lambda xv, g, b: layernorm_xla(xv, g, b, eps, activation))
    out = f(_row_view(x), _vec(gamma, feats, x.dtype),
            _vec(beta, feats, x.dtype))
    return out.reshape(x.shape)
