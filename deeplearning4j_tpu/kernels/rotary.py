"""Rotate-half rotary embedding: one pass each way over the operand.

`nn/layers/dsa.py::rope` hands its rotation here. For a head of D at
position t the pairs (i, i + D/2) turn by the angle of frequency i:

    y[i]       = x[i] cos_i - x[i + D/2] sin_i
    y[i + D/2] = x[i + D/2] cos_i + x[i] sin_i

with cos and sin times YaRN's `attention_factor` where it has one.

The Pallas candidate takes x `[S, ..., D]` as its transpose `[L, S]`, `L`
the heads' D rows one after the other and the positions along the lanes.
That is how XLA lays out the QK-norm's output on a TPU (positions minor:
the norm's statistics are then sums over sublanes, not across lanes), so
the transpose is a bitcast of what the norm wrote, not a copy; a view
`[S, L]` with the heads on the lanes had a relayout copy on each side of
every call (PERF.md §6). In the transposed view a head's two halves are
row blocks D/2 apart, whole sublane tiles where D is a multiple of 32: the
kernel reads a tile, computes the two halves in float32 and writes the
operand's dtype, with the arithmetic and the rounding of the XLA
candidate and no lane shuffle. The grid walks (position block, row
block); the tables `cos`, `sin` are float32 `[D/2, S]` and their block
index is the position block alone, so a position block fetches them once.
XLA's candidate splits each head into float32 halves and concatenates them
again (4.66 GB for one layer of `mellum2_12b_a2_5b.fit_seq16k` compiled
apart, where one read and one write of the operand each way is 0.60).

The gradient is a `custom_vjp` that saves the tables alone: the rotation is
orthogonal and its transpose is the rotation by the opposite angle, the
same body with the sine's sign turned (a static flag, no pass to negate a
table).

The XLA candidate is `dsa.rope_xla`, the pre-registry expression to the
character and the Pallas body's parity reference. It runs off the TPU, in
float64, and where a half-head is not whole sublane tiles (D off 32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deeplearning4j_tpu.kernels import registry as _registry

# Positions a grid step takes (its lanes); a sequence of at most this many
# is one block.
_POSITIONS = 512
# Operand bytes of one grid step: with the result, the two tables and the
# pipeline's second buffer of each ~4.5 MiB, inside the 16 MiB of VMEM a
# kernel has without asking for more (a kernel here never raises it:
# PERF.md §6-§7).
_BLOCK_BYTES = 1 << 20


def tables(t, inv, mscale):
    """Positions t `[S]`, inverse frequencies `[D/2]` and the factor on cos
    and sin -> the kernel's `(cos, sin)`, float32 `[D/2, S]`: the products
    `dsa.rope_xla` takes them of, the factor applied as it applies it."""
    ang = inv[:, None] * t[None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    if mscale != 1.0:
        c, s = c * mscale, s * mscale
    return c, s


def _position_block(S: int):
    if S <= _POSITIONS:
        return S
    return _POSITIONS if S % _POSITIONS == 0 else None


def _row_block(L: int, D: int, positions: int, itemsize: int) -> int:
    """The most whole heads that divide L inside `_BLOCK_BYTES`."""
    n = L // D
    most = max(1, _BLOCK_BYTES // (positions * D * itemsize))
    return D * max(k for k in range(1, min(n, most) + 1) if n % k == 0)


def _rotary_kernel(x_ref, c_ref, s_ref, o_ref, *, D, transpose):
    h = D // 2
    c, s = c_ref[...], s_ref[...]
    for k in range(0, x_ref.shape[0], D):
        a = x_ref[k:k + h, :].astype(jnp.float32)
        b = x_ref[k + h:k + D, :].astype(jnp.float32)
        if transpose:
            ya, yb = a * c + b * s, b * c - a * s
        else:
            ya, yb = a * c - b * s, b * c + a * s
        o_ref[k:k + h, :] = ya.astype(o_ref.dtype)
        o_ref[k + h:k + D, :] = yb.astype(o_ref.dtype)


# Jitted, so that a program which rotates many times (q and k of every
# layer, forward and backward) traces and lowers each body once.
@functools.partial(jax.jit, static_argnames=("D", "transpose", "interpret"))
def rotary_pallas(xt, c, s, *, D, transpose=False, interpret=False):
    """xt `[L, S]` (heads of D rows one after the other, positions along
    the lanes), the tables `[D/2, S]` -> the rotation of every head, by
    the opposite angle with `transpose`, `[L, S]` in xt's dtype."""
    from jax.experimental.pallas import tpu as pltpu

    L, S = xt.shape
    positions = _position_block(S)
    rows = _row_block(L, D, positions, xt.dtype.itemsize)
    tile = pl.BlockSpec((rows, positions), lambda j, i: (i, j))
    table = pl.BlockSpec((D // 2, positions), lambda j, i: (0, j))
    return pl.pallas_call(
        functools.partial(_rotary_kernel, D=D, transpose=transpose),
        grid=(S // positions, L // rows), in_specs=[tile, table, table],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((L, S), xt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=3 * S * L, transcendentals=0,
            bytes_accessed=2 * S * L * xt.dtype.itemsize + S * D * 4),
        interpret=interpret, name="rotary",
    )(xt, c, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate(xt, c, s, D):
    return rotary_pallas(xt, c, s, D=D, interpret=_registry.interpret_mode())


def _rotate_fwd(xt, c, s, D):
    return _rotate(xt, c, s, D), (c, s)


def _rotate_bwd(D, saved, g):
    c, s = saved
    return (rotary_pallas(g, c, s, D=D, transpose=True,
                          interpret=_registry.interpret_mode()), None, None)


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def rotate(x, c, s):
    """x `[S, ..., D]` rotated by the tables `tables` made: the Pallas
    candidate, differentiable, under `vmap` too."""
    S, D = x.shape[0], x.shape[-1]
    return _rotate(x.reshape(S, -1).T, c, s, D).T.reshape(x.shape)


# ------------------------------------------------------------- the registry
def signature(x):
    """The registry's `shapes` for a rotation of x `[S, ..., D]`: (S, L, D),
    L the elements of a position."""
    S, D = int(x.shape[0]), int(x.shape[-1])
    return S, int(x.size) // S, D


def _pallas_available(backend, shapes, dtypes, meta=(), forced=False):
    """`shapes` is `(S, L, D)`: positions, the elements of a position (heads
    times D), the head."""
    if backend != "tpu" and not forced:
        return False, ("auto off-TPU keeps dsa.rope_xla (interpret mode is "
                       "for the forced parity tests)")
    if dtypes and dtypes[0] not in ("bfloat16", "float32"):
        return False, f"dtype {dtypes[0]}: the kernel takes bfloat16 or float32"
    if shapes:
        S, L, D = shapes
        if D % 32:
            return False, (f"D={D}: a half-head must be whole sublane tiles "
                           "(D a multiple of 32)")
        if _position_block(S) is None:
            return False, (f"S={S}: over {_POSITIONS} positions and not a "
                           f"multiple of the {_POSITIONS}-position block")
    if backend == "tpu":
        return True, ("TPU rotary kernel (one pass each way over the "
                      "operand's positions-minor view, float32 tables)")
    return True, "interpret mode off-TPU (parity tests only)"


def _xla_available(backend, shapes, dtypes, meta=(), forced=False):
    return True, ("dsa.rope_xla: each head's float32 halves apart and "
                  "concatenated again (the pre-registry expression)")


_registry.register("rotary", [
    _registry.KernelImpl("pallas", _pallas_available),
    _registry.KernelImpl("xla", _xla_available),
])
