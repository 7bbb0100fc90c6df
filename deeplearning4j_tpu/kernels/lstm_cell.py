"""Fused LSTM cell kernel: recurrent matmul + gates + state update.

One `pl.pallas_call` per scan step replaces the XLA op soup of
`nn/layers/recurrent.py::_lstm_scan`'s body: the `[b, n] x [n, 4n]`
recurrent matmul runs on the MXU and every elementwise gate/state op
consumes its operands straight from VMEM — no HBM round-trips between
the split/σ/tanh/mul chain that makes char-RNN the worst-MFU workload in
every bench round (PERF.md §4).

The XLA fallback below is the LITERAL pre-registry scan body moved here
verbatim: same ops, same order, so the traced jaxpr — and therefore the
trained bits — are identical to the pre-PR engines whenever the fallback
is active (`DL4J_TPU_KERNELS=xla` or auto off-TPU).

Availability (auto mode): TPU backend, float32 or bfloat16 compute (the
recurrent matmul always accumulates in f32 via `preferred_element_type`;
outputs are cast back to the operand dtype), sigmoid gate activation,
cell activation in the supported elementwise set, `n_out` a lane (128)
multiple and batch a sublane (8) multiple, and the weights + activations
of one step fitting VMEM. Forced `pallas` drops the backend/tiling
requirements (interpret mode needs neither) but keeps the structural
ones — that is how the CPU parity tests drive the same kernel code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels import registry

# Elementwise activations the Pallas kernel can express in-kernel. Names
# follow `nn/activations.py`.
_GATE_ACTS = ("sigmoid",)
_CELL_ACTS = {
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "identity": lambda x: x,
}

_VMEM_BUDGET = 12 * 1024 * 1024


def _pallas_available(backend, shapes, dtypes, meta=(), forced=False):
    m = dict(meta)
    gate, act = m.get("gate"), m.get("act")
    if gate is not None and gate not in _GATE_ACTS:
        return False, f"gate activation {gate!r} not expressible in-kernel"
    if act is not None and act not in _CELL_ACTS:
        return False, f"cell activation {act!r} not expressible in-kernel"
    if dtypes and not set(dtypes) <= {"float32", "bfloat16"}:
        return False, f"dtype {sorted(set(dtypes))} not in (float32, bfloat16)"
    if forced and backend != "tpu":
        return True, "forced (interpret mode off-TPU)"
    if backend != "tpu":
        return False, (f"Pallas LSTM cell needs the TPU backend, have "
                       f"{backend} (DL4J_TPU_KERNEL_LSTM_CELL=pallas forces "
                       "interpret mode)")
    if not shapes:
        return True, "TPU backend (shapes unknown: assumed tile-aligned)"
    b, n = shapes
    if n % 128 or b % 8:
        return False, (f"b={b}, n_out={n} not tile-aligned "
                       "(need n_out % 128 == 0 and b % 8 == 0)")
    if forced:
        return True, "forced (TPU, tile-aligned)"
    step_bytes = 4 * (n * 4 * n + b * 4 * n + 4 * b * n)  # RW + xw + states
    if step_bytes > _VMEM_BUDGET:
        return False, f"one step needs ~{step_bytes} B VMEM > {_VMEM_BUDGET}"
    return True, "TPU fused cell (MXU recurrent matmul + in-VMEM gates)"


def _xla_available(backend, shapes, dtypes, meta=(), forced=False):
    return True, "XLA scan body (bit-identical to the pre-registry engines)"


registry.register("lstm_cell", [
    registry.KernelImpl("pallas", _pallas_available),
    registry.KernelImpl("xla", _xla_available),
])


def xla_cell(gate_act, cell_act, peephole: bool):
    """The pre-registry `_lstm_scan` step body, verbatim (bit-exactness
    contract — do not 'improve' the op order). `pw` is the
    `(p_i, p_f, p_o)` peephole triple or None; `m_t` the `[b]` step mask
    or None. Returns `(h, c, out)`."""

    def cell(xw_t, h_prev, c_prev, RW, pw, m_t):
        z = xw_t + h_prev @ RW
        zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
        if peephole:
            p_i, p_f, p_o = pw
            zi = zi + c_prev * p_i
            zf = zf + c_prev * p_f
        i = gate_act(zi)
        f = gate_act(zf)
        g = cell_act(zg)
        c = f * c_prev + i * g
        if peephole:
            zo = zo + c * p_o
        o = gate_act(zo)
        h = o * cell_act(c)
        if m_t is not None:
            m = m_t[:, None]
            h = m * h + (1.0 - m) * h_prev
            c = m * c + (1.0 - m) * c_prev
            out = m * h
        else:
            out = h
        return h, c, out

    return cell


def _cell_kernel(n_out: int, peephole: bool, masked: bool, act_name: str,
                 refs):
    """Kernel body shared by the peephole/mask variants: `refs` is the
    positional ref tuple in pallas_call order."""
    if peephole and masked:
        xw_ref, h_ref, c_ref, rw_ref, pw_ref, m_ref, ho, co, oo = refs
    elif peephole:
        xw_ref, h_ref, c_ref, rw_ref, pw_ref, ho, co, oo = refs
        m_ref = None
    elif masked:
        xw_ref, h_ref, c_ref, rw_ref, m_ref, ho, co, oo = refs
        pw_ref = None
    else:
        xw_ref, h_ref, c_ref, rw_ref, ho, co, oo = refs
        pw_ref = m_ref = None
    act = _CELL_ACTS[act_name]
    n = n_out
    h_prev = h_ref[...]
    c_prev = c_ref[...]
    z = xw_ref[...] + jnp.dot(h_prev, rw_ref[...],
                              preferred_element_type=jnp.float32)
    zi = z[:, :n]
    zf = z[:, n:2 * n]
    zo = z[:, 2 * n:3 * n]
    zg = z[:, 3 * n:]
    if peephole:
        zi = zi + c_prev * pw_ref[0, :]
        zf = zf + c_prev * pw_ref[1, :]
    i = jax.nn.sigmoid(zi)
    f = jax.nn.sigmoid(zf)
    g = act(zg)
    c = f * c_prev + i * g
    if peephole:
        zo = zo + c * pw_ref[2, :]
    o = jax.nn.sigmoid(zo)
    h = o * act(c)
    if masked:
        m = m_ref[...]  # [b, 1]
        h = m * h + (1.0 - m) * h_prev
        c = m * c + (1.0 - m) * c_prev
        out = m * h
    else:
        out = h
    # Gate math runs in f32 (matmul `preferred_element_type`); the output
    # refs carry the operand dtype (bf16 under mixed policies).
    ho[...] = h.astype(ho.dtype)
    co[...] = c.astype(co.dtype)
    oo[...] = out.astype(oo.dtype)


@functools.lru_cache(maxsize=64)
def _pallas_call(batch: int, n_out: int, peephole: bool, masked: bool,
                 act_name: str, dtype: str, interpret: bool):
    from jax.experimental import pallas as pl

    out = jax.ShapeDtypeStruct((batch, n_out), jnp.dtype(dtype))
    return pl.pallas_call(
        lambda *refs: _cell_kernel(n_out, peephole, masked, act_name, refs),
        out_shape=(out, out, out),
        interpret=interpret,
        name="lstm_cell",
    )


def pallas_cell(batch: int, n_out: int, peephole: bool, masked: bool,
                act_name: str, dtype: str, interpret: bool):
    """Fused-cell callable with the `xla_cell` signature."""
    call = _pallas_call(batch, n_out, peephole, masked, act_name, dtype,
                        interpret)

    def cell(xw_t, h_prev, c_prev, RW, pw, m_t):
        args = [xw_t, h_prev, c_prev, RW]
        if peephole:
            args.append(jnp.stack(pw))  # [3, n]: rows p_i, p_f, p_o
        if masked:
            args.append(m_t[:, None].astype(xw_t.dtype))
        return call(*args)

    return cell


def resolve_cell(*, batch, n_out, dtype, peephole, masked, gate_activation,
                 activation, gate_act, cell_act):
    """The `_lstm_scan` dispatch seam: resolve once per signature (BEFORE
    the scan body is defined — resolution never runs per timestep) and
    return a `(xw_t, h_prev, c_prev, RW, pw, m_t) -> (h, c, out)` cell."""
    res = registry.resolve(
        "lstm_cell", shapes=(int(batch), int(n_out)),
        dtypes=(str(dtype),),
        meta=(("gate", str(gate_activation)), ("act", str(activation)),
              ("peephole", bool(peephole)), ("masked", bool(masked))))
    if res.impl == "pallas":
        from deeplearning4j_tpu.kernels import _diff

        fused = pallas_cell(int(batch), int(n_out), bool(peephole),
                            bool(masked), str(activation), str(dtype),
                            interpret=registry.interpret_mode())
        # The cell runs inside the engines' value_and_grad: Pallas forward,
        # XLA-reference backward (kernels/_diff.py).
        return _diff.pallas_fwd_ref_bwd(
            fused, xla_cell(gate_act, cell_act, peephole))
    return xla_cell(gate_act, cell_act, peephole)
