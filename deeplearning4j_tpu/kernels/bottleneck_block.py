"""Fused ResNet bottleneck block: conv1x1 -> BN+act -> conv3x3 -> BN+act
-> conv1x1 -> BN -> residual add -> act, one registry-dispatched unit.

PERF.md SS2/SS3: resnet50 training sits at its HBM roofline (~0.281 MFU)
because every sub-layer of the bottleneck writes its activation back to
HBM just for the next sub-layer to read it again. The Pallas path runs
the whole chain in one VMEM residency per block: the conv1x1s are
channel matmuls on the MXU, the SAME-padded 3x3 is nine shifted matmuls,
normalization reuses the `norm_act` kernel's normalize/scale/shift/act
machinery in-register, and the residual never round-trips. Batch stats
are emitted as side outputs in train mode (f32, computed in-kernel) so
the EMA update stays engine-side in `nn/layers/bottleneck.py` — training
semantics are untouched.

The XLA fallback is the unfused vertex chain moved here verbatim — the
same `lax.conv_general_dilated` calls, the same single-pass stats, the
normalize going through `norm_act.batchnorm_norm_act`'s own seam — so
`DL4J_TPU_KERNELS=xla` is bit-identical to a resnet built from per-layer
vertices, and it doubles as the VJP reference via `kernels/_diff.py`
(forced-pallas nets train with the fallback's gradient math).

Inference additionally supports int8 weights (per-channel `__scale`
siblings, PR 8 convention): `nn/params.py::prep_layer_params` passes the
quantized leaves through untouched for this layer, the Pallas body
dequantizes in-register (`q.astype(f32) * scale`), so the serving tier
moves one byte per weight instead of four. Training on int8 weights is
refused structurally.

Availability (auto): TPU backend, float32/bfloat16 activations, conf
activation in the `norm_act` in-kernel set, and the block's working set
(whole batch for train, one image per grid step for inference) within
the VMEM budget. Forced `pallas` runs interpret mode off-TPU — the CPU
parity tests' path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels import registry
from deeplearning4j_tpu.kernels import norm_act as _norm

_ACTS = _norm._ACTS  # normalize/scale/shift/act machinery is shared

_VMEM_BUDGET = 12 * 1024 * 1024  # same headroom convention as lstm_cell

_BRANCHES = ("a", "b", "c")
_STAT_KEYS = ("mean_a", "var_a", "mean_b", "var_b", "mean_c", "var_c")
_STAT_KEYS_PROJ = _STAT_KEYS + ("mean_proj", "var_proj")


def _working_set_bytes(b, h, w, cin, f1, f3, sh, sw, project):
    """f32 elements resident at once in one kernel invocation (coarse:
    input + each intermediate + weights; bf16 inputs still compute f32)."""
    ho, wo = -(-h // sh), -(-w // sw)
    # The kernel sees the input already strided (`_strided`).
    acts = b * ho * wo * (2 * cin + 3 * f1 + 3 * f3)
    weights = cin * f1 + 9 * f1 * f1 + f1 * f3 + (cin * f3 if project else 0)
    return 4 * (acts + weights)


def _pallas_available(backend, shapes, dtypes, meta=(), forced=False):
    m = dict(meta)
    act = m.get("act")
    if act is not None and act not in _ACTS:
        return False, f"activation {act!r} not expressible in-kernel"
    if m.get("int8") and m.get("train"):
        return False, "int8 weights are inference-only (no quantized grads)"
    fdts = set(dtypes) - {"int8"}
    if fdts and not fdts <= {"float32", "bfloat16"}:
        return False, f"dtype {sorted(fdts)} not in (float32, bfloat16)"
    if forced and backend != "tpu":
        return True, "forced (interpret mode off-TPU)"
    if backend != "tpu":
        return False, ("Pallas bottleneck block needs the TPU backend, have "
                       f"{backend} (DL4J_TPU_KERNEL_BOTTLENECK_BLOCK=pallas "
                       "forces interpret mode)")
    if not shapes:
        return True, "TPU backend (shapes unknown: assumed within VMEM budget)"
    b, h, w, cin, f1, f3, sh, sw = shapes
    train = bool(m.get("train"))
    need = _working_set_bytes(b if train else 1, h, w, cin, f1, f3, sh, sw,
                              bool(m.get("project")))
    if need > _VMEM_BUDGET:
        return False, (f"block working set ~{need / 2**20:.1f} MB exceeds the "
                       f"{_VMEM_BUDGET / 2**20:.0f} MB VMEM budget "
                       f"({'whole-batch train' if train else 'per-image'} "
                       "residency)")
    return True, ("forced (TPU, fits VMEM)" if forced
                  else "TPU fused bottleneck chain")


def _xla_available(backend, shapes, dtypes, meta=(), forced=False):
    return True, ("XLA per-layer composite (bit-identical to the unfused "
                  "bottleneck vertices)")


registry.register("bottleneck_block", [
    registry.KernelImpl("pallas", _pallas_available),
    registry.KernelImpl("xla", _xla_available),
])


# ------------------------------------------------------- XLA fallback
# The unfused vertex chain moved VERBATIM: `_conv` is
# nn/layers/convolution.py::conv2d_apply's call (no bias, SAME mode —
# models/resnet.py::_conv_bn builds exactly that), `_bn_stats` is
# nn/layers/normalization.py::batchnorm_apply's single-pass stats, and
# normalization goes through norm_act's own dispatch seam, so the
# fallback inherits that kernel's behaviour too (bit-exactness contract).


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=stride, padding="SAME",
        rhs_dilation=(1, 1), dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn_stats(x):
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(x * x, axis=axes) - mean * mean
    return mean, var


def xla_train(x, wa, ga, ba, wb, gb, bb, wc, gc, bc, wp, gp, bp,
              *, stride, eps, act):
    """Train-mode composite: returns (y, stats) where stats is the flat
    (mean_a, var_a, ..) tuple; the caller owns the EMA. Pass wp/gp/bp as
    None for the identity shortcut."""
    from deeplearning4j_tpu.nn import activations

    a = _conv(x, wa, stride)
    ma, va = _bn_stats(a)
    a = _norm.batchnorm_norm_act(a, ma, va, ga, ba, eps, act)
    h = _conv(a, wb, (1, 1))
    mb, vb = _bn_stats(h)
    h = _norm.batchnorm_norm_act(h, mb, vb, gb, bb, eps, act)
    c = _conv(h, wc, (1, 1))
    mc, vc = _bn_stats(c)
    c = _norm.batchnorm_norm_act(c, mc, vc, gc, bc, eps, "identity")
    stats = (ma, va, mb, vb, mc, vc)
    if wp is None:
        shortcut = x
    else:
        p = _conv(x, wp, stride)
        mp, vp = _bn_stats(p)
        shortcut = _norm.batchnorm_norm_act(p, mp, vp, gp, bp, eps, "identity")
        stats = stats + (mp, vp)
    return activations.resolve(act)(c + shortcut), stats


def xla_infer(x, wa, ga, ba, wb, gb, bb, wc, gc, bc, wp, gp, bp, stats,
              *, stride, eps, act):
    """Inference composite: `stats` is the running-stat dict from the
    layer state (same chain as xla_train, given statistics)."""
    from deeplearning4j_tpu.nn import activations

    a = _conv(x, wa, stride)
    a = _norm.batchnorm_norm_act(a, stats["mean_a"], stats["var_a"],
                                 ga, ba, eps, act)
    h = _conv(a, wb, (1, 1))
    h = _norm.batchnorm_norm_act(h, stats["mean_b"], stats["var_b"],
                                 gb, bb, eps, act)
    c = _conv(h, wc, (1, 1))
    c = _norm.batchnorm_norm_act(c, stats["mean_c"], stats["var_c"],
                                 gc, bc, eps, "identity")
    if wp is None:
        shortcut = x
    else:
        p = _conv(x, wp, stride)
        shortcut = _norm.batchnorm_norm_act(
            p, stats["mean_proj"], stats["var_proj"], gp, bp, eps, "identity")
    return activations.resolve(act)(c + shortcut)


# -------------------------------------------------------- Pallas path
# All in-kernel math is f32 (matmuls via preferred_element_type on the
# MXU); the activation output is cast back to the input dtype, batch
# stats stay f32. Train runs the whole batch in one block so the stats
# reduce in-kernel; inference grids over the batch (one image per step)
# so real serving shapes fit VMEM, with running stats as operands.


def _in_kernel_norm(v, mean, var, gamma, beta, eps, act):
    # norm_act._bn_kernel's expression, on values instead of refs.
    xhat = (v - mean) / jnp.sqrt(var + eps)
    return _ACTS[act](gamma * xhat + beta)


_f32 = _norm._f32


def _load_w(ref, scale_ref):
    """Weight load, dequantizing int8 in-register when a per-channel
    scale operand is present (quantize.py contract: scale over the last
    axis, `q.astype(f32) * scale`)."""
    w = _f32(ref)
    if scale_ref is not None:
        w = w * scale_ref[...].reshape(1, -1) if w.ndim == 2 \
            else w * scale_ref[...].reshape(1, 1, 1, -1)
    return w


def _conv1x1(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _strided(x, sh, sw):
    """The block's stride, taken by XLA before the kernel: Mosaic has no
    strided value slice ("Only 2D gather is supported"), and every consumer
    of `x` in a strided block is a strided 1x1 conv (a strided block always
    projects its shortcut), so the kernel needs only these positions."""
    return x if (sh, sw) == (1, 1) else x[:, ::sh, ::sw, :]


def _conv3x3_same(x, w):
    ho, wo = x.shape[1], x.shape[2]
    pad = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    out = jnp.zeros(x.shape[:3] + (w.shape[-1],), jnp.float32)
    for i in range(3):
        for j in range(3):
            out = out + jnp.dot(pad[:, i:i + ho, j:j + wo, :], w[i, j],
                                preferred_element_type=jnp.float32)
    return out


def _kernel_stats(v):
    mean = jnp.mean(v, axis=(0, 1, 2))
    var = jnp.mean(v * v, axis=(0, 1, 2)) - mean * mean
    return mean, var


def _train_body(eps, act, project, x_ref, *refs):
    nw = 12 if project else 9
    win, outs = refs[:nw], refs[nw:]
    (wa, ga, ba, wb, gb, bb, wc, gc, bc) = win[:9]
    x = _f32(x_ref)

    a = _conv1x1(x, _f32(wa))
    ma, va = _kernel_stats(a)
    a = _in_kernel_norm(a, ma, va, _f32(ga), _f32(ba), eps, act)
    h = _conv3x3_same(a, _f32(wb))
    mb, vb = _kernel_stats(h)
    h = _in_kernel_norm(h, mb, vb, _f32(gb), _f32(bb), eps, act)
    c = _conv1x1(h, _f32(wc))
    mc, vc = _kernel_stats(c)
    c = _in_kernel_norm(c, mc, vc, _f32(gc), _f32(bc), eps, "identity")
    stats = [ma, va, mb, vb, mc, vc]
    if project:
        wp, gp, bp = win[9:]
        p = _conv1x1(x, _f32(wp))
        mp, vp = _kernel_stats(p)
        shortcut = _in_kernel_norm(p, mp, vp, _f32(gp), _f32(bp), eps,
                                   "identity")
        stats += [mp, vp]
    else:
        shortcut = x

    y_ref = outs[0]
    y_ref[...] = _ACTS[act](c + shortcut).astype(y_ref.dtype)
    for ref, s in zip(outs[1:], stats):
        ref[...] = s.reshape(1, -1)


def _infer_body(eps, act, project, int8, x_ref, *refs):
    # Per-branch operand groups: (w, [scale], gamma, beta, mean, var).
    per = 6 if int8 else 5
    groups = [refs[i * per:(i + 1) * per]
              for i in range(4 if project else 3)]
    y_ref = refs[per * (4 if project else 3)]

    def unpack(g):
        if int8:
            w, s, gm, bt, mu, vr = g
            return _load_w(w, s), _f32(gm), _f32(bt), _f32(mu), _f32(vr)
        w, gm, bt, mu, vr = g
        return _load_w(w, None), _f32(gm), _f32(bt), _f32(mu), _f32(vr)

    x = _f32(x_ref)
    wa, ga, ba, ma, va = unpack(groups[0])
    a = _in_kernel_norm(_conv1x1(x, wa), ma, va, ga, ba, eps, act)
    wb, gb, bb, mb, vb = unpack(groups[1])
    h = _in_kernel_norm(_conv3x3_same(a, wb), mb, vb, gb, bb, eps, act)
    wc, gc, bc, mc, vc = unpack(groups[2])
    c = _in_kernel_norm(_conv1x1(h, wc), mc, vc, gc, bc, eps, "identity")
    if project:
        wp, gp, bp, mp, vp = unpack(groups[3])
        shortcut = _in_kernel_norm(_conv1x1(x, wp), mp, vp, gp, bp, eps,
                                   "identity")
    else:
        shortcut = x
    y_ref[...] = _ACTS[act](c + shortcut).astype(y_ref.dtype)


@functools.lru_cache(maxsize=32)
def _train_call(b, ho, wo, cin, f1, f3, eps, act, project, xdtype,
                interpret):
    """`ho`/`wo`: the block's output extent (the input arrives strided)."""
    from jax.experimental import pallas as pl

    stat_dims = (f1, f1, f1, f1, f3, f3) + ((f3, f3) if project else ())
    outs = [jax.ShapeDtypeStruct((b, ho, wo, f3), jnp.dtype(xdtype))]
    outs += [jax.ShapeDtypeStruct((1, d), jnp.float32) for d in stat_dims]
    body = functools.partial(_train_body, eps, act, project)
    return pl.pallas_call(body, out_shape=outs, interpret=interpret,
                          name="bottleneck_block_train")


@functools.lru_cache(maxsize=32)
def _infer_call(b, ho, wo, cin, f1, f3, eps, act, project, int8,
                xdtype, interpret):
    """`ho`/`wo`: the block's output extent (the input arrives strided)."""
    from jax.experimental import pallas as pl

    def full(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, lambda i, _n=nd: (0,) * _n)

    branch_dims = [(cin, f1), (f1, f1), (f1, f3)]
    if project:
        branch_dims.append((cin, f3))
    in_specs = [pl.BlockSpec((1, ho, wo, cin), lambda i: (i, 0, 0, 0))]
    for bi, (ci, fo) in enumerate(branch_dims):
        wshape = (3, 3, f1, f1) if bi == 1 else (ci, fo)
        in_specs.append(full(wshape))               # weight
        if int8:
            in_specs.append(full((1, fo)))          # __scale
        in_specs += [full((1, fo))] * 4             # gamma, beta, mean, var
    body = functools.partial(_infer_body, eps, act, project, int8)
    return pl.pallas_call(
        body,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, ho, wo, f3), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, ho, wo, f3), jnp.dtype(xdtype)),
        interpret=interpret, name="bottleneck_block_infer")


# ----------------------------------------------------- dispatch seam


def _branches(project):
    return _BRANCHES + (("proj",) if project else ())


def stat_keys(project):
    return _STAT_KEYS_PROJ if project else _STAT_KEYS


def _dequant(q, scale, dtype):
    # prep_layer_params' exact dequant expression (bit-for-bit the PR 8
    # serving contract) for paths that can't keep int8 in-kernel.
    return q.astype(dtype) * scale.astype(dtype)


def _signature(x, f1, f3, stride, train, project, act, int8):
    b, h, w, cin = (int(d) for d in x.shape)
    dtypes = (str(x.dtype),) + (("int8",) if int8 else ())
    return dict(shapes=(b, h, w, cin, int(f1), int(f3),
                        int(stride[0]), int(stride[1])),
                dtypes=dtypes,
                meta=(("train", bool(train)), ("project", bool(project)),
                      ("act", str(act)), ("int8", bool(int8))))


def bottleneck_forward(x, params, state, *, stride, project, eps,
                       activation, train):
    """`nn/layers/bottleneck.py::bottleneck_apply`'s seam. Returns
    `(y, stats)`: stats is the batch-stat dict (keyed like the state) in
    train mode, None in inference — the EMA update stays in the layer."""
    eps, act = float(eps), str(activation)
    names = _branches(project)
    qscales = {n: params.get(f"W_{n}__scale") for n in names}
    int8 = all(params[f"W_{n}"].dtype == jnp.int8 and qscales[n] is not None
               for n in names)
    if train and int8:
        raise ValueError(
            "bottleneck_block: training on int8 weights is unsupported "
            "(quantized checkpoints are inference-only)")
    weights = {}
    for n in names:
        wq = params[f"W_{n}"]
        if not int8 and wq.dtype == jnp.int8:
            wq = _dequant(wq, qscales[n], x.dtype)  # mixed trees: engine-side
        weights[n] = wq
    f1 = int(weights["a"].shape[-1])
    f3 = int(weights["c"].shape[-1])
    res = registry.resolve(
        "bottleneck_block",
        **_signature(x, f1, f3, stride, train, project, act, int8))

    if res.impl != "pallas":
        wflat = []
        for n in names:
            wv = weights[n]
            if int8:
                wv = _dequant(wv, qscales[n], x.dtype)
            wflat += [wv, params[f"gamma_{n}"], params[f"beta_{n}"]]
        if not project:
            wflat += [None, None, None]
        if train:
            y, stats = xla_train(x, *wflat, stride=tuple(stride), eps=eps,
                                 act=act)
            return y, dict(zip(stat_keys(project), stats))
        return xla_infer(x, *wflat, state, stride=tuple(stride), eps=eps,
                         act=act), None

    from deeplearning4j_tpu.kernels import _diff

    interpret = registry.interpret_mode()
    b, h, w, cin = (int(d) for d in x.shape)
    sh, sw = int(stride[0]), int(stride[1])
    ho, wo = -(-h // sh), -(-w // sw)

    def row(v, feats):
        return jnp.broadcast_to(
            jnp.asarray(v, jnp.float32), (int(feats),)).reshape(1, -1)

    if train:
        call = _train_call(b, ho, wo, cin, f1, f3, eps, act,
                           bool(project), str(x.dtype), interpret)
        nstat = len(stat_keys(project))

        def pallas_fn(xv, *wflat):
            # HWIO 1x1 kernels flatten to channel matmuls; gamma/beta
            # ride as (1, F) rows (norm_act._vec convention).
            kin = []
            for bi, n in enumerate(names):
                wv, gv, bv = wflat[3 * bi:3 * bi + 3]
                feats = wv.shape[-1]
                if n != "b":
                    wv = wv.reshape(wv.shape[-2], feats)
                kin += [wv, row(gv, feats), row(bv, feats)]
            out = call(_strided(xv, sh, sw), *kin)
            return out[0], tuple(s.reshape(-1) for s in out[1:1 + nstat])

        def ref_fn(xv, *wflat):
            pad = wflat if project else wflat + (None, None, None)
            y, stats = xla_train(xv, *pad, stride=(sh, sw), eps=eps, act=act)
            # Match the Pallas output pytree: stats are (F,) f32 (they
            # only feed the EMA — value semantics, no gradient path).
            return y, tuple(s.astype(jnp.float32) for s in stats)

        args = []
        for n in names:
            args += [weights[n], params[f"gamma_{n}"], params[f"beta_{n}"]]
        y, stats = _diff.pallas_fwd_ref_bwd(pallas_fn, ref_fn)(x, *args)
        return y, dict(zip(stat_keys(project), stats))

    call = _infer_call(b, ho, wo, cin, f1, f3, eps, act,
                       bool(project), int8, str(x.dtype), interpret)

    def kernel_inputs(xv, *wflat):
        kin = []
        for bi, n in enumerate(names):
            wv, gv, bv = wflat[3 * bi:3 * bi + 3]
            feats = int(f1 if n in ("a", "b") else f3)
            if n != "b":
                wv = wv.reshape(wv.shape[-2], feats)
            kin.append(wv)
            if int8:
                kin.append(row(qscales[n], feats))
            kin += [row(gv, feats), row(bv, feats),
                    row(state[f"mean_{n}"], feats),
                    row(state[f"var_{n}"], feats)]
        return call(_strided(xv, sh, sw), *kin)

    args = []
    for n in names:
        args += [weights[n], params[f"gamma_{n}"], params[f"beta_{n}"]]
    if int8:
        # int8 weights carry no gradients; call the kernel directly.
        return kernel_inputs(x, *args), None

    def ref_fn(xv, *wflat):
        pad = wflat if project else wflat + (None, None, None)
        return xla_infer(xv, *pad, state, stride=(sh, sw), eps=eps, act=act)

    return _diff.pallas_fwd_ref_bwd(kernel_inputs, ref_fn)(x, *args), None
