"""Parameter initialization and flat-view mapping.

Equivalent of the reference's `nn/params/*ParamInitializer` family plus the
flat param view machinery of `MultiLayerNetwork.init():384-473`: params live in
a pytree `{layer_key: {param_name: array}}`; `flatten`/`unflatten` provide the
reference's contiguous 1-D view (deterministic order: layer order, then the
layer's declared `param_shapes()` order) for checkpoint compat and
parameter-averaging-style interop.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.enums import WeightInit
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization,
    BottleneckBlock,
    ConvolutionLayer,
    GravesBidirectionalLSTM,
    GravesLSTM,
    LSTM,
    Layer,
    MoELayer,
    VariationalAutoencoder,
    is_bias_param,
)
from deeplearning4j_tpu.nn.weights import init_weights


def _fans(conf: Layer, name: str, shape: Tuple[int, ...]) -> Tuple[float, float]:
    """Fan-in/out per param, following the reference's initializer conventions."""
    if isinstance(conf, ConvolutionLayer) and name == "W":
        kh, kw, cin, cout = shape
        return (cin * kh * kw, cout * kh * kw)
    if isinstance(conf, BottleneckBlock) and len(shape) == 4:
        # Per-branch conv kernels (HWIO): same fans as ConvolutionLayer
        # so fused and unfused blocks draw identical init statistics.
        kh, kw, cin, cout = shape
        return (cin * kh * kw, cout * kh * kw)
    if isinstance(conf, MoELayer) and len(shape) == 3:
        # Per-expert FFN tables [E, in, out]: fans are the PER-EXPERT matmul
        # dims, not the stacked leading axis.
        return (shape[1], shape[2])
    if len(shape) >= 2:
        return (shape[0], shape[1])
    return (shape[0], shape[0])


def init_layer_params(conf: Layer, rng: jax.Array, dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    """Initialize one layer's params from its config (weight-init scheme, bias
    init, LSTM forget-gate bias, BN gamma/beta constants)."""
    shapes = conf.param_shapes()
    if not shapes:
        return {}
    params: Dict[str, jnp.ndarray] = {}
    keys = jax.random.split(rng, len(shapes))
    bias_init = float(getattr(conf, "bias_init", 0.0) or 0.0)

    for key, (name, shape) in zip(keys, shapes.items()):
        if isinstance(conf, BatchNormalization):
            if name == "gamma":
                params[name] = jnp.full(shape, conf.gamma, dtype)
            else:
                params[name] = jnp.full(shape, conf.beta, dtype)
            continue
        if type(conf).__name__ == "LayerNormalization":
            params[name] = (jnp.ones(shape, dtype) if name == "gamma"
                            else jnp.zeros(shape, dtype))
            continue
        if (type(conf).__name__ in ("RMSNormalization", "SelfAttentionLayer")
                and name.startswith("gamma")):
            # Norm scales (RMS norm; the attention layer's q/k norms and its
            # indexer's key norm): ones. Their `beta_*` take the bias path.
            params[name] = jnp.ones(shape, dtype)
            continue
        if isinstance(conf, MoELayer) and name == "gate_b":
            # the sigmoid router's selection bias: no bias until one is
            # loaded or set
            params[name] = jnp.zeros(shape, dtype)
            continue
        if isinstance(conf, BottleneckBlock) and name.startswith("gamma_"):
            # Per-branch BN scale: ones, like BatchNormalization's default
            # gamma (beta_* lands in the bias path below -> zeros).
            params[name] = jnp.ones(shape, dtype)
            continue
        is_bias = is_bias_param(name) and name != "beta"
        is_peephole = name.startswith("pW")
        if is_bias:
            arr = jnp.full(shape, bias_init, dtype)
            if isinstance(conf, (GravesLSTM, LSTM, GravesBidirectionalLSTM)) and name.startswith("b"):
                # Forget-gate bias init (reference: LSTMParamInitializer; gate
                # order i,f,o,g -> forget block is [n_out, 2*n_out)).
                n_out = conf.n_out
                arr = arr.at[n_out : 2 * n_out].set(conf.forget_gate_bias_init)
            params[name] = arr
        elif is_peephole:
            params[name] = jnp.zeros(shape, dtype)
        else:
            fan_in, fan_out = _fans(conf, name, shape)
            if isinstance(conf, (GravesLSTM, LSTM, GravesBidirectionalLSTM)):
                # Reference inits LSTM weight blocks with fan sizes nIn/nOut
                # (not the 4x packed dims).
                fan_in = conf.n_in if name.startswith("W") else conf.n_out
                fan_out = conf.n_out
            if isinstance(conf, VariationalAutoencoder):
                fan_in, fan_out = shape[0], shape[1]
            params[name] = init_weights(
                key, shape, fan_in, fan_out,
                scheme=WeightInit.of(conf.weight_init) or WeightInit.XAVIER,
                distribution=conf.dist, dtype=dtype,
            )
    if getattr(conf, "lora_rank", None):
        from deeplearning4j_tpu.nn import lora as _lora

        # Distinct subkey stream so adding adapters never perturbs the
        # base-weight draws (the base stays bitwise-reproducible).
        params.update(_lora.init_lora_params(
            conf, jax.random.fold_in(rng, len(shapes) + 1), dtype))
    return params


def cast_floating(tree, dtype):
    """Cast every floating leaf of a pytree, leaving integer/bool leaves
    (embedding ids, quantized tensors) untouched."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def prep_layer_params(lparams: Dict[str, jnp.ndarray], compute_dtype,
                      layer: Layer = None):
    """Per-use param prep shared by both engines' `_forward_fn` (traced):
    floating leaves cast to the policy's compute dtype, int8 leaves with a
    `<name>__scale` companion (post-training quantization —
    `checkpoint/quantize.py`) dequantize as `q * scale` AT the compute
    dtype, so XLA fuses the dequant into the consuming matmul/conv and the
    f32 weights never materialize in HBM. Default-policy nets trace the
    exact same cast as the old inline `tree_map`.

    LoRA adapter leaves (`nn/lora.py`) resolve here too: a weight with
    `<name>__lora_a` / `<name>__lora_b` siblings becomes
    `W_eff = base + scale * (A @ B)` at the compute dtype, where `base`
    is the (possibly dequantized-int8) weight — adapters compose with
    quantized bases and the rank-r delta fuses into the consuming
    matmul. (`<name>__lora_scale` is consumed by the `__scale` suffix
    skip below; only the factor pair needs explicit handling.)

    `layer` (optional, the conf) lets a layer opt out of engine-side
    dequantization: the fused BottleneckBlock keeps int8 weights and
    their `__scale` siblings intact so the Pallas body dequantizes
    in-register — one byte per weight over the wire instead of four.
    Its XLA fallback applies the exact dequant expression from here. A
    layer's `full_precision_param_names()` are handed on as stored."""
    if type(layer).__name__ == "BottleneckBlock":
        out = {}
        for k, a in lparams.items():
            out[k] = (a.astype(compute_dtype)
                      if jnp.issubdtype(a.dtype, jnp.floating)
                      and not k.endswith("__scale") else a)
        return out
    out: Dict[str, jnp.ndarray] = {}
    as_stored = layer.full_precision_param_names() if layer is not None else ()
    for k, a in lparams.items():
        if k.endswith(("__scale", "__lora_a", "__lora_b")):
            continue  # consumed alongside their base tensor
        if k in as_stored:  # a norm's scale: used at its stored precision
            out[k] = a
            continue
        if isinstance(a, dict):  # nested sub-tree (defensive): recurse
            out[k] = prep_layer_params(a, compute_dtype)
            continue
        scale = lparams.get(k + "__scale")
        if scale is not None and jnp.issubdtype(a.dtype, jnp.integer):
            base = a.astype(compute_dtype) * scale.astype(compute_dtype)
        elif jnp.issubdtype(a.dtype, jnp.floating):
            base = a.astype(compute_dtype)
        else:
            out[k] = a
            continue
        la = lparams.get(k + "__lora_a")
        lb = lparams.get(k + "__lora_b")
        if la is not None and lb is not None:
            delta = la.astype(compute_dtype) @ lb.astype(compute_dtype)
            ls = lparams.get(k + "__lora_scale")
            if ls is not None:
                delta = delta * ls.astype(compute_dtype)
            base = base + delta
        out[k] = base
    return out


def init_layer_state(conf: Layer, dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    state = {}
    for name, shape in conf.state_shapes().items():
        if isinstance(conf, BatchNormalization) and name == "var":
            state[name] = jnp.ones(shape, dtype)
        elif isinstance(conf, BottleneckBlock) and name.startswith("var_"):
            state[name] = jnp.ones(shape, dtype)
        else:
            state[name] = jnp.zeros(shape, dtype)
    return state


def num_params(conf: Layer) -> int:
    return int(sum(np.prod(s) for s in conf.param_shapes().values()))


def flatten_params(params: Dict[str, Dict[str, jnp.ndarray]], layer_keys: List[str],
                   param_orders: Dict[str, List[str]]) -> np.ndarray:
    """Flatten to the reference-style contiguous 1-D view (c-order per param)."""
    chunks = []
    for lk in layer_keys:
        for pn in param_orders[lk]:
            chunks.append(np.asarray(params[lk][pn]).reshape(-1))
    if not chunks:
        return np.zeros((0,), np.float32)
    return np.concatenate(chunks)


def unflatten_params(flat: np.ndarray, template: Dict[str, Dict[str, jnp.ndarray]],
                     layer_keys: List[str], param_orders: Dict[str, List[str]]):
    """Inverse of `flatten_params`, shaped like `template`."""
    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    pos = 0
    for lk in layer_keys:
        out[lk] = {}
        for pn in param_orders[lk]:
            ref = template[lk][pn]
            n = int(np.prod(ref.shape))
            out[lk][pn] = jnp.asarray(
                np.asarray(flat[pos : pos + n]).reshape(ref.shape), ref.dtype
            )
            pos += n
    if pos != flat.size:
        raise ValueError(f"Flat param length {flat.size} != expected {pos}")
    return out
