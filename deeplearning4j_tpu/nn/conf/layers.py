"""Layer configuration classes.

Equivalent of the reference's `nn/conf/layers/*` (one config class per layer
type; inventory in SURVEY.md §2). Configs are JSON-serializable dataclasses
carrying hyperparameters and shape-inference logic; the forward math lives in
`deeplearning4j_tpu.nn.layers.*` and is looked up by config class name — the
TPU analog of the reference's conf/impl split, minus the helper SPI (XLA lowers
conv/BN/LSTM directly; no cuDNN-style plug-in point is needed).

Unset per-layer hyperparameters (None) inherit the builder's global defaults at
build time, matching `NeuralNetConfiguration.Builder` semantics.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.nn.conf.distributions import Distribution
from deeplearning4j_tpu.nn.conf.enums import (
    Activation,
    ConvolutionMode,
    GradientNormalization,
    LossFunction,
    PoolingType,
    Updater,
    WeightInit,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType

_LAYER_REGISTRY: Dict[str, type] = {}


def register_layer(cls):
    _LAYER_REGISTRY[cls.__name__] = cls
    return cls


def layer_from_dict(d: dict):
    d = dict(d)
    kind = d.pop("@class")
    cls = _LAYER_REGISTRY.get(kind)
    if cls is None:
        raise ValueError(f"Unknown layer type in config JSON: {kind}")
    return cls.from_dict(d)


def is_bias_param(name: str) -> bool:
    """Single source of truth for bias-vs-weight param classification
    (shared with `nn/params.py` init and the engines' L1/L2 penalty)."""
    return (
        name in ("b", "vb", "beta")
        or name.startswith(("b_", "eb", "db"))
        # Per-branch BN shift params of the fused BottleneckBlock
        # (beta_a/beta_b/beta_c/beta_proj): bias semantics like "beta".
        or name.startswith("beta_")
        or name.endswith("B")
    )


def _tuple2(v) -> Tuple[int, int]:
    if isinstance(v, int):
        return (v, v)
    t = tuple(int(x) for x in v)
    if len(t) == 1:
        return (t[0], t[0])
    return t  # type: ignore[return-value]


@dataclass
class Layer:
    """Base layer config: per-layer hyperparameter overrides (None = inherit global).

    Mirrors the reference's `nn/conf/layers/Layer.java` builder fields.
    """

    name: Optional[str] = None
    activation: Optional[Any] = None
    weight_init: Optional[Any] = None
    dist: Optional[Distribution] = None
    learning_rate: Optional[float] = None
    bias_learning_rate: Optional[float] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    dropout: Optional[float] = None  # retain probability; 0/1/None disables
    # DropConnect: when true, `dropout` is applied to the INPUT WEIGHTS
    # instead of the input activations (reference: `conf.isUseDropConnect()`
    # read in `BaseLayer.preOutput:371-373` / `LSTMHelpers.java:98-101`).
    use_drop_connect: Optional[bool] = None
    bias_init: Optional[float] = None
    updater: Optional[Any] = None
    momentum: Optional[float] = None
    adam_mean_decay: Optional[float] = None
    adam_var_decay: Optional[float] = None
    rho: Optional[float] = None
    rms_decay: Optional[float] = None
    epsilon: Optional[float] = None
    gradient_normalization: Optional[Any] = None
    gradient_normalization_threshold: Optional[float] = None
    # Transfer learning / LoRA (nn/transfer.py, nn/lora.py). None keeps the
    # serialized conf byte-identical to pre-transfer checkpoints (to_dict
    # skips None fields). `frozen=True` excludes the layer's base params
    # from grads and updater state; `lora_rank` adds `<name>__lora_a/b`
    # sibling leaves for every 2-D weight (base weights become frozen,
    # adapters train).
    frozen: Optional[bool] = None
    lora_rank: Optional[int] = None
    lora_alpha: Optional[float] = None
    # A `jax.named_scope` of the layer's own around its forward (and, for
    # an output layer, its loss), inside the `L.<vertex>` scope the engine
    # opens around every vertex (`nn/engine.py::scope`): `lm.head` makes the
    # head's operations read `.../L.out/lm.head/...` in every HLO
    # operation's `op_name`, forward and backward, and a reader of a device
    # trace finds them by that name whatever the vertex is called. Used as
    # written: choose a dotted lower-case name (`family.part`). None (the
    # default) adds no scope beside the vertex's. Scopes are metadata: the
    # compiled program is the same with or without them.
    scope: Optional[str] = None

    # ---- shape inference ----
    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    def set_n_in(self, input_type: InputType, override: bool) -> None:
        """Infer n_in from the previous layer's output type (no-op by default)."""

    def default_preprocessor(self, input_type: InputType):
        return None

    # ---- params ----
    def param_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """Ordered mapping param-name -> shape (defines the flat-view order)."""
        return {}

    def state_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """Non-trainable state (e.g. batchnorm running stats)."""
        return {}

    def weight_param_keys(self) -> Sequence[str]:
        """Params treated as weights for L1/L2 and weight-init purposes.
        Biases are never regularized (reference semantics)."""
        return [k for k in self.param_shapes() if not is_bias_param(k)]

    def has_params(self) -> bool:
        return bool(self.param_shapes())

    def frozen_param_names(self) -> Sequence[str]:
        """Params that never train, whatever `frozen` says (`nn/transfer.py`
        puts them in the frozen spec: no gradient, no updater state)."""
        return ()

    def full_precision_param_names(self) -> Sequence[str]:
        """Params used as they are stored, never cast to the dtype policy's
        compute dtype (`nn/params.py::prep_layer_params`): a norm's scale.
        bfloat16 has 8 bits, so a scale of 1 + d reads 1 until d passes
        0.002: under `mixed_bfloat16` a fine-tune's changes to it would
        never reach the forward pass (PERF.md PR 26)."""
        return ()

    def is_pretrainable(self) -> bool:
        return False

    # ---- serde ----
    def to_dict(self) -> dict:
        d: Dict[str, Any] = {"@class": type(self).__name__}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is None:
                continue
            if isinstance(v, Distribution):
                v = v.to_dict()
            elif isinstance(v, (Activation, WeightInit, Updater, LossFunction,
                                GradientNormalization, PoolingType, ConvolutionMode)):
                v = v.value
            elif isinstance(v, tuple):
                v = list(v)
            d[f.name] = v
        return d

    @classmethod
    def from_dict(cls, d: dict):
        kwargs = dict(d)
        if "dist" in kwargs and isinstance(kwargs["dist"], dict):
            kwargs["dist"] = Distribution.from_dict(kwargs["dist"])
        names = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in kwargs.items() if k in names}
        for key in ("kernel_size", "stride", "padding", "pooling_dimensions",
                    "encoder_layer_sizes", "decoder_layer_sizes",
                    "experts_held"):
            if key in kwargs and isinstance(kwargs[key], list):
                kwargs[key] = tuple(kwargs[key])
        return cls(**kwargs)


@dataclass
class FeedForwardLayer(Layer):
    """Base for layers with explicit n_in/n_out (reference: `FeedForwardLayer.java`)."""

    n_in: int = 0
    n_out: int = 0

    def get_output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            return InputType.recurrent(self.n_out, input_type.timeseries_length)
        return InputType.feed_forward(self.n_out)

    def set_n_in(self, input_type: InputType, override: bool) -> None:
        if override or not self.n_in:
            self.n_in = input_type.flat_size()

    def default_preprocessor(self, input_type: InputType):
        from deeplearning4j_tpu.nn.conf.preprocessors import CnnToFeedForwardPreProcessor
        if input_type.kind == "cnn":
            return CnnToFeedForwardPreProcessor(
                input_type.height, input_type.width, input_type.channels
            )
        return None

    def param_shapes(self) -> Dict[str, Tuple[int, ...]]:
        return {"W": (self.n_in, self.n_out), "b": (self.n_out,)}


@register_layer
@dataclass
class DenseLayer(FeedForwardLayer):
    """Fully-connected layer (reference: `nn/conf/layers/DenseLayer.java`)."""


@register_layer
@dataclass
class GatedDenseLayer(FeedForwardLayer):
    """Gated SiLU MLP, `W_down(silu(W_gate x) * W_up x)` with no biases
    (Shazeer 2020's SwiGLU; the dense FFN of the Llama and DeepSeek
    families): `hidden` is the inner width (0 -> 4 * n_in at build time),
    n_out the model width. `activation` (identity) applies to the result."""

    hidden: int = 0
    activation: Any = "identity"

    def set_n_in(self, input_type: InputType, override: bool) -> None:
        super().set_n_in(input_type, override)
        if not self.hidden:
            self.hidden = 4 * self.n_in

    def param_shapes(self):
        h = self.hidden or 4 * self.n_in
        return {"W_gate": (self.n_in, h), "W_up": (self.n_in, h),
                "W_down": (h, self.n_out)}


@register_layer
@dataclass
class BaseOutputLayer(FeedForwardLayer):
    loss_function: Any = LossFunction.MCXENT
    # None/True: `W` and `b` as ever; False: no bias (an LM head).
    has_bias: Optional[bool] = None

    def param_shapes(self):
        shapes = super().param_shapes()
        if self.has_bias is False:
            shapes.pop("b", None)
        return shapes

    def to_dict(self):
        d = super().to_dict()
        lf = self.loss_function
        d["loss_function"] = lf.value if isinstance(lf, LossFunction) else str(lf)
        return d


@register_layer
@dataclass
class OutputLayer(BaseOutputLayer):
    """Dense + loss output layer (reference: `nn/conf/layers/OutputLayer.java`)."""


@register_layer
@dataclass
class RnnOutputLayer(BaseOutputLayer):
    """Per-timestep output layer for RNNs (reference: `RnnOutputLayer.java`)."""

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timeseries_length)

    def default_preprocessor(self, input_type: InputType):
        from deeplearning4j_tpu.nn.conf.preprocessors import FeedForwardToRnnPreProcessor
        if input_type.kind == "ff":
            return FeedForwardToRnnPreProcessor()
        return None


@register_layer
@dataclass
class LossLayer(BaseOutputLayer):
    """Loss-only layer, no params (reference: `nn/conf/layers/LossLayer.java`)."""

    def param_shapes(self):
        return {}

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    def set_n_in(self, input_type, override):
        self.n_in = self.n_out = input_type.flat_size()


@register_layer
@dataclass
class CenterLossOutputLayer(BaseOutputLayer):
    """Output layer with center loss (reference: `CenterLossOutputLayer.java`).

    Maintains per-class feature centers as non-trainable state updated with
    EMA rate `alpha`; adds `lambda_ * ||f - c_y||^2 / 2` to the loss.
    """

    alpha: float = 0.05
    lambda_: float = 2e-4

    def state_shapes(self):
        return {"centers": (self.n_out, self.n_in)}


@register_layer
@dataclass
class ActivationLayer(Layer):
    """Activation-only layer (reference: `ActivationLayer.java`)."""

    n_in: int = 0
    n_out: int = 0

    def set_n_in(self, input_type, override):
        self.n_in = self.n_out = input_type.flat_size()


@register_layer
@dataclass
class DropoutLayer(FeedForwardLayer):
    """Dropout-only layer (reference: `DropoutLayer.java`)."""

    def param_shapes(self):
        return {}

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    def set_n_in(self, input_type, override):
        self.n_in = self.n_out = input_type.flat_size()


@register_layer
@dataclass
class EmbeddingLayer(FeedForwardLayer):
    """Index -> vector lookup (reference: `EmbeddingLayer.java`).

    Input: integer indices `[batch]` or one-hot `[batch, n_in]`. TPU-native
    implementation is a gather (`take`), not a onehot-matmul.

    `input_format` pins the interpretation: "auto" (float with last dim
    == n_in reads as one-hot, everything else as indices — ambiguous when
    the sequence length equals n_in), "ids" (always indices), "onehot"
    (always one-hot). The transformer zoo builders pin "ids".
    """

    has_bias: bool = True
    input_format: str = "auto"  # "auto" | "ids" | "onehot"

    def param_shapes(self):
        shapes = {"W": (self.n_in, self.n_out)}
        if self.has_bias:
            shapes["b"] = (self.n_out,)
        return shapes


@register_layer
@dataclass
class ConvolutionLayer(FeedForwardLayer):
    """2-D convolution (reference: `nn/conf/layers/ConvolutionLayer.java`).

    n_in = input channels, n_out = output filters. Kernel stored HWIO
    `[kh, kw, in, out]` (XLA-native); NHWC activations.
    """

    kernel_size: Tuple[int, int] = (5, 5)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: Optional[Any] = None  # None -> builder global (default TRUNCATE)
    dilation: Tuple[int, int] = (1, 1)
    has_bias: bool = True

    def __post_init__(self):
        self.kernel_size = _tuple2(self.kernel_size)
        self.stride = _tuple2(self.stride)
        self.padding = _tuple2(self.padding)
        self.dilation = _tuple2(self.dilation)

    def _out_hw(self, h: int, w: int) -> Tuple[int, int]:
        mode = ConvolutionMode.of(self.convolution_mode) or ConvolutionMode.TRUNCATE
        kh, kw = self.kernel_size
        sh, sw = self.stride
        ph, pw = self.padding
        if mode == ConvolutionMode.SAME:
            return (-(-h // sh), -(-w // sw))
        oh = (h + 2 * ph - kh) // sh + 1
        ow = (w + 2 * pw - kw) // sw + 1
        if mode == ConvolutionMode.STRICT:
            if (h + 2 * ph - kh) % sh != 0 or (w + 2 * pw - kw) % sw != 0:
                raise ValueError(
                    f"ConvolutionMode.STRICT: input {h}x{w} with kernel {self.kernel_size}, "
                    f"stride {self.stride}, padding {self.padding} doesn't tile exactly "
                    f"(reference `ConvolutionMode.java` semantics; use TRUNCATE or SAME)"
                )
        return (oh, ow)

    def get_output_type(self, input_type: InputType) -> InputType:
        oh, ow = self._out_hw(input_type.height, input_type.width)
        return InputType.convolutional(oh, ow, self.n_out)

    def set_n_in(self, input_type: InputType, override: bool) -> None:
        if override or not self.n_in:
            self.n_in = input_type.channels

    def default_preprocessor(self, input_type: InputType):
        from deeplearning4j_tpu.nn.conf.preprocessors import FeedForwardToCnnPreProcessor
        if input_type.kind == "cnnflat":
            return FeedForwardToCnnPreProcessor(
                input_type.height, input_type.width, input_type.channels
            )
        return None

    def param_shapes(self):
        kh, kw = self.kernel_size
        shapes = {"W": (kh, kw, self.n_in, self.n_out)}
        if self.has_bias:
            shapes["b"] = (self.n_out,)
        return shapes


@register_layer
@dataclass
class SubsamplingLayer(Layer):
    """Spatial pooling (reference: `SubsamplingLayer.java`). No params."""

    pooling_type: Any = PoolingType.MAX
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: Optional[Any] = None
    pnorm: int = 2

    def __post_init__(self):
        self.kernel_size = _tuple2(self.kernel_size)
        self.stride = _tuple2(self.stride)
        self.padding = _tuple2(self.padding)

    def get_output_type(self, input_type: InputType) -> InputType:
        helper = ConvolutionLayer(
            kernel_size=self.kernel_size, stride=self.stride, padding=self.padding,
            convolution_mode=self.convolution_mode, n_out=input_type.channels,
        )
        oh, ow = helper._out_hw(input_type.height, input_type.width)
        return InputType.convolutional(oh, ow, input_type.channels)


@register_layer
@dataclass
class BatchNormalization(FeedForwardLayer):
    """Batch normalization (reference: `nn/conf/layers/BatchNormalization.java:28-30`:
    decay 0.9, eps 1e-5, minibatch flag, optional locked gamma/beta)."""

    decay: float = 0.9
    eps: float = 1e-5
    is_minibatch: bool = True
    lock_gamma_beta: bool = False
    gamma: float = 1.0
    beta: float = 0.0

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    def set_n_in(self, input_type, override):
        if override or not self.n_out:
            self.n_in = self.n_out = input_type.flat_size() if input_type.kind in ("ff", "rnn") \
                else input_type.channels

    def default_preprocessor(self, input_type):
        return None

    def param_shapes(self):
        if self.lock_gamma_beta:
            return {}
        return {"gamma": (self.n_out,), "beta": (self.n_out,)}

    def state_shapes(self):
        return {"mean": (self.n_out,), "var": (self.n_out,)}


@register_layer
@dataclass
class BottleneckBlock(FeedForwardLayer):
    """Fused ResNet bottleneck block (PR 19): conv1x1 -> BN+act ->
    conv3x3 -> BN+act -> conv1x1 -> BN -> residual add -> act as ONE
    layer, dispatched through the `bottleneck_block` kernel seam
    (`kernels/bottleneck_block.py`). The unfused equivalent is the
    five-vertex chain `models/resnet.py::_bottleneck` emits; this layer
    is what `resnet50(fused_blocks=True)` emits instead — plain conv
    stacks are untouched.

    `filters` is the squeeze width (branch a/b); the block's output is
    `4 * filters` channels. `project=True` adds the 1x1 projection
    shortcut (stage boundaries); otherwise the input rides the residual
    unchanged (requires n_in == 4 * filters, the resnet invariant).
    BN hyperparameters mirror `BatchNormalization` (decay 0.9, eps 1e-5,
    minibatch stats in train mode).
    """

    filters: int = 64
    stride: Tuple[int, int] = (1, 1)
    project: bool = False
    decay: float = 0.9
    eps: float = 1e-5
    is_minibatch: bool = True

    def __post_init__(self):
        self.stride = _tuple2(self.stride)

    def branch_names(self) -> Tuple[str, ...]:
        return ("a", "b", "c") + (("proj",) if self.project else ())

    def get_output_type(self, input_type: InputType) -> InputType:
        sh, sw = self.stride
        return InputType.convolutional(
            -(-input_type.height // sh), -(-input_type.width // sw),
            4 * self.filters)

    def set_n_in(self, input_type: InputType, override: bool) -> None:
        if override or not self.n_in:
            self.n_in = input_type.channels
        self.n_out = 4 * self.filters

    def default_preprocessor(self, input_type: InputType):
        # NHWC in, NHWC out — never flatten (overrides FeedForwardLayer's
        # CnnToFeedForward default).
        return None

    def param_shapes(self):
        f1, f3 = self.filters, 4 * self.filters
        shapes = {
            "W_a": (1, 1, self.n_in, f1), "gamma_a": (f1,), "beta_a": (f1,),
            "W_b": (3, 3, f1, f1), "gamma_b": (f1,), "beta_b": (f1,),
            "W_c": (1, 1, f1, f3), "gamma_c": (f3,), "beta_c": (f3,),
        }
        if self.project:
            shapes.update({"W_proj": (1, 1, self.n_in, f3),
                           "gamma_proj": (f3,), "beta_proj": (f3,)})
        return shapes

    def state_shapes(self):
        f1, f3 = self.filters, 4 * self.filters
        shapes = {"mean_a": (f1,), "var_a": (f1,),
                  "mean_b": (f1,), "var_b": (f1,),
                  "mean_c": (f3,), "var_c": (f3,)}
        if self.project:
            shapes.update({"mean_proj": (f3,), "var_proj": (f3,)})
        return shapes


@register_layer
@dataclass
class LocalResponseNormalization(Layer):
    """Cross-channel LRN (reference: `LocalResponseNormalization.java`;
    defaults k=2, n=5, alpha=1e-4, beta=0.75). No params."""

    k: float = 2.0
    n: float = 5.0
    alpha: float = 1e-4
    beta: float = 0.75


@register_layer
@dataclass
class BaseRecurrentLayer(FeedForwardLayer):
    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timeseries_length)

    def default_preprocessor(self, input_type: InputType):
        from deeplearning4j_tpu.nn.conf.preprocessors import (
            FeedForwardToRnnPreProcessor, CnnToRnnPreProcessor,
        )
        if input_type.kind == "ff":
            return FeedForwardToRnnPreProcessor()
        if input_type.kind == "cnn":
            return CnnToRnnPreProcessor(input_type.height, input_type.width, input_type.channels)
        return None


@register_layer
@dataclass
class GravesLSTM(BaseRecurrentLayer):
    """LSTM with peephole connections (reference: `nn/conf/layers/GravesLSTM.java`,
    impl semantics `nn/layers/recurrent/LSTMHelpers.java:58-160`).

    Params: `W` input weights `[n_in, 4*n_out]` (gate order i,f,o,g),
    `RW` recurrent weights `[n_out, 4*n_out]`, `pW` peepholes `[3*n_out]`
    (f,o,g order as in the reference's 3 extra columns), `b` `[4*n_out]` with
    forget-gate bias init. The reference packs peepholes into RW's last 3
    columns; we keep a separate leaf (same dof, cleaner sharding).
    """

    forget_gate_bias_init: float = 1.0
    gate_activation: Any = Activation.SIGMOID

    def param_shapes(self):
        return {
            "W": (self.n_in, 4 * self.n_out),
            "RW": (self.n_out, 4 * self.n_out),
            "pW": (3 * self.n_out,),
            "b": (4 * self.n_out,),
        }


@register_layer
@dataclass
class LSTM(BaseRecurrentLayer):
    """Standard LSTM without peepholes (cuDNN-compatible variant)."""

    forget_gate_bias_init: float = 1.0
    gate_activation: Any = Activation.SIGMOID

    def param_shapes(self):
        return {
            "W": (self.n_in, 4 * self.n_out),
            "RW": (self.n_out, 4 * self.n_out),
            "b": (4 * self.n_out,),
        }


@register_layer
@dataclass
class GravesBidirectionalLSTM(BaseRecurrentLayer):
    """Bidirectional peephole LSTM (reference: `GravesBidirectionalLSTM.java`).
    Output is the sum of forward and backward passes (reference semantics)."""

    forget_gate_bias_init: float = 1.0
    gate_activation: Any = Activation.SIGMOID

    def param_shapes(self):
        return {
            "W_f": (self.n_in, 4 * self.n_out),
            "RW_f": (self.n_out, 4 * self.n_out),
            "pW_f": (3 * self.n_out,),
            "b_f": (4 * self.n_out,),
            "W_b": (self.n_in, 4 * self.n_out),
            "RW_b": (self.n_out, 4 * self.n_out),
            "pW_b": (3 * self.n_out,),
            "b_b": (4 * self.n_out,),
        }


@register_layer
@dataclass
class SimpleRnn(BaseRecurrentLayer):
    """Vanilla RNN: h_t = act(x_t W + h_{t-1} RW + b)."""

    def param_shapes(self):
        return {
            "W": (self.n_in, self.n_out),
            "RW": (self.n_out, self.n_out),
            "b": (self.n_out,),
        }


@register_layer
@dataclass
class LayerNormalization(FeedForwardLayer):
    """Per-example layer norm over the feature axis (gamma/beta learned).

    No reference equivalent (the reference predates LN; its normalizer is
    BatchNormalization) — added for the transformer model family
    (`models/zoo.transformer_lm`), where batch statistics are wrong for
    autoregressive training. Works on [B, F] and [B, T, F]."""

    eps: float = 1e-5
    activation: Any = "identity"

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    def set_n_in(self, input_type, override):
        self.n_in = self.n_out = input_type.flat_size()

    def param_shapes(self):
        return {"gamma": (self.n_out,), "beta": (self.n_out,)}


@register_layer
@dataclass
class RMSNormalization(FeedForwardLayer):
    """Root-mean-square norm over the feature axis, `x / rms(x) * gamma`
    (Zhang & Sennrich 2019): no mean, no shift. Statistics in at least
    float32 whatever the compute dtype. Works on [B, F] and [B, T, F]."""

    eps: float = 1e-6
    activation: Any = "identity"

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    def set_n_in(self, input_type, override):
        self.n_in = self.n_out = input_type.flat_size()

    def param_shapes(self):
        return {"gamma": (self.n_out,)}

    def full_precision_param_names(self):
        return ("gamma",)


@register_layer
@dataclass
class PositionalEmbeddingLayer(FeedForwardLayer):
    """Learned position table added to a [B, T, F] sequence (GPT-style).

    No reference equivalent (predates transformers); feeds
    `models/zoo.transformer_lm`. `max_length` rows are allocated; forward
    slices the first T (T <= max_length enforced at trace time).

    `stateful=True` adds a position cursor to the layer's (undeclared)
    state, so single-token decode steps via `rnn_time_step` get the right
    position rows (set by `transformer_lm(decode_cache_length=...)`).
    Default False: every forward starts at position 0, preserving plain /
    tBPTT semantics."""

    max_length: int = 512
    stateful: bool = False
    activation: Any = "identity"

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    def set_n_in(self, input_type, override):
        self.n_in = self.n_out = input_type.flat_size()

    def param_shapes(self):
        return {"P": (self.max_length, self.n_out)}


@register_layer
@dataclass
class SelfAttentionLayer(BaseRecurrentLayer):
    """Multi-head self-attention over a [B, T, F] sequence.

    No reference equivalent (the reference predates attention; its
    long-sequence mechanism is tBPTT, `MultiLayerNetwork.java:1207`) —
    this is SURVEY.md §5's named TPU-native extension, surfaced through
    the config DSL. The impl (`nn/layers/attention.py`) picks the Pallas
    flash kernel, masked XLA dense, or mesh-sharded ring attention at
    trace time from the active `ParallelContext`.

    n_in = input feature size, n_out = model width (divisible by
    n_heads). `activation` defaults to identity (an attention block is
    linear after the softmax-weighted sum; set it explicitly to opt in).
    """

    n_heads: int = 4
    causal: bool = True
    # "auto" (Pallas flash; ring when seq-sharded) | "dense" (XLA oracle) |
    # "ulysses" (all-to-all head sharding when seq-sharded; flash otherwise)
    attention_impl: str = "auto"
    # KV-cache capacity for stateful decode via `rnn_time_step` (None
    # disables). The layer always emits its cache as undeclared state; the
    # engines persist it only on the stateful path, and XLA dead-code-
    # eliminates it everywhere else, so training cost is zero.
    decode_cache_length: Optional[int] = None
    activation: Any = "identity"
    # Grouped-query / rotary / sparse attention (`nn/layers/dsa.py`). All
    # None: the layer above, its params and its JSON unchanged. Any set:
    # `n_heads` query heads of `head_dim` (default n_out / n_heads) over
    # `n_kv_heads` key/value heads (default n_heads), no biases, rotate-half
    # RoPE at `rope_theta` in place of a position table, RMS norm over each
    # q and k head at `qk_norm_eps`.
    n_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    rope_theta: Optional[float] = None
    qk_norm_eps: Optional[float] = None
    # Learned sparse attention (DeepSeek sparse attention's indexer): with
    # `index_top_k` set, `index_n_heads` heads of `index_head_dim` over one
    # shared key head score every earlier position, and each query attends
    # to its `index_top_k` best keys only (all of them while there are
    # fewer). The indexer's five leaves never train (`frozen_param_names`).
    index_top_k: Optional[int] = None
    index_n_heads: Optional[int] = None
    index_head_dim: Optional[int] = None
    # Sliding-window attention: query t reads the keys t - sliding_window <
    # s <= t (`sliding_window` keys, its own among them: the `transformers`
    # convention). `rope_scaling`: None, or YaRN's parameters under their
    # `transformers` names (`rope_type` "yarn", `factor`,
    # `original_max_position_embeddings`, `beta_fast`, `beta_slow`,
    # `attention_factor`; `dsa.rope_frequencies` has the equations). A layer
    # without `index_top_k` runs the registry's `banded_attention`: no
    # `[S, S]` mask is built, and with a window only the tiles that meet
    # the band are visited.
    sliding_window: Optional[int] = None
    rope_scaling: Optional[dict] = None
    # Multi-head latent attention (DeepSeek-V2's MLA, the fields under their
    # `transformers` names; `dsa._latent_sequence` has the equations): with
    # `kv_lora_rank` set, keys and values come from one compressed
    # projection `Wdkv` to `kv_lora_rank + qk_rope_head_dim` columns, the
    # latent under an RMS norm (`gamma_kv`, eps 1e-6: `dsa.LATENT_NORM_EPS`)
    # and expanded by `Wukv` to `n_heads` heads of `qk_nope_head_dim +
    # v_head_dim`; a query head is `qk_nope_head_dim + qk_rope_head_dim`
    # wide, only the rope part is rotated, and its key part is ONE head that
    # all query heads share. Scores are scaled by (nope + rope)^-1/2. The
    # core is the registry's `latent_attention`, under the scope
    # `mla.attend`; the projections and the latent norm under `mla.project`.
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None

    INDEXER_PARAMS = ("Wiq", "Wik", "Wiw", "gamma_ik", "beta_ik")

    def __post_init__(self):
        if self.kv_lora_rank is None:
            return
        if None in (self.qk_nope_head_dim, self.qk_rope_head_dim,
                    self.v_head_dim, self.rope_theta):
            raise ValueError(
                "kv_lora_rank needs qk_nope_head_dim, qk_rope_head_dim, "
                "v_head_dim and rope_theta")
        if any(v is not None for v in (
                self.n_kv_heads, self.head_dim, self.qk_norm_eps,
                self.index_top_k, self.sliding_window,
                self.rope_scaling)) or not self.causal:
            raise ValueError(
                "a latent-attention layer (kv_lora_rank) is causal and "
                "takes none of n_kv_heads, head_dim, qk_norm_eps, "
                "index_top_k, sliding_window, rope_scaling")

    def is_extended(self) -> bool:
        return any(v is not None for v in (
            self.n_kv_heads, self.head_dim, self.rope_theta,
            self.qk_norm_eps, self.index_top_k, self.sliding_window,
            self.rope_scaling, self.kv_lora_rank))

    def attention_scope(self) -> str:
        """The `jax.named_scope` around the attention of an extended layer
        without an indexer, by its kind."""
        if self.kv_lora_rank is not None:
            return "mla.attend"
        return "attn.full" if self.sliding_window is None else "attn.sliding"

    def param_shapes(self):
        if self.is_extended():
            return self._extended_param_shapes()
        # No key bias: softmax is invariant to the per-query constant q·kB
        # adds to every score, so kB's true gradient is identically zero —
        # a degenerate parameter that adaptive updaters would random-walk.
        return {
            "Wq": (self.n_in, self.n_out), "qB": (self.n_out,),
            "Wk": (self.n_in, self.n_out),
            "Wv": (self.n_in, self.n_out), "vB": (self.n_out,),
            "Wo": (self.n_out, self.n_out), "oB": (self.n_out,),
        }

    def _extended_param_shapes(self):
        H = self.n_heads
        if self.kv_lora_rank is not None:
            R, Dn, Dr, Dv = (self.kv_lora_rank, self.qk_nope_head_dim,
                             self.qk_rope_head_dim, self.v_head_dim)
            return {"Wq": (self.n_in, H * (Dn + Dr)),
                    "Wdkv": (self.n_in, R + Dr), "gamma_kv": (R,),
                    "Wukv": (R, H * (Dn + Dv)), "Wo": (H * Dv, self.n_out)}
        KV = self.n_kv_heads or H
        Dh = self.head_dim or self.n_out // H
        if H % KV:
            raise ValueError(f"n_heads ({H}) must be a multiple of "
                             f"n_kv_heads ({KV})")
        shapes = {"Wq": (self.n_in, H * Dh), "Wk": (self.n_in, KV * Dh),
                  "Wv": (self.n_in, KV * Dh), "Wo": (H * Dh, self.n_out)}
        if self.qk_norm_eps is not None:
            shapes.update(gamma_q=(Dh,), gamma_k=(Dh,))
        if self.index_top_k is not None:
            IH, ID = self.index_n_heads, self.index_head_dim
            shapes.update(Wiq=(self.n_in, IH * ID), Wik=(self.n_in, ID),
                          Wiw=(self.n_in, IH), gamma_ik=(ID,), beta_ik=(ID,))
        return shapes

    def frozen_param_names(self):
        return self.INDEXER_PARAMS if self.index_top_k is not None else ()

    def full_precision_param_names(self):
        # the q/k norms' scales, the indexer's key norm and the latent norm's
        # scale (extended path)
        return ("gamma_q", "gamma_k", "gamma_ik", "beta_ik", "gamma_kv")

    def state_shapes(self):
        # Mean number of keys a query attends to, of the last forward pass
        # (`dl4j_dsa_selected_keys_mean`, read where the score is read);
        # without an indexer, the share of the pairs in the tiles the Pallas
        # body visits that lie inside the causal band, 0 from the XLA body
        # (`dl4j_attn_band_fill_share`).
        if self.index_top_k is not None:
            return {"selected_keys_mean": ()}
        return {"band_fill_share": ()} if self.is_extended() else {}


@register_layer
@dataclass
class MoELayer(FeedForwardLayer):
    """Mixture-of-experts FFN with GShard routing (top-1/top-2, capacity
    dropping, router jitter, load-balance aux loss).

    No reference equivalent (predates MoE; SURVEY.md §2.3 extension row).
    The engines fold `aux_loss_weight * load_balance_loss` into the
    training objective; under a `ParallelContext` with an expert axis the
    experts shard across the mesh (`nn/layers/moe.py`).

    n_in = n_out = model width; `expert_hidden` is each expert's FFN
    hidden size (the expert MLP's own ReLU is fixed — `activation`
    defaults to identity and applies to the combined output).
    """

    n_experts: int = 4
    expert_hidden: int = 0  # 0 -> 4 * n_in at build time
    capacity_factor: float = 1.25
    top_k: int = 2
    router_jitter: float = 0.0
    aux_loss_weight: float = 1e-2
    activation: Any = "identity"
    # Dropless top-k routing (`parallel/expert.py::moe_ffn_dropless`): any
    # `top_k`, no capacity and no dropped token; (token, expert) pairs are
    # sorted by expert and each matrix is one grouped product. Its experts
    # are gated, `W_down(silu(W_gate x) * W_up x)` without biases, not the
    # two-layer ReLU FFN. `norm_topk_prob`: the k gate values are
    # renormalised to sum to 1. `experts_held = (first, count)`: the layer
    # holds only those experts' weights, routes over all `n_experts` and
    # computes its own experts' part of the sum (the other parts are other
    # devices'; under a `ParallelContext` with an expert axis the layer
    # holds all experts and each device takes its share by its index).
    dropless: Optional[bool] = None
    norm_topk_prob: Optional[bool] = None
    experts_held: Optional[Tuple[int, int]] = None
    # The dropless router's scoring. None: softmax over all experts, the
    # weights the chosen probabilities. "sigmoid" (DeepSeek-V3's `noaux_tc`
    # with one group, `expert.route_top_k`): each expert's own sigmoid
    # score; the choice is the top_k of score + `gate_b`, a selection bias
    # leaf [n_experts] that is float32 whatever the policy and frozen (no
    # gradient reaches it and it has no updater state; zeros at init); the
    # weights are the unbiased scores, normalised with `norm_topk_prob` and
    # multiplied by `routed_scaling_factor`; the balance term is the
    # sequence-wise one (`expert.sequence_balance`). `shared_hidden`: a
    # shared expert beside the routed ones, one gated SiLU MLP of that width
    # over every token (scope `moe.shared`), held whole whatever
    # `experts_held` says.
    scoring: Optional[str] = None
    routed_scaling_factor: Optional[float] = None
    shared_hidden: Optional[int] = None

    SHARED_PARAMS = ("shared_gate", "shared_up", "shared_down")

    def __post_init__(self):
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)
        if any(v is not None for v in (
                self.experts_held, self.norm_topk_prob, self.scoring,
                self.routed_scaling_factor, self.shared_hidden)) \
                and not self.dropless:
            raise ValueError(
                "norm_topk_prob, experts_held, scoring, "
                "routed_scaling_factor and shared_hidden belong to the "
                "dropless path: set dropless=True")
        if self.scoring not in (None, "softmax", "sigmoid"):
            raise ValueError(f"scoring {self.scoring!r}: softmax or sigmoid")
        if self.routed_scaling_factor is not None \
                and self.scoring != "sigmoid":
            raise ValueError("routed_scaling_factor scales the sigmoid "
                             "router's weights: set scoring='sigmoid'")

    def set_n_in(self, input_type: InputType, override: bool) -> None:
        super().set_n_in(input_type, override)
        if not self.expert_hidden:
            self.expert_hidden = 4 * self.n_in

    def held(self) -> Tuple[int, int]:
        first, count = self.experts_held or (0, self.n_experts)
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} is not "
                             f"inside 0..{self.n_experts}")
        return first, count

    def param_shapes(self):
        E, h = self.n_experts, self.expert_hidden or 4 * self.n_in
        if self.dropless:
            Eh = self.held()[1]
            shapes = {"gate_w": (self.n_in, E),
                      "w_gate": (Eh, self.n_in, h),
                      "w_up": (Eh, self.n_in, h),
                      "w_down": (Eh, h, self.n_out)}
            if self.scoring == "sigmoid":
                shapes["gate_b"] = (E,)
            if self.shared_hidden:
                hs = self.shared_hidden
                shapes.update(shared_gate=(self.n_in, hs),
                              shared_up=(self.n_in, hs),
                              shared_down=(hs, self.n_out))
            return shapes
        return {
            "gate_w": (self.n_in, E),
            "w1": (E, self.n_in, h), "b_1": (E, h),
            "w2": (E, h, self.n_out), "b_2": (E, self.n_out),
        }

    def frozen_param_names(self):
        return ("gate_b",)

    def full_precision_param_names(self):
        return ("gate_b",)

    def state_shapes(self):
        # Routing statistics of the last forward pass, read where the score
        # is read (`dl4j_moe_pairs_held_share`,
        # `dl4j_moe_expert_load_max_over_mean`).
        if self.dropless:
            return {"pairs_held_share": (), "expert_load_max_over_mean": ()}
        return {}


@register_layer
@dataclass
class GlobalPoolingLayer(Layer):
    """Global pooling over time or space (reference: `GlobalPoolingLayer.java`;
    SUM/AVG/MAX/PNORM, mask-aware)."""

    pooling_type: Any = PoolingType.MAX
    pooling_dimensions: Optional[Tuple[int, ...]] = None
    collapse_dimensions: bool = True
    pnorm: int = 2

    def get_output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            return InputType.feed_forward(input_type.size)
        if input_type.kind == "cnn":
            return InputType.feed_forward(input_type.channels)
        return input_type


@register_layer
@dataclass
class AutoEncoder(FeedForwardLayer):
    """Denoising autoencoder (reference: `nn/conf/layers/AutoEncoder.java`,
    impl `nn/layers/feedforward/autoencoder/AutoEncoder.java`). Pretrainable."""

    corruption_level: float = 0.3
    sparsity: float = 0.0
    loss_function: Any = LossFunction.RECONSTRUCTION_CROSSENTROPY

    def param_shapes(self):
        return {"W": (self.n_in, self.n_out), "b": (self.n_out,), "vb": (self.n_in,)}

    def is_pretrainable(self):
        return True


@register_layer
@dataclass
class RBM(FeedForwardLayer):
    """Restricted Boltzmann machine (reference: `nn/conf/layers/RBM.java:83-86`,
    contrastive divergence in `nn/layers/feedforward/rbm/RBM.java:101`).
    Visible/hidden unit types: binary | gaussian | softmax | rectified."""

    visible_unit: str = "binary"
    hidden_unit: str = "binary"
    k: int = 1  # CD-k steps
    sparsity: float = 0.0
    loss_function: Any = LossFunction.RECONSTRUCTION_CROSSENTROPY

    def param_shapes(self):
        return {"W": (self.n_in, self.n_out), "b": (self.n_out,), "vb": (self.n_in,)}

    def is_pretrainable(self):
        return True


@register_layer
@dataclass
class VariationalAutoencoder(FeedForwardLayer):
    """VAE (reference: `nn/conf/layers/variational/VariationalAutoencoder.java`,
    impl `nn/layers/variational/VariationalAutoencoder.java:48-79`): own
    encoder/decoder MLP stacks, pluggable reconstruction distribution,
    n_out = latent size. Pretrainable; supervised forward uses the mean."""

    encoder_layer_sizes: Tuple[int, ...] = (100,)
    decoder_layer_sizes: Tuple[int, ...] = (100,)
    # "gaussian" | "bernoulli" | "exponential", a loss wrapper
    # ("loss", loss_function[, activation]), or a composite list of
    # (spec, data_size) pairs (reference: `conf/layers/variational/`
    # ReconstructionDistribution SPI incl. Composite + LossFunctionWrapper).
    reconstruction_distribution: Any = "gaussian"
    pzx_activation: Any = Activation.IDENTITY
    num_samples: int = 1

    def param_shapes(self):
        shapes: Dict[str, Tuple[int, ...]] = {}
        prev = self.n_in
        for i, size in enumerate(self.encoder_layer_sizes):
            shapes[f"eW{i}"] = (prev, size)
            shapes[f"eb{i}"] = (size,)
            prev = size
        shapes["pZXMeanW"] = (prev, self.n_out)
        shapes["pZXMeanB"] = (self.n_out,)
        shapes["pZXLogStd2W"] = (prev, self.n_out)
        shapes["pZXLogStd2B"] = (self.n_out,)
        prev = self.n_out
        for i, size in enumerate(self.decoder_layer_sizes):
            shapes[f"dW{i}"] = (prev, size)
            shapes[f"db{i}"] = (size,)
            prev = size
        from deeplearning4j_tpu.nn.layers.variational import dist_input_size
        dist_size = dist_input_size(self.reconstruction_distribution, self.n_in)
        shapes["pXZW"] = (prev, dist_size)
        shapes["pXZB"] = (dist_size,)
        return shapes

    def is_pretrainable(self):
        return True
