"""NeuralNetConfiguration builder DSL and network configurations.

Equivalent of the reference's `nn/conf/NeuralNetConfiguration.java` (builder +
ListBuilder, `:200,478`), `MultiLayerConfiguration.java`, and
`ComputationGraphConfiguration.java` (GraphBuilder) — fluent builders producing
JSON-round-trippable configurations, with global hyperparameter defaults merged
into per-layer configs at build time and `InputType`-driven shape inference and
automatic preprocessor insertion (reference `ConvolutionLayerSetup.java:42`).

JSON round-trip is load-bearing in the reference (Spark broadcast, UI,
ModelSerializer) and is preserved here for checkpointing and serving.
"""

from __future__ import annotations

import copy
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from deeplearning4j_tpu.nn.conf.distributions import Distribution
from deeplearning4j_tpu.nn.conf.enums import (
    Activation,
    BackpropType,
    GradientNormalization,
    LearningRatePolicy,
    OptimizationAlgorithm,
    Updater,
    WeightInit,
    ConvolutionMode,
)
from deeplearning4j_tpu.nn.conf.graph import (
    GraphVertexConf,
    LayerVertex,
    vertex_from_dict,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import Layer, layer_from_dict
from deeplearning4j_tpu.nn.conf.preprocessors import (
    InputPreProcessor,
    preprocessor_from_dict,
)

# Per-layer fields that inherit from the builder's globals when unset
# (reference: NeuralNetConfiguration.Builder global defaults applied per layer).
_INHERITED_FIELDS = (
    "activation", "weight_init", "dist", "learning_rate", "bias_learning_rate",
    "l1", "l2", "dropout", "use_drop_connect", "bias_init", "updater", "momentum",
    "adam_mean_decay", "adam_var_decay", "rho", "rms_decay", "epsilon",
    "gradient_normalization", "gradient_normalization_threshold",
)


@dataclass
class GlobalConf:
    """Resolved global hyperparameters (reference: `NeuralNetConfiguration` fields)."""

    seed: int = 12345
    iterations: int = 1
    optimization_algo: Any = OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT
    learning_rate: float = 1e-1
    bias_learning_rate: Optional[float] = None
    lr_policy: Any = LearningRatePolicy.NONE
    lr_policy_decay_rate: float = 0.0
    lr_policy_power: float = 0.0
    lr_policy_steps: float = 1.0
    lr_schedule: Optional[Dict[int, float]] = None
    max_num_iterations: int = 1
    updater: Any = Updater.SGD
    momentum: float = 0.9
    adam_mean_decay: float = 0.9
    adam_var_decay: float = 0.999
    rho: float = 0.95
    rms_decay: float = 0.95
    epsilon: Optional[float] = None
    weight_init: Any = WeightInit.XAVIER
    dist: Optional[Distribution] = None
    activation: Any = Activation.SIGMOID
    bias_init: float = 0.0
    l1: float = 0.0
    l2: float = 0.0
    dropout: float = 0.0
    use_drop_connect: bool = False
    minimize: bool = True
    gradient_normalization: Any = GradientNormalization.NONE
    gradient_normalization_threshold: float = 1.0
    mini_batch: bool = True
    convolution_mode: Any = ConvolutionMode.TRUNCATE
    max_num_line_search_iterations: int = 5
    dtype: str = "float32"  # legacy dtype knob ("float32" | "bfloat16" | "float64")
    # First-class precision policy (nn/conf/dtype_policy.py): a DtypePolicy,
    # preset name, or dict. None = derive from the legacy `dtype` string.
    # Serialized ONLY when set, so default conf JSON — and the AOT
    # compile-cache fingerprints built from it — stay byte-identical.
    dtype_policy: Any = None
    # Superstep training: fuse up to K train iterations into ONE device
    # dispatch (lax.scan over stacked batches; PERF.md §13). 0/1 = per-batch
    # dispatch. Overridable at runtime via DL4J_TPU_SUPERSTEP_K.
    superstep_k: int = 0

    def to_dict(self):
        d = {}
        for k, v in self.__dict__.items():
            if k == "dtype_policy":
                if v is None:
                    continue  # unset policy serializes to nothing (bit-compat)
                from deeplearning4j_tpu.nn.conf.dtype_policy import DtypePolicy
                v = DtypePolicy.of(v).to_dict()
            elif isinstance(v, Distribution):
                v = v.to_dict()
            elif hasattr(v, "value") and not isinstance(v, (int, float, bool)):
                v = v.value
            d[k] = v
        return d

    @staticmethod
    def from_dict(d):
        d = dict(d or {})
        if isinstance(d.get("dist"), dict):
            d["dist"] = Distribution.from_dict(d["dist"])
        if d.get("dtype_policy") is not None:
            from deeplearning4j_tpu.nn.conf.dtype_policy import DtypePolicy
            d["dtype_policy"] = DtypePolicy.of(d["dtype_policy"])
        if d.get("lr_schedule"):
            d["lr_schedule"] = {int(k): float(v) for k, v in d["lr_schedule"].items()}
        g = GlobalConf()
        for k, v in d.items():
            if hasattr(g, k):
                setattr(g, k, v)
        return g


class NeuralNetConfiguration:
    """Entry point: `NeuralNetConfiguration.builder()` (reference `:478`)."""

    @staticmethod
    def builder() -> "Builder":
        return Builder()


class Builder:
    """Fluent global-hyperparameter builder (reference: `NeuralNetConfiguration.Builder`)."""

    def __init__(self):
        self._g = GlobalConf()

    # Each setter mirrors a reference builder method (camelCase -> snake_case).
    def seed(self, v): self._g.seed = int(v); return self
    def iterations(self, v): self._g.iterations = int(v); return self
    def optimization_algo(self, v): self._g.optimization_algo = OptimizationAlgorithm.of(v); return self
    def learning_rate(self, v): self._g.learning_rate = float(v); return self
    def bias_learning_rate(self, v): self._g.bias_learning_rate = float(v); return self
    def learning_rate_decay_policy(self, v): self._g.lr_policy = LearningRatePolicy.of(v); return self
    def lr_policy_decay_rate(self, v): self._g.lr_policy_decay_rate = float(v); return self
    def lr_policy_power(self, v): self._g.lr_policy_power = float(v); return self
    def lr_policy_steps(self, v): self._g.lr_policy_steps = float(v); return self
    def learning_rate_schedule(self, schedule):
        self._g.lr_policy = LearningRatePolicy.SCHEDULE
        self._g.lr_schedule = {int(k): float(v) for k, v in schedule.items()}
        return self
    def updater(self, v): self._g.updater = Updater.of(v); return self
    def momentum(self, v): self._g.momentum = float(v); return self
    def adam_mean_decay(self, v): self._g.adam_mean_decay = float(v); return self
    def adam_var_decay(self, v): self._g.adam_var_decay = float(v); return self
    def rho(self, v): self._g.rho = float(v); return self
    def rms_decay(self, v): self._g.rms_decay = float(v); return self
    def epsilon(self, v): self._g.epsilon = float(v); return self
    def weight_init(self, v): self._g.weight_init = WeightInit.of(v); return self
    def dist(self, v): self._g.dist = v; self._g.weight_init = WeightInit.DISTRIBUTION; return self
    def activation(self, v): self._g.activation = v; return self
    def bias_init(self, v): self._g.bias_init = float(v); return self
    def l1(self, v): self._g.l1 = float(v); return self
    def l2(self, v): self._g.l2 = float(v); return self
    def drop_out(self, v): self._g.dropout = float(v); return self
    def use_drop_connect(self, v=True): self._g.use_drop_connect = bool(v); return self
    def superstep_k(self, v): self._g.superstep_k = int(v); return self
    def minimize(self, v=True): self._g.minimize = bool(v); return self
    def gradient_normalization(self, v): self._g.gradient_normalization = GradientNormalization.of(v); return self
    def gradient_normalization_threshold(self, v): self._g.gradient_normalization_threshold = float(v); return self
    def mini_batch(self, v=True): self._g.mini_batch = bool(v); return self
    def convolution_mode(self, v): self._g.convolution_mode = ConvolutionMode.of(v); return self
    def max_num_line_search_iterations(self, v): self._g.max_num_line_search_iterations = int(v); return self
    def regularization(self, v=True): return self  # reference compat no-op: l1/l2 always honored
    def dtype(self, v): self._g.dtype = str(v); return self
    def dtype_policy(self, v):
        from deeplearning4j_tpu.nn.conf.dtype_policy import DtypePolicy
        self._g.dtype_policy = DtypePolicy.of(v); return self

    def list(self) -> "ListBuilder":
        """Start a sequential-network config (reference `:200`)."""
        return ListBuilder(copy.deepcopy(self._g))

    def graph_builder(self) -> "GraphBuilder":
        """Start a DAG config (reference: `ComputationGraphConfiguration.GraphBuilder`)."""
        return GraphBuilder(copy.deepcopy(self._g))


def _merge_globals(layer: Layer, g: GlobalConf) -> Layer:
    """Fill a layer's unset (None) hyperparams from the globals."""
    layer = copy.deepcopy(layer)
    for f in _INHERITED_FIELDS:
        if getattr(layer, f, None) is None:
            setattr(layer, f, getattr(g, f.replace("bias_learning_rate", "bias_learning_rate")))
    if layer.bias_learning_rate is None:
        layer.bias_learning_rate = layer.learning_rate
    if getattr(layer, "convolution_mode", "absent") is None:
        layer.convolution_mode = g.convolution_mode
    return layer


class ListBuilder:
    """Sequential-network builder (reference: `NeuralNetConfiguration.ListBuilder`)."""

    def __init__(self, g: GlobalConf):
        self._g = g
        self._layers: Dict[int, Layer] = {}
        self._preprocessors: Dict[int, InputPreProcessor] = {}
        self._input_type: Optional[InputType] = None
        self._backprop = True
        self._pretrain = False
        self._backprop_type = BackpropType.STANDARD
        self._tbptt_fwd = 20
        self._tbptt_back = 20

    def layer(self, index_or_layer, maybe_layer=None) -> "ListBuilder":
        if maybe_layer is None:
            self._layers[len(self._layers)] = index_or_layer
        else:
            self._layers[int(index_or_layer)] = maybe_layer
        return self

    def input_preprocessor(self, index: int, p: InputPreProcessor) -> "ListBuilder":
        self._preprocessors[int(index)] = p
        return self

    def set_input_type(self, t: InputType) -> "ListBuilder":
        self._input_type = t
        return self

    def backprop(self, v: bool) -> "ListBuilder":
        self._backprop = bool(v)
        return self

    def pretrain(self, v: bool) -> "ListBuilder":
        self._pretrain = bool(v)
        return self

    def backprop_type(self, v) -> "ListBuilder":
        self._backprop_type = BackpropType.of(v)
        return self

    def t_bptt_forward_length(self, v: int) -> "ListBuilder":
        self._tbptt_fwd = int(v)
        return self

    def t_bptt_backward_length(self, v: int) -> "ListBuilder":
        self._tbptt_back = int(v)
        return self

    def build(self) -> "MultiLayerConfiguration":
        n = len(self._layers)
        if sorted(self._layers) != list(range(n)):
            raise ValueError(f"Layer indices must be contiguous from 0; got {sorted(self._layers)}")
        layers = [_merge_globals(self._layers[i], self._g) for i in range(n)]
        preprocessors = dict(self._preprocessors)

        if self._input_type is not None:
            current = self._input_type
            for i, layer in enumerate(layers):
                if i not in preprocessors:
                    auto = layer.default_preprocessor(current)
                    if auto is not None:
                        preprocessors[i] = auto
                if i in preprocessors:
                    current = preprocessors[i].get_output_type(current)
                layer.set_n_in(current, override=True)
                current = layer.get_output_type(current)
        else:
            # Without an input type, still propagate n_in from explicit n_out chain.
            current = None
            for layer in layers:
                if current is not None:
                    layer.set_n_in(current, override=False)
                try:
                    current = layer.get_output_type(
                        current if current is not None
                        else InputType.feed_forward(getattr(layer, "n_in", 0))
                    )
                except Exception:
                    current = None

        return MultiLayerConfiguration(
            global_conf=self._g,
            layers=layers,
            input_preprocessors=preprocessors,
            backprop=self._backprop,
            pretrain=self._pretrain,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            input_type=self._input_type,
        )


@dataclass
class MultiLayerConfiguration:
    """Sequential network configuration (reference: `MultiLayerConfiguration.java`)."""

    global_conf: GlobalConf = field(default_factory=GlobalConf)
    layers: List[Layer] = field(default_factory=list)
    input_preprocessors: Dict[int, InputPreProcessor] = field(default_factory=dict)
    backprop: bool = True
    pretrain: bool = False
    backprop_type: Any = BackpropType.STANDARD
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    input_type: Optional[InputType] = None

    def to_dict(self):
        return {
            "format": "deeplearning4j_tpu/MultiLayerConfiguration",
            "version": 1,
            "global_conf": self.global_conf.to_dict(),
            "layers": [l.to_dict() for l in self.layers],
            "input_preprocessors": {str(k): v.to_dict() for k, v in self.input_preprocessors.items()},
            "backprop": self.backprop,
            "pretrain": self.pretrain,
            "backprop_type": BackpropType.of(self.backprop_type).value,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "input_type": self.input_type.to_dict() if self.input_type else None,
        }

    def to_json(self, indent=2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @staticmethod
    def from_dict(d) -> "MultiLayerConfiguration":
        return MultiLayerConfiguration(
            global_conf=GlobalConf.from_dict(d.get("global_conf")),
            layers=[layer_from_dict(l) for l in d["layers"]],
            input_preprocessors={
                int(k): preprocessor_from_dict(v)
                for k, v in (d.get("input_preprocessors") or {}).items()
            },
            backprop=d.get("backprop", True),
            pretrain=d.get("pretrain", False),
            backprop_type=BackpropType.of(d.get("backprop_type", "standard")),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
            input_type=InputType.from_dict(d.get("input_type")),
        )

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        return MultiLayerConfiguration.from_dict(json.loads(s))

    def to_yaml(self) -> str:
        """YAML form (reference: `MultiLayerConfiguration.toYaml()`
        `NeuralNetConfiguration.java:295-340` — same payload as JSON)."""
        import yaml

        return yaml.safe_dump(self.to_dict(), sort_keys=False)

    @staticmethod
    def from_yaml(s: str) -> "MultiLayerConfiguration":
        import yaml

        return MultiLayerConfiguration.from_dict(yaml.safe_load(s))


class GraphBuilder:
    """DAG builder (reference: `ComputationGraphConfiguration.GraphBuilder`)."""

    def __init__(self, g: GlobalConf):
        self._g = g
        self._inputs: List[str] = []
        self._outputs: List[str] = []
        self._vertices: Dict[str, GraphVertexConf] = {}
        self._vertex_inputs: Dict[str, List[str]] = {}
        self._input_types: Dict[str, InputType] = {}
        self._backprop = True
        self._pretrain = False
        self._backprop_type = BackpropType.STANDARD
        self._tbptt_fwd = 20
        self._tbptt_back = 20

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs = list(names)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str,
                  preprocessor: Optional[InputPreProcessor] = None) -> "GraphBuilder":
        self._vertices[name] = LayerVertex(layer=layer, preprocessor=preprocessor)
        self._vertex_inputs[name] = list(inputs)
        return self

    def add_vertex(self, name: str, vertex: GraphVertexConf, *inputs: str) -> "GraphBuilder":
        self._vertices[name] = vertex
        self._vertex_inputs[name] = list(inputs)
        return self

    def set_input_types(self, *types: InputType) -> "GraphBuilder":
        for name, t in zip(self._inputs, types):
            self._input_types[name] = t
        return self

    def backprop(self, v: bool) -> "GraphBuilder":
        self._backprop = bool(v)
        return self

    def pretrain(self, v: bool) -> "GraphBuilder":
        self._pretrain = bool(v)
        return self

    def backprop_type(self, v) -> "GraphBuilder":
        self._backprop_type = BackpropType.of(v)
        return self

    def t_bptt_forward_length(self, v: int) -> "GraphBuilder":
        self._tbptt_fwd = int(v)
        return self

    def t_bptt_backward_length(self, v: int) -> "GraphBuilder":
        self._tbptt_back = int(v)
        return self

    def build(self) -> "ComputationGraphConfiguration":
        conf = ComputationGraphConfiguration(
            global_conf=self._g,
            network_inputs=list(self._inputs),
            network_outputs=list(self._outputs),
            vertices={
                n: (LayerVertex(layer=_merge_globals(v.layer, self._g), preprocessor=v.preprocessor)
                    if isinstance(v, LayerVertex) else copy.deepcopy(v))
                for n, v in self._vertices.items()
            },
            vertex_inputs={n: list(v) for n, v in self._vertex_inputs.items()},
            input_types=dict(self._input_types),
            backprop=self._backprop,
            pretrain=self._pretrain,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
        )
        conf.validate()
        if self._input_types:
            conf.infer_shapes()
        return conf


@dataclass
class ComputationGraphConfiguration:
    """DAG network configuration (reference: `ComputationGraphConfiguration.java`)."""

    global_conf: GlobalConf = field(default_factory=GlobalConf)
    network_inputs: List[str] = field(default_factory=list)
    network_outputs: List[str] = field(default_factory=list)
    vertices: Dict[str, GraphVertexConf] = field(default_factory=dict)
    vertex_inputs: Dict[str, List[str]] = field(default_factory=dict)
    input_types: Dict[str, InputType] = field(default_factory=dict)
    backprop: bool = True
    pretrain: bool = False
    backprop_type: Any = BackpropType.STANDARD
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20

    def validate(self):
        """Structural validation (reference: `ComputationGraphConfiguration.validate()`)."""
        if not self.network_inputs:
            raise ValueError("ComputationGraph requires at least one network input")
        if not self.network_outputs:
            raise ValueError("ComputationGraph requires at least one network output")
        known = set(self.network_inputs) | set(self.vertices)
        for name, ins in self.vertex_inputs.items():
            for i in ins:
                if i not in known:
                    raise ValueError(f"Vertex {name!r} input {i!r} is not a known vertex/input")
        for o in self.network_outputs:
            if o not in self.vertices:
                raise ValueError(f"Network output {o!r} is not a vertex")
        for name, v in self.vertices.items():
            if isinstance(v, LayerVertex) and v.layer is not None \
                    and v.layer.n_inputs() > 1 and len(
                        self.vertex_inputs.get(name, ())) != \
                    v.layer.n_inputs():
                raise ValueError(
                    f"Vertex {name!r} ({type(v.layer).__name__}) takes "
                    f"{v.layer.n_inputs()} input(s), given "
                    f"{self.vertex_inputs.get(name)}")
            tied = getattr(v, "layer", None) and getattr(v.layer, "tied_to",
                                                         None)
            if tied and type(getattr(self.vertices.get(tied), "layer",
                                      None)).__name__ != "EmbeddingLayer":
                raise ValueError(f"Vertex {name!r} is tied to {tied!r}, "
                                 "which is no EmbeddingLayer vertex")
        self.topological_order()  # raises on cycles

    def topological_order(self) -> List[str]:
        """Kahn topological sort of vertex names, inputs first (reference:
        `ComputationGraph.java:851 topologicalSortOrder()`)."""
        indegree = {n: 0 for n in self.vertices}
        dependents: Dict[str, List[str]] = {n: [] for n in list(self.vertices) + self.network_inputs}
        for name, ins in self.vertex_inputs.items():
            for i in ins:
                dependents.setdefault(i, []).append(name)
                if i in self.vertices:
                    indegree[name] += 1
        order: List[str] = []
        ready = sorted(n for n, d in indegree.items() if d == 0)
        while ready:
            n = ready.pop(0)
            order.append(n)
            for dep in dependents.get(n, []):
                indegree[dep] -= 1
                if indegree[dep] == 0:
                    ready.append(dep)
        if len(order) != len(self.vertices):
            raise ValueError("Cycle detected in ComputationGraph configuration")
        return order

    def infer_shapes(self):
        """Infer n_in for all layer vertices from input types, inserting default
        preprocessors (reference: `addPreProcessors`/`getLayerActivationTypes`)."""
        types: Dict[str, InputType] = dict(self.input_types)
        for name in self.topological_order():
            vertex = self.vertices[name]
            in_types = [types[i] for i in self.vertex_inputs[name]]
            if isinstance(vertex, LayerVertex):
                it = in_types[0]
                if vertex.preprocessor is None:
                    auto = vertex.layer.default_preprocessor(it)
                    if auto is not None:
                        vertex.preprocessor = auto
                if vertex.preprocessor is not None:
                    it = vertex.preprocessor.get_output_type(it)
                vertex.layer.set_n_in(it, override=True)
                types[name] = vertex.layer.get_output_type(it)
            else:
                types[name] = vertex.get_output_type(*in_types)
        self._vertex_output_types = types
        return types

    def to_dict(self):
        return {
            "format": "deeplearning4j_tpu/ComputationGraphConfiguration",
            "version": 1,
            "global_conf": self.global_conf.to_dict(),
            "network_inputs": self.network_inputs,
            "network_outputs": self.network_outputs,
            "vertices": {n: v.to_dict() for n, v in self.vertices.items()},
            "vertex_inputs": self.vertex_inputs,
            "input_types": {n: t.to_dict() for n, t in self.input_types.items()},
            "backprop": self.backprop,
            "pretrain": self.pretrain,
            "backprop_type": BackpropType.of(self.backprop_type).value,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
        }

    def to_json(self, indent=2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @staticmethod
    def from_dict(d) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration(
            global_conf=GlobalConf.from_dict(d.get("global_conf")),
            network_inputs=list(d["network_inputs"]),
            network_outputs=list(d["network_outputs"]),
            vertices={n: vertex_from_dict(v) for n, v in d["vertices"].items()},
            vertex_inputs={n: list(v) for n, v in d["vertex_inputs"].items()},
            input_types={n: InputType.from_dict(t) for n, t in (d.get("input_types") or {}).items()},
            backprop=d.get("backprop", True),
            pretrain=d.get("pretrain", False),
            backprop_type=BackpropType.of(d.get("backprop_type", "standard")),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
        )

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration.from_dict(json.loads(s))

    def to_yaml(self) -> str:
        """YAML form (reference: `ComputationGraphConfiguration.toYaml()`)."""
        import yaml

        return yaml.safe_dump(self.to_dict(), sort_keys=False)

    @staticmethod
    def from_yaml(s: str) -> "ComputationGraphConfiguration":
        import yaml

        return ComputationGraphConfiguration.from_dict(yaml.safe_load(s))
