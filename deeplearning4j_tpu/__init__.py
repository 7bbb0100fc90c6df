"""deeplearning4j_tpu — a TPU-native deep-learning framework.

A brand-new framework with the capabilities of deeplearning4j (reference:
JelliSindhu/deeplearning4j), designed TPU-first: layer/graph configurations are
JSON-serializable builder-produced dataclasses; networks compile to pure jitted
apply/train functions over parameter pytrees; optimizers are composable gradient
transformations fused into the jitted step; data parallelism is per-step gradient
all-reduce over a `jax.sharding.Mesh` (pjit/shard_map) instead of the reference's
parameter-averaging transports (ParallelWrapper / Spark / Aeron PS).

Top-level re-exports cover the most common user-facing API.
"""

__version__ = "0.1.0"

from deeplearning4j_tpu.nn.conf.enums import (  # noqa: F401
    Activation,
    BackpropType,
    ConvolutionMode,
    GradientNormalization,
    LossFunction,
    OptimizationAlgorithm,
    PoolingType,
    Updater,
    WeightInit,
)
from deeplearning4j_tpu.nn.conf.neural_net import (  # noqa: F401
    ComputationGraphConfiguration,
    MultiLayerConfiguration,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: F401
from deeplearning4j_tpu.nn.graph import ComputationGraph  # noqa: F401

# Turn on jax's persistent compilation cache at import (opt-out via
# DL4J_TPU_COMPILE_CACHE=off; placed by JAX_COMPILATION_CACHE_DIR): init-time helper ops compile before the first
# _get_jit would lazily configure it, and a warm process should replay
# those from disk too, not just the big training programs.
from deeplearning4j_tpu.compilation import (  # noqa: F401,E402
    configure_persistent_cache as _configure_persistent_cache)

_configure_persistent_cache()
