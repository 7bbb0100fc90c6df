"""Accelerated-kernel helper layer (Pallas) behind one dispatch seam.

JAX-port equivalent of the reference's per-backend helper discovery
(`ConvolutionHelper`/`LSTMHelper`, PAPER.md layer 1): `registry.py` maps
kernel names to ordered candidates — a Pallas TPU implementation and a
bit-stable XLA fallback that is the literal pre-registry inline code —
and resolves once per jit signature. Kernels:

- ``lstm_cell``       — fused LSTM cell (recurrent matmul + gates + state
                        update), the `nn/layers/recurrent.py::_lstm_scan`
                        body for standard/Graves/bidirectional paths;
- ``fused_update``    — Adam/Nesterov/RMSProp over the stacked flattened
                        param leaves in one elementwise kernel
                        (`ops/updaters.py`, superstep carry);
- ``norm_act``        — LayerNorm stats+normalize+affine+activation in
                        one pass (`nn/layers/normalization.py`); BatchNorm's
                        tail goes through the same seam and `auto` leaves
                        it to XLA's fusions (its Pallas body: forced only);
- ``flash_attention`` — the PERF.md §6 flash kernel, migrated here from
                        `ops/flash_attention.py` (shim kept);
- ``bottleneck_block``— the fused ResNet bottleneck chain (conv1x1/BN/act
                        x3 + residual in one VMEM residency, PERF.md §27),
                        `nn/layers/bottleneck.py`'s seam, with an
                        int8-weight inference variant for serving;
- ``grouped_matmul``  — the dropless experts' grouped products over rows
                        sorted by expert (`parallel/expert.py::
                        _held_experts`): rows by group against a table,
                        against its transpose, and a table's gradient; a
                        visit list over row tiles and groups built from
                        the traced group sizes. XLA's candidate is
                        `jax.lax.ragged_dot`;
- ``rotary``          — the rotate-half rotary embedding of
                        `nn/layers/dsa.py::rope` in one pass each way over
                        the operand's positions-minor view (float32 tables
                        of cos and sin; the backward pass is the same body
                        with the sine negated). XLA's candidate is
                        `dsa.rope_xla`.

`DL4J_TPU_KERNELS=auto|xla|pallas` (+ per-kernel
`DL4J_TPU_KERNEL_<NAME>`) select the mode; `python -m
deeplearning4j_tpu.kernels` lists what resolved and why. tpulint JX010
keeps Pallas imports confined to this package. PERF.md §19 documents the
design, fallback matrix, and parity/bench methodology.
"""

from __future__ import annotations

from deeplearning4j_tpu.kernels import registry
from deeplearning4j_tpu.kernels.registry import (
    KernelImpl,
    Resolution,
    config_fingerprint,
    config_key,
    describe,
    kernel_names,
    probe_count,
    resolve,
)

__all__ = [
    "registry", "KernelImpl", "Resolution", "config_fingerprint",
    "config_key", "describe", "kernel_names", "probe_count", "resolve",
]
