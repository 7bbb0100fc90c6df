"""Batch normalization implementation.

Equivalent of the reference's `nn/layers/normalization/BatchNormalization.java:55`
(+ cuDNN helper path, subsumed by XLA fusion). Works for dense [b,f], sequence
[b,t,f], and NHWC [b,h,w,c] inputs — stats reduce over all axes but the last.

Running stats live in the layer *state* pytree (decay-EMA, reference decay 0.9,
eps 1e-5); train/inference selection is a static python flag, so each mode
compiles to its own fused XLA program (no in-graph branching).
"""

from __future__ import annotations

import jax.numpy as jnp

from deeplearning4j_tpu.kernels import norm_act as _norm_kernel


def batchnorm_apply(conf, params, state, x, *, rng=None, train=False, mask=None):
    axes = tuple(range(x.ndim - 1))
    if train and conf.is_minibatch:
        # Single-pass stats: mean and mean-of-squares fuse into ONE read of x
        # (jnp.var would re-read the activation for (x-mean)^2 — the train
        # step is HBM-bandwidth bound on TPU, so each avoided pass counts).
        mean = jnp.mean(x, axis=axes)
        var = jnp.mean(x * x, axis=axes) - mean * mean
        decay = conf.decay
        new_state = {
            "mean": decay * state["mean"] + (1.0 - decay) * mean,
            "var": decay * state["var"] + (1.0 - decay) * var,
        }
    else:
        mean = state["mean"]
        var = state["var"]
        new_state = state
    # Normalize + affine + activation through the kernel dispatch seam
    # (kernels/norm_act.py). Under `auto` this is the literal pre-registry
    # expression on every backend: the statistics above are XLA's, and a
    # custom call here would be a fusion barrier between the producer, the
    # reductions and the activation. The Pallas body runs only when forced.
    if conf.lock_gamma_beta or not params:
        gamma, beta = conf.gamma, conf.beta
    else:
        gamma, beta = params["gamma"], params["beta"]
    out = _norm_kernel.batchnorm_norm_act(x, mean, var, gamma, beta,
                                          conf.eps, conf.activation)
    return out, new_state, mask


def layernorm_apply(conf, params, state, x, *, rng=None, train=False,
                    mask=None):
    """Layer norm over the trailing feature axis (no running state — the
    statistics are per-example, so train == inference; the transformer
    family's normalizer, `nn/conf/layers.py::LayerNormalization`)."""
    from deeplearning4j_tpu.nn.layers.common import layer_input_dropout

    x = layer_input_dropout(conf, x, rng, train)
    # Stats + normalize + affine + activation through the kernel dispatch
    # seam (kernels/norm_act.py; XLA fallback is the pre-registry code).
    out = _norm_kernel.layernorm_norm_act(x, params["gamma"], params["beta"],
                                          conf.eps, conf.activation)
    return out, state, mask


def rmsnorm_apply(conf, params, state, x, *, rng=None, train=False,
                  mask=None):
    """RMS norm over the trailing feature axis
    (`nn/conf/layers.py::RMSNormalization`); plain XLA, so that it fuses
    into its neighbours (PERF.md PR 25: a custom call here is a barrier)."""
    from deeplearning4j_tpu.nn import activations
    from deeplearning4j_tpu.nn.layers import dsa
    from deeplearning4j_tpu.nn.layers.common import layer_input_dropout

    x = layer_input_dropout(conf, x, rng, train)
    out = dsa.rms_norm(x, params["gamma"], conf.eps)
    return activations.resolve(conf.activation)(out), state, mask
