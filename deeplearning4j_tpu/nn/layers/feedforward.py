"""Feed-forward layer implementations: Dense, Output, Embedding, Activation,
Dropout, AutoEncoder, RBM (supervised path), CenterLossOutput features.

Equivalent of the reference's `nn/layers/feedforward/` + `BaseLayer.java`
forward math. All functions are pure; backward is autodiff. Dense ops act on
the LAST axis and broadcast over leading axes, so the same code serves
[batch, f] and [batch, time, f] (the reference reshapes via Rnn<->FF
preprocessors instead).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations
from deeplearning4j_tpu.nn.layers.common import (
    inverted_dropout,
    layer_input_dropout,
    maybe_drop_connect,
)


def dense_apply(conf, params, state, x, *, rng=None, train=False, mask=None):
    x = layer_input_dropout(conf, x, rng, train)
    out = x @ maybe_drop_connect(conf, params["W"], rng, train)
    if "b" in params:
        out = out + params["b"]
    out = activations.resolve(conf.activation)(out)
    return out, state, mask


def gated_silu_mlp(x, w_gate, w_up, w_down):
    """`(silu(x w_gate) * x w_up) w_down` over the last axis."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def gated_dense_apply(conf, params, state, x, *, rng=None, train=False,
                      mask=None):
    """`GatedDenseLayer`: a gated SiLU MLP without biases."""
    x = layer_input_dropout(conf, x, rng, train)
    out = gated_silu_mlp(x, params["W_gate"], params["W_up"],
                         params["W_down"])
    return activations.resolve(conf.activation)(out), state, mask


def preoutput(conf, params, state, x, *, rng=None, train=False, mask=None):
    """Linear pre-activation (used by output layers for stable fused losses)."""
    x = layer_input_dropout(conf, x, rng, train)
    out = x @ maybe_drop_connect(conf, params["W"], rng, train)
    if "b" in params:
        out = out + params["b"]
    return out, state, mask


def embedding_apply(conf, params, state, x, *, rng=None, train=False, mask=None):
    """Embedding lookup (reference: `nn/layers/feedforward/embedding/EmbeddingLayer.java`).

    TPU-native: a gather instead of the reference's onehot-matmul. Accepts
    integer indices [b], [b,1], [b,t] or one-hot [..., n_in].
    """
    fmt = getattr(conf, "input_format", "auto")
    onehot = (fmt == "onehot" if fmt != "auto"
              else jnp.issubdtype(x.dtype, jnp.floating)
              and x.shape[-1] == conf.n_in)
    if onehot:
        idx = jnp.argmax(x, axis=-1)
    else:
        idx = x.astype(jnp.int32)
        if idx.ndim >= 2 and idx.shape[-1] == 1:
            idx = idx[..., 0]
    out = jnp.take(params["W"], idx, axis=0)
    if "b" in params:
        out = out + params["b"]
    out = activations.resolve(conf.activation)(out)
    return out, state, mask


def activation_apply(conf, params, state, x, *, rng=None, train=False, mask=None):
    return activations.resolve(conf.activation)(x), state, mask


def dropout_apply(conf, params, state, x, *, rng=None, train=False, mask=None):
    return inverted_dropout(x, conf.dropout, rng, train), state, mask


def autoencoder_apply(conf, params, state, x, *, rng=None, train=False, mask=None):
    """Supervised forward = encode (reference: `AutoEncoder.java` encode)."""
    return dense_apply(conf, params, state, x, rng=rng, train=train, mask=mask)


def autoencoder_reconstruct(conf, params, x, rng=None, corrupt=False):
    """Encode+decode with optional masking-noise corruption (pretrain path;
    reference: `AutoEncoder.java` getCorruptedInput/encode/decode)."""
    act = activations.resolve(conf.activation)
    if corrupt and rng is not None and conf.corruption_level > 0:
        keep = jax.random.bernoulli(rng, 1.0 - conf.corruption_level, x.shape)
        x = jnp.where(keep, x, 0.0)
    y = act(x @ params["W"] + params["b"])
    z = act(y @ params["W"].T + params["vb"])
    return z


def autoencoder_pretrain_loss(conf, params, x, rng):
    """Denoising-AE reconstruction loss (reference: `AutoEncoder.computeGradientAndScore`
    via the configured reconstruction loss, default cross-entropy)."""
    from deeplearning4j_tpu.nn import losses as losses_mod

    z = autoencoder_reconstruct(conf, params, x, rng=rng, corrupt=True)
    # z is already post-activation; pass identity so score uses it directly.
    return losses_mod.score(conf.loss_function, x, z, "identity")


def _rbm_free_energy(conf, params, v):
    """Free energy F(v) = -v.vb - sum softplus(vW + b) (binary hidden units)."""
    wx_b = v @ params["W"] + params["b"]
    vbias = v @ params["vb"]
    return -vbias - jnp.sum(jax.nn.softplus(wx_b), axis=-1)


def rbm_pretrain_loss(conf, params, x, rng):
    """CD-k contrastive divergence as a differentiable surrogate (reference:
    `nn/layers/feedforward/rbm/RBM.java:101` contrastiveDivergence).

    Gibbs-sample v_k with k steps (stop-gradient), then
    loss = mean(F(v)) - mean(F(v_k)): autodiff of this is exactly the CD-k
    gradient — the functional TPU formulation of the reference's sampled
    positive/negative phase updates.
    """
    v = x

    def sample_h(v, key):
        p = jax.nn.sigmoid(v @ params["W"] + params["b"])
        if conf.hidden_unit == "binary":
            return jax.random.bernoulli(key, p).astype(v.dtype), p
        return p, p

    def sample_v(h, key):
        pre = h @ params["W"].T + params["vb"]
        if conf.visible_unit == "gaussian":
            return pre + jax.random.normal(key, pre.shape, pre.dtype), pre
        p = jax.nn.sigmoid(pre)
        if conf.visible_unit == "binary":
            return jax.random.bernoulli(key, p).astype(v.dtype), p
        return p, p

    vk = v
    for step in range(max(1, conf.k)):
        kh = jax.random.fold_in(rng, 2 * step)
        kv = jax.random.fold_in(rng, 2 * step + 1)
        h, _ = sample_h(vk, kh)
        vk, _ = sample_v(h, kv)
    vk = jax.lax.stop_gradient(vk)
    return jnp.mean(_rbm_free_energy(conf, params, v)) - jnp.mean(
        _rbm_free_energy(conf, params, vk))


def rbm_apply(conf, params, state, x, *, rng=None, train=False, mask=None):
    """Supervised forward = propUp (reference: `nn/layers/feedforward/rbm/RBM.java`)."""
    pre = x @ params["W"] + params["b"]
    if conf.hidden_unit == "gaussian":
        out = pre
    elif conf.hidden_unit == "rectified":
        out = jax.nn.relu(pre)
    elif conf.hidden_unit == "softmax":
        out = jax.nn.softmax(pre, axis=-1)
    else:
        out = jax.nn.sigmoid(pre)
    return out, state, mask


def positional_embedding_apply(conf, params, state, x, *, rng=None,
                               train=False, mask=None):
    """x: [B, T, F] -> x + P[pos:pos+T] (learned GPT-style position table,
    `nn/conf/layers.py::PositionalEmbeddingLayer`).

    With `conf.stateful`, a position cursor rides undeclared state: a
    fresh forward starts at 0 (== P[:T]); stateful decode via
    `rnn_time_step` resumes where the previous call stopped, so
    single-token steps get the RIGHT position rows. Stateless (default)
    always adds P[:T] — the cursor must be OPT-IN because tBPTT's
    carry_rnn path would otherwise advance it across chunks, silently
    changing existing models' training."""
    T = x.shape[1]
    if T > conf.max_length:
        raise ValueError(
            f"sequence length {T} exceeds PositionalEmbeddingLayer "
            f"max_length {conf.max_length}")
    if not getattr(conf, "stateful", False):
        return x + params["P"][:T], state, mask
    start = state.get("pos", jnp.int32(0))
    if jnp.ndim(start):
        # Per-slot cursors ([B] int32): gather each row's own position rows.
        idx = jnp.clip(start[:, None] + jnp.arange(T)[None, :],
                       0, conf.max_length - 1)
        rows = params["P"][idx]                  # [B, T, F]
    else:
        rows = jax.lax.dynamic_slice(
            params["P"], (start, jnp.int32(0)), (T, params["P"].shape[1]))
    return x + rows, {"pos": start + jnp.int32(T)}, mask
