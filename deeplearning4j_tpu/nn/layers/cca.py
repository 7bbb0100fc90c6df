"""Compressed convolutional attention (CCA: Figliolia et al. 2025,
"Compressed Convolutional Attention: Efficient Attention in a Compressed
Latent Space", arXiv:2510.04476), as ZAYA1 has it: the forward of
`CompressedConvAttentionLayer` (`nn/conf/layers.py`). One sequence h
[S, n_in]; H query heads and KV key/value heads of D channels, query head j
reading key/value head g(j) = j // (H / KV):

    q~ = h Wq -> [S, H, D]     k~ = h Wk -> [S, KV, D]
    u  = [q~ ; k~] -> [S, (H + KV) D], H + KV groups of D channels
    c_t  = sum_i W_conv0[i] * u_{t-i} + b_conv0              (each channel,
           `cca_time0` taps, u before position 0 is 0)
    c^_t = sum_i c_{t-i} W_conv1[g, i] + b_conv1             (each group g of
           D channels, `cca_time1` taps), split into q^ [S, H, D], k^ [S, KV, D]
    q_j = q^_j + (q~_j + k~_g(j)) / 2                        (the q-k mean)
    k_g = k^_g + (mean_{j: g(j) = g} q~_j + k~_g) / 2
    q <- sqrt(D) q / |q|,  k <- temp_g sqrt(D) k / |k|       (|.| with 1e-6
           under the root; `temp` [KV], 1 at init)
    the first R = partial_rotary_factor * D channels of every q and k head
           take rotate-half RoPE at `rope_theta`; the others pass unchanged
    v_t,g = [(h_t Wv1)_g ; (h_{t-1} Wv2)_g]                  (D/2 + D/2
           channels a head, h before position 0 is 0)
    o_t,j = sum_{s <= t} softmax_s(q_t,j . k_s,g(j) / sqrt(D)) v_s,g(j)
    out = o Wo

The core is the registry's `banded_attention` over the causal triangle
(`dsa.banded_gqa_attention`), the rotary step the registry's `rotary`
(`dsa.rope`) on the rotated channels. `jax.named_scope`s name the parts in a
device trace: `cca.project` (the projections, the value shift, `Wo`),
`cca.conv` (both convolutions, the q-k mean, the norm and temperature, the
rotary step) and `cca.attend` (the core). The module-level functions below
are the steps by name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers import dsa

# eps under the square root of the L2 norm of q and k
L2_EPS = 1e-6


def shift(x, i: int):
    """x [S, ...] delayed by i positions along axis 0, zeros in front."""
    if i == 0:
        return x
    return jnp.pad(x[:-i], ((i, 0),) + ((0, 0),) * (x.ndim - 1))


def causal_convs(u, w0, b0, w1, b1):
    """Both causal convolutions over u [S, G, D]: one per channel of w0
    [T0, G * D] taps, summed in >= float32, then one per group of w1
    [G, T1, D, D] taps, its products in u's dtype and their sum in >=
    float32 -> [S, G, D] in >= float32."""
    S, G, D = u.shape
    acc = jnp.promote_types(u.dtype, jnp.float32)
    w0 = w0.astype(acc).reshape(-1, G, D)
    c = sum(w0[i] * shift(u, i).astype(acc) for i in range(w0.shape[0]))
    c = (c + b0.astype(acc).reshape(G, D)).astype(u.dtype)
    w1 = w1.astype(u.dtype)
    out = sum(jnp.einsum("sgd,gde->sge", shift(c, i), w1[:, i]).astype(acc)
              for i in range(w1.shape[1]))
    return out + b1.astype(acc).reshape(G, D)


def qk_mean(q_hat, k_hat, q_til, k_til):
    """The q-k mean added back: q_hat [S, H, D], k_hat [S, KV, D] (the
    convolutions' output) and the projections q_til, k_til -> (q, k)."""
    S, H, D = q_til.shape
    KV = k_til.shape[1]
    acc = q_hat.dtype
    q_til, k_til = q_til.astype(acc), k_til.astype(acc)
    q_grouped = q_til.reshape(S, KV, H // KV, D)
    q = q_hat + 0.5 * (q_grouped + k_til[:, :, None]).reshape(S, H, D)
    k = k_hat + 0.5 * (jnp.mean(q_grouped, axis=2) + k_til)
    return q, k


def l2_scale(x, scale):
    """sqrt(D) x / |x| over the last axis, times `scale` (broadcast)."""
    D = x.shape[-1]
    inv = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
    return x * inv * (D ** 0.5 * scale)


def partial_rope(x, theta: float, rotary_dim: int):
    """Rotate-half RoPE (`dsa.rope`, the registry's `rotary`) over the first
    `rotary_dim` channels of the last axis of x [S, ..., D]."""
    if rotary_dim == x.shape[-1]:
        return dsa.rope(x, theta)
    return jnp.concatenate([dsa.rope(x[..., :rotary_dim], theta),
                            x[..., rotary_dim:]], axis=-1)


def value_shift(v2):
    """The second half of the values: the previous position's."""
    return shift(v2, 1)


def one_sequence(conf, params, h):
    """h [S, n_in] -> out [S, n_out]; see the module docstring."""
    S = h.shape[0]
    H, KV, D = conf.n_heads, conf.n_kv_heads, conf.head_dim
    with jax.named_scope("cca.project"):
        q_til = (h @ params["Wq"]).reshape(S, H, D)
        k_til = (h @ params["Wk"]).reshape(S, KV, D)
        v = jnp.concatenate(
            [(h @ params["Wv1"]).reshape(S, KV, D // 2),
             value_shift(h @ params["Wv2"]).reshape(S, KV, D // 2)], axis=-1)
    with jax.named_scope("cca.conv"):
        c = causal_convs(jnp.concatenate([q_til, k_til], axis=1),
                         params["W_conv0"], params["b_conv0"],
                         params["W_conv1"], params["b_conv1"])
        q, k = qk_mean(c[:, :H], c[:, H:], q_til, k_til)
        q = l2_scale(q, 1.0).astype(h.dtype)
        k = l2_scale(k, params["temp"].astype(k.dtype)[:, None]).astype(
            h.dtype)
        q = partial_rope(q, conf.rope_theta, conf.rotary_dim())
        k = partial_rope(k, conf.rope_theta, conf.rotary_dim())
    with jax.named_scope("cca.attend"):
        o, _ = dsa.banded_gqa_attention(q, k, v, None, True)
    with jax.named_scope("cca.project"):
        return o.reshape(S, H * D) @ params["Wo"]


def cca_apply(conf, params, state, x, *, rng=None, train=False, mask=None):
    """x [B, T, n_in] -> [B, T, n_out]; one sequence at a time."""
    from deeplearning4j_tpu.nn import activations
    from deeplearning4j_tpu.nn.layers.common import layer_input_dropout

    if mask is not None:
        raise ValueError("compressed convolutional attention takes no "
                         "features mask: pad to full length")
    x = layer_input_dropout(conf, x, rng, train)
    if x.shape[0] == 1:
        out = one_sequence(conf, params, x[0])[None]
    else:
        out = jax.vmap(lambda h: one_sequence(conf, params, h))(x)
    return activations.resolve(conf.activation)(out), state, mask
