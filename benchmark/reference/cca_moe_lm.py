"""Plain reference for the compressed-convolutional-attention mixture-of-
experts language model (`zaya1_8b`: Zyphra's ZAYA1-8B): the layer equations
in `jax.numpy`, float32, matrix products at `highest` precision, no kernels,
no cache. It imports nothing of the program under test and nothing of the
other references.

One sequence of ids [S]; x_t in R^Dm; H query heads and KV key/value heads
of D channels, query head j reading group g(j) = j // (H / KV). Every layer
(parameters `p`):

    h   = RMSNorm(x; ln1)
    q~  = h wq -> [S, H, D],  k~ = h wk -> [S, KV, D],  u = [q~ ; k~]
    c_t = sum_i conv0_w[i] * u_{t-i} + conv0_b        (each channel; u_{<0} = 0)
    c^_t = sum_i c_{t-i} conv1_w[g, i] + conv1_b      (each group g of D)
    q^, k^ = the first H and the last KV groups of c^
    q_j = q^_j + (q~_j + k~_g(j)) / 2;  k_g = k^_g + (mean_{g(j)=g} q~_j + k~_g) / 2
    q <- sqrt(D) q / sqrt(|q|^2 + 1e-6);  k <- temp_g sqrt(D) k / sqrt(|k|^2 + 1e-6)
    the first `rotary_dim` channels of every q and k head: rotate-half RoPE,
         pair (i, i + rotary_dim / 2) of position t turning by
         t theta^(-2i / rotary_dim); the other channels unchanged
    v_t,g = [(h_t wv1)_g ; (h_{t-1} wv2)_g]           (D/2 + D/2; h_{-1} = 0)
    o_t,j = sum_{s <= t} softmax_s(q_t,j . k_s,g(j) / sqrt(D)) v_s,g(j)
    x' = alpha_a * x + beta_a * (concat_j o_j) wo
    h2 = RMSNorm(x'; ln2)
    r  = h2 router_down;  r' = (1 - l) r + l r'_prev, l = sigmoid(eda)
         (exponential depth averaging; the first layer: r' = r)
    z  = gelu(gelu(r' router_w1) router_w2) router_w3 over E + 1 choices
         (GELU exact), P = softmax(z), e* = argmax(z + router_bias)
    m  = P(e*) w_down[e*](silu(w_gate[e*] h2) * w_up[e*] h2) if e* is a held
         expert, else 0 (the last choice, the skip, is no expert's)
    out = alpha_f * x' + beta_f * m

then RMSNorm(norm) and the head, which is the embedding's table
(`logits = x embed^T`: tied), and the mean cross-entropy of the labels plus
`aux_coef * sum_layers E sum_{e < E} f_e P_e`, f_e = #{t: e* = e} / S,
P_e = mean_t P_e(t), over the E experts and not the skip. A training step is
`adam_update` of each leaf by that loss's gradient; `router_bias` is no leaf
of it (nothing differentiates through the choice). The held experts are
`first_expert .. first_expert + Eh - 1` (the leading axis of `w_gate`), the
held vocabulary the rows of `embed`. `params["first"]` is the first layer
held (no `eda`: it has no predecessor), `params["layers"]` the others.

Departures from the published description (ZAYA1 technical report, Zyphra
2025; CCA, arXiv:2510.04476; the config at
https://huggingface.co/Zyphra/ZAYA1-8B names the sizes only), each one a
form the configuration's `assumed` states:
- the two convolutions' layout (depthwise, then per head, each causal with
  a bias), the q-k mean's form, the value shift's halves per head and the
  temperature on k alone are the configuration's reading of CCA;
- EDA's convex form with a per-column gate, the router MLP's depth (three
  products, no biases) and GELU, and the skip as one more logit are its
  reading of the ZAYA router and of residual-scaled MoD;
- the residual gains are per channel on both the stream and the sublayer;
- `router_bias` is a frozen selection bias and the balance term above
  stands in for the family's load-driven bias controller;
- only the held experts' part of the routed sum is computed, and the loss
  is over the held slice of the vocabulary (one chip's share of eight).

Attention runs one head and one block of rows at a time, each block against
all S keys under the causal mask (`[rows_block, S]` scores are all that is
ever held), and `forward(..., remat=True)` recomputes a layer in the
backward pass, so that the gradients fit beside a resident network at
S = 8,192.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def delayed(x, i: int):
    """x [S, ...] i positions later: row t holds x_{t-i}, zeros before 0."""
    if i == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:i]), x[:-i]], axis=0)


def rotate(x, theta: float, rotary_dim: int):
    """[S, ..., D], position on axis 0: rotate-half over the first
    `rotary_dim` channels of the last axis, the others unchanged."""
    S, R = x.shape[0], rotary_dim
    inv = float(theta) ** (-jnp.arange(0, R, 2, dtype=F32) / R)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    shape = (S,) + (1,) * (x.ndim - 2) + (R // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., : R // 2], x[..., R // 2: R]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., R:]], -1)


def qkv(p, h, cfg):
    """(q [S, H, D], k [S, KV, D], v [S, KV, D]) of one layer, q and k
    convolved, mixed, normalised and rotated."""
    S = h.shape[0]
    H, KV, D = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q_til = _mm(h, p["wq"]).reshape(S, H, D)
    k_til = _mm(h, p["wk"]).reshape(S, KV, D)
    u = jnp.concatenate([q_til, k_til], axis=1)                 # [S, G, D]
    G = H + KV
    w0 = p["conv0_w"].reshape(-1, G, D)
    c = sum(w0[i] * delayed(u, i) for i in range(w0.shape[0]))
    c = c + p["conv0_b"].reshape(G, D)
    c_hat = sum(jnp.einsum("sgd,gde->sge", delayed(c, i), p["conv1_w"][:, i],
                           precision=_HIGHEST)
                for i in range(p["conv1_w"].shape[1]))
    c_hat = c_hat + p["conv1_b"].reshape(G, D)
    q_grouped = q_til.reshape(S, KV, H // KV, D)
    q = c_hat[:, :H] + 0.5 * (q_grouped + k_til[:, :, None]).reshape(S, H, D)
    k = c_hat[:, H:] + 0.5 * (jnp.mean(q_grouped, axis=2) + k_til)
    norm = lambda a: a * jax.lax.rsqrt(                          # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6) * D ** 0.5
    q, k = norm(q), norm(k) * p["temp"][:, None]
    q = rotate(q, cfg["rope_theta"], cfg["rotary_dim"])
    k = rotate(k, cfg["rope_theta"], cfg["rotary_dim"])
    v = jnp.concatenate(
        [_mm(h, p["wv1"]).reshape(S, KV, D // 2),
         delayed(_mm(h, p["wv2"]), 1).reshape(S, KV, D // 2)], axis=-1)
    return q, k, v


def attention(p, h, cfg, rows_block: int = 1024):
    """The CCA sublayer's output before its gain, one head and one block of
    rows at a time."""
    S = h.shape[0]
    H, KV = cfg["n_heads"], cfg["n_kv_heads"]
    q, k, v = qkv(p, h, cfg)
    scale = q.shape[-1] ** -0.5
    b = min(rows_block, S)
    assert S % b == 0, (S, b)
    cols = jnp.arange(S)[None, :]

    def one(at):
        head, lo = at
        g = head // (H // KV)
        qb = jax.lax.dynamic_slice_in_dim(jnp.take(q, head, axis=1), lo, b)
        s = _mm(qb, jnp.take(k, g, axis=1).T) * scale
        s = jnp.where(cols <= lo + jnp.arange(b)[:, None], s, -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), jnp.take(v, g, axis=1))

    heads = jnp.repeat(jnp.arange(H), S // b)
    los = jnp.tile(jnp.arange(0, S, b), H)
    o = jax.lax.map(jax.checkpoint(one), (heads, los))        # [H*S/b, b, D]
    o = o.reshape(H, S, -1)
    return _mm(jnp.transpose(o, (1, 0, 2)).reshape(S, -1), p["wo"])


def gated_mlp(h, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up), w_down)


def route(p, h2, prev, cfg, idx=None):
    """(the router's state r', probabilities P [S, E + 1], idx [S, top_k]).
    `prev` None: no predecessor. `idx` given: those choices are used (the
    program's) with the reference's own probabilities."""
    r = _mm(h2, p["router_down"])
    if prev is not None:
        lam = jax.nn.sigmoid(p["eda"])
        r = (1.0 - lam) * r + lam * prev
    z = jax.nn.gelu(_mm(r, p["router_w1"]), approximate=False)
    z = jax.nn.gelu(_mm(z, p["router_w2"]), approximate=False)
    z = _mm(z, p["router_w3"])
    probs = jax.nn.softmax(z, axis=-1)
    if idx is None:
        idx = jax.lax.top_k(z + p["router_bias"], cfg["top_k"])[1]
    return r, probs, idx


def experts(p, h2, prev, cfg, idx=None):
    """(m, aux, idx, r'): the held experts' part of the routed sum, by a loop
    over the held experts, each over all tokens."""
    E = cfg["n_experts"]
    S = h2.shape[0]
    r, probs, idx = route(p, h2, prev, cfg, idx)
    gate = jnp.zeros_like(probs).at[jnp.arange(S)[:, None], idx].set(
        jnp.take_along_axis(probs, idx, axis=-1))        # skip's column too
    f = jnp.zeros((probs.shape[1],), F32).at[idx.reshape(-1)].add(1.0) / S
    aux = E * jnp.sum(f[:E] * jnp.mean(probs[:, :E], axis=0))
    first = cfg.get("first_expert", 0)

    def one_expert(y, held):
        j, w_gate, w_up, w_down = held
        weight = jnp.take(gate, first + j, axis=1)[:, None]
        return y + weight * gated_mlp(h2, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h2), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y, aux, idx, r


def layer(p, x, prev, cfg, idx=None):
    """One layer: (out, the router's state, aux, idx). `idx` given: those
    choices per token are used (the program's, for gradients compared under
    one routing)."""
    eps = cfg["rms_eps"]
    x = p["alpha_a"] * x + p["beta_a"] * attention(
        p, rms_norm(x, p["ln1"], eps), cfg)
    m, aux, idx, r = experts(p, rms_norm(x, p["ln2"], eps), prev, cfg, idx)
    return p["alpha_f"] * x + p["beta_f"] * m, r, aux, idx


def forward(params, ids, cfg, routes=None, remat=False):
    """ids [S] int -> (logits [S, V], aux summed over the layers, [idx]):
    per layer the routing that was used, the first layer's first.
    `params["layers"]` is a list, or one layer's tree with a leading axis
    over them (`routes` and the result then an array [layers, S, top_k]):
    the same loop, as a `lax.scan` over the layers, one compiled body."""
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    fn = functools.partial(layer, cfg=cfg)
    if remat:
        fn = jax.checkpoint(fn)
    given = (lambda i: None) if routes is None else (lambda i: routes[i])
    x, r, aux, idx = fn(params["first"], x, None, idx=given(0))
    if isinstance(params["layers"], dict):
        def body(carry, at):
            x, r = carry
            x, r, a, idx = fn(at["p"], x, r, idx=at.get("idx"))
            return (x, r), (a, idx)

        at = {"p": params["layers"]}
        if routes is not None:
            at["idx"] = routes[1:]
        (x, r), (a, rest) = jax.lax.scan(body, (x, r), at)
        aux, routed = aux + jnp.sum(a), jnp.concatenate([idx[None], rest])
    else:
        routed = [idx]
        for i, p in enumerate(params["layers"]):
            x, r, a, idx = fn(p, x, r, idx=given(i + 1))
            aux = aux + a
            routed.append(idx)
    x = rms_norm(x, params["norm"], cfg["rms_eps"])
    return _mm(x, params["embed"].T), aux, routed


def loss(params, ids, labels, cfg, routes=None, remat=False):
    """Mean next-token cross-entropy + aux_coef * aux."""
    logits, aux, _ = forward(params, ids, cfg, routes, remat)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    ce = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) - picked)
    return ce + cfg.get("aux_coef", 0.0) * aux


def loss_and_grads(params, ids, labels, cfg, routes=None, remat=False):
    """(loss, gradients of every leaf; `router_bias`'s read zero)."""
    return jax.value_and_grad(loss)(params, ids, labels, cfg, routes, remat)


def adam_update(grad, m, v, t, lr, beta1, beta2, eps=1e-8):
    """The change one Adam step makes to a leaf (Kingma & Ba 2015, Algorithm
    1): `m`, `v` the moments before the step, `t` the step's number counted
    from 1. No weight decay."""
    m = beta1 * m + (1.0 - beta1) * grad
    v = beta2 * v + (1.0 - beta2) * grad * grad
    m_hat = m / (1.0 - beta1 ** t)
    v_hat = v / (1.0 - beta2 ** t)
    return -lr * m_hat / (jnp.sqrt(v_hat) + eps)


# ---------------------------------------------------------------- counting
def forward_needed(params, ids, cfg, rows_block: int = 256):
    """The same forward in the form whose matrix products are the ones the
    mathematics needs, for counting operations from shapes
    (`harness/flops.py`): the attention in causal blocks of `rows_block`
    rows, each over the keys up to its own end (the band's pairs); the
    experts over the expected number of held pairs under an even router,
    S * top_k * Eh / (E + 1) with the skip among the choices, sorted by
    choice (exact when no more pairs are held; it is traced for its shapes
    at the real size and run only by the tests, with every pair held); the
    router and the tied head over every token."""
    S = ids.shape[0]
    eps = cfg["rms_eps"]
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    r = None
    for p in [params["first"], *params["layers"]]:
        q, k, v = qkv(p, rms_norm(x, p["ln1"], eps), cfg)
        G = cfg["n_heads"] // cfg["n_kv_heads"]
        k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
        outs = []
        for lo in range(0, S, rows_block):
            hi = min(lo + rows_block, S)
            s = jnp.einsum("thd,shd->ths", q[lo:hi], k[:hi],
                           precision=_HIGHEST) * q.shape[-1] ** -0.5
            causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            s = jnp.where(causal[:, None, :], s, -jnp.inf)
            outs.append(jnp.einsum("ths,shd->thd", jax.nn.softmax(s, -1),
                                   v[:hi], precision=_HIGHEST))
        x = p["alpha_a"] * x + p["beta_a"] * _mm(
            jnp.concatenate(outs, 0).reshape(S, -1), p["wo"])
        h2 = rms_norm(x, p["ln2"], eps)
        r, probs, idx = route(p, h2, r, cfg)
        C, TK = probs.shape[1], cfg["top_k"]
        Eh, first = p["w_gate"].shape[0], cfg.get("first_expert", 0)
        local = idx.reshape(-1) - first
        held = (local >= 0) & (local < Eh)
        local = jnp.where(held, local, Eh)
        P = cfg.get("pairs_counted") or (S * TK * Eh) // C
        order = jnp.argsort(local, stable=True)[:P]
        tok, eid = order // TK, jnp.minimum(local[order], Eh - 1)
        gate = jnp.take_along_axis(probs, idx, axis=-1).reshape(-1)
        wgt = jnp.where(held[order], gate[order], 0.0)
        rows = h2[tok]
        hid = jax.nn.silu(jnp.einsum("pd,pdf->pf", rows, p["w_gate"][eid],
                                     precision=_HIGHEST)) * jnp.einsum(
            "pd,pdf->pf", rows, p["w_up"][eid], precision=_HIGHEST)
        out = jnp.einsum("pf,pfd->pd", hid, p["w_down"][eid],
                         precision=_HIGHEST) * wgt[:, None]
        x = p["alpha_f"] * x + p["beta_f"] * jnp.zeros_like(x).at[tok].add(
            out)
    x = rms_norm(x, params["norm"], eps)
    return _mm(x, params["embed"].T)
