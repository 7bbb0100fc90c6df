"""Plain reference for the latent-attention mixture-of-experts language model
(`kimi_vl_a3b`: Kimi-VL-A3B-Instruct's language model, the DeepSeek-V3
block): the layer equations in `jax.numpy`, float32, matrix products at
`highest` precision, no kernels, no cache. It imports nothing of the program
under test and nothing of the other references.

One sequence of ids [S]; x_t in R^D. Every layer (parameters `p`):

    h  = RMSNorm(x; ln1)
    q  = h wq -> [S, H, Dn + Dr], split q_n (Dn) | q_r (Dr)
    [c ; k_r] = h wdkv -> R + Dr;   c <- RMSNorm(c; kv_norm, kv_norm_eps)
    [k_n ; v] = c wukv -> [S, H, Dn + Dv]
    q_r, k_r <- rotate-half RoPE at theta: pair (i, i + Dr/2) of position t
         turns by t theta^(-2i/Dr); k_r is ONE head, shared by all H
    o(t, h) = sum_{s <= t} softmax_s((q_n,h(t) . k_n,h(s) + q_r,h(t) . k_r(s))
         / sqrt(Dn + Dr)) v_h(s)
    x' = x + concat_h(o) wo
    h2 = RMSNorm(x'; ln2)

The first layer (`params["dense"]`, `first_k_dense_replace` 1) then adds a
dense gated SiLU MLP, `out = x' + w_down(silu(w_gate h2) * w_up h2)`. Every
other layer (`params["layers"]`) routes:

    s = sigmoid(h2 router) over all E, each expert's own score
    choice = s + router_bias;  of the n_group groups of experts only the
         topk_group with the largest sum of their two best `choice`s stay
         (the others are set to 0: with one group, nothing);
         E(t) = the top_k largest `choice`s
    g(e) = s(e) / (sum_{E(t)} s + 1e-20) * routed_scaling_factor   (the
         UNBIASED scores: the bias enters the choice and nothing else)
    y = sum_{e in E(t), e held} g(e) w_down[e](silu(w_gate[e] h2) * w_up[e] h2)
        + ws_down(silu(ws_gate h2) * ws_up h2)          (the shared expert)
    out = x' + y     (a loop over the held experts, each over all tokens)

then RMSNorm, the head, and the mean cross-entropy of the labels, plus
`aux_coef * sum_layers sum_e f_e P_e`, the sequence-wise balance term: f_e =
E / (top_k S) * #{t: e in E(t)}, P_e = mean_t s_e(t) / sum_j s_j(t). A
training step is `adam_update` of each leaf by that loss's gradient;
`router_bias` is no leaf of it (nothing differentiates through the choice).
The held experts are `first_expert .. first_expert + Eh - 1` (the leading
axis of `w_gate`), the held vocabulary the rows of `embed` and the columns of
`head`.

Departures from the published code (`modeling_deepseek.py` beside
https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct):
- it rotates INTERLEAVED pairs (2i, 2i + 1) of the rotary columns (it
  permutes them to halves first, then rotates halves); this rotates halves
  directly. The two are the same model after one fixed permutation of the Dr
  rotary columns of `wq` (per head) and of `wdkv`, so with seeded weights
  either is exact; the program rotates halves too.
- the published inference code has no balance term (`seq_aux` and
  `aux_loss_alpha` belong to the training code of DeepSeek-V2/V3); it is the
  sequence-wise one of those papers, over the normalised sigmoid scores.
- `router_bias` (`e_score_correction_bias`) is frozen: the aux-loss-free
  update rule that moves it during pre-training is not applied.
- only the held experts' part of the routed sum is computed, the shared
  expert whole, and the loss is over the held slice of the vocabulary (one
  chip's share of eight).
- the vision tower (MoonViT and its projector) is absent: text-only ids.

Attention runs one head and one block of rows at a time, each block against
all S keys under the causal mask (`[rows_block, S]` scores are all that is
ever held), and `forward(..., remat=True)` recomputes a layer in the
backward pass, so that the gradients fit beside a resident network at S =
8,192.
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


def rotate(x, theta: float):
    """[S, ..., D], position on axis 0, rotate-half over the last axis."""
    S, D = x.shape[0], x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    shape = (S,) + (1,) * (x.ndim - 2) + (D // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def projections(p, h, cfg):
    """(q_n [S, H, Dn], q_r [S, H, Dr], k_n [S, H, Dn], k_r [S, Dr],
    v [S, H, Dv]), the rotary parts rotated."""
    S = h.shape[0]
    H, R = cfg["n_heads"], cfg["kv_lora_rank"]
    Dn, Dr, Dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    q = _mm(h, p["wq"]).reshape(S, H, Dn + Dr)
    ckv = _mm(h, p["wdkv"])
    c = rms_norm(ckv[:, :R], p["kv_norm"], cfg["kv_norm_eps"])
    kv = _mm(c, p["wukv"]).reshape(S, H, Dn + Dv)
    return (q[..., :Dn], rotate(q[..., Dn:], cfg["rope_theta"]),
            kv[..., :Dn], rotate(ckv[:, R:], cfg["rope_theta"]),
            kv[..., Dn:])


def attention(p, h, cfg, rows_block: int = 1024):
    """Latent attention, one head and one block of rows at a time."""
    S = h.shape[0]
    H = cfg["n_heads"]
    q_n, q_r, k_n, k_r, v = projections(p, h, cfg)
    scale = (q_n.shape[-1] + q_r.shape[-1]) ** -0.5
    b = min(rows_block, S)
    assert S % b == 0, (S, b)
    cols = jnp.arange(S)[None, :]

    def one(at):
        head, lo = at
        rows_of = lambda a: jax.lax.dynamic_slice_in_dim(
            jnp.take(a, head, axis=1), lo, b)
        s = (_mm(rows_of(q_n), jnp.take(k_n, head, axis=1).T)
             + _mm(rows_of(q_r), k_r.T)) * scale
        keep = cols <= lo + jnp.arange(b)[:, None]
        s = jnp.where(keep, s, -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), jnp.take(v, head, axis=1))

    heads = jnp.repeat(jnp.arange(H), S // b)
    los = jnp.tile(jnp.arange(0, S, b), H)
    o = jax.lax.map(jax.checkpoint(one), (heads, los))       # [H*S/b, b, Dv]
    o = o.reshape(H, S, -1)
    return _mm(jnp.transpose(o, (1, 0, 2)).reshape(S, -1), p["wo"])


def gated_mlp(h, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up), w_down)


def route(p, h2, cfg, idx=None):
    """(scores s [S, E], weights g [S, top_k], idx [S, top_k]). `idx` given:
    those experts are used for each token (the program's choice) with the
    reference's own scores."""
    E, K = cfg["n_experts"], cfg["top_k"]
    s = jax.nn.sigmoid(_mm(h2, p["router"]))                       # [S, E]
    if idx is None:
        # the group-limited step as published; with one group it keeps that
        # group and changes nothing
        groups = int(cfg.get("n_group", 1))
        choice = s + p["router_bias"]
        by_group = choice.reshape(-1, groups, E // groups)
        best2 = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        kept = jax.lax.top_k(best2, int(cfg.get("topk_group", 1)))[1]
        stay = jnp.zeros_like(best2, bool).at[
            jnp.arange(best2.shape[0])[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(stay, E // groups, axis=1), choice, 0.0)
        idx = jax.lax.top_k(choice, K)[1]
    g = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return s, g * cfg.get("routed_scaling_factor", 1.0), idx


def experts(p, h2, cfg, idx=None):
    """(y, aux, idx): the held experts' part of the routed sum, by a loop
    over the held experts, each over all tokens, plus the shared expert."""
    E, K = cfg["n_experts"], cfg["top_k"]
    S = h2.shape[0]
    s, g, idx = route(p, h2, cfg, idx)
    g_all = jnp.zeros_like(s).at[jnp.arange(S)[:, None], idx].set(g)
    f = jnp.zeros((E,), F32).at[idx.reshape(-1)].add(1.0) * (E / (K * S))
    aux = jnp.sum(f * jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0))
    first = cfg.get("first_expert", 0)

    def one_expert(y, held):
        j, w_gate, w_up, w_down = held
        weight = jnp.take(g_all, first + j, axis=1)[:, None]
        return y + weight * gated_mlp(h2, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h2), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y + gated_mlp(h2, p["ws_gate"], p["ws_up"], p["ws_down"]), aux, idx


def dense_layer(p, x, cfg):
    """The leading layer: attention, then a dense gated SiLU MLP."""
    x = x + attention(p, rms_norm(x, p["ln1"], cfg["rms_eps"]), cfg)
    return x + gated_mlp(rms_norm(x, p["ln2"], cfg["rms_eps"]), p["w_gate"],
                         p["w_up"], p["w_down"])


def layer(p, x, cfg, idx=None):
    """An expert layer: (out, aux, idx). `idx` given: those experts per
    token are used (the program's, for gradients compared under one
    routing)."""
    x = x + attention(p, rms_norm(x, p["ln1"], cfg["rms_eps"]), cfg)
    y, aux, idx = experts(p, rms_norm(x, p["ln2"], cfg["rms_eps"]), cfg, idx)
    return x + y, aux, idx


def forward(params, ids, cfg, routes=None, remat=False):
    """ids [S] int -> (logits [S, V], aux summed over the expert layers,
    [idx]): per expert layer the routing that was used. `params["dense"]` is
    the leading dense layer (absent: none), `params["layers"]` the expert
    layers: a list, or one layer's tree with a leading axis over them
    (`routes` and the result then likewise): the same loop, as a `lax.scan`
    over the layers, one compiled body."""
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    first = functools.partial(dense_layer, cfg=cfg)
    fn = functools.partial(layer, cfg=cfg)
    if remat:
        first, fn = jax.checkpoint(first), jax.checkpoint(fn)
    if params.get("dense") is not None:
        x = first(params["dense"], x)
    if isinstance(params["layers"], dict):
        def body(x, given):
            x, a, idx = fn(given["p"], x, idx=given.get("idx"))
            return x, (a, idx)

        given = {"p": params["layers"]}
        if routes is not None:
            given["idx"] = routes
        x, (aux, routed) = jax.lax.scan(body, x, given)
        aux = jnp.sum(aux)
    else:
        aux, routed = 0.0, []
        for i, p in enumerate(params["layers"]):
            x, a, idx = fn(p, x, idx=None if routes is None else routes[i])
            aux = aux + a
            routed.append(idx)
    x = rms_norm(x, params["norm"], cfg["rms_eps"])
    return _mm(x, params["head"]), aux, routed


def loss(params, ids, labels, cfg, routes=None, remat=False):
    """Mean next-token cross-entropy + aux_coef * aux."""
    logits, aux, _ = forward(params, ids, cfg, routes, remat)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    ce = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) - picked)
    return ce + cfg.get("aux_coef", 0.0) * aux


def loss_and_grads(params, ids, labels, cfg, routes=None, remat=False):
    """(loss, gradients of every leaf but `router_bias`, which is frozen and
    reads zero)."""
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
    rows, each over the keys up to its own end (the band's pairs: the score
    as its two products of Dn and Dr, the values at Dv); the experts over
    the expected number of held pairs, S * top_k * Eh / E, sorted by expert
    (exact when no more pairs are held; it is traced for its shapes at the
    real size and run only by the tests, with every pair held); the shared
    expert and the leading layer's MLP over every token."""
    S = ids.shape[0]
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)

    def attend(p, x):
        q_n, q_r, k_n, k_r, v = projections(
            p, rms_norm(x, p["ln1"], cfg["rms_eps"]), cfg)
        scale = (q_n.shape[-1] + q_r.shape[-1]) ** -0.5
        outs = []
        for lo in range(0, S, rows_block):
            hi = min(lo + rows_block, S)
            s = (jnp.einsum("thd,shd->ths", q_n[lo:hi], k_n[:hi],
                            precision=_HIGHEST)
                 + jnp.einsum("thd,sd->ths", q_r[lo:hi], k_r[:hi],
                              precision=_HIGHEST)) * scale
            causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            s = jnp.where(causal[:, None, :], s, -jnp.inf)
            outs.append(jnp.einsum("ths,shd->thd", jax.nn.softmax(s, -1),
                                   v[:hi], precision=_HIGHEST))
        return x + _mm(jnp.concatenate(outs, 0).reshape(S, -1), p["wo"])

    if params.get("dense") is not None:
        p = params["dense"]
        x = attend(p, x)
        x = x + gated_mlp(rms_norm(x, p["ln2"], cfg["rms_eps"]), p["w_gate"],
                          p["w_up"], p["w_down"])
    for p in params["layers"]:
        x = attend(p, x)
        h2 = rms_norm(x, p["ln2"], cfg["rms_eps"])
        E, TK = cfg["n_experts"], cfg["top_k"]
        Eh, first = p["w_gate"].shape[0], cfg.get("first_expert", 0)
        _, gate, idx = route(p, h2, cfg)
        local = idx.reshape(-1) - first
        held = (local >= 0) & (local < Eh)
        local = jnp.where(held, local, Eh)
        P = cfg.get("pairs_counted") or (S * TK * Eh) // E
        order = jnp.argsort(local, stable=True)[:P]
        tok, eid = order // TK, jnp.minimum(local[order], Eh - 1)
        wgt = jnp.where(held[order], gate.reshape(-1)[order], 0.0)
        rows = h2[tok]
        hid = jax.nn.silu(jnp.einsum("pd,pdf->pf", rows, p["w_gate"][eid],
                                     precision=_HIGHEST)) * jnp.einsum(
            "pd,pdf->pf", rows, p["w_up"][eid], precision=_HIGHEST)
        out = jnp.einsum("pf,pfd->pd", hid, p["w_down"][eid],
                         precision=_HIGHEST) * wgt[:, None]
        x = x + jnp.zeros_like(x).at[tok].add(out) + gated_mlp(
            h2, p["ws_gate"], p["ws_up"], p["ws_down"])
    x = rms_norm(x, params["norm"], cfg["rms_eps"])
    return _mm(x, params["head"])
