"""Plain reference for the sparse-attention mixture-of-experts language model
(`keye_vl2_30b_a3b`): the layer equations in `jax.numpy`, float32, matrix
products at `highest` precision, no kernels, no cache. It imports nothing
of the program under test.

One sequence of ids [S]; x_t in R^D. Layer l (parameters `p`):

    h  = RMSNorm(x; ln1)          q = h wq -> [S, H, Dh]   k = h wk, v = h wv -> [S, KV, Dh]
    q <- RMSNorm over Dh (q_norm), k likewise (k_norm); q, k <- RoPE(theta, rotate-half)
    qi = h idx_wq -> [S, IH, ID]; ki = LayerNorm(h idx_wk) -> [S, ID]; w = h idx_w -> [S, IH]
    RoPE on qi, ki;  I(t, s) = sum_j w(t, j) IH^-1/2 relu(qi(t, j) . ki(s)) ID^-1/2,  s <= t
    S(t) = the index_top_k largest I(t, .) over s <= t (`argsort`; ties to the earlier s)
    o(t, head) = sum_{s in S(t)} softmax_s(q . k / sqrt(Dh)) v,  key/value head = head // (H / KV)
    x' = x + o wo
    h2 = RMSNorm(x'; ln2); p = softmax(h2 router) over all E; E(t) = top_k of p; g = p / sum_{E(t)} p
    y  = sum_{e in E(t), e held} g(e) w_down[e](silu(w_gate[e] h2) * w_up[e] h2);  out = x' + y
         (a loop over the held experts, each over all tokens)

then RMSNorm, the head, and the mean cross-entropy of the labels, plus
`aux_coef * sum_layers E * sum_e f_e P_e` (f_e: pairs routed to e per token,
P_e: mean router probability). A training step is `adam_update` of each leaf
by that loss's gradient. The held experts are
`first_expert .. first_expert + Eh - 1` (the leading axis of `w_gate`), the
held vocabulary the rows of `embed` and the columns of `head`.

The dense [S, S] index scores and one head's [S, S] attention at a time are
all that is ever held; `forward(..., remat=True)` recomputes a layer in the
backward pass so that the gradients fit beside a resident network.
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


def layer_norm(x, g, b, eps=1e-6):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * g + b


def rope(x, theta):
    """[S, ..., D], position on axis 0, rotate-half."""
    S, D = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    shape = (S,) + (1,) * (x.ndim - 2) + (D // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_scores(p, h, cfg, rows_block=None):
    """Dense I [S, S], -inf above the diagonal. With `rows_block`, computed
    in blocks of rows, each over the keys up to its last row only (the same
    numbers; the products are then the ones the mathematics needs)."""
    S = h.shape[0]
    IH, ID = cfg["index_n_heads"], cfg["index_head_dim"]
    qi = rope(_mm(h, p["idx_wq"]).reshape(S, IH, ID), cfg["rope_theta"])
    ki = rope(layer_norm(_mm(h, p["idx_wk"]), p["idx_k_norm_g"],
                         p["idx_k_norm_b"]), cfg["rope_theta"])
    w = _mm(h, p["idx_w"])
    rows = []
    for lo in range(0, S, rows_block or S):
        hi = min(lo + (rows_block or S), S)
        s = jnp.einsum("thd,sd->ths", qi[lo:hi], ki[:hi], precision=_HIGHEST)
        I = jnp.einsum("ths,th->ts", jax.nn.relu(s), w[lo:hi],
                       precision=_HIGHEST)
        rows.append(jnp.pad(I, ((0, 0), (0, S - hi))))
    I = jnp.concatenate(rows, 0) * (IH ** -0.5) * (ID ** -0.5)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    return jnp.where(causal, I, -jnp.inf)


def selection(I, k):
    """Bool [S, S]: row t keeps its min(t + 1, k) largest scores."""
    S = I.shape[0]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    if S <= k:
        return causal
    order = jnp.argsort(-(I + 0.0), axis=1, stable=True)   # best first
    rank = jnp.argsort(order, axis=1)                      # its inverse
    return (rank < k) & causal


def attention(p, h, cfg, keep):
    """Dense masked softmax, one head at a time."""
    S = h.shape[0]
    H, KV, Dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q = _mm(h, p["wq"]).reshape(S, H, Dh)
    k = _mm(h, p["wk"]).reshape(S, KV, Dh)
    v = _mm(h, p["wv"]).reshape(S, KV, Dh)
    q = rope(rms_norm(q, p["q_norm"], cfg["rms_eps"]), cfg["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"], cfg["rms_eps"]), cfg["rope_theta"])
    group = H // KV

    def one_head(head):
        qh = jnp.take(q, head, axis=1)
        kh = jnp.take(k, head // group, axis=1)
        vh = jnp.take(v, head // group, axis=1)
        s = _mm(qh, kh.T) * (Dh ** -0.5)
        s = jnp.where(keep, s, -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), vh)

    o = jax.lax.map(jax.checkpoint(one_head), jnp.arange(H))    # [H, S, Dh]
    return _mm(jnp.transpose(o, (1, 0, 2)).reshape(S, H * Dh), p["wo"])


def experts(p, h2, cfg, idx=None):
    """(y, aux, idx): the held experts' part of the routed sum, by a loop
    over the held experts, each over all tokens. `idx` [S, top_k] given:
    those experts are used for each token (the program's choice) with the
    reference's own probabilities."""
    E, K = cfg["n_experts"], cfg["top_k"]
    probs = jax.nn.softmax(_mm(h2, p["router"]), axis=-1)          # [S, E]
    if idx is None:
        gate, idx = jax.lax.top_k(probs, K)
    else:
        gate = jnp.take_along_axis(probs, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    g_all = jnp.zeros_like(probs).at[
        jnp.arange(h2.shape[0])[:, None], idx].set(gate)           # [S, E]
    f = jnp.zeros((E,), F32).at[idx.reshape(-1)].add(1.0) / h2.shape[0]
    aux = E * jnp.sum(f * jnp.mean(probs, axis=0))
    first = cfg.get("first_expert", 0)

    def one_expert(y, held):
        j, w_gate, w_up, w_down = held
        hid = jax.nn.silu(_mm(h2, w_gate)) * _mm(h2, w_up)
        g = jnp.take(g_all, first + j, axis=1)[:, None]
        return y + g * _mm(hid, w_down), None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h2), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y, aux, idx


def layer(p, x, cfg, keep=None, idx=None):
    """(out, aux, keep, idx). `keep` / `idx` given: that selection of keys /
    those experts per token are used (the program's, for gradients compared
    under one selection and one routing)."""
    h = rms_norm(x, p["ln1"], cfg["rms_eps"])
    if keep is None:
        if cfg.get("index_top_k") is None:
            keep = jnp.tril(jnp.ones((x.shape[0],) * 2, bool))
        else:
            keep = selection(index_scores(p, h, cfg), cfg["index_top_k"])
    x = x + attention(p, h, cfg, keep)
    y, aux, idx = experts(p, rms_norm(x, p["ln2"], cfg["rms_eps"]), cfg, idx)
    return x + y, aux, keep, idx


def forward(params, ids, cfg, keeps=None, routes=None, remat=False):
    """ids [S] int -> (logits [S, V], aux summed over layers, [keep],
    [idx]): per layer the selection and the routing that were used.
    `params["layers"]` is a list of layers, or one layer's tree with a
    leading axis over the layers (`keeps`, `routes` and the two results then
    likewise): the same loop, as a `lax.scan`, one compiled body."""
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    fn = functools.partial(layer, cfg=cfg)
    if remat:
        fn = jax.checkpoint(fn)
    if isinstance(params["layers"], dict):
        def body(x, given):
            x, a, keep, idx = fn(given["p"], x, keep=given.get("keep"),
                                 idx=given.get("idx"))
            return x, (a, keep, idx)

        given = {"p": params["layers"]}
        if keeps is not None:
            given["keep"] = keeps
        if routes is not None:
            given["idx"] = routes
        x, (aux, used, routed) = jax.lax.scan(body, x, given)
        aux = jnp.sum(aux)
    else:
        aux, used, routed = 0.0, [], []
        for n, p in enumerate(params["layers"]):
            x, a, keep, idx = fn(p, x,
                                 keep=None if keeps is None else keeps[n],
                                 idx=None if routes is None else routes[n])
            aux = aux + a
            used.append(keep)
            routed.append(idx)
    x = rms_norm(x, params["norm"], cfg["rms_eps"])
    return _mm(x, params["head"]), aux, used, routed


def loss(params, ids, labels, cfg, keeps=None, routes=None, remat=False):
    """Mean next-token cross-entropy + aux_coef * aux."""
    logits, aux, _, _ = forward(params, ids, cfg, keeps, routes, remat)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    ce = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) - picked)
    return ce + cfg.get("aux_coef", 0.0) * aux


def loss_and_grads(params, ids, labels, cfg, keeps=None, routes=None,
                   remat=False):
    return jax.value_and_grad(loss)(params, ids, labels, cfg, keeps, routes,
                                    remat)


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
    (`harness/flops.py`): attention over a gather of the `index_top_k` kept
    keys per query (rows below `index_top_k` in causal blocks of
    `rows_block`), and the experts over the expected number of held pairs,
    S * top_k * Eh / E, sorted by expert (exact when no more pairs are held;
    it is traced for its shapes at the real size and run only by the tests,
    with every pair held)."""
    S = ids.shape[0]
    H, KV, Dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    K = cfg.get("index_top_k") or S
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    for p in params["layers"]:
        h = rms_norm(x, p["ln1"], cfg["rms_eps"])
        q = rope(rms_norm(_mm(h, p["wq"]).reshape(S, H, Dh), p["q_norm"],
                          cfg["rms_eps"]), cfg["rope_theta"])
        k = rope(rms_norm(_mm(h, p["wk"]).reshape(S, KV, Dh), p["k_norm"],
                          cfg["rms_eps"]), cfg["rope_theta"])
        v = _mm(h, p["wv"]).reshape(S, KV, Dh)
        qg = q.reshape(S, KV, H // KV, Dh)
        outs = []
        for lo in range(0, min(S, K), rows_block):       # rows t < K: causal
            hi = min(lo + rows_block, S, K)
            s = jnp.einsum("tghd,sgd->tghs", qg[lo:hi], k[:hi],
                           precision=_HIGHEST) * (Dh ** -0.5)
            causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            s = jnp.where(causal[:, None, None, :], s, -jnp.inf)
            outs.append(jnp.einsum("tghs,sgd->tghd", jax.nn.softmax(s, -1),
                                   v[:hi], precision=_HIGHEST))
        if S > K:                                          # rows t >= K: gather
            I = index_scores(p, h, cfg, rows_block)[K:]
            kept = jnp.argsort(-(I + 0.0), axis=1, stable=True)[:, :K]
            kk, vv = k[kept], v[kept]                      # [S-K, K, KV, Dh]
            s = jnp.einsum("tghd,tsgd->tghs", qg[K:], kk,
                           precision=_HIGHEST) * (Dh ** -0.5)
            outs.append(jnp.einsum("tghs,tsgd->tghd", jax.nn.softmax(s, -1),
                                   vv, precision=_HIGHEST))
        else:
            index_scores(p, h, cfg, rows_block)  # it runs whatever it selects
        o = jnp.concatenate(outs, 0).reshape(S, H * Dh)
        x = x + _mm(o, p["wo"])
        # experts, over the expected number of held pairs
        h2 = rms_norm(x, p["ln2"], cfg["rms_eps"])
        E, TK = cfg["n_experts"], cfg["top_k"]
        Eh, first = p["w_gate"].shape[0], cfg.get("first_expert", 0)
        probs = jax.nn.softmax(_mm(h2, p["router"]), axis=-1)
        gate, idx = jax.lax.top_k(probs, TK)
        if cfg.get("norm_topk_prob", True):
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
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
        x = x + jnp.zeros_like(x).at[tok].add(out)
    x = rms_norm(x, params["norm"], cfg["rms_eps"])
    return _mm(x, params["head"])
