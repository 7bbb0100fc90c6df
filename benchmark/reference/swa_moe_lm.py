"""Plain reference for the sliding-window mixture-of-experts language model
(`mellum2_12b_a2_5b`): the layer equations in `jax.numpy`, float32, matrix
products at `highest` precision, no kernels, no cache. It imports nothing
of the program under test and nothing of the other references.

One sequence of ids [S]; x_t in R^D. Every layer has a kind, `cfg
["layer_types"][l]`, and `cfg["attention"][kind]` says what the kind sets:
`window` (None: every earlier key) and the rotary parameters `rope` (`theta`
and, for YaRN, `factor`, `original_max_position_embeddings`, `beta_fast`,
`beta_slow`, `attention_factor`). Layer l (parameters `p`):

    h  = RMSNorm(x; ln1)          q = h wq -> [S, H, Dh]   k = h wk, v = h wv -> [S, KV, Dh]
    q <- RMSNorm over Dh (q_norm), k likewise (k_norm)
    q, k <- rotate-half RoPE: pair (i, i + Dh/2) of position t turns by t inv_i, and cos
         and sin are multiplied by m. Default: inv_i = theta^(-2i/Dh), m = 1. YaRN:
         c(r) = Dh ln(L0 / (2 pi r)) / (2 ln theta); low = floor(c(beta_fast)), high =
         ceil(c(beta_slow)), clipped to [0, Dh - 1]; ramp_i = clip((i - low) / (high -
         low), 0, 1); inv_i = theta^(-2i/Dh) (1 - ramp_i) + theta^(-2i/Dh) / factor ramp_i;
         m = attention_factor (0.1 ln factor + 1 where the config gives none)
    o(t, head) = sum_{s in W(t)} softmax_s(q . k / sqrt(Dh)) v,  W(t) = {s: t - window < s <= t}
         (`window` keys, t's own among them: the `transformers` convention), or {s <= t};
         key/value head = head // (H / KV)
    x' = x + o wo
    h2 = RMSNorm(x'; ln2); p = softmax(h2 router) over all E; E(t) = top_k of p; g = p / sum_{E(t)} p
    y  = sum_{e in E(t), e held} g(e) w_down[e](silu(w_gate[e] h2) * w_up[e] h2);  out = x' + y
         (a loop over the held experts, each over all tokens)

then RMSNorm, the head, and the mean cross-entropy of the labels, plus
`aux_coef * sum_layers E * sum_e f_e P_e` (f_e: pairs routed to e per token,
P_e: mean router probability). A training step is `adam_update` of each leaf
by that loss's gradient. The held experts are `first_expert .. first_expert
+ Eh - 1` (the leading axis of `w_gate`), the held vocabulary the rows of
`embed` and the columns of `head`.

Departures from the published description (`model_type` `mellum`,
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct): the RMS norm
on each q and k head is assumed (the Qwen3-MoE block whose keys the config
carries; the config has no key that says); the multi-token-prediction head
the family's description mentions is absent (the config has no key for it);
only the held experts' part of the routed sum is computed, and the loss is
over the held slice of the vocabulary (one chip's share of eight).

Attention runs one head and one block of rows at a time, each block against
all S keys under its mask (`[rows_block, S]` scores are all that is ever
held), and `forward(..., remat=True)` recomputes a layer in the backward
pass, so that the gradients fit beside a resident network at S = 16,384.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope_table(head_dim: int, rope: dict):
    """(inv [Dh/2] float32, m): the turn of each pair per position and the
    factor on cos and sin; see the module docstring."""
    theta = float(rope["theta"])
    inv = theta ** (-jnp.arange(0, head_dim, 2, dtype=F32) / head_dim)
    if rope.get("rope_type", "default") == "default":
        return inv, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    L0 = float(rope["original_max_position_embeddings"])

    def c(rotations):
        return head_dim * math.log(L0 / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    low = max(math.floor(c(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(c(float(rope["beta_slow"]))), head_dim - 1)
    span = high - low if high != low else 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=F32) - low) / span, 0, 1)
    m = rope.get("attention_factor")
    if m is None:
        m = 0.1 * math.log(factor) + 1.0
    return inv * (1 - ramp) + inv / factor * ramp, float(m)


def rotate(x, inv, m):
    """[S, ..., D], position on axis 0, rotate-half."""
    S, D = x.shape[0], x.shape[-1]
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    shape = (S,) + (1,) * (x.ndim - 2) + (D // 2,)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), (jnp.sin(ang) * m).reshape(
        shape)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def kind_tables(cfg, kind: str):
    """What a layer of `kind` sets, as arrays a scan can carry: (inv, m,
    window); no window is given as 2^30, which no row of any sequence
    reaches."""
    a = cfg["attention"][kind]
    inv, m = rope_table(cfg["head_dim"], a["rope"])
    window = a.get("window")
    return inv, jnp.asarray(m, F32), jnp.asarray(
        2 ** 30 if window is None else int(window), jnp.int32)


def attention(p, h, cfg, tables, rows_block: int = 1024):
    """Softmax attention under the causal band, one head and one block of
    rows at a time."""
    S = h.shape[0]
    H, KV, Dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    inv, m, window = tables
    q = _mm(h, p["wq"]).reshape(S, H, Dh)
    k = _mm(h, p["wk"]).reshape(S, KV, Dh)
    v = _mm(h, p["wv"]).reshape(S, KV, Dh)
    q = rotate(rms_norm(q, p["q_norm"], cfg["rms_eps"]), inv, m)
    k = rotate(rms_norm(k, p["k_norm"], cfg["rms_eps"]), inv, m)
    group = H // KV
    b = min(rows_block, S)
    assert S % b == 0, (S, b)
    cols = jnp.arange(S)[None, :]

    def one(at):
        head, lo = at
        qb = jax.lax.dynamic_slice_in_dim(jnp.take(q, head, axis=1), lo, b)
        kh = jnp.take(k, head // group, axis=1)
        vh = jnp.take(v, head // group, axis=1)
        rows = lo + jnp.arange(b)[:, None]
        keep = (cols <= rows) & (cols > rows - window)
        s = jnp.where(keep, _mm(qb, kh.T) * (Dh ** -0.5), -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), vh)

    heads = jnp.repeat(jnp.arange(H), S // b)
    los = jnp.tile(jnp.arange(0, S, b), H)
    o = jax.lax.map(jax.checkpoint(one), (heads, los))       # [H*S/b, b, Dh]
    o = o.reshape(H, S, Dh)
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


def layer(p, x, cfg, tables, idx=None):
    """(out, aux, idx). `idx` given: those experts per token are used (the
    program's, for gradients compared under one routing)."""
    h = rms_norm(x, p["ln1"], cfg["rms_eps"])
    x = x + attention(p, h, cfg, tables)
    y, aux, idx = experts(p, rms_norm(x, p["ln2"], cfg["rms_eps"]), cfg, idx)
    return x + y, aux, idx


def forward(params, ids, cfg, routes=None, remat=False):
    """ids [S] int -> (logits [S, V], aux summed over layers, [idx]): per
    layer the routing that was used. `params["layers"]` is a list of layers,
    or one layer's tree with a leading axis over the layers (`routes` and
    the result then likewise): the same loop, as a `lax.scan` over the
    layers and their kinds' tables, one compiled body."""
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    fn = functools.partial(layer, cfg=cfg)
    if remat:
        fn = jax.checkpoint(fn)
    stacked = isinstance(params["layers"], dict)
    n = (jax.tree_util.tree_leaves(params["layers"])[0].shape[0] if stacked
         else len(params["layers"]))
    kinds = [kind_tables(cfg, cfg["layer_types"][i % len(cfg["layer_types"])])
             for i in range(n)]
    if stacked:
        def body(x, given):
            x, a, idx = fn(given["p"], x, tables=given["tables"],
                           idx=given.get("idx"))
            return x, (a, idx)

        given = {"p": params["layers"],
                 "tables": tuple(jnp.stack(t) for t in zip(*kinds))}
        if routes is not None:
            given["idx"] = routes
        x, (aux, routed) = jax.lax.scan(body, x, given)
        aux = jnp.sum(aux)
    else:
        aux, routed = 0.0, []
        for i, p in enumerate(params["layers"]):
            x, a, idx = fn(p, x, tables=kinds[i],
                           idx=None if routes is None else routes[i])
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
    (`harness/flops.py`): a query past the window against a gather of its
    `window` keys and no other; the queries before it (all of them in a full
    layer) in causal blocks of `rows_block`, each over the keys up to its
    own end; and the experts over the expected number of held pairs, S *
    top_k * Eh / E, sorted by expert (exact when no more pairs are held; it
    is traced for its shapes at the real size and run only by the tests,
    with every pair held)."""
    S = ids.shape[0]
    H, KV, Dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    for i, p in enumerate(params["layers"]):
        a = cfg["attention"][cfg["layer_types"][i % len(cfg["layer_types"])]]
        inv, m = rope_table(Dh, a["rope"])
        W = min(a.get("window") or S, S)
        h = rms_norm(x, p["ln1"], cfg["rms_eps"])
        q = rotate(rms_norm(_mm(h, p["wq"]).reshape(S, H, Dh), p["q_norm"],
                            cfg["rms_eps"]), inv, m)
        k = rotate(rms_norm(_mm(h, p["wk"]).reshape(S, KV, Dh), p["k_norm"],
                            cfg["rms_eps"]), inv, m)
        v = _mm(h, p["wv"]).reshape(S, KV, Dh)
        qg = q.reshape(S, KV, H // KV, Dh)
        outs = []
        for lo in range(0, W, rows_block):               # rows t < W: causal
            hi = min(lo + rows_block, W)
            s = jnp.einsum("tghd,sgd->tghs", qg[lo:hi], k[:hi],
                           precision=_HIGHEST) * (Dh ** -0.5)
            causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            s = jnp.where(causal[:, None, None, :], s, -jnp.inf)
            outs.append(jnp.einsum("tghs,sgd->tghd", jax.nn.softmax(s, -1),
                                   v[:hi], precision=_HIGHEST))
        if S > W:                          # rows t >= W: their W keys each
            kept = jnp.arange(W, S)[:, None] - jnp.arange(W)[None, ::-1]
            kk, vv = k[kept], v[kept]                      # [S-W, W, KV, Dh]
            s = jnp.einsum("tghd,tsgd->tghs", qg[W:], kk,
                           precision=_HIGHEST) * (Dh ** -0.5)
            outs.append(jnp.einsum("tghs,tsgd->tghd", jax.nn.softmax(s, -1),
                                   vv, precision=_HIGHEST))
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
