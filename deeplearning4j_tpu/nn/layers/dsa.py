"""Grouped-query attention with rotary positions, QK-norm and one of three
choices of the keys a query reads: a learned sparse selection (DeepSeek
sparse attention: an indexer beside the attention scores every earlier
position and each query attends to its `index_top_k` best keys only), a
sliding window of the `sliding_window` latest keys, or all of them.

`extended_attention_apply` is `SelfAttentionLayer`'s forward when any of
its extended fields is set (`nn/conf/layers.py`). One sequence [S, n_in]:

    q = h Wq -> [S, H, Dh]    k = h Wk, v = h Wv -> [S, KV, Dh]
    q, k <- RMSNorm over Dh (gamma_q, gamma_k), then rotate-half RoPE at
             `rope_theta`, its frequencies blended and its cos and sin
             scaled under a `rope_scaling` (YaRN: `rope_frequencies`)
    indexer  I(t, s) = sum_j w(t, j) IH^-1/2 relu(qi(t, j) . ki(s)) ID^-1/2
             with qi = h Wiq, ki = LayerNorm(h Wik), w = h Wiw, RoPE on qi, ki
    select   S(t) = the index_top_k largest I(t, s) over s <= t, ties to the
             earlier position; all of s <= t while t < index_top_k.
             Without an indexer: S(t) = {s: t - sliding_window < s <= t}, or
             every s <= t, or every s (`causal=False`)
    attend   o(t, head) = softmax over S(t) of q . k / sqrt(Dh), times v,
             head `h` reading key/value head `h // (H / KV)`
    out = o Wo

The selection is exact: the threshold of a row is its `index_top_k`-th
largest score, found by bisection on the scores' bit patterns (31 passes
of compare-and-count over the [S, S] scores; a sort of the same rows took
four times as long on a v5e, PERF.md PR 26), and ties at the threshold are
broken by position in a branch that runs only when a row has one. S(t)
is a constant of the backward pass: the indexer's leaves are frozen
(`SelfAttentionLayer.frozen_param_names`) and nothing differentiates
through a comparison.

Under an indexer the attention is a dense causal pass under that mask, and
no [H, S, S] tensor is ever kept. `masked_gqa_attention` resolves the kernel
registry's `masked_attention`: on a TPU, at tile-multiple shapes in bf16 or
f32, one Pallas flash body forward and backward that takes the [S, S]
selection as an int8 operand and shares each K, V and mask tile among the
H/KV query heads of a KV head (`kernels/flash_attention.py`, PERF.md PR 27);
anywhere else (the CPU, float64, an S off the tile, `DL4J_TPU_KERNELS=xla`)
XLA row blocks, each recomputed in the backward pass (`jax.checkpoint`),
which are also the kernel's parity reference. Both take the layer's
`causal`: with it no tile or row block past a row's own position is read,
without it (a bidirectional layer, whose mask is all ones) every key is.
A gather of `index_top_k` key rows per query would move H/KV times less
arithmetic and 50 times more bytes (PERF.md PR 26).

A layer WITHOUT an indexer neither builds nor returns an [S, S] array (PR
30). `banded_gqa_attention` resolves the registry's `banded_attention`: the
same Pallas kernels with no mask operand, the tile's mask built from two
iotas and, under a window, only the tiles that meet the band visited; or
XLA row blocks that read the keys `[lo - window, hi)` of a block of rows
`[lo, hi)` only. The layer's declared state is then `band_fill_share`, the
share of the pairs in the tiles the Pallas body visits that the band holds
(static; 0 where the XLA body ran: it visits no tiles).

`jax.named_scope`s name the parts in a device trace: `dsa.indexer`,
`dsa.select`, `dsa.attend` under an indexer; `attn.sliding` or `attn.full`
around the attention of a layer without one; `attn.rope` around the rotary
step of either.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG = -1e30
# The RMS norm on a latent-attention layer's compressed key/value latent: the
# published DeepSeek-V2/V3 code builds that norm with its class default, not
# with the model's `rms_norm_eps`.
LATENT_NORM_EPS = 1e-6


def rms_norm(x, gamma, eps):
    """x / rms(x) * gamma over the last axis; statistics in >= float32."""
    acc = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(acc)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(acc)).astype(x.dtype)


def layer_norm(x, gamma, beta, eps):
    acc = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(acc)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(acc) + beta.astype(acc)).astype(x.dtype)


def rope_frequencies(D: int, theta: float, scaling, dtype):
    """(inverse frequencies [D/2], the factor on cos and sin) of a rotary
    embedding over heads of D. `scaling` None: theta^(-2i/D) and 1. YaRN
    (Peng et al. 2023, as `transformers` computes it; keys `factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow`,
    `attention_factor`): with c(r) = D ln(L0 / (2 pi r)) /
    (2 ln theta), low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
    both clipped to [0, D - 1], ramp_i = clip((i - low) / (high - low), 0,
    1), frequency i is theta^(-2i/D) (1 - ramp_i) + theta^(-2i/D) / factor
    ramp_i: the fast dimensions keep their frequency, the slow ones are
    interpolated; cos and sin are multiplied by `attention_factor`
    (default 0.1 ln factor + 1)."""
    import math

    inv = theta ** (-jnp.arange(0, D, 2, dtype=dtype) / D)          # [D/2]
    if scaling is None:
        return inv, 1.0
    if scaling.get("rope_type", "yarn") != "yarn":
        raise ValueError(f"rope_scaling {scaling.get('rope_type')!r}: only "
                         "YaRN is implemented")
    factor = scaling["factor"]
    L0 = scaling["original_max_position_embeddings"]

    def c(rotations):
        return D * math.log(L0 / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    low = max(math.floor(c(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(c(scaling.get("beta_slow", 1))), D - 1)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=dtype) - low)
                    / (high - low if high != low else 0.001), 0.0, 1.0)
    mscale = scaling.get("attention_factor")
    if mscale is None:
        mscale = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv * (1 - ramp) + inv / factor * ramp, mscale


def rope(x, theta: float, scaling=None):
    """Rotate-half rotary embedding over the last axis of [S, ..., D]
    (position t on axis 0): pairs (i, i + D/2) turn by t * theta^(-2i/D),
    or by `rope_frequencies`' under a `scaling`. The registry's `rotary`
    decides between the Pallas body (`kernels/rotary.py`: one pass each way
    over the operand) and `rope_xla`; both take cos and sin from the same
    operations and compute in the same float32."""
    from deeplearning4j_tpu.kernels import registry, rotary

    res = registry.resolve("rotary", shapes=rotary.signature(x),
                           dtypes=(str(x.dtype),))
    if res.impl != "pallas":
        return rope_xla(x, theta, scaling)
    acc = jnp.promote_types(x.dtype, jnp.float32)
    t = jnp.arange(x.shape[0], dtype=acc)
    inv, mscale = rope_frequencies(x.shape[-1], theta, scaling, acc)
    return rotary.rotate(x, *rotary.tables(t, inv, mscale))


def rope_xla(x, theta: float, scaling=None):
    """`rope` in XLA: the two halves of every head apart, in >= float32,
    and concatenated again."""
    S, D = x.shape[0], x.shape[-1]
    acc = jnp.promote_types(x.dtype, jnp.float32)
    t = jnp.arange(S, dtype=acc)
    inv, mscale = rope_frequencies(D, theta, scaling, acc)
    ang = t[:, None] * inv[None, :]                                # [S, D/2]
    shape = (S,) + (1,) * (x.ndim - 2) + (D // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    xf = x.astype(acc)
    x1, x2 = xf[..., : D // 2], xf[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _row_blocks(S: int, block: int):
    block = min(block, S)
    if S % block:
        raise ValueError(f"sequence length {S} is not a multiple of the "
                         f"attention row block {block}")
    return [(i, i + block) for i in range(0, S, block)]


def index_scores(qi, ki, w, *, block: int = 512):
    """I(t, s) for all t, s <= t; -inf above the diagonal. qi: [S, IH, ID],
    ki: [S, ID], w: [S, IH] -> [S, S] float32 (float64 if the inputs are).
    Row blocks, so that only [block, IH, S] scores exist at a time."""
    S, IH, ID = qi.shape
    acc = jnp.promote_types(qi.dtype, jnp.float32)
    scale = (IH ** -0.5) * (ID ** -0.5)
    rows = []
    for lo, hi in _row_blocks(S, block):
        s = jnp.einsum("thd,sd->ths", qi[lo:hi], ki[:hi],
                       preferred_element_type=acc)
        r = jnp.einsum("ths,th->ts", jax.nn.relu(s),
                       w[lo:hi].astype(acc)) * scale
        causal = (jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None])
        r = jnp.where(causal, r, -jnp.inf)
        rows.append(jnp.pad(r, ((0, 0), (0, S - hi)),
                            constant_values=-jnp.inf))
    return jnp.concatenate(rows, axis=0)


def _sort_keys(scores):
    """float32 scores -> int32 keys in the same order (-0.0 and 0.0 equal)."""
    # The keys ARE float32's bit patterns: the index scores' own dtype.
    u = jax.lax.bitcast_convert_type(
        (scores + 0.0).astype(jnp.float32),  # tpulint: disable=JX009
        jnp.int32)
    return jnp.where(u < 0, u ^ jnp.int32(0x7FFFFFFF), u)


def kth_largest_key(keys, k: int):
    """Per row of int32 `keys` [R, S], the k-th largest (rows are assumed to
    hold at least k entries that matter): bisection from the sign bit down,
    one compare-and-count pass over `keys` per bit."""
    def count_ge(c):
        return jnp.sum(keys >= c[:, None], axis=1, dtype=jnp.int32)

    nonneg = count_ge(jnp.zeros(keys.shape[0], jnp.int32)) >= k
    lo = jnp.where(nonneg, jnp.int32(0), jnp.int32(-2 ** 31))

    def body(i, lo):
        cand = lo + jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count_ge(cand) >= k, cand, lo)

    return jax.lax.fori_loop(0, 31, body, lo)


def select_top_k(scores, k: int, *, span: int = 2048):
    """[S, S] index scores (-inf above the diagonal) -> bool mask of S(t):
    exactly min(t + 1, k) keys a row, the largest scores, ties to the earlier
    position. Rows below k keep all their keys; the others are searched in
    spans of rows, each over the keys up to its own end only."""
    S = scores.shape[0]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    if S <= k:
        return causal
    parts = [causal[:k]]
    for lo, hi in [(lo, min(lo + span, S)) for lo in range(k, S, span)]:
        keys = _sort_keys(scores[lo:hi, :hi])
        thr = kth_largest_key(keys, k)[:, None]
        above = keys > thr
        at = keys == thr
        n_above = jnp.sum(above, axis=1, keepdims=True, dtype=jnp.int32)
        n_at = jnp.sum(at, axis=1, keepdims=True, dtype=jnp.int32)

        def with_ties(_, above=above, at=at, n_above=n_above):
            rank = jnp.cumsum(at, axis=1, dtype=jnp.int32)
            return above | (at & (rank <= k - n_above))

        chosen = jax.lax.cond(jnp.any(n_above + n_at > k), with_ties,
                              lambda _, above=above, at=at: above | at, None)
        parts.append(jnp.pad(chosen, ((0, 0), (0, S - hi))))
    return jnp.concatenate(parts, axis=0)


def masked_gqa_attention(q, k, v, keep, causal: bool = True):
    """Dense masked attention. q: [S, H, Dh]; k, v: [S, KV, Dh]; keep: [S, S]
    bool (row t's keys) -> [S, H, Dh]. `causal` says that `keep` holds
    nothing above the diagonal, so no row block reads the keys past its own
    end. Softmax in >= float32. The registry's `masked_attention` decides
    between the Pallas flash body and `masked_gqa_attention_xla`."""
    from deeplearning4j_tpu.kernels import flash_attention, registry

    res = registry.resolve(
        "masked_attention", dtypes=(str(q.dtype),),
        shapes=tuple(int(d) for d in q.shape) + (int(k.shape[1]),))
    if res.impl == "pallas":
        return flash_attention.masked_attention(q, k, v, keep, causal)
    return masked_gqa_attention_xla(q, k, v, keep, causal)


def _grouped(q, k, v):
    """[S, H, Dh], [S, KV, Dh] x 2 -> q as [KV, G, S, Dh], k and v as
    [KV, S, Dh]."""
    S, H, Dh = q.shape
    KV = k.shape[1]
    return (jnp.transpose(q.reshape(S, KV, H // KV, Dh), (1, 2, 0, 3)),
            jnp.transpose(k, (1, 0, 2)), jnp.transpose(v, (1, 0, 2)))


def _ungrouped(o):
    """[KV, G, S, Dh] -> [S, H, Dh]."""
    KV, G, S, Dh = o.shape
    return jnp.transpose(o, (2, 0, 1, 3)).reshape(S, KV * G, Dh)


@jax.checkpoint
def _rows_attention(qb, kb, vb, mb):
    """One block of rows over the keys it is given, recomputed in the
    backward pass. qb: [KV, G, b, Dh]; kb, vb: [KV, n, Dh]; mb: [b, n] bool
    or None (every key) -> [KV, G, b, Dh]."""
    acc = jnp.promote_types(qb.dtype, jnp.float32)
    s = jnp.einsum("ghtd,gsd->ghts", qb, kb,
                   preferred_element_type=acc) * qb.shape[-1] ** -0.5
    if mb is not None:
        s = jnp.where(mb[None, None], s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    den = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("ghts,gsd->ghtd", e.astype(vb.dtype), vb,
                   preferred_element_type=acc)
    return (o / den).astype(qb.dtype)


def _blocks_of_rows(qg, lo, hi, block):
    """Rows lo..hi of `qg` [KV, G, S, Dh] as [n, KV, G, b, Dh] blocks."""
    KV, G, _, Dh = qg.shape
    blocks = _row_blocks(hi - lo, block)
    n, b = len(blocks), blocks[0][1]
    return jnp.moveaxis(qg[:, :, lo:hi].reshape(KV, G, n, b, Dh), 2, 0), n, b


def _span_attention(q, k, v, causal, block, span, per_block, mask_of):
    """Attention in blocks of `block` rows, each recomputed in the backward
    pass; the blocks of one `span` of rows run as a loop over the keys up to
    the span's end, or over all of them where not `causal` (one compiled
    body a span: blocks of 256 over exactly their own keys were 13% faster
    on a v5e and eight times the program, PERF.md PR 26).
    `per_block(lo, hi, n, b, keys)` gives what the loop carries for each of
    a span's n blocks, `mask_of(that, b, keys)` a block's `[b, keys]` mask."""
    S = q.shape[0]
    qg, kg, vg = _grouped(q, k, v)
    out = []
    for lo, hi in _row_blocks(S, span):
        keys = hi if causal else S
        qs, n, b = _blocks_of_rows(qg, lo, hi, block)
        o = jax.lax.map(
            lambda a, b=b, keys=keys: _rows_attention(
                a[0], kg[:, :keys], vg[:, :keys], mask_of(a[1], b, keys)),
            (qs, per_block(lo, hi, n, b, keys)))         # [n, KV, G, b, Dh]
        out.append(jnp.moveaxis(o, 0, 2).reshape(*qg.shape[:2], hi - lo, -1))
    return _ungrouped(jnp.concatenate(out, axis=2))


def masked_gqa_attention_xla(q, k, v, keep, causal: bool = True, *,
                             block: int = 256, span: int = 2048):
    """`masked_gqa_attention` in XLA row blocks (`_span_attention`), a
    block's mask its rows of `keep`."""
    return _span_attention(
        q, k, v, causal, block, span,
        lambda lo, hi, n, b, keys: keep[lo:hi, :keys].reshape(n, b, keys),
        lambda rows, b, keys: rows)


def banded_gqa_attention(q, k, v, window=None, causal: bool = True):
    """Attention over the causal triangle, over the band `t - window < s <=
    t` of a sliding-window layer, or over every key (`causal=False`), with
    no `[S, S]` array anywhere. q: [S, H, Dh]; k, v: [S, KV, Dh] ->
    ([S, H, Dh], the share of the pairs in the tiles the Pallas body visits
    that lie inside the band: a float, static; 0.0 from the XLA body, which
    visits no tiles). The registry's `banded_attention` decides
    between the Pallas flash body (`kernels/flash_attention.py`: the masked
    kernels with the tile's mask from iotas, only the tiles that meet the
    band visited) and `banded_gqa_attention_xla`."""
    from deeplearning4j_tpu.kernels import flash_attention, registry

    if window is not None and not causal:
        raise ValueError("sliding_window reads earlier keys: the layer must "
                         "be causal")
    res = registry.resolve(
        "banded_attention", dtypes=(str(q.dtype),),
        shapes=tuple(int(d) for d in q.shape) + (int(k.shape[1]),))
    S, H, Dh = q.shape
    if res.impl == "pallas":
        return (flash_attention.banded_attention(q, k, v, window, causal),
                flash_attention.band_fill_share(
                    S, H // k.shape[1], Dh, q.dtype.itemsize, window, causal))
    return banded_gqa_attention_xla(q, k, v, window, causal), 0.0


def _window_reach(window: int, block: int) -> int:
    """Keys before a block's first row that its rows read under a window:
    window - 1, rounded up to whole blocks."""
    return -(-(window - 1) // block) * block


def banded_gqa_attention_xla(q, k, v, window=None, causal: bool = True, *,
                             block: int = 256, span: int = 2048):
    """`banded_gqa_attention` in XLA row blocks, each recomputed in the
    backward pass, the mask of a block built from its rows' and keys'
    positions. Under a window shorter than the sequence a block of rows
    `[lo, hi)` reads only the keys `[lo - W, hi)`, W the window rounded up
    to whole blocks (one compiled body, the keys padded by W in front);
    else `_span_attention`, as `masked_gqa_attention_xla`."""
    S = q.shape[0]
    if window is None or window >= S:
        return _span_attention(
            q, k, v, causal, block, span,
            lambda lo, hi, n, b, keys: lo + jnp.arange(n) * b,
            lambda r0, b, keys: (jnp.arange(keys)[None, :]
                                 <= r0 + jnp.arange(b)[:, None])
            if causal else None)
    qg, kg, vg = _grouped(q, k, v)
    qs, n, b = _blocks_of_rows(qg, 0, S, block)
    W = _window_reach(window, b)
    pad = lambda a: jnp.pad(a, ((0, 0), (W, 0), (0, 0)))
    kp, vp = pad(kg), pad(vg)

    def one(a):
        qb, r0 = a
        rows = r0 + jnp.arange(b)[:, None]
        cols = r0 - W + jnp.arange(W + b)[None, :]
        keep = (cols >= 0) & (cols <= rows) & (cols > rows - window)
        keys = lambda x: jax.lax.dynamic_slice_in_dim(x, r0, W + b, 1)
        return _rows_attention(qb, keys(kp), keys(vp), keep)

    o = jax.lax.map(one, (qs, jnp.arange(n) * b))        # [n, KV, G, b, Dh]
    return _ungrouped(jnp.moveaxis(o, 0, 2).reshape(qg.shape))


def latent_attention(q_n, q_r, k_n, k_r, v):
    """Causal attention whose score is the sum of two products, the second
    against a key all heads share: q_n, k_n: [S, H, Dn]; q_r: [S, H, Dr];
    k_r: [S, Dr]; v: [S, H, Dv] -> ([S, H, Dv], the Pallas body's fill share
    as `banded_gqa_attention` gives it). `score_h[t, s] = (q_n,h[t] .
    k_n,h[s] + q_r,h[t] . k_r[s]) / sqrt(Dn + Dr)`. The registry's
    `latent_attention` decides between the Pallas flash body
    (`kernels/flash_attention.py`: the masked kernels with the second
    product added into the score tile, 2 (Dn + Dr) FLOP a pair for the score
    and 2 Dv for the values, no operand padded) and
    `latent_attention_xla`."""
    from deeplearning4j_tpu.kernels import flash_attention, registry

    S, H, Dn = q_n.shape
    Dr, Dv = q_r.shape[2], v.shape[2]
    res = registry.resolve("latent_attention", dtypes=(str(q_n.dtype),),
                           shapes=(int(S), int(H), int(Dn), int(Dr), int(Dv)))
    if res.impl == "pallas":
        return (flash_attention.latent_attention(q_n, q_r, k_n, k_r, v),
                flash_attention.band_fill_share(S, 1, Dn + Dr,
                                                q_n.dtype.itemsize))
    return latent_attention_xla(q_n, q_r, k_n, k_r, v), 0.0


def latent_attention_xla(q_n, q_r, k_n, k_r, v):
    """`latent_attention` in XLA row blocks: the two parts of each head's
    query side by side, the shared rotary key copied beside every head's own
    part, and `banded_gqa_attention_xla` over heads of Dn + Dr with values of
    Dv (its scale is that width's). The shared key's gradient is the sum
    over the heads by the broadcast's transpose."""
    S, H, _ = q_n.shape
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, None, :], (S, H, k_r.shape[-1]))],
        axis=-1)
    return banded_gqa_attention_xla(q, k, v)


def _latent_sequence(conf, params, h):
    """Multi-head latent attention over one sequence h: [S, n_in] ->
    (out [S, n_out], None, the fill share of its Pallas body's tiles):

        q = h Wq -> [S, H, Dn + Dr], split q_n | q_r
        [c ; k_r] = h Wdkv -> kv_lora_rank + Dr;  c <- RMSNorm(c; gamma_kv)
        [k_n ; v] = c Wukv -> [S, H, Dn + Dv]
        q_r, k_r <- rotate-half RoPE at rope_theta; k_r is one head for all
        o_h(t) = sum_{s <= t} softmax_s((q_n,h . k_n,h + q_r,h . k_r)
                 / sqrt(Dn + Dr)) v_h(s);   out = concat_h(o_h) Wo"""
    S, H = h.shape[0], conf.n_heads
    R, Dn, Dr, Dv = (conf.kv_lora_rank, conf.qk_nope_head_dim,
                     conf.qk_rope_head_dim, conf.v_head_dim)
    with jax.named_scope("mla.project"):
        q = (h @ params["Wq"]).reshape(S, H, Dn + Dr)
        ckv = h @ params["Wdkv"]
        c = rms_norm(ckv[:, :R], params["gamma_kv"], LATENT_NORM_EPS)
        kv = (c @ params["Wukv"]).reshape(S, H, Dn + Dv)
    with jax.named_scope("attn.rope"):
        q_r = rope(q[..., Dn:], conf.rope_theta)
        k_r = rope(ckv[:, R:], conf.rope_theta)
    with jax.named_scope(conf.attention_scope()):
        o, fill = latent_attention(q[..., :Dn], q_r, kv[..., :Dn], k_r,
                                   kv[..., Dn:])
    acc = jnp.promote_types(h.dtype, jnp.float32)
    return o.reshape(S, H * Dv) @ params["Wo"], None, jnp.asarray(fill, acc)


def select_keys(conf, params, h):
    """h: [S, n_in] -> bool [S, S], the keys each query of a layer with
    `index_top_k` attends to: the indexer's selection S(t). A constant of
    the backward pass."""
    S = h.shape[0]
    IH, ID = conf.index_n_heads, conf.index_head_dim
    with jax.named_scope("dsa.indexer"):
        hs = jax.lax.stop_gradient(h)
        qi = (hs @ params["Wiq"]).reshape(S, IH, ID)
        ki = layer_norm(hs @ params["Wik"], params["gamma_ik"],
                        params["beta_ik"], 1e-6)
        w = hs @ params["Wiw"]
        if conf.rope_theta is not None:
            qi, ki = rope(qi, conf.rope_theta), rope(ki, conf.rope_theta)
        scores = index_scores(qi, ki, w)
    with jax.named_scope("dsa.select"):
        return jax.lax.stop_gradient(
            select_top_k(scores, conf.index_top_k))


def _one_sequence(conf, params, h):
    """h: [S, n_in] -> (out [S, n_out], the keys each query attended to as
    bool [S, S], their mean number a query) under an indexer; without one
    (out, None, the share of the pairs in the tiles its attention's Pallas
    body visits that lie inside the causal band: static per layer, sequence
    length and block choice; 0 from the XLA body)."""
    if conf.kv_lora_rank is not None:
        return _latent_sequence(conf, params, h)
    S = h.shape[0]
    H = conf.n_heads
    KV = conf.n_kv_heads or H
    Dh = conf.head_dim or conf.n_out // H

    q = (h @ params["Wq"]).reshape(S, H, Dh)
    k = (h @ params["Wk"]).reshape(S, KV, Dh)
    v = (h @ params["Wv"]).reshape(S, KV, Dh)
    if conf.qk_norm_eps is not None:
        q = rms_norm(q, params["gamma_q"], conf.qk_norm_eps)
        k = rms_norm(k, params["gamma_k"], conf.qk_norm_eps)
    if conf.rope_theta is not None:
        with jax.named_scope("attn.rope"):
            q = rope(q, conf.rope_theta, conf.rope_scaling)
            k = rope(k, conf.rope_theta, conf.rope_scaling)

    acc = jnp.promote_types(h.dtype, jnp.float32)
    if conf.index_top_k is None:
        keep = None
        with jax.named_scope(conf.attention_scope()):
            o, fill = banded_gqa_attention(q, k, v, conf.sliding_window,
                                           conf.causal)
        stat = jnp.asarray(fill, acc)
    else:
        keep = select_keys(conf, params, h)
        stat = jnp.mean(jnp.sum(keep, axis=1, dtype=acc))
        with jax.named_scope("dsa.attend"):
            o = masked_gqa_attention(q, k, v, keep, conf.causal)
    return o.reshape(S, H * Dh) @ params["Wo"], keep, stat


def extended_attention_apply(conf, params, state, x, mask=None):
    """x: [B, T, n_in] -> [B, T, n_out]; see the module docstring."""
    from deeplearning4j_tpu.nn import activations

    if conf.decode_cache_length:
        raise ValueError(
            "grouped-query / rotary / sparse attention has no decode cache "
            "yet (sparse selection inside paged decode: ROADMAP B10)")
    if mask is not None:
        raise ValueError("grouped-query / rotary / sparse attention takes "
                         "no features mask: pad to full length")
    if conf.index_top_k is not None and not conf.causal:
        raise ValueError("index_top_k selects among earlier positions: the "
                         "layer must be causal")
    if conf.index_top_k is not None and conf.sliding_window is not None:
        raise ValueError("a layer selects its keys by index_top_k or by "
                         "sliding_window, not both")
    if x.shape[0] == 1:
        out, keep, stat = _one_sequence(conf, params, x[0])
        out = out[None]
        keep = None if keep is None else keep[None]
    else:
        out, keep, stat = jax.vmap(
            lambda h: _one_sequence(conf, params, h))(x)
        stat = jnp.mean(stat)
    out = activations.resolve(conf.activation)(out)
    if keep is None:
        return out, dict(state, band_fill_share=stat), mask
    # `_selected_keys` is a by-product: no engine keeps it as state, and
    # `ComputationGraph.loss_and_gradients(collect=["<layer>.selected_keys"])`
    # hands it out of the pass that used it.
    return out, dict(state, _selected_keys=keep,
                     selected_keys_mean=stat), mask
