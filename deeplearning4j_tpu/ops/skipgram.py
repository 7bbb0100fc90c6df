"""Batched skip-gram / CBOW update kernels.

TPU-native equivalent of ND4J's fused `AggregateSkipGram`/`AggregateCBOW`
native ops (reference: `learning/impl/elements/SkipGram.java:17,258-264` —
the op boundary of Word2Vec training, SURVEY.md §3.5). The reference trains
with lock-free Hogwild threads mutating shared syn0/syn1; that doesn't map to
functional TPU updates (SURVEY.md §7 hard part (c)), so here a BATCH of
(center, target) pairs becomes one jitted program: gather -> fused sigmoid
cross-entropy -> segment-sum scatter-add updates, with donated tables.

All batches are padded to fixed sizes (pair_mask marks real pairs) so each
batch shape compiles exactly once.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

MAX_EXP = 6.0  # word2vec exp-table range; gradients are cut off beyond it
MAX_ROW_UPDATE_NORM = 1.0  # L2 cap on a row's AGGREGATED per-batch update


def _clip_rows(update):
    """Cap each row's aggregated update norm. Sequential Hogwild (the
    reference) self-stabilizes because each pair sees the previous pair's
    write; a batched scatter-add applies all collided updates against the
    same stale state, which oscillates/diverges when one row collects many
    contributions (small vocabs, very frequent words). Normal aggregates sit
    far below this cap, so typical training is unaffected."""
    norm = jnp.linalg.norm(update, axis=-1, keepdims=True)
    return update * jnp.minimum(1.0, MAX_ROW_UPDATE_NORM / jnp.maximum(norm, 1e-12))


@partial(jax.jit, donate_argnums=(0, 1))
def hs_skipgram_step(syn0, syn1, centers, codes, points, code_mask, pair_mask, lr):
    """Hierarchical-softmax skip-gram update.

    syn0: [V, D] word vectors; syn1: [I, D] inner-node vectors.
    centers: [B] word whose vector is updated (the context word in w2v
    convention); codes/points/code_mask: [B, L] Huffman paths of the predicted
    word; pair_mask: [B] marks real (non-padding) pairs.
    """
    V, D = syn0.shape
    B, L = codes.shape
    m = code_mask * pair_mask[:, None]  # [B, L]

    h = syn0[centers]  # [B, D]
    nodes = syn1[points]  # [B, L, D]
    logits = jnp.einsum("bd,bld->bl", h, nodes)
    f = jax.nn.sigmoid(logits)
    g = (1.0 - codes.astype(syn0.dtype) - f) * lr * m  # [B, L]
    # word2vec MAX_EXP semantics: saturated nodes contribute no update (the
    # C reference `continue`s outside +-6) — also the stabilizer that bounds
    # batched scatter-add aggregation over repeated indices.
    g = jnp.where(jnp.abs(logits) < MAX_EXP, g, 0.0)

    # dL/dh accumulated from the old syn1 (word2vec update order).
    h_grad = jnp.einsum("bl,bld->bd", g, nodes)  # [B, D]

    # syn1[points] += g * h  (scatter-add over flattened B*L)
    contrib1 = (g[:, :, None] * h[:, None, :]).reshape(B * L, D)
    syn1 = syn1 + _clip_rows(jax.ops.segment_sum(
        contrib1, points.reshape(-1), num_segments=syn1.shape[0]))

    # syn0[centers] += h_grad
    syn0 = syn0 + _clip_rows(jax.ops.segment_sum(h_grad, centers, num_segments=V))
    return syn0, syn1


@partial(jax.jit, donate_argnums=(0, 1))
def ns_skipgram_step(syn0, syn1neg, centers, targets, labels, pair_mask, lr):
    """Negative-sampling skip-gram update.

    targets: [B, 1+K] (positive word first, then K sampled negatives);
    labels: [B, 1+K] 1/0.
    """
    V, D = syn0.shape
    B, K1 = targets.shape
    h = syn0[centers]
    tv = syn1neg[targets]  # [B, K1, D]
    logits = jnp.einsum("bd,bkd->bk", h, tv)
    f = jax.nn.sigmoid(logits)
    lab = labels.astype(syn0.dtype)
    g = (lab - f) * lr * pair_mask[:, None]
    # word2vec MAX_EXP saturation (C reference): g = (label-1)*alpha above
    # +6, label*alpha below -6.
    g = jnp.where(logits > MAX_EXP, (lab - 1.0) * lr * pair_mask[:, None], g)
    g = jnp.where(logits < -MAX_EXP, lab * lr * pair_mask[:, None], g)

    h_grad = jnp.einsum("bk,bkd->bd", g, tv)
    contrib = (g[:, :, None] * h[:, None, :]).reshape(B * K1, D)
    syn1neg = syn1neg + _clip_rows(jax.ops.segment_sum(
        contrib, targets.reshape(-1), num_segments=syn1neg.shape[0]))
    syn0 = syn0 + _clip_rows(jax.ops.segment_sum(h_grad, centers, num_segments=V))
    return syn0, syn1neg


@partial(jax.jit, donate_argnums=(0, 1))
def ns_cbow_step(syn0, syn1neg, context, context_mask, targets, labels,
                 pair_mask, lr):
    """Negative-sampling CBOW update (reference: `AggregateCBOW` native op
    invoked from `learning/impl/elements/CBOW.java:160` with negative > 0 —
    word2vec.c semantics: h = mean of context vectors trained against the
    positive word + K sampled negatives on syn1neg, with the accumulated
    input gradient distributed to every context word).

    context: [B, W] padded context indices; context_mask: [B, W];
    targets: [B, 1+K] (positive first); labels: [B, 1+K] 1/0.
    """
    V, D = syn0.shape
    B, W = context.shape
    cm = context_mask * pair_mask[:, None]
    counts = jnp.maximum(jnp.sum(cm, axis=1, keepdims=True), 1.0)
    ctx = syn0[context] * cm[:, :, None]
    h = jnp.sum(ctx, axis=1) / counts                 # [B, D]

    tv = syn1neg[targets]                             # [B, 1+K, D]
    logits = jnp.einsum("bd,bkd->bk", h, tv)
    f = jax.nn.sigmoid(logits)
    lab = labels.astype(syn0.dtype)
    g = (lab - f) * lr * pair_mask[:, None]
    g = jnp.where(logits > MAX_EXP, (lab - 1.0) * lr * pair_mask[:, None], g)
    g = jnp.where(logits < -MAX_EXP, lab * lr * pair_mask[:, None], g)

    h_grad = jnp.einsum("bk,bkd->bd", g, tv)          # [B, D]
    K1 = targets.shape[1]
    contrib = (g[:, :, None] * h[:, None, :]).reshape(B * K1, D)
    syn1neg = syn1neg + _clip_rows(jax.ops.segment_sum(
        contrib, targets.reshape(-1), num_segments=syn1neg.shape[0]))

    per_word = jnp.broadcast_to(h_grad[:, None, :], (B, W, D)) * cm[:, :, None]
    syn0 = syn0 + _clip_rows(jax.ops.segment_sum(
        per_word.reshape(B * W, D), context.reshape(-1), num_segments=V))
    return syn0, syn1neg


@partial(jax.jit, donate_argnums=(0, 1))
def hs_cbow_step(syn0, syn1, context, context_mask, codes, points, code_mask,
                 pair_mask, lr):
    """Hierarchical-softmax CBOW update: h = mean of context vectors; the
    input-gradient is distributed back to every context word.

    context: [B, W] context word indices (padded); context_mask: [B, W].
    """
    V, D = syn0.shape
    B, W = context.shape
    cm = context_mask * pair_mask[:, None]
    counts = jnp.maximum(jnp.sum(cm, axis=1, keepdims=True), 1.0)  # [B,1]
    ctx = syn0[context] * cm[:, :, None]  # [B, W, D]
    h = jnp.sum(ctx, axis=1) / counts  # [B, D]

    nodes = syn1[points]
    logits = jnp.einsum("bd,bld->bl", h, nodes)
    f = jax.nn.sigmoid(logits)
    m = code_mask * pair_mask[:, None]
    g = (1.0 - codes.astype(syn0.dtype) - f) * lr * m
    g = jnp.where(jnp.abs(logits) < MAX_EXP, g, 0.0)

    h_grad = jnp.einsum("bl,bld->bd", g, nodes)  # [B, D]
    L = codes.shape[1]
    contrib1 = (g[:, :, None] * h[:, None, :]).reshape(B * L, D)
    syn1 = syn1 + _clip_rows(jax.ops.segment_sum(
        contrib1, points.reshape(-1), num_segments=syn1.shape[0]))

    # Each context word gets the full h_grad (word2vec reference behavior).
    per_word = jnp.broadcast_to(h_grad[:, None, :], (B, W, D)) * cm[:, :, None]
    syn0 = syn0 + _clip_rows(jax.ops.segment_sum(
        per_word.reshape(B * W, D), context.reshape(-1), num_segments=V))
    return syn0, syn1


@partial(jax.jit, donate_argnums=(0, 1))
def hs_skipgram_step_tbl(syn0, syn1, centers, words, codes_tbl, points_tbl,
                         cmask_tbl, pair_mask, lr):
    """HS skip-gram with device-resident Huffman tables: gathers the [B, L]
    paths from the [V, L] tables ON DEVICE, so each flush ships only [B]
    int32 indices over the host link. (The host-side `codes_tbl[words]`
    gather + its [B, L] transfer per flush dominated training time over a
    high-latency transport — PERF.md §5.)"""
    return hs_skipgram_step.__wrapped__(
        syn0, syn1, centers, codes_tbl[words], points_tbl[words],
        cmask_tbl[words], pair_mask, lr)


@partial(jax.jit, donate_argnums=(0, 1))
def hs_cbow_step_tbl(syn0, syn1, context, context_mask, words, codes_tbl,
                     points_tbl, cmask_tbl, pair_mask, lr):
    """HS CBOW with device-resident Huffman tables (see hs_skipgram_step_tbl)."""
    return hs_cbow_step.__wrapped__(
        syn0, syn1, context, context_mask, codes_tbl[words],
        points_tbl[words], cmask_tbl[words], pair_mask, lr)


@partial(jax.jit, donate_argnums=(0, 1))
def hs_skipgram_scan_tbl(syn0, syn1, centers, words, codes_tbl, points_tbl,
                         cmask_tbl, pair_mask, lrs):
    """K stacked HS skip-gram batches in ONE dispatch: `lax.scan` of
    `hs_skipgram_step_tbl` over the leading K axis: the word2vec flush
    loop batches K flushes per dispatch, so the host pays one dispatch for
    K steps (what a dispatch costs is not measured on the current machine).

    centers/words/pair_mask: [K, B]; lrs: [K]."""
    def body(carry, inp):
        syn0, syn1 = carry
        c, w, pm, lr = inp
        syn0, syn1 = hs_skipgram_step_tbl.__wrapped__(
            syn0, syn1, c, w, codes_tbl, points_tbl, cmask_tbl, pm, lr)
        return (syn0, syn1), None

    (syn0, syn1), _ = jax.lax.scan(
        body, (syn0, syn1), (centers, words, pair_mask, lrs))
    return syn0, syn1


@partial(jax.jit, donate_argnums=(0, 1))
def hs_cbow_scan_tbl(syn0, syn1, context, context_mask, words, codes_tbl,
                     points_tbl, cmask_tbl, pair_mask, lrs):
    """K stacked HS CBOW batches in one dispatch (see hs_skipgram_scan_tbl).
    context/context_mask: [K, B, W]; words/pair_mask: [K, B]; lrs: [K]."""
    def body(carry, inp):
        syn0, syn1 = carry
        ctx, cm, w, pm, lr = inp
        syn0, syn1 = hs_cbow_step_tbl.__wrapped__(
            syn0, syn1, ctx, cm, w, codes_tbl, points_tbl, cmask_tbl, pm, lr)
        return (syn0, syn1), None

    (syn0, syn1), _ = jax.lax.scan(
        body, (syn0, syn1), (context, context_mask, words, pair_mask, lrs))
    return syn0, syn1


@partial(jax.jit, donate_argnums=(0, 1))
def ns_skipgram_scan(syn0, syn1neg, centers, targets, labels, pair_mask, lrs):
    """K stacked NS skip-gram batches in one dispatch (see
    hs_skipgram_scan_tbl). centers/pair_mask: [K, B]; targets:
    [K, B, 1+neg]; labels: [B, 1+neg] SHARED across the K batches (it is a
    constant — positive first, zeros after — so it uploads once, not per
    dispatch); lrs: [K]."""
    def body(carry, inp):
        syn0, syn1neg = carry
        c, t, pm, lr = inp
        syn0, syn1neg = ns_skipgram_step.__wrapped__(
            syn0, syn1neg, c, t, labels, pm, lr)
        return (syn0, syn1neg), None

    (syn0, syn1neg), _ = jax.lax.scan(
        body, (syn0, syn1neg), (centers, targets, pair_mask, lrs))
    return syn0, syn1neg


@partial(jax.jit, donate_argnums=(0, 1))
def ns_cbow_scan(syn0, syn1neg, context, context_mask, targets, labels,
                 pair_mask, lrs):
    """K stacked NS CBOW batches in one dispatch; labels [B, 1+neg] shared
    (see ns_skipgram_scan)."""
    def body(carry, inp):
        syn0, syn1neg = carry
        ctx, cm, t, pm, lr = inp
        syn0, syn1neg = ns_cbow_step.__wrapped__(
            syn0, syn1neg, ctx, cm, t, labels, pm, lr)
        return (syn0, syn1neg), None

    (syn0, syn1neg), _ = jax.lax.scan(
        body, (syn0, syn1neg),
        (context, context_mask, targets, pair_mask, lrs))
    return syn0, syn1neg


class ScanDispatchQueue:
    """The K-flush dispatch protocol shared by Word2Vec and
    ParagraphVectors (PERF.md §5): enqueue flush batches; at `k` of them,
    hand the whole list to `dispatch_many` (one scanned program); any
    leftover short of `k` goes through `dispatch_one` per batch so only
    two program shapes ever compile."""

    def __init__(self, k: int, dispatch_many, dispatch_one):
        self.k = int(k)
        self._many = dispatch_many
        self._one = dispatch_one
        self._q = []

    def add(self, item) -> None:
        self._q.append(item)
        if len(self._q) == self.k:
            self._many(self._q)
            self._q.clear()

    def drain(self) -> None:
        """Dispatch whatever is queued (call once at end of training)."""
        if not self._q:
            return
        if len(self._q) == self.k:
            self._many(self._q)
        else:
            for item in self._q:
                self._one(item)
        self._q.clear()
