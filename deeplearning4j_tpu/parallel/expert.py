"""Expert parallelism: a mixture-of-experts FFN sharded over a mesh axis.

The reference has no MoE (it predates the architecture); this completes
the framework's parallelism matrix (dp/tp/sp/pp/ep — the driver's
multi-chip dryrun exercises all five). The design is the Mesh-TensorFlow /
GShard einsum formulation, TPU-first: routing builds a dense
[tokens, experts, capacity] dispatch tensor, the per-expert FFN runs as
batched einsums over a [E, C, D] tensor whose EXPERT axis is sharded over
the mesh — XLA's GSPMD inserts the all-to-alls that move each token to its
expert's device and back; nothing is hand-scheduled. Over-capacity tokens
are dropped (output zero) exactly as in GShard; capacity_factor sizes the
buffer.

Everything is jit-compatible (static shapes, no data-dependent control
flow) and differentiable — the router's combine weights carry the gradient
through the top-k selection.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.kernels import grouped_matmul


def init_moe_params(rng_key, d_model: int, d_hidden: int, n_experts: int,
                    dtype=jnp.float32):
    """Per-expert two-layer FFN + router. Returns a params dict with every
    expert table carrying a leading [E, ...] axis (shard it over the
    expert mesh axis with `shard_moe_params`)."""
    k1, k2, k3 = jax.random.split(rng_key, 3)
    s1 = (2.0 / d_model) ** 0.5
    s2 = (2.0 / d_hidden) ** 0.5
    return {
        "gate_w": jax.random.normal(k1, (d_model, n_experts), dtype) * s1,
        "w1": jax.random.normal(k2, (n_experts, d_model, d_hidden),
                                dtype) * s1,
        "b1": jnp.zeros((n_experts, d_hidden), dtype),
        "w2": jax.random.normal(k3, (n_experts, d_hidden, d_model),
                                dtype) * s2,
        "b2": jnp.zeros((n_experts, d_model), dtype),
    }


def shard_moe_params(params, mesh: Mesh, expert_axis: str = "expert"):
    """Place each per-expert table with its leading axis on `expert_axis`;
    the router replicates."""
    def put(name, a):
        if name == "gate_w":
            return jax.device_put(a, NamedSharding(mesh, P()))
        spec = P(expert_axis, *([None] * (a.ndim - 1)))
        return jax.device_put(a, NamedSharding(mesh, spec))
    return {k: put(k, v) for k, v in params.items()}


def _capacity_dispatch(onehot, C, acc, *, base_count=None):
    """[N, E] assignment one-hot -> [N, E, C] dispatch tensor.

    Position of each token within its expert's capacity buffer is its rank
    among same-expert tokens (first-come order); `base_count` [E] offsets the
    ranks (top-2 second choices queue behind every first choice, GShard
    semantics)."""
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot           # [N, E]
    if base_count is not None:
        pos = pos + base_count[None, :] * onehot
    pos_tok = jnp.sum(pos, axis=-1)                             # [N]
    keep = pos_tok < C
    # int cast for one_hot (it rejects float indices going forward);
    # over-capacity tokens are already zeroed by the keep mask.
    return (onehot * keep[:, None])[:, :, None] * jax.nn.one_hot(
        pos_tok.astype(jnp.int32), C, dtype=acc)[:, None, :]    # [N, E, C]


def moe_ffn(params, x, *, capacity_factor: float = 1.25,
            mesh: Optional[Mesh] = None, expert_axis: str = "expert",
            top_k: int = 1, rng=None, jitter_eps: float = 0.0,
            return_aux: bool = False):
    """Top-1 / top-2 routed MoE FFN. x: [N, D] tokens -> [N, D_out].

    GShard routing semantics (the module's design donor):
    - `top_k=2`: each token is dispatched to its two highest-probability
      experts; the two gate values are renormalized to sum to 1; second
      choices queue behind ALL first choices in each expert's capacity
      buffer, so under pressure first choices win buffer slots.
    - load-balance auxiliary loss `E * sum_e(fraction_tokens_e * mean_prob_e)`
      over FIRST-choice assignments (GShard eq. (4) / Switch Transformer
      eq. (4)); minimized at 1.0 for a perfectly uniform router. Returned
      when `return_aux=True` as `(y, aux_loss)`; callers scale it into
      their training loss.
    - router jitter: with `rng` and `jitter_eps > 0`, router inputs are
      multiplied by uniform noise in [1-eps, 1+eps] (training-time
      exploration; pass rng=None at eval).

    With `mesh`, the [E, C, D] expert batch is sharding-constrained to the
    expert axis so GSPMD all-to-alls tokens to their expert's device; the
    math is identical with or without a mesh (exact-equivalence tested)."""
    N, D = x.shape
    E = params["gate_w"].shape[1]
    C = max(1, int(capacity_factor * top_k * N / E))
    # Accumulate in at least fp32 (fp64 stays fp64 so x64 tests are exact).
    acc = jnp.promote_types(x.dtype, jnp.float32)

    x_router = x.astype(acc)
    if rng is not None and jitter_eps > 0.0:
        x_router = x_router * jax.random.uniform(
            rng, x.shape, acc, 1.0 - jitter_eps, 1.0 + jitter_eps)
    logits = x_router @ params["gate_w"].astype(acc)            # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(logits, axis=-1)            # [N] first choice
    gate1 = jnp.take_along_axis(probs, expert_idx[:, None], axis=1)[:, 0]
    onehot1 = jax.nn.one_hot(expert_idx, E, dtype=acc)          # [N, E]

    # Load-balance aux loss from FIRST-choice fractions (GShard eq. 4).
    frac_tokens = jnp.mean(onehot1, axis=0)                     # [E]
    mean_prob = jnp.mean(probs, axis=0)                         # [E]
    aux_loss = E * jnp.sum(frac_tokens * mean_prob)

    if top_k == 1:
        dispatch = _capacity_dispatch(onehot1, C, acc)
        combine = dispatch * gate1[:, None, None]
    elif top_k == 2:
        # Second choice = highest remaining LOGIT (not prob): a saturated
        # softmax zeroes the non-first-choice probs exactly, and an argmax
        # over those zeros would re-select the first-choice expert.
        logits2 = jnp.where(onehot1 > 0, -jnp.inf, logits)
        idx2 = jnp.argmax(logits2, axis=-1)
        gate2 = jnp.take_along_axis(probs, idx2[:, None], axis=1)[:, 0]
        onehot2 = jax.nn.one_hot(idx2, E, dtype=acc)
        denom = gate1 + gate2 + 1e-9
        g1, g2 = gate1 / denom, gate2 / denom
        d1 = _capacity_dispatch(onehot1, C, acc)
        count1 = jnp.sum(onehot1, axis=0)                       # [E]
        d2 = _capacity_dispatch(onehot2, C, acc, base_count=count1)
        dispatch = d1 + d2
        combine = d1 * g1[:, None, None] + d2 * g2[:, None, None]
    else:
        raise ValueError(f"top_k must be 1 or 2, got {top_k}")

    expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                           x.astype(acc))                       # [E, C, D]
    if mesh is not None:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(expert_axis, None, None)))
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", expert_in,
                               params["w1"].astype(acc))
                    + params["b1"][:, None, :])
    out_e = (jnp.einsum("ech,ehd->ecd", h,
                        params["w2"].astype(acc))
             + params["b2"][:, None, :])
    if mesh is not None:
        out_e = jax.lax.with_sharding_constraint(
            out_e, NamedSharding(mesh, P(expert_axis, None, None)))
    y = jnp.einsum("nec,ecd->nd", combine, out_e)
    y = y.astype(x.dtype)
    if return_aux:
        return y, aux_loss
    return y


def route_top_k(gate_w, x, top_k: int, norm_topk_prob: bool = True, *,
                scoring: str = "softmax", gate_b=None,
                routed_scaling_factor: float = 1.0):
    """The router of the dropless path: softmax over all experts' logits in
    >= float32, the `top_k` largest per token. x: [N, D], gate_w: [D, E] ->
    `(probs [N, E], gate [N, k], idx [N, k])`, `gate` renormalised to sum
    to 1 with `norm_topk_prob`.

    `scoring="sigmoid"` (DeepSeek-V3's `noaux_tc` router with one group):
    the scores are `s = sigmoid(logits)`, each expert's own; the choice is
    the `top_k` largest of `s + gate_b` (`gate_b` [E]: the selection bias,
    which enters the choice and nothing else, so no gradient reaches it);
    `gate` is the unbiased `s` at the chosen experts, divided by its sum
    (+ 1e-20) with `norm_topk_prob`, times `routed_scaling_factor`; and
    `probs` is `s / sum_e s`, what the balance term averages."""
    acc = jnp.promote_types(x.dtype, jnp.float32)
    logits = x.astype(acc) @ gate_w.astype(acc)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores if gate_b is None else scores + gate_b.astype(acc)
        _, idx = jax.lax.top_k(jax.lax.stop_gradient(choice), top_k)
        gate = jnp.take_along_axis(scores, idx, axis=-1)
        if norm_topk_prob:
            gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
        return (scores / jnp.sum(scores, axis=-1, keepdims=True),
                gate * routed_scaling_factor, idx)
    if scoring != "softmax":
        raise ValueError(f"scoring {scoring!r}: softmax or sigmoid")
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return probs, gate, idx


def sequence_balance(probs, idx, sequences: int):
    """The sequence-wise balance term (DeepSeek-V2/V3): `sum_i f_i P_i` with
    `f_i = E / (k S) * #{t: i chosen}` and `P_i = mean_t probs_i(t)` over
    each sequence's own S tokens, averaged over the sequences. probs:
    [N, E], idx: [N, k], N = sequences * S. 1 under an even router."""
    N, E = probs.shape
    S, k = N // sequences, idx.shape[1]
    counts = jnp.zeros((sequences, E), probs.dtype).at[
        jnp.arange(N)[:, None] // S, idx].add(1.0)
    mean_probs = jnp.mean(probs.reshape(sequences, S, E), axis=1)
    return jnp.mean(jnp.sum(counts * (E / (k * S)) * mean_probs, axis=1))


def _rows_of_pairs(src, token):
    """`src[token]`: each sorted pair's row of the `[N, D]` source, by the
    pair's token, with no `jnp.repeat(src, top_k)` in front. A chip's tokens
    fit its fast memory (16,384 rows of 2,304 in bfloat16 are 75 MB), and
    XLA's gather from there runs at copy speed: 1.0 ms for 131,072 rows,
    where the same rows gathered out of the repeated `[N * top_k, D]` array
    took 5.9 ms, from a float32 source of twice the bytes 5.8 ms, and chunk
    after chunk over a live prefix of 48% in a loop 2.7 ms (PERF.md PR 31).
    So the gather is whole: the rows of pairs held elsewhere come along,
    finite and never read."""
    return src[token]


def _permute_scalars(v, inverse):
    """`v[perm]` for a permutation `perm` given its `inverse`, as a sort of
    `v` keyed by `inverse`: 0.27 ms for 131,072 float32 scalars on a v5e
    where XLA's gather of them took 1.2 ms (PERF.md PR 31)."""
    return jax.lax.sort((inverse, v), num_keys=1)[1]


def _grouped_ffn(x, tables, token, group_sizes):
    """The held experts over the sorted pairs: the rows, the two inner
    products and the output, each `[N * top_k, ...]` in expert order. A
    grouped product (the registry's `grouped_matmul`) writes the rows of its
    groups, the live prefix, and leaves the rest of its result as the buffer
    was."""
    w_gate, w_up, w_down = tables
    rows = _rows_of_pairs(x, token)
    g = grouped_matmul.rows_table(rows, w_gate, group_sizes)
    u = grouped_matmul.rows_table(rows, w_up, group_sizes)
    out = grouped_matmul.rows_table(jax.nn.silu(g) * u, w_down, group_sizes)
    return rows, g, u, out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_experts(top_k, x, gate, tables, order, inverse, group_sizes):
    """`moe_ffn_dropless`'s expert block: x [N, D], gate [N, top_k] in the
    accumulator dtype, the three held tables in x's dtype, and the sort of
    the pairs (`order`, its `inverse`, the held experts' `group_sizes`) ->
    y [N, D_out] in x's dtype. Its backward pass is written by hand
    (`_held_experts_bwd`)."""
    return _held_experts_fwd(top_k, x, gate, tables, order, inverse,
                             group_sizes)[0]


def _held_experts_fwd(top_k, x, gate, tables, order, inverse, group_sizes):
    N = x.shape[0]
    n_held = jnp.sum(group_sizes)
    *_, out = _grouped_ffn(x, tables, order // top_k, group_sizes)
    # Back to (token, slot) order, whole: live pairs are no prefix there. A
    # pair held elsewhere reads a row nobody wrote and is masked here, in
    # the pass that sums a token's slots.
    held = (inverse < n_held).reshape(N, top_k, 1)
    out = out[inverse].reshape(N, top_k, -1).astype(gate.dtype)
    y = jnp.sum(jnp.where(held, out * gate[:, :, None], 0), axis=1)
    # no [N * top_k, ...] tensor among the residuals
    return y.astype(x.dtype), (x, gate, tables, order, inverse, group_sizes)


def _held_experts_bwd(top_k, res, dy):
    x, gate, tables, order, inverse, group_sizes = res
    N, acc = x.shape[0], gate.dtype
    n_held = jnp.sum(group_sizes)
    token = order // top_k
    held = (inverse < n_held).reshape(N, top_k)
    # dy in expert order first: with `out` beside it the gate's gradient
    # needs no second trip of `out` to token order.
    dy_rows = _rows_of_pairs(dy, token)
    # The recomputation starts from operands XLA cannot tell from the forward
    # pass's and that exist only once `dy_rows` does (what `jax.checkpoint`
    # does for its own): merged with the forward pass's, its [N * top_k, ...]
    # arrays live from one pass to the other, 3.3 GB more temporaries in the
    # step of `mellum2_12b_a2_5b.fit_seq16k` (PERF.md PR 31).
    x, tables, dy_rows = jax.lax.optimization_barrier((x, tables, dy_rows))
    # The tables as the backward products take them: XLA's candidate wants
    # transposed copies, made here where they always were; the kernel reads
    # a table as stored.
    t_gate, t_up, t_down = (
        grouped_matmul.transposed(w, dy_rows.shape[0], x.dtype)
        for w in tables)
    rows, g, u, out = _grouped_ffn(x, tables, token, group_sizes)
    hmid, silu_mul_vjp = jax.vjp(lambda g, u: jax.nn.silu(g) * u, g, u)
    dy_rows = dy_rows.astype(acc)
    dgate = jnp.sum(out.astype(acc) * dy_rows, axis=-1)
    dgate = jnp.where(
        held, _permute_scalars(dgate, order).reshape(N, top_k), 0)
    gate = _permute_scalars(gate.reshape(-1), inverse)
    dout = (dy_rows * gate[:, None]).astype(out.dtype)

    dhmid = grouped_matmul.rows_table_t(dout, t_down, group_sizes)
    dg, du = silu_mul_vjp(dhmid)
    drows = (grouped_matmul.rows_table_t(dg, t_gate, group_sizes)
             + grouped_matmul.rows_table_t(du, t_up, group_sizes))
    dtables = (grouped_matmul.contracted(rows, dg, group_sizes),
               grouped_matmul.contracted(rows, du, group_sizes),
               grouped_matmul.contracted(hmid, dout, group_sizes))
    drows = drows[inverse].reshape(N, top_k, -1)
    dx = jnp.sum(jnp.where(held[:, :, None], drows, 0), axis=1, dtype=acc)
    return dx.astype(x.dtype), dgate, dtables, None, None, None


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def moe_ffn_dropless(params, x, *, top_k: int, first=0,
                     norm_topk_prob: bool = True, scoring: str = "softmax",
                     routed_scaling_factor: float = 1.0, sequences: int = 1):
    """Dropless top-k routed experts, this holder's part of the sum.

    x: [N, D] tokens. `params["gate_w"]`: [D, E] router over ALL E experts;
    the expert tables carry a leading [Eh, ...] axis for the Eh experts held
    here, which are experts `first .. first + Eh - 1` (`first` may be a
    traced scalar: a device's index on an expert axis). The experts are
    gated, without biases: `w_down(silu(w_gate x) * w_up x)`.

    Routing: softmax over the E logits in >= float32, the `top_k` largest
    per token, their values renormalised to sum to 1 with
    `norm_topk_prob`. No capacity, no dropped token: the (token, expert)
    pairs are sorted by expert, pairs of experts held elsewhere last, so
    the pairs held here are the first `n_held` (a traced count) of the
    sorted axis: the live prefix. What is defined of the `[N * top_k, ...]`
    arrays in expert order, stage by stage:

    - the rows are gathered whole from `x` by token index
      (`_rows_of_pairs`), no repeat of `x` in front: every row is some
      token's, those past the prefix no held expert's;
    - each matrix is one grouped product (the registry's `grouped_matmul`:
      a Pallas kernel on the TPU, `jax.lax.ragged_dot` elsewhere), which
      skips the rows past the groups and leaves them unwritten:
      65,536 rows of which 8,192 are live cost what 10,240 rows do (PERF.md
      PR 26). Its result is defined on the live prefix only, and so is
      everything computed from it; with the groups contracted (the tables'
      gradients) it reads no row past them either, NaN there or not
      (shown on the chip, PERF.md PR 31);
    - nothing zeroes a dead row in expert order. A dead pair is masked where
      it is consumed in token order, by a `where` on its held bit inside the
      pass that exists anyway (never a multiply: a dead row may hold NaN):
      the weighted sum over a token's slots forward, the sum over slots that
      makes `dx` and the `N * top_k` scalars of the gate's gradient
      backward.

    The two gathers back to token order (the output forward, the rows'
    cotangent backward) read an `[N * top_k, D]` source, which fits no fast
    memory, and cost by the row. The backward pass is written by hand
    (`_held_experts_bwd`): it keeps `x`, the gate values, the tables and the
    sort's integer vectors, no `[N * top_k, ...]` tensor, recomputes the
    grouped part, and takes the gate's gradient in expert order from the
    recomputed output and the gathered cotangent.

    Returns `(y [N, D_out], aux, stats, idx)`: `aux = E * sum_e f_e * P_e`
    over all E experts with f_e the pairs routed to e per token and P_e the
    mean router probability (Qwen3-MoE's `load_balancing_loss_func`);
    under `scoring="sigmoid"` (`route_top_k`: the bias is `params["gate_b"]`
    where the layer has one) `sequence_balance` over the `sequences` the
    tokens are; `stats` = `(pairs_held_share, expert_load_max_over_mean)`
    over the held experts; `idx` [N, top_k] the experts each token was
    routed to."""
    N, D = x.shape
    E = params["gate_w"].shape[1]
    Eh = params["w_gate"].shape[0]
    acc = jnp.promote_types(x.dtype, jnp.float32)

    with jax.named_scope("moe.route"):
        probs, gate, idx = route_top_k(
            params["gate_w"], x, top_k, norm_topk_prob, scoring=scoring,
            gate_b=params.get("gate_b"),
            routed_scaling_factor=routed_scaling_factor)          # [N, k]
        if scoring == "sigmoid":
            aux = sequence_balance(probs, idx, sequences)
        else:
            counts = jnp.zeros((E,), acc).at[idx.reshape(-1)].add(1.0)
            aux = E * jnp.sum(counts / N * jnp.mean(probs, axis=0))
        # Sort the pairs by local expert, those held elsewhere (Eh) last.
        local = idx.reshape(-1) - first
        held = (local >= 0) & (local < Eh)
        local = jnp.where(held, local, Eh).astype(jnp.int32)
        order = jnp.argsort(local, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.zeros((Eh + 1,), jnp.int32).at[local].add(1)[:Eh]
        n_held = jnp.sum(group_sizes)
        loads = group_sizes.astype(acc)
        stats = (n_held.astype(acc) / (N * top_k),
                 jnp.max(loads) / jnp.maximum(jnp.mean(loads), 1e-9))

    with jax.named_scope("moe.experts"):
        y = _held_experts(top_k, x, gate.astype(acc), tuple(
            params[n].astype(x.dtype) for n in ("w_gate", "w_up", "w_down")),
            order, inverse, group_sizes)
    return y, aux, stats, idx


def moe_ffn_dropless_sharded(params, x, mesh: Mesh, expert_axis: str, *,
                             top_k: int, first=0,
                             norm_topk_prob: bool = True, **routing):
    """`moe_ffn_dropless` with the held expert tables split over
    `expert_axis`: every device routes every token over all experts, takes
    its own experts by its index on the axis, and the parts are summed
    across it. Same returns (the load statistic is the worst device's)."""
    from jax import shard_map

    n_dev = int(mesh.shape[expert_axis])
    count = params["w_gate"].shape[0]
    if count % n_dev:
        raise ValueError(f"{count} held experts do not split over an "
                         f"expert axis of {n_dev}")

    def part(tables, tokens):
        me = jax.lax.axis_index(expert_axis)
        y, aux, (share, load), idx = moe_ffn_dropless(
            tables, tokens, top_k=top_k,
            first=first + me * (count // n_dev),
            norm_topk_prob=norm_topk_prob, **routing)
        return (jax.lax.psum(y, expert_axis), aux,
                (jax.lax.psum(share, expert_axis),
                 jax.lax.pmax(load, expert_axis)), idx)

    specs = {k: (P() if k in ("gate_w", "gate_b") else P(expert_axis))
             for k in params}
    return shard_map(part, mesh=mesh, in_specs=(specs, P()),
                     out_specs=(P(), P(), (P(), P()), P()),
                     check_vma=False)(params, x)


def dense_moe_reference(params, x, *, capacity_factor: float = 1.25,
                        top_k: int = 1):
    """Per-token reference: run every token through ITS expert(s)' FFN
    directly (same capacity/queueing rules as `moe_ffn`), for equivalence
    tests. Second choices queue behind every first choice (GShard)."""
    import numpy as np

    x64 = np.asarray(x, np.float64)
    gate_w = np.asarray(params["gate_w"], np.float64)
    N, D = x64.shape
    E = gate_w.shape[1]
    C = max(1, int(capacity_factor * top_k * N / E))
    logits = x64 @ gate_w
    e = np.exp(logits - logits.max(axis=1, keepdims=True))
    probs = e / e.sum(axis=1, keepdims=True)
    idx = logits.argmax(axis=1)
    d_out = np.asarray(params["w2"]).shape[-1]
    out = np.zeros((N, d_out), np.float64)

    def expert_out(j, v):
        h = np.maximum(v @ np.asarray(params["w1"][j], np.float64)
                       + np.asarray(params["b1"][j], np.float64), 0.0)
        return h @ np.asarray(params["w2"][j], np.float64) + np.asarray(
            params["b2"][j], np.float64)

    counts = {j: 0 for j in range(E)}
    if top_k == 1:
        for n in range(N):
            j = int(idx[n])
            if counts[j] >= C:
                continue  # dropped
            counts[j] += 1
            out[n] = expert_out(j, x64[n]) * probs[n, j]
        return out
    # top-2: first choices claim buffer slots for ALL tokens first; second
    # choice is the highest remaining LOGIT (matches moe_ffn's tie-robust
    # selection under saturated softmax).
    logits2 = logits.copy()
    logits2[np.arange(N), idx] = -np.inf
    idx2 = logits2.argmax(axis=1)
    g1 = probs[np.arange(N), idx]
    g2 = probs[np.arange(N), idx2]
    denom = g1 + g2 + 1e-9
    for n in range(N):
        j = int(idx[n])
        if counts[j] < C:
            counts[j] += 1
            out[n] += expert_out(j, x64[n]) * (g1[n] / denom[n])
    for n in range(N):
        j = int(idx2[n])
        if counts[j] < C:
            counts[j] += 1
            out[n] += expert_out(j, x64[n]) * (g2[n] / denom[n])
    return out
