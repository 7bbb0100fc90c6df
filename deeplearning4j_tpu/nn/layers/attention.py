"""Multi-head self-attention as a first-class DSL layer.

The reference framework predates attention (its long-sequence story is
tBPTT, `MultiLayerNetwork.java:1207`); SURVEY.md §5 names attention with
ring/Ulysses sequence parallelism as the TPU-native extension. Round 4
shipped the kernels as standalone functions (`parallel/sequence.py`,
`ops/flash_attention.py`); this module makes them reachable from the
framework's own config DSL: `SelfAttentionLayer` in a
`NeuralNetConfiguration` builds a model whose jitted train step computes
attention through

- the Pallas flash kernel (single device, no mask — `impl="auto"`),
- XLA dense attention with key masking (when a features mask is present),
- ring attention over the active mesh's sequence axis, selected at trace
  time from the installed `parallel.context.ParallelContext` — the same
  DSL model trains sequence-sharded under `ParallelWrapper(...,
  seq_axis=...)` with zero config changes.

The layer is an ordinary engine citizen: gradient-checked
(`tests/test_gradientcheck.py`), serialized to JSON/YAML, updater/L2
semantics identical to every other layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations
from deeplearning4j_tpu.nn.layers.common import layer_input_dropout
from deeplearning4j_tpu.parallel.context import current_context

_NEG = -1e30


def _masked_dense_attention(q, k, v, mask, causal, scale):
    """Dense attention with key-position masking. q/k/v: [B, T, H, D];
    mask: [B, T] (1 = real, 0 = padded). Masked KEYS are excluded from
    every softmax; masked QUERY rows produce zeros (their downstream loss
    contribution is masked anyway, and zeros keep them finite)."""
    acc = jnp.promote_types(q.dtype, jnp.float32)
    qt, kt, vt = (jnp.swapaxes(a, 1, 2).astype(acc) for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    s = jnp.where(mask[:, None, None, :] > 0, s, _NEG)
    if causal:
        T = s.shape[-1]
        s = jnp.where(jnp.triu(jnp.ones((T, T), bool), 1)[None, None], _NEG, s)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(m <= _NEG / 2, 0.0, p)  # fully-masked rows -> all-zero p
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhqk,bhkd->bhqd", p / denom, vt)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def _cached_decode_attention(q, kc, vc, pos, causal):
    """Decode-step attention against a fixed-size KV cache. q: [B, T, H, D]
    (the NEW positions, globally at [pos, pos+T)); kc/vc: [B, L, H, D] with
    valid keys in [0, pos+T). Causal: query i sees keys <= pos+i.

    `pos` is either a scalar cursor (every row at the same position — the
    single-sequence decode path) or a [B] vector of per-row cursors (the
    continuous-batching scheduler, where each slot is at its own depth)."""
    B, T, H, D = q.shape
    L = kc.shape[1]
    acc = jnp.promote_types(q.dtype, jnp.float32)
    qt = jnp.swapaxes(q, 1, 2).astype(acc) * (D ** -0.5)
    kt = jnp.swapaxes(kc, 1, 2).astype(acc)
    vt = jnp.swapaxes(vc, 1, 2).astype(acc)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt)
    kpos = jnp.arange(L)
    pos_b = jnp.reshape(pos, (-1, 1))            # [1,1] scalar / [B,1] vector
    if causal:
        limit = pos_b + 1 + jnp.arange(T)[None, :]  # query i sees < pos+i+1
    else:
        limit = jnp.broadcast_to(pos_b + T, (pos_b.shape[0], T))
    s = jnp.where(kpos[None, None, None, :] < limit[:, None, :, None],
                  s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def self_attention_apply(conf, params, state, x, *, rng=None, train=False,
                         mask=None):
    """x: [B, T, n_in] -> [B, T, n_out] multi-head self-attention.

    Path selection (trace-time, static):
    1. KV cache present in `state` (stateful decode via rnn_time_step,
       `conf.decode_cache_length`) -> fixed-size cached attention;
    2. active ParallelContext with a >1 sequence axis -> ring attention
       (sequence-sharded exact attention; requires causal or no mask);
    3. features mask present -> XLA dense with key masking;
    4. otherwise -> `parallel.sequence.attention` (Pallas flash kernel for
       `impl="auto"`, dense oracle for `impl="dense"`).

    With `decode_cache_length` set the layer ALWAYS returns its cache
    entries (k_cache/v_cache/kv_pos) as undeclared state: the engines
    persist them only on the stateful-inference path and XLA eliminates
    the dead outputs everywhere else.
    """
    from deeplearning4j_tpu.parallel import sequence as seq_mod

    x = layer_input_dropout(conf, x, rng, train)
    if conf.is_extended():
        # Grouped-query heads, rotary positions, QK-norm, learned sparse
        # selection: `nn/layers/dsa.py` (no cache, mask or mesh path yet).
        from deeplearning4j_tpu.nn.layers import dsa

        return dsa.extended_attention_apply(conf, params, state, x, mask)
    B, T, _ = x.shape
    H = conf.n_heads
    if conf.n_out % H:
        raise ValueError(
            f"SelfAttentionLayer n_out ({conf.n_out}) must be divisible by "
            f"n_heads ({H})")
    Dh = conf.n_out // H

    def proj(w, b=None):
        h = x @ params[w]
        if b is not None:
            h = h + params[b]
        return h.reshape(B, T, H, Dh)

    q = proj("Wq", "qB")
    k = proj("Wk")  # key bias is a softmax no-op (see conf.param_shapes)
    v = proj("Wv", "vB")
    scale = Dh ** -0.5

    L = conf.decode_cache_length
    if L and "k_pages" in state:
        # Paged decode step: KV lives in a pool of fixed-size pages shared
        # by all slots (`models/kv_pool.py` owns the refcounts/CoW); this
        # branch scatters the new k/v rows through the per-slot page table
        # and reads through the `flash_attention_paged` kernel seam. The
        # pool guarantees every page in a slot's write range has refcount 1
        # (CoW before dispatch), so rows never collide; free slots' table
        # rows are all-zero, landing their writes on the reserved zero
        # page. Garbage rows (pad tails, zero page) sit at masked key
        # positions whose softmax weight underflows to exactly 0.0, which
        # keeps this path bit-identical to the dense cache under the XLA
        # dense-gather fallback.
        from deeplearning4j_tpu.kernels import flash_attention as _fa

        pos = state["kv_pos"]                       # [B] int32 cursors
        pt = state["page_table"]                    # [B, NP] int32
        kp, vp = state["k_pages"], state["v_pages"]
        page = kp.shape[1]
        gpos = pos[:, None] + jnp.arange(T)[None, :]           # [B, T]
        # Free slots' cursors grow unbounded; clip keeps the gather legal
        # and their writes stay on the zero page regardless.
        phys = jnp.take_along_axis(pt, jnp.clip(gpos // page, 0,
                                                pt.shape[1] - 1), axis=1)
        off = gpos % page
        kp = kp.at[phys.reshape(-1), off.reshape(-1)].set(
            k.reshape(B * T, H, Dh))
        vp = vp.at[phys.reshape(-1), off.reshape(-1)].set(
            v.reshape(B * T, H, Dh))
        ctx = current_context()
        if (ctx is not None and ctx.model_axis is not None
                and ctx.axis_size("model") > 1
                and H % ctx.axis_size("model") == 0):
            # Tensor-parallel decode (PERF.md §28): pin the page storage to
            # its head partitioning THROUGH the scatter, so XLA never
            # round-trips pages to a replicated layout between steps — q/k/v
            # arrive head-sharded from the column-parallel projections, the
            # scatter and the paged read stay shard-local, and the step's
            # only collective is Wo's row-parallel all-reduce.
            from deeplearning4j_tpu.parallel import mesh as _mesh_mod

            _pin = _mesh_mod.kv_page_sharding(ctx.mesh, 4, ctx.model_axis)
            kp = jax.lax.with_sharding_constraint(kp, _pin)
            vp = jax.lax.with_sharding_constraint(vp, _pin)
        o = _fa.paged_decode_attention(q, kp, vp, pt, pos, conf.causal)
        out = o.reshape(B, T, conf.n_out) @ params["Wo"] + params["oB"]
        out = activations.resolve(conf.activation)(out)
        return out, {"k_pages": kp, "v_pages": vp, "page_table": pt,
                     "kv_pos": pos + jnp.int32(T)}, mask

    if L and "kv_pos" in state:
        # Stateful decode step: fold the new k/v into the cache at the
        # cursor, attend against the valid prefix.
        pos = state["kv_pos"]
        zero = jnp.zeros((), jnp.int32)
        if jnp.ndim(pos):
            # Per-slot cursors ([B] int32): each row lands at its own depth.
            upd = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(
                c, u, (p, zero, zero)))
            kc = upd(state["k_cache"], k, pos)
            vc = upd(state["v_cache"], v, pos)
        else:
            kc = jax.lax.dynamic_update_slice(state["k_cache"], k,
                                              (zero, pos, zero, zero))
            vc = jax.lax.dynamic_update_slice(state["v_cache"], v,
                                              (zero, pos, zero, zero))
        o = _cached_decode_attention(q, kc, vc, pos, conf.causal)
        out = o.reshape(B, T, conf.n_out) @ params["Wo"] + params["oB"]
        out = activations.resolve(conf.activation)(out)
        return out, {"k_cache": kc, "v_cache": vc,
                     "kv_pos": pos + jnp.int32(T)}, mask

    ctx = current_context()
    if ctx is not None and ctx.seq_axis is not None and ctx.axis_size("seq") > 1:
        if mask is not None and not conf.causal:
            raise ValueError(
                "sequence-sharded non-causal attention with a features mask "
                "is not supported; pad to full length or drop the seq axis")
        # impl="ulysses" opts into the all-to-all variant (cheaper
        # collectives at moderate T; needs n_heads % axis == 0); anything
        # else sequence-sharded takes the ring.
        sp = (seq_mod.ulysses_attention
              if conf.attention_impl == "ulysses" else seq_mod.ring_attention)
        o = sp(q, k, v, ctx.mesh, seq_axis=ctx.seq_axis,
               batch_axis=ctx.data_axis, causal=conf.causal, scale=scale)
    elif mask is not None:
        o = _masked_dense_attention(q, k, v, mask, conf.causal, scale)
    else:
        o = seq_mod.attention(q, k, v, causal=conf.causal, scale=scale,
                              impl=conf.attention_impl)
    out = o.reshape(B, T, conf.n_out) @ params["Wo"] + params["oB"]
    out = activations.resolve(conf.activation)(out)
    new_state = state
    if L and T <= L:
        # Prime the decode cache (undeclared state: persists only via
        # rnn_time_step; dead code elsewhere). T > L skips priming — the
        # plain forward must keep working on sequences longer than the
        # cache; the engines' rnn_time_step guards capacity host-side.
        pad = [(0, 0), (0, L - T), (0, 0), (0, 0)]
        new_state = {
            "k_cache": jnp.pad(k, pad), "v_cache": jnp.pad(v, pad),
            "kv_pos": jnp.int32(T),
        }
    return out, new_state, mask
