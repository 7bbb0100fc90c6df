"""Pallas flash-attention kernels (TPU): five registry names.

`flash_attention` (causal or full, below), `flash_attention_paged` (decode
against the paged KV pool, further down), `masked_attention`
(grouped-query attention under a per-query `[S, S]` key mask, forward and
backward: the sparse-attention layer's `dsa.attend`, `nn/layers/dsa.py`)
and `banded_attention` (the same three kernels with no mask operand: what an
extended attention layer without an indexer runs, `attn.full` and
`attn.sliding`; PR 30), and `latent_attention` (those kernels again with
one more static case: the score tile is the sum of two products of different
widths, the second against a rotary key that all heads share; multi-head
latent attention's `mla.attend`). The masked family shares the streaming
kernels' prefetched lower-triangle sequence (`_pair_arrays`, which also knows a
sliding window: only the tiles that meet the band `t - window < s <= t`)
and differs in three ways its own section explains: the mask is an operand
or is built inside the tile from iotas, the query heads of one KV head
share every tile, and bf16 operands reach the MXU as bf16. Their XLA
fallbacks, the row blocks of `dsa.masked_gqa_attention_xla` and
`dsa.banded_gqa_attention_xla`, are what `auto` runs off the TPU and what
the parity tests hold the kernels to.

The reference predates attention entirely; this backs the framework's
long-context extension (`parallel/sequence.py`). Online-softmax
accumulation in fp32 — no [T, T] score matrix ever exists — with a hybrid
of two layouts chosen by K/V footprint: a K/V-resident kernel (K/V
fetched once per batch-head, reused across q-block programs, causal loop
stops at the diagonal) while they fit VMEM, and a streaming kernel
(k-blocks as the innermost grid dim, VMEM scratch accumulators, O(block)
memory at any T) beyond it.

Its speed against XLA dense attention is not measured on the current
machine (PERF.md §6 holds an earlier chip set-up's figures). Reached via
`parallel.sequence.attention(..., impl="auto")`, the framework's default
attention entry.

The streaming layout enumerates its (q-block, k-block) pairs through a
SCALAR-PREFETCHED index sequence (`_pair_arrays`): for causal attention
the sequence is exactly the lower triangle, so above-diagonal k-blocks
are never DMA'd at all — at long causal T this halves the streamed
bandwidth relative to a rectangular grid with compute-only gating (the
round-4 "known headroom", closed in round 5).

Differentiation: `flash_attention` carries a custom_vjp with a Pallas
backward in BOTH regimes — the standard two-kernel flash formulation
(dq over q-blocks; dk/dv over k-blocks) recomputing p from the saved lse
per block, O(T·D) memory. While K/V fit VMEM the backward kernels keep
them resident (fetched once per batch-head); beyond that they stream k/v
(dq) and q/do (dkv) blocks through the same triangular prefetch sequences,
so TRAINING at any block-multiple T never materializes a [T, T] matrix. A
T that is not a block multiple is the registry's decision, not the
kernel's: `is_available` answers no and `auto` resolves the XLA dense
path, forward and VJP. For sequence-sharded long-T training use
ring attention (`parallel/sequence.py`); this kernel is the single-device
path.

On non-TPU backends the kernel runs in Pallas interpret mode (numerics
identical, speed irrelevant) so the CPU test mesh exercises the same code.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deeplearning4j_tpu.kernels import registry as _registry

_NEG = -1e30


def _resident_softmax_loop(q_ref, k_ref, v_ref, *, block_k: int,
                           causal: bool, scale: float):
    """The resident online-softmax accumulation shared by the plain and
    lse-emitting forward kernels: returns (acc [BQ, D], m [BQ, 1],
    l [BQ, 1]) with l clamped positive."""
    BQ, D = q_ref.shape[1], q_ref.shape[2]
    T = k_ref.shape[1]
    i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    q_off = i * BQ

    nk = T // block_k
    if causal:
        nk = jnp.minimum(nk, (q_off + BQ - 1) // block_k + 1)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = q_off + jax.lax.broadcasted_iota(
                jnp.int32, (BQ, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (BQ, block_k), 1)
            s = jnp.where(kpos > qpos, _NEG, s)
        blk_max = jnp.max(s, axis=1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        p = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, new_m, l

    acc = jnp.zeros((BQ, D), jnp.float32)
    m = jnp.full((BQ, 1), _NEG, jnp.float32)
    l = jnp.zeros((BQ, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk, body, (acc, m, l))
    return acc, m, jnp.maximum(l, 1e-30)


def _flash_kernel_resident(q_ref, k_ref, v_ref, o_ref, *, block_k: int,
                           causal: bool, scale: float):
    """Fast path while K/V fit in VMEM: one program per (bh, q-block),
    K/V BlockSpec'd whole — their index map doesn't change across the
    q-block grid steps of one bh, so Pallas fetches them ONCE per
    batch-head and every q-block reuses the resident copy (measured ~1.5x
    the streaming kernel at T<=16k). The fori_loop bound stops at the
    causal diagonal, skipping both compute and reads of future blocks."""
    acc, m, l = _resident_softmax_loop(q_ref, k_ref, v_ref, block_k=block_k,
                                       causal=causal, scale=scale)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.lru_cache(maxsize=64)
def _pair_arrays(nq: int, nk: int, block_q: int, block_k: int, causal: bool,
                 order: str, window: Optional[int] = None):
    """The streamed (q-block i, k-block j) visit sequence, scalar-prefetched
    into the kernels. Causal sequences cover ONLY the lower triangle —
    above-diagonal blocks are never DMA'd; with `window` (row t reads keys
    t - window < s <= t) only the tiles that meet that band.
    `order="row"` (i-major: forward, dq — scratch accumulates along j) or
    `"col"` (j-major: dk/dv — scratch accumulates along i)."""
    import numpy as np

    if window is not None and not causal:
        raise ValueError("a window of earlier keys needs causal=True")
    pairs = []
    if order == "row":
        for i in range(nq):
            jm = min(nk - 1, ((i + 1) * block_q - 1) // block_k) \
                if causal else nk - 1
            j0 = 0 if window is None else \
                max(0, i * block_q - window + 1) // block_k
            pairs += [(i, j) for j in range(j0, jm + 1)]
    else:
        for j in range(nk):
            i0 = (j * block_k) // block_q if causal else 0
            im = nq - 1 if window is None else \
                min(nq - 1, ((j + 1) * block_k + window - 2) // block_q)
            pairs += [(i, j) for i in range(i0, im + 1)]
    i_idx = np.asarray([p[0] for p in pairs], np.int32)
    j_idx = np.asarray([p[1] for p in pairs], np.int32)
    return i_idx, j_idx


def _flash_stream_kernel(i_ref, j_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                         acc_ref, m_ref, l_ref, *, block_q: int,
                         block_k: int, nk: int, causal: bool, scale: float):
    """One streamed step: fold k/v block j into q block i's accumulator.

    TPU grids run sequentially, so the VMEM scratch (acc/m/l) persists
    across the j steps of one (bh, i) pair (the prefetched sequence is
    i-major) and Pallas double-buffers the next block's DMA against this
    block's compute. Emits lse = m + log(l) for the backward."""
    BQ, D = q_ref.shape[1], q_ref.shape[2]
    BK = k_ref.shape[1]
    t = pl.program_id(1)
    i, j = i_ref[t], j_ref[t]

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_off, k_off = i * BQ, j * BK
    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
        kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
        s = jnp.where(kpos > qpos, _NEG, s)
    m = m_ref[:]
    blk_max = jnp.max(s, axis=1, keepdims=True)
    new_m = jnp.maximum(m, blk_max)
    p = jnp.exp(s - new_m)
    corr = jnp.exp(m - new_m)
    l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = new_m

    if causal:
        jmax = jnp.minimum(((i + 1) * block_q - 1) // block_k, nk - 1)
    else:
        jmax = nk - 1

    @pl.when(j == jmax)
    def _():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l)


# Above this footprint of the operands a resident kernel keeps whole, the
# kernel would oversubscribe VMEM (16 MiB scoped by default on a v5e,
# shared with the q/out blocks and double buffering). Set from what the
# chip's compiler accepts: every resident forward and backward tried at or
# under it compiles (D 64/128/256, f32 and bf16); the forward is refused
# at 10 MiB (bf16 D=64 T=20480) and the backward at 12 MiB (T=8192).
_RESIDENT_KV_LIMIT = 8 * 1024 * 1024


def _resident(T: int, D: int, itemsize: int, backward: bool = False) -> bool:
    """Whether the resident kernels fit VMEM at this shape. Counts the
    operands kept whole as the chip lays them out, minor dim padded to 128
    lanes: K and V (forward, dq), or q and do plus the `[T, 1]` f32 lse and
    d_row columns (dk/dv) — a 1-wide column costs a full lane tile per 8
    rows, which at long T outweighs q and do themselves."""
    lanes = -(-D // 128) * 128
    held = 2 * T * lanes * itemsize
    if backward:
        held += 2 * T * 128 * 4
    return held <= _RESIDENT_KV_LIMIT


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def _flash_fwd_stream_bhtd(q, k, v, causal, scale, block_q, block_k,
                           interpret):
    """Streaming forward via the prefetched block sequence: (o, lse)."""
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    nq, nk = T // block_q, T // block_k
    i_idx, j_idx = _pair_arrays(nq, nk, block_q, block_k, causal, "row")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, len(i_idx)),
        in_specs=[
            pl.BlockSpec((1, block_q, D),
                         lambda b, t, ii, jj: (b, ii[t], 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, t, ii, jj: (b, jj[t], 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, t, ii, jj: (b, jj[t], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, t, ii, jj: (b, ii[t], 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, t, ii, jj: (b, ii[t], 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_flash_stream_kernel, block_q=block_q,
                          block_k=block_k, nk=nk, causal=causal, scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((BH, T, 1), jnp.float32)],
        interpret=interpret,
        name="flash_fwd_stream",
    )(jnp.asarray(i_idx), jnp.asarray(j_idx), q, k, v)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def _flash_fwd_bhtd(q, k, v, causal, scale, block_q, block_k, interpret):
    """q/k/v: [BH, T, D] -> [BH, T, D]."""
    BH, T, D = q.shape
    if _resident(T, D, q.dtype.itemsize):
        return pl.pallas_call(
            functools.partial(_flash_kernel_resident, block_k=block_k,
                              causal=causal, scale=scale),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            grid=(BH, T // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            interpret=interpret,
            name="flash_fwd_resident",
        )(q, k, v)
    o, _ = _flash_fwd_stream_bhtd(q, k, v, causal, scale, block_q, block_k,
                                  interpret)
    return o


def _dense_ref(q, k, v, causal, scale):
    """XLA dense attention on [B, T, H, D] — the single shared dense
    implementation (`parallel/sequence.py`), also the VJP donor."""
    from deeplearning4j_tpu.parallel.sequence import dense_attention

    return dense_attention(q, k, v, causal=causal, scale=scale)


def _require_block_multiple(T, block_q, block_k):
    if T % block_q or T % block_k:
        raise ValueError(
            f"flash kernel needs T % block == 0; got T={T}, blocks "
            f"({block_q}, {block_k})")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_pallas(q, k, v, causal: bool = True,
                            scale: Optional[float] = None,
                            block_q: int = 256, block_k: int = 256,
                            interpret: bool = False):
    """Flash multi-head attention. q/k/v: [B, T, H, Dh] -> [B, T, H, Dh].
    T must be a multiple of both blocks (`flash_attention` asks the
    registry, which resolves the XLA dense path otherwise)."""
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    B, T, H, D = q.shape
    _require_block_multiple(T, block_q, block_k)
    to_bhtd = lambda a: jnp.swapaxes(a, 1, 2).reshape(B * H, T, D)
    o = _flash_fwd_bhtd(to_bhtd(q), to_bhtd(k), to_bhtd(v), causal, scale,
                        block_q, block_k, interpret)
    return jnp.swapaxes(o.reshape(B, H, T, D), 1, 2)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 256, block_k: int = 256):
    """Registry-dispatched entry (kernel name ``flash_attention``): the
    Pallas kernel above (interpret off-TPU, its historical behavior under
    ``auto``) or the XLA dense reference under ``DL4J_TPU_KERNELS=xla`` /
    a per-kernel override. Same [B, T, H, Dh] contract either way."""
    res = _registry.resolve("flash_attention",
                            shapes=tuple(int(d) for d in q.shape),
                            dtypes=(str(q.dtype),),
                            meta=(("block_q", int(block_q)),
                                  ("block_k", int(block_k))))
    if res.impl != "pallas":
        s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
        return _dense_ref(q, k, v, causal, s)
    return _flash_attention_pallas(q, k, v, causal, scale, block_q, block_k,
                                   _registry.interpret_mode())


def _fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    scale_v = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    B, T, H, D = q.shape
    _require_block_multiple(T, block_q, block_k)
    to_bhtd = lambda a: jnp.swapaxes(a, 1, 2).reshape(B * H, T, D)
    if _resident(T, D, q.dtype.itemsize):
        o, lse = _flash_fwd_lse_bhtd(to_bhtd(q), to_bhtd(k), to_bhtd(v),
                                     causal, scale_v, block_q, block_k,
                                     interpret)
    else:
        o, lse = _flash_fwd_stream_bhtd(
            to_bhtd(q), to_bhtd(k), to_bhtd(v), causal, scale_v,
            block_q, block_k, interpret)
    return (jnp.swapaxes(o.reshape(B, H, T, D), 1, 2), (q, k, v, o, lse))


def _bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, o_bhtd, lse = res
    scale_v = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    B, T, H, D = q.shape
    to_bhtd = lambda a: jnp.swapaxes(a, 1, 2).reshape(B * H, T, D)
    # The backward decides for itself: either forward leaves the same
    # (o, lse), and the resident dk/dv kernel outgrows VMEM well before
    # the resident forward does.
    if _resident(T, D, q.dtype.itemsize, backward=True):
        dq, dk, dv = _flash_bwd_bhtd(
            to_bhtd(q), to_bhtd(k), to_bhtd(v), to_bhtd(g), o_bhtd, lse,
            causal, scale_v, block_q, block_k, interpret)
    else:
        dq, dk, dv = _flash_bwd_stream_bhtd(
            to_bhtd(q), to_bhtd(k), to_bhtd(v), to_bhtd(g), o_bhtd, lse,
            causal, scale_v, block_q, block_k, interpret)
    back = lambda a: jnp.swapaxes(a.reshape(B, H, T, D), 1, 2)
    return (back(dq).astype(q.dtype), back(dk).astype(k.dtype),
            back(dv).astype(v.dtype))


_flash_attention_pallas.defvjp(_fwd, _bwd)


def _pallas_available(backend, shapes, dtypes, meta=(), forced=False):
    if shapes:
        m = dict(meta)
        T, bq, bk = shapes[1], m.get("block_q", 256), m.get("block_k", 256)
        if T % bq or T % bk:
            return False, (f"T={T} is not a multiple of the ({bq}, {bk}) "
                           "blocks the kernel tiles by")
    if backend == "tpu":
        return True, "TPU flash kernel (resident/streaming hybrid, PERF.md §6)"
    return True, ("interpret mode off-TPU (numerics identical, speed "
                  "irrelevant — the CPU test mesh's path)")


def _xla_available(backend, shapes, dtypes, meta=(), forced=False):
    return True, "XLA dense attention (parallel.sequence.dense_attention)"


_registry.register("flash_attention", [
    _registry.KernelImpl("pallas", _pallas_available),
    _registry.KernelImpl("xla", _xla_available),
])


# ----------------------------------------------------------------- backward
#
# Flash backward (resident regime): recompute p from (q, k, lse) per block
# instead of keeping the [T, T] probability matrix — the standard
# two-kernel formulation (dq over q-blocks; dk/dv over k-blocks), O(T·D)
# memory. The forward saves lse = m + log(l) per row. Outside the resident
# regime (or non-multiple T) the custom_vjp falls back to the XLA dense
# VJP exactly as before.


def _flash_fwd_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                          block_k: int, causal: bool, scale: float):
    """Resident forward that also emits lse = m + log(l) (the backward's
    softmax normalizer), sharing `_resident_softmax_loop`."""
    acc, m, l = _resident_softmax_loop(q_ref, k_ref, v_ref, block_k=block_k,
                                       causal=causal, scale=scale)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)          # [BQ, 1]


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                         dq_ref, *, block_k: int, causal: bool,
                         scale: float):
    """dq for one (bh, q-block): loop k/v blocks, recompute p from lse."""
    BQ, D = q_ref.shape[1], q_ref.shape[2]
    T = k_ref.shape[1]
    i = pl.program_id(1)
    q_off = i * BQ
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]                 # [BQ]
    d_row = d_ref[0, :, 0]                 # [BQ] = rowsum(do * o)

    nk = T // block_k
    if causal:
        nk = jnp.minimum(nk, (q_off + BQ - 1) // block_k + 1)

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_off + jax.lax.broadcasted_iota(
                jnp.int32, (BQ, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (BQ, block_k), 1)
            s = jnp.where(kpos > qpos, _NEG, s)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - d_row[:, None])
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nk, body, jnp.zeros((BQ, D), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, d_ref,
                          dk_ref, dv_ref, *, block_q: int, causal: bool,
                          scale: float):
    """dk/dv for one (bh, k-block): loop q blocks (from the diagonal when
    causal), recompute p from lse."""
    BK, D = k_ref.shape[1], k_ref.shape[2]
    T = q_ref.shape[1]
    j = pl.program_id(1)
    k_off = j * BK
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)

    nq = T // block_q
    i0 = (k_off // block_q) if causal else 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), 0]
        d_row = d_ref[0, pl.ds(i * block_q, block_q), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, BK), 0)
            kpos = k_off + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, BK), 1)
            s = jnp.where(kpos > qpos, _NEG, s)
        p = jnp.exp(s - lse[:, None])                    # [BQ, BK]
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - d_row[:, None])
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    dk = jnp.zeros((BK, D), jnp.float32)
    dv = jnp.zeros((BK, D), jnp.float32)
    dk, dv = jax.lax.fori_loop(i0, nq, body, (dk, dv))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def _flash_fwd_lse_bhtd(q, k, v, causal, scale, block_q, block_k,
                        interpret):
    """Resident forward emitting (o, lse). [BH, T, D] ->
    ([BH, T, D], [BH, T, 1] fp32)."""
    BH, T, D = q.shape
    return pl.pallas_call(
        functools.partial(_flash_fwd_lse_kernel, block_k=block_k,
                          causal=causal, scale=scale),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((BH, T, 1), jnp.float32)],
        grid=(BH, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))],
        interpret=interpret,
        name="flash_fwd_lse",
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def _flash_bwd_bhtd(q, k, v, do, o, lse, causal, scale, block_q, block_k,
                    interpret):
    """Resident backward: (dq, dk, dv) each [BH, T, D]."""
    BH, T, D = q.shape
    d_row = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, T, 1]
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                          causal=causal, scale=scale),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        grid=(BH, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, d_row)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          causal=causal, scale=scale),
        out_shape=[jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        grid=(BH, T // block_k),
        in_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, T, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, T, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, T, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, T, 1), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
                   pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0))],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(k, v, q, do, lse, d_row)
    return dq, dk, dv


# ------------------------------------------------- streaming backward
#
# Beyond the resident K/V limit the backward streams blocks through the
# same scalar-prefetched sequences as the forward: dq walks the causal
# triangle row-major (k/v blocks stream; dq accumulates in VMEM scratch
# per q-block), dk/dv walk it column-major (q/do blocks stream; dk/dv
# accumulate per k-block). O(block) VMEM at any T — long-T training never
# materializes [T, T].


def _flash_bwd_dq_stream_kernel(i_ref, j_ref, q_ref, k_ref, v_ref, do_ref,
                                lse_ref, d_ref, dq_ref, dq_acc, *,
                                block_q: int, block_k: int, nk: int,
                                causal: bool, scale: float):
    BQ = q_ref.shape[1]
    BK = k_ref.shape[1]
    t = pl.program_id(1)
    i, j = i_ref[t], j_ref[t]

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]
    d_row = d_ref[0, :, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (BQ, BK), 0)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (BQ, BK), 1)
        s = jnp.where(kpos > qpos, _NEG, s)
    p = jnp.exp(s - lse[:, None])
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - d_row[:, None])
    dq_acc[:] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal:
        jmax = jnp.minimum(((i + 1) * block_q - 1) // block_k, nk - 1)
    else:
        jmax = nk - 1

    @pl.when(j == jmax)
    def _():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_stream_kernel(i_ref, j_ref, k_ref, v_ref, q_ref, do_ref,
                                 lse_ref, d_ref, dk_ref, dv_ref, dk_acc,
                                 dv_acc, *, block_q: int, block_k: int,
                                 nq: int, causal: bool, scale: float):
    BK = k_ref.shape[1]
    BQ = q_ref.shape[1]
    t = pl.program_id(1)
    i, j = i_ref[t], j_ref[t]
    i0 = (j * block_k) // block_q if causal else 0

    @pl.when(i == i0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]
    d_row = d_ref[0, :, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (BQ, BK), 0)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (BQ, BK), 1)
        s = jnp.where(kpos > qpos, _NEG, s)
    p = jnp.exp(s - lse[:, None])
    dv_acc[:] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - d_row[:, None])
    dk_acc[:] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def _flash_bwd_stream_bhtd(q, k, v, do, o, lse, causal, scale, block_q,
                           block_k, interpret):
    """Streaming backward: (dq, dk, dv) each [BH, T, D], O(block) VMEM."""
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    nq, nk = T // block_q, T // block_k
    d_row = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, T, 1]

    ir, jr = _pair_arrays(nq, nk, block_q, block_k, causal, "row")
    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, len(ir)),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, t, ii, jj: (b, ii[t], 0)),
            pl.BlockSpec((1, block_k, D), lambda b, t, ii, jj: (b, jj[t], 0)),
            pl.BlockSpec((1, block_k, D), lambda b, t, ii, jj: (b, jj[t], 0)),
            pl.BlockSpec((1, block_q, D), lambda b, t, ii, jj: (b, ii[t], 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, t, ii, jj: (b, ii[t], 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, t, ii, jj: (b, ii[t], 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda b, t, ii, jj: (b, ii[t], 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_stream_kernel, block_q=block_q,
                          block_k=block_k, nk=nk, causal=causal, scale=scale),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        interpret=interpret,
        name="flash_bwd_dq_stream",
    )(jnp.asarray(ir), jnp.asarray(jr), q, k, v, do, lse, d_row)

    ic, jc = _pair_arrays(nq, nk, block_q, block_k, causal, "col")
    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, len(ic)),
        in_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, t, ii, jj: (b, jj[t], 0)),
            pl.BlockSpec((1, block_k, D), lambda b, t, ii, jj: (b, jj[t], 0)),
            pl.BlockSpec((1, block_q, D), lambda b, t, ii, jj: (b, ii[t], 0)),
            pl.BlockSpec((1, block_q, D), lambda b, t, ii, jj: (b, ii[t], 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, t, ii, jj: (b, ii[t], 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, t, ii, jj: (b, ii[t], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, t, ii, jj: (b, jj[t], 0)),
            pl.BlockSpec((1, block_k, D), lambda b, t, ii, jj: (b, jj[t], 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_stream_kernel, block_q=block_q,
                          block_k=block_k, nq=nq, causal=causal, scale=scale),
        grid_spec=dkv_spec,
        out_shape=[jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv_stream",
    )(jnp.asarray(ic), jnp.asarray(jc), k, v, q, do, lse, d_row)
    return dq, dk, dv


# ----------------------------------------------------------------- masked
#
# Grouped-query attention under a per-query key mask (kernel name
# ``masked_attention``; `nn/layers/dsa.py::masked_gqa_attention` resolves
# it), and the same kernels with no mask operand (``banded_attention``;
# `dsa.banded_gqa_attention`): the keys a query reads are then the causal
# triangle, or the band `t - window < s <= t` of a sliding-window layer,
# or everything (a bidirectional layer), and a tile's mask is built inside
# the kernel from two iotas. `keep[t, s]` says whether query t attends to
# key s. Under `causal` the caller vouches that `keep` holds nothing above
# the diagonal, and the visited (q-block, k-block) pairs are the lower
# triangle of `_pair_arrays`, with `window` only its tiles that meet the
# band; without `causal` they are the whole rectangle. Inside a visited
# tile the mask alone decides. A mask operand travels as int8, one
# `[block_q, block_k]` tile a step.
#
# The G = H / KV query heads of one KV head share every step: operands are
# FOLDED by q-block in XLA (`_fold_heads`: row `(i, g, r)` of `[KV, G*S,
# Dh]` is position `i*block_q + r` of head `kv*G + g`), so a q block is
# `[G*block_q, Dh]` rows against one K tile, one V tile and one mask tile,
# fetched once for the group. Operands reach the MXU in the dtype they
# arrive in (bf16 under `mixed_bfloat16`); products accumulate in float32,
# max/exp/sum and the rescales are float32, `p` and `ds` are cast to the
# operands' dtype for the second product. Masked scores are `_NEG`, as in
# the XLA body: a row's tiles before its first kept key accumulate weight
# 1 a key against a running max of `_NEG`, and the first kept key rescales
# all of that by exp(_NEG - m) = 0 exactly; every row keeps a key.
#
# Backward: the two-kernel form above. dq walks the pairs row-major
# like the forward; dk/dv walk them column-major in the TRANSPOSED
# orientation (`s^T = k q^T`, keys on sublanes, so `p^T do` and `ds^T q`
# are plain products and the sum over the group's heads is a loop over
# its row slices), reading `keep^T` tiles and lse / d_row as lane rows.


def _fold_heads(x, KV: int, block_q: int):
    """[S, H, Dh] -> [KV, G*S, Dh], the G heads of a KV head folded into the
    rows of each q block."""
    S, H, Dh = x.shape
    G = H // KV
    x = x.reshape(S // block_q, block_q, KV, G, Dh)
    return jnp.transpose(x, (2, 0, 3, 1, 4)).reshape(KV, G * S, Dh)


def _unfold_heads(x, G: int, block_q: int):
    """Inverse of `_fold_heads`: [KV, G*S, Dh] -> [S, KV*G, Dh]."""
    KV, GS, Dh = x.shape
    S = GS // G
    x = x.reshape(KV, S // block_q, G, block_q, Dh)
    return jnp.transpose(x, (1, 3, 0, 2, 4)).reshape(S, KV * G, Dh)


def _tile_keep(keep_ref, i, j, block_q, block_k, causal, window,
               transposed=False):
    """The bool mask of tile (i, j): the operand's tile, or with no operand
    the causal band from the tile's place (its row r is query `i*block_q +
    r`, its column c key `j*block_k + c`: kept where key <= query, and key >
    query - window under a window); None where every pair is kept.
    `transposed`: `[BK, BQ]`."""
    if keep_ref is not None:
        return keep_ref[...].astype(jnp.int32) != 0
    if not causal:
        return None
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    rows = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 if transposed else 0)
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0 if transposed else 1)
    keep = cols <= rows
    return keep if window is None else keep & (cols > rows - window)


def _masked_scores(a, b, keep, scale, rope=None):
    """Masked scaled scores `a b^T` of one tile in float32. `keep` is the
    tile's mask (None: no pair is masked), shared by the groups of rows of
    `a` stacked on it: folded q rows `[G*BQ, Dh]` against `[BQ, BK]`, or in
    the transposed orientation a k tile against one head's q rows and a
    `keep^T` tile `[BK, BQ]`. `rope` (`latent_attention`): a second pair of
    operands of another width whose product is added into the tile before
    the scale, `a b^T + a_r b_r^T`."""
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if rope is not None:
        s = s + jax.lax.dot_general(*rope, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    s = s * scale
    if keep is None:
        return s
    return jnp.where(keep, s.reshape(-1, *keep.shape), _NEG).reshape(s.shape)


def _lanes(x, n: int):
    """A lane-replicated `[rows, W]` statistic at width n: whole copies side
    by side (free on the chip: no cross-lane move), or its first lanes."""
    W = x.shape[1]
    if n < W:
        return x[:, :n]
    assert n % W == 0, (n, W)
    return x if n == W else jnp.tile(x, (1, n // W))


def _first_k_block(i, block_q, block_k, window):
    """The k block that starts q block i's row of `_pair_arrays`' sequence."""
    if window is None:
        return 0
    return jnp.maximum(i * block_q - window + 1, 0) // block_k


def _last_k_block(i, block_q, block_k, nk, causal):
    """The k block that ends q block i's row of `_pair_arrays`' sequence."""
    if not causal:
        return nk - 1
    return jnp.minimum(((i + 1) * block_q - 1) // block_k, nk - 1)


def _last_q_block(j, block_q, block_k, nq, window):
    """The q block that ends k block j's column of the sequence."""
    if window is None:
        return nq - 1
    return jnp.minimum(((j + 1) * block_k + window - 2) // block_q, nq - 1)


def _keep_first(refs, has_keep: bool):
    """(the mask's ref or None, the other refs): a kernel's mask, where the
    call has one, is the first ref after the operands every call has."""
    return (refs[0], refs[1:]) if has_keep else (None, refs)


def _rope_first(refs, has_rope: bool):
    """((the rotary query rows' ref, the shared rotary key tile's ref) or
    None, the other refs): `latent_attention`'s second pair of operands
    comes where a mask would, after the operands every call has."""
    return (refs[:2], refs[2:]) if has_rope else (None, refs)


def _masked_fwd_kernel(i_ref, j_ref, q_ref, k_ref, v_ref, *refs, block_q: int,
                       block_k: int, nk: int, causal: bool, scale: float,
                       window=None, has_keep: bool = True,
                       has_rope: bool = False):
    """One streamed step of the online softmax. The running max `m` and sum
    `l` live lane-replicated (`[rows, 128]`): `l` adds the score tile's
    128-lane columns elementwise and is summed across lanes once a q block,
    so a step pays one cross-lane reduction (the max), not two, and no
    broadcast of a `[rows, 1]` column."""
    keep_ref, refs = _keep_first(refs, has_keep)
    rope, (o_ref, lse_ref, acc_ref, m_ref, l_ref) = _rope_first(
        refs, has_rope)
    t = pl.program_id(1)
    i, j = i_ref[t], j_ref[t]

    @pl.when(j == _first_k_block(i, block_q, block_k, window))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    v = v_ref[0]
    s = _masked_scores(q_ref[0], k_ref[0],
                       _tile_keep(keep_ref, i, j, block_q, block_k, causal,
                                  window), scale,
                       rope and (rope[0][0], rope[1][0]))
    W = m_ref.shape[1]
    m = m_ref[...]
    new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - _lanes(new_m, block_k))
    corr = jnp.exp(m - new_m)
    l_ref[...] = l_ref[...] * corr + sum(
        p[:, c:c + W] for c in range(0, block_k, W))
    acc_ref[...] = acc_ref[...] * _lanes(corr, acc_ref.shape[1]) \
        + jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    m_ref[...] = new_m

    @pl.when(j == _last_k_block(i, block_q, block_k, nk, causal))
    def _():
        l = jnp.maximum(jnp.sum(l_ref[...], axis=1, keepdims=True), 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l)


def _masked_dq_kernel(i_ref, j_ref, q_ref, k_ref, v_ref, *refs, block_q: int,
                      block_k: int, nk: int, causal: bool, scale: float,
                      window=None, has_keep: bool = True,
                      has_rope: bool = False):
    keep_ref, refs = _keep_first(refs, has_keep)
    rope, refs = _rope_first(refs, has_rope)
    if has_rope:   # a second output and a second accumulator, each last
        do_ref, lse_ref, d_ref, dq_ref, dqr_ref, dq_acc, dqr_acc = refs
    else:
        do_ref, lse_ref, d_ref, dq_ref, dq_acc = refs
    t = pl.program_id(1)
    i, j = i_ref[t], j_ref[t]

    @pl.when(j == _first_k_block(i, block_q, block_k, window))
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if has_rope:
            dqr_acc[...] = jnp.zeros_like(dqr_acc)

    k = k_ref[0]
    s = _masked_scores(q_ref[0], k,
                       _tile_keep(keep_ref, i, j, block_q, block_k, causal,
                                  window), scale,
                       rope and (rope[0][0], rope[1][0]))
    p = jnp.exp(s - lse_ref[0])
    dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - d_ref[0])
    dq_acc[...] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if has_rope:
        k_r = rope[1][0]
        dqr_acc[...] += jax.lax.dot_general(
            ds.astype(k_r.dtype), k_r, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == _last_k_block(i, block_q, block_k, nk, causal))
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)
        if has_rope:
            dqr_ref[0] = (dqr_acc[...] * scale).astype(dqr_ref.dtype)


def _masked_dkv_kernel(i_ref, j_ref, k_ref, v_ref, q_ref, do_ref, *refs,
                       block_q: int, block_k: int, nq: int, causal: bool,
                       scale: float, window=None, has_keep: bool = True,
                       has_rope: bool = False):
    keep_t_ref, refs = _keep_first(refs, has_keep)
    rope, refs = _rope_first(refs, has_rope)
    if has_rope:   # a third output and a third accumulator, each last
        (lse_ref, d_ref, dk_ref, dv_ref, dkr_ref, dk_acc, dv_acc,
         dkr_acc) = refs
        qr_ref, kr_ref = rope
    else:
        lse_ref, d_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    t = pl.program_id(1)
    i, j = i_ref[t], j_ref[t]

    @pl.when(i == ((j * block_k) // block_q if causal else 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if has_rope:
            dkr_acc[...] = jnp.zeros_like(dkr_acc)

    k, v = k_ref[0], v_ref[0]
    keep_t = _tile_keep(keep_t_ref, i, j, block_q, block_k, causal, window,
                        transposed=True)                     # [BK, BQ]
    dk = jnp.zeros(dk_acc.shape, jnp.float32)
    dv = jnp.zeros(dv_acc.shape, jnp.float32)
    for g in range(lse_ref.shape[2]):    # the group's heads share k, v, keep
        rows = slice(g * block_q, (g + 1) * block_q)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        q_r = qr_ref[0, rows, :] if has_rope else None
        p_t = jnp.exp(_masked_scores(k, q, keep_t, scale,
                                     has_rope and (kr_ref[0], q_r) or None)
                      - lse_ref[0, 0, g:g + 1, :])           # [BK, BQ]
        dv += jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - d_ref[0, 0, g:g + 1, :])
        dk += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if has_rope:
            dkr_acc[...] += jax.lax.dot_general(
                ds_t.astype(q_r.dtype), q_r, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    dk_acc[...] += dk
    dv_acc[...] += dv

    @pl.when(i == _last_q_block(j, block_q, block_k, nq, window))
    def _():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        if has_rope:
            dkr_ref[0] = (dkr_acc[...] * scale).astype(dkr_ref.dtype)


def _masked_specs(block_q, block_k, G, D, Dr=None):
    """BlockSpecs over the prefetched (i, j) sequence: folded q rows, a k/v
    tile, a `keep` tile, a `keep^T` tile, a `[.., 1]` column of the folded
    rows; with `Dr` (`latent_attention`) the rotary query rows, the rotary
    key tile every head shares (its block index ignores the head) and the
    per-head tile of that key's gradient."""
    rows = G * block_q
    rope = {} if Dr is None else dict(
        qr=pl.BlockSpec((1, rows, Dr), lambda b, t, ii, jj: (b, ii[t], 0)),
        kr=pl.BlockSpec((1, block_k, Dr), lambda b, t, ii, jj: (0, jj[t], 0)),
        dkr=pl.BlockSpec((1, block_k, Dr),
                         lambda b, t, ii, jj: (b, jj[t], 0)))
    return dict(
        rope,
        q=pl.BlockSpec((1, rows, D), lambda b, t, ii, jj: (b, ii[t], 0)),
        kv=pl.BlockSpec((1, block_k, D), lambda b, t, ii, jj: (b, jj[t], 0)),
        keep=pl.BlockSpec((block_q, block_k),
                          lambda b, t, ii, jj: (ii[t], jj[t])),
        keep_t=pl.BlockSpec((block_k, block_q),
                            lambda b, t, ii, jj: (jj[t], ii[t])),
        col=pl.BlockSpec((1, rows, 1), lambda b, t, ii, jj: (b, ii[t], 0)),
        row=pl.BlockSpec((1, 1, G, block_q),
                         lambda b, t, ii, jj: (b, ii[t], 0, 0)))


def _mask_operand(keep8, spec, rope=None, sp=None):
    """(the in_specs, the operands, the call's family name) of what a call
    has beside the operands every call has: a mask (`masked_attention`),
    nothing (`banded_attention`), or with `rope` = (rotary query rows
    folded, the shared rotary key `[1, S, Dr]`) those two
    (`latent_attention`)."""
    if rope is not None:
        return [sp["qr"], sp["kr"]], list(rope), "latent_attention"
    if keep8 is not None:
        return [spec], [keep8], "masked_attention"
    return [], [], "banded_attention"


def _rope_statics(rope):
    """The kernels' static arguments that only `latent_attention` sets: none
    for the other two families, whose calls stay as they were."""
    return {} if rope is None else {"has_rope": True}


def _masked_fwd(q, k, v, keep8, G, scale, causal, block_q, block_k,
                interpret, window=None, rope=None):
    """Folded q `[KV, G*S, D]`, k, v `[KV, S, D]`, keep8 `[S, S]` int8 or
    None -> (o folded, lse `[KV, G*S, 1]` float32). `rope`: see
    `_mask_operand`."""
    from jax.experimental.pallas import tpu as pltpu

    KV, S, D = k.shape
    nq, nk = S // block_q, S // block_k
    ir, jr = _pair_arrays(nq, nk, block_q, block_k, causal, "row", window)
    sp = _masked_specs(block_q, block_k, G, D,
                       None if rope is None else rope[1].shape[-1])
    rows, W = G * block_q, min(128, block_k)   # W: the statistics' lanes
    keep_spec, keep_arg, family = _mask_operand(keep8, sp["keep"], rope, sp)
    return pl.pallas_call(
        functools.partial(_masked_fwd_kernel, block_q=block_q,
                          block_k=block_k, nk=nk, causal=causal, scale=scale,
                          window=window, has_keep=keep8 is not None,
                          **_rope_statics(rope)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(KV, len(ir)),
            in_specs=[sp["q"], sp["kv"], sp["kv"]] + keep_spec,
            out_specs=[sp["q"], sp["col"]],
            scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32),
                            pltpu.VMEM((rows, W), jnp.float32),
                            pltpu.VMEM((rows, W), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((KV, G * S, 1), jnp.float32)],
        interpret=interpret,
        name=f"{family}_fwd",
    )(jnp.asarray(ir), jnp.asarray(jr), q, k, v, *keep_arg)


def _masked_bwd(q, k, v, keep8, do, o, lse, G, scale, causal, block_q,
                block_k, interpret, window=None, rope=None):
    """(dq folded, dk, dv) from the folded residuals; with `rope` (see
    `_mask_operand`) `dq` is (dq, the rotary rows' gradient folded) and a
    fourth result is the shared rotary key's gradient by KV head,
    `[KV, S, Dr]`, for the caller to sum over the heads."""
    from jax.experimental.pallas import tpu as pltpu

    KV, S, D = k.shape
    nq, nk = S // block_q, S // block_k
    rows = G * block_q
    d_row = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                  # [KV, G*S, 1]
    Dr = None if rope is None else rope[1].shape[-1]
    sp = _masked_specs(block_q, block_k, G, D, Dr)
    # what only a call with `rope` has: one more result and accumulator each
    dq_more = ([], [], []) if rope is None else (
        [sp["qr"]], [pltpu.VMEM((rows, Dr), jnp.float32)],
        [jax.ShapeDtypeStruct(rope[0].shape, rope[0].dtype)])
    dkv_more = ([], [], []) if rope is None else (
        [sp["dkr"]], [pltpu.VMEM((block_k, Dr), jnp.float32)],
        [jax.ShapeDtypeStruct((KV, S, Dr), jnp.float32)])

    ir, jr = _pair_arrays(nq, nk, block_q, block_k, causal, "row", window)
    keep_spec, keep_arg, family = _mask_operand(keep8, sp["keep"], rope, sp)
    dq = pl.pallas_call(
        functools.partial(_masked_dq_kernel, block_q=block_q,
                          block_k=block_k, nk=nk, causal=causal, scale=scale,
                          window=window, has_keep=keep8 is not None,
                          **_rope_statics(rope)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(KV, len(ir)),
            in_specs=[sp["q"], sp["kv"], sp["kv"]] + keep_spec
            + [sp["q"], sp["col"], sp["col"]],
            out_specs=[sp["q"]] + dq_more[0] if rope else sp["q"],
            scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32)]
            + dq_more[1]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] + dq_more[2]
        if rope else jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=f"{family}_dq",
    )(jnp.asarray(ir), jnp.asarray(jr), q, k, v, *keep_arg, do, lse, d_row)

    ic, jc = _pair_arrays(nq, nk, block_q, block_k, causal, "col", window)
    as_rows = lambda c: c.reshape(KV, nq, G, block_q)
    keep_spec, keep_arg, family = _mask_operand(
        None if keep8 is None else keep8.T, sp["keep_t"], rope, sp)
    dk, dv, *dk_r = pl.pallas_call(
        functools.partial(_masked_dkv_kernel, block_q=block_q,
                          block_k=block_k, nq=nq, causal=causal, scale=scale,
                          window=window, has_keep=keep8 is not None,
                          **_rope_statics(rope)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(KV, len(ic)),
            in_specs=[sp["kv"], sp["kv"], sp["q"], sp["q"]] + keep_spec
            + [sp["row"], sp["row"]],
            out_specs=[sp["kv"], sp["kv"]] + dkv_more[0],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32)]
            + dkv_more[1]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)] + dkv_more[2],
        interpret=interpret,
        name=f"{family}_dkv",
    )(jnp.asarray(ic), jnp.asarray(jc), k, v, q, do, *keep_arg,
      as_rows(lse), as_rows(d_row))
    return (dq, dk, dv, *dk_r)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _masked_attention_pallas(q, k, v, keep, causal: bool, block_q: int,
                             block_k: int, interpret: bool = False,
                             window: Optional[int] = None):
    """q: [S, H, Dh]; k, v: [S, KV, Dh]; keep: [S, S] bool, row t's keys,
    none above the diagonal where `causal`; or None, and row t's keys are
    then s <= t under `causal` (t - window < s <= t with `window`), every
    key without -> [S, H, Dh]. S must be a multiple of both blocks (the
    callers ask the registry first)."""
    return _masked_attention_fwd(q, k, v, keep, causal, block_q, block_k,
                                 interpret, window)[0]


def _masked_operands(q, k, v, keep, block_q):
    """What the kernels take: (q folded, k and v `[KV, S, Dh]`, keep as
    int8 or None), then G and the scale."""
    KV = k.shape[1]
    return ((_fold_heads(q, KV, block_q), jnp.swapaxes(k, 0, 1),
             jnp.swapaxes(v, 0, 1),
             None if keep is None else keep.astype(jnp.int8)),
            q.shape[1] // KV, q.shape[2] ** -0.5)


def _masked_attention_fwd(q, k, v, keep, causal, block_q, block_k, interpret,
                          window):
    _require_block_multiple(q.shape[0], block_q, block_k)
    operands, G, scale = _masked_operands(q, k, v, keep, block_q)
    o, lse = _masked_fwd(*operands, G, scale, causal, block_q, block_k,
                         interpret, window)
    return _unfold_heads(o, G, block_q), (q, k, v, keep, o, lse)


def _masked_attention_bwd(causal, block_q, block_k, interpret, window, res,
                          g):
    q, k, v, keep, o, lse = res
    operands, G, scale = _masked_operands(q, k, v, keep, block_q)
    dq, dk, dv = _masked_bwd(
        *operands, _fold_heads(g, k.shape[1], block_q), o, lse, G, scale,
        causal, block_q, block_k, interpret, window)
    return (_unfold_heads(dq, G, block_q), jnp.swapaxes(dk, 0, 1),
            jnp.swapaxes(dv, 0, 1), None)


_masked_attention_pallas.defvjp(_masked_attention_fwd, _masked_attention_bwd)


# What the hungriest of the three kernels (dq) may hold in VMEM, by
# `_masked_vmem_bytes`' count: the 16 MiB scoped default recorded at
# `_RESIDENT_KV_LIMIT`. The count is set from what the chip's compiler
# accepts (`tests/test_chip_compile.py`): bf16 and f32 at Dh 128 pass with
# a `[1024, 1024]` score tile, f32 at Dh 256 passes at 512 keys and is
# refused at 1,024, a `[4096, 512]` tile is refused.
_MASKED_VMEM_LIMIT = 16 * 1024 * 1024


def _masked_vmem_bytes(G, block_q, block_k, Dh, itemsize):
    rows, lanes = G * block_q, -(-Dh // 128) * 128
    blocks = 2 * (3 * rows * lanes * itemsize      # q, do, dq: double buffers
                  + 2 * block_k * lanes * itemsize            # k, v
                  + block_q * block_k                         # int8 mask
                  + 2 * rows * 128 * 4)       # lse, d_row: `[rows, 1]` padded
    # the accumulator, and two float32 score tiles live at a time
    return blocks + rows * lanes * 4 + 2 * rows * block_k * 4


def masked_blocks(S: int, G: int, Dh: int, itemsize: int):
    """(block_q, block_k) for a sequence of S positions, G query heads a KV
    head and heads of Dh, or None where S is off the 128-lane tile or the
    group too large: `G * block_q` rows of about 1,024 (the MXU streams them
    against one K tile) against up to 512 keys, fewer where VMEM asks
    (1,024 keys a step measured no faster on `keye_vl2_30b_a3b`'s layer,
    PERF.md PR 27)."""
    fit = lambda sizes: next((b for b in sizes if S % b == 0), None)
    block_q = fit([b for b in (1024, 512, 256, 128) if G * b <= 1024]
                  or [128])
    if block_q is None:
        return None
    block_k = fit([b for b in (512, 256, 128)
                   if _masked_vmem_bytes(G, block_q, b, Dh, itemsize)
                   <= _MASKED_VMEM_LIMIT])
    return None if block_k is None else (block_q, block_k)


def masked_attention(q, k, v, keep, causal: bool = True):
    """The Pallas body of `masked_attention` at the blocks `masked_blocks`
    chooses; the caller has resolved the registry."""
    block_q, block_k = masked_blocks(q.shape[0], q.shape[1] // k.shape[1],
                                     q.shape[2], q.dtype.itemsize)
    return _masked_attention_pallas(q, k, v, keep, causal, block_q, block_k,
                                    _registry.interpret_mode())


def banded_attention(q, k, v, window: Optional[int] = None,
                     causal: bool = True):
    """The Pallas body of `banded_attention`: the same kernels and blocks
    with no mask operand; the caller has resolved the registry."""
    block_q, block_k = masked_blocks(q.shape[0], q.shape[1] // k.shape[1],
                                     q.shape[2], q.dtype.itemsize)
    return _masked_attention_pallas(q, k, v, None, causal, block_q, block_k,
                                    _registry.interpret_mode(),
                                    band_window(q.shape[0], window))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _latent_attention_pallas(q_n, q_r, k_n, k_r, v, block_q: int,
                             block_k: int, interpret: bool = False):
    """Causal attention whose score is a sum of two products of different
    widths, the second against a key every head shares (multi-head latent
    attention): q_n, k_n: [S, H, Dn]; q_r: [S, H, Dr]; k_r: [S, Dr]; v:
    [S, H, Dn] -> [S, H, Dn], with `score_h[t, s] = (q_n . k_n + q_r . k_r)
    / sqrt(Dn + Dr)`. The masked kernels with no mask operand and the
    static case `has_rope`: each head is its own KV head (G = 1), the
    shared key's tile is fetched by key block alone, and its gradient
    leaves the dk/dv kernel by head, in float32, to be summed here."""
    return _latent_attention_fwd(q_n, q_r, k_n, k_r, v, block_q, block_k,
                                 interpret)[0]


def _latent_operands(q_n, q_r, k_n, k_r, v, block_q):
    """(q_n folded, k_n and v `[H, S, Dn]`), the rotary pair (q_r folded,
    k_r `[1, S, Dr]`) and the scale."""
    H = q_n.shape[1]
    return ((_fold_heads(q_n, H, block_q), jnp.swapaxes(k_n, 0, 1),
             jnp.swapaxes(v, 0, 1)),
            (_fold_heads(q_r, H, block_q), k_r[None]),
            (q_n.shape[2] + q_r.shape[2]) ** -0.5)


def _latent_attention_fwd(q_n, q_r, k_n, k_r, v, block_q, block_k,
                          interpret):
    _require_block_multiple(q_n.shape[0], block_q, block_k)
    operands, rope, scale = _latent_operands(q_n, q_r, k_n, k_r, v, block_q)
    o, lse = _masked_fwd(*operands, None, 1, scale, True, block_q, block_k,
                         interpret, rope=rope)
    return _unfold_heads(o, 1, block_q), (q_n, q_r, k_n, k_r, v, o, lse)


def _latent_attention_bwd(block_q, block_k, interpret, res, g):
    q_n, q_r, k_n, k_r, v, o, lse = res
    operands, rope, scale = _latent_operands(q_n, q_r, k_n, k_r, v, block_q)
    (dq_n, dq_r), dk_n, dv, dk_r = _masked_bwd(
        *operands, None, _fold_heads(g, g.shape[1], block_q), o, lse, 1,
        scale, True, block_q, block_k, interpret, rope=rope)
    return (_unfold_heads(dq_n, 1, block_q), _unfold_heads(dq_r, 1, block_q),
            jnp.swapaxes(dk_n, 0, 1),
            jnp.sum(dk_r, axis=0).astype(k_r.dtype), jnp.swapaxes(dv, 0, 1))


_latent_attention_pallas.defvjp(_latent_attention_fwd, _latent_attention_bwd)


def latent_attention(q_n, q_r, k_n, k_r, v):
    """The Pallas body of `latent_attention` at `masked_blocks`' blocks for
    one head a KV head and rows of Dn + Dr; the caller has resolved the
    registry."""
    block_q, block_k = masked_blocks(
        q_n.shape[0], 1, q_n.shape[2] + q_r.shape[2], q_n.dtype.itemsize)
    return _latent_attention_pallas(q_n, q_r, k_n, k_r, v, block_q, block_k,
                                    _registry.interpret_mode())


def band_window(S: int, window: Optional[int]):
    """`window` as the kernels take it: None where it reaches every earlier
    key of a sequence of S anyway."""
    return None if window is None or window >= S else int(window)


def band_pairs(S: int, window: Optional[int] = None, causal: bool = True):
    """(query, key) pairs a layer's attention reads at S positions: row t
    reads min(t + 1, window) keys under `causal`, all S without."""
    if not causal:
        return S * S
    w = min(window or S, S)
    return w * (w + 1) // 2 + (S - w) * w


def band_fill_share(S: int, G: int, Dh: int, itemsize: int,
                    window: Optional[int] = None, causal: bool = True):
    """Pairs inside the band over pairs in the tiles the Pallas body visits
    at `masked_blocks`' blocks (1.0: no tile holds a pair it masks)."""
    block_q, block_k = masked_blocks(S, G, Dh, itemsize)
    visited, _ = _pair_arrays(S // block_q, S // block_k, block_q, block_k,
                              causal, "row", band_window(S, window))
    return band_pairs(S, window, causal) / (len(visited) * block_q * block_k)


def _masked_pallas_available(backend, shapes, dtypes, meta=(), forced=False):
    """`shapes` is `(S, H, Dh, KV)`."""
    if backend != "tpu" and not forced:
        return False, ("auto off-TPU keeps the XLA row-block body (interpret "
                       "mode is for the forced parity tests)")
    if dtypes and dtypes[0] not in ("bfloat16", "float32"):
        return False, (f"dtype {dtypes[0]}: the kernel takes bfloat16 or "
                       "float32 operands (Mosaic has no 64-bit arithmetic)")
    if shapes:
        S, H, Dh, KV = shapes
        if H % KV:
            return False, f"H={H} is not a multiple of KV={KV}"
        if masked_blocks(S, H // KV, Dh, 2 if dtypes == ("bfloat16",)
                         else 4) is None:
            return False, (f"S={S}, G={H // KV}, Dh={Dh}: S is not a "
                           "multiple of a 128-lane block, or the group's "
                           "blocks outgrow VMEM")
        if backend == "tpu" and Dh != 64 and Dh % 128:
            return False, (f"Dh={Dh}: the widths the described-chip compile "
                           "covers are 64 (half the lanes idle) and "
                           "multiples of 128")
    if backend == "tpu":
        return True, ("TPU masked flash kernel (per-query int8 mask tiles, "
                      "the group's heads folded into the q rows)")
    return True, "interpret mode off-TPU (float-close parity tests only)"


def _masked_xla_available(backend, shapes, dtypes, meta=(), forced=False):
    return True, ("XLA row blocks under jax.checkpoint "
                  "(nn/layers/dsa.py: the parity reference)")


def _banded_pallas_available(backend, shapes, dtypes, meta=(), forced=False):
    """`shapes` is `(S, H, Dh, KV)`: what `masked_attention` takes, the
    kernels being the same."""
    ok, why = _masked_pallas_available(backend, shapes, dtypes, meta, forced)
    if ok and backend == "tpu":
        why = ("TPU masked flash kernel with no mask operand (the causal "
               "band from iotas, only the tiles that meet it visited)")
    return ok, why


def _latent_pallas_available(backend, shapes, dtypes, meta=(), forced=False):
    """`shapes` is `(S, H, Dn, Dr, Dv)`: the widths of the two products and
    of the values."""
    if backend != "tpu" and not forced:
        return False, ("auto off-TPU keeps the XLA row-block body (interpret "
                       "mode is for the forced parity tests)")
    if dtypes and dtypes[0] not in ("bfloat16", "float32"):
        return False, (f"dtype {dtypes[0]}: the kernel takes bfloat16 or "
                       "float32 operands (Mosaic has no 64-bit arithmetic)")
    if shapes:
        S, H, Dn, Dr, Dv = shapes
        if Dn != Dv:
            return False, (f"Dn={Dn}, Dv={Dv}: the kernel's key and value "
                           "tiles are one width")
        if masked_blocks(S, 1, Dn + Dr, 2 if dtypes == ("bfloat16",)
                         else 4) is None:
            return False, (f"S={S}, Dn+Dr={Dn + Dr}: S is not a multiple of "
                           "a 128-lane block, or the blocks outgrow VMEM")
        if backend == "tpu" and (Dn % 128 or (Dr != 64 and Dr % 128)):
            return False, (f"Dn={Dn}, Dr={Dr}: the widths the described-chip "
                           "compile covers are multiples of 128 and, for "
                           "the rotary part, 64")
    if backend == "tpu":
        return True, ("TPU masked flash kernel with a second, rotary "
                      "product into the score tile against a key tile all "
                      "heads share")
    return True, "interpret mode off-TPU (float-close parity tests only)"


_registry.register("masked_attention", [
    _registry.KernelImpl("pallas", _masked_pallas_available),
    _registry.KernelImpl("xla", _masked_xla_available),
])
_registry.register("banded_attention", [
    _registry.KernelImpl("pallas", _banded_pallas_available),
    _registry.KernelImpl("xla", _masked_xla_available),
])
_registry.register("latent_attention", [
    _registry.KernelImpl("pallas", _latent_pallas_available),
    _registry.KernelImpl("xla", _masked_xla_available),
])


# ------------------------------------------------------------------ paged
# Decode-step attention against the paged KV pool (PagedAttention, Kwon
# et al. SOSP 2023): k/v live as [P, page, H, D] pools, each batch row
# reads through its [NP] row of the int32 page table. Inference-only and
# deliberately VJP-EXEMPT: the decode path never differentiates (the
# engines refuse training with decode caches), so no custom_vjp is
# defined — differentiating through it is a loud error, not a silent
# dense fallback.


def _paged_gather_dense(q, k_pages, v_pages, page_table, pos, causal):
    """XLA fallback: gather the pages into the dense [B, L, H, D] cache
    layout and reuse `_cached_decode_attention` VERBATIM. Bit-identical
    to the dense stepper: garbage rows (zero page, pad/CoW tails) land
    exactly on masked key positions, where the softmax weight underflows
    to exactly 0.0 and contributes +0.0 to the same-order contraction."""
    from deeplearning4j_tpu.nn.layers.attention import (
        _cached_decode_attention,
    )

    B = q.shape[0]
    NP = page_table.shape[1]
    _, page, H, D = k_pages.shape
    kc = k_pages[page_table].reshape(B, NP * page, H, D)
    vc = v_pages[page_table].reshape(B, NP * page, H, D)
    return _cached_decode_attention(q, kc, vc, pos, causal)


def _paged_flash_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *, page, n_pages, heads,
                        causal, scale):
    """One (batch, logical-page) step over ALL heads: the page table is
    scalar-prefetched, so the k/v BlockSpec index maps DMA exactly the
    physical page this slot's logical page j resolves to — no dense gather
    ever materializes. Operands arrive with heads folded into the lane dim
    (`[.., H*D]`, a free reshape of the pool's `[.., H, D]`): a block then
    spans whole trailing dims, which is what the TPU lowering requires (a
    one-head `(1, page, 1, D)` block of the 4-D pool is refused), and each
    head is a static lane slice. VMEM scratch (acc, m, l) carries the
    online softmax across the NP sequential grid steps."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    T = q_ref.shape[1]
    D = q_ref.shape[2] // heads

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Pages wholly past this row's last new position hold nothing it may
    # attend to (unallocated table entries point at the zero page).
    @pl.when(j * page < pos_ref[b] + T)
    def _fold():
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (T, page), 1)
        if causal:
            limit = (pos_ref[b] + 1
                     + jax.lax.broadcasted_iota(jnp.int32, (T, page), 0))
        else:
            limit = pos_ref[b] + T
        visible = kpos < limit
        for h in range(heads):
            lanes = slice(h * D, (h + 1) * D)
            q = q_ref[0, :, lanes].astype(jnp.float32) * scale
            k = k_ref[0, :, lanes].astype(jnp.float32)
            v = v_ref[0, :, lanes].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(visible, s, _NEG)                  # [T, page]
            m = m_ref[h]
            new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - new_m)
            corr = jnp.exp(m - new_m)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = new_m

    @pl.when(j == n_pages - 1)
    def _finish():
        for h in range(heads):
            o_ref[0, :, h * D:(h + 1) * D] = (
                acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def _paged_flash(q, k_pages, v_pages, page_table, pos, causal, interpret):
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    P, page = k_pages.shape[:2]
    NP = page_table.shape[1]
    scale = D ** -0.5
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NP),
        in_specs=[
            pl.BlockSpec((1, T, H * D), lambda b, j, pt, pos: (b, 0, 0)),
            pl.BlockSpec((1, page, H * D),
                         lambda b, j, pt, pos: (pt[b, j], 0, 0)),
            pl.BlockSpec((1, page, H * D),
                         lambda b, j, pt, pos: (pt[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, H * D), lambda b, j, pt, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, T, D), jnp.float32),
            pltpu.VMEM((H, T, 1), jnp.float32),
            pltpu.VMEM((H, T, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_flash_kernel, page=page, n_pages=NP,
                          heads=H, causal=causal, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, H * D), q.dtype),
        interpret=interpret,
        name="paged_flash",
    )(page_table, jnp.reshape(pos, (-1,)).astype(jnp.int32),
      q.reshape(B, T, H * D), k_pages.reshape(P, page, H * D),
      v_pages.reshape(P, page, H * D))
    return out.reshape(B, T, H, D)


def paged_decode_attention(q, k_pages, v_pages, page_table, pos, causal):
    """Decode attention through the paged KV pool. q: [B, T, H, D] (the
    new positions, globally at [pos, pos+T) per row); k_pages/v_pages:
    [P, page, H, D]; page_table: [B, NP] int32 (0 = the zero page);
    pos: [B] int32 cursors.

    Resolves `flash_attention_paged` through the kernel registry: the
    Pallas paged-gather kernel on TPU (or when forced — interpret mode,
    float-close), else the XLA dense-gather composite, which is
    bit-identical to the dense stepper's `_cached_decode_attention`.
    Inference-only: no VJP is defined (see module note above)."""
    B, T, H, D = q.shape
    res = _registry.resolve(
        "flash_attention_paged",
        shapes=(B, T, H, D, k_pages.shape[0], k_pages.shape[1],
                page_table.shape[1]),
        dtypes=(str(q.dtype),), meta=(("causal", bool(causal)),))
    if res.impl == "pallas":
        return _paged_flash(q, k_pages, v_pages, page_table, pos, causal,
                            _registry.interpret_mode())
    return _paged_gather_dense(q, k_pages, v_pages, page_table, pos, causal)


# VMEM the paged kernel may spend on its K and V page blocks (all heads of
# a page each, double-buffered). The chip's compiler accepts 14 MiB and
# refuses 16 MiB (f32, page 256, H=32, D=128) under the 16 MiB scoped
# default.
_PAGED_KV_VMEM = 12 * 1024 * 1024


def _paged_pallas_available(backend, shapes, dtypes, meta=(), forced=False):
    """`shapes` is `(B, T, H, D, pages, page_size, pages_per_seq)`."""
    if backend == "tpu":
        if shapes:
            _, _, H, D, _, page, _ = shapes
            itemsize = 2 if dtypes and dtypes[0] == "bfloat16" else 4
            held = 4 * page * H * D * itemsize
            if held > _PAGED_KV_VMEM:
                return False, (
                    f"K+V page blocks need {held / 2**20:.1f} MiB of VMEM "
                    f"(page={page}, H={H}, D={D}, double-buffered) > "
                    f"{_PAGED_KV_VMEM / 2**20:.0f} MiB")
        return True, ("TPU paged-gather flash kernel (scalar-prefetched "
                      "page table, all heads of a page per block)")
    if forced:
        return True, ("interpret mode off-TPU (float-close parity tests "
                      "only)")
    return False, ("auto off-TPU keeps the XLA dense-gather composite — "
                   "bit-identical to the dense stepper")


def _paged_xla_available(backend, shapes, dtypes, meta=(), forced=False):
    return True, ("XLA dense-gather + _cached_decode_attention "
                  "(bit-identical fallback)")


_registry.register("flash_attention_paged", [
    _registry.KernelImpl("pallas", _paged_pallas_available),
    _registry.KernelImpl("xla", _paged_xla_available),
])
