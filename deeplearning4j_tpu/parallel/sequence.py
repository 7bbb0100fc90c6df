"""Sequence/context parallelism: ring attention and Ulysses-style
all-to-all attention over a mesh axis.

The reference framework predates attention — its only long-sequence
mechanism is truncated BPTT (`MultiLayerNetwork.java:1207`,
`MultiLayerConfiguration.java:66-68`) — so SURVEY.md §5 sets tBPTT+masking
as the parity bar and names "ring-attention/context-parallel via shard_map
collective-permute over ICI" as the TPU-native extension for sequence-length
scaling. This module is that extension:

- `ring_attention(...)`: exact attention over a sequence axis sharded across
  mesh devices. Each device holds a [B, T/p, H, Dh] block of q/k/v; k/v
  blocks rotate around the ring via `lax.ppermute` while a flash-style
  online-softmax accumulator (running max + running sum) folds each block
  in, so no device ever materializes the [T, T] score matrix and per-device
  memory is O(T/p). Compute overlaps the ICI transfer because each
  ppermute'd block is consumed by the next scan step.
- `ulysses_attention(...)`: the all-to-all variant — redistribute
  [seq-sharded, all heads] -> [all seq, head-sharded] with
  `lax.all_to_all`, run ordinary full attention per head group, and
  redistribute back. Cheaper collectives for moderate T; requires
  n_heads % mesh_axis == 0.

Both are differentiable (scan + ppermute/all_to_all have transposes), jit
under `shard_map`, and are exact — equivalence against dense single-device
attention is tested on the 8-device virtual CPU mesh in
`tests/test_sequence_parallel.py`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG = -1e30  # finite mask value: keeps exp() well-defined for masked rows


def _block_update(carry, q, k, v, kpos, qpos, causal, scale):
    """Fold one k/v block into the online-softmax accumulator.

    q: [B, H, Tq, D]; k, v: [B, H, Tk, D];
    carry = (acc [B, H, Tq, D], m [B, H, Tq], l [B, H, Tq]).
    """
    acc, m, l = carry
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s = jnp.where(kpos[None, :] > qpos[:, None], _NEG, s)
    blk_max = jnp.max(s, axis=-1)
    new_m = jnp.maximum(m, blk_max)
    corr = jnp.exp(m - new_m)
    p = jnp.exp(s - new_m[..., None])
    if causal:
        # Rows whose every key so far is masked: new_m == _NEG makes
        # p == exp(0); zero those contributions explicitly.
        p = jnp.where(new_m[..., None] <= _NEG / 2, 0.0, p)
    l = l * corr + jnp.sum(p, axis=-1)
    acc = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return acc, new_m, l


def _ring_local(q, k, v, *, axis_name: str, n_blocks: int, causal: bool,
                scale: float):
    """Per-device body (runs inside shard_map). q/k/v: [B, T_loc, H, D]."""
    me = jax.lax.axis_index(axis_name)
    orig_dtype = q.dtype
    # [B, H, T, D] layout for the attention inner loops; accumulate in at
    # least fp32 (fp64 stays fp64 so x64 tests are exact).
    acc_dtype = jnp.promote_types(orig_dtype, jnp.float32)
    q, k, v = (jnp.swapaxes(a, 1, 2).astype(acc_dtype) for a in (q, k, v))
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    qpos = me * Tq + jnp.arange(Tq)
    perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]

    acc = jnp.zeros((B, H, Tq, D), acc_dtype)
    m = jnp.full((B, H, Tq), _NEG, acc_dtype)
    l = jnp.zeros((B, H, Tq), acc_dtype)

    def step(carry, s):
        k, v, acc, m, l = carry
        src = (me - s) % n_blocks  # ring step s holds src's original block
        kpos = src * Tk + jnp.arange(Tk)
        acc, m, l = _block_update((acc, m, l), q, k, v, kpos, qpos, causal,
                                  scale)
        k, v = jax.lax.ppermute((k, v), axis_name, perm)
        return (k, v, acc, m, l), None

    (k, v, acc, m, l), _ = jax.lax.scan(
        step, (k, v, acc, m, l), jnp.arange(n_blocks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.swapaxes(out, 1, 2).astype(orig_dtype)


def ring_attention(q, k, v, mesh: Mesh, *, seq_axis: str = "seq",
                   batch_axis: Optional[str] = "data", causal: bool = True,
                   scale: Optional[float] = None):
    """Exact multi-head attention with the SEQUENCE dim sharded over
    `mesh.shape[seq_axis]` devices (and optionally batch over `batch_axis`).

    q, k, v: [B, T, H, Dh] global arrays (or already-sharded). Returns
    [B, T, H, Dh] with the same sharding. Set `causal=False` for full
    (encoder) attention.
    """
    n = int(mesh.shape[seq_axis])
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    b_ax = batch_axis if batch_axis in mesh.shape else None
    spec = P(b_ax, seq_axis, None, None)
    fn = shard_map(
        functools.partial(_ring_local, axis_name=seq_axis, n_blocks=n,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def _dense_attn(q, k, v, causal, scale):
    """Single-device reference attention (also the Ulysses per-shard body).
    q/k/v: [B, H, T, D] fp32."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        T = s.shape[-1]
        s = jnp.where(jnp.triu(jnp.ones((T, T), bool), 1)[None, None], _NEG, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, scale: float):
    """Per-device body: seq-sharded [B, T/p, H, D] -> all_to_all ->
    head-sharded [B, T, H/p, D] -> dense attention -> all_to_all back."""
    orig_dtype = q.dtype
    acc_dtype = jnp.promote_types(orig_dtype, jnp.float32)

    def to_heads(a):  # [B, T/p, H, D] -> [B, H/p, T, D]
        a = jax.lax.all_to_all(a, axis_name, split_axis=2, concat_axis=1,
                               tiled=True)
        return jnp.swapaxes(a, 1, 2).astype(acc_dtype)

    o = _dense_attn(to_heads(q), to_heads(k), to_heads(v), causal, scale)
    o = jnp.swapaxes(o, 1, 2).astype(orig_dtype)  # [B, T, H/p, D]
    return jax.lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh, *, seq_axis: str = "seq",
                      batch_axis: Optional[str] = "data",
                      causal: bool = True, scale: Optional[float] = None):
    """DeepSpeed-Ulysses-style sequence parallelism: one all-to-all turns
    sequence sharding into head sharding, each device runs full-sequence
    attention for its head group, and a second all-to-all restores sequence
    sharding. Requires n_heads divisible by the mesh axis size."""
    n = int(mesh.shape[seq_axis])
    H = q.shape[2]
    if H % n:
        raise ValueError(
            f"ulysses_attention needs n_heads ({H}) divisible by mesh axis "
            f"'{seq_axis}' ({n}); use ring_attention otherwise")
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    b_ax = batch_axis if batch_axis in mesh.shape else None
    spec = P(b_ax, seq_axis, None, None)
    fn = shard_map(
        functools.partial(_ulysses_local, axis_name=seq_axis, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def attention(q, k, v, *, causal: bool = True,
              scale: Optional[float] = None, impl: str = "auto"):
    """Single-device multi-head attention, q/k/v [B, T, H, Dh] — the
    framework's default attention entry point.

    impl="auto" uses the Pallas flash kernel (`ops/flash_attention.py`:
    1.2-3.1x XLA dense on a v5e, O(T·D) memory; falls back to dense
    internally when T isn't a block multiple); impl="dense" forces the XLA
    path (also the test oracle). For sequence-sharded attention use
    `ring_attention` / `ulysses_attention`."""
    if impl == "dense":
        return dense_attention(q, k, v, causal=causal, scale=scale)
    from deeplearning4j_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v, causal, scale)


def dense_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None):
    """Single-device reference: q/k/v [B, T, H, Dh] -> [B, T, H, Dh]."""
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    acc_dtype = jnp.promote_types(q.dtype, jnp.float32)
    q_, k_, v_ = (jnp.swapaxes(a, 1, 2).astype(acc_dtype)
                  for a in (q, k, v))
    o = _dense_attn(q_, k_, v_, causal, scale)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)
