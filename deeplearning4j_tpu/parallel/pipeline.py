"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.3 marks it an
optional TPU-native extension; its cluster story is parameter-averaging data
parallelism only). This module adds the TPU idiom for it — the "collective
pipeline" pattern: stage parameters are STACKED on a leading [S, ...] axis
and sharded across the mesh's `pipe` axis (each device holds one stage);
microbatch activations rotate device-to-device via `lax.ppermute` inside a
`lax.scan` over M + S - 1 ticks (the GPipe schedule with its warm-up/drain
bubble). The whole schedule is one differentiable jitted program, so the
BACKWARD pipeline — activations cached per tick, gradients flowing the ring
in reverse — is generated by jax.grad through the scan+ppermute transpose
rather than hand-scheduled.

Constraints (standard for collective pipelining): stages must be
structurally identical (same param pytree shapes, activation shape
preserved), because shard_map requires uniform per-device computation. Use
`stack_stage_params` to build the [S, ...] stack from per-stage pytrees.

Composes with the other axes: the mesh may carry ("data", "pipe") and the
batch dim of each microbatch shards over "data" as usual.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(stage_params: Sequence):
    """Stack S structurally-identical per-stage param pytrees into one
    pytree with a leading [S, ...] axis (shard this axis over `pipe`)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_params)


def unstack_stage_params(stacked, n_stages: int):
    """Inverse of `stack_stage_params`."""
    return [jax.tree.map(lambda a, i=i: a[i], stacked)
            for i in range(n_stages)]


def _pipe_local(params, xs, *, stage_fn, axis_name, n_stages, n_micro,
                shard_inputs=False):
    """Per-device body. params: [1, ...] (my stage's slice); xs: [M, mb, F]
    microbatches (replicated; only stage 0 ingests them) — or, with
    `shard_inputs`, [M/S, mb, F] (this device's contiguous chunk of the
    microbatch axis; the owner psums each microbatch to everyone at its
    ingest tick, so per-device input memory is O(M/S) instead of O(M))."""
    params = jax.tree.map(lambda a: a[0], params)
    d = jax.lax.axis_index(axis_name)
    is_first = d == 0
    is_last = d == n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    M, S = n_micro, n_stages
    chunk = xs.shape[0]                  # M (replicated) or M // S (sharded)
    buf = jnp.zeros_like(xs[0])          # activation arriving at my stage
    outs = jnp.zeros_like(
        xs, shape=(M,) + xs.shape[1:])   # last stage's microbatches

    def feed(t):
        """Microbatch t's features on stage 0's device."""
        if not shard_inputs:
            return xs[jnp.clip(t, 0, M - 1)]
        mb = jnp.clip(t, 0, M - 1)
        owner = mb // chunk
        mine = xs[jnp.clip(mb - d * chunk, 0, chunk - 1)]
        # One mb-sized all-reduce delivers the owner's chunk row to stage 0
        # (and harmlessly to everyone): the memory/bandwidth trade that
        # removes the full [M, mb, F] per-device replica.
        return jax.lax.psum(jnp.where(d == owner, mine, 0.0), axis_name)

    def tick(carry, t):
        buf, outs = carry
        mb_idx = t - d                   # microbatch at my stage this tick
        active = (mb_idx >= 0) & (mb_idx < M)
        inp = jnp.where(is_first, feed(t), buf)
        # Feed a benign input during my warm-up/drain bubble: evaluating
        # stage_fn on bubble garbage and masking only the OUTPUT leaks
        # NaN/inf through jnp.where's transpose whenever stage_fn has a
        # non-finite derivative at that garbage (the classic where-grad
        # trap), poisoning the psum'd parameter gradients.
        inp = jnp.where(active, inp, jnp.ones_like(inp))
        y = stage_fn(params, inp)
        y = jnp.where(active, y, buf)
        outs = jax.lax.cond(
            active & is_last,
            lambda o: jax.lax.dynamic_update_slice_in_dim(
                o, y[None], jnp.clip(mb_idx, 0, M - 1), axis=0),
            lambda o: o, outs)
        buf = jax.lax.ppermute(y, axis_name, perm)
        return (buf, outs), None

    (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(M + S - 1))
    # Only the last stage's `outs` is meaningful; psum replicates it.
    return jax.lax.psum(jnp.where(is_last, outs, 0.0), axis_name)


def pipeline_apply(stage_fn: Callable, stacked_params, x, mesh: Mesh, *,
                   pipe_axis: str = "pipe", data_axis: str = "data",
                   n_microbatches: int, remat: bool = False,
                   shard_inputs: bool = False):
    """Run x through S pipelined stages: out = stage_{S-1}(... stage_0(x)).

    stage_fn(params_i, x_mb) -> y_mb with y_mb.shape == x_mb.shape;
    stacked_params: leading [S, ...] axis (see `stack_stage_params`);
    x: [B, F...] with B divisible by n_microbatches (times the data axis).
    Differentiable in both params and x.

    `remat=True` wraps the stage body in `jax.checkpoint`: the backward
    pipeline recomputes stage activations instead of stashing every tick's
    intermediates, cutting the schedule's activation memory from
    O((M+S) * stage_intermediates) to O((M+S) * stage_io) — the standard
    GPipe rematerialization trade (recompute ≈ one extra forward).

    `shard_inputs=True` (requires M % S == 0) shards the microbatch stack
    over the pipe axis instead of replicating it: each device holds its
    M/S-chunk and the owner psums each microbatch to stage 0 at its
    ingest tick — per-device input memory O(M/S) at the cost of one
    mb-sized collective per tick.
    """
    if remat:
        stage_fn = jax.checkpoint(stage_fn)
    S = int(mesh.shape[pipe_axis])
    M = int(n_microbatches)
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by n_microbatches {M}")
    n_stacked = {int(a.shape[0]) for a in jax.tree.leaves(stacked_params)}
    if n_stacked != {S}:
        # P(pipe_axis) would silently give each device a MULTI-stage block
        # of which _pipe_local uses only the first — wrong results, no error.
        raise ValueError(
            f"stacked_params leading dim {sorted(n_stacked)} must equal the "
            f"'{pipe_axis}' mesh axis size {S} (one stage per device)")
    if shard_inputs and M % S:
        raise ValueError(
            f"shard_inputs needs n_microbatches ({M}) divisible by the "
            f"'{pipe_axis}' axis size ({S})")
    xs = x.reshape((M, B // M) + x.shape[1:])
    b_ax = data_axis if data_axis in mesh.shape else None
    p_spec = jax.tree.map(lambda _: P(pipe_axis), stacked_params)
    x_spec = P(pipe_axis if shard_inputs else None, b_ax)
    out_spec = P(None, b_ax)
    fn = shard_map(
        functools.partial(_pipe_local, stage_fn=stage_fn,
                          axis_name=pipe_axis, n_stages=S, n_micro=M,
                          shard_inputs=shard_inputs),
        mesh=mesh, in_specs=(p_spec, x_spec), out_specs=out_spec,
        check_vma=False)
    out = fn(stacked_params, xs)
    return out.reshape((B,) + out.shape[2:])
