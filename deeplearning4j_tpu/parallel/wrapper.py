"""ParallelWrapper: data-parallel training over a device mesh.

API-level equivalent of the reference's
`deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java` — but where
the reference spawns N replica threads, round-robins minibatches, barriers, and
averages parameters every `averagingFrequency` iterations (`:322,353,179`), here
the SAME jitted train step simply runs with the batch sharded over the mesh's
"data" axis: XLA GSPMD emits the gradient all-reduce over ICI inside the step.
There is no averaging frequency because gradients synchronize every step (the
k=1 case the reference can't afford over its transports), no trainer threads,
and no updater-state divergence to repair (`:198-225`).
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets import staging as _staging
from deeplearning4j_tpu.datasets.iterators import (
    MultiSuperbatch,
    Superbatch,
    batch_signature,
    maybe_reset,
    transfer_cast,
)
from deeplearning4j_tpu.parallel import mesh as mesh_mod
from deeplearning4j_tpu.parallel.context import parallel_context
from deeplearning4j_tpu import observability as _obs

_M_BATCHES = _obs.metrics.counter(
    "dl4j_parallel_batches_total",
    "Batches sharded and dispatched through ParallelWrapper.fit")
_M_INPUT_WAIT = _obs.metrics.histogram(
    "dl4j_input_wait_seconds",
    "Host seconds blocked in iterator-next waiting for the next batch "
    "(input starvation; the device is idle while this accrues)",
    label_names=("source",)).labels(source="parallel")
_M_SHARD_SECONDS = _obs.metrics.counter(
    "dl4j_parallel_shard_dispatch_seconds_total",
    "Host seconds spent padding + device_put-sharding batches over the mesh "
    "(the host-side proxy for data distribution cost; in-step collective "
    "wait is inside XLA and not host-visible — see PERF.md)")
_M_DEVICES = _obs.metrics.gauge(
    "dl4j_parallel_devices", "Mesh size of the active ParallelWrapper")


class ParallelWrapper:
    """Data-parallel fit() driver (see module docstring).

    `workers`/`averaging_frequency`/`prefetch_buffer` are accepted for
    reference API parity; `workers` maps to the mesh size, averaging is
    per-step by construction.
    """

    def __init__(self, net, mesh=None, workers: Optional[int] = None,
                 averaging_frequency: int = 1, prefetch_buffer: int = 2,
                 report_score_after_averaging: bool = True,
                 model_axis: Optional[str] = None,
                 seq_axis: Optional[str] = None,
                 expert_axis: Optional[str] = None):
        self.net = net
        self.prefetch_buffer = max(1, int(prefetch_buffer or 2))
        if mesh is None:
            devices = jax.devices()[:workers] if workers else jax.devices()
            mesh = mesh_mod.create_mesh(devices=devices)
        self.mesh = mesh
        self.data_axis = mesh.axis_names[0]
        self.n_devices = int(np.prod(mesh.devices.shape))
        if not net._initialized:
            net.init()
        mesh_mod.shard_params(net, mesh, model_axis=model_axis,
                              expert_axis=expert_axis)
        # Axis roles beyond "data" activate the corresponding layer paths
        # (ring attention over seq_axis, expert-parallel MoE) at trace time
        # via the ParallelContext installed around every dispatch.
        from deeplearning4j_tpu.parallel.context import ParallelContext

        self.context = ParallelContext(
            mesh=mesh, data_axis=self.data_axis, model_axis=model_axis,
            seq_axis=seq_axis, expert_axis=expert_axis)
        _M_DEVICES.set(self.n_devices)

    def _pad_dataset(self, ds: DataSet) -> DataSet:
        """Pad the batch dim up to a multiple of the mesh size (XLA needs the
        sharded dim divisible). Padded rows are masked out of the loss via a
        zeroed labels mask, so a ragged final batch trains identically to the
        unpadded batch (padded rows contribute zero to the summed loss, the
        score divisor counts only real rows, and CenterLoss center updates
        are mask-weighted). Known limitation: BatchNormalization batch
        statistics in train mode are computed over the padded batch (the
        duplicated last row slightly skews mean/var for a ragged batch);
        exact for every batch divisible by the mesh."""
        b = np.asarray(ds.features).shape[0]
        rem = b % self.n_devices
        if rem == 0:
            return ds
        pad = self.n_devices - rem
        labels = None if ds.labels is None else np.asarray(ds.labels)
        lmask = ds.labels_mask
        if labels is not None:
            lmask = _full_labels_mask(labels, lmask,
                                      sequence=self._seq_output())
        return DataSet(
            _pad_rows(np.asarray(ds.features), pad),
            _pad_rows(labels, pad),
            _pad_rows(ds.features_mask, pad, fill_last=False),
            _pad_rows(lmask, pad, fill_last=False),
        )

    def _pad_mds(self, mds: MultiDataSet) -> MultiDataSet:
        """MultiDataSet variant of `_pad_dataset` for ComputationGraph."""
        b = mds.num_examples()
        rem = b % self.n_devices
        if rem == 0:
            return mds
        pad = self.n_devices - rem
        labels = [np.asarray(l) for l in mds.labels]
        lmasks = list(mds.labels_masks) if mds.labels_masks is not None else [None] * len(labels)
        seq = self._seq_output()
        lmasks = [_full_labels_mask(l, m, sequence=seq)
                  for l, m in zip(labels, lmasks)]
        fmasks = mds.features_masks
        return MultiDataSet(
            features=[_pad_rows(np.asarray(f), pad) for f in mds.features],
            labels=[_pad_rows(l, pad) for l in labels],
            features_masks=None if fmasks is None
            else [_pad_rows(m, pad, fill_last=False) for m in fmasks],
            labels_masks=[_pad_rows(m, pad, fill_last=False) for m in lmasks],
        )

    def _seq_output(self) -> bool:
        """Whether the net's output layer(s) emit per-timestep labels —
        disambiguates 2-D INTEGER labels ([b, t] sparse ids vs [b, c]
        integer one-hot) when padding."""
        return any(type(layer).__name__ == "RnnOutputLayer"
                   for _, layer in self.net.named_layers())

    def _shard(self, a):
        if a is None:
            return None
        return jax.device_put(
            a, mesh_mod.data_sharding(self.mesh, np.ndim(a), self.data_axis)
        )

    def _prepare(self, ds, is_graph: bool):
        """Pad one host batch to a mesh-size-multiple batch dim, then apply
        the net's DtypePolicy `transfer_dtype` cast host-side so every
        per-device shard crosses the link in the reduced representation
        (same knob as the local SuperbatchIterator staging path)."""
        if is_graph:
            mds = MultiDataSet.from_dataset(ds) if isinstance(ds, DataSet) else ds
            padded = self._pad_mds(mds)
        else:
            if isinstance(ds, MultiDataSet):
                raise TypeError("MultiDataSet input requires a ComputationGraph net")
            padded = self._pad_dataset(ds)
        pol = getattr(self.net, "dtype_policy", None)
        tdt = getattr(pol, "transfer_dtype", None)
        return padded if tdt is None else transfer_cast(padded, tdt)

    def _shard_batch(self, padded, is_graph: bool):
        """device_put one padded batch with the batch dim over the mesh."""
        if is_graph:
            return MultiDataSet(
                features=[self._shard(np.asarray(f)) for f in padded.features],
                labels=[self._shard(np.asarray(l)) for l in padded.labels],
                features_masks=None if padded.features_masks is None
                else [self._shard(m) for m in padded.features_masks],
                labels_masks=None if padded.labels_masks is None
                else [self._shard(m) for m in padded.labels_masks],
            )
        return DataSet(
            self._shard(np.asarray(padded.features)),
            self._shard(None if padded.labels is None else np.asarray(padded.labels)),
            self._shard(padded.features_mask),
            self._shard(padded.labels_mask),
        )

    def _shard_super(self, parts):
        """np.stack K same-shape parts to [K, B, ...] and device_put with
        the BATCH axis (dim 1) sharded over the mesh — one transfer per
        part for the whole K-block."""
        if parts[0] is None:
            return None
        stacked = np.stack([np.asarray(p) for p in parts])
        return jax.device_put(stacked, mesh_mod.superbatch_sharding(
            self.mesh, stacked.ndim, self.data_axis))

    def _stack_shard(self, pending, is_graph: bool):
        """Stack K padded same-signature batches into a sharded superbatch."""
        k = len(pending)
        if is_graph:
            first = pending[0]
            feats = [self._shard_super([p.features[i] for p in pending])
                     for i in range(len(first.features))]
            labs = [self._shard_super([p.labels[i] for p in pending])
                    for i in range(len(first.labels))]
            fmasks = None if first.features_masks is None else [
                self._shard_super([p.features_masks[i] for p in pending])
                for i in range(len(first.features_masks))]
            lmasks = None if first.labels_masks is None else [
                self._shard_super([p.labels_masks[i] for p in pending])
                for i in range(len(first.labels_masks))]
            return MultiSuperbatch(feats, labs, fmasks, lmasks, k=k)
        return Superbatch(
            self._shard_super([p.features for p in pending]),
            self._shard_super([p.labels for p in pending]),
            self._shard_super([p.features_mask for p in pending]),
            self._shard_super([p.labels_mask for p in pending]),
            k=k,
        )

    def _grouped(self, iterator, k: int, is_graph: bool):
        """Yield lists of padded, transfer-cast, same-signature host
        batches: singletons when the superstep knob is off, else up to K
        per group (a signature change flushes early — heterogeneous
        shapes form per-signature blocks). Runs on the stager thread when
        staging is enabled, so pad+cast host work overlaps compute."""
        pending: list = []
        sig = None
        for ds in iterator:
            t0 = time.perf_counter()
            padded = self._prepare(ds, is_graph)
            _M_SHARD_SECONDS.inc(time.perf_counter() - t0)
            if k < 2:
                yield [padded]
                continue
            s = batch_signature(padded)
            if pending and s != sig:
                yield pending
                pending = []
            sig = s
            pending.append(padded)
            if len(pending) >= k:
                yield pending
                pending = []
        if pending:
            yield pending

    def _stage_group(self, group, is_graph: bool):
        """Shard one padded group over the mesh: a singleton becomes a
        batch-sharded DataSet/MultiDataSet, K batches a `[K, B, ...]`
        superbatch sharded on the batch axis. The DeviceStager's
        `stage_fn` — per-shard puts issue on the stager thread, ahead of
        dispatch."""
        t0 = time.perf_counter()
        if len(group) == 1:
            sharded = self._shard_batch(group[0], is_graph)
        else:
            sharded = self._stack_shard(group, is_graph)
        _M_SHARD_SECONDS.inc(time.perf_counter() - t0)
        return sharded

    def fit(self, iterator):
        """One pass over the iterator, each batch sharded across the mesh.

        Accepts the same inputs as the wrapped engine's `fit`: DataSet /
        iterator of DataSets for `MultiLayerNetwork`, plus MultiDataSet for
        `ComputationGraph` (the reference ParallelWrapper supports both,
        `ParallelWrapper.java:322` and the MDS variant `:151`).

        When the engine's `superstep_k` knob is active, consecutive
        same-signature padded batches are stacked into `[K, B, ...]`
        superbatches sharded on the BATCH axis (dim 1), so sharded training
        amortizes dispatch the same way local training does (PERF.md §13);
        the engine gate (`_superstep_k`) also covers the stats-listener /
        tBPTT / solver fallbacks here.

        Multi-batch epochs pad/cast/shard on a background `DeviceStager`
        (`prefetch_buffer` deep — the reference knob, now real), so the
        next sharded batch crosses the link while the current dispatch
        runs; single-batch fits (the elastic per-step path) shard
        synchronously, as does `DL4J_TPU_STAGING=0`."""
        net = self.net
        is_graph = type(net).__name__ == "ComputationGraph"
        maybe_reset(iterator)
        single = isinstance(iterator, (DataSet, MultiDataSet)) or (
            isinstance(iterator, (list, tuple)) and len(iterator) <= 1)
        if isinstance(iterator, (DataSet, MultiDataSet)):
            iterator = [iterator]
        k = net._superstep_k() if hasattr(net, "_superstep_k") else 0
        groups = self._grouped(iterator, k, is_graph)

        def stage(group):
            return self._stage_group(group, is_graph)

        if single or not _staging.staging_enabled():
            src = map(stage, groups)
        else:
            src = _staging.DeviceStager(
                groups, stage_fn=stage, net=net, engine="parallel",
                depth=self.prefetch_buffer)
        input_wait = ("graph" if is_graph else "mln") + ".input_wait"
        try:
            while True:
                t_wait = time.perf_counter()
                with _obs.tracer.span(input_wait, cat="train"):
                    try:
                        sharded = next(src)
                    except StopIteration:
                        break
                wait = time.perf_counter() - t_wait
                _M_INPUT_WAIT.observe(wait)
                # K batches feed one stacked dispatch: the flight record's
                # input_wait is the wait behind that dispatch.
                net._last_input_wait = wait
                _M_BATCHES.inc(int(getattr(sharded, "k", 1)))
                with _obs.tracer.span("parallel.batch", cat="parallel",
                                      devices=self.n_devices,
                                      data_axis=self.data_axis,
                                      k=int(getattr(sharded, "k", 1))):
                    with parallel_context(getattr(self, "context", None)):
                        net._fit_dispatch(sharded)
        finally:
            _staging.close_stager(src)
        return net

    def evaluate(self, iterator, top_n: int = 1):
        """Mesh-sharded evaluation (reference: the Spark module's
        distributed `evaluate`); see `parallel/evaluation.py`."""
        from deeplearning4j_tpu.parallel.evaluation import sharded_evaluate

        return sharded_evaluate(self.net, iterator, mesh=self.mesh,
                                top_n=top_n)

    def warmup(self, data=None, kinds=None, background: bool = False,
               batch_size: int = 32):
        """Pre-compile the SHARDED programs `fit()` will dispatch: the
        example batch (synthetic when `data` is None) is padded and
        device_put over this wrapper's mesh exactly like a training batch,
        so the warmed programs carry the right input shardings and mesh
        context. When the superstep knob is active the `[K, B, ...]`
        superbatch program is warmed too. See
        `compilation.warmup.warmup_net` for the return contract."""
        from deeplearning4j_tpu.compilation import warmup as warmup_mod

        net = self.net
        is_graph = type(net).__name__ == "ComputationGraph"
        if data is None:
            data = warmup_mod.synthetic_dataset(net, batch_size)
        if isinstance(data, (DataSet, MultiDataSet)):
            data = [data]
        k = net._superstep_k() if hasattr(net, "_superstep_k") else 0
        items = []
        for ds in data:
            padded = self._prepare(ds, is_graph)
            items.append(self._shard_batch(padded, is_graph))
            has_labels = (padded.labels is not None)
            if k > 1 and kinds is None and has_labels:
                items.append(self._stack_shard([padded] * k, is_graph))
        return warmup_mod.warmup_net(net, items, kinds=kinds,
                                     background=background,
                                     batch_size=batch_size,
                                     context=self.context)

    def push_host_state(self, params_tree=None, opt_state=None, state=None):
        """Install host-side trees (numpy / jnp leaves) into the wrapped
        net and re-apply THIS wrapper's placement rules — the write-back
        half of host-mediated parameter averaging (`parallel/elastic.py`
        averages over the coordinator, then pushes the mean back through
        the same `shard_params` rules the constructor applied, so the
        next dispatch sees correctly-placed params, not host arrays).
        Only the trees passed are replaced; `None` leaves the net's
        current tree untouched."""
        net = self.net
        if params_tree is not None:
            net.params_tree = params_tree
        if opt_state is not None:
            net.opt_state = opt_state
        if state is not None:
            net.state = state
        ctx = getattr(self, "context", None)
        mesh_mod.shard_params(
            net, self.mesh,
            model_axis=None if ctx is None else ctx.model_axis,
            expert_axis=None if ctx is None else ctx.expert_axis)
        return net

    # ------------------------------------------------------- checkpointing

    def checkpoint_manager(self, directory: str, **kwargs):
        """A `CheckpointManager` bound to THIS wrapper's mesh and axis
        roles: saves shard per-device over the mesh, restores elastically
        onto it — including a checkpoint written by a different mesh shape
        (the elastic-resume path: save on 8 chips, resume on 4, or on CPU).
        """
        from deeplearning4j_tpu.checkpoint import CheckpointManager

        return CheckpointManager(directory, context=self.context, **kwargs)

    def save_checkpoint(self, directory: str, step=None) -> str:
        """Committed sharded checkpoint of the wrapped net (synchronous;
        use `checkpoint_manager()` for async saves + retention)."""
        return self.checkpoint_manager(directory, keep_last=0,
                                       async_save=False).save(self.net, step)

    def restore_checkpoint(self, directory: str, step=None):
        """Restore the latest (or named) committed step INTO the wrapped
        net, placed per this wrapper's mesh, whatever shape saved it."""
        ctx = self.context
        net = self.checkpoint_manager(directory).restore(step=step,
                                                         net=self.net)
        if ctx.expert_axis is not None:
            # The elastic restore places per param_shardings (replicated /
            # model-sharded); MoE expert tables additionally shard over the
            # expert axis — re-apply the full placement rules.
            mesh_mod.shard_params(net, self.mesh, model_axis=ctx.model_axis,
                                  expert_axis=ctx.expert_axis)
        self.net = net
        return net


def _pad_rows(a, pad: int, fill_last: bool = True):
    """Append `pad` rows: copies of the last row (features/labels — keeps
    values finite and typical) or zeros (masks — padded rows masked out)."""
    if a is None:
        return None
    a = np.asarray(a)
    tail = np.repeat(a[-1:], pad, axis=0) if fill_last else np.zeros(
        (pad,) + a.shape[1:], a.dtype)
    return np.concatenate([a, tail], axis=0)


def _full_labels_mask(labels: np.ndarray, lmask, sequence: bool = False):
    """An explicit all-ones labels mask matching the labels' batch/time shape
    (so the pad can zero the appended rows). `sequence` disambiguates 2-D
    integer labels: per-timestep [b, t] ids need a [b, t] mask, while
    integer-dtype one-hot [b, c] needs the per-example [b] mask — the
    label array alone can't tell them apart, so the caller decides from
    the net's output-layer type."""
    if lmask is not None:
        return np.asarray(lmask)
    if (labels.ndim == 2 and sequence
            and np.issubdtype(labels.dtype, np.integer)):
        shape = labels.shape  # sparse [b, t] class ids: per-timestep mask
    else:
        shape = (labels.shape[0],) if labels.ndim == 2 else labels.shape[:2]
    return np.ones(shape, np.float32)
