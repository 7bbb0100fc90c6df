"""Device mesh construction and sharding rules.

TPU-native replacement for the reference's three parameter-averaging
transports (SURVEY.md §2.3/§5): in-process `ParallelWrapper`
(`parallelism/ParallelWrapper.java:322`), Spark `ParameterAveragingTrainingMaster`,
and the Aeron parameter server. Here a single `jax.sharding.Mesh` + sharding
annotations make XLA emit per-step gradient all-reduce over ICI inside the
jitted train step — gradient (not parameter) averaging every step, which
strictly dominates the reference's every-k-iterations averaging.

Axes:
- "data": batch-dim data parallelism (the reference's only parallelism mode);
- "model": tensor parallelism over large weight matrices' output dim
  (no reference equivalent — the TPU-first extension).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import observability as _obs

#: Every ≥`min_shard_size` 2-D leaf the sharding rules left fully
#: replicated. A big matrix silently falling through the divisibility
#: gates (odd head count, misaligned vocab) costs full-copy HBM on every
#: chip — this counter makes that visible on /metrics instead of only in
#: an OOM three layers later. Incremented by `shard_params`; use
#: `describe_shardings` to see WHICH leaves.
M_REPLICATED_LEAVES = _obs.metrics.counter(
    "dl4j_params_replicated_leaves",
    "Large (>=min_shard_size) 2-D param leaves left fully replicated by "
    "param_shardings rules")


def create_mesh(
    shape: Optional[Tuple[int, ...]] = None,
    axis_names: Sequence[str] = ("data",),
    devices=None,
) -> Mesh:
    """Build a mesh over the available devices. Default: 1-D data-parallel
    mesh over all devices."""
    devices = devices if devices is not None else jax.devices()
    if shape is None:
        shape = (len(devices),)
    arr = np.asarray(devices[: int(np.prod(shape))]).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def local_mesh(shape: Optional[Tuple[int, ...]] = None,
               axis_names: Sequence[str] = ("data",)) -> Mesh:
    """Mesh over THIS process's addressable devices only — the elastic
    trainer's per-worker mesh: each surviving worker trains on its local
    slice and synchronizes through the host-side coordinator, so the mesh
    never spans processes and a host loss never invalidates it."""
    return create_mesh(shape, axis_names=axis_names,
                       devices=jax.local_devices())


def data_sharding(mesh: Mesh, ndim: int, axis: str = "data") -> NamedSharding:
    """Shard the leading (batch) axis; replicate the rest."""
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def superbatch_sharding(mesh: Mesh, ndim: int,
                        axis: str = "data") -> NamedSharding:
    """Sharding for a `[K, B, ...]` stacked superstep block: the batch axis
    (dim 1) shards over `axis`, the K step axis and feature dims replicate —
    each scan iteration then sees the same per-device batch split that
    `data_sharding` gives a single dispatched batch."""
    return NamedSharding(mesh, P(None, axis, *([None] * (ndim - 2))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def own_on_device(x):
    """An XLA-owned copy of an already-placed array (sharding preserved).

    `device_put` / `make_array_from_callback` zero-copy suitably-aligned
    host numpy buffers on the CPU backend, so a leaf placed from a
    TRANSIENT numpy array (a checkpoint-restore scratch buffer, the
    elastic averaging result) can end up aliasing memory the host
    allocator reclaims once the numpy object dies. That alias is harmless
    until the train step DONATES the leaf: XLA then reuses the aliased
    allocation in place for the updated parameter, and the live training
    state is sitting in freed host memory — the next unrelated host
    allocation silently stomps the weights. (Observed on CPU CI as
    elastic restore -> fit -> params corrupted some reads later; small
    leaves survived because sub-alignment-threshold arrays are copied,
    not aliased.) An eager on-device copy's output buffer comes from the
    XLA pool, decoupling the leaf from whatever host memory placed it.
    Use at every host->device boundary that feeds donated training state.
    """
    import jax.numpy as jnp

    return jnp.copy(x)


def batch_shardings(mesh: Mesh, tree, axis: str = "data"):
    """Sharding pytree for a batch structure: leading dim on `axis`."""
    return jax.tree_util.tree_map(
        lambda a: data_sharding(mesh, np.ndim(a), axis) if a is not None else None,
        tree,
        is_leaf=lambda a: a is None or hasattr(a, "ndim"),
    )


def _layer_confs(net) -> Dict[str, object]:
    """Param-tree top-level key -> layer conf, for either engine (layer key
    for MultiLayerNetwork, vertex name for ComputationGraph)."""
    return dict(net.named_layers())


#: Layer conf class names whose params stay replicated on purpose: small
#: per-feature vectors (norms) and token tables (embeddings — the decode
#:  path gathers one row per token, so splitting the vocab dim buys an
#: all-gather per step for ~nothing at serving batch sizes).
_REPLICATED_LAYER_TYPES = frozenset({
    "EmbeddingLayer", "BatchNormalization", "LocalResponseNormalization",
    "ActivationLayer", "DropoutLayer",
})


def _layer_param_specs(conf, axis_size: int,
                       model_axis: str) -> Optional[Dict[str, P]]:
    """Megatron-style per-param PartitionSpecs for one layer conf, or None
    when this layer type has no head-aware rule (caller falls back to the
    generic divisibility rule). A returned dict may still map a param to
    P() — that's an INTENTIONAL replication, not a fallback."""
    kind = type(conf).__name__
    if kind == "SelfAttentionLayer":
        # Head-aligned: column-splitting Wq/Wk/Wv's last dim by the axis
        # size keeps whole heads per shard only when n_heads divides, and
        # the attention kernel reshapes to [B, T, H, Dh] — a non-aligned
        # split would slice through a head. Wo is row-parallel (its input
        # is the head-sharded concat); XLA all-reduces the partial sums.
        if getattr(conf, "n_heads", 0) % axis_size:
            return None
        return {
            "Wq": P(None, model_axis), "qB": P(model_axis),
            "Wk": P(None, model_axis),
            "Wv": P(None, model_axis), "vB": P(model_axis),
            "Wo": P(model_axis, None), "oB": P(),
        }
    if kind in _REPLICATED_LAYER_TYPES:
        return {pn: P() for pn in conf.param_shapes()}
    if kind == "DenseLayer":
        n_in = getattr(conf, "n_in", 0)
        n_out = getattr(conf, "n_out", 0)
        if n_out >= n_in and n_out % axis_size == 0:
            # Expanding matmul (an MLP up-projection): column-parallel,
            # bias shards with the output features.
            return {"W": P(None, model_axis), "b": P(model_axis)}
        if n_in % axis_size == 0:
            # Contracting matmul (MLP down-projection): row-parallel over
            # the already-sharded input features; the bias is added after
            # the all-reduce, so it replicates.
            return {"W": P(model_axis, None), "b": P()}
        return None
    return None


def param_shardings(params, mesh: Mesh, model_axis: Optional[str] = None,
                    min_shard_size: int = 2048, net=None):
    """Sharding pytree for params: replicated by default; with `model_axis`,
    2-D weight matrices whose output dim divides the axis size (and is big
    enough to be worth sharding) split along their last dim (Megatron-style
    column parallel — XLA inserts the matching collectives).

    With `net`, the rules become layer-aware: attention QKV/output
    projections partition on heads (column/row-parallel, gated on
    `n_heads % axis_size == 0`), DenseLayer matmuls split column-wise when
    expanding and row-wise when contracting, and embeddings/norms stay
    replicated — the layout PERF.md §28 documents. Layers without a
    specific rule fall back to the generic last-dim divisibility rule."""
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(model_axis, 1)
    by_layer: Dict[str, Dict[str, P]] = {}
    if net is not None and model_axis is not None and axis_size > 1:
        for key, conf in _layer_confs(net).items():
            specs = _layer_param_specs(conf, axis_size, model_axis)
            if specs is not None:
                by_layer[key] = specs

    def generic(a):
        if (
            model_axis is not None
            and axis_size > 1
            and hasattr(a, "ndim")
            and a.ndim >= 2
            and a.shape[-1] % axis_size == 0
            and int(np.prod(a.shape)) >= min_shard_size
        ):
            return NamedSharding(mesh, P(*([None] * (a.ndim - 1)), model_axis))
        return NamedSharding(mesh, P())

    def rule(path, a):
        for i, k in enumerate(path):
            specs = by_layer.get(getattr(k, "key", None))
            if specs is None:
                continue
            # Updater state mirrors the param dict, so the param name is
            # somewhere below the layer key even when slots nest deeper.
            for k2 in path[i + 1:]:
                spec = specs.get(getattr(k2, "key", None))
                if spec is not None:
                    return NamedSharding(mesh, spec)
            break
        return generic(a)

    return jax.tree_util.tree_map_with_path(rule, params)


def describe_shardings(net, mesh: Mesh, model_axis: Optional[str] = None,
                       min_shard_size: int = 2048) -> List[dict]:
    """Per-leaf layout report for `shard_params(net, mesh, ...)` — what
    WOULD be placed where. Each row: ``{path, shape, bytes, spec,
    replicated, large_replicated}``; `large_replicated` marks the leaves
    `dl4j_params_replicated_leaves` counts (≥ min_shard_size elements,
    ndim ≥ 2, fully replicated) — the "is 90% of my HBM secretly on every
    chip" question answered in one call."""
    ps = param_shardings(net.params_tree, mesh, model_axis,
                         min_shard_size=min_shard_size, net=net)
    rows: List[dict] = []
    flat, _ = jax.tree_util.tree_flatten_with_path(net.params_tree)
    flat_s = jax.tree_util.tree_leaves(
        ps, is_leaf=lambda s: isinstance(s, NamedSharding))
    for (path, a), s in zip(flat, flat_s):
        spec = s.spec if isinstance(s, NamedSharding) else P()
        replicated = all(dim is None for dim in spec)
        rows.append({
            "path": jax.tree_util.keystr(path),
            "shape": tuple(getattr(a, "shape", ())),
            "bytes": int(getattr(a, "nbytes", 0)),
            "spec": str(spec),
            "replicated": replicated,
            "large_replicated": bool(
                replicated and getattr(a, "ndim", 0) >= 2
                and int(np.prod(getattr(a, "shape", (0,)))) >= min_shard_size),
        })
    return rows


def axis_sharding(mesh: Mesh, ndim: int, dim: int,
                  axis: Optional[str]) -> NamedSharding:
    """Partition one dimension over `axis`, replicate the rest (the
    single construction seam layer/stepper code goes through — tpulint
    JX020 keeps NamedSharding construction inside parallel/)."""
    spec = [None] * ndim
    if axis is not None:
        spec[dim] = axis
    return NamedSharding(mesh, P(*spec))


def kv_page_sharding(mesh: Mesh, ndim: int,
                     model_axis: Optional[str]) -> NamedSharding:
    """Paged KV storage `[pages, page_size, H, Dh]`: partition the head
    dim (2) over the model axis — the same split the attention QKV
    column-parallel rules give q/k/v, so the paged scatter + decode
    attention run shard-local with zero KV collectives. Page tables,
    refcounts and cursors stay replicated/host-side."""
    return axis_sharding(mesh, ndim, 2, model_axis)


def _moe_layers(net) -> Dict[str, object]:
    """Param-tree keys of MoELayer configs in either engine (layer key for
    MultiLayerNetwork, vertex name for ComputationGraph)."""
    return {key: layer for key, layer in net.named_layers()
            if type(layer).__name__ == "MoELayer"}


def shard_params(net, mesh: Mesh, model_axis: Optional[str] = None,
                 expert_axis: Optional[str] = None, put=None):
    """Place a network's params/opt_state/state on the mesh in-place.

    `put(leaf, sharding)` is the placement primitive: `jax.device_put` by
    default (single-process — all mesh devices addressable); multi-process
    callers pass `parallel/distributed.py`'s global-array builder. One
    routine, one set of sharding rules for both worlds.

    With `expert_axis`, every MoELayer's per-expert tables (leading [E]
    axis) shard over that axis — the expert-parallel placement
    `nn/layers/moe.py`'s sharding constraints then keep through the step."""
    raw_put = jax.device_put if put is None else put

    def put(a, s):
        placed = raw_put(a, s)
        if isinstance(a, np.ndarray):
            # Host-sourced leaf (elastic averaging write-back, host-side
            # restores): the placement may zero-copy the caller's numpy
            # buffer, which the donated train step must never alias — see
            # `own_on_device`. Device-sourced leaves skip the copy (the
            # common ctor path re-places arrays XLA already owns).
            placed = own_on_device(placed)
        return placed

    ps = param_shardings(net.params_tree, mesh, model_axis, net=net)
    for row in describe_shardings(net, mesh, model_axis):
        if row["large_replicated"]:
            M_REPLICATED_LEAVES.inc()
    moe = _moe_layers(net) if expert_axis in mesh.shape else {}
    for lk, layer in moe.items():
        for pn in ("w1", "b_1", "w2", "b_2"):
            a = net.params_tree[lk][pn]
            ps[lk][pn] = NamedSharding(
                mesh, P(expert_axis, *([None] * (a.ndim - 1))))
    net.params_tree = jax.tree_util.tree_map(put, net.params_tree, ps)
    if net.opt_state is not None:
        os_shard = param_shardings(net.opt_state, mesh, model_axis, net=net)
        expert_param_names = {"w1", "b_1", "w2", "b_2"}
        for lk in moe:
            # Updater state mirrors the param dict (tree_map(zeros_like)),
            # so the PATH carries the param name — shard by name, exactly
            # like the params branch above (a shape heuristic would
            # mis-shard gate_w state when n_in == n_experts).
            flat, treedef = jax.tree_util.tree_flatten_with_path(
                net.opt_state[lk])
            flat_s = jax.tree_util.tree_leaves(os_shard[lk])
            new_s = []
            for (path, a), s in zip(flat, flat_s):
                names = {getattr(k, "key", None) for k in path}
                if names & expert_param_names and hasattr(a, "ndim"):
                    s = NamedSharding(
                        mesh, P(expert_axis, *([None] * (a.ndim - 1))))
                new_s.append(s)
            os_shard[lk] = jax.tree_util.tree_unflatten(treedef, new_s)
        net.opt_state = jax.tree_util.tree_map(
            lambda a, s: put(a, s) if hasattr(a, "shape") else a,
            net.opt_state, os_shard)
    if net.state:
        repl = NamedSharding(mesh, P())
        net.state = jax.tree_util.tree_map(lambda a: put(a, repl), net.state)
    return net
