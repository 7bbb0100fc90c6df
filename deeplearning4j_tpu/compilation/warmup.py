"""Warmup API + CLI (tentpole layer 3): pre-compile before traffic.

`warmup_net(net, data)` builds the exact argument tuples the engines'
dispatch paths pass (`_fit_one` / `output` / `score` / `_fit_superstep`)
and warms each program through `CachedProgram.warm` — AOT-store hit, or
live compile + write-back — WITHOUT executing anything: parameters,
optimizer state, RNG stream, and iteration counters are untouched.
`MultiLayerNetwork.warmup` / `ComputationGraph.warmup` /
`ParallelWrapper.warmup` delegate here; `background=True` runs it on a
daemon thread so compilation overlaps data loading.

The CLI pre-populates the compile cache for deploy pipelines::

    JAX_COMPILATION_CACHE_DIR=DIR \
    python -m deeplearning4j_tpu.compilation.warmup <checkpoint> \
        [--batch-size N] [--shape H,W,C] [--kinds output,train_step]

It loads the checkpoint (sharded dir / manager root / legacy ZIP —
`checkpoint.load_any`), synthesizes a batch from the model's declared
input type, and warms the requested programs; a later process started
with the same ``JAX_COMPILATION_CACHE_DIR`` (or, with it unset, from the
same checkout) starts with zero cold compiles for those programs. The
directory is named in the environment, not by a flag: JAX reads it as it
is imported, which is before this module's `main` runs.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

DEFAULT_KINDS = ("train_step", "output", "score")


def infer_feature_shape(net) -> Optional[Tuple[int, ...]]:
    """Per-example feature shape from the model's declared input type
    (`set_input_type`), or from the first layer's `n_in` as a fallback.
    None when the model doesn't declare enough (multi-input graphs without
    input types) — callers must then provide an example batch."""
    conf = getattr(net, "conf", None)
    itypes: List[Any] = []
    if conf is not None:
        single = getattr(conf, "input_type", None)
        if single is not None:
            itypes = [single]
        else:
            named = getattr(conf, "input_types", None) or {}
            inputs = getattr(conf, "network_inputs", list(named))
            if named and len(inputs) == 1 and inputs[0] in named:
                itypes = [named[inputs[0]]]
    if itypes:
        t = itypes[0]
        if t.kind == "cnn":
            return (t.height, t.width, t.channels)
        if t.kind in ("ff", "cnnflat"):
            return (t.flat_size(),)
        if t.kind == "rnn":
            return (t.timeseries_length or 8, t.size)
    layers = getattr(net, "layers", None)
    if layers:
        n_in = getattr(layers[0], "n_in", None)
        if n_in:
            return (int(n_in),)
    return None


def _label_shape(net, batch: int) -> Optional[Tuple[int, ...]]:
    """Synthetic one-hot label shape from the net's last layer `n_out`."""
    layers = getattr(net, "layers", None)
    if layers:
        n_out = getattr(layers[-1], "n_out", None)
        if n_out:
            if type(layers[-1]).__name__ == "RnnOutputLayer":
                shape = infer_feature_shape(net)
                t = shape[0] if shape and len(shape) == 2 else 8
                return (batch, t, int(n_out))
            return (batch, int(n_out))
    return None


def synthetic_dataset(net, batch_size: int,
                      shape: Optional[Sequence[int]] = None,
                      dtype=np.float32):
    """A zeros DataSet matching the model's declared input (and, when the
    output layer declares `n_out`, labels) — enough to warm every default
    program kind. `dtype` must match what live traffic will send: an
    int32-ids model warmed with float32 features is a DIFFERENT compiled
    program, and the warmup buys nothing."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    fshape = tuple(shape) if shape else infer_feature_shape(net)
    if fshape is None:
        raise ValueError(
            "cannot infer the model's input shape (no set_input_type on "
            "the config and no first-layer n_in); pass an example batch "
            "or an explicit shape")
    x = np.zeros((batch_size,) + fshape, dtype)
    lshape = _label_shape(net, batch_size)
    y = None if lshape is None else np.zeros(lshape, np.float32)
    return DataSet(x, y)


def warmup_buckets(net, batch_sizes: Sequence[int],
                   shape: Optional[Sequence[int]] = None,
                   dtype=np.float32,
                   param_variants: Optional[Sequence[Any]] = None
                   ) -> Dict[int, Dict[str, Any]]:
    """Bucket-ladder warmup for the serving tier: warm the inference
    program (`output`, train=False — the exact static signature
    `net.output` dispatches) at EVERY padded batch-size bucket, so no
    admitted request shape ever triggers an XLA compile. Features-only —
    parameters, optimizer state and RNG are untouched.

    `param_variants`: substitute params trees (adapter-merged serving
    trees — `nn/lora.py`) to warm IN ADDITION to the net's own at every
    bucket. A merged tree carries `__lora_*` leaves, a different jit
    signature than the bare base, so per-adapter dispatch only stays
    compile-free after warming a variant-shaped program per bucket.
    Returns `{bucket: warmup summary}`."""
    from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet

    fshape = tuple(shape) if shape else infer_feature_shape(net)
    if fshape is None:
        raise ValueError(
            "cannot infer the model's input shape for bucket warmup; pass "
            "shape=(...)")
    is_graph = type(net).__name__ == "ComputationGraph"
    out: Dict[int, Dict[str, Any]] = {}
    for b in sorted({int(b) for b in batch_sizes}):
        x = np.zeros((b,) + fshape, dtype)
        ds = (MultiDataSet(features=[x], labels=None) if is_graph
              else DataSet(x, None))
        out[b] = warmup_net(net, ds, kinds=("output",),
                            param_variants=param_variants)
    return out


def summarize_bucket_warmup(out: Dict[int, Dict[str, Any]]
                            ) -> Dict[str, Any]:
    """Collapse a `warmup_buckets` result into the rollout ledger the
    serving fleet records per drained-replica warm: how many buckets were
    driven, how many programs actually COMPILED (vs landed from the AOT
    store — the number that must be zero once the compile cache is hot),
    and the wall seconds the drain window spent warming."""
    buckets = sorted(out)
    return {
        "buckets": len(buckets),
        "compiled": sum(int(s.get("compiled", 0)) for s in out.values()),
        "aot": sum(int(s.get("aot", 0)) for s in out.values()),
        "seconds": round(sum(float(s.get("seconds", 0.0))
                             for s in out.values()), 4),
    }


# ----------------------------------------------------------- program args


def _clock_like(net):
    """Same avals as `net._device_clock()` — a float32 scalar step counter
    and a PRNGKey — without touching the net's live clock."""
    import jax
    import jax.numpy as jnp

    return (jnp.asarray(np.float32(0.0)), jax.random.PRNGKey(0))


def _mln_args(net, ds, kind: str):
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(ds.features)
    y = None if ds.labels is None else jnp.asarray(ds.labels)
    fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
    lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
    if kind in ("train_step", "train_step_stats"):
        return (net.params_tree, net.state, net.opt_state, x, y, fm, lm,
                _clock_like(net))
    if kind == "output":
        return (net.params_tree, net.state, x, fm, jax.random.PRNGKey(0))
    if kind == "score":
        return (net.params_tree, net.state, x, y, fm, lm)
    raise ValueError(f"unsupported warmup kind {kind!r}")


def _graph_args(net, mds, kind: str):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.graph import _as_mask_list

    xs = [jnp.asarray(f) for f in mds.features]
    ys = None if mds.labels is None else [jnp.asarray(l) for l in mds.labels]
    fms = _as_mask_list(mds.features_masks)
    lms = _as_mask_list(mds.labels_masks)
    if kind in ("train_step", "train_step_stats"):
        return (net.params_tree, net.state, net.opt_state, xs, ys, fms, lms,
                _clock_like(net))
    if kind == "output":
        return (net.params_tree, net.state, xs, None, jax.random.PRNGKey(0))
    if kind == "score":
        return (net.params_tree, net.state, xs, ys, fms, lms)
    raise ValueError(f"unsupported warmup kind {kind!r}")


def _superstep_args(net, item, is_graph: bool):
    """[K, B, ...] superstep arguments: from a prepared Superbatch /
    MultiSuperbatch (ParallelWrapper path) or by stacking a plain batch K
    times (local path)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.graph import _as_mask_list

    if is_graph:
        return (net.params_tree, net.state, net.opt_state,
                [jnp.asarray(f) for f in item.features],
                [jnp.asarray(l) for l in item.labels],
                _as_mask_list(item.features_masks),
                _as_mask_list(item.labels_masks),
                _clock_like(net))
    return (net.params_tree, net.state, net.opt_state,
            jnp.asarray(item.features), jnp.asarray(item.labels),
            None if item.features_mask is None
            else jnp.asarray(item.features_mask),
            None if item.labels_mask is None
            else jnp.asarray(item.labels_mask),
            _clock_like(net))


def _stack_superbatch(ds, k: int, is_graph: bool):
    from deeplearning4j_tpu.datasets.iterators import (
        MultiSuperbatch, Superbatch)

    def stack(a):
        return None if a is None else np.stack([np.asarray(a)] * k)

    if is_graph:
        return MultiSuperbatch(
            [stack(f) for f in ds.features],
            [stack(l) for l in ds.labels],
            None if ds.features_masks is None
            else [stack(m) for m in ds.features_masks],
            None if ds.labels_masks is None
            else [stack(m) for m in ds.labels_masks],
            k=k)
    return Superbatch(stack(ds.features), stack(ds.labels),
                      stack(ds.features_mask), stack(ds.labels_mask), k=k)


# ---------------------------------------------------------------- warmup


def warmup_net(net, data=None, kinds: Optional[Sequence[str]] = None,
               background: bool = False, batch_size: int = 32,
               context=None, param_variants: Optional[Sequence[Any]] = None):
    """Pre-compile `net`'s programs for the given example batch(es).

    `data`: a DataSet / MultiDataSet / `(features, labels)` tuple, a list
    of them (one per expected batch signature), or None to synthesize a
    batch from the model's declared input type. `kinds` defaults to
    train_step + output + score (+ train_superstep when the superstep knob
    is active); labels-free items warm only `output`.

    `param_variants`: extra params trees to warm the inference program
    with (args[0] substituted) — adapter-merged serving trees have their
    own jit signature, and the synthetic-dataset path would otherwise
    only ever warm the net's bare base tree.

    Returns a summary dict ``{"programs", "aot", "compiled", "ready",
    "jit", "seconds"}`` — or, with `background=True`, the started daemon
    thread (its ``.warmup_result`` attribute carries the summary when
    done; compile errors land in ``.warmup_error`` instead of raising on
    the caller's thread).
    """
    from deeplearning4j_tpu.parallel.context import (
        current_context, parallel_context)

    ctx = context if context is not None else current_context()
    items = _normalize_items(net, data, batch_size)

    if background:
        thread = threading.Thread(
            target=_warmup_worker,
            args=(net, items, kinds, ctx, param_variants),
            name="dl4j-warmup", daemon=True)
        thread.warmup_result = None
        thread.warmup_error = None
        thread.start()
        return thread
    with parallel_context(ctx):
        return _warmup_items(net, items, kinds, param_variants)


def _warmup_worker(net, items, kinds, ctx, param_variants=None):
    from deeplearning4j_tpu.parallel.context import parallel_context

    thread = threading.current_thread()
    try:
        with parallel_context(ctx):
            thread.warmup_result = _warmup_items(net, items, kinds,
                                                 param_variants)
    except Exception as e:  # surfaced via the thread object, not the log
        thread.warmup_error = e


def _normalize_items(net, data, batch_size: int) -> List[Any]:
    from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
    from deeplearning4j_tpu.datasets.iterators import (
        MultiSuperbatch, Superbatch)

    if data is None:
        return [synthetic_dataset(net, batch_size)]
    if isinstance(data, (DataSet, MultiDataSet, Superbatch,
                         MultiSuperbatch)):
        return [data]
    if isinstance(data, tuple) and len(data) == 2:
        return [DataSet(np.asarray(data[0]),
                        None if data[1] is None else np.asarray(data[1]))]
    if isinstance(data, np.ndarray):
        return [DataSet(data, None)]
    return [_normalize_items(net, item, batch_size)[0] for item in data]


def _warmup_items(net, items, kinds, param_variants=None) -> Dict[str, Any]:
    from deeplearning4j_tpu.datasets.iterators import (
        MultiSuperbatch, Superbatch)
    from deeplearning4j_tpu.nn import superstep as _superstep

    if not getattr(net, "_initialized", False):
        net.init()
    is_graph = type(net).__name__ == "ComputationGraph"
    k = net._superstep_k() if hasattr(net, "_superstep_k") else 0
    t0 = time.perf_counter()
    counts = {"programs": 0, "aot": 0, "compiled": 0, "ready": 0, "jit": 0}

    def warm(kind, static, args):
        prog = net._get_jit(kind, **static)
        if hasattr(prog, "warm"):
            status = prog.warm(*args)
        else:
            # Store disabled: lower+compile anyway so the backend compile
            # lands in the persistent XLA cache (the first real call still
            # re-traces, but its backend compile becomes a disk read).
            prog.lower(*args).compile()
            status = "jit"
        counts["programs"] += 1
        counts[status] = counts.get(status, 0) + 1

    from deeplearning4j_tpu.datasets.staging import transfer_cast

    tdt = getattr(getattr(net, "dtype_policy", None), "transfer_dtype", None)
    for item in items:
        if isinstance(item, (Superbatch, MultiSuperbatch)):
            warm("train_superstep",
                 {"k": int(item.k), "scan": _superstep.use_scan()},
                 _superstep_args(net, item, is_graph))
            continue
        # Live batches reach dispatch through the staging tier, which
        # ships them in the policy's transfer dtype — warm the program
        # for THAT signature or the warmup compiles the wrong one.
        item = transfer_cast(item, tdt)
        has_labels = (item.labels is not None)
        item_kinds = list(kinds) if kinds is not None else [
            kd for kd in DEFAULT_KINDS if has_labels or kd == "output"]
        make = _graph_args if is_graph else _mln_args
        for kind in item_kinds:
            # Match the live dispatch's static args exactly — `output` is
            # always requested with train=False (`net.output` passes it),
            # and a static mismatch is a different cached program.
            static = {"train": False} if kind == "output" else {}
            args = make(net, item, kind)
            warm(kind, static, args)
            if kind == "output":
                for variant in (param_variants or ()):
                    warm(kind, static, (variant,) + args[1:])
        if k > 1 and kinds is None and has_labels:
            sb = _stack_superbatch(item, k, is_graph)
            warm("train_superstep", {"k": k, "scan": _superstep.use_scan()},
                 _superstep_args(net, sb, is_graph))
    counts["seconds"] = round(time.perf_counter() - t0, 3)
    return counts


# ------------------------------------------------------------------- CLI


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    import json
    import os

    from deeplearning4j_tpu.compilation import cache as _cache

    parser = argparse.ArgumentParser(
        prog="python -m deeplearning4j_tpu.compilation.warmup",
        description=("Pre-populate the compile cache for a checkpointed "
                     "model (see module docstring)."))
    parser.add_argument("checkpoint",
                        help="sharded checkpoint dir / manager root / "
                             "legacy model ZIP")
    parser.add_argument("--batch-size", type=int, default=32,
                        help="synthetic batch size (match serving "
                             "max_batch_size for zero-compile serving)")
    parser.add_argument("--shape", default=None,
                        help="per-example feature shape, comma-separated "
                             "(default: inferred from the model config)")
    parser.add_argument("--kinds", default=None,
                        help="comma list of program kinds (default: "
                             "train_step,output,score)")
    args = parser.parse_args(argv)

    root = _cache.configure_persistent_cache()
    if root is None:
        parser.error(f"the compile cache is disabled (${_cache.ENV_KNOB}"
                     f"={os.environ.get(_cache.ENV_KNOB)!r}); warmup "
                     "would have nowhere to write")

    from deeplearning4j_tpu.checkpoint import load_any

    net = load_any(args.checkpoint)
    shape = (tuple(int(s) for s in args.shape.split(","))
             if args.shape else None)
    ds = synthetic_dataset(net, args.batch_size, shape=shape)
    kinds = args.kinds.split(",") if args.kinds else None
    summary = warmup_net(net, ds, kinds=kinds)
    summary["cache_dir"] = root
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
