"""Shape-bucket request batching for the predict path.

The PR 5 batcher padded every coalesced batch to ONE `max_batch_size`
shape; at real traffic that wastes the MXU on mostly-padding batches. The
bucket batcher pads to the smallest bucket in a ladder (powers of two up
to `max_batch_size` by default), and the serving warmup drives EVERY
bucket through the `compilation/` AOT store at startup — mixed-size
traffic then never compiles (`dl4j_xla_compiles_total` stays flat).

Admission is bounded: the queue has a hard depth, `submit` raises
`ServerOverloadedError` (-> 503 + `Retry-After`) instead of buffering
without bound, and every `_Pending` carries a deadline plus a `cancelled`
flag so a request whose caller gave up is DROPPED at batch-build time
instead of burning device time (counted under
`dl4j_requests_total{outcome="timeout"}`).

Input dtype policy (the float32-mangles-token-ids fix): the expected
feature dtype is resolved from the model's declared structure — the same
policy source as `nn/conf/preprocessors.py` (`_uint8_policy` /
`_uint8_policies` on the engines) — ids models get int32 features and a
400 on fractional floats, value models get float32 and a 400 on
non-numeric payloads.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu import observability as _obs
from deeplearning4j_tpu.observability import propagate as _prop
from deeplearning4j_tpu.serving import metrics as _m
from deeplearning4j_tpu.serving.errors import (
    InputValidationError,
    ServerOverloadedError,
)


def bucket_ladder(max_batch_size: int,
                  buckets: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
    """The padded batch-size ladder: explicit `buckets` (capped/extended to
    include `max_batch_size`), or powers of two up to it."""
    if buckets:
        ladder = sorted({int(b) for b in buckets if 0 < int(b)})
        if not ladder:
            raise ValueError("batch_buckets must contain a positive size")
        return tuple(b for b in ladder if b < max_batch_size) + (
            int(max_batch_size),)
    out, b = [], 1
    while b < max_batch_size:
        out.append(b)
        b *= 2
    out.append(int(max_batch_size))
    return tuple(out)


# ------------------------------------------------------------ input dtype


def expected_input_kind(net) -> str:
    """'ids' when the model's declared structure consumes integer token
    ids (ids-format EmbeddingLayer first layer / single-input consumer —
    the `nn/conf/preprocessors.py` policy), else 'values'."""
    from deeplearning4j_tpu.nn.conf import preprocessors as _pre

    try:
        policy = getattr(net, "_uint8_policy", None)
        if policy is None:
            policies = getattr(net, "_uint8_policies", None)
            if policies and len(policies) == 1:
                policy = next(iter(policies.values()))
    except Exception:
        policy = None
    return "ids" if policy == _pre.UINT8_IDS else "values"


def canonicalize_features(net, data) -> np.ndarray:
    """Stage one request's features for batching, or raise
    `InputValidationError` (-> 400). Ids models keep integer precision
    (int32, never a float round-trip) and 2-D token grids gain the
    trailing index axis the ids EmbeddingLayer expects."""
    try:
        arr = np.asarray(data)
    except Exception as e:
        raise InputValidationError(f"features are not array-like: {e}")
    if arr.dtype.kind not in "fiub":
        raise InputValidationError(
            f"features must be numeric, got dtype {arr.dtype}")
    if arr.ndim == 0:
        raise InputValidationError("features must be a batch of examples")
    if expected_input_kind(net) == "ids":
        if arr.dtype.kind == "f":
            if not np.all(np.isfinite(arr)) or np.any(np.mod(arr, 1) != 0):
                raise InputValidationError(
                    "this model consumes integer token ids; got fractional "
                    "or non-finite floats")
        arr = arr.astype(np.int32)
        if arr.ndim == 2:
            arr = arr[..., None]  # [b, t] -> [b, t, 1] index layout
        return arr
    return np.ascontiguousarray(arr, np.float32)


def serving_feature_spec(net, warmup_shape=None):
    """(per-example shape, dtype) the batcher pads and warms with. An
    explicit `warmup_shape` is trusted; otherwise the declared input type
    decides, with ids models switching the feature axis to the [t, 1]
    token-index layout and int32."""
    from deeplearning4j_tpu.compilation.warmup import infer_feature_shape

    kind = expected_input_kind(net)
    dtype = np.int32 if kind == "ids" else np.float32
    if warmup_shape is not None:
        return tuple(warmup_shape), dtype
    shape = infer_feature_shape(net)
    if shape is not None and kind == "ids" and len(shape) == 2:
        shape = (shape[0], 1)
    return shape, dtype


# ---------------------------------------------------------------- batcher


class _Pending:
    __slots__ = ("array", "event", "result", "error", "deadline",
                 "cancelled", "ctx", "t_submit_ns", "adapter", "params",
                 "ledger_rec")

    def __init__(self, array: np.ndarray,
                 deadline: Optional[float] = None,
                 adapter: Optional[str] = None, params=None,
                 ledger_rec=None):
        self.array = array
        # Multi-tenant serving: the adapter name is part of the batch
        # grouping key (rows dispatched through different param trees
        # can't share one forward), `params` the merged tree to dispatch
        # with (None = the model's own base params).
        self.adapter = adapter
        self.params = params
        self.event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[str] = None
        self.deadline = deadline          # time.monotonic() instant or None
        self.cancelled = False            # set by an abandoning caller
        # Trace context rides the queue item: the batch loop runs on its
        # own thread, where the submitter's thread-local binding is gone.
        self.ctx = _prop.current()
        self.t_submit_ns = time.perf_counter_ns()
        # The request's accounting record (observability/ledger.py): the
        # batch loop credits it queue-wait and its row-share of each
        # dispatch's wall time; the SERVER owns open/close.
        self.ledger_rec = ledger_rec


class ShapeBucketBatcher:
    """One model's predict-path batcher: bounded admission queue, delay-
    window coalescing, bucket-padded dispatch. Lifecycle: `start()` spawns
    the daemon loop, `submit()` enqueues (or sheds), `stop()` drains."""

    def __init__(self, net, model_name: str = "default",
                 max_batch_size: int = 32,
                 buckets: Optional[Sequence[int]] = None,
                 max_delay_s: float = 0.005,
                 queue_depth: int = 256,
                 warmup_shape=None, context=None):
        self.net = net
        # Tensor-parallel serving: the ParallelContext the host sharded
        # `net` under. Warm-up and every forward trace inside it, like the
        # decode stepper's dispatches: the jit-cache key and the kernel
        # registry then see the mesh the program is partitioned over
        # (outside it the registry resolves Pallas bodies the TPU compiler
        # refuses in a partitioned program).
        self.context = context
        self.model_name = model_name
        self.buckets = bucket_ladder(max_batch_size, buckets)
        self.max_batch_size = self.buckets[-1]
        self.max_delay_s = float(max_delay_s)
        self.warmup_shape = warmup_shape
        self._queue: "queue.Queue[Optional[_Pending]]" = queue.Queue(
            maxsize=int(queue_depth))
        self._thread: Optional[threading.Thread] = None
        # Multi-tenant hook (serving/server.py): a callable returning the
        # adapter-merged param trees to warm alongside the base — the
        # merged trees carry `__lora_*` leaves, which is a DIFFERENT jit
        # signature than the bare base tree, so an unwarmed adapter path
        # would compile on the first adapter request.
        self.param_variants = None
        _m.MODEL_QUEUE_DEPTH.labels(
            model=model_name, route="predict").set_function(self._queue.qsize)
        self._dispatch_seconds = _m.DISPATCH_SECONDS.labels(
            model=model_name, phase="forward")

    # ------------------------------------------------------------ control

    def start(self) -> "ShapeBucketBatcher":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._batch_loop,
                name=f"dl4j-batcher-{self.model_name}", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        t = self._thread
        if t is not None:
            self._thread = None
            try:
                self._queue.put_nowait(None)
            except queue.Full:
                pass  # the loop sheds the backlog and exits on the sentinel
            # Bounded join: a worker left mid-dispatch at interpreter
            # shutdown dies inside native code (SIGABRT, not a clean exit).
            t.join(timeout=10.0)

    def qsize(self) -> int:
        return self._queue.qsize()

    # ---------------------------------------------------------- admission

    def submit(self, arr: np.ndarray,
               deadline: Optional[float] = None,
               adapter: Optional[str] = None, params=None,
               ledger_rec=None) -> _Pending:
        """Enqueue one request's rows; sheds (503 + Retry-After) when the
        bounded queue is full instead of growing it."""
        p = _Pending(arr, deadline, adapter=adapter, params=params,
                     ledger_rec=ledger_rec)
        try:
            self._queue.put_nowait(p)
        except queue.Full:
            raise ServerOverloadedError(
                f"model {self.model_name!r} admission queue is full "
                f"({self._queue.maxsize} requests); retry later")
        return p

    # ------------------------------------------------------------- warmup

    def warm(self) -> None:
        """Pre-compile every bucket through the AOT store. Engines warm
        via `warmup_buckets` (no execution); bare objects that only expose
        `output` fall back to one executed max-bucket batch — the PR 5
        behavior."""
        from deeplearning4j_tpu.compilation.warmup import warmup_buckets

        shape, dtype = serving_feature_spec(self.net, self.warmup_shape)
        if shape is None:
            raise ValueError(
                "cannot infer the model's input shape; pass "
                "warmup_shape=(...) to InferenceServer")
        variants = (self.param_variants() if callable(self.param_variants)
                    else self.param_variants)
        if hasattr(self.net, "_get_jit"):
            with self._in_context():
                warmup_buckets(self.net, self.buckets, shape=shape,
                               dtype=dtype, param_variants=variants)
        else:
            x = np.zeros((self.max_batch_size,) + tuple(shape), dtype)
            np.asarray(self._forward(x))

    # ------------------------------------------------------------ batching

    def _in_context(self):
        from deeplearning4j_tpu.parallel.context import context_if_any

        return context_if_any(self.context)

    def _forward(self, x: np.ndarray, params=None) -> np.ndarray:
        with self._in_context():
            out = (self.net.output(x, params=params) if params is not None
                   else self.net.output(x))
        if isinstance(out, list):  # ComputationGraph returns [out, ...]
            out = out[0]
        return np.asarray(out)

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _run_batch(self, pending: List[_Pending]) -> None:
        now = time.monotonic()
        live: List[_Pending] = []
        for p in pending:
            expired = p.deadline is not None and now > p.deadline
            if p.cancelled or expired:
                # Dropped BEFORE the device sees it: an abandoned request
                # must not burn a forward pass.
                _m.REQUESTS.labels(model=self.model_name, route="predict",
                                   outcome="timeout").inc()
                if expired and not p.cancelled:
                    p.error = "__deadline__"
                p.event.set()
                continue
            live.append(p)
        # Requests with different per-example shapes can't share one padded
        # batch, and neither can requests dispatching through different
        # adapter trees — run one sub-batch per (shape, adapter) group.
        groups: dict = {}
        for p in live:
            groups.setdefault((p.array.shape[1:], p.adapter), []).append(p)
        for group in groups.values():
            self._run_group(group)

    def _run_group(self, live: List[_Pending]) -> None:
        counts = [p.array.shape[0] for p in live]
        # Traced requests get retroactive queue-wait spans (submit ->
        # batch build) and a per-request device-dispatch span parented to
        # the replica request span — untraced traffic skips all of it.
        traced = [p for p in live if p.ctx is not None]
        now_ns = time.perf_counter_ns()
        for p in traced:
            _obs.tracer.complete(
                "serving.queue_wait", p.t_submit_ns,
                now_ns - p.t_submit_ns, cat="serving",
                parent_ctx=p.ctx, model=self.model_name)
        try:
            x = np.concatenate([p.array for p in live], axis=0)
            n = x.shape[0]
            _m.BATCH_SIZE.observe(n)
            bucket = self._bucket_for(n)
            if n < bucket:
                pad = np.zeros((bucket - n,) + x.shape[1:], x.dtype)
                x = np.concatenate([x, pad], axis=0)
            t_fwd = time.perf_counter_ns()
            with _obs.tracer.span("serving.batch", cat="serving",
                                  model=self.model_name, requests=len(live),
                                  rows=n, padded_to=bucket):
                preds = self._forward(x, params=live[0].params)[:n]
            dur_fwd = time.perf_counter_ns() - t_fwd
            for p in traced:
                _obs.tracer.complete(
                    "serving.device_dispatch", t_fwd, dur_fwd,
                    cat="serving", parent_ctx=p.ctx,
                    model=self.model_name, rows=n, padded_to=bucket)
            # Cost attribution choke point: ONE dispatch's wall time is
            # split across its co-batched requests by real (pre-padding)
            # row share, so tenant device-seconds sum to measured
            # dispatch seconds.
            dispatch_s = dur_fwd / 1e9
            self._dispatch_seconds.inc(dispatch_s)
            for p, c in zip(live, counts):
                rec = p.ledger_rec
                if rec is not None:
                    rec.set_queue_wait((t_fwd - p.t_submit_ns) / 1e9)
                    rec.mark("queue_done")
                    rec.add_device_seconds(dispatch_s * (c / n))
            off = 0
            for p, c in zip(live, counts):
                p.result = preds[off:off + c]
                off += c
        except Exception as e:  # surface the failure to every caller; the
            for p in live:      # loop thread must survive any bad batch
                p.error = f"{type(e).__name__}: {e}"
            _obs.flight.on_crash("serving.batch", e)
        for p in live:
            p.event.set()

    def _batch_loop(self) -> None:
        try:
            self._batch_loop_inner()
        except Exception as e:
            # The loop thread is about to die with requests in flight:
            # capture the flight bundle before the stack unwinds.
            _obs.flight.on_crash("serving.batch_loop", e)
            raise

    def _batch_loop_inner(self) -> None:
        holdover: Optional[_Pending] = None
        while True:
            first = holdover if holdover is not None else self._queue.get()
            holdover = None
            if first is None:
                return
            batch = [first]
            total = first.array.shape[0]
            # Coalesce whatever arrives within the delay window, up to the
            # LARGEST bucket; a request that would overflow it is held for
            # the next batch (bucket shapes are the only compiled shapes).
            end = time.monotonic() + self.max_delay_s
            while total < self.max_batch_size:
                remaining = end - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    item = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                if item is None:
                    self._run_batch(batch)
                    return
                if total + item.array.shape[0] > self.max_batch_size:
                    holdover = item
                    break
                batch.append(item)
                total += item.array.shape[0]
            self._run_batch(batch)
