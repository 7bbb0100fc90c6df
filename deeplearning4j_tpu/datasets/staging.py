"""Overlapped host->device staging (ROADMAP open item 3; PERF.md §20).

The transfer layer of the input pipeline: everything that moves a batch
across the host->device link lives here, so the engines' fit loops never
call `device_put` themselves (tpulint JX011 enforces that split).

Two tiers:

* The synchronous primitives (`transfer_cast`, `stage_to_device`,
  `stage_item`) — moved from `datasets/iterators.py`, unchanged in
  behavior. `transfer_cast` applies the DtypePolicy `transfer_dtype`
  cast HOST-side (f32 -> bf16 halves wire bytes) while leaving integer /
  uint8 parts untouched — compact image bytes ship as-is and are scaled
  on device by the engine's uint8 policy, so the wire always carries the
  reduced representation.

* `DeviceStager` — a background thread that pulls from a base iterator,
  applies the cast, and issues non-blocking `device_put`s into a bounded
  in-flight window so the NEXT batch crosses the link while the current
  train step runs. With JAX's async dispatch the consumer thread only
  enqueues device work, so on streaming workloads the link transfer is
  hidden behind compute and `dl4j_input_wait_seconds` collapses to ~0.

Backpressure: the in-flight window is budgeted in BYTES (not batch
count) against `DL4J_TPU_STAGE_BYTES`, defaulting to half the device
headroom left after `observability.memory.measured_model_bytes` (model +
optimizer + largest recorded transient). When the budget is tight the
window SHRINKS — the worker blocks until the consumer retires bytes —
and a single oversized batch is still admitted once the window is empty,
so staging degrades toward the synchronous path instead of erroring.

Donation note (the PR 9 aliasing lesson): train steps donate ONLY params
and opt_state (`donate_argnums` never includes batch arguments), so a
staged batch buffer is read-only to the step and needs no
`mesh.own_on_device` defensive copy. Anything staged here that later
feeds DONATED state (e.g. a checkpoint restore path reusing these
helpers) must copy via `mesh.own_on_device` first.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Callable, Iterable, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu import observability as _obs

# Hot-loop series resolved once at import (observability/metrics.py rule 2).
_M_INFLIGHT = _obs.metrics.gauge(
    "dl4j_staging_inflight_bytes",
    "Bytes admitted to DeviceStager in-flight windows and not yet handed "
    "to a consumer (bounded by the staging byte budget)")
_M_DEPTH = _obs.metrics.gauge(
    "dl4j_staging_depth",
    "Batches currently staged ahead across DeviceStager queues")
_M_STAGE_WAIT = _obs.metrics.histogram(
    "dl4j_staging_wait_seconds",
    "Stager-thread seconds blocked waiting on the base iterator's next "
    "(producer-side stall, the dual of dl4j_input_wait_seconds)")
_M_STAGED_BYTES = _obs.metrics.counter(
    "dl4j_staging_bytes_total",
    "Host bytes shipped to device by background DeviceStager threads "
    "(the overlapped share of host->device traffic)")
_M_PUT_SECONDS = _obs.metrics.counter(
    "dl4j_staging_put_seconds_total",
    "Host seconds spent issuing device_put, split by whether the put ran "
    "on a DeviceStager thread (overlapped with compute) or on the caller "
    "thread (synchronous)",
    label_names=("mode",))
_M_PUT_OVERLAPPED = _M_PUT_SECONDS.labels(mode="overlapped")
_M_PUT_SYNC = _M_PUT_SECONDS.labels(mode="synchronous")

# Families shared with the engines/iterators: re-registration returns the
# existing family (kind+labels must match), children are cached per label.
_H2D_FAMILY = _obs.metrics.counter(
    "dl4j_host_to_device_bytes_total",
    "Host-resident bytes staged to device with training batches",
    label_names=("engine",))
_WAIT_FAMILY = _obs.metrics.histogram(
    "dl4j_input_wait_seconds",
    "Host seconds blocked in iterator-next waiting for the next batch "
    "(input starvation; the device is idle while this accrues)",
    label_names=("source",))
_H2D_CHILDREN: dict = {}
_WAIT_CHILDREN: dict = {}


def _h2d_child(engine: str):
    child = _H2D_CHILDREN.get(engine)
    if child is None:
        child = _H2D_FAMILY.labels(engine=engine)
        _H2D_CHILDREN[engine] = child
    return child


def _wait_child(source: str):
    child = _WAIT_CHILDREN.get(source)
    if child is None:
        child = _WAIT_FAMILY.labels(source=source)
        _WAIT_CHILDREN[source] = child
    return child


# Puts issued from a DeviceStager worker are overlapped with compute;
# everything else is synchronous caller-thread transfer time.
_TLS = threading.local()


def _put_seconds_child():
    return (_M_PUT_OVERLAPPED if getattr(_TLS, "overlapped", False)
            else _M_PUT_SYNC)


# Up to this many bytes the whole batch tuple goes in one device_put (one
# call for several small arrays); above it, one put per array. The
# threshold was set on an earlier chip set-up, no longer available, and is
# not measured on the current machine.
_TUPLE_PUT_MAX_BYTES = 4 << 20


def _stage_arrays(parts: Sequence[np.ndarray]) -> List:
    """device_put a set of host arrays, as one tuple or one by one by
    total size (see _TUPLE_PUT_MAX_BYTES)."""
    import jax

    t0 = time.perf_counter()
    if sum(p.nbytes for p in parts) <= _TUPLE_PUT_MAX_BYTES:
        out = list(jax.device_put(tuple(parts)))
    else:
        out = [jax.device_put(p) for p in parts]
    _put_seconds_child().inc(time.perf_counter() - t0)
    return out


def _np_transfer_dtype(transfer_dtype):
    """Resolve a DtypePolicy `transfer_dtype` string to a numpy dtype
    (bf16 via ml_dtypes). None passes through (no cast)."""
    if transfer_dtype is None:
        return None
    s = str(transfer_dtype)
    if s in ("bfloat16", "bf16"):
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    if s in ("float16", "f16", "fp16"):
        return np.dtype(np.float16)
    return np.dtype(s)


def transfer_cast(item, transfer_dtype):
    """Cast a batch's floating features/labels HOST-SIDE to the policy's
    `transfer_dtype` before staging: bytes over the host->device link halve
    (f32 -> bf16) and the `dl4j_host_to_device_bytes_total` counters record
    the reduced size.
    Masks and integer parts (embedding ids, image bytes) are untouched;
    already-staged device arrays pass through (their transfer is sunk)."""
    dt = _np_transfer_dtype(transfer_dtype)
    if dt is None:
        return item

    def cast(a):
        if (isinstance(a, np.ndarray)
                and np.issubdtype(a.dtype, np.floating) and a.dtype != dt):
            return a.astype(dt)
        return a

    def host(a):
        return a if hasattr(a, "dtype") else np.asarray(a)

    if isinstance(item, MultiDataSet):
        return MultiDataSet(
            features=[cast(host(f)) for f in item.features],
            labels=[cast(host(l)) for l in item.labels],
            features_masks=item.features_masks,
            labels_masks=item.labels_masks,
        )
    if isinstance(item, DataSet):
        return DataSet(
            cast(host(item.features)),
            None if item.labels is None else cast(host(item.labels)),
            item.features_mask,
            item.labels_mask,
        )
    return item


def stage_to_device(ds: DataSet, transfer_dtype=None) -> DataSet:
    """Transfer one DataSet's arrays host->device (see _stage_arrays),
    optionally casting floating features/labels to `transfer_dtype` first
    so the link carries the reduced representation."""
    if transfer_dtype is not None:
        ds = transfer_cast(ds, transfer_dtype)
    parts = [np.asarray(ds.features)]
    idx = {"features": 0}
    for name in ("labels", "features_mask", "labels_mask"):
        a = getattr(ds, name)
        if a is not None:
            idx[name] = len(parts)
            parts.append(np.asarray(a))
    staged = _stage_arrays(parts)
    return DataSet(
        staged[0],
        staged[idx["labels"]] if "labels" in idx else None,
        staged[idx["features_mask"]] if "features_mask" in idx else None,
        staged[idx["labels_mask"]] if "labels_mask" in idx else None,
    )


def _maybe_stage(parts: List) -> List:
    """Stage the np members of a flat part list to device (one tuple-put
    when small, per-array puts when large — see `_stage_arrays`)."""
    np_idx = [i for i, p in enumerate(parts) if isinstance(p, np.ndarray)]
    if not np_idx:
        return parts
    staged = _stage_arrays([parts[i] for i in np_idx])
    out = list(parts)
    for i, s in zip(np_idx, staged):
        out[i] = s
    return out


def _host(a):
    if a is None or hasattr(a, "dtype"):
        return a
    return np.asarray(a)


def stage_item(item):
    """Device-put every host leaf of a batch container, preserving the
    container type: DataSet, MultiDataSet, and the superstep
    Superbatch/MultiSuperbatch stacks (duck-typed on `k` so this module
    never imports iterators). Device-resident leaves pass through."""
    if isinstance(item, DataSet):
        return stage_to_device(item)
    if isinstance(item, MultiDataSet) or (
            hasattr(item, "features_masks") and hasattr(item, "features")):
        feats = [_host(a) for a in item.features]
        labs = [_host(a) for a in item.labels]
        fmasks = (None if item.features_masks is None
                  else [_host(a) for a in item.features_masks])
        lmasks = (None if item.labels_masks is None
                  else [_host(a) for a in item.labels_masks])
        flat = _maybe_stage(feats + labs + (fmasks or []) + (lmasks or []))
        pos = 0
        out = []
        for src in (feats, labs, fmasks, lmasks):
            if src is None:
                out.append(None)
                continue
            out.append(flat[pos:pos + len(src)])
            pos += len(src)
        if isinstance(item, MultiDataSet):
            return MultiDataSet(features=out[0], labels=out[1],
                                features_masks=out[2], labels_masks=out[3])
        return type(item)(out[0], out[1], out[2], out[3], k=item.k)
    if hasattr(item, "features"):  # Superbatch
        parts = _maybe_stage([
            _host(item.features), _host(item.labels),
            _host(item.features_mask), _host(item.labels_mask)])
        return type(item)(parts[0], parts[1], parts[2], parts[3],
                          k=getattr(item, "k", 1))
    return item


def _iter_leaves(item):
    """Yield every non-None array leaf of a batch container (or of a
    list/tuple of containers)."""
    if item is None:
        return
    if isinstance(item, (list, tuple)):
        for sub in item:
            yield from _iter_leaves(sub)
        return
    if hasattr(item, "features"):
        if hasattr(item, "features_masks"):
            slots = (item.features, item.labels, item.features_masks,
                     item.labels_masks)
        else:
            slots = (item.features, item.labels, item.features_mask,
                     item.labels_mask)
        for s in slots:
            if s is None:
                continue
            if isinstance(s, (list, tuple)):
                for a in s:
                    if a is not None:
                        yield a
            else:
                yield s
        return
    yield item


def host_item_nbytes(item) -> int:
    """Bytes a batch container will move over the link when staged: the
    sum of its HOST (numpy) leaves. Device-resident leaves cost nothing
    (their transfer is sunk), so a DeviceCache replay budgets at 0."""
    return sum(a.nbytes for a in _iter_leaves(item)
               if isinstance(a, np.ndarray))


def drop_item(item) -> None:
    """Eagerly free a staged batch's device buffers (best-effort)."""
    for a in _iter_leaves(item):
        delete = getattr(a, "delete", None)
        if delete is None:
            continue
        try:
            delete()
        except Exception:
            pass  # already deleted / not a device array


def _drop_staged(staged: Sequence) -> None:
    """Eagerly free the device buffers of partially staged batches."""
    for ds in staged:
        drop_item(ds)


# ------------------------------------------------------------------ knobs

_DEFAULT_BUDGET = 256 << 20  # no device memory stats (CPU backend)
_MIN_BUDGET = 16 << 20


def staging_enabled() -> bool:
    """Overlapped staging on/off (`DL4J_TPU_STAGING=0|false|off` disables;
    every consumer then degrades to its synchronous path)."""
    return (os.environ.get("DL4J_TPU_STAGING", "").strip().lower()
            not in ("0", "false", "off"))


def staging_depth() -> int:
    """Default stager queue depth (`DL4J_TPU_STAGE_DEPTH`, default 2:
    double-buffering — one batch in flight while one is consumed)."""
    try:
        return max(1, int(os.environ.get("DL4J_TPU_STAGE_DEPTH", "2")))
    except ValueError:
        return 2


def staging_budget_bytes(net=None) -> int:
    """Byte budget for a stager's in-flight window: `DL4J_TPU_STAGE_BYTES`
    when set, else half the device headroom after the net's measured
    footprint (`measured_model_bytes`: params + optimizer + largest
    recorded transient), else a 256 MiB default when the backend reports
    no memory stats."""
    env = os.environ.get("DL4J_TPU_STAGE_BYTES")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    limit = 0
    try:
        import jax

        stats = getattr(jax.devices()[0], "memory_stats", lambda: None)()
        limit = int((stats or {}).get("bytes_limit", 0))
    except Exception:
        limit = 0
    if limit:
        reserved = 0
        if net is not None:
            try:
                from deeplearning4j_tpu.observability import memory as _mem

                reserved = int(_mem.measured_model_bytes(net) or 0)
            except Exception:
                reserved = 0
        headroom = max(0, limit - reserved)
        if headroom:
            return max(_MIN_BUDGET, headroom // 2)
    return _DEFAULT_BUDGET


_END = object()


class DeviceStager:
    """Background-thread staging of a batch stream to device.

    Pulls items from `base` on a worker thread, applies `transform` then
    the `transfer_dtype` cast, stages via `stage_fn` (default
    `stage_item`; `device_stage=False` skips the put for host-only
    prefetch), and hands consumers already-resident batches through a
    bounded queue. Iteration order and contents match the base stream
    exactly; a producer exception is re-raised on the consumer side.

    In-flight bytes are admitted against `max_bytes` BEFORE each put (see
    module docstring for the backpressure contract); `max_inflight_bytes`
    records the high-water mark. `close()` is idempotent: it stops the
    worker, joins it, and drops any staged-but-unconsumed device buffers
    so the in-flight gauges return to their pre-stager level.
    """

    stages_to_device = True

    def __init__(self, base: Iterable, *, stage_fn: Optional[Callable] = None,
                 transform: Optional[Callable] = None, transfer_dtype=None,
                 device_stage: bool = True, depth: Optional[int] = None,
                 max_bytes: Optional[int] = None, net=None,
                 engine: Optional[str] = None, source: Optional[str] = None):
        self.base = base
        self._transform = transform
        self._transfer_dtype = transfer_dtype
        self._device_stage = bool(device_stage)
        self._stage_fn = stage_item if stage_fn is None else stage_fn
        self.depth = staging_depth() if depth is None else max(1, int(depth))
        if max_bytes is None and self._device_stage:
            max_bytes = staging_budget_bytes(net)
        self.max_bytes = max_bytes
        self._h2d = (_h2d_child(engine)
                     if engine and self._device_stage else None)
        self._wait_obs = _wait_child(source) if source else None
        self._q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._lock = threading.Lock()
        self._can_admit = threading.Condition(self._lock)
        self._inflight = 0
        self.max_inflight_bytes = 0
        self.last_wait = 0.0
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._closed = False
        self._done = False
        self._thread = threading.Thread(
            target=self._worker, daemon=True, name="dl4j-device-stager")
        self._thread.start()

    # ------------------------------------------------------------ producer

    def _admit(self, nb: int) -> bool:
        """Block until `nb` bytes fit the in-flight window (an oversized
        item is admitted alone once the window is empty, so tight budgets
        shrink the window instead of erroring). False when closed."""
        with self._can_admit:
            while (self.max_bytes is not None and self._inflight > 0
                   and self._inflight + nb > self.max_bytes):
                if self._stop.is_set():
                    return False
                self._can_admit.wait(timeout=0.1)
            if self._stop.is_set():
                return False
            self._inflight += nb
            if self._inflight > self.max_inflight_bytes:
                self.max_inflight_bytes = self._inflight
        _M_INFLIGHT.inc(nb)
        return True

    def _retire(self, nb: int, item=None, drop: bool = False) -> None:
        with self._can_admit:
            self._inflight -= nb
            self._can_admit.notify_all()
        _M_INFLIGHT.inc(-nb)
        if drop and item is not None:
            drop_item(item)

    def _offer(self, payload) -> bool:
        # Bounded put that gives up when the consumer abandoned iteration,
        # so the worker never blocks forever holding device buffers.
        while not self._stop.is_set():
            try:
                self._q.put(payload, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self) -> None:
        _TLS.overlapped = True
        try:
            base_it = iter(self.base)
            while not self._stop.is_set():
                t0 = time.perf_counter()
                try:
                    item = next(base_it)
                except StopIteration:
                    break
                _M_STAGE_WAIT.observe(time.perf_counter() - t0)
                if self._transform is not None:
                    item = self._transform(item)
                if self._transfer_dtype is not None:
                    item = transfer_cast(item, self._transfer_dtype)
                nb = host_item_nbytes(item) if self._device_stage else 0
                if self._device_stage:
                    if not self._admit(nb):
                        return
                    try:
                        with _obs.tracer.span("staging.put", cat="input"):
                            staged = self._stage_fn(item)
                    except BaseException:
                        self._retire(nb)
                        raise
                    _M_STAGED_BYTES.inc(nb)
                    if self._h2d is not None:
                        self._h2d.inc(nb)
                else:
                    staged = item
                if not self._offer((staged, nb)):
                    self._retire(nb, staged, drop=self._device_stage)
                    return
                _M_DEPTH.inc(1)
        except BaseException as e:  # surfaced on the consumer side
            self._error = e
        finally:
            self._offer(_END)
            _TLS.overlapped = False

    # ------------------------------------------------------------ consumer

    def __iter__(self):
        return self

    def __next__(self):
        if self._done or self._closed:
            self._finish()
        t0 = time.perf_counter()
        payload = self._q.get()
        wait = time.perf_counter() - t0
        self.last_wait = wait
        if self._wait_obs is not None:
            self._wait_obs.observe(wait)
        if payload is _END:
            self._done = True
            self._thread.join(timeout=5)
            self._finish()
        item, nb = payload
        _M_DEPTH.inc(-1)
        self._retire(nb)
        return item

    def _finish(self):
        if self._error is not None:
            raise self._error
        raise StopIteration

    def close(self) -> None:
        """Stop the worker, join it, and drop staged-but-unconsumed
        device buffers. Idempotent; the stager then iterates as
        exhausted (a stored producer error still re-raises)."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        with self._can_admit:
            self._can_admit.notify_all()
        self._drain()
        self._thread.join(timeout=5)
        self._drain()  # a put may have landed between drain and join

    def _drain(self) -> None:
        while True:
            try:
                payload = self._q.get_nowait()
            except queue.Empty:
                return
            if payload is _END:
                continue
            item, nb = payload
            _M_DEPTH.inc(-1)
            self._retire(nb, item, drop=self._device_stage)

    def __del__(self):  # pragma: no cover - GC safety net
        try:
            self.close()
        except Exception:
            pass


def maybe_stage(src, *, net=None, engine: Optional[str] = None,
                transfer_dtype=None, source: Optional[str] = None,
                depth: Optional[int] = None):
    """Wrap an epoch's batch source in a `DeviceStager` unless staging is
    disabled, the source already stages to device (`stages_to_device` —
    Async/DeviceCache/SuperbatchIterator), or it is a single-batch
    list/tuple (the `fit(ds)` and elastic per-step paths, where a thread
    per call buys nothing); those pass through to the synchronous path."""
    if not staging_enabled():
        return src
    if getattr(src, "stages_to_device", False):
        return src
    if isinstance(src, (list, tuple)) and len(src) <= 1:
        return src
    return DeviceStager(src, net=net, engine=engine,
                        transfer_dtype=transfer_dtype, source=source,
                        depth=depth)


def close_stager(src) -> None:
    """Close `src` if it is a DeviceStager (no-op otherwise) — the
    engines' fit loops call this in a finally so an abandoned epoch
    never leaves staged buffers in HBM."""
    if isinstance(src, DeviceStager):
        src.close()
