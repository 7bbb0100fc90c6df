"""The serving facade: `InferenceServer`, now a multi-model host.

The PR 5 single-model server (`deeplearning4j_tpu/serving.py`) became this
package; the constructor, `from_checkpoint`, `predict`, `wait_ready`,
`url`, `stop` and the HTTP surface (`/health`, `/healthz`, `/metrics`,
`/predict`) are unchanged for existing callers. What's new underneath:

- admission goes through a per-model `ShapeBucketBatcher` (bounded queue,
  bucket-ladder padding, deadline/cancellation drops) instead of one
  unbounded queue + one fixed compile shape;
- `add_model(name, net=..., path=...)` hosts several models in one
  process under a `ModelHost` HBM budget (LRU eviction + reload);
- LM engines with a KV-cached decode path get a continuous-batching
  `GenerationScheduler` (`generate()`, `POST /generate`);
- warmup drives EVERY batch bucket (and every prompt bucket + the decode
  step) through the `compilation/` AOT store, so mixed-shape traffic
  never compiles post-startup.
"""

from __future__ import annotations

import threading
import time
from http.server import ThreadingHTTPServer
from typing import Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu.observability.ledger import ledger as _ledger
from deeplearning4j_tpu.serving import metrics as _m
from deeplearning4j_tpu.serving.batcher import (
    ShapeBucketBatcher,
    canonicalize_features,
)
from deeplearning4j_tpu.serving.errors import (
    InputValidationError,
    ModelNotReadyError,
    RequestTimeoutError,
    ServerOverloadedError,
    ServingError,
)
from deeplearning4j_tpu.serving.host import ModelHost
from deeplearning4j_tpu.serving.scheduler import GenerationScheduler

_UNSET = object()


class InferenceServer:
    """HTTP predict/generate server over trained engines (anything with
    `output(x)`; LM generation needs a ComputationGraph with a KV-cached
    attention decode path).

    `max_batch_size` bounds the LARGEST padded compile shape; requests pad
    to the smallest bucket in `batch_buckets` (powers of two up to
    `max_batch_size` by default). `max_delay_ms` is the coalescing window.
    With `warmup=True`, `start()` returns immediately and compiles every
    bucket on a background thread; poll `GET /healthz` or `wait_ready()`
    before sending traffic. `hbm_budget_bytes` turns on LRU eviction of
    cold checkpoint-backed models.
    """

    def __init__(self, net=None, port: int = 0, host: str = "127.0.0.1",
                 max_batch_size: int = 32, max_delay_ms: float = 5.0,
                 predict_timeout_s: Optional[float] = 300.0,
                 warmup: bool = False,
                 warmup_shape: Optional[Tuple[int, ...]] = None,
                 batch_buckets: Optional[Sequence[int]] = None,
                 queue_depth: int = 256,
                 hbm_budget_bytes: Optional[int] = None,
                 decode_slots: int = 4,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 generate_queue_depth: int = 64,
                 scheduler_mode: str = "continuous",
                 kv_cache: str = "dense",
                 kv_page_size: int = 64,
                 kv_pages: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 draft=None, spec_k: int = 4,
                 model_parallel: int = 1,
                 default_model: str = "default"):
        self.host = host
        self.port = port
        # How long predict() waits for its batch; the first request after a
        # model/shape change pays a fresh XLA compile, so the default is
        # generous. None waits indefinitely.
        self.predict_timeout_s = predict_timeout_s
        self.max_batch_size = int(max_batch_size)
        self.max_delay_s = float(max_delay_ms) / 1000.0
        self.warmup = bool(warmup)
        self.warmup_shape = (None if warmup_shape is None
                             else tuple(warmup_shape))
        self.batch_buckets = batch_buckets
        self.queue_depth = int(queue_depth)
        self.decode_slots = int(decode_slots)
        self.prompt_buckets = prompt_buckets
        self.generate_queue_depth = int(generate_queue_depth)
        self.scheduler_mode = scheduler_mode
        # Paged-KV / prefix-cache / speculative-decoding defaults
        # (per-model overrides in add_model). kv_cache="paged" swaps the
        # dense DecodeStepper for the page-pool stepper; `draft` is a
        # small zoo LM proposing spec_k tokens per decode round.
        self.kv_cache = kv_cache
        self.kv_page_size = int(kv_page_size)
        self.kv_pages = kv_pages
        self.prefix_cache = prefix_cache
        self.draft = draft
        self.spec_k = int(spec_k)
        # Tensor-parallel serving (PERF.md §28): n > 1 builds a
        # ("data", "model") mesh over this process's devices at attach
        # time, shards each hosted model's params over the model axis
        # (`parallel/mesh.shard_params` head-aware rules) and runs the
        # decode loop under the matching ParallelContext — per-chip HBM
        # drops ~1/n and XLA inserts the collectives.
        self.model_parallel = int(model_parallel)
        self.default_model = default_model
        self._contexts: dict = {}  # ways -> shared ParallelContext
        self.models = ModelHost(hbm_budget_bytes=hbm_budget_bytes,
                                on_load=self._attach)
        self._ready = threading.Event()
        self._ready.set()
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._serve_thread: Optional[threading.Thread] = None
        self._warmup_thread: Optional[threading.Thread] = None
        self._warmup_error: Optional[Exception] = None
        if net is not None:
            self.add_model(default_model, net=net)

    @classmethod
    def from_checkpoint(cls, path, **kwargs) -> "InferenceServer":
        """Serve straight from a checkpoint on disk: a sharded checkpoint
        directory (a committed step or a `CheckpointManager` root — latest
        committed step wins) or a legacy model ZIP. The deploy path is one
        call: train anywhere, point the server at the checkpoint store —
        with `warmup=True` the checkpointed model is pre-compiled before
        the first request arrives (watch `GET /healthz` for "ready").
        Keeping `path` on the default model makes it evictable (and
        reloadable) under an `hbm_budget_bytes`."""
        server = cls(None, **kwargs)
        server.add_model(server.default_model, path=path)
        return server

    # --------------------------------------------------------------- models

    @property
    def net(self):
        """The default model's engine (the PR 5 single-model attribute)."""
        return self.models.get(self.default_model).net

    def add_model(self, name: str, net=None, path=None, *,
                  max_batch_size: Optional[int] = None,
                  batch_buckets: Optional[Sequence[int]] = None,
                  max_delay_ms: Optional[float] = None,
                  queue_depth: Optional[int] = None,
                  warmup_shape: Optional[Tuple[int, ...]] = None,
                  lm: object = "auto",
                  decode_slots: Optional[int] = None,
                  prompt_buckets: Optional[Sequence[int]] = None,
                  generate_queue_depth: Optional[int] = None,
                  scheduler_mode: Optional[str] = None,
                  kv_cache: Optional[str] = None,
                  kv_page_size: Optional[int] = None,
                  kv_pages: object = _UNSET,
                  prefix_cache: object = _UNSET,
                  draft: object = _UNSET,
                  spec_k: Optional[int] = None,
                  model_parallel: Optional[int] = None,
                  pinned: Optional[bool] = None):
        """Host another model (server-level knobs are the defaults). With
        `path`, the checkpoint loads now and can be LRU-evicted/reloaded
        under the HBM budget; a live `net` with no path is pinned."""
        if net is None:
            if path is None:
                raise ValueError("add_model needs a net or a path")
            from deeplearning4j_tpu.checkpoint.legacy import load_any

            net = load_any(path)
        opts = {
            "max_batch_size": (self.max_batch_size if max_batch_size is None
                               else int(max_batch_size)),
            "batch_buckets": (self.batch_buckets if batch_buckets is None
                              else batch_buckets),
            "max_delay_s": (self.max_delay_s if max_delay_ms is None
                            else float(max_delay_ms) / 1000.0),
            "queue_depth": (self.queue_depth if queue_depth is None
                            else int(queue_depth)),
            "warmup_shape": (self.warmup_shape if warmup_shape is None
                             else tuple(warmup_shape)),
            "lm": lm,
            "decode_slots": (self.decode_slots if decode_slots is None
                             else int(decode_slots)),
            "prompt_buckets": (self.prompt_buckets if prompt_buckets is None
                               else prompt_buckets),
            "generate_queue_depth": (
                self.generate_queue_depth if generate_queue_depth is None
                else int(generate_queue_depth)),
            "scheduler_mode": (self.scheduler_mode if scheduler_mode is None
                               else scheduler_mode),
            "kv_cache": (self.kv_cache if kv_cache is None else kv_cache),
            "kv_page_size": (self.kv_page_size if kv_page_size is None
                             else int(kv_page_size)),
            "kv_pages": (self.kv_pages if kv_pages is _UNSET else kv_pages),
            "prefix_cache": (self.prefix_cache if prefix_cache is _UNSET
                             else prefix_cache),
            "draft": (self.draft if draft is _UNSET else draft),
            "spec_k": (self.spec_k if spec_k is None else int(spec_k)),
            "model_parallel": (self.model_parallel if model_parallel is None
                               else int(model_parallel)),
        }
        return self.models.add(name, net=net, path=path, pinned=pinned,
                               **opts)

    def _parallel_context(self, ways: int):
        """The server's ("data", "model") mesh context for `ways`-way
        tensor parallelism, built once and shared by every model that
        asks for the same width (one mesh -> one jit-cache/fingerprint
        identity across models and reloads)."""
        import jax

        from deeplearning4j_tpu.parallel import mesh as mesh_mod
        from deeplearning4j_tpu.parallel.context import ParallelContext

        ctx = self._contexts.get(ways)
        if ctx is None:
            n_dev = len(jax.devices())
            if ways > n_dev:
                raise ValueError(
                    f"model_parallel={ways} needs {ways} devices; this "
                    f"process has {n_dev}")
            mesh = mesh_mod.create_mesh((1, ways), ("data", "model"))
            ctx = ParallelContext(mesh, model_axis="model")
            self._contexts[ways] = ctx
        return ctx

    def _attach(self, model) -> None:
        """ModelHost on_load hook: build + start the model's serving
        runtime (runs at add time and again after an eviction reload)."""
        o = model.options
        ways = int(o.get("model_parallel") or 1)
        if ways > 1:
            from deeplearning4j_tpu.parallel import mesh as mesh_mod
            from deeplearning4j_tpu.serving.host import sharding_desc

            ctx = self._parallel_context(ways)
            # Restore-onto-mesh: the freshly loaded (or reloaded) params
            # land sharded before any program traces against them.
            mesh_mod.shard_params(model.net, ctx.mesh, model_axis="model")
            model.context = ctx
            model.sharding = sharding_desc(ctx)
        else:
            model.context = None
            model.sharding = "none"
        model.batcher = ShapeBucketBatcher(
            model.net, model_name=model.name,
            max_batch_size=o["max_batch_size"], buckets=o["batch_buckets"],
            max_delay_s=o["max_delay_s"], queue_depth=o["queue_depth"],
            warmup_shape=o["warmup_shape"], context=model.context).start()
        # Multi-tenant hooks: warmup and per-request dispatch resolve
        # adapter-merged trees through the ServedModel registry (lazy, so
        # adapters loaded after _attach are picked up too).
        model.batcher.param_variants = (
            lambda: [model.adapter_params(n)
                     for n in sorted(model.adapters)])
        if o["lm"] and hasattr(model.net, "_get_jit"):
            try:
                model.scheduler = GenerationScheduler(
                    model.net, model_name=model.name,
                    slots=o["decode_slots"],
                    prompt_buckets=o["prompt_buckets"],
                    queue_depth=o["generate_queue_depth"],
                    mode=o["scheduler_mode"],
                    kv=o["kv_cache"], page_size=o["kv_page_size"],
                    kv_pages=o["kv_pages"],
                    prefix_cache=o["prefix_cache"],
                    draft=o["draft"], spec_k=o["spec_k"],
                    context=model.context)
                model.scheduler.adapter_params = model.adapter_params
                model.scheduler.adapter_names = (
                    lambda: sorted(model.adapters))
                model.scheduler.start()
            except Exception:
                # lm="auto" probes: a model without a KV-cached decode path
                # simply doesn't serve /generate.
                if o["lm"] is not True:
                    model.scheduler = None
                else:
                    raise
        model.ready.set()

    # ------------------------------------------------------------- adapters

    def load_adapter(self, name: str, path=None, net=None,
                     model: Optional[str] = None,
                     pinned: bool = True):
        """Host a LoRA adapter next to a resident base model. `path` loads
        an adapter checkpoint (`checkpoint/adapters.py` — refused unless
        its base fingerprint matches the resident base); `net` extracts
        the delta straight from a live fine-tuned engine. Requests then
        select it with `adapter=name` on predict/generate — the base stays
        resident once, every adapter adds only its rank-r delta to HBM,
        and (after warmup) hot-swapping adapters compiles nothing."""
        from deeplearning4j_tpu.nn import lora as lora_mod

        served = self.models.get(self.default_model if model is None
                                 else model)
        if (path is None) == (net is None):
            raise ValueError("load_adapter needs exactly one of path/net")
        if path is not None:
            from deeplearning4j_tpu.checkpoint import adapters as _adapters

            tree = _adapters.load_adapter(path, base_net=served.net)
        else:
            tree = lora_mod.extract_adapter(net.params_tree)
            if not tree:
                raise ValueError(
                    "net has no LoRA adapter leaves to extract")
        return served.add_adapter(name, tree, pinned=pinned)

    def _resolve_adapter(self, served, adapter: Optional[str]):
        """Adapter name -> merged params tree (None passes through); an
        unknown name is a 400, not a 500. Counting happens at OUTCOME
        time (`_count_adapter`), not here — the outcome label needs the
        request's fate."""
        if adapter is None:
            return None
        try:
            params = served.adapter_params(str(adapter))
        except KeyError as e:
            raise InputValidationError(str(e.args[0]) if e.args else str(e))
        return params

    @staticmethod
    def _count_adapter(model: str, adapter: Optional[str],
                       outcome: str) -> None:
        """dl4j_adapter_requests_total{model,adapter,outcome} — per-tenant
        error rates without joining the ledger. Base-model traffic
        (adapter=None) counts only under dl4j_requests_total."""
        if adapter is not None:
            _m.ADAPTER_REQUESTS.labels(
                model=model, adapter=str(adapter),
                outcome="failed" if outcome == "invalid" else outcome).inc()

    # -------------------------------------------------------------- warmup

    @property
    def _status(self) -> str:
        # Derived from the Event (its own lock) so the warmup thread and
        # the HTTP handlers never race on a plain attribute; the error is
        # written before the Event is set.
        if not self._ready.is_set():
            return "warming"
        return "ready" if self._warmup_error is None else "failed"

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        """Block until warmup finished (immediately True without warmup).
        Raises when the warm-up itself failed: a server whose programs did
        not compile has not become ready, whatever it still admits."""
        done = self._ready.wait(timeout)
        if done and self._warmup_error is not None:
            raise RuntimeError(
                "serving warmup failed") from self._warmup_error
        return done

    def _warmup_run(self) -> None:
        """Drive every model's batch-bucket ladder (and, for LMs, every
        prompt bucket + the decode step) through the AOT store so no real
        request triggers an XLA compile. A failure is kept, not survived in
        silence: `/healthz` answers "failed" and `wait_ready` raises it.
        Requests are still admitted — the first one meets the same error,
        or pays the compile, exactly the no-warmup behavior."""
        try:
            for name in self.models.names():
                model = self.models.get(name)
                try:
                    if model.batcher is not None:
                        model.batcher.warm()
                    if model.scheduler is not None:
                        model.scheduler.warmup()
                except Exception as e:
                    import warnings

                    self._warmup_error = e
                    warnings.warn(
                        f"serving warmup failed ({type(e).__name__}: {e})")
                finally:
                    model.ready.set()
        finally:
            self._ready.set()

    # ------------------------------------------------------------- predict

    def predict(self, data, model: Optional[str] = None,
                timeout_s: object = _UNSET,
                adapter: Optional[str] = None) -> np.ndarray:
        """In-process entry (the HTTP handler calls this too). Observed once
        per caller request into the latency histograms, however many
        bucket-sized chunks it splits into. `adapter` routes the request
        through a loaded LoRA delta over the same resident base."""
        name = self.default_model if model is None else model
        timeout = (self.predict_timeout_s if timeout_s is _UNSET
                   else timeout_s)
        t0 = time.perf_counter()
        rec = _ledger.open(route="predict", model=name,
                           adapter="" if adapter is None else str(adapter))
        try:
            served = self.models.get(name)
            params = self._resolve_adapter(served, adapter)
            arr = canonicalize_features(served.net, data)
            rec.add_tokens_in(int(arr.shape[0]))  # predict: rows in
            result = self._predict_rows(served, arr, timeout,
                                        adapter=adapter, params=params,
                                        ledger_rec=rec)
        except Exception as e:
            _m.REQUESTS_LEGACY.labels(outcome="error").inc()
            _m.REQUESTS.labels(model=name, route="predict",
                               outcome=self._outcome(e)).inc()
            self._count_adapter(name, adapter, self._ledger_outcome(e))
            _ledger.close(rec, outcome=self._ledger_outcome(e))
            raise
        _m.REQUESTS_LEGACY.labels(outcome="ok").inc()
        _m.REQUESTS.labels(model=name, route="predict", outcome="ok").inc()
        self._count_adapter(name, adapter, "ok")
        _ledger.close(rec, outcome="ok")
        dt = time.perf_counter() - t0
        _m.REQ_LATENCY.observe(dt)
        _m.REQUEST_SECONDS.labels(model=name, route="predict").observe(dt)
        return result

    @staticmethod
    def _outcome(e: Exception) -> str:
        if isinstance(e, ServerOverloadedError):
            return "shed"
        if isinstance(e, (InputValidationError, ModelNotReadyError)):
            return "invalid"
        if isinstance(e, TimeoutError):
            # The batcher/scheduler already counted "timeout" when it
            # dropped the request; don't double count under it.
            return "error"
        return "error"

    @staticmethod
    def _ledger_outcome(e: Exception) -> str:
        """Ledger/adapter outcome vocabulary (ok/timeout/shed/failed plus
        'invalid', which _count_adapter folds into 'failed')."""
        if isinstance(e, ServerOverloadedError):
            return "shed"
        if isinstance(e, (RequestTimeoutError, TimeoutError)):
            return "timeout"
        if isinstance(e, (InputValidationError, ModelNotReadyError)):
            return "invalid"
        return "failed"

    def _predict_rows(self, served, arr: np.ndarray,
                      timeout: Optional[float],
                      adapter: Optional[str] = None,
                      params=None, ledger_rec=None) -> np.ndarray:
        deadline = None if timeout is None else time.monotonic() + timeout
        size = served.batcher.max_batch_size
        # Split oversized requests into bucket-sized chunks; all chunks are
        # queued up front so they coalesce into consecutive batches.
        chunks = ([arr[i:i + size] for i in range(0, arr.shape[0], size)]
                  or [arr])
        pendings = [served.batcher.submit(c, deadline, adapter=adapter,
                                          params=params,
                                          ledger_rec=ledger_rec)
                    for c in chunks]
        results = []
        for p in pendings:
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            p.event.wait(timeout=remaining)
            if not p.event.is_set():
                for q in pendings:
                    q.cancelled = True  # the batcher drops + counts them
                raise TimeoutError(
                    f"prediction timed out after {timeout}s "
                    "(cold XLA compiles can be slow; raise predict_timeout_s "
                    "or pass None to wait indefinitely)")
            if p.error == "__deadline__":
                for q in pendings:
                    q.cancelled = True
                raise RequestTimeoutError(
                    f"prediction deadline ({timeout}s) expired in the "
                    "batch queue")
            if p.error is not None:
                raise RuntimeError(p.error)
            results.append(p.result)
        if len(results) == 1:
            return results[0]
        return np.concatenate(results, axis=0)

    # ------------------------------------------------------------ generate

    def generate(self, prompt_ids, n_steps: int,
                 model: Optional[str] = None,
                 timeout_s: object = _UNSET,
                 adapter: Optional[str] = None, **sampling):
        """Continuously-batched LM generation: returns the full token list
        (prompt + generated), float-close to `generate_lm(use_cache=True)`
        for the same seed/sampling knobs. `adapter` decodes through a
        loaded LoRA delta; slots on different adapters share the decode
        loop (grouped dispatch per round)."""
        name = self.default_model if model is None else model
        timeout = (self.predict_timeout_s if timeout_s is _UNSET
                   else timeout_s)
        t0 = time.perf_counter()
        rec = _ledger.open(route="generate", model=name,
                           adapter="" if adapter is None else str(adapter),
                           tokens_in=len(prompt_ids))
        try:
            served = self.models.get(name)
            if served.scheduler is None:
                raise InputValidationError(
                    f"model {name!r} does not serve generation (no "
                    "KV-cached decode path)")
            ids = served.scheduler.generate(prompt_ids, n_steps,
                                            timeout_s=timeout,
                                            adapter=adapter,
                                            ledger_rec=rec, **sampling)
        except Exception as e:
            _m.REQUESTS.labels(model=name, route="generate",
                               outcome=self._outcome(e)).inc()
            self._count_adapter(name, adapter, self._ledger_outcome(e))
            _ledger.close(rec, outcome=self._ledger_outcome(e))
            raise
        _m.REQUESTS.labels(model=name, route="generate",
                           outcome="ok").inc()
        self._count_adapter(name, adapter, "ok")
        _ledger.close(rec, outcome="ok")
        _m.REQUEST_SECONDS.labels(model=name, route="generate").observe(
            time.perf_counter() - t0)
        return ids

    # ------------------------------------------------------------- tenants

    def tenant_snapshot(self) -> list:
        """`GET /v1/tenants` payload: the ledger's per-(model, adapter)
        rollups joined with adapter HBM residency from the model host —
        requests, tokens in/out, attributed device-seconds, mean queue
        wait, and each adapter's share of its base model's HBM."""
        rows = _ledger.tenants()
        for row in rows:
            row["hbm_bytes"] = None
            row["hbm_share"] = None
            try:
                served = self.models.get(row["model"])
            except Exception:
                continue
            info = served.adapters.get(row["adapter"])
            if info is not None:
                row["hbm_bytes"] = int(info.get("bytes") or 0)
                base = getattr(served, "hbm_bytes", 0) or 0
                if base:
                    row["hbm_share"] = row["hbm_bytes"] / float(base)
        return rows

    # ---------------------------------------------------------------- http

    def start(self) -> "InferenceServer":
        from deeplearning4j_tpu.serving.http import make_handler

        _m.QUEUE_DEPTH.set_function(self._total_queue_depth)
        self._httpd = ThreadingHTTPServer((self.host, self.port),
                                          make_handler(self))
        self.port = self._httpd.server_address[1]
        self._serve_thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._serve_thread.start()
        if self.warmup:
            # The port is already bound and /healthz answers "warming", so
            # orchestrators can watch readiness while the models compile.
            self._ready.clear()
            for name in self.models.names():
                self.models.get(name).ready.clear()
            self._warmup_thread = threading.Thread(
                target=self._warmup_run, name="dl4j-serving-warmup",
                daemon=True)
            self._warmup_thread.start()
        return self

    def _total_queue_depth(self) -> int:
        total = 0
        for name in self.models.names():
            m = self.models._models.get(name)
            if m is not None and m.batcher is not None:
                total += m.batcher.qsize()
        return total

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self) -> None:
        _m.QUEUE_DEPTH.set_function(None)
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        self.models.stop()
