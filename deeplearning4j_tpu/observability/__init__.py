"""Production observability core: metrics, tracing, step profiling.

Three parts (see each module's docstring):

- `metrics`   — process-global `MetricsRegistry` (Prometheus text + JSON)
- `tracer`    — process-global `Tracer` (Chrome trace-event ring buffer)
- `StepProfiler` — compile/execute/transfer/FLOPs split for one engine

Scrape points: `UIServer` and `InferenceServer` both serve `/metrics`
(Prometheus text) and the UIServer adds `/api/trace` (Chrome trace JSON —
save it and open in ui.perfetto.dev). `bench.py` embeds `bench_snapshot()`
into BENCH_out.json.

Env knobs (read once at import):

- `DL4J_TPU_OBS`              — "0"/"false"/"off" disables both the default
                                registry and tracer (mutators become one
                                bool check; spans become a shared no-op).
- `DL4J_TPU_TRACE_BUFFER`     — trace ring-buffer capacity (default 16384).
- `DL4J_TPU_FLIGHT*`          — flight-recorder knobs (see `flight.py`).

PR 7 adds the forensics + memory tier: `flight` (always-on crash/NaN/
preemption FlightRecorder, bundles inspectable with `python -m
deeplearning4j_tpu.observability.flight <bundle>`) and `memory`
(per-program HBM gauges from `memory_analysis()`, live-buffer
attribution, measured serving footprints). UIServer serves both at
`/api/flight` and `/api/memory`.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional

from deeplearning4j_tpu.observability import propagate
from deeplearning4j_tpu.observability.metrics import (
    DEFAULT_BUCKETS, WIDE_BUCKETS, MetricsRegistry, backend_is_up,
    install_builtin_collectors)
from deeplearning4j_tpu.observability.tracing import Tracer
from deeplearning4j_tpu.observability.profiler import (
    StepProfiler, chip_peak_flops, chip_peak_hbm_bw, estimate_step_cost,
    estimate_step_flops)

__all__ = [
    "metrics", "tracer", "StepProfiler", "MetricsRegistry",
    "Tracer", "DEFAULT_BUCKETS", "WIDE_BUCKETS", "enable", "disable",
    "iteration_span", "host_nbytes", "install_jax_compile_hook",
    "bench_snapshot", "prometheus_payload", "chip_peak_flops",
    "chip_peak_hbm_bw", "estimate_step_cost",
    "estimate_step_flops", "flight", "FlightRecorder", "memory",
    "propagate", "install_build_info", "request_ledger", "RequestLedger",
    "slo",
]

OBS_ENABLED = os.environ.get("DL4J_TPU_OBS", "1").lower() not in (
    "0", "false", "off")

# The process-global instruments. Hot-loop call sites resolve their labeled
# children from `metrics` once at module import; `enable()`/`disable()` flip
# both at runtime regardless of the env default.
metrics = MetricsRegistry(enabled=OBS_ENABLED)
install_builtin_collectors(metrics)
tracer = Tracer(enabled=OBS_ENABLED)


def install_build_info(registry: Optional[MetricsRegistry] = None) -> None:
    """Register the `dl4j_build_info{version,jax,backend,device_kind}`
    info-gauge (constant 1). Labels resolve at scrape time and only from
    what the process has ALREADY done: jax is never imported just to
    report a version, and a backend is never initialised just to name it —
    a router, coordinator or manager that answers `/metrics` would
    otherwise take the chip from the replica that needs it. The series
    upgrades in place once jax / the backend come up. Federated scrapes
    read this to spot mixed-version fleets mid-rolling-update."""
    reg = registry or metrics
    fam = reg.gauge(
        "dl4j_build_info",
        "Build/runtime identity of this process (value is always 1); "
        "compare worker_id series in a federated scrape to detect "
        "mixed-version fleets during rolling updates",
        label_names=("version", "jax", "backend", "device_kind"))
    state: Dict[str, Any] = {}

    def collect(_reg: MetricsRegistry) -> None:
        import sys

        import deeplearning4j_tpu as _pkg

        labels = {"version": getattr(_pkg, "__version__", "unknown"),
                  "jax": "unloaded", "backend": "unknown",
                  "device_kind": "unknown"}
        jax = sys.modules.get("jax")  # never import jax just to report it
        if jax is not None:
            labels["jax"] = jax.__version__
            if backend_is_up():
                labels["backend"] = jax.default_backend()
                labels["device_kind"] = jax.devices()[0].device_kind
        key = tuple(labels.values())
        if state.get("key") != key:
            prev = state.get("child")
            if prev is not None:
                prev.set(0.0)  # labels upgraded (jax came up): retire old
            state["key"] = key
            state["child"] = fam.labels(**labels)
        state["child"].set(1.0)

    reg.register_collector(collect)


install_build_info(metrics)


def enable() -> None:
    metrics.enable()
    tracer.enabled = True


def disable() -> None:
    metrics.disable()
    tracer.enabled = False


def iteration_span(engine: str, iteration: int, **args):
    """Span for one training iteration (`<engine>.iteration`)."""
    return tracer.span(f"{engine}.iteration", cat="train", engine=engine,
                       iteration=iteration, **args)


def host_nbytes(*parts) -> int:
    """Total bytes of host-resident numpy arrays among `parts` (arrays,
    lists/tuples of arrays, or None) — the host->device transfer cost of
    staging them; device-resident jax arrays count 0."""
    import numpy as np

    total = 0
    for part in parts:
        if part is None:
            continue
        arrays = part if isinstance(part, (list, tuple)) else [part]
        for a in arrays:
            if isinstance(a, np.ndarray):
                total += a.nbytes
    return total


# ------------------------------------------------------- XLA compile hook

_hook_lock = threading.Lock()
_hook_installed = False
_hook_registries: list = []
# Persistent-cache hit attribution: on a hit jax STILL emits a
# `backend_compile` duration event (near-zero — the "compile" was a disk
# read), which used to be miscounted as a real compile. The cache_hits
# event precedes it on the same thread, so a thread-local pending flag
# re-routes the next backend_compile event to the persistent bucket.
_hook_tls = threading.local()


def _register_hook_families(reg: MetricsRegistry) -> None:
    reg.counter("dl4j_xla_compiles_total",
                "XLA backend compiles observed via jax.monitoring "
                "(persistent-cache hits excluded)")
    reg.counter("dl4j_xla_compile_seconds_total",
                "Seconds in jax compile pipeline phases",
                label_names=("phase",))
    reg.counter("dl4j_compile_cache_hits_total",
                "Compile-cache hits by layer (aot = framework executable "
                "store, persistent = jax/XLA persistent compilation cache)",
                label_names=("source",))
    reg.counter("dl4j_compile_cache_misses_total",
                "Compile-cache misses by layer (see "
                "dl4j_compile_cache_hits_total)",
                label_names=("source",))
    reg.histogram("dl4j_compile_seconds",
                  "Seconds to make one program runnable, by source (trace = "
                  "full lowering + backend compile, persistent = XLA cache "
                  "retrieval, aot = executable deserialization)",
                  label_names=("source",), buckets=WIDE_BUCKETS)


def install_jax_compile_hook(registry: Optional[MetricsRegistry] = None) -> bool:
    """Feed `jax.monitoring` compile events into the registry:

    - `dl4j_xla_compiles_total` — real backend compiles (a persistent-cache
      hit fires jax's backend_compile event with ~zero duration; those are
      attributed to the cache, not counted here)
    - `dl4j_xla_compile_seconds_total{phase}` — trace / mlir / backend...
    - `dl4j_compile_cache_hits_total` / `_misses_total` {source=persistent}
    - `dl4j_compile_seconds{source=trace|persistent}` (the `aot` source is
      observed by `compilation.store`, not here)

    The jax listeners are installed once per process; additional registries
    passed on later calls are fanned out to. Returns True when the hook is
    (now) active."""
    global _hook_installed
    reg = registry or metrics
    with _hook_lock:
        if reg not in _hook_registries:
            _hook_registries.append(reg)
            _register_hook_families(reg)
        if _hook_installed:
            return True
        try:
            from jax import monitoring
        except Exception:
            return False

        def on_cache_event(event: str, **kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                _hook_tls.persistent_hit = True
                for r in _hook_registries:
                    r.counter("dl4j_compile_cache_hits_total",
                              label_names=("source",)).labels(
                                  source="persistent").inc()
            elif event == "/jax/compilation_cache/cache_misses":
                _hook_tls.persistent_hit = False
                for r in _hook_registries:
                    r.counter("dl4j_compile_cache_misses_total",
                              label_names=("source",)).labels(
                                  source="persistent").inc()

        def on_event(event: str, duration: float, **kw) -> None:
            if event.endswith("/cache_retrieval_time_sec"):
                for r in _hook_registries:
                    r.histogram("dl4j_compile_seconds",
                                label_names=("source",)).labels(
                                    source="persistent").observe(duration)
                return
            if not event.startswith("/jax/core/compile"):
                return
            # '/jax/core/compile/backend_compile_duration' -> 'backend_compile'
            phase = event.rsplit("/", 1)[-1]
            if phase.endswith("_duration"):
                phase = phase[:-len("_duration")]
            is_backend = phase == "backend_compile"
            pending_hit = is_backend and getattr(
                _hook_tls, "persistent_hit", False)
            if pending_hit:
                _hook_tls.persistent_hit = False
            for r in _hook_registries:
                r.counter("dl4j_xla_compile_seconds_total",
                          label_names=("phase",)).labels(
                              phase=phase).inc(duration)
                if is_backend and not pending_hit:
                    r.counter("dl4j_xla_compiles_total").inc()
                    r.histogram("dl4j_compile_seconds",
                                label_names=("source",)).labels(
                                    source="trace").observe(duration)

        try:
            monitoring.register_event_listener(on_cache_event)
            monitoring.register_event_duration_secs_listener(on_event)
        except Exception:
            return False
        _hook_installed = True
        return True


# ------------------------------------------------------------- exposition


def prometheus_payload(fmt: str = "prometheus",
                       registry: Optional[MetricsRegistry] = None,
                       names: Optional[Any] = None):
    """One scrape body for every HTTP surface (`UIServer` and the serving
    tier both mount `GET /metrics` on this): returns `(body_bytes,
    content_type)`. `fmt="json"` serves the structured snapshot instead of
    Prometheus text 0.0.4. `names` (iterable of family names, from the
    `?names=a,b` query param) narrows the body to those families — the
    needle scrape the fleet router's load poll uses, whose cost must not
    scale with how many families the process hosts."""
    import json

    reg = registry or metrics
    if fmt == "json":
        return (json.dumps(reg.to_json(names=names)).encode(),
                "application/json")
    return (reg.to_prometheus(names=names).encode(),
            "text/plain; version=0.0.4")


# ------------------------------------------------------------ bench glue


def bench_snapshot(registry: Optional[MetricsRegistry] = None) -> Dict[str, Any]:
    """Compact observability summary for BENCH_out.json: step-latency
    histogram summaries, compile totals, MFU, jit-cache hit/miss, transfer
    and checkpoint byte counters. Safe to call with nothing recorded."""
    reg = registry or metrics
    out: Dict[str, Any] = {}

    def family_values(name):
        fam = reg.get_family(name)
        if fam is None:
            return None
        vals = {}
        for child in fam.children():
            key = ",".join(f"{k}={v}" for k, v in child.labels.items()) or "_"
            vals[key] = child.get()
        return vals or None

    for hist in ("dl4j_step_latency_seconds", "dl4j_step_dispatch_seconds",
                 "dl4j_infer_latency_seconds", "dl4j_request_latency_seconds",
                 "dl4j_serving_request_seconds", "dl4j_serving_ttft_seconds",
                 "dl4j_serving_itl_seconds",
                 "dl4j_serving_decode_step_seconds", "dl4j_compile_seconds",
                 "dl4j_input_wait_seconds"):
        fam = reg.get_family(hist)
        if fam is None:
            continue
        for child in fam.children():
            summary = child.summarize()
            if not summary.get("count"):
                continue
            key = ",".join(f"{k}={v}" for k, v in child.labels.items())
            out.setdefault(hist, {})[key or "_"] = summary
    for name in ("dl4j_xla_compiles_total", "dl4j_xla_compile_seconds_total",
                 "dl4j_compile_cache_hits_total",
                 "dl4j_compile_cache_misses_total",
                 "dl4j_requests_total",
                 "dl4j_serving_generated_tokens_total",
                 "dl4j_serving_evictions_total",
                 "dl4j_tenant_device_seconds_total",
                 "dl4j_tenant_tokens_total",
                 "dl4j_jit_cache_hits_total", "dl4j_jit_cache_misses_total",
                 "dl4j_host_to_device_bytes_total",
                 "dl4j_checkpoint_bytes_written_total",
                 "dl4j_program_hbm_bytes", "dl4j_flight_dumps_total",
                 "dl4j_profiler_compile_seconds",
                 "dl4j_profiler_execute_seconds_median",
                 "dl4j_train_flops_per_step", "dl4j_train_mfu"):
        vals = family_values(name)
        if vals:
            out[name] = vals
    return out


# ------------------------------------------------- forensics + memory tier
# Imported LAST: both modules resolve their metric families from the
# process-global `metrics` defined above. `flight` is re-exported as the
# recorder INSTANCE (`observability.flight.dump()` / `.record_step(...)`);
# the module itself stays importable as
# `deeplearning4j_tpu.observability.flight` (and runnable with -m).

from deeplearning4j_tpu.observability import memory  # noqa: E402,F401
from deeplearning4j_tpu.observability.flight import (  # noqa: E402
    FlightRecorder, recorder as flight)
# `request_ledger` is the instance; the module keeps its dotted name
# (`deeplearning4j_tpu.observability.ledger`) for the serving tier.
from deeplearning4j_tpu.observability.ledger import (  # noqa: E402
    RequestLedger, ledger as request_ledger)
from deeplearning4j_tpu.observability import slo  # noqa: E402,F401
