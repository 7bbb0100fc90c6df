"""StepProfiler: where did this step's milliseconds go?

Wraps a live engine (`MultiLayerNetwork` / `ComputationGraph`) and splits
the time of every staged-batch dispatch into the pieces BENCH rounds have
had to eyeball from the outside:

- **compile vs execute**: XLA compile durations are captured through
  `jax.monitoring`'s event-duration hook (`/jax/core/compile/*` — the
  lowering/compile pipeline reports itself), cross-checked against the
  engines' jit-cache hit/miss counters; the first dispatch of each program
  is recorded separately from steady-state dispatches.
- **step latency**: each dispatch is (optionally) settled with
  `jax.block_until_ready` on what the step left behind and observed into
  the `dl4j_step_latency_seconds` histogram. `sync=False` records dispatch
  time only (does not perturb async pipelining, but under-reports).
- **host->device transfer bytes**: counted from the host-resident arrays of
  every dispatched batch (`dl4j_host_to_device_bytes_total`).
- **FLOPs + MFU**: `lower().compile().cost_analysis()` on the engine's own
  jitted train step gives FLOPs/step; divided by steady-state step time and
  the chip's published peak (`CHIP_PEAKS`) it becomes the `dl4j_train_mfu`
  gauge. A CPU has no peak, so there the gauge stays absent unless the
  caller passes `peak_flops=`; a TPU that is not in the table raises.

Usage::

    from deeplearning4j_tpu.observability import StepProfiler

    with StepProfiler(net) as prof:
        net.fit(iterator)
    print(prof.summary())   # and scrape /metrics for the histograms
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional


def estimate_step_flops(net, ds) -> Optional[float]:
    """XLA cost-analysis FLOPs of the engine's actual jitted train step for
    one staged batch (`bench.py` delegates here). Returns None when the
    backend does not report flops."""
    return estimate_step_cost(net, ds).get("flops")


def estimate_step_cost(net, ds) -> Dict[str, Optional[float]]:
    """XLA cost analysis of the jitted train step for one staged batch:
    ``{"flops": ..., "bytes": ...}`` where ``bytes`` is the backend's
    "bytes accessed" estimate — the HBM traffic one step moves, the
    numerator of the roofline check `bench.py` prints next to MFU. Either
    value is None when the backend does not report it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    out: Dict[str, Optional[float]] = {"flops": None, "bytes": None}
    try:
        clock = (jnp.asarray(0.0, jnp.float32), jax.random.PRNGKey(0))
        fn = net._get_jit("train_step")
        if type(net).__name__ == "ComputationGraph":
            feats = [jnp.asarray(np.asarray(f)) for f in ds.features]
            labs = [jnp.asarray(np.asarray(l)) for l in ds.labels]
            args = (net.params_tree, net.state, net.opt_state, feats, labs,
                    None, None, clock)
        else:
            args = (net.params_tree, net.state, net.opt_state,
                    jnp.asarray(np.asarray(ds.features)),
                    jnp.asarray(np.asarray(ds.labels)), None, None, clock)
        lowered = fn.lower(*args)
        compiled = None
        try:
            compiled = lowered.compile()
            cost = compiled.cost_analysis()
        except Exception:
            cost = lowered.cost_analysis()
        if compiled is not None:
            # Piggyback: the compiled step is in hand, so its static HBM
            # footprint feeds dl4j_program_hbm_bytes for free.
            from deeplearning4j_tpu.observability import memory as _mem

            engine = ("graph" if type(net).__name__ == "ComputationGraph"
                      else "mln")
            _mem.record_program_memory(f"{engine}.train_step", compiled,
                                       net=net)
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        nbytes = float(cost.get("bytes accessed", 0.0))
        out["flops"] = flops if flops > 0 else None
        out["bytes"] = nbytes if nbytes > 0 else None
        return out
    except Exception:
        return out


# Published peaks of one chip, keyed by the exact `device_kind` JAX reports
# for it: (bf16 FLOP/s, HBM bytes/s, where the figures are published). A
# device that is not here is an error, not a default: a utilization computed
# against another chip's peak is a wrong number that looks right. (Where a
# chip has two entries, both spellings are in jax's own TPU table.)
CHIP_PEAKS = {
    "TPU v5 lite": (197e12, 819e9,
                    'Google Cloud documentation, "TPU v5e"'),
    "TPU v5e": (197e12, 819e9, 'Google Cloud documentation, "TPU v5e"'),
    "TPU v5p": (459e12, 2765e9, 'Google Cloud documentation, "TPU v5p"'),
    "TPU v5": (459e12, 2765e9, 'Google Cloud documentation, "TPU v5p"'),
    "TPU v6 lite": (918e12, 1640e9,
                    'Google Cloud documentation, "TPU v6e"'),
    "TPU v6e": (918e12, 1640e9, 'Google Cloud documentation, "TPU v6e"'),
    "TPU v4": (275e12, 1228e9, 'Google Cloud documentation, "TPU v4"'),
}


class UnknownDeviceError(LookupError):
    """The local device has no entry in `CHIP_PEAKS`."""


def _chip_peaks():
    import jax

    kind = jax.devices()[0].device_kind
    try:
        return CHIP_PEAKS[kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peak for device_kind {kind!r}; known: "
            f"{sorted(CHIP_PEAKS)}") from None


def chip_peak_flops() -> float:
    """Peak bf16 FLOP/s of the local accelerator, from `CHIP_PEAKS`.
    Raises `UnknownDeviceError` for any other device (a CPU included)."""
    return _chip_peaks()[0]


def chip_peak_hbm_bw() -> float:
    """Peak HBM bandwidth (bytes/sec) of the local accelerator, from
    `CHIP_PEAKS`. Paired with the cost-analysis "bytes accessed" estimate
    this yields the roofline memory-time bound bench.py compares against
    compute time. Raises `UnknownDeviceError` for any other device."""
    return _chip_peaks()[1]


class StepProfiler:
    """See module docstring. Patches the engine instance's `_fit_dispatch`
    (one call per staged batch on every path: plain / tBPTT / solver) and
    `output` (inference latency) for the lifetime of the `with` block;
    restores them on exit."""

    def __init__(self, net, registry=None, tracer=None, sync: bool = True,
                 peak_flops: Optional[float] = None):
        from deeplearning4j_tpu import observability as obs

        self.net = net
        self.registry = registry or obs.metrics
        self.tracer = tracer or obs.tracer
        self.sync = bool(sync)
        self.peak_flops = peak_flops
        self.step_times: List[float] = []      # steady-state dispatches
        self.first_step_times: List[float] = []  # compile-inclusive firsts
        self.infer_times: List[float] = []
        self.h2d_bytes = 0
        self._last_ds = None
        self._patched = False
        reg = self.registry
        self._m_latency = reg.histogram(
            "dl4j_step_latency_seconds",
            "Settled train-step latency measured under StepProfiler "
            "(first compile-inclusive call excluded)")
        self._m_first = reg.histogram(
            "dl4j_step_first_call_seconds",
            "First (compile-inclusive) dispatch of each jitted program "
            "under StepProfiler", buckets=(0.1, 0.5, 1, 2.5, 5, 10, 30,
                                           60, 120, 300))
        self._m_infer = reg.histogram(
            "dl4j_infer_latency_seconds",
            "Settled output() latency measured under StepProfiler")
        self._m_compile = reg.gauge(
            "dl4j_profiler_compile_seconds",
            "XLA compile seconds attributed to the profiled window")
        self._m_execute = reg.gauge(
            "dl4j_profiler_execute_seconds_median",
            "Median steady-state step seconds in the profiled window")
        self._m_flops = reg.gauge(
            "dl4j_train_flops_per_step",
            "XLA cost-analysis FLOPs of one jitted train step")
        self._m_mfu = reg.gauge(
            "dl4j_train_mfu",
            "Model FLOPs utilization: flops/step / step_time / chip peak "
            "(absent without a known peak — see PERF.md CPU caveats)")

    # ------------------------------------------------------------ patching

    def __enter__(self) -> "StepProfiler":
        from deeplearning4j_tpu import observability as obs

        obs.install_jax_compile_hook(self.registry)
        try:
            from deeplearning4j_tpu.observability import memory as _mem

            _mem.register_tree(type(self.net).__name__, self.net)
        except Exception:
            pass
        self._compile_s0 = self._compile_seconds()
        self._cache_counts0 = self._cache_counts()
        self._input_wait0 = self._input_wait_totals()
        self._staging0 = self._staging_totals()
        self._jit_known = len(self.net._jit_cache)
        self._orig_dispatch = self.net._fit_dispatch
        self._orig_output = self.net.output
        net = self.net

        def dispatch(ds, *a, **kw):
            self._last_ds = ds
            self.h2d_bytes += _host_nbytes(ds)
            known = len(net._jit_cache)
            t0 = time.perf_counter()
            # No extra span here: the engine's own iteration span already
            # covers the dispatch, and an extra wrapper would usurp its
            # parentage in the trace.
            out = self._orig_dispatch(ds, *a, **kw)
            if self.sync:
                _settle(net)
            dt = time.perf_counter() - t0
            if len(net._jit_cache) > known:
                # This dispatch traced (and on first real call, compiled) a
                # new program: keep it out of the steady-state histogram.
                self.first_step_times.append(dt)
                self._m_first.observe(dt)
            else:
                self.step_times.append(dt)
                self._m_latency.observe(dt)
            return out

        def output(*a, **kw):
            t0 = time.perf_counter()
            result = self._orig_output(*a, **kw)
            dt = time.perf_counter() - t0
            self.infer_times.append(dt)
            self._m_infer.observe(dt)
            return result

        self.net._fit_dispatch = dispatch
        self.net.output = output
        self._patched = True
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.stop()
        return False

    def stop(self) -> None:
        if self._patched:
            self.net._fit_dispatch = self._orig_dispatch
            self.net.output = self._orig_output
            self._patched = False
        self._finalize()

    # ----------------------------------------------------------- reporting

    def _compile_seconds(self) -> float:
        fam = self.registry.get_family("dl4j_xla_compile_seconds_total")
        if fam is None:
            return 0.0
        return sum(c.get() for c in fam.children())

    def compile_seconds(self) -> float:
        """XLA compile seconds that elapsed inside the profiled window.
        A persistent-cache hit's near-zero backend_compile event still
        lands here (it is seconds spent, just tiny); the hit itself is
        reported under `summary()['compile_cache']`, not as a compile."""
        return max(0.0, self._compile_seconds() - self._compile_s0)

    def _cache_counts(self) -> Dict[str, float]:
        counts: Dict[str, float] = {}
        for kind, name in (("hits", "dl4j_compile_cache_hits_total"),
                           ("misses", "dl4j_compile_cache_misses_total")):
            fam = self.registry.get_family(name)
            if fam is None:
                continue
            for child in fam.children():
                source = child.labels.get("source", "_")
                counts[f"{kind}_{source}"] = child.get()
        return counts

    def compile_cache_deltas(self) -> Dict[str, float]:
        """Per-source compile-cache hit/miss counts inside the profiled
        window, e.g. {'hits_aot': 2, 'misses_persistent': 1}."""
        base = getattr(self, "_cache_counts0", {})
        out: Dict[str, float] = {}
        for key, val in self._cache_counts().items():
            delta = val - base.get(key, 0.0)
            if delta > 0:
                out[key] = delta
        return out

    def _input_wait_totals(self) -> tuple:
        fam = self.registry.get_family("dl4j_input_wait_seconds")
        if fam is None:
            return (0.0, 0)
        s_total, c_total = 0.0, 0
        for child in fam.children():
            _, _, s, c = child.histogram_state()
            s_total += s
            c_total += c
        return (s_total, c_total)

    def input_wait(self) -> tuple:
        """(seconds, observations) the host spent blocked in iterator-next
        inside the profiled window — starvation shows up here, not in step
        latency."""
        s0, c0 = getattr(self, "_input_wait0", (0.0, 0))
        s, c = self._input_wait_totals()
        return (max(0.0, s - s0), max(0, c - c0))

    def _staging_totals(self) -> Dict[str, float]:
        """Current totals of the datasets/staging transfer counters:
        bytes shipped by background stagers, and device_put seconds split
        by overlapped (stager-thread) vs synchronous (caller-thread)."""
        out = {"overlapped_bytes": 0.0, "overlapped_put_seconds": 0.0,
               "synchronous_put_seconds": 0.0, "staging_wait_seconds": 0.0}
        fam = self.registry.get_family("dl4j_staging_bytes_total")
        if fam is not None:
            out["overlapped_bytes"] = sum(c.get() for c in fam.children())
        fam = self.registry.get_family("dl4j_staging_put_seconds_total")
        if fam is not None:
            for child in fam.children():
                mode = child.labels.get("mode", "synchronous")
                out[f"{mode}_put_seconds"] = (
                    out.get(f"{mode}_put_seconds", 0.0) + child.get())
        fam = self.registry.get_family("dl4j_staging_wait_seconds")
        if fam is not None:
            for child in fam.children():
                _, _, s, _ = child.histogram_state()
                out["staging_wait_seconds"] += s
        return out

    def staging_deltas(self) -> Dict[str, float]:
        """Overlapped-transfer activity inside the profiled window (see
        `_staging_totals` for the keys). All zeros when no DeviceStager
        ran — the synchronous path."""
        base = getattr(self, "_staging0", {})
        return {key: max(0.0, val - base.get(key, 0.0))
                for key, val in self._staging_totals().items()}

    def execute_seconds_median(self) -> Optional[float]:
        if not self.step_times:
            return None
        return sorted(self.step_times)[len(self.step_times) // 2]

    def _finalize(self) -> None:
        compile_s = self.compile_seconds()
        if not compile_s and self.first_step_times and self.step_times:
            # No monitoring hook on this jax: fall back to first-call-minus-
            # steady-state (documented as an estimate in summary()).
            med = self.execute_seconds_median() or 0.0
            compile_s = max(0.0, sum(self.first_step_times)
                            - med * len(self.first_step_times))
        self._m_compile.set(compile_s)
        med = self.execute_seconds_median()
        if med is not None:
            self._m_execute.set(med)
        flops = None
        if self._last_ds is not None:
            flops = estimate_step_flops(self.net, self._last_ds)
        if flops:
            self._m_flops.set(flops)
            peak = self.peak_flops
            if peak is None and _on_accelerator():
                peak = chip_peak_flops()
            if peak and med:
                self._m_mfu.set(flops / med / peak)

    def summary(self) -> Dict[str, Any]:
        med = self.execute_seconds_median()
        staging = self.staging_deltas()
        out: Dict[str, Any] = {
            "steps": len(self.step_times) + len(self.first_step_times),
            "first_call_steps": len(self.first_step_times),
            "compile_seconds": self.compile_seconds() or self._m_compile.get(),
            "execute_seconds_median": med,
            # Dispatch-visible host bytes plus what background stagers
            # shipped (staged batches reach dispatch device-resident, so
            # the dispatch-side count alone would read ~0 under overlap).
            "host_to_device_bytes": (self.h2d_bytes
                                     + int(staging["overlapped_bytes"])),
        }
        if any(staging.values()):
            out["transfer"] = {
                "overlapped_bytes": int(staging["overlapped_bytes"]),
                "synchronous_bytes": self.h2d_bytes,
                "overlapped_put_seconds": staging["overlapped_put_seconds"],
                "synchronous_put_seconds": staging["synchronous_put_seconds"],
                "staging_wait_seconds": staging["staging_wait_seconds"],
            }
        cache = self.compile_cache_deltas()
        if cache:
            out["compile_cache"] = cache
        wait_s, wait_n = self.input_wait()
        if wait_n:
            out["input_wait"] = {"seconds": wait_s, "observations": wait_n,
                                 "mean": wait_s / wait_n}
        if self.step_times:
            s = sorted(self.step_times)
            out["step_latency"] = {
                "mean": sum(s) / len(s), "p50": s[len(s) // 2],
                "min": s[0], "max": s[-1],
                "sync": self.sync,
            }
        if self.infer_times:
            s = sorted(self.infer_times)
            out["infer_latency"] = {"mean": sum(s) / len(s),
                                    "p50": s[len(s) // 2], "count": len(s)}
        flops = self._m_flops.get()
        if flops:
            out["flops_per_step"] = flops
            if med:
                out["flops_per_sec"] = flops / med
        mfu = self._m_mfu.get()
        if mfu:
            out["mfu"] = mfu
        return out


def _on_accelerator() -> bool:
    import jax

    return jax.devices()[0].platform != "cpu"


def _settle(net) -> None:
    """Wait until the dispatched step has finished on the device: its new
    parameters are the last thing it writes, and the loss with them. A
    device error in the step surfaces here."""
    import jax

    jax.block_until_ready((net.params_tree, getattr(net, "_score", None)))


def _host_nbytes(ds) -> int:
    """Bytes of host-resident (numpy) arrays in a DataSet / MultiDataSet —
    the batch's host->device transfer cost; device-resident arrays count 0."""
    import numpy as np

    total = 0
    for name in ("features", "labels", "features_mask", "labels_mask",
                 "features_masks", "labels_masks"):
        part = getattr(ds, name, None)
        if part is None:
            continue
        arrays = part if isinstance(part, (list, tuple)) else [part]
        for a in arrays:
            if isinstance(a, np.ndarray):
                total += a.nbytes
    return total
