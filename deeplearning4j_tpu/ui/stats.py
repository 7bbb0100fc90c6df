"""StatsListener: rich periodic training telemetry.

TPU-native equivalent of the reference's
`deeplearning4j-ui-model/.../stats/BaseStatsListener.java:43,273`: every N
iterations it samples score, per-layer parameter/gradient/update mean
magnitudes and histograms, per-step wall time, throughput, learning-rate
info and device memory, and routes the record through a
`StatsStorageRouter` (`api/storage.py`). Where the reference pulls
gradients off the host model object, here gradient/update magnitudes are
computed INSIDE the jitted train step (only scalars leave the device —
`Engine._train_step(collect_stats=True)`, nn/engine.py); histograms are taken
from the params pytree on the sampled iterations only.
"""

from __future__ import annotations

import time
import uuid
from typing import Any, Dict, Optional

import numpy as np

from deeplearning4j_tpu.api.storage import StatsStorageRouter
from deeplearning4j_tpu.optimize.listeners import IterationListener


def _host_rss_mb():
    """CURRENT process resident-set size in MiB (the process-level analog
    of the reference BaseStatsListener's JVM memory reporting). Prefers
    /proc/self/statm (live value, Linux); falls back to getrusage peak RSS
    with the platform's unit (KiB on Linux, bytes on macOS)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        import os

        return pages * os.sysconf("SC_PAGE_SIZE") / 1048576.0
    except Exception:
        pass
    try:
        import resource
        import sys

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return peak / (1048576.0 if sys.platform == "darwin" else 1024.0)
    except Exception:
        return None


class StatsListener(IterationListener):
    """See module docstring. `frequency` = sample every N iterations."""

    requires_training_stats = True

    def __init__(self, storage: StatsStorageRouter, frequency: int = 10,
                 session_id: Optional[str] = None, worker_id: str = "worker_0",
                 collect_histograms: bool = True, histogram_bins: int = 20):
        self.storage = storage
        self.frequency = max(1, int(frequency))
        self.session_id = session_id or f"session_{uuid.uuid4().hex[:12]}"
        self.worker_id = worker_id
        self.collect_histograms = collect_histograms
        self.histogram_bins = int(histogram_bins)
        self._static_sent = False
        self._last_time: Optional[float] = None
        self._last_iter = 0

    # ------------------------------------------------------------- helpers

    def _send_static(self, model) -> None:
        info: Dict[str, Any] = {
            "session_id": self.session_id,
            "worker_id": self.worker_id,
            "model_class": type(model).__name__,
            "num_params": int(model.num_params()),
        }
        try:
            info["model_config_json"] = model.conf.to_json()
        except Exception:
            pass
        self.storage.put_static_info(info)
        self._static_sent = True

    def _histogram(self, arr: np.ndarray):
        counts, edges = np.histogram(arr, bins=self.histogram_bins)
        return {"min": float(edges[0]), "max": float(edges[-1]),
                "counts": counts.tolist()}

    @staticmethod
    def _device_memory() -> Optional[Dict[str, int]]:
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats()
            if not stats:
                return None
            return {k: int(v) for k, v in stats.items()
                    if k in ("bytes_in_use", "peak_bytes_in_use",
                             "bytes_limit", "largest_alloc_size")}
        except Exception:
            return None

    # ---------------------------------------------------------------- hook

    def iteration_done(self, model, iteration: int) -> None:
        if not self._static_sent:
            self._send_static(model)
        if iteration % self.frequency != 0:
            return
        now = time.perf_counter()
        record: Dict[str, Any] = {
            "session_id": self.session_id,
            "worker_id": self.worker_id,
            "iteration": int(iteration),
            "score": float(model.score_value),
        }
        if self._last_time is not None and iteration > self._last_iter:
            dt = now - self._last_time
            record["iterations_per_sec"] = (iteration - self._last_iter) / dt
            record["ms_per_iteration"] = 1000.0 * dt / (iteration - self._last_iter)
        self._last_time = now
        self._last_iter = iteration

        # In-jit gradient/update/param mean magnitudes (device scalars).
        tstats = getattr(model, "last_training_stats", None)
        if tstats:
            record["layer_stats"] = {
                lk: {pn: {k: float(v) for k, v in d.items()}
                     for pn, d in lstats.items()}
                for lk, lstats in tstats.items()
            }
        if self.collect_histograms:
            hists: Dict[str, Any] = {}
            for lk, lparams in model.params_tree.items():
                for pn, arr in lparams.items():
                    hists[f"{lk}/{pn}"] = self._histogram(
                        np.asarray(arr, dtype="float32").ravel())
            record["param_histograms"] = hists
        mem = self._device_memory()
        if mem:
            record["device_memory"] = mem
        rss = _host_rss_mb()
        if rss is not None:
            record["host_rss_mb"] = rss
        self.storage.put_update(record)


class ConvolutionalListener(IterationListener):
    """Sample convolutional activation grids for the UI's `/activations`
    page (reference: `ui/module/convolutional/ConvolutionalListenerModule`
    fed by `ConvolutionalIterationListener` — activation maps rendered as
    image grids).

    The reference listener grabs the live minibatch's activations off the
    mutable model; the jitted engines don't keep batches around, so this
    listener carries its own fixed `probe_input` (one example is enough)
    and runs a forward pass on the sampled iterations. 4-D [1, H, W, C]
    activations are strided down to `max_hw` per side and capped at
    `max_channels`, then shipped as row-major float lists in the update
    record under `conv_activations`.

    Pass the StatsListener's `session_id` when using both, so the UI sees
    one merged update stream."""

    def __init__(self, storage: StatsStorageRouter, probe_input,
                 frequency: int = 25, session_id: Optional[str] = None,
                 max_hw: int = 24, max_channels: int = 16):
        self.storage = storage
        self.probe = np.asarray(probe_input)[:1]
        self.frequency = max(1, int(frequency))
        self.session_id = session_id or f"session_{uuid.uuid4().hex[:12]}"
        self.max_hw = int(max_hw)
        self.max_channels = int(max_channels)

    def iteration_done(self, model, iteration: int) -> None:
        if iteration % self.frequency != 0:
            return
        acts = model.feed_forward(self.probe)
        grids: Dict[str, Any] = {}
        for (name, _), a in zip(model.named_layers(), acts):
            a = np.asarray(a, dtype="float32")
            if a.ndim != 4:  # NHWC conv activations only
                continue
            a = a[0]
            # Ceil division: guarantees <= max_hw per side (floor under-
            # strides, e.g. 47//24 == 1 would ship a 47x47 grid).
            sh = max(1, -(-a.shape[0] // self.max_hw))
            sw = max(1, -(-a.shape[1] // self.max_hw))
            a = a[::sh, ::sw, : self.max_channels]
            grids[name] = {
                "h": int(a.shape[0]), "w": int(a.shape[1]),
                "channels": [a[:, :, c].ravel().tolist()
                             for c in range(a.shape[2])],
            }
        if grids:
            self.storage.put_update({
                "session_id": self.session_id,
                "iteration": int(iteration),
                "conv_activations": grids,
            })


class ProfilerListener(IterationListener):
    """Opt-in `jax.profiler` trace around a window of iterations — the
    XPlane-level analog of the reference's per-phase timing stats
    (SURVEY.md §5 tracing). Produces a TensorBoard-loadable trace dir."""

    def __init__(self, log_dir: str, start_iteration: int = 10,
                 num_iterations: int = 5):
        self.log_dir = log_dir
        self.start_iteration = int(start_iteration)
        self.stop_iteration = int(start_iteration + num_iterations)
        self._active = False

    def iteration_done(self, model, iteration: int) -> None:
        import jax

        if not self._active and iteration == self.start_iteration:
            jax.profiler.start_trace(self.log_dir)
            self._active = True
        elif self._active and iteration >= self.stop_iteration:
            jax.block_until_ready(model.params_tree)
            jax.profiler.stop_trace()
            self._active = False

    def on_epoch_end(self, model) -> None:
        if self._active:  # never leak an open trace
            import jax

            jax.profiler.stop_trace()
            self._active = False
