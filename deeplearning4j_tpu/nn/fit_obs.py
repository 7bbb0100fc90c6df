"""The fit loop's instruments.

`MultiLayerNetwork` ("mln") and `ComputationGraph` ("graph") run one loop
(`nn/engine.py`): wait for a batch, dispatch it, enqueue the compiled step.
`FitObs` holds one network class's hot-loop metric series (resolved once at
import, observability/metrics.py rule 2) and opens the spans of that loop:

    <e>.fit          one epoch                       (the engine's `fit`)
    <e>.input_wait   `next()` of the batch source    (`batches`)
    <e>.iteration    one `_fit_dispatch`             (`dispatch`)
    <e>.enqueue      the call of the compiled step   (`enqueue`)

`<e>.iteration` minus `<e>.enqueue` is the engine's own host time per
step. The names are read by `benchmark/harness/host_spans.py`.

These are the host's side. The device's side is named where the step is
traced, not here: `nn/engine.py::scope` runs every vertex under
`L.<vertex>` and every phase of the train step under `step.grad_cast`,
`step.update` (a layer's part under `L.<key>` inside it) and `step.store`,
`jax.named_scope`s that reach each HLO operation's `op_name`; a layer body's
own scopes (`dsa.attend`, `moe.experts`, `Layer.scope`, ...) nest inside.
`benchmark/harness/scope_table.py` reads a traced step by them. The gauges
below ride in the step's state and are set where the score is read.
"""

from __future__ import annotations

import time

from deeplearning4j_tpu import observability as _obs


# Layer statistics that ride in a net's declared state (small arrays the
# compiled step returns anyway) -> the gauge each is published under, per
# layer, where the score is read: no fetch of its own per step.
LAYER_STATS = {
    "pairs_held_share": (
        "dl4j_moe_pairs_held_share",
        "Share of a dropless MoE layer's (token, expert) pairs routed to "
        "the experts the layer holds (last step read)"),
    "expert_load_max_over_mean": (
        "dl4j_moe_expert_load_max_over_mean",
        "Largest load over mean load among the experts a dropless MoE "
        "layer holds (last step read)"),
    "selected_keys_mean": (
        "dl4j_dsa_selected_keys_mean",
        "Mean number of keys a query of a sparse attention layer attends "
        "to (last step read)"),
    "band_fill_share": (
        "dl4j_attn_band_fill_share",
        "Pairs inside an attention layer's causal band (or window) over "
        "pairs in the tiles its Pallas kernel visits: static per layer, "
        "sequence length and block choice; 1.0 is no wasted tile, 0 the XLA "
        "body (no tiles)"),
}


class FitObs:
    """One engine's metric series and spans (see module docstring)."""

    def __init__(self, engine: str):
        m = _obs.metrics
        self.engine = engine
        self.iters = m.counter(
            "dl4j_train_iterations_total", "Completed training iterations",
            label_names=("engine",)).labels(engine=engine)
        self.epochs = m.counter(
            "dl4j_train_epochs_total", "Completed fit() epochs",
            label_names=("engine",)).labels(engine=engine)
        self._dispatch_family = m.histogram(
            "dl4j_step_dispatch_seconds",
            "Host time to dispatch one staged batch (async — completion is "
            "NOT awaited; see dl4j_step_latency_seconds from StepProfiler "
            "for settled latency); `k` = train iterations fused into the "
            "dispatch (superstep)",
            label_names=("engine", "k"))
        # Few distinct k values per process; children are cached.
        self._dispatch_k = {
            1: self._dispatch_family.labels(engine=engine, k="1")}
        self.h2d = m.counter(
            "dl4j_host_to_device_bytes_total",
            "Host-resident bytes staged to device with training batches",
            label_names=("engine",)).labels(engine=engine)
        self.jit_hit = m.counter(
            "dl4j_jit_cache_hits_total", "Engine jit-program cache hits",
            label_names=("engine",)).labels(engine=engine)
        self.jit_miss = m.counter(
            "dl4j_jit_cache_misses_total",
            "Engine jit-program cache misses (a new program will "
            "trace+compile)",
            label_names=("engine",)).labels(engine=engine)
        self.input_wait = m.histogram(
            "dl4j_input_wait_seconds",
            "Host seconds blocked in iterator-next waiting for the next "
            "batch (input starvation; the device is idle while this "
            "accrues)",
            label_names=("source",)).labels(source=engine)

    def publish_layer_stats(self, net) -> None:
        """Set the `LAYER_STATS` gauges from `net.state`. Called where the
        score is read (a sync the caller asked for); a net none of whose
        layers declares such state pays one attribute lookup."""
        keys = getattr(net, "_layer_stat_keys", None)
        if keys is None:
            # (layer, state key, its gauge's series), resolved once per net
            keys = net._layer_stat_keys = [
                (name, key, _obs.metrics.gauge(
                    *LAYER_STATS[key], label_names=("layer",)).labels(
                        layer=name))
                for name, entries in (net.state or {}).items()
                for key in entries if key in LAYER_STATS]
        for name, key, series in keys:
            series.set(float(net.state[name][key]))

    def enqueue(self):
        """Span around the call of the compiled step, and nothing else."""
        return _obs.tracer.span(f"{self.engine}.enqueue", cat="train")

    def batches(self, net, source):
        """Yield `source`'s items, each `next()` under `<e>.input_wait`.
        The wait is timed separately from the dispatch: with async/staged
        input tiers it is pure device starvation."""
        source = iter(source)
        while True:
            t_wait = time.perf_counter()
            with _obs.tracer.span(f"{self.engine}.input_wait", cat="train"):
                try:
                    item = next(source)
                except StopIteration:
                    return
            net._last_input_wait = time.perf_counter() - t_wait
            self.input_wait.observe(net._last_input_wait)
            yield item

    def dispatch(self, net, batch, h2d: int, inner):
        """`inner(batch)` under `<e>.iteration` — the engine's observability
        choke point: every training path (plain / tBPTT / solver /
        superstep, local or sharded) goes through here."""
        self.h2d.inc(h2d)
        k = int(getattr(batch, "k", 1))
        it0 = net.iteration
        t0 = time.perf_counter()
        with _obs.iteration_span(self.engine, it0 + 1):
            try:
                return inner(batch)
            except Exception as e:
                # Forensics for uncaught dispatch failures: the bundle is
                # written before the exception unwinds the fit loop.
                _obs.flight.on_crash(f"{self.engine}.dispatch", e)
                raise
            finally:
                dt = time.perf_counter() - t0
                child = self._dispatch_k.get(k)
                if child is None:
                    child = self._dispatch_family.labels(
                        engine=self.engine, k=str(k))
                    self._dispatch_k[k] = child
                child.observe(dt)
                self.iters.inc(max(0, net.iteration - it0))
                _obs.flight.record_step(
                    self.engine, net.iteration, loss=net._score, seconds=dt,
                    k=k, h2d_bytes=h2d,
                    input_wait=getattr(net, "_last_input_wait", None),
                    jit_hits=self.jit_hit.get(),
                    jit_misses=self.jit_miss.get())
