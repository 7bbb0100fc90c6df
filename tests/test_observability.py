"""Observability stack tests: StatsListener -> storage -> UIServer.

Reference analogs: `BaseStatsListener.java:43,273` (stats collection),
`InMemoryStatsStorage`/`FileStatsStorage` (`api/storage/impl/`), the Play
UI's train-module JSON routes (`TrainModule.java:92-99`), and the
TrainingListener epoch hooks (`optimize/api/TrainingListener.java`).

These exercise the engines' `train_step_stats` jit variants in CI (the
stats pytree shape is load-bearing for the UI) and the epoch-hook dispatch
from both engines' fit().
"""

import json
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.api.storage import (
    FileStatsStorage,
    InMemoryStatsStorage,
)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    DenseLayer,
    LSTM,
    OutputLayer,
    RnnOutputLayer,
)
from deeplearning4j_tpu.nn.conf.neural_net import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.listeners import IterationListener
from deeplearning4j_tpu.ui.server import UIServer
from deeplearning4j_tpu.ui.stats import ProfilerListener, StatsListener


def mlp_net():
    conf = (NeuralNetConfiguration.builder()
            .seed(7).learning_rate(0.1).updater("sgd")
            .list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss_function="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def graph_net():
    gb = (NeuralNetConfiguration.builder()
          .seed(7).learning_rate(0.1).updater("sgd")
          .graph_builder()
          .add_inputs("in")
          .add_layer("d", DenseLayer(n_out=8, activation="tanh"), "in")
          .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                        loss_function="mcxent"), "d")
          .set_outputs("out"))
    gb.set_input_types(InputType.feed_forward(4))
    return ComputationGraph(gb.build()).init()


def batch(rng, b=16):
    x = rng.randn(b, 4).astype("float32")
    y = np.eye(3)[rng.randint(0, 3, b)].astype("float32")
    return x, y


class TestStatsListener:
    def test_mln_records_content(self, rng):
        storage = InMemoryStatsStorage()
        net = mlp_net()
        net.set_listeners(StatsListener(storage, frequency=1,
                                        session_id="s1"))
        assert net._collect_stats  # stats jit variant engaged
        x, y = batch(rng)
        for _ in range(3):
            net.fit(x, y)

        info = storage.get_static_info("s1")
        assert info["model_class"] == "MultiLayerNetwork"
        assert info["num_params"] == net.num_params()
        updates = storage.get_updates("s1")
        assert len(updates) == 3
        rec = updates[-1]
        assert np.isfinite(rec["score"])
        # In-jit mean magnitudes for every param of every trainable layer.
        ls = rec["layer_stats"]
        for lk in net.layer_keys:
            if net.params_tree.get(lk):
                for pn in net.params_tree[lk]:
                    for stat in ("grad_mm", "update_mm", "param_mm"):
                        assert np.isfinite(ls[lk][pn][stat])
        # Histograms cover the same params.
        assert any(k.endswith("/W") for k in rec["param_histograms"])
        counts = next(iter(rec["param_histograms"].values()))["counts"]
        assert sum(counts) > 0

    def test_graph_records_content(self, rng):
        storage = InMemoryStatsStorage()
        net = graph_net()
        net.set_listeners(StatsListener(storage, frequency=1,
                                        session_id="g1",
                                        collect_histograms=False))
        x, y = batch(rng)
        for _ in range(2):
            net.fit(x, y)
        rec = storage.get_latest_update("g1")
        assert rec["layer_stats"]["d"]["W"]["grad_mm"] >= 0
        assert rec["layer_stats"]["out"]["W"]["update_mm"] >= 0

    def test_tbptt_stats_collected(self, rng):
        """tBPTT training must feed StatsListener too (ADVICE r2: the tbptt
        jit previously never collected, leaving stale/no stats)."""
        conf = (NeuralNetConfiguration.builder()
                .seed(7).learning_rate(0.05).updater("sgd")
                .list()
                .layer(LSTM(n_out=6, activation="tanh"))
                .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                      loss_function="mcxent"))
                .set_input_type(InputType.recurrent(4, 12))
                .backprop_type("truncatedbptt")
                .t_bptt_forward_length(4).t_bptt_backward_length(4)
                .build())
        net = MultiLayerNetwork(conf).init()
        storage = InMemoryStatsStorage()
        net.set_listeners(StatsListener(storage, frequency=1,
                                        session_id="t1",
                                        collect_histograms=False))
        x = rng.randn(2, 12, 4).astype("float32")
        y = np.eye(3)[rng.randint(0, 3, (2, 12))].astype("float32")
        net.fit(x, y)
        rec = storage.get_latest_update("t1")
        ls = rec["layer_stats"]
        assert np.isfinite(ls["layer_0"]["W"]["grad_mm"])
        assert np.isfinite(ls["layer_1"]["W"]["update_mm"])


class TestFileStatsStorage:
    def test_jsonl_roundtrip(self, tmp_path, rng):
        path = str(tmp_path / "stats.jsonl")
        storage = FileStatsStorage(path)
        storage.put_static_info({"session_id": "f1", "worker_id": "w0",
                                 "model_class": "X", "num_params": 3})
        storage.put_update({"session_id": "f1", "iteration": 1, "score": 0.5})
        storage.put_update({"session_id": "f1", "iteration": 2, "score": 0.4})

        # Fresh instance reads back what the first wrote (restart survival).
        readback = FileStatsStorage(path)
        assert readback.list_session_ids() == ["f1"]
        assert readback.get_static_info("f1")["num_params"] == 3
        ups = readback.get_updates("f1")
        assert [u["iteration"] for u in ups] == [1, 2]
        assert readback.get_latest_update("f1")["score"] == 0.4
        # Every line is valid JSON with a timestamp.
        with open(path) as f:
            for line in f:
                assert "timestamp" in json.loads(line)

    def test_listener_through_file_storage(self, tmp_path, rng):
        storage = FileStatsStorage(str(tmp_path / "s.jsonl"))
        net = mlp_net()
        net.set_listeners(StatsListener(storage, frequency=1, session_id="f2",
                                        collect_histograms=False))
        x, y = batch(rng)
        net.fit(x, y)
        assert storage.get_latest_update("f2")["iteration"] == 1


class TestUIServer:
    def test_endpoints_over_http(self, rng):
        storage = InMemoryStatsStorage()
        net = mlp_net()
        net.set_listeners(StatsListener(storage, frequency=1, session_id="u1",
                                        collect_histograms=False))
        x, y = batch(rng)
        net.fit(x, y)

        server = UIServer(port=0).attach(storage).start()
        try:
            def get(path):
                with urllib.request.urlopen(server.url.rstrip("/") + path,
                                            timeout=5) as r:
                    return r.status, r.read()

            status, body = get("/api/sessions")
            assert status == 200 and json.loads(body) == ["u1"]
            status, body = get("/api/static?sid=u1")
            assert json.loads(body)["model_class"] == "MultiLayerNetwork"
            status, body = get("/api/updates?sid=u1")
            ups = json.loads(body)
            assert len(ups) == 1 and np.isfinite(ups[0]["score"])
            status, body = get("/")
            assert status == 200 and b"training UI" in body
            status, _ = urllib.request.urlopen(
                server.url.rstrip("/") + "/api/sessions", timeout=5).status, None
        finally:
            server.stop()

    def test_unknown_path_404(self):
        server = UIServer(port=0).attach(InMemoryStatsStorage()).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(server.url.rstrip("/") + "/nope",
                                       timeout=5)
            assert ei.value.code == 404
        finally:
            server.stop()


class TestProfilerListener:
    def test_trace_dir_created(self, tmp_path, rng):
        log_dir = str(tmp_path / "trace")
        net = mlp_net()
        net.set_listeners(ProfilerListener(log_dir, start_iteration=2,
                                           num_iterations=2))
        x, y = batch(rng)
        for _ in range(6):
            net.fit(x, y)
        import glob
        import os
        assert os.path.isdir(log_dir)
        assert glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), "no xplane trace written"


class _EpochSpy(IterationListener):
    def __init__(self):
        self.starts = 0
        self.ends = 0
        self.iters = 0

    def on_epoch_start(self, model):
        self.starts += 1

    def on_epoch_end(self, model):
        self.ends += 1

    def iteration_done(self, model, iteration):
        self.iters += 1


class TestEpochHooks:
    def test_mln_dispatches_epoch_hooks(self, rng):
        net = mlp_net()
        spy = _EpochSpy()
        net.set_listeners(spy)
        x, y = batch(rng)
        ds = DataSet(x, y)
        net.fit([ds, ds])   # one epoch, two batches
        net.fit([ds])       # second epoch
        assert spy.starts == 2
        assert spy.ends == 2
        assert spy.iters == 3

    def test_graph_dispatches_epoch_hooks(self, rng):
        net = graph_net()
        spy = _EpochSpy()
        net.set_listeners(spy)
        x, y = batch(rng)
        net.fit(x, y)
        assert spy.starts == 1 and spy.ends == 1 and spy.iters == 1


class TestSystemPage:
    def test_system_page_and_host_rss(self, rng):
        """The /system page serves, and update records carry host RSS +
        device memory (reference: TrainModule system tab +
        BaseStatsListener memory reporting)."""
        import urllib.request

        from deeplearning4j_tpu.api.storage import InMemoryStatsStorage
        from deeplearning4j_tpu.ui.server import UIServer
        from deeplearning4j_tpu.ui.stats import StatsListener

        storage = InMemoryStatsStorage()
        net = mlp_net()
        net.set_listeners(StatsListener(storage, frequency=1,
                                        collect_histograms=False))
        X, Y = batch(rng)
        for _ in range(3):
            net.fit(X, Y)
        sid = storage.list_session_ids()[0]
        ups = storage.get_updates(sid)
        assert any("host_rss_mb" in u and u["host_rss_mb"] > 0 for u in ups)
        server = UIServer(port=0).attach(storage).start()
        try:
            html = urllib.request.urlopen(server.url + "/system",
                                          timeout=10).read().decode()
            assert "Device memory" in html and "host_rss_mb" in html
        finally:
            server.stop()


class TestRound5UIModules:
    """The three reference UI modules added in round 5: flow (network
    graph), t-SNE, convolutional activations — pages render and their data
    routes serve live content during a fit (reference:
    `deeplearning4j-play/.../ui/module/{flow,tsne,convolutional}/`)."""

    def _get(self, server, path):
        with urllib.request.urlopen(server.url.rstrip("/") + path,
                                    timeout=5) as r:
            return r.status, r.read()

    def test_flow_page_and_graph_json(self, rng):
        storage = InMemoryStatsStorage()
        net = mlp_net()
        net.set_listeners(StatsListener(storage, frequency=1,
                                        session_id="f1",
                                        collect_histograms=False))
        x, y = batch(rng)
        net.fit(x, y)
        server = UIServer(port=0).attach(storage).start()
        try:
            status, body = self._get(server, "/flow")
            assert status == 200 and b"Network graph" in body
            # The page's data source: static info must carry the config
            # JSON the layout() JS walks.
            _, body = self._get(server, "/api/static?sid=f1")
            conf = json.loads(json.loads(body)["model_config_json"])
            assert "layers" in conf or "vertices" in conf
        finally:
            server.stop()

    def test_tsne_page_and_upload(self, rng):
        from deeplearning4j_tpu.plot.tsne import Tsne

        X = np.concatenate([rng.randn(15, 4), rng.randn(15, 4) + 6.0])
        Y = Tsne(max_iter=30, perplexity=5.0).fit_transform(X)
        labels = [0] * 15 + [1] * 15

        server = UIServer(port=0, enable_remote=True).attach(
            InMemoryStatsStorage()).start()
        try:
            server.upload_tsne(Y, labels=labels, name="test-embedding")
            status, body = self._get(server, "/tsne")
            assert status == 200 and b"t-SNE" in body
            _, body = self._get(server, "/api/tsne")
            data = json.loads(body)
            assert len(data["coords"]) == 30 and data["labels"] == labels
            # HTTP upload path too (the reference's file-upload analog).
            req = urllib.request.Request(
                server.url.rstrip("/") + "/api/tsne",
                data=json.dumps({"coords": [[0.0, 1.0], [1.0, 0.0]],
                                 "labels": ["a", "b"]}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=5) as r:
                assert json.loads(r.read())["n"] == 2
            _, body = self._get(server, "/api/tsne")
            assert len(json.loads(body)["coords"]) == 2
        finally:
            server.stop()

    def test_activations_page_live_during_fit(self, rng):
        from deeplearning4j_tpu.nn.conf.layers import (
            ConvolutionLayer, OutputLayer, SubsamplingLayer,
        )
        from deeplearning4j_tpu.ui.stats import ConvolutionalListener

        conf = (NeuralNetConfiguration.builder()
                .seed(4).learning_rate(0.01).updater("adam")
                .list()
                .layer(ConvolutionLayer(n_out=6, kernel_size=3,
                                        convolution_mode="same",
                                        activation="relu"))
                .layer(SubsamplingLayer(kernel_size=2, stride=2))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss_function="mcxent"))
                .set_input_type(InputType.convolutional(12, 12, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        storage = InMemoryStatsStorage()
        probe = rng.rand(1, 12, 12, 1).astype("float32")
        net.set_listeners(
            StatsListener(storage, frequency=1, session_id="c1",
                          collect_histograms=False),
            ConvolutionalListener(storage, probe, frequency=1,
                                  session_id="c1"))
        x = rng.rand(8, 12, 12, 1).astype("float32")
        y = np.eye(3, dtype="float32")[rng.randint(0, 3, 8)]
        net.fit(DataSet(x, y))

        server = UIServer(port=0).attach(storage).start()
        try:
            status, body = self._get(server, "/activations")
            assert status == 200 and b"Convolutional activations" in body
            _, body = self._get(server, "/api/updates?sid=c1")
            ups = json.loads(body)
            conv = [u for u in ups if "conv_activations" in u]
            assert conv, "no activation sample reached storage"
            grids = conv[-1]["conv_activations"]
            assert "layer_0" in grids
            g = grids["layer_0"]
            assert len(g["channels"]) == 6
            assert len(g["channels"][0]) == g["h"] * g["w"]
        finally:
            server.stop()


def test_tsne_post_gated_by_enable_remote():
    """HTTP t-SNE writes follow the same explicit-enable policy as /remote."""
    server = UIServer(port=0).attach(InMemoryStatsStorage()).start()
    try:
        req = urllib.request.Request(
            server.url.rstrip("/") + "/api/tsne",
            data=json.dumps({"coords": [[0.0, 1.0]]}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 403
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Observability core (deeplearning4j_tpu/observability/): metrics registry,
# span tracing, StepProfiler, and the wired-through endpoints.
# ---------------------------------------------------------------------------


def _http_get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read()


class TestMetricsRegistry:
    def test_counter_gauge_histogram_values(self):
        from deeplearning4j_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry()
        c = reg.counter("req_total", "requests", label_names=("code",))
        c.labels(code="200").inc()
        c.labels(code="200").inc(2)
        c.labels(code="500").inc()
        assert c.labels(code="200").get() == 3
        assert c.labels(code="500").get() == 1
        g = reg.gauge("depth", "queue depth")
        g.set(7)
        assert g.get() == 7
        h = reg.histogram("lat", "latency", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        buckets, cum, s, count = h._only().histogram_state()
        assert buckets == (0.1, 1.0)
        assert cum == [1, 2, 3] and count == 3
        assert abs(s - 5.55) < 1e-9

    def test_prometheus_text_format_conformance(self):
        """Text format 0.0.4: HELP/TYPE lines, escaped label values,
        cumulative _bucket series ending at +Inf == _count, _sum/_count."""
        from deeplearning4j_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry()
        c = reg.counter("odd_total", "counts odd things",
                        label_names=("name",))
        c.labels(name='a"b\\c\nd').inc()
        h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = reg.to_prometheus()
        lines = text.strip().split("\n")
        assert "# HELP odd_total counts odd things" in lines
        assert "# TYPE odd_total counter" in lines
        # Escaping: backslash, double-quote, newline within the label value.
        assert 'odd_total{name="a\\"b\\\\c\\nd"} 1' in lines
        assert "# TYPE lat_seconds histogram" in lines
        assert 'lat_seconds_bucket{le="0.1"} 1' in lines
        assert 'lat_seconds_bucket{le="1"} 2' in lines
        assert 'lat_seconds_bucket{le="+Inf"} 2' in lines
        assert any(l.startswith("lat_seconds_sum ") for l in lines)
        assert "lat_seconds_count 2" in lines
        # Buckets are cumulative and non-decreasing.
        vals = [int(l.rsplit(" ", 1)[1]) for l in lines
                if l.startswith("lat_seconds_bucket")]
        assert vals == sorted(vals)

    def test_family_dedupe_and_kind_mismatch(self):
        from deeplearning4j_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry()
        a = reg.counter("x_total", "x", label_names=("k",))
        b = reg.counter("x_total", "ignored", label_names=("k",))
        assert a is b
        with pytest.raises(ValueError):
            reg.gauge("x_total")
        with pytest.raises(ValueError):
            reg.counter("x_total", label_names=("other",))
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            a.labels(wrong="v")

    def test_json_snapshot_and_summary(self):
        from deeplearning4j_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry()
        h = reg.histogram("s_seconds", "steps", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.2, 0.3, 2.0):
            h.observe(v)
        snap = reg.to_json()
        series = snap["s_seconds"]["series"][0]
        assert series["count"] == 4
        summary = series["summary"]
        assert summary["count"] == 4 and summary["mean"] == pytest.approx(
            2.55 / 4)
        assert 0 < summary["p50"] <= 1.0
        reg.reset()  # values drop to zero; the family itself survives
        assert reg.to_json()["s_seconds"]["series"][0]["count"] == 0

    def test_scrape_time_gauge_and_collector(self):
        from deeplearning4j_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry()
        g = reg.gauge("fn_gauge", "from function")
        g.set_function(lambda: 42.0)
        calls = []
        reg.register_collector(lambda r: calls.append(1))
        text = reg.to_prometheus()
        assert "fn_gauge 42" in text
        assert calls  # collector ran at scrape

    def test_disabled_registry_records_nothing(self):
        from deeplearning4j_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry(enabled=False)
        c = reg.counter("n_total")
        c.inc(5)
        h = reg.histogram("h_seconds")
        h.observe(1.0)
        assert c.get() == 0
        assert h._only().histogram_state()[3] == 0


class TestDisabledOverhead:
    def test_noop_path_is_cheap(self):
        """The ISSUE 2 bar: a disabled registry/tracer adds < a few µs per
        call. Budget is generous (10µs) for noisy shared CI machines; the
        real cost is one attribute load + bool check (~0.1µs)."""
        import time as _t

        from deeplearning4j_tpu.observability.metrics import MetricsRegistry
        from deeplearning4j_tpu.observability.tracing import Tracer

        reg = MetricsRegistry(enabled=False)
        c = reg.counter("o_total")
        h = reg.histogram("o_seconds")
        tr = Tracer(enabled=False)
        n = 20000
        t0 = _t.perf_counter()
        for _ in range(n):
            c.inc()
            h.observe(0.5)
            with tr.span("x"):
                pass
        per_call = (_t.perf_counter() - t0) / (3 * n)
        assert per_call < 10e-6, f"{per_call * 1e6:.2f}µs per disabled call"
        assert c.get() == 0 and not tr.events()


class TestTracing:
    def test_nested_spans_and_chrome_export(self):
        from deeplearning4j_tpu.observability.tracing import Tracer

        tr = Tracer()
        with tr.span("outer", cat="test"):
            with tr.span("inner", cat="test", k="v"):
                pass
        doc = tr.export_chrome()
        # Valid Chrome trace JSON: round-trips and has the required fields.
        doc2 = json.loads(json.dumps(doc))
        assert doc2["traceEvents"]
        by_name = {e["name"]: e for e in doc2["traceEvents"]}
        inner, outer = by_name["inner"], by_name["outer"]
        for e in (inner, outer):
            assert e["ph"] == "X"
            assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert inner["args"]["parent"] == "outer"
        assert inner["args"]["k"] == "v"
        assert "parent" not in outer["args"]
        # Inner is contained within outer on the timeline.
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3

    def test_ring_buffer_bounded(self):
        from deeplearning4j_tpu.observability.tracing import Tracer

        tr = Tracer(max_events=16)
        for i in range(100):
            with tr.span(f"s{i}"):
                pass
        events = tr.events()
        assert len(events) == 16
        assert events[-1]["name"] == "s99"  # newest kept, oldest dropped

    def test_decorator_error_attr_and_instant(self):
        from deeplearning4j_tpu.observability.tracing import Tracer

        tr = Tracer()

        @tr.trace("worker")
        def work():
            return 5

        assert work() == 5
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        tr.instant("marker", note="here")
        names = {e["name"]: e for e in tr.events()}
        assert "worker" in names
        assert names["boom"]["args"]["error"] == "ValueError"
        assert names["marker"]["ph"] == "i"


class TestComposableEpochHooks:
    def test_composable_fans_out_epoch_hooks(self, rng):
        """Regression lock for the ISSUE 2 satellite: composed listeners
        must see on_epoch_start/on_epoch_end, not just iteration_done."""
        from deeplearning4j_tpu.optimize.listeners import (
            ComposableIterationListener,
        )

        class Recorder(IterationListener):
            def __init__(self):
                self.calls = []

            def iteration_done(self, model, iteration):
                self.calls.append("iter")

            def on_epoch_start(self, model):
                self.calls.append("start")

            def on_epoch_end(self, model):
                self.calls.append("end")

        a, b = Recorder(), Recorder()
        net = mlp_net()
        net.set_listeners(ComposableIterationListener(a, b))
        x, y = batch(rng)
        net.fit(DataSet(x, y))
        for r in (a, b):
            assert r.calls == ["start", "iter", "end"]


class TestPerformanceListenerHonesty:
    def test_no_stale_samples_per_sec(self, rng):
        """An interval without record_batch must report NaN, not the
        previous interval's number."""
        from deeplearning4j_tpu.optimize.listeners import PerformanceListener

        listener = PerformanceListener(frequency=1, out=lambda s: None)
        net = mlp_net()
        net.set_listeners(listener)
        x, y = batch(rng)
        net.fit(DataSet(x, y))  # primes the clock
        listener.record_batch(16)
        net.fit(DataSet(x, y))
        assert listener.last_samples_per_sec > 0
        net.fit(DataSet(x, y))  # no record_batch this interval
        assert np.isnan(listener.last_samples_per_sec)
        assert listener.last_batches_per_sec > 0  # still per-iteration

    def test_sync_knob_settles_before_sampling(self, rng):
        from deeplearning4j_tpu.optimize.listeners import PerformanceListener

        msgs = []
        listener = PerformanceListener(frequency=1, sync=True,
                                       out=msgs.append)
        net = mlp_net()
        net.set_listeners(listener)
        x, y = batch(rng)
        for _ in range(3):
            net.fit(DataSet(x, y))
        assert msgs and listener.last_batches_per_sec > 0


class TestStepProfilerAcceptance:
    def test_smoke_run_metrics_and_trace(self, rng, tmp_path):
        """The ISSUE 2 acceptance smoke: fit a small MLP under StepProfiler
        with an in-fit checkpoint save, serve a request through
        InferenceServer, then assert the /metrics scrape carries the
        step-latency histogram, the compile-vs-execute split, checkpoint
        bytes, and request latency — and the exported trace nests
        fit -> iteration -> checkpoint."""
        from deeplearning4j_tpu import observability as obs
        from deeplearning4j_tpu.checkpoint import CheckpointManager
        from deeplearning4j_tpu.observability import StepProfiler
        from deeplearning4j_tpu.serving import InferenceServer

        obs.tracer.clear()
        net = mlp_net()
        mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)

        class SaveAt(IterationListener):
            def iteration_done(self, model, iteration):
                if iteration == 3:
                    mgr.save(model, step=iteration)

        net.set_listeners(SaveAt())
        x, y = batch(rng)
        with StepProfiler(net, sync=True) as prof:
            for _ in range(5):
                net.fit(DataSet(x, y))

        # One adam-updated fit so the kernel dispatch seam (ISSUE 10)
        # resolves `fused_update` and its counter carries a child for the
        # scrape assertion below (mlp_net's sgd never enters the seam).
        adam_conf = (NeuralNetConfiguration.builder()
                     .seed(7).learning_rate(0.1).updater("adam")
                     .list()
                     .layer(DenseLayer(n_out=8, activation="tanh"))
                     .layer(OutputLayer(n_out=3, activation="softmax",
                                        loss_function="mcxent"))
                     .set_input_type(InputType.feed_forward(4))
                     .build())
        MultiLayerNetwork(adam_conf).init().fit(DataSet(x, y))
        summary = prof.summary()
        assert summary["steps"] == 5
        assert summary["first_call_steps"] >= 1
        assert summary["compile_seconds"] > 0
        assert summary["execute_seconds_median"] > 0
        assert summary["host_to_device_bytes"] > 0

        server = InferenceServer(net, port=0).start()
        # One LoRA tenant next to the resident base, exercised through one
        # adapter-routed predict, so the multi-tenant metric families
        # (ISSUE 16) carry children for the scrape assertion below.
        from deeplearning4j_tpu.nn.transfer import TransferLearning

        server.load_adapter(
            "tenant-a", net=TransferLearning(net).add_lora(rank=1).build())
        try:
            req = urllib.request.Request(
                server.url + "/predict",
                data=json.dumps({"data": x[:4].tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                preds = json.loads(r.read())["predictions"]
            assert len(preds) == 4
            req = urllib.request.Request(
                server.url + "/predict",
                data=json.dumps({"data": x[:4].tolist(),
                                 "adapter": "tenant-a"}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                assert len(json.loads(r.read())["predictions"]) == 4
            status, body = _http_get(server.url + "/metrics")
        finally:
            server.stop()
        assert status == 200
        scrape = body.decode()
        for needle in (
                "dl4j_step_latency_seconds_bucket",       # step histogram
                "dl4j_profiler_compile_seconds",          # compile vs ...
                "dl4j_profiler_execute_seconds_median",   # ... execute split
                "dl4j_checkpoint_bytes_written_total",    # checkpoint bytes
                "dl4j_request_latency_seconds_bucket",    # request histogram
                "dl4j_serving_batch_size_bucket",
                'dl4j_jit_cache_misses_total{engine="mln"}',
                "dl4j_kernel_dispatch_total{",    # kernel registry seam
                "dl4j_train_flops_per_step",
                "dl4j_program_hbm_bytes",                 # static HBM gauges
                "dl4j_input_wait_seconds_bucket",         # starvation split
                "dl4j_adapters_resident{",                # LoRA tenants ...
                "dl4j_adapter_requests_total{",           # ... routed
                'adapter="tenant-a"',
        ):
            assert needle in scrape, f"missing {needle} in /metrics"

        # Bucket-ladder audit: every histogram family with observations must
        # resolve the majority of them inside its finite ladder — a family
        # whose observations mostly clamp into +Inf is measuring nothing.
        for name, fam in obs.metrics.to_json().items():
            if fam["type"] != "histogram":
                continue
            for series in fam["series"]:
                count = series["count"]
                if not count:
                    continue
                finite = max(series["buckets"].values(), default=0)
                assert count - finite <= count / 2, (
                    f"{name}{series['labels']}: {count - finite}/{count} "
                    "observations beyond the largest finite bucket — widen "
                    "the ladder (WIDE_BUCKETS)")

        doc = json.loads(json.dumps(obs.tracer.export_chrome()))
        events = doc["traceEvents"]
        assert events
        for e in events:
            assert e["ph"] in ("X", "i")
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        edges = {(e["name"], e["args"].get("parent")) for e in events
                 if e["ph"] == "X"}
        assert ("mln.iteration", "mln.fit") in edges
        assert ("checkpoint.snapshot", "mln.iteration") in edges
        assert ("checkpoint.write", "mln.iteration") in edges
        assert any(e["name"] == "serving.batch" for e in events)


class TestUIServerObsRoutes:
    def test_metrics_trace_and_route_index(self):
        from deeplearning4j_tpu import observability as obs

        obs.metrics.counter("ui_probe_total", "probe").inc()
        with obs.tracer.span("ui.probe"):
            pass
        server = UIServer(port=0).attach(InMemoryStatsStorage()).start()
        base = server.url.rstrip("/")
        try:
            status, body = _http_get(base + "/metrics")
            assert status == 200
            assert "# TYPE ui_probe_total counter" in body.decode()
            status, body = _http_get(base + "/api/trace")
            doc = json.loads(body)
            assert any(e["name"] == "ui.probe" for e in doc["traceEvents"])
            status, body = _http_get(base + "/api/flight")
            flight = json.loads(body)
            assert {"enabled", "capacity", "records",
                    "dump_dir"} <= set(flight)
            status, body = _http_get(base + "/api/memory")
            memdoc = json.loads(body)
            assert {"programs", "live"} <= set(memdoc)
            status, body = _http_get(base + "/api")
            routes = json.loads(body)["routes"]
            assert "/metrics" in routes and "/api/trace" in routes
            assert "/api/flight" in routes and "/api/memory" in routes
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/definitely/not/a/route",
                                       timeout=5)
            assert ei.value.code == 404
            nf = json.loads(ei.value.read())
            assert nf["error"] == "not found"
            assert "/metrics" in nf["routes"]  # 404s advertise the index
        finally:
            server.stop()


# ------------------------------------------------------- device identity


class TestChipPeaks:
    def test_unknown_device_raises_and_no_environment_override(
            self, monkeypatch):
        from deeplearning4j_tpu.observability import (chip_peak_flops,
                                                      chip_peak_hbm_bw)
        from deeplearning4j_tpu.observability.profiler import (
            CHIP_PEAKS, UnknownDeviceError)

        for name in ("DL4J_TPU_PEAK_FLOPS", "BENCH_PEAK_FLOPS",
                     "DL4J_TPU_PEAK_HBM_BW", "BENCH_PEAK_HBM_BW"):
            monkeypatch.setenv(name, "1e12")
        # The CPU backend's device_kind ("cpu") is not a chip in the table.
        with pytest.raises(UnknownDeviceError, match="'cpu'"):
            chip_peak_flops()
        with pytest.raises(UnknownDeviceError, match="'cpu'"):
            chip_peak_hbm_bw()
        flops, bw, source = CHIP_PEAKS["TPU v5 lite"]
        assert (flops, bw) == (197e12, 819e9) and "TPU v5e" in source
        # Exact keys: nothing unlisted inherits a peak by containing "v5".
        assert "TPU v5 experimental" not in CHIP_PEAKS


_SCRAPE_CHILD = r"""
import json, sys
from deeplearning4j_tpu import observability as obs
from jax._src import xla_bridge
first = obs.metrics.to_prometheus()
untouched = not xla_bridge.backends_are_initialized()
import jax
jax.numpy.zeros(1).block_until_ready()
second = obs.metrics.to_prometheus()
pick = lambda text: [l for l in text.splitlines()
                     if l.startswith("dl4j_build_info{") and l.endswith(" 1")]
print(json.dumps({"untouched": untouched, "first": pick(first),
                  "second": pick(second)}))
"""


def test_build_info_scrape_does_not_initialise_a_backend():
    """A router, coordinator or manager that answers /metrics must not take
    the chip: the info gauge names the backend only once the process has
    initialised one itself."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRAPE_CHILD],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["untouched"], "serving the scrape initialised a backend"
    assert len(got["first"]) == 1 and 'backend="unknown"' in got["first"][0]
    assert len(got["second"]) == 1 and 'backend="cpu"' in got["second"][0]
