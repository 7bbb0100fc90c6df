"""Production serving tier (`deeplearning4j_tpu/serving/`).

Acceptance coverage for the serving-tier PR:

- continuous-batched generation is float-close (here: exactly equal,
  greedy and seeded) to the sequential `generate_lm(use_cache=True)` path,
  including under concurrent interleaved admission and slot recycling;
- admission is bounded and observable: full queues shed with 503 +
  `Retry-After`, expired/abandoned requests are DROPPED before the device
  sees them and counted under `dl4j_requests_total{outcome="timeout"}`;
- the token-ids dtype policy: ids models never round-trip through
  float32, fractional floats are a 400;
- cross-process zero-compile: a fresh process serving 2 models over a
  >= 3-bucket ladder from a warmed AOT store performs ZERO XLA compiles;
- multi-model hosting: per-model routing, HBM gauges, LRU eviction under
  a budget and transparent reload;
- one `/metrics` scrape carries the per-model SLO families.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (MultiLayerNetwork, NeuralNetConfiguration,
                                observability as obs)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.serving import (
    InferenceServer,
    InputValidationError,
    ServerOverloadedError,
    ShapeBucketBatcher,
    bucket_ladder,
    prompt_bucket_ladder,
)


def mlp_net(seed=1, n_in=3, n_out=2):
    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder()
         .seed(seed).learning_rate(0.1).weight_init("xavier")
         .list()
         .layer(DenseLayer(n_out=4, activation="tanh"))
         .layer(OutputLayer(n_out=n_out, activation="softmax",
                            loss_function="mcxent"))
         .set_input_type(InputType.feed_forward(n_in))
         .build())).init()


def _counter_total(name, **match):
    fam = obs.metrics.get_family(name)
    if fam is None:
        return 0.0
    return sum(c.get() for c in fam.children()
               if all(c.labels.get(k) == v for k, v in match.items()))


@pytest.fixture(scope="module")
def lm():
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = zoo.transformer_lm(vocab_size=17, t=16, d_model=16, n_heads=2,
                              n_blocks=1, decode_cache_length=32)
    return ComputationGraph(conf).init()


# ------------------------------------------------------ continuous batching


class TestContinuousGeneration:
    def test_greedy_and_seeded_match_sequential(self, lm):
        from deeplearning4j_tpu.models import zoo

        server = InferenceServer(lm, decode_slots=3)
        try:
            ref = zoo.generate_lm(lm, [1, 2, 3], 6, window=16,
                                  use_cache=True, temperature=0.0)
            assert server.generate([1, 2, 3], 6, temperature=0.0) == ref
            ref = zoo.generate_lm(lm, [4, 5], 5, window=16, use_cache=True,
                                  temperature=0.8, seed=7, top_k=5)
            assert server.generate([4, 5], 5, temperature=0.8, seed=7,
                                   top_k=5) == ref
        finally:
            server.stop()

    def test_concurrent_interleaved_and_slot_recycling(self, lm):
        """More concurrent generations than decode slots: sequences join
        mid-flight at step boundaries and recycle slots on completion —
        every result still exactly matches the sequential path."""
        from deeplearning4j_tpu.models import zoo

        server = InferenceServer(lm, decode_slots=2)
        try:
            results, errors = {}, []

            def run(i):
                try:
                    results[i] = server.generate([1 + i, 2 + i], 4 + i % 3,
                                                 temperature=0.0)
                except Exception as e:  # pragma: no cover - diagnostic
                    errors.append(e)

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors
            for i in range(6):
                ref = zoo.generate_lm(lm, [1 + i, 2 + i], 4 + i % 3,
                                      window=16, use_cache=True,
                                      temperature=0.0)
                assert results[i] == ref
        finally:
            server.stop()

    def test_drain_mode_matches_too(self, lm):
        from deeplearning4j_tpu.models import zoo

        server = InferenceServer(lm, decode_slots=2,
                                 scheduler_mode="drain")
        try:
            ref = zoo.generate_lm(lm, [3, 1], 5, window=16, use_cache=True,
                                  temperature=0.0)
            assert server.generate([3, 1], 5, temperature=0.0) == ref
        finally:
            server.stop()

    def test_capacity_and_deadline(self, lm):
        server = InferenceServer(lm, decode_slots=2)
        try:
            with pytest.raises(InputValidationError):
                server.generate([1] * 30, 10, temperature=0.0)
            # A deadline far shorter than prefill+decode: the request is
            # retired at a step boundary and surfaces as a timeout...
            with pytest.raises(TimeoutError):
                server.generate([1, 2], 28, temperature=0.0,
                                timeout_s=0.001)
            # ...and the slot is recycled — the next generation succeeds.
            out = server.generate([2, 3], 3, temperature=0.0)
            assert len(out) == 5
        finally:
            server.stop()


# --------------------------------------------------------------- admission


class _CountingNet:
    """Records each executed batch's row count."""

    def __init__(self, n_out=2):
        self.n_out = n_out
        self.batches = []

    def output(self, x):
        x = np.asarray(x)
        self.batches.append(x.shape[0])
        return np.zeros((x.shape[0], self.n_out), np.float32)


class TestAdmission:
    def test_bucket_ladders(self):
        assert bucket_ladder(32) == (1, 2, 4, 8, 16, 32)
        assert bucket_ladder(12, buckets=(4, 8)) == (4, 8, 12)
        assert prompt_bucket_ladder(64) == (8, 16, 32, 64)
        assert prompt_bucket_ladder(24, buckets=(8,)) == (8, 24)

    def test_full_queue_sheds_503(self):
        net = _CountingNet()
        batcher = ShapeBucketBatcher(net, model_name="shed-test",
                                     max_batch_size=2, queue_depth=2,
                                     warmup_shape=(3,))
        # Loop never started: the queue can only fill.
        row = np.zeros((1, 3), np.float32)
        batcher.submit(row, None)
        batcher.submit(row, None)
        with pytest.raises(ServerOverloadedError) as e:
            batcher.submit(row, None)
        assert e.value.status == 503
        assert e.value.retry_after == 1

    def test_http_shed_has_retry_after(self):
        server = InferenceServer(_CountingNet(), port=0, queue_depth=1,
                                 warmup_shape=(3,)).start()
        try:
            served = server.models.get(server.default_model)
            served.batcher.stop()  # freeze the drain so the queue fills
            time.sleep(0.05)
            served.batcher.submit(np.zeros((1, 3), np.float32), None)
            req = urllib.request.Request(
                server.url + "/predict",
                data=json.dumps({"data": [[0.0, 0.0, 0.0]]}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=10)
            assert e.value.code == 503
            assert e.value.headers.get("Retry-After") == "1"
        finally:
            server.stop()

    def test_cancelled_and_expired_dropped_before_device(self):
        """The timeout-abandonment fix: a request whose caller gave up (or
        whose deadline passed in the queue) is dropped at batch-build time
        — the model never executes it — and counted as a timeout."""
        net = _CountingNet()
        batcher = ShapeBucketBatcher(net, model_name="drop-test",
                                     max_batch_size=4, warmup_shape=(3,))
        before = _counter_total("dl4j_requests_total", model="drop-test",
                                outcome="timeout")
        abandoned = batcher.submit(np.zeros((1, 3), np.float32), None)
        abandoned.cancelled = True
        expired = batcher.submit(np.zeros((1, 3), np.float32),
                                 time.monotonic() - 1.0)
        live = batcher.submit(np.zeros((2, 3), np.float32), None)
        batcher._run_batch([abandoned, expired, live])
        assert net.batches == [2]  # only the live rows ran (bucket 2)
        assert live.result is not None
        assert abandoned.event.is_set() and expired.event.is_set()
        assert expired.error == "__deadline__"
        after = _counter_total("dl4j_requests_total", model="drop-test",
                               outcome="timeout")
        assert after == before + 2

    def test_caller_timeout_cancels_and_next_batch_skips(self):
        """End-to-end: A occupies the (slow) device, B's caller times out
        while queued; when the loop builds the next batch it drops B."""
        class Slow(_CountingNet):
            def output(self, x):
                time.sleep(0.25)
                return super().output(x)

        net = Slow()
        server = InferenceServer(net, max_delay_ms=1.0, warmup_shape=(3,))
        try:
            row = [[0.0, 0.0, 0.0]]
            a = threading.Thread(target=server.predict, args=(row,))
            a.start()
            time.sleep(0.05)  # A's batch is executing
            with pytest.raises(TimeoutError) as e:
                server.predict(row, timeout_s=0.05)
            assert "predict_timeout_s" in str(e.value)
            a.join()
            time.sleep(0.4)  # let the loop drain the cancelled entry
            assert net.batches == [1]  # B never reached the model
        finally:
            server.stop()

    def test_concurrent_predicts_all_complete(self):
        net = mlp_net()
        server = InferenceServer(net, max_batch_size=4, max_delay_ms=2.0)
        try:
            X = np.random.RandomState(0).rand(12, 3).astype(np.float32)
            full = np.asarray(net.output(X))
            results = {}

            def call(i):
                results[i] = server.predict(X[i:i + 1])

            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, p in results.items():
                np.testing.assert_allclose(p[0], full[i], rtol=1e-5,
                                           atol=1e-6)
        finally:
            server.stop()


# ------------------------------------------------------------ dtype policy


class TestInputDtypePolicy:
    def test_ids_model_keeps_integer_precision(self, lm):
        server = InferenceServer(lm, max_batch_size=4)
        try:
            ids = np.array([[1, 2, 3, 4, 5, 6, 7, 8]], np.int64)
            out = server.predict(ids)
            ref = np.asarray(
                lm.output(ids.astype(np.float32)[..., None])[0])
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
        finally:
            server.stop()

    def test_fractional_floats_rejected_400(self, lm):
        server = InferenceServer(lm, max_batch_size=4)
        try:
            with pytest.raises(InputValidationError) as e:
                server.predict([[1.5, 2.0, 3.0, 1.0, 1.0, 1.0, 1.0, 1.0]])
            assert e.value.status == 400
            with pytest.raises(InputValidationError):
                server.predict("definitely not features")
        finally:
            server.stop()

    def test_http_400_on_bad_dtype(self, lm):
        server = InferenceServer(lm, port=0, max_batch_size=4).start()
        try:
            req = urllib.request.Request(
                server.url + "/predict",
                data=json.dumps(
                    {"data": [[0.5, 1.0, 2.0, 3.0, 1.0, 1.0, 1.0,
                               1.0]]}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=10)
            assert e.value.code == 400
        finally:
            server.stop()

    def test_values_model_casts_float32(self):
        net = mlp_net()
        server = InferenceServer(net)
        try:
            out = server.predict([[0.25, 0.5, 0.75]])  # python lists
            assert out.dtype == np.float32
            assert out.shape == (1, 2)
        finally:
            server.stop()


# ------------------------------------------- cross-process zero compile


_CHILD_SCRIPT = r"""
import json, os
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.serving import InferenceServer

obs.install_jax_compile_hook(obs.metrics)

def mlp(seed, n_in, n_hidden):
    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder()
         .seed(seed).learning_rate(0.1).updater("sgd").weight_init("xavier")
         .list()
         .layer(DenseLayer(n_out=n_hidden, activation="relu"))
         .layer(OutputLayer(n_out=3, activation="softmax",
                            loss_function="mcxent"))
         .set_input_type(InputType.feed_forward(n_in))
         .build())).init()

server = InferenceServer(max_batch_size=8, max_delay_ms=1.0)  # 4 buckets
server.add_model("alpha", net=mlp(1, 4, 8))
server.add_model("beta", net=mlp(2, 6, 12))
for name in ("alpha", "beta"):
    server.models.get(name).batcher.warm()
if os.environ["CHILD_MODE"] == "traffic":
    # Mixed-shape traffic across both models: every request pads to a
    # pre-warmed bucket, so a warmed AOT store means zero compiles below.
    for name, n_in in (("alpha", 4), ("beta", 6)):
        for rows in (1, 2, 3, 5, 8):
            out = server.predict(np.zeros((rows, n_in), np.float32),
                                 model=name)
            assert out.shape == (rows, 3)
server.stop()

fam = obs.metrics.get_family("dl4j_xla_compiles_total")
total = sum(c.get() for c in fam.children()) if fam else 0.0
print(json.dumps({"xla_compiles": total,
                  "buckets": [1, 2, 4, 8]}))
"""


def _run_child(cache_dir, mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu", CHILD_MODE=mode)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env.pop("XLA_FLAGS", None)  # plain 1-device CPU child
    proc = subprocess.run([sys.executable, "-c", _CHILD_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


class TestZeroCompileServing:
    def test_two_models_four_buckets_zero_compiles_when_warm(self, tmp_path):
        cache = str(tmp_path / "serving-cache")
        cold = _run_child(cache, "warm")
        assert cold["xla_compiles"] > 0  # the warm child populates the store
        warm = _run_child(cache, "traffic")
        # The acceptance property: a fresh process serving mixed-shape
        # traffic for two models over a 4-bucket ladder never compiles —
        # every bucket replays from the AOT executable store.
        assert warm["xla_compiles"] == 0


# ------------------------------------------------------------- multi-model


class TestMultiModelHost:
    def _save(self, net, path):
        from deeplearning4j_tpu.checkpoint import CheckpointManager

        CheckpointManager(str(path), async_save=False).save(net)
        return str(path)

    def test_routing_and_v1_models(self, tmp_path):
        a, b = mlp_net(seed=1), mlp_net(seed=2)
        server = InferenceServer(port=0, max_delay_ms=1.0)
        server.add_model("a", path=self._save(a, tmp_path / "a"))
        server.add_model("b", path=self._save(b, tmp_path / "b"))
        server.start()
        try:
            x = np.random.RandomState(0).rand(3, 3).astype(np.float32)
            np.testing.assert_allclose(server.predict(x, model="a"),
                                       np.asarray(a.output(x)), rtol=1e-5)
            np.testing.assert_allclose(server.predict(x, model="b"),
                                       np.asarray(b.output(x)), rtol=1e-5)
            with urllib.request.urlopen(server.url + "/v1/models",
                                        timeout=10) as r:
                rows = {m["name"]: m for m in json.loads(r.read())["models"]}
            assert set(rows) == {"a", "b"}
            for row in rows.values():
                assert row["resident"] and row["hbm_bytes"] > 0
            # Unknown model routes to a 404, not a traceback 500.
            req = urllib.request.Request(
                server.url + "/predict",
                data=json.dumps({"data": x.tolist(),
                                 "model": "nope"}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=10)
            assert e.value.code == 404
        finally:
            server.stop()

    def test_lru_eviction_and_reload_under_budget(self, tmp_path):
        a, b = mlp_net(seed=1), mlp_net(seed=2)
        pa = self._save(a, tmp_path / "a")
        pb = self._save(b, tmp_path / "b")
        # A budget smaller than one model: at most one stays resident.
        server = InferenceServer(port=0, hbm_budget_bytes=1)
        server.add_model("a", path=pa)
        server.add_model("b", path=pb)
        try:
            snap = {m["name"]: m for m in server.models.snapshot()}
            assert snap["b"]["resident"] and not snap["a"]["resident"]
            ev0 = _counter_total("dl4j_serving_evictions_total")
            x = np.zeros((1, 3), np.float32)
            # Using "a" reloads it and LRU-evicts "b"...
            np.testing.assert_allclose(server.predict(x, model="a"),
                                       np.asarray(a.output(x)), rtol=1e-5)
            snap = {m["name"]: m for m in server.models.snapshot()}
            assert snap["a"]["resident"] and not snap["b"]["resident"]
            assert _counter_total("dl4j_serving_evictions_total") > ev0
            # ...and "b" still serves correct predictions after its reload.
            np.testing.assert_allclose(server.predict(x, model="b"),
                                       np.asarray(b.output(x)), rtol=1e-5)
        finally:
            server.stop()


# ----------------------------------------------------------------- metrics


class TestSLOMetricsScrape:
    def test_one_scrape_carries_per_model_slo_series(self, lm):
        server = InferenceServer(lm, port=0, max_batch_size=4,
                                 max_delay_ms=1.0).start()
        try:
            server.predict(np.array([[1, 2, 3, 4, 5, 6, 7, 8]], np.int32))
            server.generate([1, 2], 3, temperature=0.0)
            with urllib.request.urlopen(server.url + "/metrics",
                                        timeout=10) as r:
                scrape = r.read().decode()
        finally:
            server.stop()
        for needle in (
                # per-model/route SLO latency histograms (p50/p99 source)
                'dl4j_serving_request_seconds_bucket{model="default",'
                'route="predict"',
                'dl4j_serving_request_seconds_bucket{model="default",'
                'route="generate"',
                'dl4j_serving_ttft_seconds_bucket{model="default"',
                'dl4j_serving_itl_seconds_bucket{model="default"',
                'dl4j_serving_decode_step_seconds_bucket{model="default"',
                # outcome-labeled request counter
                'dl4j_requests_total{model="default",route="predict",'
                'outcome="ok"}',
                # queue-depth and HBM gauges
                'dl4j_serving_model_queue_depth{model="default",'
                'route="predict"}',
                'dl4j_serving_model_hbm_bytes{model="default"}',
                'dl4j_serving_generated_tokens_total{model="default"}',
                # legacy families survive unchanged
                "dl4j_request_latency_seconds_bucket",
                "dl4j_serving_batch_size_bucket",
        ):
            assert needle in scrape, f"missing {needle} in /metrics"

    def test_metrics_json_format(self, lm):
        server = InferenceServer(lm, port=0).start()
        try:
            with urllib.request.urlopen(
                    server.url + "/metrics?format=json", timeout=10) as r:
                assert r.headers.get_content_type() == "application/json"
                doc = json.loads(r.read())
            assert "dl4j_serving_model_hbm_bytes" in doc
        finally:
            server.stop()
