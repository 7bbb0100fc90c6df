"""Persistent compile cache + AOT warmup (`deeplearning4j_tpu/compilation/`).

Covers the acceptance criteria of the compile-cache PR: fingerprint
invalidation (config / static-args / mesh / version changes each force a
miss), corrupt-artifact fallback (warning + bit-identical results),
warmup-then-fit with ZERO first-batch traces in a fresh process (checked
via `dl4j_xla_compiles_total` in a subprocess), the CLI, and the serving
readiness protocol (`/healthz`, 503 while warming).
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
                                compilation)
from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.compilation import cache as cache_mod
from deeplearning4j_tpu.compilation import store as store_mod
from deeplearning4j_tpu.compilation import warmup as warmup_mod
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer


def mlp_conf(n_in=4, n_out=3, seed=42, lr=0.1):
    return (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(lr).updater("sgd")
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=n_out, activation="softmax",
                               loss_function="mcxent"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())


def small_dataset(n=16, n_in=4, n_out=3, seed=0):
    r = np.random.RandomState(seed)
    x = r.rand(n, n_in).astype("float32")
    y = np.eye(n_out, dtype="float32")[r.randint(0, n_out, n)]
    return DataSet(x, y)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """Fresh per-test cache root (the session default from conftest stays
    untouched); resets the store singleton on both sides. The program
    leaves `jax_compilation_cache_dir` alone when the environment names
    the directory (jax read it at import), so re-pointing jax's side
    mid-process is the test's job."""
    import jax

    d = str(tmp_path / "compile-cache")
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache_mod.JAX_ENV_DIR, d)
    jax.config.update("jax_compilation_cache_dir", d)
    compilation.reset()
    yield d
    jax.config.update("jax_compilation_cache_dir", before)
    compilation.reset()


def _counter_total(name, source=None):
    fam = obs.metrics.get_family(name)
    if fam is None:
        return 0.0
    total = 0.0
    for child in fam.children():
        if source is not None and child.labels.get("source") != source:
            continue
        total += child.get()
    return total


# ------------------------------------------------------------ fingerprint


class TestFingerprint:
    def _doc(self, net=None, static=None, ds=None):
        net = net or MultiLayerNetwork(mlp_conf())
        if not net._initialized:
            net.init()
        ds = ds or small_dataset()
        args = warmup_mod._mln_args(net, ds, "train_step")
        return store_mod.build_fingerprint_doc(net, "train_step",
                                               static or {}, args)

    def test_stable_for_identical_inputs(self):
        net = MultiLayerNetwork(mlp_conf())
        net.init()
        ds = small_dataset()
        fp1 = store_mod.fingerprint(self._doc(net, ds=ds))
        fp2 = store_mod.fingerprint(self._doc(net, ds=ds))
        assert fp1 == fp2

    def test_model_config_edit_forces_miss(self):
        base = store_mod.fingerprint(self._doc())
        edited = MultiLayerNetwork(mlp_conf(lr=0.2))
        edited.init()
        assert store_mod.fingerprint(self._doc(edited)) != base

    def test_superstep_k_change_forces_miss(self):
        net = MultiLayerNetwork(mlp_conf())
        net.init()
        ds = small_dataset()
        fp2 = store_mod.fingerprint(self._doc(net, {"k": 2}, ds))
        fp4 = store_mod.fingerprint(self._doc(net, {"k": 4}, ds))
        assert fp2 != fp4

    def test_mesh_context_forces_miss(self):
        import jax

        from deeplearning4j_tpu.parallel import mesh as mesh_mod
        from deeplearning4j_tpu.parallel.context import (ParallelContext,
                                                         parallel_context)

        net = MultiLayerNetwork(mlp_conf())
        net.init()
        ds = small_dataset()
        base = store_mod.fingerprint(self._doc(net, ds=ds))
        mesh = mesh_mod.create_mesh(devices=jax.devices()[:2])
        ctx = ParallelContext(mesh=mesh, data_axis=mesh.axis_names[0])
        with parallel_context(ctx):
            sharded = store_mod.fingerprint(self._doc(net, ds=ds))
        assert sharded != base

    def test_version_bump_forces_miss(self):
        doc = self._doc()
        bumped = dict(doc, jax="999.0.0")
        assert store_mod.fingerprint(bumped) != store_mod.fingerprint(doc)

    def test_batch_signature_forces_miss(self):
        net = MultiLayerNetwork(mlp_conf())
        net.init()
        fp16 = store_mod.fingerprint(self._doc(net, ds=small_dataset(16)))
        fp32 = store_mod.fingerprint(self._doc(net, ds=small_dataset(32)))
        assert fp16 != fp32


# ------------------------------------------------------- store + fallback


class TestAOTStoreFallback:
    def test_warmup_writes_artifacts(self, cache_dir):
        net = MultiLayerNetwork(mlp_conf())
        net.init()
        summary = net.warmup(small_dataset())
        assert summary["programs"] >= 3
        assert summary["compiled"] + summary["aot"] >= 3
        aot = os.path.join(cache_dir, "aot")
        assert any(f.endswith(".jaxec") for f in os.listdir(aot))

    def test_corrupt_artifact_warns_and_falls_back(self, cache_dir):
        ds = small_dataset()
        net = MultiLayerNetwork(mlp_conf())
        net.init()
        net.warmup(ds, kinds=["output"])
        aot = os.path.join(cache_dir, "aot")
        for name in os.listdir(aot):
            if name.endswith(".jaxec"):
                with open(os.path.join(aot, name), "wb") as f:
                    f.write(b"\x00corrupt garbage\xff")
        compilation.reset()  # fresh store: drop the in-process executables

        fresh = MultiLayerNetwork(mlp_conf())
        fresh.init()
        with pytest.warns(UserWarning, match="unusable AOT"):
            out = np.asarray(fresh.output(ds.features))

        clean = MultiLayerNetwork(mlp_conf())
        clean.init()
        expected = np.asarray(clean.output(ds.features))
        np.testing.assert_array_equal(out, expected)

    def test_disabled_knob_returns_raw_program(self, monkeypatch):
        monkeypatch.setenv(compilation.ENV_KNOB, "0")
        compilation.reset()
        try:
            assert compilation.cache_root() is None
            assert compilation.get_store() is None
            sentinel = object()
            assert compilation.wrap_program(sentinel, None, "output",
                                            {}) is sentinel
        finally:
            monkeypatch.undo()
            compilation.reset()


class TestCompileErrorsPropagate:
    def test_cached_program_does_not_fall_back_to_plain_jit(self, cache_dir):
        """Only the store may degrade to a miss, never the compile: the
        plain jit path would compile the same program again, and fail
        again at the first request behind a server that called itself
        warm."""
        class _Refuses:
            calls = 0

            def lower(self, *args):
                raise RuntimeError("RESOURCE_EXHAUSTED: vmem")

            def __call__(self, *args):
                _Refuses.calls += 1

        net = MultiLayerNetwork(mlp_conf())
        program = compilation.CachedProgram(_Refuses(), net, "output", {})
        x = np.zeros((2, 4), np.float32)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            program.warm(x)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            program(x)
        assert _Refuses.calls == 0 and program.executables() == []


class TestStoredProgramDevices:
    """JAX 0.9 loads a deserialized executable for EVERY device of the
    backend unless told otherwise; on a several-device host a stored
    one-device program then died at its first call ("expected 8 shards").
    The store records each artifact's device ids and loads for those."""

    def _round_trip(self, cache_dir, fn, *args):
        import jax
        import jaxlib

        compiled = jax.jit(fn).lower(*args).compile()
        store = store_mod.AOTStore(cache_dir)
        assert store.save("f" * 64, compiled, {"jax": jax.__version__,
                                               "jaxlib": jaxlib.__version__})
        loaded = store.load("f" * 64)
        assert loaded is not None
        return compiled, loaded

    def test_one_device_program_on_a_several_device_host(self, cache_dir):
        import jax
        import jax.numpy as jnp

        assert len(jax.devices()) >= 8
        dev = jax.devices()[3]
        x = jax.device_put(jnp.arange(12.0).reshape(3, 4), dev)
        compiled, loaded = self._round_trip(
            cache_dir, lambda a: jnp.tanh(a) @ a.T, x)
        out = loaded(x)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(compiled(x)))
        assert out.sharding.device_set == {dev}

    def test_two_device_mesh_program(self, cache_dir):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = [jax.devices()[5], jax.devices()[2]]  # order is the assignment
        sharding = NamedSharding(Mesh(np.array(devs), ("m",)), P("m"))
        x = jax.device_put(jnp.arange(32.0).reshape(8, 4), sharding)
        compiled, loaded = self._round_trip(
            cache_dir, lambda a: (a * 2.0).sum(axis=1), x)
        out = loaded(x)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(compiled(x)))
        assert out.sharding.device_set == set(devs)

    def test_missing_device_is_a_miss_at_load_time(self, cache_dir):
        import pickle

        import jax
        import jax.numpy as jnp

        x = jnp.ones((4, 4))
        self._round_trip(cache_dir, lambda a: a + 1.0, x)
        path = os.path.join(cache_dir, "aot", "f" * 64 + ".jaxec")
        with open(path, "rb") as f:
            blob = pickle.load(f)
        blob["device_ids"] = [len(jax.devices()) + 7]
        with open(path, "wb") as f:
            pickle.dump(blob, f)
        with pytest.warns(UserWarning, match="has no device"):
            assert store_mod.AOTStore(cache_dir).load("f" * 64) is None


# ------------------------------------------------------------- placement

_PLACEMENT_CHILD = r"""
import json, os
import jax
as_jax_set_it = jax.config.jax_compilation_cache_dir
import numpy as np
from deeplearning4j_tpu import (MultiLayerNetwork, NeuralNetConfiguration,
                                compilation)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer

written = []
if os.environ.get("CHILD_COMPILES"):
    conf = (NeuralNetConfiguration.builder().seed(1).learning_rate(0.1)
            .updater("sgd").list()
            .layer(DenseLayer(n_out=4, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss_function="mcxent"))
            .set_input_type(InputType.feed_forward(3)).build())
    net = MultiLayerNetwork(conf).init()
    net.warmup(DataSet(np.zeros((2, 3), "float32"),
                       np.zeros((2, 2), "float32")), kinds=["output"])
    root = compilation.cache_root()
    written = sorted(os.path.relpath(os.path.join(d, f), root)
                     for d, _, fs in os.walk(root) for f in fs)
store = compilation.get_store()
print(json.dumps({
    "as_jax_set_it": as_jax_set_it,
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "root": compilation.cache_root(),
    "aot_dir": None if store is None else store.root,
    "written": written,
}))
"""


def _placement_child(env_dir, compiles):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("CHILD_COMPILES", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    if compiles:
        env["CHILD_COMPILES"] = "1"
    proc = subprocess.run([sys.executable, "-c", _PLACEMENT_CHILD],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


class TestCachePlacement:
    """Where the cache lives is decided from outside the program."""

    def test_environment_places_both_layers(self, tmp_path):
        placed = str(tmp_path / "placed")
        got = _placement_child(placed, compiles=True)
        # The program did not touch what jax read from the environment.
        assert got["as_jax_set_it"] == placed
        assert got["jax_dir"] == placed
        assert got["root"] == placed
        assert got["aot_dir"] == os.path.join(placed, "aot")
        assert any(w.startswith("aot" + os.sep) and w.endswith(".jaxec")
                   for w in got["written"])
        assert any(not w.startswith("aot" + os.sep)
                   for w in got["written"]), "no jax cache entry written"

    def test_unset_means_the_fixed_checkout_path(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        fixed = os.path.join(repo, ".dl4j_compile_cache")
        # No compile here: the checkout's cache stays as the user left it.
        first = _placement_child(None, compiles=False)
        assert first["as_jax_set_it"] is None
        assert first["root"] == fixed == compilation.checkout_cache_dir()
        assert first["jax_dir"] == os.path.join(fixed, "xla")
        assert first["aot_dir"] == os.path.join(fixed, "aot")
        # Fixed means fixed: no pid, time or temporary name in either path
        # (the path is part of jax's cache key; one that moves never hits).
        assert _placement_child(None, compiles=False) == first

    def test_the_old_knob_only_switches_off(self, monkeypatch, tmp_path):
        monkeypatch.setenv(compilation.ENV_KNOB, str(tmp_path))
        with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
            compilation.cache_root()
        for off in ("off", "0", "false", ""):
            monkeypatch.setenv(compilation.ENV_KNOB, off)
            assert compilation.cache_root() is None


# ---------------------------------------------------------------- warmup


class TestWarmup:
    def test_warmup_then_fit_compiles_nothing_new(self, cache_dir):
        obs.install_jax_compile_hook(obs.metrics)
        ds = small_dataset()
        net = MultiLayerNetwork(mlp_conf())
        net.init()
        params_before = [np.asarray(p) for p in
                         __import__("jax").tree_util.tree_leaves(
                             net.params_tree)]
        net.warmup(ds)
        params_after = [np.asarray(p) for p in
                        __import__("jax").tree_util.tree_leaves(
                            net.params_tree)]
        for a, b in zip(params_before, params_after):
            np.testing.assert_array_equal(a, b)

        compiles_before = _counter_total("dl4j_xla_compiles_total")
        net.fit(ds)
        net.output(ds.features)
        assert _counter_total("dl4j_xla_compiles_total") == compiles_before

    def test_background_warmup_thread(self, cache_dir):
        net = MultiLayerNetwork(mlp_conf())
        net.init()
        thread = net.warmup(small_dataset(), background=True)
        thread.join(timeout=120)
        assert not thread.is_alive()
        assert thread.warmup_error is None
        assert thread.warmup_result["programs"] >= 3

    def test_synthetic_dataset_from_input_type(self):
        net = MultiLayerNetwork(mlp_conf())
        ds = warmup_mod.synthetic_dataset(net, 8)
        assert np.asarray(ds.features).shape == (8, 4)
        assert np.asarray(ds.labels).shape == (8, 3)


_CHILD_SCRIPT = r"""
import json, os
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer

obs.install_jax_compile_hook(obs.metrics)
conf = (NeuralNetConfiguration.builder()
        .seed(42).learning_rate(0.1).updater("sgd").weight_init("xavier")
        .list()
        .layer(DenseLayer(n_out=8, activation="relu"))
        .layer(OutputLayer(n_out=3, activation="softmax",
                           loss_function="mcxent"))
        .set_input_type(InputType.feed_forward(4))
        .build())
net = MultiLayerNetwork(conf)
net.init()
r = np.random.RandomState(0)
x = r.rand(16, 4).astype("float32")
y = np.eye(3, dtype="float32")[r.randint(0, 3, 16)]
ds = DataSet(x, y)
mode = os.environ["CHILD_MODE"]
if mode == "warm":
    net.warmup(ds)
else:
    net.fit(ds)
    net.output(x)

def total(name, source=None):
    fam = obs.metrics.get_family(name)
    if fam is None:
        return 0.0
    return sum(c.get() for c in fam.children()
               if source is None or c.labels.get("source") == source)

print(json.dumps({
    "xla_compiles": total("dl4j_xla_compiles_total"),
    "aot_hits": total("dl4j_compile_cache_hits_total", "aot"),
    "aot_misses": total("dl4j_compile_cache_misses_total", "aot"),
}))
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


class TestCrossProcessWarmStart:
    def test_populated_cache_means_zero_traces_in_fresh_process(
            self, tmp_path):
        cache = str(tmp_path / "shared-cache")
        cold = _run_child(cache, "warm")
        assert cold["xla_compiles"] > 0
        assert cold["aot_misses"] > 0
        warm = _run_child(cache, "fit")
        # The whole point of the PR: a fresh process replays every seen
        # program from the executable store — zero full XLA traces.
        assert warm["xla_compiles"] == 0
        assert warm["aot_hits"] >= 2  # train_step + output at minimum


class TestWarmupCLI:
    def test_cli_smoke(self, tmp_path):
        from deeplearning4j_tpu.checkpoint import save_checkpoint

        net = MultiLayerNetwork(mlp_conf())
        net.init()
        ckpt = str(tmp_path / "ckpt")
        save_checkpoint(net, ckpt)
        cache = str(tmp_path / "cli-cache")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=cache)
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, "-m", "deeplearning4j_tpu.compilation.warmup",
             ckpt, "--batch-size", "4"],
            capture_output=True, text=True, env=env, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        summary = json.loads(proc.stdout.strip().splitlines()[-1])
        assert summary["programs"] >= 1
        assert summary["cache_dir"] == cache
        assert os.path.isdir(os.path.join(cache, "aot"))
        assert any(f.endswith(".jaxec")
                   for f in os.listdir(os.path.join(cache, "aot")))


# --------------------------------------------------------------- serving


class _BlockingNet:
    """output() blocks until released — holds the server in "warming"."""

    def __init__(self):
        self.release = threading.Event()
        self.calls = 0

    def output(self, x):
        self.calls += 1
        if self.calls == 1:  # only the warmup batch blocks
            self.release.wait(timeout=60)
        return np.zeros((np.asarray(x).shape[0], 2), np.float32)


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read())


class TestServingWarmup:
    def test_healthz_and_503_while_warming(self):
        from deeplearning4j_tpu.serving import InferenceServer

        net = _BlockingNet()
        server = InferenceServer(net, max_batch_size=4, warmup=True,
                                 warmup_shape=(3,),
                                 predict_timeout_s=30.0).start()
        try:
            deadline = time.monotonic() + 10
            while (server._status != "warming"
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert _get_json(server.url + "/healthz")["status"] == "warming"

            req = urllib.request.Request(
                server.url + "/predict",
                data=json.dumps({"data": [[0.0, 0.0, 0.0]]}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(req, timeout=10)
            assert exc_info.value.code == 503
            assert exc_info.value.headers.get("Retry-After") == "1"

            net.release.set()
            assert server.wait_ready(timeout=30)
            assert _get_json(server.url + "/healthz")["status"] == "ready"
            with urllib.request.urlopen(req, timeout=30) as resp:
                preds = json.loads(resp.read())["predictions"]
            assert len(preds) == 1
        finally:
            net.release.set()
            server.stop()

    def test_failed_warmup_is_reported_not_survived_in_silence(self):
        """A warm-up that raised (on the chip: a kernel the compiler
        refuses) used to flip the server to "ready" all the same."""
        from deeplearning4j_tpu.serving import InferenceServer

        class _Refused:
            def output(self, x):
                raise RuntimeError("Mosaic failed to compile TPU kernel")

        server = InferenceServer(_Refused(), max_batch_size=2, warmup=True,
                                 warmup_shape=(3,))
        with pytest.warns(UserWarning, match="serving warmup failed"):
            server.start()
            with pytest.raises(RuntimeError, match="serving warmup failed"):
                server.wait_ready(timeout=30)
        try:
            assert _get_json(server.url + "/healthz")["status"] == "failed"
        finally:
            server.stop()

    def test_warmed_first_request_latency_near_steady_state(self, cache_dir):
        from deeplearning4j_tpu.serving import InferenceServer

        net = MultiLayerNetwork(mlp_conf())
        net.init()
        server = InferenceServer(net, max_batch_size=8, max_delay_ms=1.0,
                                 warmup=True).start()
        try:
            assert server.wait_ready(timeout=120)
            fam = obs.metrics.get_family("dl4j_request_latency_seconds")
            count0 = fam.summarize().get("count", 0)
            row = [[0.1, 0.2, 0.3, 0.4]]
            times = []
            for _ in range(6):
                t0 = time.perf_counter()
                server.predict(row)
                times.append(time.perf_counter() - t0)
            assert fam.summarize()["count"] == count0 + 6
            steady = sorted(times[1:])[len(times[1:]) // 2]
            # Warmed: the first request pays no XLA compile, so it sits
            # within 2x of steady state (floor absorbs scheduler noise on
            # sub-millisecond CPU batches).
            assert times[0] <= max(2.0 * steady, 0.25)
        finally:
            server.stop()


# ------------------------------------------- fused-bottleneck serving warmup


class TestBottleneckServingWarmup:
    """PR 19 regression: `warmup_buckets` (the serving batcher's warm path)
    must warm the fused `BottleneckBlock` layer's resolved kernel signature
    for resnet-family checkpoints — an int8-quantized fused checkpoint then
    serves over HTTP with ZERO XLA compiles across the bucket ladder."""

    def _fused_conf(self):
        from deeplearning4j_tpu.models.resnet import (_bottleneck_fused,
                                                      _conv_bn)
        from deeplearning4j_tpu.nn.conf.layers import GlobalPoolingLayer

        b = (NeuralNetConfiguration.builder()
             .seed(9).learning_rate(0.01).updater("nesterovs").momentum(0.9)
             .weight_init("relu").dtype("float32")
             .graph_builder().add_inputs("input"))
        x = _conv_bn(b, "stem", "input", 8, (1, 1), (1, 1))
        x = _bottleneck_fused(b, "b0", x, 2, (1, 1), project=False)
        x = _bottleneck_fused(b, "b1", x, 2, (2, 2), project=True)
        b.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
        b.add_layer("fc", OutputLayer(n_out=3, activation="softmax",
                                      loss_function="mcxent",
                                      weight_init="xavier"), "avgpool")
        return (b.set_outputs("fc")
                .set_input_types(InputType.convolutional(6, 6, 3))
                .build())

    def test_int8_checkpoint_serves_zero_compiles_after_warmup(
            self, cache_dir, tmp_path):
        from deeplearning4j_tpu.checkpoint import load_any, save_checkpoint
        from deeplearning4j_tpu.checkpoint.quantize import quantize_checkpoint
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.serving import InferenceServer

        rng = np.random.RandomState(5)
        X = rng.randn(4, 6, 6, 3).astype(np.float32)
        Y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 4)]
        net = ComputationGraph(self._fused_conf()).init()
        net.fit(DataSet(X, Y))
        src = str(tmp_path / "step1")
        dst = str(tmp_path / "step1-int8")
        save_checkpoint(net, src)
        quantize_checkpoint(src, dst)
        srv = load_any(dst)
        blk = srv.params_tree["b0_block"]
        assert blk["W_a"].dtype == np.int8 and "W_a__scale" in blk

        obs.install_jax_compile_hook(obs.metrics)
        server = InferenceServer(srv, max_batch_size=4, max_delay_ms=1.0,
                                 warmup=True).start()
        try:
            assert server.wait_ready(timeout=300)
            # Reference outputs first: the direct output() below runs at
            # exact (unpadded) row counts, which are NOT all bucket shapes.
            refs = {rows: np.asarray(srv.output(X[:rows]))[0]
                    for rows in (1, 2, 3, 4)}
            compiles_before = _counter_total("dl4j_xla_compiles_total")
            for rows in (1, 2, 3, 4):  # every bucket of the ladder
                req = urllib.request.Request(
                    server.url + "/predict",
                    data=json.dumps({"data": X[:rows].tolist()}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as resp:
                    preds = np.asarray(json.loads(resp.read())["predictions"])
                assert preds.shape == (rows, 3)
                np.testing.assert_allclose(preds, refs[rows], rtol=1e-4,
                                           atol=1e-5)
            assert (_counter_total("dl4j_xla_compiles_total")
                    == compiles_before)
        finally:
            server.stop()
