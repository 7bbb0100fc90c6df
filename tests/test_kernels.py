"""Kernel registry tests: env-knob override matrix, CPU graceful fallback,
per-signature memoized resolution (the hoisting counter contract), AOT
fingerprint invalidation on knob flips, CLI smoke, Pallas-vs-XLA parity for
every registered kernel, and the acceptance bit-identity contract
(`DL4J_TPU_KERNELS=xla` trains bit-identically to `auto` on CPU through
both engines, per-batch and k=4 superstep). PERF.md §19."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import DeviceCacheDataSetIterator
from deeplearning4j_tpu.kernels import fused_update, lstm_cell, norm_act, registry
from deeplearning4j_tpu.kernels import flash_attention as kflash
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization,
    DenseLayer,
    DropoutLayer,
    GravesLSTM,
    OutputLayer,
    RnnOutputLayer,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph

from conftest import make_classification_data

N_IN, N_OUT = 4, 3

_ENV_VARS = ["DL4J_TPU_KERNELS"] + [
    "DL4J_TPU_KERNEL_" + k.upper() for k in registry.kernel_names()]


@pytest.fixture(autouse=True)
def _clean_kernel_env(monkeypatch):
    """Every test starts from the default (auto) config with an empty
    resolution memo, and leaves no memo entries keyed by its env behind."""
    for var in _ENV_VARS:
        monkeypatch.delenv(var, raising=False)
    registry.clear_cache()
    yield
    registry.clear_cache()


def _mlp_conf(superstep_k=0, updater="adam"):
    return (NeuralNetConfiguration.builder()
            .seed(7).learning_rate(0.05).updater(updater)
            .weight_init("xavier").superstep_k(superstep_k)
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(BatchNormalization())
            .layer(DropoutLayer(dropout=0.5))
            .layer(OutputLayer(n_out=N_OUT, activation="softmax",
                               loss_function="mcxent"))
            .set_input_type(InputType.feed_forward(N_IN)).build())


def _graph_conf(superstep_k=0):
    return (NeuralNetConfiguration.builder()
            .seed(7).learning_rate(0.05).updater("adam").weight_init("xavier")
            .superstep_k(superstep_k)
            .graph_builder()
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=N_OUT, activation="softmax",
                                          loss_function="mcxent"), "d")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(N_IN))
            .build())


def _lstm_conf(updater="adam"):
    return (NeuralNetConfiguration.builder()
            .seed(7).learning_rate(0.05).updater(updater).weight_init("xavier")
            .list()
            .layer(GravesLSTM(n_out=6, activation="tanh"))
            .layer(RnnOutputLayer(n_out=N_OUT, activation="softmax",
                                  loss_function="mcxent"))
            .set_input_type(InputType.recurrent(N_IN))
            .build())


def _make_batches(seed, n_batches=7, batch=6):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n_batches):
        X, Y = make_classification_data(rng, n=batch, n_features=N_IN,
                                        n_classes=N_OUT, dtype="float32")
        out.append(DataSet(X, Y))
    return out


def _assert_trees_identical(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------
# Env-knob matrix


class TestModeKnobs:
    def test_default_is_auto(self):
        for k in registry.kernel_names():
            assert registry.mode_for(k) == ("auto", "default")

    def test_global_knob(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_KERNELS", "xla")
        for k in registry.kernel_names():
            assert registry.mode_for(k) == ("xla", "DL4J_TPU_KERNELS")

    def test_per_kernel_override_wins(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_KERNELS", "xla")
        monkeypatch.setenv("DL4J_TPU_KERNEL_LSTM_CELL", "pallas")
        assert registry.mode_for("lstm_cell") == (
            "pallas", "DL4J_TPU_KERNEL_LSTM_CELL")
        assert registry.mode_for("norm_act") == ("xla", "DL4J_TPU_KERNELS")

    @pytest.mark.parametrize("var", ["DL4J_TPU_KERNELS",
                                     "DL4J_TPU_KERNEL_NORM_ACT"])
    def test_invalid_value_raises(self, monkeypatch, var):
        monkeypatch.setenv(var, "cuda")
        with pytest.raises(ValueError, match="cuda"):
            registry.mode_for("norm_act")

    def test_config_key_tracks_env(self, monkeypatch):
        base = registry.config_key()
        assert base == tuple((k, "auto") for k in registry.kernel_names())
        monkeypatch.setenv("DL4J_TPU_KERNELS", "xla")
        flipped = registry.config_key()
        assert flipped != base
        assert dict(flipped) == {k: "xla" for k in registry.kernel_names()}
        fp = registry.config_fingerprint()
        assert fp == {**dict(flipped),
                      "selection_rules": registry.SELECTION_RULES}
        json.dumps(fp)  # must stay JSON-able for the AOT sidecar


# --------------------------------------------------------------------------
# Resolution: CPU graceful fallback, forced modes, memoization


class TestResolution:
    def test_unknown_kernel_raises(self):
        with pytest.raises(KeyError, match="unknown kernel"):
            registry.resolve("conv3d", backend="cpu")

    def test_auto_on_cpu_falls_back_to_xla(self):
        for name in ("lstm_cell", "fused_update", "norm_act",
                     "bottleneck_block"):
            res = registry.resolve(name, backend="cpu")
            assert res.impl == "xla", res
        # flash_attention's Pallas kernel historically interprets off-TPU
        # (its pre-registry behavior) — auto keeps that.
        assert registry.resolve("flash_attention", backend="cpu").impl == "pallas"

    def test_forced_pallas_interprets_off_tpu(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_KERNELS", "pallas")
        res = registry.resolve(
            "lstm_cell", backend="cpu", shapes=(6, 6), dtypes=("float32",),
            meta=(("gate", "sigmoid"), ("act", "tanh"),
                  ("peephole", True), ("masked", False)))
        assert res.impl == "pallas"
        assert "forced" in res.reason

    def test_forced_pallas_structural_refusal_falls_back(self, monkeypatch):
        # A gate activation the kernel cannot express: even forced mode
        # must fall back (with the probe's reason surfaced), not crash.
        monkeypatch.setenv("DL4J_TPU_KERNEL_LSTM_CELL", "pallas")
        res = registry.resolve(
            "lstm_cell", backend="cpu", shapes=(6, 6), dtypes=("float32",),
            meta=(("gate", "hardtanh"), ("act", "tanh"),
                  ("peephole", False), ("masked", False)))
        assert res.impl == "xla"
        assert "unavailable" in res.reason
        assert "hardtanh" in res.reason

    def test_forced_xla_everywhere(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_KERNELS", "xla")
        for name in registry.kernel_names():
            res = registry.resolve(name, backend="cpu")
            assert res.impl == "xla"
            assert "forced via DL4J_TPU_KERNELS" in res.reason

    def test_fused_update_cpu_reasons(self):
        res = registry.resolve(
            "fused_update", backend="cpu", shapes=((8, 3),),
            dtypes=("float32",), meta=(("kind", "adam"),
                                       ("hyper", (0.9, 0.999, 1e-8))))
        assert res.impl == "xla"
        # Unfused updaters never get the Pallas path even on TPU.
        res = registry.resolve(
            "fused_update", backend="tpu", shapes=((8, 3),),
            dtypes=("float32",), meta=(("kind", "adagrad"), ("hyper", (1e-6,))))
        assert res.impl == "xla"
        ok, reason = fused_update._pallas_available(
            "tpu", ((8, 3),), ("float32",), meta=(("kind", "adagrad"),))
        assert not ok and "no fused kernel" in reason

    def test_resolution_memoized_per_signature(self):
        registry.clear_cache()
        sig = dict(backend="cpu", shapes=(8, 128), dtypes=("float32",),
                   meta=(("gate", "sigmoid"), ("act", "tanh"),
                         ("peephole", False), ("masked", False)))
        registry.resolve("lstm_cell", **sig)
        probes = registry.probe_count()
        for _ in range(5):
            registry.resolve("lstm_cell", **sig)
        assert registry.probe_count() == probes  # memo hit: zero new probes
        registry.resolve("lstm_cell", **dict(sig, shapes=(16, 128)))
        assert registry.probe_count() > probes  # new signature re-probes

    def test_clear_cache_reprobes(self):
        registry.resolve("norm_act", backend="cpu")
        probes = registry.probe_count()
        registry.clear_cache()
        registry.resolve("norm_act", backend="cpu")
        assert registry.probe_count() > probes

    def test_describe_covers_all_kernels(self):
        rows = registry.describe(backend="cpu")
        assert [r["kernel"] for r in rows] == sorted(registry.kernel_names())
        for r in rows:
            assert r["mode"] == "auto" and r["impl"] and r["reason"]


# --------------------------------------------------------------------------
# `auto` and BatchNorm: the layer type decides, not the shape (PR 25)

# ResNet-50 at batch 256: stage 1's output and the last stage's.
_RESNET50_BN_SHAPES = [(256 * 56 * 56, 256), (256 * 7 * 7, 2048)]


def _norm_sig(op, shape, dtype, act):
    return dict(backend="tpu", shapes=shape, dtypes=(dtype,),
                meta=(("op", op), ("act", act)))


class TestNormActAutoSelection:
    @pytest.mark.parametrize("act", ["relu", "identity"])
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("shape", _RESNET50_BN_SHAPES,
                             ids=["56x56x256", "7x7x2048"])
    def test_auto_by_layer_type_at_resnet50_shapes(self, monkeypatch, shape,
                                                   dtype, act):
        bn = registry.resolve("norm_act",
                              **_norm_sig("batchnorm", shape, dtype, act))
        assert bn.impl == "xla", bn
        # The winner's reason carries why Pallas said no.
        assert "pallas unavailable" in bn.reason, bn
        assert "fusion barrier" in bn.reason, bn
        ln = registry.resolve("norm_act",
                              **_norm_sig("layernorm", shape, dtype, act))
        assert ln.impl == "pallas", ln
        assert ln.reason == "TPU fused normalize+affine+activation"
        # The knob still drives the BatchNorm body (parity tests, smoke).
        monkeypatch.setenv("DL4J_TPU_KERNEL_NORM_ACT", "pallas")
        registry.clear_cache()
        forced = registry.resolve(
            "norm_act", **_norm_sig("batchnorm", shape, dtype, act))
        assert forced.impl == "pallas", forced
        assert "forced via DL4J_TPU_KERNEL_NORM_ACT" in forced.reason

    def test_probe_reports_the_refusal(self):
        selected, rows = registry.probe(
            "norm_act", **_norm_sig("batchnorm", _RESNET50_BN_SHAPES[0],
                                    "bfloat16", "relu"))
        assert selected == "xla"
        by_impl = {r["impl"]: r for r in rows}
        assert not by_impl["pallas"]["available"]
        assert "fusion barrier" in by_impl["pallas"]["reason"]
        assert by_impl["xla"]["available"]

    def test_batchnorm_refusal_comes_before_the_shape_checks(self):
        # Not a shape rule: an unaligned BatchNorm is refused for being a
        # BatchNorm, an unaligned LayerNorm for its shape.
        ok, why = norm_act._pallas_available(
            "tpu", (10, 64), ("float32",),
            meta=(("op", "batchnorm"), ("act", "relu")))
        assert not ok and "fusion barrier" in why
        ok, why = norm_act._pallas_available(
            "tpu", (10, 64), ("float32",),
            meta=(("op", "layernorm"), ("act", "relu")))
        assert not ok and "tile-aligned" in why

    def test_batchnorm_seam_traces_no_pallas_call_on_tpu(self, monkeypatch):
        # What the layer gets: under `auto` with a TPU for a backend the
        # BatchNorm seam traces the XLA expression, and nothing else.
        monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
        x = jnp.zeros((64, 128), jnp.float32)
        v = jnp.ones((128,), jnp.float32)
        bn = jax.make_jaxpr(lambda *a: norm_act.batchnorm_norm_act(
            *a, 1e-5, "relu"))(x, v, v, v, v)
        assert "pallas_call" not in str(bn)
        ref = jax.make_jaxpr(lambda *a: norm_act.batchnorm_xla(
            *a, 1e-5, "relu"))(x, v, v, v, v)
        assert str(bn) == str(ref)
        took = [r for r in registry.resolved() if r.kernel == "norm_act"]
        assert [r.impl for r in took] == ["xla"], took


# --------------------------------------------------------------------------
# `auto` and the updater seam: the leaves' sizes decide (PR 29)

# One dispatch is one layer's trainable leaves, in `tree_leaves` order.
# `keye_vl2_30b_a3b`: an MoE layer (75.8 M elements), an attention layer,
# a norm's scale; ResNet-50: a 3x3 convolution, a 1x1 under one grid
# block, a BatchNorm's gamma and beta.
_UPDATE_SIGNATURES = {
    "keye-ffn": (((2048, 128), (16, 768, 2048), (16, 2048, 768),
                  (16, 2048, 768)), "xla"),
    "keye-attn": (((2048, 512), (4096, 2048), (2048, 4096), (2048, 512),
                   (128,), (128,)), "xla"),
    "keye-norm": (((2048,),), "pallas"),
    "resnet-conv3x3": (((3, 3, 512, 512),), "xla"),
    "resnet-conv1x1": (((1, 1, 256, 64),), "pallas"),
    "resnet-batchnorm": (((256,), (256,)), "pallas"),
}
_UPDATE_KINDS = {"adam": (0.9, 0.95, 1e-8), "nesterovs": (0.9,)}


def _update_sig(kind, shapes):
    return dict(backend="tpu", shapes=shapes,
                dtypes=("float32",) * len(shapes),
                meta=(("kind", kind), ("hyper", _UPDATE_KINDS[kind])))


class TestFusedUpdateAutoSelection:
    @pytest.mark.parametrize("kind", sorted(_UPDATE_KINDS))
    @pytest.mark.parametrize("name", sorted(_UPDATE_SIGNATURES))
    def test_auto_by_leaf_size_at_the_cells_signatures(self, monkeypatch,
                                                       name, kind):
        shapes, want = _UPDATE_SIGNATURES[name]
        res = registry.resolve("fused_update", **_update_sig(kind, shapes))
        assert res.impl == want, res
        if want == "xla":
            # The winner's reason carries why Pallas said no.
            assert "pallas unavailable" in res.reason, res
            assert "raveled into one flat vector" in res.reason, res
        # The knob still drives the body (parity tests, smoke).
        monkeypatch.setenv("DL4J_TPU_KERNEL_FUSED_UPDATE", "pallas")
        registry.clear_cache()
        forced = registry.resolve("fused_update", **_update_sig(kind, shapes))
        assert forced.impl == "pallas", forced
        assert "forced via DL4J_TPU_KERNEL_FUSED_UPDATE" in forced.reason

    @pytest.mark.parametrize("kind", sorted(_UPDATE_KINDS))
    def test_probe_reports_the_refusal(self, kind):
        shapes, _ = _UPDATE_SIGNATURES["keye-ffn"]
        selected, rows = registry.probe("fused_update",
                                        **_update_sig(kind, shapes))
        assert selected == "xla"
        by_impl = {r["impl"]: r for r in rows}
        assert not by_impl["pallas"]["available"]
        assert "25165824 elements" in by_impl["pallas"]["reason"]
        assert by_impl["xla"]["available"]

    def test_the_limit_is_one_grid_block(self):
        limit = fused_update._RAVEL_LIMIT
        for n, want in ((limit - 1, True), (limit, False)):
            ok, why = fused_update._pallas_available(
                "tpu", ((7,), (n,)), ("float32",) * 2,
                meta=(("kind", "adam"),))
            assert ok is want, why

    @pytest.mark.parametrize("kind", sorted(_UPDATE_KINDS))
    def test_large_leaves_trace_no_pallas_call_on_tpu(self, monkeypatch,
                                                      kind):
        # What the engine gets: under `auto` with a TPU for a backend a
        # dispatch of large leaves traces the per-leaf XLA expression and
        # nothing else; one of small leaves still traces the body.
        monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
        hyper = _UPDATE_KINDS[kind]
        fields = ("m", "v") if kind == "adam" else ("v",)

        def jaxpr(fn, shapes):
            tree = {str(i): jax.ShapeDtypeStruct(s, jnp.float32)
                    for i, s in enumerate(shapes)}
            return str(jax.make_jaxpr(fn)({f: tree for f in fields}, tree))

        def seam(st, g):
            return fused_update.dispatch(kind, st, g, 0.01, 3, hyper)

        def reference(st, g):
            xla = {"adam": fused_update.adam_xla,
                   "nesterovs": fused_update.nesterovs_xla}[kind]
            return xla(st, g, 0.01, 3, *hyper)

        large = ((2048, 128), (4, 512, 768))
        assert jaxpr(seam, large) == jaxpr(reference, large)
        assert "pallas_call" in jaxpr(seam, ((256,), (256,)))
        took = [r.impl for r in registry.resolved()
                if r.kernel == "fused_update"]
        assert sorted(took) == ["pallas", "xla"], took


# --------------------------------------------------------------------------
# Program identity: jit-cache keys and the AOT fingerprint


# What `config_fingerprint()` returned at PR 24 (commit 5644a09) under a
# default environment, pinned: the modes alone. An AOT artifact written
# then, under `auto`, was traced with BatchNorm on the Pallas path.
_PARENTS_KERNELS_FINGERPRINT = {
    "bottleneck_block": "auto", "flash_attention": "auto",
    "flash_attention_paged": "auto", "fused_update": "auto",
    "lstm_cell": "auto", "norm_act": "auto"}


class TestProgramIdentity:
    def test_fingerprint_differs_from_the_parents_rules(self):
        # The same modes now select another program, so the registry's
        # part of the fingerprint document must differ from the parent's.
        fp = registry.config_fingerprint()
        assert {k: fp[k] for k in _PARENTS_KERNELS_FINGERPRINT} \
            == _PARENTS_KERNELS_FINGERPRINT
        assert fp != _PARENTS_KERNELS_FINGERPRINT
        # 3 since PR 27: `masked_attention` joined the names, and the
        # sparse-attention layer's step is another program on a TPU. 4
        # since PR 29: a step with large layers no longer ravels them.
        assert fp["selection_rules"] >= 4
        assert fp["masked_attention"] == "auto"

    def test_fingerprint_doc_differs_from_the_parents(self):
        from deeplearning4j_tpu.compilation.store import (
            build_fingerprint_doc, fingerprint)

        net = MultiLayerNetwork(_mlp_conf()).init()
        X = jnp.zeros((6, N_IN), jnp.float32)
        Y = jnp.zeros((6, N_OUT), jnp.float32)
        doc = build_fingerprint_doc(net, "train_step", {}, (X, Y))
        # The parent's document for the same net, batch and environment:
        # this PR changes no other field of it.
        parents = dict(doc, kernels=_PARENTS_KERNELS_FINGERPRINT)
        assert fingerprint(doc) != fingerprint(parents)

    def test_fingerprint_doc_invalidates_on_knob_flip(self, monkeypatch):
        from deeplearning4j_tpu.compilation.store import (
            build_fingerprint_doc, fingerprint)

        net = MultiLayerNetwork(_mlp_conf()).init()
        X = jnp.zeros((6, N_IN), jnp.float32)
        Y = jnp.zeros((6, N_OUT), jnp.float32)
        doc_auto = build_fingerprint_doc(net, "train_step", {}, (X, Y))
        assert doc_auto["kernels"] == {
            **{k: "auto" for k in registry.kernel_names()},
            "selection_rules": registry.SELECTION_RULES}
        monkeypatch.setenv("DL4J_TPU_KERNELS", "xla")
        doc_xla = build_fingerprint_doc(net, "train_step", {}, (X, Y))
        assert doc_xla["kernels"]["lstm_cell"] == "xla"
        assert fingerprint(doc_auto) != fingerprint(doc_xla)

    def test_jit_cache_key_includes_kernel_config(self, monkeypatch):
        net = MultiLayerNetwork(_mlp_conf()).init()
        ds = _make_batches(9, n_batches=1)[0]
        net.fit(ds)
        n_auto = len(net._jit_cache)
        net.fit(ds)
        assert len(net._jit_cache) == n_auto  # same env: cache hit
        monkeypatch.setenv("DL4J_TPU_KERNELS", "xla")
        registry.clear_cache()
        net.fit(ds)
        assert len(net._jit_cache) > n_auto  # knob flip: distinct program
        keys = {k[-1] for k in net._jit_cache}
        assert len(keys) == 2  # one kernel config per env


# --------------------------------------------------------------------------
# Hoisting: repeated same-signature blocks never re-run probes


class TestProbeHoisting:
    def test_superstep_restack_adds_zero_probes(self):
        net = MultiLayerNetwork(_mlp_conf(superstep_k=4)).init()
        batches = _make_batches(0, n_batches=8)
        net.fit(batches)  # traces k=4 blocks: probes run here
        probes = registry.probe_count()
        net.fit(batches)  # restacked same-shape blocks: memo hits only
        net.fit(batches)
        assert registry.probe_count() == probes

    def test_device_cache_epochs_add_zero_probes(self):
        net = MultiLayerNetwork(_mlp_conf()).init()
        it = DeviceCacheDataSetIterator(_make_batches(0, n_batches=4))
        net.fit(it)
        probes = registry.probe_count()
        for _ in range(3):
            net.fit(it)
        assert registry.probe_count() == probes


# --------------------------------------------------------------------------
# CLI smoke


class TestCLI:
    _REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _run(self, *argv):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for var in _ENV_VARS:
            env.pop(var, None)
        return subprocess.run(
            [sys.executable, "-m", "deeplearning4j_tpu.kernels", *argv],
            cwd=self._REPO, env=env, capture_output=True, text=True,
            timeout=120)

    def test_table_lists_all_kernels(self):
        proc = self._run()
        assert proc.returncode == 0, proc.stderr
        for name in registry.kernel_names():
            assert name in proc.stdout

    def test_json_output(self):
        proc = self._run("--json")
        assert proc.returncode == 0, proc.stderr
        rows = json.loads(proc.stdout)
        assert {r["kernel"] for r in rows} == set(registry.kernel_names())
        for r in rows:
            assert set(r) >= {"kernel", "mode", "mode_source", "impl",
                              "reason"}

    @pytest.mark.parametrize("shapes,selected", [
        ("2048,128;16,2048,768", "xla"), ("256;256", "pallas"),
        ("3,3,512,512;", "xla")])
    def test_probe_takes_one_shape_a_leaf(self, shapes, selected):
        proc = self._run("--backend", "tpu", "--json", "--probe",
                         "fused_update", shapes, "float32",
                         "--meta", "kind=adam")
        assert proc.returncode == 0, proc.stderr
        assert json.loads(proc.stdout)["selected"] == selected


# --------------------------------------------------------------------------
# Dispatch metric


class TestDispatchMetric:
    def test_resolve_increments_counter(self):
        registry.resolve("norm_act", backend="cpu")
        fam = obs.metrics.to_json()["dl4j_kernel_dispatch_total"]
        series = {(s["labels"]["kernel"], s["labels"]["impl"]): s["value"]
                  for s in fam["series"]}
        before = series[("norm_act", "xla")]
        registry.resolve("norm_act", backend="cpu")  # memo hit still counts
        fam = obs.metrics.to_json()["dl4j_kernel_dispatch_total"]
        series = {(s["labels"]["kernel"], s["labels"]["impl"]): s["value"]
                  for s in fam["series"]}
        assert series[("norm_act", "xla")] == before + 1


# --------------------------------------------------------------------------
# Parity: every kernel's Pallas path (interpret on CPU) vs its XLA fallback

# The gate below fails when a kernel is added to the registry without a
# parity test here (or, for flash_attention, in test_flash_attention.py;
# for bottleneck_block, in test_bottleneck_block.py).
PARITY_COVERED = {"lstm_cell", "fused_update", "norm_act", "flash_attention",
                  "flash_attention_paged", "bottleneck_block",
                  "masked_attention",      # test_masked_attention.py
                  "banded_attention",      # test_banded_attention.py
                  "latent_attention",      # test_latent_moe_lm.py
                  "grouped_matmul",        # test_grouped_matmul.py
                  "rotary"}                # test_rotary.py


def test_every_kernel_has_parity_coverage():
    assert set(registry.kernel_names()) == PARITY_COVERED


# bf16 rows of the parity matrix compare bf16-in/bf16-out paths whose
# internals accumulate differently (Pallas: f32 `preferred_element_type`;
# XLA fallback: operand-dtype math) — tolerances sized to bf16's ~8-bit
# mantissa, not to f32 roundoff.
_PARITY_TOLS = {"float32": dict(rtol=1e-5, atol=1e-5),
                "bfloat16": dict(rtol=4e-2, atol=4e-2)}


class TestParity:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("peephole,masked", [
        (False, False), (True, False), (False, True), (True, True)])
    def test_lstm_cell(self, monkeypatch, peephole, masked, dtype):
        rng = np.random.RandomState(3)
        dt = jnp.dtype(dtype)
        b, n = 5, 7
        xw = jnp.asarray(rng.randn(b, 4 * n), dt)
        h0 = jnp.asarray(rng.randn(b, n), dt)
        c0 = jnp.asarray(rng.randn(b, n), dt)
        RW = jnp.asarray(rng.randn(n, 4 * n) * 0.1, dt)
        pw = tuple(jnp.asarray(rng.randn(n) * 0.1, dt)
                   for _ in range(3)) if peephole else None
        m = (jnp.asarray(rng.rand(b) < 0.6, dt) if masked else None)

        def cell_for(mode):
            monkeypatch.setenv("DL4J_TPU_KERNEL_LSTM_CELL", mode)
            registry.clear_cache()
            return lstm_cell.resolve_cell(
                batch=b, n_out=n, dtype=dtype, peephole=peephole,
                masked=masked, gate_activation="sigmoid", activation="tanh",
                gate_act=jax.nn.sigmoid, cell_act=jnp.tanh)

        ref = cell_for("xla")(xw, h0, c0, RW, pw, m)
        got = cell_for("pallas")(xw, h0, c0, RW, pw, m)
        for r, g in zip(ref, got):
            assert g.dtype == dt
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(r, np.float32),
                                       **_PARITY_TOLS[dtype])

    @pytest.mark.parametrize("kind,fields,hyper", [
        ("adam", ("m", "v"), (0.9, 0.999, 1e-8)),
        ("nesterovs", ("v",), (0.9,)),
        ("rmsprop", ("g2",), (0.95, 1e-8)),
    ])
    def test_fused_update(self, monkeypatch, kind, fields, hyper):
        rng = np.random.RandomState(4)
        tree = lambda: {"W": jnp.asarray(rng.randn(9, 5), jnp.float32),
                        "b": jnp.asarray(rng.randn(5), jnp.float32)}
        grads = tree()
        state = {f: tree() for f in fields}

        def run(mode):
            monkeypatch.setenv("DL4J_TPU_KERNEL_FUSED_UPDATE", mode)
            registry.clear_cache()
            return fused_update.dispatch(kind, state, grads,
                                         jnp.float32(0.05), jnp.int32(2),
                                         hyper)

        ref_state, ref_deltas = run("xla")
        got_state, got_deltas = run("pallas")
        for r, g in zip(jax.tree_util.tree_leaves((ref_state, ref_deltas)),
                        jax.tree_util.tree_leaves((got_state, got_deltas))):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("op,act", [("batchnorm", "relu"),
                                        ("layernorm", "tanh"),
                                        ("batchnorm", "identity")])
    def test_norm_act(self, monkeypatch, op, act, dtype):
        rng = np.random.RandomState(5)
        dt = jnp.dtype(dtype)
        x = jnp.asarray(rng.randn(6, 10), dt)
        gamma = jnp.asarray(rng.rand(10) + 0.5, dt)
        beta = jnp.asarray(rng.randn(10), dt)
        mean = jnp.asarray(rng.randn(10), dt)
        var = jnp.asarray(rng.rand(10) + 0.1, dt)

        def run(mode):
            monkeypatch.setenv("DL4J_TPU_KERNEL_NORM_ACT", mode)
            registry.clear_cache()
            if op == "batchnorm":
                return norm_act.batchnorm_norm_act(x, mean, var, gamma, beta,
                                                   1e-5, act)
            return norm_act.layernorm_norm_act(x, gamma, beta, 1e-5, act)

        got, ref = run("pallas"), run("xla")
        assert got.dtype == dt
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   **_PARITY_TOLS[dtype])

    def test_fused_update_refuses_bf16_gracefully(self, monkeypatch):
        # Optimizer state is always f32 master copies (mixed-precision
        # policies cast COMPUTE, not params), so the fused kernel refuses
        # bf16 leaves — the bf16 row of the parity matrix for this kernel
        # is the graceful fallback, not a numeric comparison.
        monkeypatch.setenv("DL4J_TPU_KERNEL_FUSED_UPDATE", "pallas")
        registry.clear_cache()
        res = registry.resolve(
            "fused_update", backend="cpu", shapes=((8, 3),),
            dtypes=("bfloat16",),
            meta=(("kind", "adam"), ("hyper", (0.9, 0.999, 1e-8))))
        assert res.impl == "xla"
        assert "bfloat16" in res.reason

    def test_lstm_cell_grad(self, monkeypatch):
        # pallas_call has no autodiff rule; the cell must still sit inside
        # the engines' value_and_grad (kernels/_diff.py pairs the Pallas
        # forward with the XLA reference's VJP).
        rng = np.random.RandomState(7)
        b, n = 4, 6
        xw = jnp.asarray(rng.randn(b, 4 * n), jnp.float32)
        h0 = jnp.asarray(rng.randn(b, n), jnp.float32)
        c0 = jnp.asarray(rng.randn(b, n), jnp.float32)
        RW = jnp.asarray(rng.randn(n, 4 * n) * 0.1, jnp.float32)

        def loss_with(mode):
            monkeypatch.setenv("DL4J_TPU_KERNEL_LSTM_CELL", mode)
            registry.clear_cache()
            cell = lstm_cell.resolve_cell(
                batch=b, n_out=n, dtype="float32", peephole=False,
                masked=False, gate_activation="sigmoid", activation="tanh",
                gate_act=jax.nn.sigmoid, cell_act=jnp.tanh)

            def loss(rw):
                h, c, out = cell(xw, h0, c0, rw, None, None)
                return jnp.sum(out ** 2) + jnp.sum(c)

            return jax.grad(loss)(RW)

        np.testing.assert_allclose(np.asarray(loss_with("pallas")),
                                   np.asarray(loss_with("xla")),
                                   rtol=1e-4, atol=1e-5)

    def test_norm_act_grad(self, monkeypatch):
        rng = np.random.RandomState(8)
        x = jnp.asarray(rng.randn(6, 10), jnp.float32)
        gamma = jnp.asarray(rng.rand(10) + 0.5, jnp.float32)
        beta = jnp.asarray(rng.randn(10), jnp.float32)

        def grads_with(mode):
            monkeypatch.setenv("DL4J_TPU_KERNEL_NORM_ACT", mode)
            registry.clear_cache()
            return jax.grad(
                lambda xv, g: jnp.sum(
                    norm_act.layernorm_norm_act(xv, g, beta, 1e-5, "tanh")
                    ** 2),
                argnums=(0, 1))(x, gamma)

        for p, r in zip(grads_with("pallas"), grads_with("xla")):
            np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                       rtol=1e-4, atol=1e-5)

    def test_forced_pallas_net_trains_float_close(self, monkeypatch):
        # The end-to-end regression for the autodiff seam: a BN net trained
        # with every kernel forced to Pallas (interpret on CPU) must run —
        # not crash in value_and_grad — and land float-close to XLA.
        def train(mode):
            if mode is None:
                monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
            else:
                monkeypatch.setenv("DL4J_TPU_KERNELS", mode)
            registry.clear_cache()
            net = MultiLayerNetwork(_mlp_conf()).init()
            for ds in _make_batches(8, n_batches=4):
                net.fit(ds)
            return np.asarray(net.params())

        np.testing.assert_allclose(train("pallas"), train("xla"),
                                   rtol=1e-3, atol=1e-4)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_flash_attention_xla_mode_matches_pallas(self, monkeypatch, dtype):
        rng = np.random.RandomState(6)
        dt = jnp.dtype(dtype)
        q, k, v = (jnp.asarray(rng.randn(2, 16, 2, 8), dt) for _ in range(3))

        def run(mode):
            if mode is None:
                monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
            else:
                monkeypatch.setenv("DL4J_TPU_KERNELS", mode)
            registry.clear_cache()
            return kflash.flash_attention(q, k, v, causal=True)

        got, ref = run(None), run("xla")  # auto: pallas vs dense reference
        assert got.dtype == dt
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   **_PARITY_TOLS[dtype])

    @pytest.mark.parametrize("t", [1, 3])
    def test_flash_attention_paged_pallas_matches_xla(self, monkeypatch, t):
        # Paged gather over a pool with pad tail, zero-page rows, and a
        # multi-token (speculative verify) query width.
        rng = np.random.RandomState(9)
        B, H, D, page, P, NP = 3, 2, 8, 4, 7, 4
        q = jnp.asarray(rng.randn(B, t, H, D), jnp.float32)
        kp = jnp.asarray(rng.randn(P, page, H, D), jnp.float32)
        vp = jnp.asarray(rng.randn(P, page, H, D), jnp.float32)
        table = jnp.asarray([[1, 2, 3, 0], [4, 0, 0, 0], [0, 0, 0, 0]],
                            jnp.int32)
        pos = jnp.asarray([9, 2, 0], jnp.int32)  # row 2: empty slot

        def run(mode):
            monkeypatch.setenv("DL4J_TPU_KERNEL_FLASH_ATTENTION_PAGED", mode)
            registry.clear_cache()
            return kflash.paged_decode_attention(q, kp, vp, table, pos, True)

        np.testing.assert_allclose(np.asarray(run("pallas")),
                                   np.asarray(run("xla")),
                                   rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# Acceptance: DL4J_TPU_KERNELS=xla is bit-identical to auto on CPU


class TestBitIdentity:
    def _train(self, conf_fn, batches_fn, mode, monkeypatch):
        if mode is None:
            monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
        else:
            monkeypatch.setenv("DL4J_TPU_KERNELS", mode)
        registry.clear_cache()
        net = conf_fn()
        for _ in range(2):
            for ds in batches_fn():
                net.fit(ds)
        return net.params_tree, net.opt_state

    def _pair(self, conf_fn, batches_fn, monkeypatch):
        ref = self._train(conf_fn, batches_fn, "xla", monkeypatch)
        got = self._train(conf_fn, batches_fn, None, monkeypatch)
        _assert_trees_identical(ref, got)

    def test_mln_adam_bn(self, monkeypatch):
        self._pair(lambda: MultiLayerNetwork(_mlp_conf()).init(),
                   lambda: _make_batches(1, n_batches=3), monkeypatch)

    def test_graph_engine(self, monkeypatch):
        self._pair(lambda: ComputationGraph(_graph_conf()).init(),
                   lambda: _make_batches(2, n_batches=3), monkeypatch)

    def test_lstm_net(self, monkeypatch):
        def batches():
            rng = np.random.RandomState(3)
            b, t = 4, 9
            X = rng.randn(b, t, N_IN).astype("float32")
            Y = np.eye(N_OUT)[rng.randint(0, N_OUT, (b, t))].astype("float32")
            return [DataSet(X, Y)]

        self._pair(lambda: MultiLayerNetwork(_lstm_conf()).init(),
                   batches, monkeypatch)

    def test_superstep_k4(self, monkeypatch):
        def train(mode):
            if mode is None:
                monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
            else:
                monkeypatch.setenv("DL4J_TPU_KERNELS", mode)
            registry.clear_cache()
            net = MultiLayerNetwork(_mlp_conf(superstep_k=4)).init()
            net.fit(_make_batches(4, n_batches=7))
            return net.params_tree, net.opt_state

        _assert_trees_identical(train("xla"), train(None))
