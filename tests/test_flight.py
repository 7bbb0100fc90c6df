"""Flight-recorder + HBM-attribution tests (the crash/NaN/preemption
forensics tier).

Three layers:
- FlightRecorder unit tests: ring semantics, bundle contents, per-reason
  rate limiting, the CLI pretty-printer;
- crash-forensics subprocess tests: a NaN-diverging fit and a SIGTERM'd
  run must each leave a self-contained bundle behind (MANIFEST + steps
  JSONL + a valid Chrome trace + metrics snapshot);
- memory attribution: per-program `memory_analysis()` gauges, live-buffer
  attribution to registered trees, and the serving host's measured-HBM
  eviction budgets.
"""

import json
import math
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.neural_net import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import memory as mem
from deeplearning4j_tpu.observability.flight import FlightRecorder
from deeplearning4j_tpu.observability.flight import main as flight_cli


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


# The builder prelude shared by the subprocess children below.
_CHILD_PRELUDE = r"""
import numpy as np
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.neural_net import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

conf = (NeuralNetConfiguration.builder()
        .seed(7).learning_rate(0.1).updater("sgd")
        .list()
        .layer(DenseLayer(n_out=8, activation="tanh"))
        .layer(OutputLayer(n_out=3, activation="softmax",
                           loss_function="mcxent"))
        .set_input_type(InputType.feed_forward(4))
        .build())
net = MultiLayerNetwork(conf).init()
rng = np.random.RandomState(0)
y = np.eye(3, dtype="float32")[rng.randint(0, 3, 8)]
"""


def _child_env(tmp_path, **extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DL4J_TPU_FLIGHT="1",
               DL4J_TPU_FLIGHT_DIR=str(tmp_path / "flight"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.update(extra)
    return env


def _bundles(tmp_path):
    root = tmp_path / "flight"
    if not root.is_dir():
        return []
    return sorted(p for p in root.iterdir() if (p / "MANIFEST.json").exists())


def _assert_bundle_valid(bundle):
    """A bundle must be self-contained and parseable: manifest, steps
    JSONL, a structurally valid Chrome trace, and a metrics snapshot."""
    manifest = json.loads((bundle / "MANIFEST.json").read_text())
    assert manifest["bundle_format"] == 1
    assert manifest["pid"] > 0 and manifest["versions"]["python"]
    lines = (bundle / "steps.jsonl").read_text().splitlines()
    records = [json.loads(line) for line in lines if line.strip()]
    trace = json.loads((bundle / "trace.json").read_text())
    assert isinstance(trace["traceEvents"], list)
    for e in trace["traceEvents"]:
        assert e["ph"] in ("X", "i")
        assert "name" in e and "ts" in e
    metrics = json.loads((bundle / "metrics.json").read_text())
    assert isinstance(metrics, dict)
    return manifest, records, trace, metrics


# ----------------------------------------------------------- unit tests


class TestFlightRecorder:
    def test_ring_keeps_last_capacity_records(self, tmp_path):
        rec = FlightRecorder(capacity=16, enabled=True,
                             dump_dir=str(tmp_path))
        for i in range(40):
            rec.record_step("mln", i, loss=0.5, seconds=0.001)
        snap = rec.snapshot()
        assert len(snap) == 16
        assert [r["iteration"] for r in snap] == list(range(24, 40))
        seqs = [r["seq"] for r in snap]
        assert seqs == sorted(seqs)  # oldest first

    def test_disabled_recording_still_dumps_on_demand(self, tmp_path):
        rec = FlightRecorder(capacity=16, enabled=False,
                             dump_dir=str(tmp_path))
        rec.record_step("mln", 1, loss=0.5)
        rec.record_event("probe")
        assert rec.snapshot() == []
        bundle = rec.dump(reason="manual")
        assert bundle is not None and os.path.isfile(
            os.path.join(bundle, "MANIFEST.json"))

    def test_dump_bundle_contents_and_nan_loss_materialization(
            self, tmp_path):
        rec = FlightRecorder(capacity=32, enabled=True,
                             dump_dir=str(tmp_path))
        rec.record_step("mln", 1, loss=0.25, seconds=0.002, k=4,
                        h2d_bytes=1024, input_wait=0.0001,
                        jit_hits=1, jit_misses=1)
        rec.record_step("mln", 2, loss=float("nan"), seconds=0.002,
                        jit_hits=2, jit_misses=1)
        rec.record_event("nan_loss", engine="MultiLayerNetwork",
                         iteration=2)
        bundle = rec.dump(reason="nan-loss",
                          exc=FloatingPointError("non-finite loss"))
        from pathlib import Path

        manifest, records, trace, _metrics = _assert_bundle_valid(
            Path(bundle))
        assert manifest["reason"] == "nan-loss"
        assert manifest["exception"]["type"] == "FloatingPointError"
        steps = [r for r in records if r["type"] == "step"]
        assert [s["iteration"] for s in steps] == [1, 2]
        assert steps[0]["k"] == 4 and steps[0]["h2d_bytes"] == 1024
        assert steps[0]["input_wait"] == pytest.approx(0.0001)
        # the NaN loss must be JSON-safe (materialized to its repr)
        assert steps[1]["loss"] == "nan"
        assert steps[1]["jit_hits_delta"] == 1
        assert any(r["type"] == "nan_loss" for r in records)
        names = {e["name"] for e in trace["traceEvents"]}
        assert "mln.step" in names and "flight.nan_loss" in names

    def test_auto_dumps_are_rate_limited_per_reason(self, tmp_path,
                                                    monkeypatch):
        rec = FlightRecorder(capacity=16, enabled=True,
                             dump_dir=str(tmp_path))
        rec.min_interval_s = 3600.0
        first = rec.dump(reason="crash:mln.dispatch", force=False)
        assert first is not None
        assert rec.dump(reason="crash:mln.dispatch", force=False) is None
        # a different reason has its own limiter window
        assert rec.dump(reason="nan-loss", force=False) is not None
        # explicit dumps always write
        assert rec.dump(reason="crash:mln.dispatch", force=True) is not None

    def test_on_crash_records_event_and_dumps(self, tmp_path):
        rec = FlightRecorder(capacity=16, enabled=True,
                             dump_dir=str(tmp_path))
        bundle = rec.on_crash("serving.batch", ValueError("boom"))
        assert bundle is not None
        records = rec.snapshot()
        assert records and records[-1]["type"] == "crash"
        assert records[-1]["where"] == "serving.batch"
        manifest = json.loads(
            open(os.path.join(bundle, "MANIFEST.json")).read())
        assert manifest["reason"] == "crash:serving.batch"

    def test_status_shape(self, tmp_path):
        rec = FlightRecorder(capacity=16, enabled=True,
                             dump_dir=str(tmp_path))
        rec.record_step("mln", 1, loss=0.5)
        st = rec.status()
        assert st["enabled"] is True and st["capacity"] == 16
        assert st["records"] == 1 and st["dump_dir"] == str(tmp_path)
        assert st["dumps"] == [] and len(st["recent"]) == 1
        rec.clear()
        assert rec.status()["records"] == 0

    def test_cli_pretty_prints_bundle(self, tmp_path, capsys):
        rec = FlightRecorder(capacity=16, enabled=True,
                             dump_dir=str(tmp_path))
        for i in range(5):
            rec.record_step("mln", i, loss=0.5 - i * 0.01, seconds=0.001)
        bundle = rec.dump(reason="manual")
        assert flight_cli([bundle]) == 0
        out = capsys.readouterr().out
        assert "reason : manual" in out
        assert "5 step records" in out
        assert flight_cli([str(tmp_path / "nope")]) == 2

    def test_cli_module_is_runnable(self, tmp_path):
        """`observability.flight` the attribute is the recorder instance;
        the module must still be reachable for `python -m`."""
        rec = FlightRecorder(capacity=16, enabled=True,
                             dump_dir=str(tmp_path))
        rec.record_step("mln", 1, loss=0.5)
        bundle = rec.dump(reason="manual")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m",
             "deeplearning4j_tpu.observability.flight", bundle],
            capture_output=True, text=True, env=env, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "flight bundle" in proc.stdout


# ------------------------------------------------- crash forensics (e2e)


_NAN_CHILD = _CHILD_PRELUDE + r"""
from deeplearning4j_tpu.analysis.runtime import install_nan_guard

install_nan_guard(net)
x = np.full((8, 4), np.nan, dtype="float32")
try:
    net.fit(DataSet(x, y))
except FloatingPointError:
    raise SystemExit(7)
raise SystemExit(3)  # the guard failed to fire
"""

_SIGTERM_CHILD = _CHILD_PRELUDE + r"""
import sys, time
x = rng.randn(8, 4).astype("float32")
for _ in range(3):
    net.fit(DataSet(x, y))  # records steps; installs the signal hooks
print("READY", flush=True)
time.sleep(120)
"""


class TestCrashForensics:
    def test_nan_loss_dumps_bundle_in_subprocess(self, tmp_path):
        """The headline scenario: a diverging run dies with
        FloatingPointError AND leaves a forensics bundle behind."""
        proc = subprocess.run([sys.executable, "-c", _NAN_CHILD],
                              capture_output=True, text=True,
                              env=_child_env(tmp_path), timeout=600)
        assert proc.returncode == 7, (proc.stdout, proc.stderr[-2000:])
        bundles = _bundles(tmp_path)
        assert len(bundles) == 1, "expected exactly one nan-loss bundle"
        manifest, records, trace, metrics = _assert_bundle_valid(bundles[0])
        assert manifest["reason"] == "nan-loss"
        assert "nan-loss" in bundles[0].name
        steps = [r for r in records if r["type"] == "step"]
        assert steps, "ring must hold the steps leading up to divergence"
        assert steps[-1]["loss"] == "nan"
        assert any(r["type"] == "nan_loss" for r in records)
        assert "dl4j_train_iterations_total" in metrics

    def test_sigterm_dumps_bundle_and_reraises(self, tmp_path):
        """Preemption forensics: SIGTERM writes a bundle, then the process
        still dies with the signal status (handlers chain/restore)."""
        proc = subprocess.Popen([sys.executable, "-c", _SIGTERM_CHILD],
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True,
                                env=_child_env(tmp_path))
        try:
            line = proc.stdout.readline()
            assert line.strip() == "READY", proc.stderr.read()[-2000:]
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=120)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        assert proc.returncode == -signal.SIGTERM
        bundles = _bundles(tmp_path)
        assert len(bundles) == 1
        manifest, records, _trace, _metrics = _assert_bundle_valid(
            bundles[0])
        assert manifest["reason"] == "signal:SIGTERM"
        assert any(r["type"] == "step" for r in records)


# ------------------------------------------------------ HBM attribution


class _FakeAnalysis:
    argument_size_in_bytes = 1000
    output_size_in_bytes = 200
    temp_size_in_bytes = 4096
    generated_code_size_in_bytes = 300
    alias_size_in_bytes = 96


class _FakeCompiled:
    def memory_analysis(self):
        return _FakeAnalysis()


class TestProgramMemory:
    def test_program_label(self):
        assert mem.program_label("train_step") == "train_step"
        assert (mem.program_label("solver_step", {"algo": "LBFGS", "k": 2})
                == "solver_step[algo=LBFGS,k=2]")

    def test_record_program_memory_sets_gauges(self):
        from deeplearning4j_tpu import observability as obs

        stats = mem.record_program_memory("test.fake_step", _FakeCompiled())
        assert stats == {"argument": 1000, "output": 200, "temp": 4096,
                         "generated_code": 300, "alias": 96,
                         "total": 1000 + 200 + 4096 + 300 - 96}
        snap = mem.program_memory_snapshot()
        assert snap["test.fake_step"]["temp"] == 4096
        fam = obs.metrics.get_family("dl4j_program_hbm_bytes")
        by_labels = {(c.labels["program"], c.labels["kind"]): c.get()
                     for c in fam.children()}
        assert by_labels[("test.fake_step", "temp")] == 4096
        assert by_labels[("test.fake_step", "total")] == 5500

    def test_record_program_memory_never_raises(self):
        class Broken:
            def memory_analysis(self):
                raise RuntimeError("backend says no")

        assert mem.record_program_memory("test.broken", Broken()) is None
        assert mem.record_program_memory("test.none", object()) is None
        assert "test.broken" not in mem.program_memory_snapshot()

    def test_real_train_step_records_memory(self, rng=None):
        """End to end on the CPU backend: fitting once must record the
        engine's train step in the per-program gauges."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        net = mlp_net()
        r = np.random.RandomState(0)
        x = r.randn(8, 4).astype("float32")
        y = np.eye(3, dtype="float32")[r.randint(0, 3, 8)]
        from deeplearning4j_tpu.observability import StepProfiler

        with StepProfiler(net):
            net.fit(DataSet(x, y))
        snap = mem.program_memory_snapshot()
        labels = [p for p in snap if "train_step" in p]
        assert labels, f"no train_step program recorded: {sorted(snap)}"
        assert all(snap[p]["total"] >= 0 for p in labels)


class TestLiveBufferAttribution:
    def test_registered_tree_owns_its_buffers(self):
        net = mlp_net()
        mem.register_tree("attr_probe", net)
        try:
            report = mem.live_buffer_report()
            assert report["total_bytes"] > 0
            assert "attr_probe" in report["models"]
            model = report["models"]["attr_probe"]
            assert model["bytes"] > 0
            assert any(g.startswith("params_tree/")
                       for g in model["groups"])
            assert (model["bytes"] + report["unattributed_bytes"]
                    <= report["total_bytes"] + 1)
        finally:
            mem.unregister_tree("attr_probe")
        report = mem.live_buffer_report()
        assert "attr_probe" not in report["models"]

    def test_measured_model_bytes(self):
        net = mlp_net()
        measured = mem.measured_model_bytes(net)
        assert measured is not None and measured > 0
        # at least the params themselves
        import jax

        params = sum(int(leaf.nbytes)
                     for leaf in jax.tree_util.tree_leaves(net.params_tree)
                     if isinstance(leaf, jax.Array))
        assert measured >= params

    def test_report_shape(self):
        doc = mem.report()
        assert set(doc) == {"programs", "live"}
        assert {"total_bytes", "models",
                "unattributed_bytes"} <= set(doc["live"])


class TestServingMeasuredHbm:
    def test_host_uses_measured_bytes_for_live_net(self):
        from deeplearning4j_tpu.serving.host import (
            ModelHost, estimate_hbm_bytes,
        )

        net = mlp_net()
        host = ModelHost()
        try:
            model = host.add("measured-probe", net=net)
            assert model.hbm_source == "measured"
            assert model.hbm_bytes >= estimate_hbm_bytes(net)
            rows = {r["name"]: r for r in host.snapshot()}
            row = rows["measured-probe"]
            assert row["hbm_source"] == "measured"
            assert row["hbm_bytes"] == model.hbm_bytes
            # the host registered the net for live attribution
            report = mem.live_buffer_report()
            assert "measured-probe" in report["models"]
        finally:
            host.stop()
            mem.unregister_tree("measured-probe")
