"""The program's spans in the device trace (ISSUE 24).

A live span of `observability.tracer` also opens a
`jax.profiler.TraceAnnotation` while a `jax.profiler` capture runs, so the
span vocabulary of `fit` and of the decode loop lands on `/host:CPU`, on
its thread's line, nested, beside the device's operations. The ring, the
ids and federation stay as they were; the copy of every round's span that
each active request used to get is one `serving.decode` per request.
"""

import ast
import glob
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.neural_net import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import federation, propagate
from deeplearning4j_tpu.observability.tracing import NOOP_SPAN, Tracer
from deeplearning4j_tpu.serving.scheduler import (
    GenerationRequest,
    GenerationScheduler,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Capture:
    """A `jax.profiler` capture with the options the benchmark's
    `WindowTracer` uses (host tracer on, Python tracer off)."""

    def __init__(self, directory):
        self.directory = str(directory)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False

    def host_lines(self):
        """`[[(name, start_ns, end_ns, stats)]]`, one list per line of
        `/host:CPU`."""
        from jax.profiler import ProfileData

        path = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        return [[(e.name, e.start_ns, e.start_ns + e.duration_ns,
                  {k: v for k, v in e.stats}) for e in line.events]
                for plane in ProfileData.from_file(path).planes
                if plane.name == "/host:CPU" for line in plane.lines]


def _mln():
    conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.1)
            .updater("sgd").list()
            .layer(0, DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(1, OutputLayer(n_in=8, n_out=3, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _graph():
    conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.1)
            .updater("sgd").graph_builder().add_inputs("in")
            .add_layer("h", DenseLayer(n_in=4, n_out=8, activation="relu"),
                       "in")
            .add_layer("out", OutputLayer(n_in=8, n_out=3,
                                          activation="softmax",
                                          loss_function="mcxent"), "h")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def _batches(n=3):
    rng = np.random.RandomState(0)
    return [DataSet(rng.rand(6, 4).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.randint(0, 3, 6)])
            for _ in range(n)]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("engine,build", [("graph", _graph), ("mln", _mln)])
def test_fit_spans_nest_on_one_host_line(engine, build, tmp_path):
    net = build()
    data = _batches()
    net.fit(data)  # compile outside the capture
    obs.tracer.clear()
    it0 = net.iteration
    with _Capture(tmp_path) as capture:
        net.fit(data)
    names = [f"{engine}.{what}"
             for what in ("fit", "input_wait", "iteration", "enqueue")]
    lines = [line for line in capture.host_lines()
             if any(e[0] == names[0] for e in line)]
    assert len(lines) == 1, "the engine's spans are on one thread's line"
    by_name = {n: [e for e in lines[0] if e[0] == n] for n in names}
    fit, waits, iterations, enqueues = (by_name[n] for n in names)
    assert len(fit) == 1 and len(iterations) == 3 and len(enqueues) == 3
    assert len(waits) == 4  # three batches and the StopIteration
    for it, enq in zip(iterations, enqueues):
        assert _inside(enq, it) and _inside(it, fit[0])
    for wait in waits:
        assert _inside(wait, fit[0])
        assert not any(_inside(wait, it) for it in iterations)
    assert [it[3]["iteration"] for it in iterations] == [
        it0 + 1, it0 + 2, it0 + 3]
    assert iterations[0][3]["engine"] == engine
    ring = [ev["name"] for ev in obs.tracer.events()]
    for name, events in by_name.items():
        assert ring.count(name) == len(events)
    parents = {ev["name"]: ev["args"].get("parent")
               for ev in obs.tracer.events()}
    assert parents[f"{engine}.enqueue"] == f"{engine}.iteration"
    assert parents[f"{engine}.iteration"] == f"{engine}.fit"
    assert parents[f"{engine}.input_wait"] == f"{engine}.fit"


def test_stager_thread_records_staging_put():
    from deeplearning4j_tpu.datasets import staging

    obs.tracer.clear()
    stager = staging.DeviceStager(_batches(4), engine="mln")
    try:
        assert len(list(stager)) == 4
    finally:
        staging.close_stager(stager)
    puts = [ev for ev in obs.tracer.events() if ev["name"] == "staging.put"]
    assert len(puts) == 4
    assert {ev["tid"] for ev in puts} != {
        threading.get_ident() & 0x7FFFFFFF}, "recorded on the stager thread"


def test_spans_never_import_jax():
    """A process without jax opens spans without importing it. (This
    package's own import brings jax in, so the child forgets it again
    before the first span: what is shown is that no span asks for it.)"""
    code = (
        "import sys\n"
        "from deeplearning4j_tpu.observability.tracing import Tracer\n"
        "for name in [m for m in sys.modules\n"
        "             if m == 'jax' or m.startswith('jax.')]:\n"
        "    del sys.modules[name]\n"
        "t = Tracer()\n"
        "with t.span('router.predict', cat='fleet', k=2):\n"
        "    with t.span('inner'):\n"
        "        pass\n"
        "t.complete('wait', 0, 10)\n"
        "assert [e['name'] for e in t.events()] == "
        "['inner', 'router.predict', 'wait']\n"
        "assert 'jax' not in sys.modules, 'a span imported jax'\n"
        "print('ok')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "ok"


def test_disabled_tracer_is_noop_and_writes_no_annotation(tmp_path):
    live, off = Tracer(), Tracer(enabled=False)
    assert off.span("off.span", k=1) is NOOP_SPAN
    with _Capture(tmp_path) as capture:
        with off.span("off.span"):
            pass
        off.complete("off.wait", 0, 10)
        with live.span("live.span", k=3, label="x", blob=[1, 2]):
            pass
        live.complete("live.wait", 0, 10)
    events = {e[0]: e for line in capture.host_lines() for e in line}
    assert "off.span" not in events and not off.events()
    # Scalars travel as stats; a retroactive `complete` is ring-only.
    assert events["live.span"][3] == {"k": 3, "label": "x"}
    assert "live.wait" not in events
    assert [e["name"] for e in live.events()] == ["live.span", "live.wait"]


def test_no_capture_running_opens_no_annotation():
    t = Tracer()
    with t.span("quiet") as span:
        assert span._annotation is None
    assert [e["name"] for e in t.events()] == ["quiet"]


# ------------------------------------------------------------- serving


V, CAP = 17, 32


@pytest.fixture(scope="module")
def lm():
    conf = zoo.transformer_lm(vocab_size=V, t=16, d_model=16, n_heads=2,
                              n_blocks=1, decode_cache_length=CAP,
                              seed=12345)
    return ComputationGraph(conf).init()


def test_decode_loop_spans_and_one_decode_span_a_request(lm):
    sched = GenerationScheduler(lm, model_name="spans", slots=2).start()
    obs.tracer.clear()
    roots = [propagate.mint(), propagate.mint()]
    steps = [6, 4]
    reqs = []
    try:
        for root, n in zip(roots, steps):
            with propagate.bound(root):
                reqs.append(sched.submit(GenerationRequest(
                    [1, 2, 3], n, temperature=0.0)))
        for req in reqs:
            assert req.event.wait(120) and req.error is None
    finally:
        sched.stop()
    events = obs.tracer.events()
    by_name = {}
    for ev in events:
        by_name.setdefault(ev["name"], []).append(ev)
    # The decode loop's whole vocabulary: nothing per slot or per token.
    assert {n for n in by_name if n.startswith("serving.")} == {
        "serving.admission_wait", "serving.admit", "serving.prefill",
        "serving.decode_round", "serving.enqueue", "serving.fetch",
        "serving.sample", "serving.decode"}
    rounds = by_name["serving.decode_round"]
    # One live span a round, whatever the number of active requests: the
    # longer request rides n - 1 rounds after its first token.
    assert len(rounds) == max(steps) - 1
    assert all(1 <= ev["args"]["slots"] <= 2 for ev in rounds)
    for name in ("serving.enqueue", "serving.fetch"):
        in_rounds = [ev for ev in by_name[name]
                     if ev["args"]["parent"] == "serving.decode_round"]
        in_prefill = [ev for ev in by_name[name]
                      if ev["args"]["parent"] == "serving.prefill"]
        assert len(in_rounds) == len(rounds)
        assert len(in_prefill) == 2
    assert len(by_name["serving.admit"]) == 2
    assert all(ev["args"]["parent"] == "serving.admit"
               for ev in by_name["serving.prefill"])
    samples = by_name["serving.sample"]
    assert len(samples) == len(rounds) + 2  # a round each, a first token each
    decodes = by_name["serving.decode"]
    assert len(decodes) == 2
    for root, n in zip(roots, steps):
        mine = [ev for ev in decodes
                if ev["args"]["trace_id"] == root.trace_id]
        assert len(mine) == 1
        assert mine[0]["args"]["rounds"] == n - 1
        assert mine[0]["args"]["tokens"] == n - 1
        assert mine[0]["args"]["parent_span_id"] == root.span_id
        assert mine[0]["dur"] > 0

    # The federated request tree: admission_wait, prefill and decode hang
    # off the request's own span id, O(1) spans a request.
    merged = federation.merge_traces({"replica": obs.tracer.export_chrome()})
    for root in roots:
        children = sorted(
            ev["name"] for ev in merged["traceEvents"]
            if ev.get("ph") == "X"
            and ev["args"].get("parent_span_id") == root.span_id)
        assert children == ["serving.admission_wait", "serving.decode",
                            "serving.prefill"]
        assert all(ev["args"]["worker_id"] == "replica"
                   for ev in merged["traceEvents"] if ev.get("ph") == "X")


# ------------------------------------------------------- kernel names


def _pallas_call_sites():
    sites = []
    for path in sorted(glob.glob(os.path.join(
            ROOT, "deeplearning4j_tpu", "kernels", "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                sites.append(pytest.param(
                    node, id=f"{os.path.basename(path)}:{node.lineno}"))
    return sites


@pytest.mark.parametrize("call", _pallas_call_sites())
def test_every_pallas_call_is_named(call):
    """A kernel's operations are found in the trace's `XLA Ops` by the
    `name=` of its `pallas_call` (one name per kernel body)."""
    names = [kw.value for kw in call.keywords if kw.arg == "name"]
    assert len(names) == 1, "pl.pallas_call without name="
    assert isinstance(names[0], (ast.Constant, ast.JoinedStr))


def test_kernels_have_pallas_call_sites():
    assert len(_pallas_call_sites()) >= 13
