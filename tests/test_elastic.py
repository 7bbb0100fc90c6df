"""Elastic fault-tolerant training tests: retry/backoff, the fault
harness, the host coordinator, checkpoint fallback, and ElasticTrainer
end to end — including REAL multi-process chaos runs (kill a worker,
hang the coordinator, truncate the newest checkpoint) that must recover
onto the survivor and land float-close to an unfaulted run.

The multi-process tests use the host-side coordinator transport
(`parallel/coordinator.py`), which works on CPU CI where cross-process
XLA collectives don't — that is the elastic path's whole point.
Equivalence maths: per-step parameter averaging after identical-start
SGD updates equals gradient averaging, and the mean gradient over two
equal half-batches equals the full-batch gradient — so a 2-worker
averaged run (and a recovered 1-worker run on full batches) must both
match plain single-machine training on the full batch stream.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from deeplearning4j_tpu.checkpoint.array_store import CheckpointCorruptError
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (
    ListDataSetIterator, fast_forward)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.neural_net import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.elastic import EVENTS
from deeplearning4j_tpu.parallel.coordinator import (
    ClusterChanged, Coordinator, CoordinatorClient, CoordinatorError,
    parse_address)
from deeplearning4j_tpu.parallel.elastic import ElasticTrainer
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
from deeplearning4j_tpu.util.faultinject import (
    FaultPlan, truncate_newest_chunk)
from deeplearning4j_tpu.util.retry import Backoff, RetryError, with_retries

# --------------------------------------------------------------- helpers

CONF_CODE = textwrap.dedent("""
    import numpy as np
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.conf.neural_net import NeuralNetConfiguration

    def make_conf():
        return (NeuralNetConfiguration.builder()
                .seed(7).learning_rate(0.1).updater("sgd")
                .list()
                .layer(DenseLayer(n_out=16, activation="tanh"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss_function="mcxent"))
                .set_input_type(InputType.feed_forward(4))
                .build())

    def make_data(step):
        r = np.random.RandomState(100 + step)
        X = r.randn(16, 4).astype("float32")
        Y = np.eye(3)[r.randint(0, 3, 16)].astype("float32")
        return X, Y
""")

_NS = {}
exec(CONF_CODE, _NS)
make_conf, make_data = _NS["make_conf"], _NS["make_data"]


def full_batch(step):
    X, Y = make_data(step)
    return DataSet(X, Y)


def shard_fn(step, rank, world):
    """Each worker's slice of the step's 16-row batch; the concatenation
    across ranks is exactly the full batch (the equivalence contract)."""
    X, Y = make_data(step)
    n = X.shape[0] // world
    return DataSet(X[rank * n:(rank + 1) * n], Y[rank * n:(rank + 1) * n])


def reference_params(steps):
    """Plain single-machine training on the full batch stream."""
    net = MultiLayerNetwork(make_conf()).init()
    w = ParallelWrapper(net, workers=1)
    for s in range(steps):
        w.fit(full_batch(s))
    return net


def flat_params(net):
    return {f"{lk}/{pk}": np.asarray(v)
            for lk, layer in net.params_tree.items()
            for pk, v in layer.items()}


def assert_params_close(got, net, rtol=1e-4, atol=1e-6):
    want = flat_params(net)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), want[k],
                                   rtol=rtol, atol=atol, err_msg=f"param {k}")


def event_count(event):
    return EVENTS.labels(event=event).get()


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ------------------------------------------------------------ util/retry

def test_backoff_schedule_and_budget():
    sleeps = []
    bo = Backoff(base_s=0.1, max_s=0.4, tries=4, jitter=False,
                 _sleep=sleeps.append)
    calls = []

    def always_fails():
        calls.append(1)
        raise OSError("nope")

    with pytest.raises(RetryError) as ei:
        bo.run(always_fails, retry_on=(OSError,), describe="doomed")
    assert len(calls) == 4            # tries counts attempts
    assert sleeps == [0.1, 0.2, 0.4]  # exponential, capped, no jitter
    assert isinstance(ei.value.last, OSError)

    # Full jitter: sleep is uniform in [0, cap] — pinned rand halves it.
    bo2 = Backoff(base_s=0.1, max_s=10.0, tries=3, _sleep=sleeps.append,
                  _rand=lambda: 0.5)
    assert bo2.sleep_for(0) == pytest.approx(0.05)
    assert bo2.sleep_for(3) == pytest.approx(0.4)

    # Succeeds mid-way: returns the value, stops retrying.
    state = {"n": 0}

    def flaky():
        state["n"] += 1
        if state["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert bo.run(flaky, retry_on=(OSError,)) == "ok"
    assert state["n"] == 3

    # Non-retryable exception escapes untouched.
    with pytest.raises(ValueError):
        bo.run(lambda: (_ for _ in ()).throw(ValueError("bad")),
               retry_on=(OSError,))


def test_with_retries_env_knobs(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_RETRY_TRIES", "2")
    monkeypatch.setenv("DL4J_TPU_RETRY_BASE_S", "0.0")
    calls = []

    def fails():
        calls.append(1)
        raise OSError("x")

    with pytest.raises(RetryError):
        with_retries(fails, retry_on=(OSError,))
    assert len(calls) == 2  # env default picked up
    calls.clear()
    with pytest.raises(RetryError):
        with_retries(fails, tries=3, retry_on=(OSError,))
    assert len(calls) == 3  # explicit kwarg wins


# ------------------------------------------------------- util/faultinject

def test_fault_plan_parsing(tmp_path, monkeypatch):
    plan = FaultPlan.from_json(
        '[{"kind": "kill", "step": 7, "worker": 1},'
        ' {"kind": "hang_coordinator", "step": 1, "seconds": 2.5}]')
    assert len(plan.faults) == 2 and bool(plan)
    assert plan.faults[0].worker == 1
    assert plan.faults[1].worker is None
    assert plan.faults[1].args == {"seconds": 2.5}

    with pytest.raises(ValueError):
        FaultPlan.from_json('{"kind": "kill", "step": 1}')  # not a list
    with pytest.raises(ValueError):
        FaultPlan.from_json('[{"kind": "meteor", "step": 1}]')
    with pytest.raises(ValueError):
        FaultPlan.from_json('[{"kind": "kill"}]')  # no step

    monkeypatch.delenv("DL4J_TPU_FAULT_PLAN", raising=False)
    assert not FaultPlan.from_env()
    monkeypatch.setenv("DL4J_TPU_FAULT_PLAN",
                       '[{"kind": "preempt", "step": 3}]')
    assert FaultPlan.from_env().faults[0].kind == "preempt"
    plan_file = tmp_path / "plan.json"
    plan_file.write_text('[{"kind": "delay_h2d", "step": 2, "ms": 1}]')
    monkeypatch.setenv("DL4J_TPU_FAULT_PLAN", f"@{plan_file}")
    assert FaultPlan.from_env().faults[0].kind == "delay_h2d"


def test_fault_fire_once_and_filters():
    plan = FaultPlan.from_json(
        '[{"kind": "kill", "step": 5, "worker": 1},'
        ' {"kind": "preempt", "step": 5},'
        ' {"kind": "hang_coordinator", "step": 6}]')
    hits = []
    handlers = {"kill": lambda f: hits.append("kill"),
                "preempt": lambda f: hits.append("preempt")}

    assert plan.maybe_fire(4, 1, handlers) == []        # wrong step
    fired = plan.maybe_fire(5, 0, handlers)             # rank filter
    assert [f.kind for f in fired] == ["preempt"]       # kill wants rank 1
    fired = plan.maybe_fire(5, 1, handlers)
    assert [f.kind for f in fired] == ["kill"]          # preempt fired once
    assert plan.maybe_fire(5, 1, handlers) == []        # fire-once
    # Handler-less hang is marked fired (no action) and reported.
    fired = plan.maybe_fire(6, 0, {})
    assert [f.kind for f in fired] == ["hang_coordinator"]
    assert hits == ["preempt", "kill"]


def test_truncate_newest_chunk(tmp_path):
    d = tmp_path / "step_00000004"
    d.mkdir()
    (d / "manifest.json").write_text("x" * 500)
    (d / "COMMIT").write_text("ok")
    (d / "chunk_small.bin").write_bytes(b"a" * 100)
    (d / "chunk_big.bin").write_bytes(b"b" * 1000)
    hit = truncate_newest_chunk(str(d), drop_bytes=64)
    assert hit.endswith("chunk_big.bin")  # largest non-manifest/COMMIT file
    assert os.path.getsize(d / "chunk_big.bin") == 936
    assert os.path.getsize(d / "manifest.json") == 500
    empty = tmp_path / "empty"
    empty.mkdir()
    assert truncate_newest_chunk(str(empty)) is None


# --------------------------------------------------- checkpoint fallback

def _managed_net(tmp_path, steps=2):
    net = MultiLayerNetwork(make_conf()).init()
    wrapper = ParallelWrapper(net, workers=1)
    mgr = wrapper.checkpoint_manager(str(tmp_path / "ckpt"),
                                     async_save=False)
    for s in range(steps):
        wrapper.fit(full_batch(s))
        mgr.save(net)
    return net, wrapper, mgr


def test_manager_maybe_save_cadence(tmp_path):
    net = MultiLayerNetwork(make_conf()).init()
    wrapper = ParallelWrapper(net, workers=1)
    mgr = wrapper.checkpoint_manager(str(tmp_path / "c"), async_save=False,
                                     save_every=3)
    assert mgr.maybe_save(net, step=0) is None   # step 0 never saves
    assert mgr.maybe_save(net, step=2) is None
    assert mgr.maybe_save(net, step=3) is not None
    assert mgr.maybe_save(net, step=4) is None
    assert mgr.maybe_save(net, step=6) is not None
    assert mgr.all_steps() == [3, 6]
    off = wrapper.checkpoint_manager(str(tmp_path / "c2"), async_save=False)
    assert off.maybe_save(net, step=3) is None   # cadence disabled


def test_manager_restore_falls_back_past_corrupt_newest(tmp_path):
    net, wrapper, mgr = _managed_net(tmp_path)
    steps = mgr.all_steps()
    assert len(steps) == 2
    truncate_newest_chunk(mgr.step_path(steps[-1]))
    before = event_count("restore_fallback")
    fresh = MultiLayerNetwork(make_conf()).init()
    with pytest.warns(RuntimeWarning, match="corruption"):
        restored = mgr.restore(net=fresh)
    assert restored.iteration == steps[0]  # fell back to previous commit
    assert event_count("restore_fallback") >= before + 1
    # An explicitly named bad step still raises — the caller asked for it.
    with pytest.raises(CheckpointCorruptError):
        mgr.restore(step=steps[-1], net=fresh)
    # Every copy corrupt -> clean terminal error.
    truncate_newest_chunk(mgr.step_path(steps[0]))
    with pytest.raises(CheckpointCorruptError):
        with pytest.warns(RuntimeWarning):
            mgr.restore(net=fresh)


def test_fast_forward_iterator():
    batches = [full_batch(s) for s in range(5)]
    it = ListDataSetIterator(batches, batch_size=16)
    stream = fast_forward(it, 2)
    nxt = next(stream)
    np.testing.assert_array_equal(nxt.features, batches[2].features)
    assert len(list(stream)) == 2  # 3 and 4 remain
    # Past the end -> exhausted, not an error.
    assert list(fast_forward(it, 99)) == []


# ------------------------------------------------------- host coordinator

def test_coordinator_join_allreduce_and_barrier():
    coord = Coordinator(lost_after_s=30.0).start()
    try:
        results = {}

        def worker(wid, vec):
            c = CoordinatorClient(coord.address, wid, rpc_timeout_s=5.0)
            doc = c.join(expected=2, grace_s=10.0)
            c.barrier("start", step=0, timeout_s=10.0)
            mean = c.allreduce_mean("params", 1, {"v": np.asarray(vec)},
                                    timeout_s=10.0)
            # Idempotent re-ask: cached result, same mean, no double-count.
            again = c.allreduce_mean("params", 1, {"v": np.asarray(vec)},
                                     timeout_s=10.0)
            # Second barrier: leave() bumps the generation, which would
            # turn a peer's still-in-flight re-ask into ClusterChanged.
            c.barrier("done", step=1, timeout_s=10.0)
            results[wid] = (doc, mean, again)
            c.leave()

        ts = [threading.Thread(target=worker,
                               args=(wid, vec), daemon=True)
              for wid, vec in (("a", [1.0, 2.0]), ("b", [3.0, 4.0]))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
            assert not t.is_alive()
        assert results["a"][0]["rank"] == 0 and results["b"][0]["rank"] == 1
        assert results["a"][0]["world"] == 2
        for wid in ("a", "b"):
            np.testing.assert_allclose(results[wid][1]["v"], [2.0, 3.0])
            np.testing.assert_allclose(results[wid][2]["v"], [2.0, 3.0])
    finally:
        coord.close()


def test_coordinator_hang_survived_by_retry():
    coord = Coordinator(lost_after_s=30.0).start()
    try:
        c = CoordinatorClient(coord.address, "w", rpc_timeout_s=0.2)
        c.join(expected=1, grace_s=5.0)
        before = event_count("coordinator_retry")
        coord.inject_hang(0.8)
        doc = c.heartbeat()  # stalls > rpc timeout -> backoff retries
        assert doc["ok"] and doc["known"]
        assert event_count("coordinator_retry") >= before + 1
    finally:
        coord.close()


def test_coordinator_evicts_lost_host_and_unblocks_collective():
    coord = Coordinator(lost_after_s=0.6).start()
    try:
        a = CoordinatorClient(coord.address, "a", rpc_timeout_s=5.0)
        b = CoordinatorClient(coord.address, "b", rpc_timeout_s=5.0)
        docs = {}
        t = threading.Thread(
            target=lambda: docs.update(b=b.join(expected=2, grace_s=10.0)),
            daemon=True)
        t.start()
        a.join(expected=2, grace_s=10.0)
        t.join(timeout=10)
        a.start_heartbeats(0.15)
        before = event_count("host_lost")
        try:
            # "b" never heartbeats: the reaper evicts it mid-collective and
            # the survivor unblocks with ClusterChanged, not a hang.
            with pytest.raises(ClusterChanged):
                a.allreduce_mean("p", 1, {"v": np.ones(2)}, timeout_s=10.0)
            assert event_count("host_lost") >= before + 1
            # The heartbeat thread saw the new generation too.
            deadline = 20
            while not a.cluster_changed and deadline:
                threading.Event().wait(0.1)
                deadline -= 1
            with pytest.raises(ClusterChanged):
                a.check()
            # Re-join clears the flag and re-forms on the survivor.
            doc = a.join(expected=None, grace_s=1.0)
            assert doc["world"] == 1 and doc["members"] == ["a"]
            a.check()
        finally:
            a.stop_heartbeats()
    finally:
        coord.close()


def test_join_wait_survives_lease_shorter_than_grace():
    """A joiner blocked waiting for the expected world heartbeats only
    AFTER join returns — the coordinator must keep its lease fresh while
    it waits, or the reaper evicts the very worker sitting in the join
    (join grace > lease is the default configuration)."""
    coord = Coordinator(lost_after_s=0.3).start()
    try:
        c = CoordinatorClient(coord.address, "slowpoke", rpc_timeout_s=10.0)
        # The second worker never shows: the join blocks through several
        # full lease periods, then forms the cluster on whoever is there.
        doc = c.join(expected=2, grace_s=1.2)
        assert doc["ok"] and doc["members"] == ["slowpoke"]
        assert doc["rank"] == 0 and doc["world"] == 1
    finally:
        coord.close()


def test_coordinator_purges_stale_collective_state():
    coord = Coordinator(lost_after_s=30.0).start()
    try:
        a = CoordinatorClient(coord.address, "a", rpc_timeout_s=5.0)
        a.join(expected=1, grace_s=5.0)
        # Completed barriers are kept only as a bounded tail.
        for s in range(20):
            a.barrier("b", step=s, timeout_s=5.0)
        with coord._cond:
            assert 0 < len(coord._barriers) <= 8
        # Plant an abandoned old-gen contribution (a worker that died
        # mid-allreduce); any generation bump must purge it and every
        # old-gen barrier set.
        with coord._cond:
            coord._contribs[(a.gen, 99, "orphan")] = {"a": {}}
        b = CoordinatorClient(coord.address, "b", rpc_timeout_s=5.0)
        b.join(expected=None, grace_s=5.0)  # gen bump
        with coord._cond:
            assert not coord._contribs
            assert not coord._barriers
    finally:
        coord.close()


def test_parse_address_portless():
    assert parse_address("myhost:1234") == ("myhost", 1234)
    assert parse_address(":1234") == ("127.0.0.1", 1234)
    assert parse_address("myhost") == ("myhost", 0)  # no ValueError
    c = CoordinatorClient("myhost", "w")  # parse-time must not raise
    assert (c.host, c.port) == ("myhost", 0)


# --------------------------------------------- ElasticTrainer, in-process

def test_elastic_single_process_train_and_resume(tmp_path):
    root = str(tmp_path / "ckpt")
    net = MultiLayerNetwork(make_conf()).init()
    tr = ElasticTrainer(ParallelWrapper(net, workers=1),
                        checkpoint_root=root, save_every=2,
                        fault_plan=FaultPlan())
    res = tr.run(shard_fn, steps=6)
    assert res.status == "finished" and res.step == 6 and res.restarts == 0
    assert tr.manager.all_steps() == [2, 4, 6]

    # A relaunched process resumes from the newest commit, not step 0.
    before = event_count("restore")
    net2 = MultiLayerNetwork(make_conf()).init()
    tr2 = ElasticTrainer(ParallelWrapper(net2, workers=1),
                         checkpoint_root=root, save_every=2,
                         fault_plan=FaultPlan())
    res2 = tr2.run(shard_fn, steps=8)
    assert res2.status == "finished" and res2.step == 8
    assert event_count("restore") >= before + 1
    assert_params_close(flat_params(net2), reference_params(8),
                        rtol=1e-6, atol=1e-9)


def test_elastic_iterator_data_fast_forwards_on_resume(tmp_path):
    root = str(tmp_path / "ckpt")
    batches = [full_batch(s) for s in range(8)]
    net = MultiLayerNetwork(make_conf()).init()
    tr = ElasticTrainer(ParallelWrapper(net, workers=1),
                        checkpoint_root=root, save_every=2,
                        fault_plan=FaultPlan())
    assert tr.run(ListDataSetIterator(batches, 16), steps=4).step == 4

    net2 = MultiLayerNetwork(make_conf()).init()
    tr2 = ElasticTrainer(ParallelWrapper(net2, workers=1),
                         checkpoint_root=root, save_every=2,
                         fault_plan=FaultPlan())
    res = tr2.run(ListDataSetIterator(batches, 16), steps=8)
    assert res.step == 8  # restored 4, fast-forwarded, trained 4..7
    assert_params_close(flat_params(net2), reference_params(8),
                        rtol=1e-6, atol=1e-9)


def test_position_stream_non_resettable_skips_only_delta():
    """On an in-run restart the shared iterator is already partially
    consumed; a non-resettable stream must skip only the delta to the
    restored step — not `restored_step` MORE batches from the current
    position (silent training-data loss on every recovery)."""
    net = MultiLayerNetwork(make_conf()).init()
    tr = ElasticTrainer(ParallelWrapper(net, workers=1),
                        fault_plan=FaultPlan())
    gen = iter([full_batch(s) for s in range(8)])  # no reset()

    stream = tr._position_stream(gen, 2)  # fresh start restored at step 2
    np.testing.assert_array_equal(next(stream).features,
                                  full_batch(2).features)
    tr._stream_pos += 1  # the train loop accounts for each draw

    # Restart restored at step 3 == current position: skip NOTHING.
    stream = tr._position_stream(gen, 3)
    np.testing.assert_array_equal(next(stream).features,
                                  full_batch(3).features)
    tr._stream_pos += 1

    # Restored step behind the live position: unreplayable -> warn,
    # continue from where the stream actually is.
    with pytest.warns(RuntimeWarning, match="not resettable"):
        stream = tr._position_stream(gen, 1)
    np.testing.assert_array_equal(next(stream).features,
                                  full_batch(4).features)


def test_coordinator_error_is_recoverable(monkeypatch):
    """An error document from the coordinator (e.g. a transient
    membership-shape failure) must consume the restart budget, not kill
    the run outright."""
    net = MultiLayerNetwork(make_conf()).init()
    tr = ElasticTrainer(ParallelWrapper(net, workers=1),
                        fault_plan=FaultPlan(), max_restarts=2)
    real = tr._train
    calls = {"n": 0}

    def flaky(data, steps, result):
        calls["n"] += 1
        if calls["n"] == 1:
            raise CoordinatorError("coordinator error: ValueError: boom")
        return real(data, steps, result)

    monkeypatch.setattr(tr, "_train", flaky)
    res = tr.run(shard_fn, steps=3)
    assert res.status == "finished" and res.step == 3
    assert res.restarts == 1


def test_elastic_sigterm_preempt_checkpoints_and_exits(tmp_path, monkeypatch):
    from deeplearning4j_tpu.observability import flight

    monkeypatch.setattr(flight, "dump_dir", str(tmp_path / "flight"))
    root = str(tmp_path / "ckpt")
    net = MultiLayerNetwork(make_conf()).init()
    plan = FaultPlan.from_json('[{"kind": "preempt", "step": 2}]')
    tr = ElasticTrainer(ParallelWrapper(net, workers=1),
                        checkpoint_root=root, save_every=0,  # only the
                        fault_plan=plan)                     # preempt save
    before = event_count("preempt")
    res = tr.run(shard_fn, steps=6)
    assert res.status == "preempted" and res.step == 2
    assert res.checkpoint and res.checkpoint.endswith("step_00000002")
    assert tr.manager.all_steps() == [2]  # exactly one committed step
    assert event_count("preempt") == before + 1
    # The run's SIGTERM handler was uninstalled on exit.
    import signal as _signal
    assert _signal.getsignal(_signal.SIGTERM) is tr._prev_sigterm \
        or tr._prev_sigterm is None


def test_elastic_two_worker_averaging_matches_single_machine(tmp_path):
    """The coordinator-transport equivalence (in threads): 2 workers,
    per-step parameter averaging == single-machine full-batch SGD."""
    addr = f"127.0.0.1:{_free_port()}"
    nets, errs = {}, []

    def worker(wid, host):
        try:
            net = MultiLayerNetwork(make_conf()).init()
            tr = ElasticTrainer(
                ParallelWrapper(net, workers=1), coordinator_address=addr,
                worker_id=wid, expected_world=2, host_coordinator=host,
                heartbeat_s=0.2, join_grace_s=20.0,
                collective_timeout_s=20.0, fault_plan=FaultPlan())
            res = tr.run(shard_fn, steps=5)
            assert res.status == "finished" and res.step == 5
            nets[wid] = net
        except Exception as e:  # surfaced by the main thread
            errs.append(e)

    ts = [threading.Thread(target=worker, args=("a", True), daemon=True),
          threading.Thread(target=worker, args=("b", False), daemon=True)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
        assert not t.is_alive(), "elastic worker thread hung"
    assert not errs, errs
    ref = reference_params(5)
    assert_params_close(flat_params(nets["a"]), ref)
    assert_params_close(flat_params(nets["b"]), ref)


# ------------------------------------------------- multi-process chaos CI

CHAOS_WORKER = """
import json, os, sys
wid = sys.argv[1]; addr = sys.argv[2]; root = sys.argv[3]; out = sys.argv[4]
is_host = sys.argv[5] == "host"
os.environ["DL4J_TPU_FLIGHT_DIR"] = os.path.join(root, "flight-" + wid)
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

__CONF__

import numpy as np
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.elastic import ElasticTrainer
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

def shard_fn(step, rank, world):
    X, Y = make_data(step)
    n = X.shape[0] // world
    return DataSet(X[rank*n:(rank+1)*n], Y[rank*n:(rank+1)*n])

net = MultiLayerNetwork(make_conf()).init()
trainer = ElasticTrainer(
    ParallelWrapper(net, workers=1),
    coordinator_address=addr, worker_id=wid, expected_world=2,
    checkpoint_root=os.path.join(root, "ckpt"), save_every=__SAVE_EVERY__,
    host_coordinator=is_host, heartbeat_s=0.25, join_grace_s=60.0,
    collective_timeout_s=20.0, lost_after_s=2.0)
if trainer.manager is not None:
    # Deterministic commit-before-fault ordering for the test schedule.
    trainer.manager.async_save = False
# Short RPC timeout so an injected coordinator hang forces visible
# backoff retries instead of hiding inside one long blocking read.
trainer.client.rpc_timeout_s = 1.0
result = trainer.run(shard_fn, steps=__STEPS__)

from deeplearning4j_tpu.observability.elastic import EVENTS
events = dict((e, EVENTS.labels(event=e).get())
              for e in ("preempt", "host_lost", "restart", "restore",
                        "restore_fallback", "coordinator_retry"))
params = dict()
for lk, layer in net.params_tree.items():
    for pk, v in layer.items():
        params[lk + "/" + pk] = np.asarray(v).tolist()
committed = trainer.manager.all_steps() if trainer.manager else []
flight_dir = os.environ["DL4J_TPU_FLIGHT_DIR"]
bundles = sorted(os.listdir(flight_dir)) if os.path.isdir(flight_dir) else []
with open(out, "w") as f:
    json.dump({"status": result.status, "step": result.step,
               "restarts": result.restarts,
               "recoveries_s": list(result.recoveries_s),
               "checkpoint": result.checkpoint, "committed": committed,
               "bundles": bundles, "events": events, "params": params}, f)
print("worker", wid, "done", flush=True)
"""


def _spawn_elastic_workers(tmp_path, plan, steps, save_every):
    addr = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "worker.py"
    script.write_text(CHAOS_WORKER
                      .replace("__CONF__", CONF_CODE)
                      .replace("__SAVE_EVERY__", str(save_every))
                      .replace("__STEPS__", str(steps)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "compile-cache")
    env["DL4J_TPU_FAULT_PLAN"] = json.dumps(plan)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    procs = []
    for wid, role in (("a", "host"), ("b", "peer")):
        procs.append(subprocess.Popen(
            [sys.executable, str(script), wid, addr, str(tmp_path),
             str(tmp_path / f"out-{wid}.json"), role],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env=env, text=True))
    outputs = []
    try:
        for p in procs:
            # XLA:CPU logs a kilobyte-long line per loaded cache entry;
            # they would push the traceback out of the asserts' tails.
            outputs.append("\n".join(
                line for line in p.communicate(timeout=300)[0].splitlines()
                if "cpu_aot_loader" not in line))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outputs


def _load_out(tmp_path, wid):
    with open(tmp_path / f"out-{wid}.json") as f:
        return json.load(f)


def test_chaos_two_process_kill_hang_truncate_recovers(tmp_path):
    """The CI chaos drill (acceptance criteria): a 2-process run whose
    plan hangs the coordinator at step 1, truncates the newest committed
    checkpoint at step 7 (worker a) and kills worker b at step 7 must
    recover on the survivor — restore past the corrupt copy onto the
    re-formed world-1 cluster, finish all 10 steps, and land float-close
    to an unfaulted single-machine run of the same schedule — with every
    recovery event visible in dl4j_elastic_events_total."""
    steps = 10
    procs, outputs = _spawn_elastic_workers(
        tmp_path,
        plan=[
            {"kind": "hang_coordinator", "step": 1, "worker": 0,
             "seconds": 2.0},
            {"kind": "truncate_chunk", "step": 7, "worker": 0, "bytes": 64},
            {"kind": "kill", "step": 7, "worker": 1},
        ],
        steps=steps, save_every=2)
    assert procs[0].returncode == 0, f"survivor failed:\n{outputs[0][-3000:]}"
    assert procs[1].returncode == 137, \
        f"worker b should die by os._exit(137):\n{outputs[1][-3000:]}"

    got = _load_out(tmp_path, "a")
    assert got["status"] == "finished"
    assert got["step"] == steps
    assert got["restarts"] == 1
    assert len(got["recoveries_s"]) == 1 and got["recoveries_s"][0] > 0
    ev = got["events"]
    assert ev["host_lost"] >= 1, ev          # reaper evicted worker b
    assert ev["restart"] >= 1, ev            # supervisor re-entered join
    assert ev["restore"] >= 1, ev            # checkpoint restored
    assert ev["restore_fallback"] >= 1, ev   # corrupt newest skipped
    assert ev["coordinator_retry"] >= 1, ev  # hang survived via backoff
    assert ev["preempt"] == 0, ev
    # Float-close equivalence with the unfaulted run of the same schedule.
    assert_params_close(got["params"], reference_params(steps))


def test_preemption_forensics_two_process_then_resume(tmp_path):
    """Satellite contract: SIGTERM (via the fault plan's preempt) during a
    2-process run leaves EXACTLY one committed checkpoint and one flight
    bundle per process; a restarted cluster resumes at the checkpointed
    step and finishes float-close to an uninterrupted run."""
    steps = 6
    procs, outputs = _spawn_elastic_workers(
        tmp_path, plan=[{"kind": "preempt", "step": 3}],
        steps=steps, save_every=0)
    for p, text in zip(procs, outputs):
        assert p.returncode == 0, (
            f"worker {p.args[2]} failed:\n{text[-3000:]}")
    for wid in ("a", "b"):
        got = _load_out(tmp_path, wid)
        assert got["status"] == "preempted", got
        assert got["step"] == 3
        assert got["committed"] == [3], \
            f"exactly one committed checkpoint expected: {got['committed']}"
        assert len(got["bundles"]) == 1, \
            f"exactly one flight bundle expected: {got['bundles']}"
        assert got["events"]["preempt"] == 1

    # Restart the cluster (in threads) on the same checkpoint root: both
    # workers must restore step 3 and finish the schedule.
    addr = f"127.0.0.1:{_free_port()}"
    nets, errs = {}, []

    def worker(wid, host):
        try:
            net = MultiLayerNetwork(make_conf()).init()
            tr = ElasticTrainer(
                ParallelWrapper(net, workers=1), coordinator_address=addr,
                worker_id=wid, expected_world=2, host_coordinator=host,
                checkpoint_root=str(tmp_path / "ckpt"), save_every=0,
                heartbeat_s=0.2, join_grace_s=20.0,
                collective_timeout_s=20.0, fault_plan=FaultPlan())
            res = tr.run(shard_fn, steps=steps)
            assert res.status == "finished" and res.step == steps
            nets[wid] = net
        except Exception as e:
            errs.append(e)

    before = event_count("restore")
    ts = [threading.Thread(target=worker, args=("a", True), daemon=True),
          threading.Thread(target=worker, args=("b", False), daemon=True)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
        assert not t.is_alive(), "resume worker thread hung"
    assert not errs, errs
    assert event_count("restore") >= before + 2  # both workers restored
    ref = reference_params(steps)
    assert_params_close(flat_params(nets["a"]), ref)
    assert_params_close(flat_params(nets["b"]), ref)
