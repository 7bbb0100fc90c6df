"""`nn/engine.py`: the one train step, on both network classes.

The same chain built as a `MultiLayerNetwork` and as a `ComputationGraph`
(vertices named like the layer keys, so both parameter trees have one
structure) must train to the same bits: the step, the superstep, tBPTT, the
f32 master copy, loss scaling with its skipped step, frozen layers and LoRA
adapters are one piece of code and the graph's side had no test of its own.
"""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization,
    DenseLayer,
    DropoutLayer,
    GravesLSTM,
    OutputLayer,
    RnnOutputLayer,
)
from deeplearning4j_tpu.nn.engine import Engine
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.transfer import TransferLearning

POLICIES = ("float32", "mixed_bfloat16", "bfloat16", "mixed_float16")
N_IN, N_OUT, T = 5, 3, 25


def _dense_layers():
    return [DenseLayer(n_out=8, activation="tanh"), BatchNormalization(),
            DropoutLayer(dropout=0.5),
            OutputLayer(n_out=N_OUT, activation="softmax",
                        loss_function="mcxent")]


def _rnn_layers():
    return [GravesLSTM(n_out=6, activation="tanh", dropout=0.3),
            RnnOutputLayer(n_out=N_OUT, activation="softmax",
                           loss_function="mcxent")]


def _builder(policy, superstep_k=0):
    return (NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
            .updater("adam").weight_init("xavier").l2(1e-3)
            .dtype_policy(policy).superstep_k(superstep_k))


def _pair(layers, policy, input_type, tbptt=False, superstep_k=0):
    """The chain as both classes. Vertex names sort like the layer keys, so
    both draw the same initial parameters (and f32 master copies)."""
    lb = _builder(policy, superstep_k).list()
    gb = _builder(policy, superstep_k).graph_builder().add_inputs("in")
    prev = "in"
    for i, layer in enumerate(layers()):
        lb = lb.layer(layer)
        gb = gb.add_layer(f"layer_{i}", layer, prev)
        prev = f"layer_{i}"
    lb = lb.set_input_type(input_type)
    gb = gb.set_outputs(prev)
    gb.set_input_types(input_type)
    if tbptt:
        lb = lb.backprop_type("truncatedbptt").t_bptt_forward_length(10)
        gb = gb.backprop_type("truncatedbptt").t_bptt_forward_length(10)
    mln = MultiLayerNetwork(lb.build()).init()
    graph = ComputationGraph(gb.build()).init()
    _assert_same(_snapshot(mln), _snapshot(graph))
    return mln, graph


def _dense_batches(rng, n, overflow_at=None):
    out = []
    for i in range(n):
        x = rng.randn(6, N_IN).astype("float32")
        if i == overflow_at:
            x[0, 0] = np.inf
        y = np.eye(N_OUT, dtype="float32")[rng.randint(0, N_OUT, 6)]
        out.append(DataSet(x, y))
    return out


def _rnn_batches(rng, n, overflow_at=None):
    out = []
    for i in range(n):
        x = rng.randn(4, T, N_IN).astype("float32")
        if i == overflow_at:
            x[0, :, 0] = np.inf  # in every chunk
        y = np.eye(N_OUT, dtype="float32")[rng.randint(0, N_OUT, (4, T))]
        lmask = np.ones((4, T), "float32")
        lmask[0, 7:] = 0.0
        out.append(DataSet(x, y, None, lmask))
    return out


def _snapshot(net):
    return jax.tree_util.tree_map(
        lambda a: np.array(a, np.float64),
        (net.params_tree, net.state, net.opt_state))


def _assert_same(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


def _fit_both(mln, graph, batches):
    """Fit batch by batch; losses and every tree equal to the bit."""
    for ds in batches:
        mln.fit(ds)
        graph.fit(ds)
        lm, lg = mln.score_value, graph.score_value
        assert lm == lg or (np.isnan(lm) and np.isnan(lg))
        _assert_same(_snapshot(mln), _snapshot(graph))
    assert mln.iteration == graph.iteration


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("mode", ("train_step", "train_superstep", "tbptt"))
def test_both_classes_train_to_the_same_bits(rng, policy, mode):
    scaling = policy == "mixed_float16"
    if mode == "tbptt":
        mln, graph = _pair(_rnn_layers, policy, InputType.recurrent(N_IN),
                           tbptt=True)
        batches = _rnn_batches(rng, 3, overflow_at=1 if scaling else None)
    else:
        k = 2 if mode == "train_superstep" else 0
        mln, graph = _pair(_dense_layers, policy,
                           InputType.feed_forward(N_IN), superstep_k=k)
        batches = _dense_batches(rng, 3, overflow_at=1 if scaling else None)
    if policy == "bfloat16":
        assert graph.params_tree["layer_0"]["W"].dtype == jax.numpy.bfloat16
        assert graph.opt_state["_master"]["layer_0"]["W"].dtype == np.float32

    if mode == "train_superstep":
        # One epoch over three batches: a block of two and a singleton.
        mln.fit(batches)
        graph.fit(batches)
        assert mln.iteration == graph.iteration == 3
        assert any(key[0] == "train_superstep" for key in graph._jit_cache)
        _assert_same(_snapshot(mln), _snapshot(graph))
        if scaling:
            scale = float(graph.opt_state["_ls"][0])
            assert scale == graph.dtype_policy.initial_loss_scale * \
                graph.dtype_policy.loss_scale_backoff_factor
        return

    _fit_both(mln, graph, batches[:1])
    before = _snapshot(graph)
    _fit_both(mln, graph, batches[1:2])
    if scaling:
        # The planted overflow: the step is skipped (parameters, updater
        # state and batch statistics keep their values), the scale backs off.
        pol = graph.dtype_policy
        after = _snapshot(graph)
        _assert_same(before[0], after[0])
        _assert_same(before[1], after[1])
        _assert_same({k: v for k, v in before[2].items() if k != "_ls"},
                     {k: v for k, v in after[2].items() if k != "_ls"})
        steps = 3 if mode == "tbptt" else 1  # a step a chunk
        assert float(graph.opt_state["_ls"][0]) == \
            pol.initial_loss_scale * pol.loss_scale_backoff_factor ** steps
    _fit_both(mln, graph, batches[2:])
    assert graph.iteration == 3 and np.isfinite(graph.score_value)
    with pytest.raises(AssertionError):  # the steps did train
        _assert_same(before[0], _snapshot(graph)[0])


@pytest.mark.parametrize("policy", ("float32", "bfloat16"))
@pytest.mark.parametrize("kind", ("frozen", "lora"))
def test_frozen_and_lora_leaves_on_both_classes(rng, policy, kind):
    mln, graph = _pair(_dense_layers, policy, InputType.feed_forward(N_IN))

    def tuned(net):
        tl = TransferLearning(net)
        tl = (tl.freeze("layer_0") if kind == "frozen"
              else tl.add_lora(rank=2, layers=["layer_0"]))
        return tl.build()

    mln, graph = tuned(mln), tuned(graph)
    assert mln._frozen_spec == graph._frozen_spec
    assert set(graph._frozen_spec["layer_0"]) >= {"W", "b"}
    _assert_same(_snapshot(mln), _snapshot(graph))
    held = {name: (leaf.unsafe_buffer_pointer(), np.array(leaf, np.float64))
            for name, leaf in graph.params_tree["layer_0"].items()
            if name in graph._frozen_spec["layer_0"]}
    first = np.array(graph.params_tree["layer_3"]["W"], np.float64)
    _fit_both(mln, graph, _dense_batches(rng, 3))
    for name, (pointer, value) in held.items():
        # out as in: the step hands a frozen leaf's buffer back, no copy
        leaf = graph.params_tree["layer_0"][name]
        assert leaf.unsafe_buffer_pointer() == pointer
        np.testing.assert_array_equal(np.array(leaf, np.float64), value)
        assert name not in (graph.opt_state["layer_0"] or {}).get("m", {})
    assert not np.array_equal(
        first, np.array(graph.params_tree["layer_3"]["W"], np.float64))
    if kind == "lora":
        lora = [n for n in graph.params_tree["layer_0"] if n not in held]
        assert lora and all(
            np.any(np.array(graph.params_tree["layer_0"][n], np.float64))
            for n in lora)


def test_set_params_keeps_the_graphs_master_copy_in_step(rng):
    _, graph = _pair(_dense_layers, "bfloat16", InputType.feed_forward(N_IN))
    flat = np.asarray(graph.params(), np.float32)
    graph.set_params(flat * 0.5)
    master = graph.opt_state["_master"]["layer_0"]["W"]
    assert master.dtype == np.float32
    np.testing.assert_array_equal(
        np.asarray(master),
        np.asarray(graph.params_tree["layer_0"]["W"]).astype(np.float32))


def test_evaluate_and_score_agree_across_classes(rng):
    mln, graph = _pair(_dense_layers, "float32", InputType.feed_forward(N_IN))
    batches = _dense_batches(rng, 2)
    assert mln.score(batches[0]) == graph.score(batches[0])
    em, eg = mln.evaluate(batches), graph.evaluate(batches)
    assert em.accuracy() == eg.accuracy()
    np.testing.assert_array_equal(mln.params(), graph.params())
    assert mln.num_params() == graph.num_params()


SHARED = (
    "score_value", "init", "_device_clock", "_next_rng", "_get_jit", "warmup",
    "_l1_l2_penalty", "_train_step", "_apply_updates", "fit", "_batch",
    "_fit_dispatch", "_fit_dispatch_inner", "_fit_one", "_fit_solver",
    "_superstep_k", "_check_sgd_only_policy", "_superstep_wrap",
    "_fit_superstep", "_fit_tbptt", "_finish_tbptt", "_declared_state",
    "_output_arrays", "_rnn_step", "rnn_clear_previous_state", "score",
    "evaluate", "set_listeners", "num_params", "_param_orders", "params",
    "set_params", "updater_state_flat", "set_updater_state_flat", "clone",
)


@pytest.mark.parametrize("name", SHARED)
def test_the_twin_has_not_grown_back(name):
    """Both classes inherit one function: a copy on either is the twin."""
    assert getattr(MultiLayerNetwork, name) is getattr(ComputationGraph, name)
    assert name in vars(Engine)


def test_build_jit_override_holds_one_kind():
    assert "_build_jit" not in vars(ComputationGraph)
    mln, _ = _pair(_dense_layers, "float32", InputType.feed_forward(N_IN))
    assert MultiLayerNetwork._build_jit is not Engine._build_jit
    with pytest.raises(ValueError):
        ComputationGraph._build_jit(mln, "feedforward")
    assert callable(mln._build_jit("feedforward"))
    assert callable(mln._build_jit("train_step"))
