"""Every operation of a compiled train step runs under a name the engine
gave it (PERF.md PR 36): `L.<vertex>` around each vertex of either network
class (its parameters' cast, its preprocessor, its forward, an output layer's
loss) and `step.grad_cast` / `step.update` (inside it `L.<key>` a layer) /
`step.store` around the phases of `Engine._train_step`. The names are
metadata: the compiled program is the same with every scope taken out, and no
vertex name can read as one of the dotted scopes the layer bodies open."""

import functools
import re
from contextlib import nullcontext
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import scope_table
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models import resnet, zoo
from deeplearning4j_tpu.nn.conf.enums import Updater
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    DenseLayer, GlobalPoolingLayer, OutputLayer)
from deeplearning4j_tpu.nn.conf.neural_net import NeuralNetConfiguration
from deeplearning4j_tpu.nn.engine import scope
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

# what the readers under benchmark/layer_metrics search an `op_name` for
NEEDLES = ("dsa.", "moe.", "lm.head", "attn.sliding", "attn.full",
           "attn.rope", "mla.attend", "mla.project", "moe.shared",
           "ffn.dense")
LM = dict(t=64, d_model=64, n_heads=4, n_experts=8, top_k=2,
          expert_hidden=32, experts_held=(0, 2),
          dtype_policy={"name": "mixed_bfloat16"})


def _lm_batch(vocab=48, t=64):
    ids = np.random.default_rng(3).integers(0, vocab, (1, t + 1)).astype(
        np.int32)
    return DataSet(ids[:, :-1], ids[:, 1:], None,
                   np.full((1, t), 1.0 / t, np.float32))


def _keye_like():
    conf = zoo.sparse_moe_lm(48, n_blocks=2, n_kv_heads=2, head_dim=16,
                             index_top_k=16, index_n_heads=2,
                             index_head_dim=8, **LM)
    return ComputationGraph(conf).init(), _lm_batch()


def _mellum_like():
    conf = zoo.sparse_moe_lm(
        48, n_blocks=2, n_kv_heads=2, head_dim=16,
        layer_types=["sliding_attention", "full_attention"],
        attention_types={
            "sliding_attention": {"sliding_window": 16, "rope_theta": 5e5},
            "full_attention": {"rope_theta": 5e5, "rope_scaling": {
                "rope_type": "yarn", "factor": 16,
                "original_max_position_embeddings": 32, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.277}}}, **LM)
    return ComputationGraph(conf).init(), _lm_batch()


def _kimi_like():
    conf = zoo.sparse_moe_lm(
        48, n_blocks=2, rope_theta=8e5, rms_eps=1e-5,
        latent_attention={"kv_lora_rank": 32, "qk_nope_head_dim": 16,
                          "qk_rope_head_dim": 8, "v_head_dim": 16},
        first_dense=1, dense_hidden=96, scoring="sigmoid",
        routed_scaling_factor=2.446, shared_hidden=64, **LM)
    return ComputationGraph(conf).init(), _lm_batch()


def _resnet_block():
    """ResNet-50's stem and two bottleneck blocks (one projects), its pool
    and its head, under the benchmark cell's policy: Nesterov, l2, bf16
    parameters' compute copy."""
    b = (NeuralNetConfiguration.builder().seed(5).learning_rate(1e-3)
         .updater(Updater.NESTEROVS).momentum(0.9).weight_init("relu")
         .l2(1e-4).graph_builder().add_inputs("input"))
    x = resnet._conv_bn(b, "stem", "input", 8, (3, 3), (1, 1))
    x = resnet._bottleneck(b, "s0_b0", x, 4, (1, 1), project=True)
    x = resnet._bottleneck(b, "s0_b1", x, 4, (1, 1), project=False)
    b.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
    b.add_layer("fc", OutputLayer(n_out=5, activation="softmax",
                                  loss_function="mcxent"), "avgpool")
    conf = (b.set_outputs("fc")
            .set_input_types(InputType.convolutional(8, 8, 3)).build())
    conf.global_conf.dtype_policy = {"name": "mixed_bfloat16"}
    rng = np.random.default_rng(1)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 4)]
    return (ComputationGraph(conf).init(),
            DataSet(rng.random((4, 8, 8, 3), dtype=np.float32), y))


def _mln(policy="mixed_float16"):
    """A chain under loss scaling (`step.grad_cast` unscales, `step.store`
    selects), or with its parameters stored in bfloat16 (both cast)."""
    conf = (NeuralNetConfiguration.builder().seed(2).learning_rate(0.1)
            .updater("adam").list()
            .layer(DenseLayer(n_in=6, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss_function="mcxent"))
            .build())
    conf.global_conf.dtype_policy = {"name": policy}
    rng = np.random.default_rng(4)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 5)]
    return (MultiLayerNetwork(conf).init(),
            DataSet(rng.random((5, 6), dtype=np.float32), y))


CASES = {"keye_like": _keye_like, "mellum_like": _mellum_like,
         "kimi_like": _kimi_like, "resnet_block": _resnet_block,
         "mln": _mln, "mln_bf16": functools.partial(_mln, "bfloat16")}


def _step_text(net, ds) -> str:
    """The optimised HLO of the net's train step, compiled here."""
    parts = net._batch(net._as_data(ds))
    return net._build_jit("train_step").lower(
        net.params_tree, net.state, net.opt_state, *parts,
        net._device_clock()).compile().as_text()


@functools.lru_cache(maxsize=None)
def _built(case):
    return CASES[case]()


@functools.lru_cache(maxsize=None)
def _texts(case):
    """(the step's text, the same step's with `jax.named_scope` a no-op).
    The compile cache's key leaves the names out, so the second compile
    would be the first one's executable read back: for these two the key
    holds them."""
    net, ds = _built(case)
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        named = _step_text(net, ds)
        with mock.patch.object(jax, "named_scope",
                               lambda name: nullcontext()):
            bare = _step_text(net, ds)
    finally:
        jax.config.update(flag, was)
    return named, bare


def _op_names(text):
    """`op_name` of every instruction that has one, as the table's reader
    takes them: an argument's own name and a reducer's count as none."""
    return [op for op, _ in scope_table.instructions(text).values() if op]


# What the engine does not name, and why each is no vertex's and no phase's.
LEFT_OUT = [re.compile(p) for p in (
    # the step's clock: the key's split, its slices, `step + 1`
    r"jit\(_threefry_split\)", r"^jit\(\w+\)/(slice|add|squeeze)$",
    # the sum over the outputs' losses, the penalty and the layers' terms,
    # over the batch size, and that sum's cotangents
    r"^jit\(\w+\)/jvp\(\)/(add|div|mul)$",
    # what autodiff issues between vertices: a value used twice
    r"^jit\(\w+\)/transpose\(jvp\(\)\)/",
    # constants XLA hoists out of every vertex that holds one and merges
    r"^jit\(\w+\)/jit\(_where\)/(broadcast_in_dim|convert_element_type)$",
    r"^jit\(\w+\)/(iota|broadcast_in_dim)$",
)]


@pytest.mark.parametrize("case", list(CASES))
def test_every_named_instruction_is_under_a_vertex_or_a_phase(case):
    names = _op_names(_texts(case)[0])
    bare = [op for op in names if "L." not in op and "step." not in op]
    stray = [op for op in bare if not any(rx.search(op) for rx in LEFT_OUT)]
    assert not stray, sorted(set(stray))
    # the key's split is ~140 small instructions on the CPU whatever the net
    few = [op for op in bare if "_threefry_split" not in op]
    assert len(names) > 50 and len(few) < 0.1 * len(names)


@pytest.mark.parametrize("case", list(CASES))
def test_each_trained_layers_update_has_its_scope(case):
    names = _op_names(_texts(case)[0])
    net, _ = _built(case)
    frozen = getattr(net, "_frozen_spec", None) or {}
    trained = [key for key, leaves in net.params_tree.items()
               if any(leaf not in frozen.get(key, ()) for leaf in leaves)]
    assert len(trained) >= 2
    for key in trained:
        assert any(f"step.update/L.{key}/" in op for op in names), key


@pytest.mark.parametrize("case,phase,found", [
    # loss scaling: the gradients' unscaling and the skip-step selects
    ("mln", "step.grad_cast/", True), ("mln", "step.store/", True),
    # parameters stored in bfloat16: the master -> stored cast (the
    # gradients' cast to float32 the compiler folds into their products)
    ("mln_bf16", "step.store/", True),
    # float32 parameters and no scaling, as all four benchmark cells: both
    # phases are empty
    ("resnet_block", "step.grad_cast/", False),
    ("keye_like", "step.store/", False)])
def test_a_phase_holds_operations_where_the_policy_has_it(case, phase, found):
    assert any(phase in op for op in _op_names(_texts(case)[0])) == found


@pytest.mark.parametrize("case", list(CASES))
def test_the_program_is_the_same_without_the_scopes(case):
    with_names, without = _texts(case)
    assert "L." in with_names and "L." not in without  # two compiles
    # what names an operation or says where it was traced from: each
    # instruction's metadata, and the header's tables of files and frames
    debug = (r",? ?metadata=\{[^}]*\}|(?ms:^(?:FileNames|FunctionNames|"
             r"FileLocations|StackFrames)\n.*?\n\n)")
    named, bare = (re.sub(debug, "", text) for text in (with_names, without))
    assert "op_name" not in named
    assert named == bare


@pytest.mark.parametrize("case,needle,found", [
    ("keye_like", "dsa.attend", True), ("keye_like", "moe.experts", True),
    ("keye_like", "lm.head", True), ("mellum_like", "attn.sliding", True),
    ("mellum_like", "attn.full", True), ("kimi_like", "mla.attend", True),
    ("kimi_like", "ffn.dense", True), ("kimi_like", "moe.shared", True),
    ("resnet_block", "moe.", False), ("mln", "lm.head", False)])
def test_the_layers_own_scopes_nest_inside_the_vertex(case, needle, found):
    hits = [op for op in _op_names(_texts(case)[0]) if needle in op]
    assert bool(hits) == found
    # forward `jvp(L.attn0)/dsa.attend/...`: the vertex first, then the body's
    assert all(re.search(r"L\.[\w\-]+\)*/(?:[\w.]+/)*" + re.escape(needle),
                         op) for op in hits), hits[:3]


def test_a_vertex_name_cannot_read_as_a_scope_of_a_layer_body():
    b = (NeuralNetConfiguration.builder().seed(1).learning_rate(0.1)
         .graph_builder().add_inputs("in"))
    b.add_layer("moe.x", DenseLayer(n_in=4, n_out=4, activation="relu"),
                "in")
    b.add_layer("dsa.attend/y", DenseLayer(n_in=4, n_out=4), "moe.x")
    b.add_vertex("attn.full", ElementWiseVertex(op="add"), "moe.x",
                 "dsa.attend/y")
    b.add_layer("lm.head", OutputLayer(n_in=4, n_out=3, activation="softmax",
                                       loss_function="mcxent"), "attn.full")
    net = ComputationGraph(b.set_outputs("lm.head").build()).init()
    rng = np.random.default_rng(0)
    ds = DataSet(rng.random((5, 4), dtype=np.float32),
                 np.eye(3, dtype=np.float32)[rng.integers(0, 3, 5)])
    names = _op_names(_step_text(net, ds))
    for needle in NEEDLES:
        assert not [op for op in names if needle in op], needle
    for vertex in ("L.moe_x", "L.dsa_attend_y", "L.attn_full", "L.lm_head",
                   "step.update/L.moe_x", "step.update/L.lm_head"):
        assert any(vertex + "/" in op or vertex + ")" in op
                   for op in names), vertex


@pytest.mark.parametrize("name,prefix,expected", [
    ("attn0", "L.", "L.attn0"), ("moe.x", "L.", "L.moe_x"),
    ("a/b c", "L.", "L.a_b_c"), ("s0_b1-add", "L.", "L.s0_b1-add"),
    ("update", "step.", "step.update"), ("lm.head", "", "lm.head"),
    (3, "L.", "L.3")])
def test_scope_names(name, prefix, expected):
    def f(x):
        with scope(name, prefix):
            return jnp.sin(x)

    # the lowered text: a compiled one may come from the compile cache,
    # whose key does not hold the names
    text = jax.jit(f).lower(np.float32(1.0)).as_text(debug_info=True)
    assert f'loc("jit(f)/{expected}/sin"' in text


def test_no_name_is_no_scope():
    assert isinstance(scope(None), nullcontext)
    assert isinstance(scope(""), nullcontext)
