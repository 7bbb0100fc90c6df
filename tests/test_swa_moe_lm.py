"""`zoo.sparse_moe_lm` with a pattern of sliding-window and full layers
(`mellum2_12b_a2_5b`) against the plain float32 reference
(`benchmark/reference/swa_moe_lm.py`) at the rehearsal size: loss, logits,
routing, the gradient of every leaf and one Adam step; YaRN's frequencies
against the closed form; the window's edges through the layer; the eight
shares of one expert layer; the new fields' round trip and the gauge."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import cells, fit_check
from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.gradientcheck import check_gradients
from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.nn.conf.layers import (
    MoELayer, SelfAttentionLayer, layer_from_dict)
from deeplearning4j_tpu.nn.conf.neural_net import (
    ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import dsa
from deeplearning4j_tpu.nn.layers import moe as moe_layer

CELL = cells.Cell("mellum2_12b_a2_5b.fit_seq16k", rehearsal=True)
CONFIG = cells.load_module("configs", "mellum2_12b_a2_5b")
REF = cells.load_module("reference", "swa_moe_lm")
PUBLISHED = cells.load_json("configs", "mellum2_12b_a2_5b")
N_LAYERS = int(CELL.sizes["num_hidden_layers"])
S = int(CELL.sizes["seq_len"])
V = int(CELL.sizes["held"]["ids"])
KINDS = ["sliding_attention"] * 3 + ["full_attention"]


def _batch(seed=7, dtype=np.float32):
    ids = np.random.default_rng(seed).integers(0, V, (1, S + 1)).astype(
        np.int32)
    return DataSet(ids[:, :-1], ids[:, 1:], None,
                   np.full((1, S), 1.0 / S, dtype))


def _leaves(net):
    return [(layer, name) for layer, leaves in sorted(net.params_tree.items())
            for name in sorted(leaves)]


def _ref_path(layer, name):
    """A program leaf's place in the reference's tree."""
    if layer in ("emb", "out", "ln_out"):
        return ({"emb": "embed", "out": "head", "ln_out": "norm"}[layer],)
    i = int(layer[-1])
    key = {"ln_a": {"gamma": "ln1"}, "ln_f": {"gamma": "ln2"},
           "attn": {"Wq": "wq", "Wk": "wk", "Wv": "wv", "Wo": "wo",
                    "gamma_q": "q_norm", "gamma_k": "k_norm"},
           "ffn": {"gate_w": "router", "w_gate": "w_gate", "w_up": "w_up",
                   "w_down": "w_down"}}[layer[:-1]][name]
    return ("layers", i, key)


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _compare(policy):
    """Program and reference on one batch: everything the tests below read."""
    sizes = dict(CELL.sizes, dtype_policy={"name": policy})
    net = ComputationGraph(CONFIG.make_conf(sizes, 11)).init()
    f64 = policy == "float64"
    batch = _batch(dtype=np.float64 if f64 else np.float32)
    collect = ["out"] + [f"ffn{i}.expert_idx" for i in range(N_LAYERS)]
    loss_p, grads_p, values = net.loss_and_gradients(batch, collect=collect)
    routes_p = [values[f"ffn{i}.expert_idx"][0] for i in range(N_LAYERS)]
    cfg = CONFIG.model_cfg(sizes)
    rparams = fit_check.reference_params(net.params_tree, N_LAYERS)
    ids, labels = jnp.asarray(batch.features[0]), jnp.asarray(batch.labels[0])
    logits_r, _, routes_r = REF.forward(rparams, ids, cfg)
    loss_r, grads_r = REF.loss_and_grads(rparams, ids, labels, cfg)
    loss_g, grads_g = REF.loss_and_grads(rparams, ids, labels, cfg,
                                         routes=routes_p)
    return dict(net=net, batch=batch, cfg=cfg, loss_p=float(loss_p),
                grads_p=grads_p,
                logits_p=np.asarray(values["out"][0], np.float32),
                routes_p=routes_p, routes_r=routes_r,
                logits_r=np.asarray(logits_r), loss_r=float(loss_r),
                grads_r=grads_r, loss_g=float(loss_g), grads_g=grads_g)


@pytest.fixture(scope="module")
def f32():
    return _compare("float32")


@pytest.fixture(scope="module")
def bf16():
    return _compare("mixed_bfloat16")


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


LEAVES = _leaves(ComputationGraph(CONFIG.make_conf(CELL.sizes, 1)).init())


def test_the_pattern_of_layer_types_is_built():
    conf = CONFIG.make_conf(CELL.sizes, 1)
    assert CONFIG.layer_types(CELL.sizes) == KINDS
    assert len(LEAVES) == 3 + N_LAYERS * 12
    for i, kind in enumerate(KINDS):
        attn = conf.vertices[f"attn{i}"].layer
        assert attn.index_top_k is None and attn.rope_theta == 5e5
        if kind == "sliding_attention":
            assert attn.sliding_window == 16 and attn.rope_scaling is None
            assert attn.attention_scope() == "attn.sliding"
        else:
            assert attn.sliding_window is None
            assert attn.rope_scaling["rope_type"] == "yarn"
            assert attn.attention_scope() == "attn.full"
    # a period is repeated, a list as long as n_blocks is taken as it is,
    # and without either every block is what it was
    kw = dict(t=8, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
              n_experts=2, top_k=1, expert_hidden=4)
    types = {"a": {"sliding_window": 3}, "b": {"rope_theta": 10.0}}
    twice = zoo.sparse_moe_lm(8, n_blocks=4, layer_types=["a", "b"],
                              attention_types=types, **kw)
    whole = zoo.sparse_moe_lm(8, n_blocks=4, layer_types=["a", "b", "a", "b"],
                              attention_types=types, **kw)
    assert twice.to_json() == whole.to_json()
    assert [twice.vertices[f"attn{i}"].layer.sliding_window
            for i in range(4)] == [3, None, 3, None]
    assert twice.vertices["attn1"].layer.rope_theta == 10.0
    plain = zoo.sparse_moe_lm(8, n_blocks=2, **kw)
    assert plain.vertices["attn1"].layer.sliding_window is None
    assert "sliding_window" not in plain.to_json()
    with pytest.raises(ValueError, match="periods"):
        zoo.sparse_moe_lm(8, n_blocks=3, layer_types=["a", "b"],
                          attention_types=types, **kw)


def test_f32_loss_logits_and_routing_match_the_reference(f32):
    assert abs(f32["loss_p"] - f32["loss_r"]) <= 1e-5 * abs(f32["loss_r"])
    assert _rel(f32["logits_p"], f32["logits_r"]) <= 1e-5
    for rp, rr in zip(f32["routes_p"], f32["routes_r"]):
        assert np.array_equal(np.sort(np.asarray(rp), 1),
                              np.sort(np.asarray(rr), 1))


@pytest.mark.parametrize("layer,name", LEAVES,
                         ids=[f"{l}.{n}" for l, n in LEAVES])
def test_f32_gradient_matches_the_reference(f32, layer, name):
    want = _at(f32["grads_r"], _ref_path(layer, name))
    assert _rel(f32["grads_p"][layer][name], want) <= 1e-5


def test_f32_train_step_is_the_references_adam_step(f32):
    """One `fit` from a fresh state against `adam_update` of every leaf by
    the reference's own gradient (zero moments, step 1)."""
    net, sizes = f32["net"], CELL.sizes
    before = jax.tree_util.tree_map(np.asarray, net.params_tree)
    net.fit(f32["batch"])
    for layer, name in LEAVES:
        grad = _at(f32["grads_r"], _ref_path(layer, name))
        want = REF.adam_update(
            grad, jnp.zeros_like(grad), jnp.zeros_like(grad), 1,
            float(sizes["learning_rate"]), float(sizes["adam_mean_decay"]),
            float(sizes["adam_var_decay"]))
        got = np.asarray(net.params_tree[layer][name]) - before[layer][name]
        # at step 1 Adam's change is lr * g / (|g| + 1e-8), lr 1e-5: an entry
        # whose gradient is near zero is not yet lr * sign(g) and follows the
        # gradient's own rounding, and the difference of two float32
        # parameters near 1 is only known to their last bit, 1.2e-7
        firm = np.abs(np.asarray(grad)) > 1e-3 * np.abs(np.asarray(grad)).max()
        np.testing.assert_allclose(got[firm], np.asarray(want)[firm],
                                   rtol=2e-3, atol=1.3e-7)


def test_bf16_within_the_stated_band(bf16):
    """`mixed_bfloat16`: bf16 products against float32 `highest`. Loss within
    1e-2 of the reference's own; given the program's routing, loss within
    5e-4 and every gradient within 8e-2 (the rehearsal's matrices are 64
    wide: the chip's limits at 2304 are in
    `benchmark/configs/mellum2_12b_a2_5b.py`)."""
    assert abs(bf16["loss_p"] - bf16["loss_r"]) <= 1e-2 * bf16["loss_r"]
    assert abs(bf16["loss_p"] - bf16["loss_g"]) <= 5e-4 * bf16["loss_g"]
    for layer, name in LEAVES:
        want = _at(bf16["grads_g"], _ref_path(layer, name))
        assert _rel(bf16["grads_p"][layer][name], want) <= 8e-2, (layer, name)


def test_float64_program_meets_the_reference_closer_than_float32_can():
    """Where the program allows float64 it agrees with the float32 reference
    to that reference's own rounding."""
    got = _compare("float64")
    assert abs(got["loss_p"] - got["loss_r"]) <= 2e-6 * abs(got["loss_r"])
    assert _rel(got["logits_p"], got["logits_r"]) <= 5e-6


def test_gradient_check_of_the_windowed_and_scaled_layers():
    sizes = dict(CELL.sizes, dtype_policy={"name": "float64"}, seq_len=16,
                 sliding_window=5)
    net = ComputationGraph(CONFIG.make_conf(sizes, 9, t=16)).init()
    ids = np.random.default_rng(1).integers(0, V, (2, 17)).astype(np.int32)
    ds = DataSet(ids[:, :-1], ids[:, 1:], None,
                 np.full((2, 16), 1.0 / 16, np.float64))
    assert check_gradients(net, ds, epsilon=1e-6, max_rel_error=1e-4,
                           subset=150, seed=3)


def test_yarn_frequencies_against_the_closed_form():
    """Dh 128, theta 5e5, factor 16 over 8,192 positions, beta 32 / 1: low
    18, high 35; the 18 fastest frequencies are RoPE's own, those from the
    35th on are RoPE's / 16, and between them the blend is linear."""
    rope = PUBLISHED["rope_parameters"]["full_attention"]
    Dh, theta, f = 128, 5e5, 16.0

    def c(r):
        return Dh * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(theta))

    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    base = theta ** (-np.arange(64) * 2.0 / Dh)
    ramp = np.clip((np.arange(64) - 18) / (35 - 18), 0, 1)
    want = base * (1 - ramp) + base / f * ramp
    scaling = {k: v for k, v in rope.items() if k != "rope_theta"}
    inv, mscale = dsa.rope_frequencies(Dh, theta, scaling, jnp.float64)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=1e-12)
    np.testing.assert_allclose(np.asarray(inv[:19]), base[:19], rtol=1e-12)
    np.testing.assert_allclose(np.asarray(inv[35:]), base[35:] / f,
                               rtol=1e-12)
    assert mscale == rope["attention_factor"] == pytest.approx(
        0.1 * math.log(f) + 1)
    # the reference's own table, written independently
    inv_r, m_r = REF.rope_table(Dh, dict(scaling, theta=theta))
    np.testing.assert_allclose(np.asarray(inv_r), want, rtol=2e-6)
    assert m_r == mscale
    # without an attention_factor, 0.1 ln f + 1; plain RoPE without scaling
    assert dsa.rope_frequencies(Dh, theta, {
        k: v for k, v in scaling.items() if k != "attention_factor"},
        jnp.float32)[1] == pytest.approx(mscale)
    plain, one = dsa.rope_frequencies(Dh, theta, None, jnp.float64)
    np.testing.assert_allclose(np.asarray(plain), base, rtol=1e-12)
    assert one == 1.0
    with pytest.raises(ValueError, match="YaRN"):
        dsa.rope_frequencies(Dh, theta, {"rope_type": "llama3"}, jnp.float32)


def test_yarn_turns_and_scales_what_it_rotates(rng):
    x = jnp.asarray(rng.randn(32, 2, 16), jnp.float64)
    scaling = {"rope_type": "yarn", "factor": 16.0,
               "original_max_position_embeddings": 32, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.25}
    got = np.asarray(dsa.rope(x, 5e5, scaling))
    inv, m = dsa.rope_frequencies(16, 5e5, scaling, jnp.float64)
    ang = np.arange(32)[:, None] * np.asarray(inv)[None, :]
    x1, x2 = np.asarray(x[..., :8]), np.asarray(x[..., 8:])
    cos, sin = np.cos(ang)[:, None] * m, np.sin(ang)[:, None] * m
    np.testing.assert_allclose(
        got, np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1),
        rtol=1e-12, atol=1e-12)
    # every pair's length grows by the factor
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               1.25 * np.linalg.norm(np.asarray(x), axis=-1))


def test_the_layer_reads_its_window_and_no_further(rng):
    """Through `SelfAttentionLayer(sliding_window=1024)` at 2,048 positions:
    position t's output moves with input t - 1,023 and not with t - 1,024."""
    W, T, t = 1024, 2048, 1500
    conf = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2, n_kv_heads=1,
                              head_dim=4, rope_theta=5e5, qk_norm_eps=1e-6,
                              sliding_window=W)
    params = {n: jnp.asarray(0.5 * rng.randn(*shape), jnp.float32)
              for n, shape in conf.param_shapes().items()}
    x = jnp.asarray(rng.randn(1, T, 8), jnp.float32)
    run = jax.jit(lambda x: dsa.extended_attention_apply(conf, params, {},
                                                         x))
    base, state, _ = run(x)
    assert set(state) == {"band_fill_share"}          # no [S, S] by-product
    assert state["band_fill_share"].shape == ()
    read = np.asarray(run(x.at[0, t - W + 1].add(10.0))[0])
    unread = np.asarray(run(x.at[0, t - W].add(10.0))[0])
    assert not np.allclose(read[0, t], np.asarray(base)[0, t])
    np.testing.assert_array_equal(unread[0, t], np.asarray(base)[0, t])
    assert not np.allclose(unread[0, t - 1], np.asarray(base)[0, t - 1])
    with pytest.raises(ValueError, match="not both"):
        dsa.extended_attention_apply(
            SelfAttentionLayer(n_in=8, n_out=8, n_heads=2, sliding_window=4,
                               index_top_k=2, index_n_heads=1,
                               index_head_dim=4), params, {}, x)


def _moe_tables(rng, E, D, F):
    return {"gate_w": rng.normal(size=(D, E)).astype(np.float32),
            "w_gate": (rng.normal(size=(E, D, F)) * 0.2).astype(np.float32),
            "w_up": (rng.normal(size=(E, D, F)) * 0.2).astype(np.float32),
            "w_down": (rng.normal(size=(E, F, D)) * 0.2).astype(np.float32)}


def test_eight_shares_of_one_expert_layer_sum_to_the_uncut_reference():
    """64 experts top-8, 8 held a share (the published counts at a small
    width): the eight chips' outputs, added, are the uncut layer's."""
    rng = np.random.default_rng(2)
    E, D, F, N, K = 64, 32, 24, 48, 8
    tables = _moe_tables(rng, E, D, F)
    x = rng.normal(size=(1, N, D)).astype(np.float32)
    total = np.zeros((1, N, D), np.float32)
    shares = []
    for j in range(8):
        conf = MoELayer(n_in=D, n_out=D, n_experts=E, expert_hidden=F,
                        top_k=K, dropless=True, norm_topk_prob=True,
                        experts_held=(8 * j, 8))
        params = {k: (v if k == "gate_w" else v[8 * j:8 * j + 8])
                  for k, v in tables.items()}
        assert {k: v.shape for k, v in params.items()} == conf.param_shapes()
        out, state, _ = moe_layer.moe_apply(conf, params, {}, jnp.asarray(x))
        total += np.asarray(out)
        shares.append(float(state["pairs_held_share"]))
    assert sum(shares) == pytest.approx(1.0)
    ref_p = {"router": tables["gate_w"], "w_gate": tables["w_gate"],
             "w_up": tables["w_up"], "w_down": tables["w_down"]}
    want, _, _ = REF.experts(ref_p, jnp.asarray(x[0]),
                             {"n_experts": E, "top_k": K, "first_expert": 0})
    assert _rel(total[0], want) <= 1e-5


def test_new_fields_round_trip_and_stay_out_of_a_plain_layers_json():
    conf = CONFIG.make_conf(CELL.sizes, 1)
    text = conf.to_json()
    again = ComputationGraphConfiguration.from_json(text)
    assert again.to_json() == text
    assert ComputationGraphConfiguration.from_yaml(
        conf.to_yaml()).to_json() == text
    assert again.vertices["attn0"].layer.sliding_window == 16
    assert again.vertices["attn3"].layer.rope_scaling == \
        conf.vertices["attn3"].layer.rope_scaling
    plain = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2).to_dict()
    assert not {"sliding_window", "rope_scaling"} & set(plain)
    assert layer_from_dict(plain).state_shapes() == {}
    windowed = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2,
                                  sliding_window=4)
    assert windowed.is_extended()
    assert windowed.state_shapes() == {"band_fill_share": ()}


def test_band_fill_gauge_is_published_where_the_score_is_read():
    net = ComputationGraph(CONFIG.make_conf(CELL.sizes, 5)).init()
    net.fit(_batch())
    net.score_value
    fill = {c.labels["layer"]: c.get() for c in obs.metrics.get_family(
        "dl4j_attn_band_fill_share").children()}
    assert fill == {f"attn{i}": 0.0 for i in range(len(KINDS))}   # XLA body
