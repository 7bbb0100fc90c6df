"""`zoo.zaya_moe_lm` (`zaya1_8b`: compressed convolutional attention, an MLP
router whose state passes from block to block and may skip a token, learned
residual gains, a head tied to the embedding) against the plain float32
reference (`benchmark/reference/cca_moe_lm.py`) at the rehearsal size: loss,
logits, routing, the gradient of every leaf and one Adam step; then one test
per mechanism, and the shares test: the two halves of the experts add up to
the whole layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import cells
from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.layers import (
    CompressedConvAttentionLayer, MoELayer, RouterStateLayer,
    ScaledResidualLayer, layer_from_dict)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import cca, dsa
from deeplearning4j_tpu.parallel import expert

CELL = cells.Cell("zaya1_8b.fit_seq8k", rehearsal=True)
CONFIG = cells.load_module("configs", "zaya1_8b")
REF = cells.load_module("reference", "cca_moe_lm")
N_LAYERS = int(CELL.sizes["num_hidden_layers"])
S = int(CELL.sizes["seq_len"])
V = int(CELL.sizes["held"]["ids"])
E = int(CELL.sizes["num_experts"])


def _batch(seed=7):
    ids = np.random.default_rng(seed).integers(0, V, (1, S + 1)).astype(
        np.int32)
    return DataSet(ids[:, :-1], ids[:, 1:], None,
                   np.full((1, S), 1.0 / S, np.float32))


def _leaves(net):
    frozen = net._frozen_spec
    return [(layer, name) for layer, leaves in sorted(net.params_tree.items())
            for name in sorted(leaves) if name not in frozen.get(layer, ())]


_NAMES = {(stem, leaf): ref for ref, (stem, leaf) in
          {**CONFIG._LAYER, **CONFIG._EDA}.items()}


def _ref_path(layer, name):
    """A program leaf's place in the reference's tree (layers as a list)."""
    if layer in ("emb", "ln_out"):
        return ({"emb": "embed", "ln_out": "norm"}[layer],)
    stem, i = layer.rstrip("0123456789"), int(layer[-1])
    key = _NAMES[stem, name]
    return ("first", key) if i == 0 else ("layers", i - 1, key)


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def _compare(policy):
    """Program and reference on one batch: everything the tests below read."""
    sizes = dict(CELL.sizes, dtype_policy={"name": policy})
    net = CONFIG.make_net(sizes, 11)
    batch = _batch()
    collect = ["out"] + [f"ffn{i}.expert_idx" for i in range(N_LAYERS)]
    loss_p, grads_p, values = net.loss_and_gradients(batch, collect=collect)
    routes_p = [values[f"ffn{i}.expert_idx"][0] for i in range(N_LAYERS)]
    cfg = CONFIG.model_cfg(sizes)
    rparams = CONFIG.reference_params(net.params_tree, sizes)
    ids, labels = jnp.asarray(batch.features[0]), jnp.asarray(batch.labels[0])

    @jax.jit
    def reference(rparams):
        def loss(rparams):
            logits, aux, routes = REF.forward(rparams, ids, cfg)
            picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
            ce = jnp.mean(jax.nn.logsumexp(logits, -1) - picked)
            return ce + cfg["aux_coef"] * aux, (logits, routes)
        return jax.value_and_grad(loss, has_aux=True)(rparams)

    (loss_r, (logits_r, routes_r)), grads_r = reference(rparams)
    return dict(net=net, batch=batch, cfg=cfg, loss_p=float(loss_p),
                grads_p=grads_p,
                logits_p=np.asarray(values["out"][0], np.float32),
                routes_p=routes_p, routes_r=routes_r,
                logits_r=np.asarray(logits_r), loss_r=float(loss_r),
                grads_r=grads_r)


@pytest.fixture(scope="module")
def f32():
    return _compare("float32")


LEAVES = _leaves(CONFIG.make_net(CELL.sizes, 1))


def test_the_builder_wires_cca_the_router_state_the_gains_and_a_tied_head():
    conf = CONFIG.make_conf(CELL.sizes, 1)
    ins = conf.vertex_inputs
    for i in range(N_LAYERS):
        attn = conf.vertices[f"attn{i}"].layer
        assert isinstance(attn, CompressedConvAttentionLayer)
        assert (attn.n_heads, attn.n_kv_heads, attn.head_dim,
                attn.rotary_dim(), attn.cca_time0, attn.cca_time1) == (
            4, 2, 16, 8, 2, 2)
        route = conf.vertices[f"route{i}"].layer
        assert isinstance(route, RouterStateLayer) and route.carry == (i > 0)
        assert ins[f"route{i}"] == [f"ln_f{i}"] + (
            [f"route{i - 1}"] if i else [])
        assert ins[f"ffn{i}"] == [f"ln_f{i}", f"route{i}"]
        ffn = conf.vertices[f"ffn{i}"].layer
        assert (ffn.param_shapes()["router_w3"][1], ffn.top_k,
                ffn.norm_topk_prob, ffn.experts_held) == (E + 1, 1, False,
                                                          (0, 2))
        for res, args in ((f"res_a{i}", [f"res_f{i - 1}" if i else "emb",
                                         f"attn{i}"]),
                          (f"res_f{i}", [f"res_a{i}", f"ffn{i}"])):
            assert isinstance(conf.vertices[res].layer, ScaledResidualLayer)
            assert ins[res] == args
    out = conf.vertices["out"].layer
    assert out.tied_to == "emb" and out.param_shapes() == {}
    # the configuration survives JSON, multi-input layers and the tie too
    for name in ("attn1", "route1", "ffn1", "res_a1", "out"):
        layer = conf.vertices[name].layer
        assert layer_from_dict(layer.to_dict()) == layer


def test_one_leaf_is_the_table_and_the_head_holds_none():
    net = CONFIG.make_net(CELL.sizes, 1)
    assert net.params_tree["out"] == {} and net.opt_state["out"]["m"] == {}
    assert net.params_tree["emb"]["W"].shape == (V, 64)
    assert "eda_theta" not in net.params_tree["route0"]
    assert set(net.params_tree["route1"]) == {"W_d", "eda_theta"}
    assert "gate_b" in net._frozen_spec["ffn0"]


def test_f32_loss_logits_and_routing_match_the_reference(f32):
    assert abs(f32["loss_p"] - f32["loss_r"]) <= 1e-5 * abs(f32["loss_r"])
    assert _rel(f32["logits_p"], f32["logits_r"]) <= 1e-5
    for rp, rr in zip(f32["routes_p"], f32["routes_r"]):
        assert np.array_equal(np.asarray(rp), np.asarray(rr))
    # every choice is taken somewhere, the skip among them
    chosen = np.concatenate([np.asarray(r).ravel() for r in f32["routes_p"]])
    assert set(chosen) == set(range(E + 1))


@pytest.mark.parametrize("layer,name", LEAVES,
                         ids=[f"{l}.{n}" for l, n in LEAVES])
def test_f32_gradient_matches_the_reference(f32, layer, name):
    want = _at(f32["grads_r"], _ref_path(layer, name))
    assert _rel(f32["grads_p"][layer][name], want) <= 2e-5


def test_f32_train_step_is_the_references_adam_step(f32):
    """One `fit` from a fresh state against `adam_update` of every leaf by the
    reference's own gradient (zero moments, step 1); the selection bias keeps
    its bits."""
    net, sizes = f32["net"], CELL.sizes
    before = jax.tree_util.tree_map(np.asarray, net.params_tree)
    net.fit(f32["batch"])
    for i in range(N_LAYERS):
        assert np.array_equal(np.asarray(net.params_tree[f"ffn{i}"]["gate_b"]),
                              before[f"ffn{i}"]["gate_b"])
    for layer, name in LEAVES:
        grad = _at(f32["grads_r"], _ref_path(layer, name))
        want = REF.adam_update(
            grad, jnp.zeros_like(grad), jnp.zeros_like(grad), 1,
            float(sizes["learning_rate"]), float(sizes["adam_mean_decay"]),
            float(sizes["adam_var_decay"]))
        got = np.asarray(net.params_tree[layer][name]) - before[layer][name]
        # entries whose gradient is near zero follow its own rounding
        firm = np.abs(np.asarray(grad)) > 1e-3 * np.abs(np.asarray(grad)).max()
        np.testing.assert_allclose(got[firm], np.asarray(want)[firm],
                                   rtol=2e-3, atol=1.3e-7)


# ------------------------------------------------------- the attention layer
def _cca_layer(rotary=0.5):
    conf = CompressedConvAttentionLayer(
        n_in=32, n_out=32, n_heads=4, n_kv_heads=2, head_dim=8,
        rope_theta=5e6, partial_rotary_factor=rotary)
    from deeplearning4j_tpu.nn import params as params_mod
    return conf, params_mod.init_layer_params(conf, jax.random.PRNGKey(3))


@pytest.mark.parametrize("at", [0, 5, 17])
def test_both_convolutions_are_causal_and_reach_two_positions_back(at):
    """The output at t does not move when u moves after t; it moves with
    u_{t-2} (one tap of each convolution) and not with u_{t-3}."""
    _, p = _cca_layer()
    u = jax.random.normal(jax.random.PRNGKey(0), (24, 6, 8))
    conv = jax.jit(lambda u: cca.causal_convs(u, p["W_conv0"], p["b_conv0"],
                                              p["W_conv1"], p["b_conv1"]))
    base = conv(u)
    later = conv(u.at[at + 1:].add(1.0))
    np.testing.assert_array_equal(base[:at + 1], later[:at + 1])
    if at >= 3:
        assert not np.allclose(conv(u.at[at - 2].add(1.0))[at], base[at])
        np.testing.assert_allclose(conv(u.at[at - 3].add(1.0))[at], base[at],
                                   rtol=0, atol=1e-6)


@pytest.mark.parametrize("at", [0, 9])
def test_the_whole_layer_is_causal(at):
    conf, p = _cca_layer()
    h = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    layer = jax.jit(lambda h: cca.one_sequence(conf, p, h))
    base, moved = layer(h), layer(h.at[at + 1:].add(1.0))
    np.testing.assert_allclose(moved[:at + 1], base[:at + 1], atol=1e-5)


def test_half_of_each_value_comes_from_the_previous_position():
    """With W_v1 at zero the values are h_{t-1} W_v2 alone: the first
    position reads only v_0 = 0, and a change to h_t reaches v at t + 1."""
    conf, p = _cca_layer()
    p = dict(p, Wv1=jnp.zeros_like(p["Wv1"]))
    h = jax.random.normal(jax.random.PRNGKey(2), (16, 32))
    out = cca.one_sequence(conf, p, h)
    np.testing.assert_array_equal(out[0], np.zeros(32, np.float32))
    assert np.abs(np.asarray(out[1])).max() > 1e-3
    v = cca.value_shift(h @ p["Wv2"])
    np.testing.assert_array_equal(v[0], 0.0)
    np.testing.assert_array_equal(v[1:], (h @ p["Wv2"])[:-1])


@pytest.mark.parametrize("rotary_dim", [4, 8])
def test_rotary_turns_the_first_channels_of_a_head_and_leaves_the_rest(
        rotary_dim):
    x = jax.random.normal(jax.random.PRNGKey(4), (32, 3, 8))
    got = cca.partial_rope(x, 5e6, rotary_dim)
    np.testing.assert_array_equal(got[..., rotary_dim:], x[..., rotary_dim:])
    np.testing.assert_allclose(got[..., :rotary_dim],
                               dsa.rope_xla(x[..., :rotary_dim], 5e6),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[0], x[0], atol=1e-6)       # position 0
    ref = REF.rotate(x, 5e6, rotary_dim)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- the router
@pytest.mark.parametrize("theta,reaches", [(0.0, True), (-1e4, False)])
def test_eda_carries_the_gradient_to_the_previous_blocks_router(theta,
                                                                 reaches):
    """Block 1's router state depends on block 0's projection through the
    depth average, lambda = sigmoid(theta), and on nothing of it at
    lambda = 0; the whole network's gradient of block 0's projection is the
    reference's (`test_f32_gradient_matches_the_reference[route0.W_d]`)."""
    from deeplearning4j_tpu.nn.layers import moe

    rng = np.random.default_rng(0)
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    h0, h1, w0, w1 = mk(8, 16), mk(8, 16), mk(16, 4), mk(16, 4)
    first = RouterStateLayer(n_in=16, n_out=4)
    later = RouterStateLayer(n_in=16, n_out=4, carry=True)

    def state1(w0):
        r0 = moe.router_state_apply(first, {"W_d": w0}, {}, h0)[0]
        return jnp.sum(moe.router_state_apply(
            later, {"W_d": w1, "eda_theta": jnp.full((4,), theta)}, {},
            (h1, r0))[0] ** 2)

    grad = np.asarray(jax.grad(state1)(w0))
    assert (np.abs(grad).max() > 1e-3) == reaches
    if not reaches:
        np.testing.assert_array_equal(grad, 0.0)


def _mlp_router(rng, N=64, R=16, C=E + 1):
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                                    jnp.float32)
    return ({"router_w1": mk(R, R), "router_w2": mk(R, R),
             "router_w3": mk(R, C), "gate_b": jnp.zeros((C,), jnp.float32)},
            jnp.asarray(rng.normal(size=(N, R)), jnp.float32))


@pytest.mark.parametrize("norm", [False, True])
def test_the_top1_router_trains_unless_its_gate_is_renormalised(rng, norm):
    params, r = _mlp_router(rng)
    g = jax.grad(lambda w3: jnp.sum(expert.route_mlp(
        dict(params, router_w3=w3), r, 1, norm)[1]))(params["router_w3"])
    assert (np.abs(np.asarray(g)).max() > 1e-3) == (not norm)


def _experts(rng, params, N, D=16, F=8, held=4):
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[1]),
                                    jnp.float32)
    return dict(params, w_gate=mk(held, D, F), w_up=mk(held, D, F),
                w_down=mk(held, F, D)), jnp.asarray(rng.normal(size=(N, D)),
                                                    jnp.float32)


def test_a_skipped_token_adds_nothing_and_takes_no_grouped_row(rng):
    params, r = _mlp_router(rng)
    params, x = _experts(rng, params, r.shape[0])
    kw = dict(top_k=1, norm_topk_prob=False, router_state=r)
    # a bias that sends about half the tokens to the skip
    z = expert.route_mlp(dict(params), r, 1)[0]
    logits = jnp.log(z)
    margin = jnp.max(logits[:, :E], axis=1) - logits[:, E]
    params["gate_b"] = params["gate_b"].at[E].set(float(jnp.median(margin)))
    y, _, (held, _), idx = expert.moe_ffn_dropless(params, x, **kw)
    skipped = np.asarray(idx[:, 0]) == E
    assert 0 < skipped.sum() < len(skipped)
    np.testing.assert_array_equal(np.asarray(y)[skipped], 0.0)
    in_table = np.asarray(idx[:, 0]) < 4
    assert float(held) == in_table.mean()                 # rows taken
    assert np.all(np.abs(np.asarray(y)[in_table]).max(axis=1) > 0)
    params["gate_b"] = params["gate_b"].at[E].set(1e4)    # every token skips
    y, aux, (held, _), idx = expert.moe_ffn_dropless(params, x, **kw)
    assert float(held) == 0.0 and np.all(np.asarray(idx) == E)
    np.testing.assert_array_equal(np.asarray(y), 0.0)
    assert float(aux) == 0.0         # the balance term counts experts only


def test_the_two_shares_of_the_experts_add_up_to_the_whole_layer(rng):
    """Guide section 4's test: the MoE sublayer of the share holding experts
    0..7 and that of the share holding 8..15, the router's state, its MLP
    and the skip computed alike on both and counted once, sum to the uncut
    reference's sublayer over all 16."""
    params, r = _mlp_router(rng, N=96)
    whole, x = _experts(rng, params, 96, held=E)
    cfg = {"n_experts": E, "top_k": 1}
    ref = {"router_down": jnp.eye(16), "router_w1": whole["router_w1"],
           "router_w2": whole["router_w2"], "router_w3": whole["router_w3"],
           "router_bias": whole["gate_b"], "w_gate": whole["w_gate"],
           "w_up": whole["w_up"], "w_down": whole["w_down"]}
    # the reference's router state is h2 W_d: here r itself (W_d = I, h2 = r
    # fed beside x), so both sides route the same tokens alike
    want = _uncut(ref, x, r, cfg)
    parts = []
    for first in (0, E // 2):
        share = dict(whole, **{k: whole[k][first:first + E // 2]
                               for k in ("w_gate", "w_up", "w_down")})
        y, _, _, _ = expert.moe_ffn_dropless(
            share, x, top_k=1, first=first, norm_topk_prob=False,
            router_state=r)
        parts.append(np.asarray(y))
    np.testing.assert_allclose(parts[0] + parts[1], want, rtol=2e-5,
                               atol=2e-6)
    assert np.abs(parts[0]).max() > 0 and np.abs(parts[1]).max() > 0


def _uncut(p, x, r, cfg):
    """The reference's routed sum over all experts, its router fed `r`."""
    _, probs, idx = REF.route(p, r, None, cfg)
    gate = jnp.zeros_like(probs).at[jnp.arange(r.shape[0])[:, None], idx].set(
        jnp.take_along_axis(probs, idx, axis=-1))
    return sum(gate[:, e:e + 1] * REF.gated_mlp(
        x, p["w_gate"][e], p["w_up"][e], p["w_down"][e]) for e in range(E))


# ------------------------------------------------------------ the tied head
def test_the_tied_gradient_is_the_sum_of_its_two_uses(f32):
    """The same network untied, its head a copy of the table: the gradients
    of the lookup's table and of the head's (transposed) add up to the tied
    table's gradient."""
    sizes = dict(CELL.sizes, dtype_policy={"name": "float32"})
    tied = CONFIG.make_net(sizes, 11)       # the fixture's net before its step
    conf = CONFIG.make_conf(sizes, 11)
    conf.vertices["out"].layer.tied_to = None
    untied = ComputationGraph(conf).init(dict(
        tied.params_tree, out={"W": tied.params_tree["emb"]["W"].T}))
    _, g, _ = untied.loss_and_gradients(f32["batch"], wrt={"emb": ["W"],
                                                           "out": ["W"]})
    lookup, head = np.asarray(g["emb"]["W"]), np.asarray(g["out"]["W"]).T
    assert np.abs(lookup).max() > 0 and np.abs(head).max() > 0
    np.testing.assert_allclose(lookup + head, np.asarray(
        f32["grads_p"]["emb"]["W"]), rtol=1e-5, atol=1e-8)


def test_the_skip_share_is_published_beside_the_held_share(f32):
    net = f32["net"]
    net.fit(f32["batch"])
    net.score_value
    family = obs.metrics.get_family("dl4j_moe_skip_share")
    layers = {c.labels["layer"]: c.get() for c in family.children()}
    assert {f"ffn{i}" for i in range(N_LAYERS)} <= set(layers)
    for i in range(N_LAYERS):
        assert layers[f"ffn{i}"] == pytest.approx(
            float(net.state[f"ffn{i}"]["skip_share"]))
    with pytest.raises(ValueError, match="MLP router"):
        MoELayer(n_out=8, n_experts=4, router_hidden=4)
