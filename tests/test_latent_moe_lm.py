"""`zoo.sparse_moe_lm` with latent attention, a sigmoid router with a
selection bias, a shared expert and a leading dense layer (`kimi_vl_a3b`)
against the plain float32 reference (`benchmark/reference/mla_moe_lm.py`) at
the rehearsal size: loss, logits, routing, the gradient of every leaf and one
Adam step; the `latent_attention` kernel's Pallas body, interpreted, against
its XLA body; the router's bias, weights and balance term; the frozen bias;
the eight shares of one expert layer; ids through the staged path; and that
the layers' defaults are still the softmax, no-shared-expert, one-width
path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import cells
from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import DeviceCacheDataSetIterator
from deeplearning4j_tpu.gradientcheck import check_gradients
from deeplearning4j_tpu.kernels import flash_attention as fa
from deeplearning4j_tpu.kernels import registry
from deeplearning4j_tpu.nn.conf.layers import (
    GatedDenseLayer, MoELayer, SelfAttentionLayer, layer_from_dict)
from deeplearning4j_tpu.nn.conf.neural_net import (
    ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import dsa
from deeplearning4j_tpu.nn.layers import moe as moe_layer
from deeplearning4j_tpu.parallel import expert

CELL = cells.Cell("kimi_vl_a3b.fit_seq8k", rehearsal=True)
CONFIG = cells.load_module("configs", "kimi_vl_a3b")
REF = cells.load_module("reference", "mla_moe_lm")
N_LAYERS = int(CELL.sizes["num_hidden_layers"])
EXPERT_LAYERS = list(range(CONFIG.n_dense(CELL.sizes), N_LAYERS))
S = int(CELL.sizes["seq_len"])
V = int(CELL.sizes["held"]["ids"])
MLA = dict(kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
           v_head_dim=8, rope_theta=8e5)


def _batch(seed=7, dtype=np.float32):
    ids = np.random.default_rng(seed).integers(0, V, (1, S + 1)).astype(
        np.int32)
    return DataSet(ids[:, :-1], ids[:, 1:], None,
                   np.full((1, S), 1.0 / S, dtype))


def _leaves(net):
    frozen = net._frozen_spec
    return [(layer, name) for layer, leaves in sorted(net.params_tree.items())
            for name in sorted(leaves) if name not in frozen.get(layer, ())]


_NAMES = {"ln_a": {"gamma": "ln1"}, "ln_f": {"gamma": "ln2"},
          "attn": {"Wq": "wq", "Wdkv": "wdkv", "gamma_kv": "kv_norm",
                   "Wukv": "wukv", "Wo": "wo"},
          "ffn": {"W_gate": "w_gate", "W_up": "w_up", "W_down": "w_down",
                  "gate_w": "router", "w_gate": "w_gate", "w_up": "w_up",
                  "w_down": "w_down", "shared_gate": "ws_gate",
                  "shared_up": "ws_up", "shared_down": "ws_down"}}


def _ref_path(layer, name):
    """A program leaf's place in the reference's tree."""
    if layer in ("emb", "out", "ln_out"):
        return ({"emb": "embed", "out": "head", "ln_out": "norm"}[layer],)
    i, key = int(layer[-1]), _NAMES[layer[:-1]][name]
    return ("dense", key) if i < EXPERT_LAYERS[0] else (
        "layers", i - EXPERT_LAYERS[0], key)


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _compare(policy):
    """Program and reference on one batch: everything the tests below read."""
    sizes = dict(CELL.sizes, dtype_policy={"name": policy})
    net = CONFIG.make_net(sizes, 11)
    f64 = policy == "float64"
    batch = _batch(dtype=np.float64 if f64 else np.float32)
    collect = ["out"] + [f"ffn{i}.expert_idx" for i in EXPERT_LAYERS]
    loss_p, grads_p, values = net.loss_and_gradients(batch, collect=collect)
    routes_p = [values[f"ffn{i}.expert_idx"][0] for i in EXPERT_LAYERS]
    cfg = CONFIG.model_cfg(sizes)
    rparams = CONFIG.reference_params(net.params_tree, sizes)
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


LEAVES = _leaves(CONFIG.make_net(CELL.sizes, 1))


def test_the_builder_makes_a_leading_dense_layer_and_latent_attention():
    conf = CONFIG.make_conf(CELL.sizes, 1)
    assert EXPERT_LAYERS == [1, 2] and len(LEAVES) == 3 + 10 + 2 * 14
    for i in range(N_LAYERS):
        attn = conf.vertices[f"attn{i}"].layer
        assert (attn.kv_lora_rank, attn.qk_nope_head_dim,
                attn.qk_rope_head_dim, attn.v_head_dim) == (32, 16, 8, 16)
        assert attn.rope_theta == 8e5 and attn.qk_norm_eps is None
        assert attn.attention_scope() == "mla.attend"
    dense = conf.vertices["ffn0"].layer
    assert isinstance(dense, GatedDenseLayer) and dense.scope == "ffn.dense"
    assert dense.hidden == int(CELL.sizes["intermediate_size"])
    for i in EXPERT_LAYERS:
        ffn = conf.vertices[f"ffn{i}"].layer
        assert (ffn.scoring, ffn.routed_scaling_factor, ffn.shared_hidden,
                ffn.top_k) == ("sigmoid", 2.446, 64, 2)


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


def test_f32_train_step_is_the_references_adam_step_and_the_bias_stays(f32):
    """One `fit` from a fresh state against `adam_update` of every leaf by
    the reference's own gradient (zero moments, step 1); the selection bias
    is no leaf of the step: it has no gradient, no Adam state and keeps its
    bits."""
    net, sizes = f32["net"], CELL.sizes
    before = jax.tree_util.tree_map(np.asarray, net.params_tree)
    for i in EXPERT_LAYERS:
        assert "gate_b" in net._frozen_spec[f"ffn{i}"]
        assert "gate_b" not in f32["grads_p"][f"ffn{i}"]
        assert "gate_b" not in net.opt_state[f"ffn{i}"]["m"]
        assert net.params_tree[f"ffn{i}"]["gate_b"].dtype == jnp.float32
        assert np.any(before[f"ffn{i}"]["gate_b"] != 0)
    net.fit(f32["batch"])
    for i in EXPERT_LAYERS:
        assert np.array_equal(np.asarray(net.params_tree[f"ffn{i}"]["gate_b"]),
                              before[f"ffn{i}"]["gate_b"])
        assert "gate_b" not in net.opt_state[f"ffn{i}"]["m"]
    for layer, name in LEAVES:
        grad = _at(f32["grads_r"], _ref_path(layer, name))
        want = REF.adam_update(
            grad, jnp.zeros_like(grad), jnp.zeros_like(grad), 1,
            float(sizes["learning_rate"]), float(sizes["adam_mean_decay"]),
            float(sizes["adam_var_decay"]))
        got = np.asarray(net.params_tree[layer][name]) - before[layer][name]
        # see tests/test_swa_moe_lm.py: entries whose gradient is near zero
        # follow the gradient's own rounding
        firm = np.abs(np.asarray(grad)) > 1e-3 * np.abs(np.asarray(grad)).max()
        np.testing.assert_allclose(got[firm], np.asarray(want)[firm],
                                   rtol=2e-3, atol=1.3e-7)


def test_bf16_within_the_stated_band(bf16):
    """`mixed_bfloat16`: bf16 products against float32 `highest`. Loss within
    1e-2 of the reference's own; given the program's routing, loss within
    5e-4 and every gradient within 8e-2 (the rehearsal's matrices are 64
    wide: the chip's limits at 2048 are in
    `benchmark/configs/kimi_vl_a3b.py`). The bias reaches the router in
    float32 whatever the policy."""
    assert abs(bf16["loss_p"] - bf16["loss_r"]) <= 1e-2 * bf16["loss_r"]
    assert abs(bf16["loss_p"] - bf16["loss_g"]) <= 5e-4 * bf16["loss_g"]
    for layer, name in LEAVES:
        want = _at(bf16["grads_g"], _ref_path(layer, name))
        assert _rel(bf16["grads_p"][layer][name], want) <= 8e-2, (layer, name)
    for rp, rr in zip(bf16["routes_p"], bf16["routes_r"]):
        agree = np.mean(np.any(np.asarray(rp)[:, :, None]
                               == np.asarray(rr)[:, None, :], axis=2))
        assert agree >= 0.9


def test_float64_program_meets_the_reference_closer_than_float32_can():
    got = _compare("float64")
    assert abs(got["loss_p"] - got["loss_r"]) <= 2e-6 * abs(got["loss_r"])
    assert _rel(got["logits_p"], got["logits_r"]) <= 5e-6


def test_gradient_check_of_the_latent_and_shared_layers():
    sizes = dict(CELL.sizes, dtype_policy={"name": "float64"}, seq_len=16)
    net = CONFIG.make_net(sizes, 9, t=16)
    ids = np.random.default_rng(1).integers(0, V, (2, 17)).astype(np.int32)
    ds = DataSet(ids[:, :-1], ids[:, 1:], None,
                 np.full((2, 16), 1.0 / 16, np.float64))
    assert check_gradients(net, ds, epsilon=1e-6, max_rel_error=1e-4,
                           subset=150, seed=3)


# ------------------------------------------------------------- the kernel
def _latent_operands(rng, dtype, s=256, h=4, dn=32, dr=16):
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    return (mk(s, h, dn), mk(s, h, dr), mk(s, h, dn), mk(s, dr),
            mk(s, h, dn))


def _value_and_grads(attn, operands, w):
    def f(*ops):
        return jnp.sum(attn(*ops).astype(jnp.float32) * w)
    return jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(*operands)


def _dense_latent(q_n, q_r, k_n, k_r, v):
    """The equations with the `[H, S, S]` scores written out."""
    S = q_n.shape[0]
    f = lambda a: a.astype(jnp.float32)
    s = (jnp.einsum("thd,shd->hts", f(q_n), f(k_n))
         + jnp.einsum("thd,sd->hts", f(q_r), f(k_r))) \
        * (q_n.shape[2] + q_r.shape[2]) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), f(v))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("block_q,block_k", [(64, 32), (32, 64), (128, 128)])
def test_latent_pallas_body_matches_the_xla_body_and_the_dense_form(
        rng, dtype, block_q, block_k):
    """Forward and all five gradients, the shared rotary key's (summed over
    the heads) among them; the kernel's tiles are 32 + 16 wide against
    values of 32, nothing padded to one width."""
    operands = _latent_operands(rng, dtype)
    w = jnp.asarray(rng.normal(size=operands[0].shape), jnp.float32)
    pallas = lambda *ops: fa._latent_attention_pallas(*ops, block_q, block_k,
                                                      True)
    got, got_g = _value_and_grads(pallas, operands, w)
    want, want_g = _value_and_grads(dsa.latent_attention_xla, operands, w)
    dense, dense_g = _value_and_grads(_dense_latent, operands, w)
    tol = 3e-2 if dtype == "bfloat16" else 2e-5
    assert abs(float(got) - float(want)) <= tol * abs(float(want)) + tol
    assert abs(float(want) - float(dense)) <= tol * abs(float(dense)) + tol
    for g, x, d, name in zip(got_g, want_g, dense_g,
                             ("dq_n", "dq_r", "dk_n", "dk_r", "dv")):
        assert g.shape == x.shape and g.dtype == x.dtype, name
        assert _rel(g, d) <= tol, name
        assert _rel(x, d) <= tol, name


def test_latent_attention_dispatch_and_what_the_registry_answers(
        rng, monkeypatch):
    operands = _latent_operands(rng, "float32")

    def dispatched(impl):
        fam = obs.metrics.get_family("dl4j_kernel_dispatch_total")
        return sum(c.get() for c in (fam.children() if fam else [])
                   if c.labels == {"kernel": "latent_attention",
                                   "impl": impl})

    before = dispatched("xla"), dispatched("pallas")
    out, fill = dsa.latent_attention(*operands)
    assert fill == 0.0                          # auto off the TPU: XLA body
    monkeypatch.setenv("DL4J_TPU_KERNEL_LATENT_ATTENTION", "pallas")
    registry.clear_cache()
    forced, fill = dsa.latent_attention(*operands)
    registry.clear_cache()
    assert 0.5 < fill <= 1.0
    assert (dispatched("xla"), dispatched("pallas")) == (before[0] + 1,
                                                         before[1] + 1)
    assert _rel(forced, out) <= 2e-5
    for shapes, dtype, ok, why in [
            ((8192, 16, 128, 64, 128), "bfloat16", True, "rotary"),
            ((8192, 16, 128, 64, 128), "float64", False, "64-bit"),
            ((8192, 16, 128, 64, 64), "bfloat16", False, "one width"),
            ((8200, 16, 128, 64, 128), "bfloat16", False, "multiple"),
            ((8192, 16, 128, 32, 128), "bfloat16", False, "rotary part")]:
        got, reason = fa._latent_pallas_available("tpu", shapes, (dtype,))
        assert got is ok and why in reason, (shapes, reason)


def test_the_other_kernels_calls_are_what_they_were():
    """`masked_attention` and `banded_attention` share the kernels' code
    with `latent_attention`; their calls get no operand, result, scratch or
    static argument they did not have."""
    S, H, KV, Dh = 128, 4, 2, 16
    q = jnp.zeros((S, H, Dh)); k = v = jnp.zeros((S, KV, Dh))
    keep = jnp.tril(jnp.ones((S, S), bool))
    for mask, family in ((keep, "masked_attention"),
                         (None, "banded_attention")):
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v, mask=mask: jnp.sum(fa._masked_attention_pallas(
                q, k, v, mask, True, 64, 64, True)), argnums=(0, 1, 2)))(
                    q, k, v))
        assert f"{family}_fwd" in text and f"{family}_dkv" in text
        assert "latent_attention" not in text and "has_rope" not in text


# ------------------------------------------------------------- the router
def _router(rng, N=96, D=16, E=64):
    return (jnp.asarray(rng.normal(size=(D, E)), jnp.float32),
            jnp.asarray(rng.normal(size=(N, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(E,)) * 0.3, jnp.float32))


def test_the_bias_moves_the_choice_and_never_a_weight(rng):
    gate_w, x, bias = _router(rng)
    kw = dict(scoring="sigmoid", routed_scaling_factor=2.446)
    scores = jax.nn.sigmoid(x @ gate_w)
    _, plain_w, plain_idx = expert.route_top_k(gate_w, x, 6, True, **kw)
    probs, w, idx = expert.route_top_k(gate_w, x, 6, True, gate_b=bias, **kw)
    assert not np.array_equal(np.sort(idx, 1), np.sort(plain_idx, 1))
    assert np.array_equal(np.sort(idx, 1), np.sort(
        jax.lax.top_k(scores + bias, 6)[1], 1))
    chosen = jnp.take_along_axis(scores, idx, axis=1)       # no bias in it
    np.testing.assert_allclose(
        w, 2.446 * chosen / chosen.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w.sum(1), 2.446, rtol=1e-6)
    np.testing.assert_allclose(probs, scores / scores.sum(1, keepdims=True),
                               rtol=1e-6)
    # without the normalisation the weights are the scores times the factor
    _, raw, _ = expert.route_top_k(gate_w, x, 6, False, gate_b=bias, **kw)
    np.testing.assert_allclose(raw, 2.446 * chosen, rtol=1e-6)
    # no gradient reaches the bias, one reaches the router through the weights
    def total(gate_w, bias):
        return jnp.sum(expert.route_top_k(gate_w, x, 6, True, gate_b=bias,
                                          **kw)[1] ** 2)
    dw, db = jax.grad(total, argnums=(0, 1))(gate_w, bias)
    assert float(jnp.abs(db).max()) == 0.0 and float(jnp.abs(dw).max()) > 0


@pytest.mark.parametrize("n_group,topk_group,same", [(1, 1, True),
                                                     (4, 4, True),
                                                     (4, 1, False)])
def test_one_group_is_the_identity(rng, n_group, topk_group, same):
    """The reference carries the published group-limited step; with one
    group (the configuration's), or with every group kept, it chooses what
    the program's router chooses, which has no such step."""
    gate_w, x, bias = _router(rng)
    cfg = {"n_experts": 64, "top_k": 6, "n_group": n_group,
           "topk_group": topk_group, "routed_scaling_factor": 2.446}
    _, w_r, idx_r = REF.route({"router": gate_w, "router_bias": bias}, x, cfg)
    _, w_p, idx_p = expert.route_top_k(
        gate_w, x, 6, True, scoring="sigmoid", gate_b=bias,
        routed_scaling_factor=2.446)
    assert np.array_equal(np.asarray(idx_r), np.asarray(idx_p)) is same
    if same:
        np.testing.assert_allclose(w_r, w_p, rtol=1e-5)
    else:                          # every token's experts are of one group
        assert np.all(np.ptp(np.asarray(idx_r) // 16, axis=1) == 0)


def test_sequence_balance_is_per_sequence_and_one_under_an_even_router():
    E, K, S_, B = 8, 2, 16, 3
    even = jnp.full((B * S_, E), 1.0 / E)
    idx = jnp.stack([(jnp.arange(B * S_) * K) % E,
                     (jnp.arange(B * S_) * K + 1) % E], axis=1)
    assert float(expert.sequence_balance(even, idx, B)) == pytest.approx(1.0)
    rng = np.random.default_rng(0)
    probs = rng.random((B * S_, E)).astype(np.float32)
    probs /= probs.sum(1, keepdims=True)
    idx = np.stack([rng.permutation(E)[:K] for _ in range(B * S_)])
    by_hand = []
    for b in range(B):
        rows = slice(b * S_, (b + 1) * S_)
        f = np.bincount(idx[rows].ravel(), minlength=E) * E / (K * S_)
        by_hand.append(float(np.sum(f * probs[rows].mean(0))))
    got = expert.sequence_balance(jnp.asarray(probs), jnp.asarray(idx), B)
    assert float(got) == pytest.approx(np.mean(by_hand), rel=1e-5)
    assert float(got) != pytest.approx(float(expert.sequence_balance(
        jnp.asarray(probs), jnp.asarray(idx), 1)), rel=1e-4)


# -------------------------------------------------------- the shares add up
def test_eight_shares_of_one_expert_layer_sum_to_the_uncut_reference():
    """64 experts top-6 by sigmoid scores and a bias, 8 held a share, a
    shared expert (the published counts at a small width): the eight chips'
    routed parts, added, and the shared expert counted once are the uncut
    layer's output."""
    rng = np.random.default_rng(2)
    E, D, F, Fs, N, K = 64, 32, 24, 40, 48, 6
    n = lambda *shape: (rng.normal(size=shape) * 0.2).astype(np.float32)
    tables = {"gate_w": rng.normal(size=(D, E)).astype(np.float32),
              "gate_b": (rng.normal(size=(E,)) * 0.3).astype(np.float32),
              "w_gate": n(E, D, F), "w_up": n(E, D, F), "w_down": n(E, F, D),
              "shared_gate": n(D, Fs), "shared_up": n(D, Fs),
              "shared_down": n(Fs, D)}
    whole = ("gate_w", "gate_b", "shared_gate", "shared_up", "shared_down")
    x = rng.normal(size=(1, N, D)).astype(np.float32)
    total, shares = np.zeros((1, N, D), np.float32), []
    for j in range(8):
        conf = MoELayer(n_in=D, n_out=D, n_experts=E, expert_hidden=F,
                        top_k=K, dropless=True, norm_topk_prob=True,
                        experts_held=(8 * j, 8), scoring="sigmoid",
                        routed_scaling_factor=2.446, shared_hidden=Fs)
        params = {k: (v if k in whole else v[8 * j:8 * j + 8])
                  for k, v in tables.items()}
        assert {k: v.shape for k, v in params.items()} == conf.param_shapes()
        out, state, _ = moe_layer.moe_apply(conf, params, {}, jnp.asarray(x))
        total += np.asarray(out)
        shares.append(float(state["pairs_held_share"]))
    assert sum(shares) == pytest.approx(1.0)
    ref_p = {"router": tables["gate_w"], "router_bias": tables["gate_b"],
             "w_gate": tables["w_gate"], "w_up": tables["w_up"],
             "w_down": tables["w_down"], "ws_gate": tables["shared_gate"],
             "ws_up": tables["shared_up"], "ws_down": tables["shared_down"]}
    cfg = {"n_experts": E, "top_k": K, "first_expert": 0,
           "routed_scaling_factor": 2.446}
    want, _, _ = REF.experts(ref_p, jnp.asarray(x[0]), cfg)
    shared = REF.gated_mlp(jnp.asarray(x[0]), ref_p["ws_gate"],
                           ref_p["ws_up"], ref_p["ws_down"])
    # each share added the shared expert: counted once, seven go
    assert _rel(total[0] - 7 * np.asarray(shared), want) <= 1e-5


# ------------------------------------------------------------ staged input
@pytest.mark.parametrize("transfer", ["bfloat16", None])
def test_integer_ids_reach_the_embedding_exact_through_the_staged_fit_path(
        transfer):
    """Ids up to 20,479, staged by `DeviceCacheDataSetIterator` under
    `mixed_bfloat16` with a bf16 transfer dtype, are never cast to a float:
    row `i` of the embedding comes back for id `i` (bf16 keeps 8 bits: a
    float id of 20,479 would read 20,480)."""
    ids_held = 20480
    sizes = dict(CELL.sizes, held=dict(CELL.sizes["held"], ids=ids_held),
                 dtype_policy={"name": "mixed_bfloat16",
                               "transfer_dtype": transfer},
                 num_hidden_layers=2)
    net = CONFIG.make_net(sizes, 2)
    table = np.zeros((ids_held, int(sizes["hidden_size"])), np.float32)
    table[:, 0], table[:, 1] = (np.arange(ids_held) // 256,
                                np.arange(ids_held) % 256)
    net.params_tree["emb"]["W"] = jnp.asarray(table)
    ids = np.concatenate([[20479, 20478, 257, 256, 255, 0],
                          np.random.default_rng(0).integers(0, ids_held,
                                                            S - 5)])
    ids = ids.astype(np.int32)[None]
    ds = DataSet(ids[:, :-1], ids[:, 1:], None,
                 np.full((1, S), 1.0 / S, np.float32))
    staged = next(iter(DeviceCacheDataSetIterator(
        [ds], transfer_dtype=net.dtype_policy.transfer_dtype)))
    assert jnp.issubdtype(staged.features.dtype, jnp.integer)
    assert jnp.issubdtype(staged.labels.dtype, jnp.integer)
    _, _, values = net.loss_and_gradients(staged, wrt={"out": ["W"]},
                                          collect=["emb"])
    rows = np.asarray(values["emb"][0], np.float32)
    assert np.array_equal(rows[:, 0] * 256 + rows[:, 1], ids[0, :-1])
    net.fit(DeviceCacheDataSetIterator(
        [ds], transfer_dtype=net.dtype_policy.transfer_dtype))
    assert np.isfinite(net.score_value)


# ------------------------------------------------- fields, defaults, gauges
def test_new_fields_round_trip_and_stay_out_of_a_plain_layers_json():
    conf = CONFIG.make_conf(CELL.sizes, 1)
    text = conf.to_json()
    again = ComputationGraphConfiguration.from_json(text)
    assert again.to_json() == text
    assert ComputationGraphConfiguration.from_yaml(
        conf.to_yaml()).to_json() == text
    assert again.vertices["attn1"].layer.kv_lora_rank == 32
    assert again.vertices["ffn1"].layer.shared_hidden == 64
    assert isinstance(again.vertices["ffn0"].layer, GatedDenseLayer)
    plain = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2).to_dict()
    assert not {"kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim"} & set(plain)
    assert layer_from_dict(plain).state_shapes() == {}
    moe = MoELayer(n_in=8, n_out=8, dropless=True).to_dict()
    assert not {"scoring", "routed_scaling_factor", "shared_hidden"} \
        & set(moe)


@pytest.mark.parametrize("kwargs,match", [
    (dict(kv_lora_rank=8), "needs"),
    (dict(MLA, n_kv_heads=2), "none of"),
    (dict(MLA, qk_norm_eps=1e-6), "none of"),
    (dict(MLA, sliding_window=4), "none of"),
    (dict(MLA, causal=False), "causal")])
def test_a_latent_layer_refuses_what_it_cannot_be(kwargs, match):
    with pytest.raises(ValueError, match=match):
        SelfAttentionLayer(n_in=8, n_out=8, n_heads=2, **kwargs)


@pytest.mark.parametrize("kwargs,match", [
    (dict(scoring="sigmoid"), "dropless"),
    (dict(shared_hidden=8), "dropless"),
    (dict(dropless=True, scoring="tanh"), "softmax or sigmoid"),
    (dict(dropless=True, routed_scaling_factor=2.0), "sigmoid")])
def test_a_router_setting_off_the_dropless_sigmoid_path_is_refused(kwargs,
                                                                   match):
    with pytest.raises(ValueError, match=match):
        MoELayer(n_in=8, n_out=8, **kwargs)


def test_the_defaults_are_the_softmax_no_shared_expert_one_width_path(rng):
    """What both sibling configurations run: `route_top_k`, `MoELayer` and
    `SelfAttentionLayer` by their defaults."""
    gate_w, x, _ = _router(rng)
    probs, gate, idx = expert.route_top_k(gate_w, x, 8)
    soft = jax.nn.softmax(x @ gate_w, axis=-1)
    np.testing.assert_allclose(probs, soft, rtol=1e-6)
    top, top_idx = jax.lax.top_k(soft, 8)
    assert np.array_equal(np.asarray(idx), np.asarray(top_idx))
    np.testing.assert_allclose(gate, top / top.sum(1, keepdims=True),
                               rtol=1e-6)
    moe = MoELayer(n_in=16, n_out=16, n_experts=8, expert_hidden=4, top_k=2,
                   dropless=True, experts_held=(0, 2))
    assert (moe.scoring, moe.routed_scaling_factor, moe.shared_hidden) == (
        None, None, None)
    assert list(moe.param_shapes()) == ["gate_w", "w_gate", "w_up", "w_down"]
    attn = SelfAttentionLayer(n_in=16, n_out=16, n_heads=4, n_kv_heads=2,
                              head_dim=4, rope_theta=1e4, qk_norm_eps=1e-6)
    assert attn.kv_lora_rank is None and attn.attention_scope() == "attn.full"
    assert list(attn.param_shapes()) == ["Wq", "Wk", "Wv", "Wo", "gamma_q",
                                         "gamma_k"]
    text = str(jax.make_jaxpr(lambda p, h: moe_layer.moe_apply(
        moe, p, {}, h)[0])({k: jnp.zeros(s) for k, s in
                            moe.param_shapes().items()},
                           jnp.zeros((1, 8, 16))))
    # the experts' SiLU is the only sigmoid: none of [N, E], and no shared
    # expert's scope
    assert "logistic" in text and ":f32[8,8] = logistic" not in text
    assert "moe.shared" not in text


def test_expert_gauges_are_published_for_the_expert_layers():
    net = CONFIG.make_net(CELL.sizes, 5)
    net.fit(_batch())
    net.score_value
    for family in ("dl4j_moe_pairs_held_share",
                   "dl4j_moe_expert_load_max_over_mean"):
        layers = {c.labels["layer"] for c in obs.metrics.get_family(
            family).children()}
        assert {f"ffn{i}" for i in EXPERT_LAYERS} <= layers
        assert "ffn0" not in layers or family  # the dense layer routes nothing
    fill = {c.labels["layer"]: c.get() for c in obs.metrics.get_family(
        "dl4j_attn_band_fill_share").children()}
    assert all(fill[f"attn{i}"] == 0.0 for i in range(N_LAYERS))  # XLA body
