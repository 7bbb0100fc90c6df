"""`zoo.sparse_moe_lm` and its layers against the plain float32 reference
(`benchmark/reference/sparse_moe_lm.py`) at the rehearsal size: loss,
logits, the selected sets and the gradient of every trainable leaf; the
eight shares of one expert layer; the frozen indexer; the new fields'
round trip; integer ids through the staged fit path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import cells
from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import DeviceCacheDataSetIterator
from deeplearning4j_tpu.gradientcheck import check_gradients
from deeplearning4j_tpu.kernels import grouped_matmul, registry
from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.nn.conf.layers import (
    MoELayer, RMSNormalization, SelfAttentionLayer, layer_from_dict)
from deeplearning4j_tpu.nn.conf.neural_net import (
    ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import dsa
from deeplearning4j_tpu.nn.layers import moe as moe_layer
from deeplearning4j_tpu.parallel import expert as expert_mod

CELL = cells.Cell("keye_vl2_30b_a3b.fit_seq8k", rehearsal=True)
CONFIG = cells.load_module("configs", "keye_vl2_30b_a3b")
REF = cells.load_module("reference", "sparse_moe_lm")
N_LAYERS = int(CELL.sizes["num_hidden_layers"])
S = int(CELL.sizes["seq_len"])
V = int(CELL.sizes["held"]["ids"])


def _batch(seed=7):
    ids = np.random.default_rng(seed).integers(0, V, (1, S + 1)).astype(
        np.int32)
    return DataSet(ids[:, :-1], ids[:, 1:], None,
                   np.full((1, S), 1.0 / S, np.float32))


def _trainable(net):
    spec = net._frozen_spec
    return [(layer, name) for layer, leaves in sorted(net.params_tree.items())
            for name in sorted(leaves) if name not in spec.get(layer, ())]


def _ref_path(layer, name):
    """A program leaf's place in the reference's tree."""
    if layer == "emb":
        return ("embed",)
    if layer == "out":
        return ("head",)
    if layer == "ln_out":
        return ("norm",)
    i = int(layer[-1])
    key = {"ln_a": {"gamma": "ln1"}, "ln_f": {"gamma": "ln2"},
           "attn": {"Wq": "wq", "Wk": "wk", "Wv": "wv", "Wo": "wo",
                    "gamma_q": "q_norm", "gamma_k": "k_norm"},
           "ffn": {"gate_w": "router", "w_gate": "w_gate", "w_up": "w_up",
                   "w_down": "w_down"}}[layer[:-1]][name]
    return ("layers", i, key)


def _compare(policy):
    """Program and reference on one batch: everything the tests below read."""
    sizes = dict(CELL.sizes, dtype_policy={"name": policy})
    net = ComputationGraph(CONFIG.make_conf(sizes, 11)).init()
    batch = _batch()
    collect = ["out"] + CONFIG.collected_sets(N_LAYERS)
    loss_p, grads_p, values = net.loss_and_gradients(batch, collect=collect)
    keeps_p = [values[f"attn{i}.selected_keys"][0] for i in range(N_LAYERS)]
    routes_p = [values[f"ffn{i}.expert_idx"][0] for i in range(N_LAYERS)]
    cfg = CONFIG.model_cfg(sizes)
    rparams = CONFIG.reference_params(net.params_tree, N_LAYERS)
    ids, labels = jnp.asarray(batch.features[0]), jnp.asarray(batch.labels[0])
    logits_r, _, keeps_r, routes_r = REF.forward(rparams, ids, cfg)
    loss_r, grads_r = REF.loss_and_grads(rparams, ids, labels, cfg)
    loss_g, grads_g = REF.loss_and_grads(rparams, ids, labels, cfg,
                                         keeps=keeps_p, routes=routes_p)
    return dict(net=net, loss_p=float(loss_p), grads_p=grads_p,
                logits_p=np.asarray(values["out"][0], np.float32),
                keeps_p=keeps_p, routes_p=routes_p, keeps_r=keeps_r,
                routes_r=routes_r, logits_r=np.asarray(logits_r),
                loss_r=float(loss_r), grads_r=grads_r, loss_g=float(loss_g),
                grads_g=grads_g)


@pytest.fixture(scope="module")
def f32():
    return _compare("float32")


@pytest.fixture(scope="module")
def bf16():
    return _compare("mixed_bfloat16")


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


LEAVES = _trainable(ComputationGraph(CONFIG.make_conf(CELL.sizes, 1)).init())


def test_every_trainable_leaf_is_compared():
    assert len(LEAVES) == 3 + N_LAYERS * 12
    assert not any(n in SelfAttentionLayer.INDEXER_PARAMS for _, n in LEAVES)


def test_f32_loss_and_logits_match_the_reference(f32):
    assert abs(f32["loss_p"] - f32["loss_r"]) <= 1e-5 * abs(f32["loss_r"])
    assert _rel(f32["logits_p"], f32["logits_r"]) <= 1e-5


def test_f32_selected_sets_and_routing_equal_the_reference(f32):
    for kp, kr in zip(f32["keeps_p"], f32["keeps_r"]):
        assert np.array_equal(np.asarray(kp), np.asarray(kr))
        rows = np.asarray(kp).sum(axis=1)
        top = int(CELL.sizes["sa_config"]["topk"])
        assert np.array_equal(rows, np.minimum(np.arange(S) + 1, top))
    for rp, rr in zip(f32["routes_p"], f32["routes_r"]):
        assert np.array_equal(np.sort(np.asarray(rp), 1),
                              np.sort(np.asarray(rr), 1))


@pytest.mark.parametrize("layer,name", LEAVES,
                         ids=[f"{l}.{n}" for l, n in LEAVES])
def test_f32_gradient_matches_the_reference(f32, layer, name):
    want = f32["grads_r"]
    for key in _ref_path(layer, name):
        want = want[key]
    assert _rel(f32["grads_p"][layer][name], want) <= 1e-5


def test_bf16_within_the_stated_band(bf16):
    """`mixed_bfloat16`: bf16 products against float32 `highest`. Loss within
    1e-2 of the reference's own (at 64 positions of 16 keys one flipped
    near-tie moves it); given the program's selection and routing, loss
    within 5e-4 and every gradient within 8e-2 (the rehearsal's matrices are
    64 wide: the chip's limits at 2048 are in
    `benchmark/configs/keye_vl2_30b_a3b.py`)."""
    assert abs(bf16["loss_p"] - bf16["loss_r"]) <= 1e-2 * bf16["loss_r"]
    assert abs(bf16["loss_p"] - bf16["loss_g"]) <= 5e-4 * bf16["loss_g"]
    for layer, name in LEAVES:
        want = bf16["grads_g"]
        for key in _ref_path(layer, name):
            want = want[key]
        assert _rel(bf16["grads_p"][layer][name], want) <= 8e-2, (layer, name)


def test_indexer_is_frozen_and_holds_no_updater_state():
    net = ComputationGraph(CONFIG.make_conf(CELL.sizes, 3)).init()
    before = jax.tree_util.tree_map(np.asarray, net.params_tree)
    for i in range(N_LAYERS):
        assert net._frozen_spec[f"attn{i}"] == frozenset(
            SelfAttentionLayer.INDEXER_PARAMS)
        for moment in net.opt_state[f"attn{i}"].values():
            assert not set(moment) & set(SelfAttentionLayer.INDEXER_PARAMS)
            assert {"Wq", "Wk", "Wv", "Wo"} <= set(moment)
    for _ in range(2):
        net.fit(_batch())
    assert np.isfinite(net.score_value)
    for layer, leaves in net.params_tree.items():
        for name, leaf in leaves.items():
            same = np.array_equal(np.asarray(leaf), before[layer][name])
            assert same == (name in SelfAttentionLayer.INDEXER_PARAMS), (
                layer, name)
    with pytest.raises(ValueError, match="frozen"):
        net.loss_and_gradients(_batch(), wrt={"attn0": ["Wiq"]})


def test_counters_are_published_where_the_score_is_read():
    net = ComputationGraph(CONFIG.make_conf(CELL.sizes, 5)).init()
    net.fit(_batch())
    net.score_value
    top = int(CELL.sizes["sa_config"]["topk"])
    want = np.minimum(np.arange(S) + 1, top).mean()

    def values(name):
        return {c.labels["layer"]: c.get()
                for c in obs.metrics.get_family(name).children()}

    keys = values("dl4j_dsa_selected_keys_mean")
    assert keys["attn0"] == pytest.approx(want) == keys["attn1"]
    share = values("dl4j_moe_pairs_held_share")
    load = values("dl4j_moe_expert_load_max_over_mean")
    for i in range(N_LAYERS):
        assert 0.0 < share[f"ffn{i}"] < 1.0
        assert load[f"ffn{i}"] >= 1.0


@pytest.mark.parametrize("case", ["distinct", "ties_at_threshold",
                                  "all_equal", "zeros_and_negative_zeros"])
def test_select_top_k_is_exact(case):
    rng = np.random.default_rng(0)
    n, k = 48, 8
    scores = rng.normal(size=(n, n)).astype(np.float32)
    if case == "ties_at_threshold":
        scores = np.round(scores * 2) / 2
    elif case == "all_equal":
        scores[:] = 1.5
    elif case == "zeros_and_negative_zeros":
        scores = np.where(rng.random((n, n)) < 0.7,
                          np.where(rng.random((n, n)) < 0.5, 0.0, -0.0),
                          scores).astype(np.float32)
    causal = np.tril(np.ones((n, n), bool))
    scores = np.where(causal, scores, -np.inf).astype(np.float32)
    got = np.asarray(dsa.select_top_k(jnp.asarray(scores), k))
    order = np.argsort(-(scores + 0.0), axis=1, kind="stable")
    want = np.zeros((n, n), bool)
    for t in range(n):
        want[t, order[t, :min(t + 1, k)]] = True
    assert np.array_equal(got, want)
    assert np.array_equal(np.asarray(REF.selection(jnp.asarray(scores), k)),
                          want)


def _moe_tables(rng, E, D, F):
    return {"gate_w": rng.normal(size=(D, E)).astype(np.float32),
            "w_gate": (rng.normal(size=(E, D, F)) * 0.2).astype(np.float32),
            "w_up": (rng.normal(size=(E, D, F)) * 0.2).astype(np.float32),
            "w_down": (rng.normal(size=(E, F, D)) * 0.2).astype(np.float32)}


def test_eight_shares_of_one_expert_layer_sum_to_the_uncut_reference():
    rng = np.random.default_rng(2)
    E, D, F, N, K = 16, 32, 24, 40, 4
    tables = _moe_tables(rng, E, D, F)
    x = rng.normal(size=(1, N, D)).astype(np.float32)
    total = np.zeros((1, N, D), np.float32)
    shares = []
    for j in range(8):
        conf = MoELayer(n_in=D, n_out=D, n_experts=E, expert_hidden=F,
                        top_k=K, dropless=True, norm_topk_prob=True,
                        experts_held=(2 * j, 2))
        params = {k: (v if k == "gate_w" else v[2 * j:2 * j + 2])
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


def test_expert_axis_gives_each_device_its_experts():
    from jax.sharding import Mesh

    from deeplearning4j_tpu.parallel.context import (
        ParallelContext, parallel_context)

    rng = np.random.default_rng(4)
    E, D, F, N, K = 16, 32, 24, 40, 4
    tables = _moe_tables(rng, E, D, F)
    x = jnp.asarray(rng.normal(size=(1, N, D)).astype(np.float32))
    conf = MoELayer(n_in=D, n_out=D, n_experts=E, expert_hidden=F, top_k=K,
                    dropless=True, norm_topk_prob=True)
    one, state1, _ = moe_layer.moe_apply(conf, tables, {}, x)
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("expert",))
    ctx = ParallelContext(mesh, data_axis=None, expert_axis="expert")
    with parallel_context(ctx):
        many, state8, _ = jax.jit(
            lambda t, x: moe_layer.moe_apply(conf, t, {}, x))(tables, x)
    assert _rel(many, one) <= 1e-5
    assert float(state8["pairs_held_share"]) == pytest.approx(1.0)
    assert float(state8["_aux_loss"]) == pytest.approx(
        float(state1["_aux_loss"]), rel=1e-5)


def test_dropless_experts_match_a_loop_over_the_held_pairs():
    """`moe_ffn_dropless` with experts 1-2 of 4 held: every (token, expert)
    pair of a held expert, one at a time, and nothing for the others."""
    rng = np.random.default_rng(6)
    E, D, F, N, K, first, Eh = 4, 16, 12, 24, 2, 1, 2
    p = {k: jnp.asarray(v) for k, v in _moe_tables(rng, E, D, F).items()}
    held = {k: (v if k == "gate_w" else v[first:first + Eh])
            for k, v in p.items()}
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    y, _, (share, _), routed = expert_mod.moe_ffn_dropless(
        held, x, top_k=K, first=first)
    _, gate, idx = expert_mod.route_top_k(p["gate_w"], x, K)
    assert np.array_equal(np.asarray(routed), np.asarray(idx))
    want, pairs = np.zeros((N, D)), 0
    for n in range(N):
        for g, e in zip(np.asarray(gate[n]), np.asarray(idx[n])):
            if first <= e < first + Eh:
                xn = np.asarray(x[n], np.float64)
                a = xn @ np.asarray(p["w_gate"][e], np.float64)
                hid = a / (1 + np.exp(-a)) * (xn @ np.asarray(p["w_up"][e]))
                want[n] += g * (hid @ np.asarray(p["w_down"][e]))
                pairs += 1
    assert 0 < pairs < N * K
    assert float(share) == pytest.approx(pairs / (N * K))
    assert _rel(y, want) <= 1e-5


# Which experts of 8 a holder keeps, and what that makes of the sorted axis
# (N * top_k = 48 pairs; a tile of the chip is 8 rows): no pair held (expert
# 0, which the router's bias keeps every token away from), every pair, a
# live prefix that ends inside a tile, and one shorter than a tile.
HELD_CASES = {"none": (0, 1), "all": (0, 8), "ragged_prefix": (1, 3),
              "under_a_tile": (5, 1)}


def _value_and_grads(fn, *args):
    (_, y), grads = jax.value_and_grad(fn, argnums=tuple(range(len(args))),
                                       has_aux=True)(*args)
    return (y,) + tuple(grads)


def _held_case(case):
    """Tables, tokens and a cotangent for one case: `run(first)` gives the
    program's y and its gradients with respect to x, the router and the
    three held tables; `loop(x, gate_w, w_gate, w_up, w_down)` over all 8
    experts' tables is the float32 loop over the held pairs they are
    compared with, -> (sum(y * r), y)."""
    rng = np.random.default_rng(12)
    E, D, F, N, K = 8, 16, 12, 24, 2
    first, count = HELD_CASES[case]
    p = _moe_tables(rng, E, D, F)
    x = rng.normal(size=(N, D)).astype(np.float32)
    x[:, 0] = 1.0
    p["gate_w"][0, 0] = -100.0          # no token's top 2 holds expert 0
    p = {k: jnp.asarray(v) for k, v in p.items()}
    x, r = jnp.asarray(x), jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    _, _, idx = expert_mod.route_top_k(p["gate_w"], x, K)
    pairs = [(n, s, int(e)) for n, row in enumerate(np.asarray(idx))
             for s, e in enumerate(row) if first <= e < first + count]

    def loop(x, gate_w, w_gate, w_up, w_down):
        _, gate, _ = expert_mod.route_top_k(gate_w, x, K)
        y = jnp.zeros((N, D), jnp.float32)
        for n, s, e in pairs:
            hid = jax.nn.silu(x[n] @ w_gate[e]) * (x[n] @ w_up[e])
            y = y.at[n].add(gate[n, s] * (hid @ w_down[e]))
        return jnp.sum(y * r), y

    held = slice(first, first + count)
    args = (x, p["gate_w"], p["w_gate"], p["w_up"], p["w_down"])

    def run(first):
        def program(x, gate_w, w_gate, w_up, w_down):
            y, _, _, _ = expert_mod.moe_ffn_dropless(
                {"gate_w": gate_w, "w_gate": w_gate, "w_up": w_up,
                 "w_down": w_down}, x, top_k=K, first=first)
            return jnp.sum(y * r), y
        return _value_and_grads(program, *args[:2],
                                *(t[held] for t in args[2:]))

    return dict(pairs=pairs, n_pairs=N * K, held=held, first=first, args=args,
                loop=loop, run=run)


def _check_held_case(case, got, c):
    """y, dx, dgate_w and the held tables' gradients against the loop's."""
    want = list(_value_and_grads(c["loop"], *c["args"]))
    want[3:] = [g[c["held"]] for g in want[3:]]
    n_held = len(c["pairs"])
    assert {"none": n_held == 0, "all": n_held == c["n_pairs"],
            "ragged_prefix": n_held > 8 and n_held % 8,
            "under_a_tile": 0 < n_held < 8}[case], n_held
    for name, a, b in zip(("y", "dx", "dgate_w", "dw_gate", "dw_up",
                           "dw_down"), got, want):
        assert np.all(np.isfinite(np.asarray(a))), name
        if case == "none":
            assert not np.any(np.asarray(a)), name
        else:
            assert _rel(a, b) <= 2e-5, (name, _rel(a, b))


@pytest.mark.parametrize("jitted", [False, True],
                         ids=["eager", "jit_first_traced"])
@pytest.mark.parametrize("case", list(HELD_CASES))
def test_dropless_value_and_gradients_match_a_loop_over_the_held_pairs(
        case, jitted):
    """`moe_ffn_dropless` and its hand-written backward pass: y and the
    gradients with respect to x, the router and the three held tables, with
    `first` a Python int and traced under `jax.jit`."""
    c = _held_case(case)
    if jitted:
        got = jax.jit(c["run"])(jnp.int32(c["first"]))
    else:
        got = c["run"](c["first"])
    _check_held_case(case, got, c)


@pytest.mark.parametrize("case", list(HELD_CASES))
def test_dropless_dead_rows_may_hold_anything(case, monkeypatch):
    """Rows of a sorted array past the live prefix are nobody's. On the chip
    a grouped product leaves them unwritten, and with the groups contracted
    reads none of them (PERF.md PR 31); here every gather into expert order
    and every grouped product hands back NaN there, and y and every
    gradient stay finite and equal to the run without."""
    c = _held_case(case)
    clean = c["run"](c["first"])
    poisoned = []

    def dead_rows_nan(fn):
        def wrapped(*args):
            rows = fn(*args)
            poisoned.append(rows.shape)
            dead = jnp.arange(rows.shape[0]) >= len(c["pairs"])
            return jnp.where(dead[:, None], jnp.nan, rows)
        return wrapped

    monkeypatch.setattr(expert_mod, "_rows_of_pairs",
                        dead_rows_nan(expert_mod._rows_of_pairs))
    # the registry's `grouped_matmul` resolves its XLA candidate here, which
    # looks `jax.lax.ragged_dot` up at each call
    assert registry.resolve("grouped_matmul").impl == "xla"
    monkeypatch.setattr(grouped_matmul.jax.lax, "ragged_dot",
                        dead_rows_nan(jax.lax.ragged_dot))
    got = c["run"](c["first"])
    # three gathers into expert order, nine grouped products with rows out
    assert len(poisoned) == 12 and all(
        shape[0] == c["n_pairs"] for shape in poisoned)
    for a, b in zip(got, clean):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    _check_held_case(case, got, c)


# The Pallas bodies of `grouped_matmul` want a lane grid and four row tiles:
# 128 tokens top-4 (512 pairs), rows of 128 and 256. Experts `first ..` of 16
# held: (12, 6) holds two experts the router does not have, groups of no rows.
KERNEL_CASES = {"softmax-first-0": ("softmax", 0, 6),
                "softmax-some-empty": ("softmax", 12, 6),
                "sigmoid-first-3": ("sigmoid", 3, 5),
                "sigmoid-some-empty": ("sigmoid", 13, 4)}


def _kernel_case(case, dtype):
    scoring, first, count = KERNEL_CASES[case]
    rng = np.random.default_rng(21)
    E, D, F, N, K = 16, 128, 256, 128, 4

    def mk(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    params = {"gate_w": mk(D, E), "w_gate": mk(count, D, F, scale=0.1),
              "w_up": mk(count, D, F, scale=0.1),
              "w_down": mk(count, F, D, scale=0.1)}
    if scoring == "sigmoid":
        params["gate_b"] = jnp.asarray(rng.normal(size=(E,)) * 0.05,
                                       jnp.float32)
    x, r = mk(N, D), jnp.asarray(rng.normal(size=(N, D)), jnp.float32)

    def run(params, x):
        def program(params, x):
            y, aux, stats, _ = expert_mod.moe_ffn_dropless(
                params, x, top_k=K, first=first, scoring=scoring,
                routed_scaling_factor=2.0 if scoring == "sigmoid" else 1.0)
            return jnp.sum(y.astype(jnp.float32) * r) + aux, (y, stats)
        (_, (y, stats)), (dp, dx) = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True))(params, x)
        dp.pop("gate_b", None)          # frozen: it enters the choice alone
        return dict(dp, y=y, dx=dx), stats
    return run, params, x, (N * K, first, count, E)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_dropless_experts_under_the_grouped_kernel_match_xla(
        case, dtype, monkeypatch):
    """`moe_ffn_dropless` with the registry's `grouped_matmul` forced to its
    Pallas body (interpreted here) against XLA's `ragged_dot`: y, dx, the
    router's and the three tables' gradients."""
    run, params, x, (pairs, first, count, E) = _kernel_case(
        case, jnp.dtype(dtype))
    got = {}
    for mode in ("xla", "pallas"):
        monkeypatch.setenv("DL4J_TPU_KERNEL_GROUPED_MATMUL", mode)
        registry.clear_cache()
        got[mode], stats = run(params, x)
        assert {r.impl for r in registry.resolved()
                if r.kernel == "grouped_matmul"} == {mode}
    registry.clear_cache()
    assert 0.05 < float(stats[0]) < 0.6
    tol = 2e-5 if dtype == "float32" else 2e-2
    for name, want in got["xla"].items():
        a = np.asarray(got["pallas"][name], np.float32)
        assert np.all(np.isfinite(a)), name
        assert _rel(a, np.asarray(want, np.float32)) <= tol, name
    if first + count > E:       # experts the router has not: their tables'
        for name in ("w_gate", "w_up", "w_down"):       # gradients are zeros
            assert not np.any(np.asarray(
                got["pallas"][name][E - first:], np.float32)), name


def test_dead_rows_may_hold_anything_under_the_grouped_kernel(monkeypatch):
    """The same guard as above for the Pallas bodies: NaN in every row the
    gathers into expert order make past the live prefix changes nothing."""
    run, params, x, _ = _kernel_case("softmax-some-empty", jnp.float32)
    monkeypatch.setenv("DL4J_TPU_KERNEL_GROUPED_MATMUL", "pallas")
    registry.clear_cache()
    clean, stats = run(params, x)
    live = int(round(float(stats[0]) * 512))
    gathers = []

    def dead_rows_nan(src, token):
        gathers.append(src.shape)
        dead = jnp.arange(token.shape[0]) >= live
        return jnp.where(dead[:, None], jnp.nan, src[token])

    monkeypatch.setattr(expert_mod, "_rows_of_pairs", dead_rows_nan)
    got, _ = run(params, x)
    registry.clear_cache()
    assert len(gathers) == 3 and 0 < live < 512
    for name, want in clean.items():
        assert np.array_equal(np.asarray(got[name]), np.asarray(want)), name


def test_gradient_check_of_the_new_layers():
    sizes = dict(CELL.sizes, dtype_policy={"name": "float64"}, seq_len=16,
                 sa_config=dict(CELL.sizes["sa_config"], topk=6))
    conf = CONFIG.make_conf(sizes, 9, t=16)
    net = ComputationGraph(conf).init()
    ids = np.random.default_rng(1).integers(0, V, (2, 17)).astype(np.int32)
    ds = DataSet(ids[:, :-1], ids[:, 1:], None,
                 np.full((2, 16), 1.0 / 16, np.float64))
    assert check_gradients(net, ds, epsilon=1e-6, max_rel_error=1e-4,
                           subset=150, seed=3)


def test_new_fields_round_trip_through_json_and_yaml():
    conf = CONFIG.make_conf(CELL.sizes, 1)
    text = conf.to_json()
    again = ComputationGraphConfiguration.from_json(text)
    assert again.to_json() == text
    assert ComputationGraphConfiguration.from_yaml(
        conf.to_yaml()).to_json() == text
    attn = again.vertices["attn0"].layer
    assert (attn.n_kv_heads, attn.head_dim, attn.index_top_k) == (
        int(CELL.sizes["num_key_value_heads"]), int(CELL.sizes["head_dim"]),
        int(CELL.sizes["sa_config"]["topk"]))
    ffn = again.vertices["ffn1"].layer
    assert ffn.experts_held == (0, int(CELL.sizes["held"]["experts"]))
    assert ffn.dropless and ffn.norm_topk_prob
    assert set(ffn.param_shapes()) == {"gate_w", "w_gate", "w_up", "w_down"}
    assert again.vertices["out"].layer.scope == "lm.head"
    assert isinstance(again.vertices["ln_out"].layer, RMSNormalization)
    # the fields a plain layer does not set stay out of its JSON
    plain = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2).to_dict()
    assert not {"n_kv_heads", "rope_theta", "index_top_k", "scope"} & set(plain)
    assert layer_from_dict(plain).param_shapes()["qB"] == (8,)
    with pytest.raises(ValueError, match="dropless"):
        MoELayer(n_in=8, n_out=8, norm_topk_prob=True)


@pytest.mark.parametrize("transfer", ["bfloat16", None])
def test_integer_ids_reach_the_embedding_exact_through_the_staged_fit_path(
        transfer):
    """Ids up to 18,991, staged by `DeviceCacheDataSetIterator` under
    `mixed_bfloat16` with a bf16 transfer dtype, are never cast to a float:
    row `i` of the embedding comes back for id `i` (bf16 keeps 8 bits: a
    float id of 18,991 would read 18,944)."""
    sizes = dict(CELL.sizes, held=dict(CELL.sizes["held"], ids=18992),
                 dtype_policy={"name": "mixed_bfloat16",
                               "transfer_dtype": transfer},
                 num_hidden_layers=1)
    net = ComputationGraph(CONFIG.make_conf(sizes, 2)).init()
    table = np.zeros((18992, int(sizes["hidden_size"])), np.float32)
    table[:, 0], table[:, 1] = np.arange(18992) // 256, np.arange(18992) % 256
    net.params_tree["emb"]["W"] = jnp.asarray(table)
    ids = np.concatenate([[18991, 18990, 257, 256, 255, 0],
                          np.random.default_rng(0).integers(0, 18992, S - 5)])
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
