"""The `keye_vl2_30b_a3b` configuration's benchmark files: the plain
reference against a tiny case written out by hand, its two forms against
each other, the cell's rehearsal as a command, the scope readers on a canned
trace, and the count of operations `fit_mfu` is computed from."""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import cells, flops, scope_time

REF = cells.load_module("reference", "sparse_moe_lm")
CONFIG = cells.load_module("configs", "keye_vl2_30b_a3b")
SIZES = cells.load_json("configs", "keye_vl2_30b_a3b")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _tiny(seed=0, S=12, D=8, H=4, KV=2, Dh=4, IH=2, ID=4, E=4, Eh=2, F=6,
          V=10, top_k=2, index_top_k=5, n_layers=1):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)

    layer = {"ln1": w(D) + 1, "ln2": w(D) + 1, "wq": w(D, H * Dh),
             "wk": w(D, KV * Dh), "wv": w(D, KV * Dh), "wo": w(H * Dh, D),
             "q_norm": w(Dh) + 1, "k_norm": w(Dh) + 1,
             "idx_wq": w(D, IH * ID), "idx_wk": w(D, ID), "idx_w": w(D, IH),
             "idx_k_norm_g": w(ID) + 1, "idx_k_norm_b": w(ID),
             "router": w(D, E), "w_gate": w(Eh, D, F), "w_up": w(Eh, D, F),
             "w_down": w(Eh, F, D)}
    params = {"embed": w(V, D), "layers": [layer] * n_layers,
              "norm": w(D) + 1, "head": w(D, V)}
    cfg = {"n_heads": H, "n_kv_heads": KV, "head_dim": Dh, "rope_theta": 1e4,
           "rms_eps": 1e-6, "index_n_heads": IH, "index_head_dim": ID,
           "index_top_k": index_top_k, "n_experts": E, "top_k": top_k,
           "first_expert": 1, "norm_topk_prob": True, "aux_coef": 0.01}
    ids = jnp.asarray(rng.integers(0, V, S), jnp.int32)
    return params, cfg, ids


def _by_hand(params, cfg, ids):
    """The same equations with numpy loops over positions, heads and
    experts: nothing shared with the reference but the parameters."""
    p = {k: np.asarray(v, np.float64) for k, v in params["layers"][0].items()}
    x = np.asarray(params["embed"], np.float64)[np.asarray(ids)]
    S, D = x.shape
    H, KV, Dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    IH, ID = cfg["index_n_heads"], cfg["index_head_dim"]

    def rms(v, g):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + cfg["rms_eps"]) * g

    def rope(v, t):
        half = v.shape[-1] // 2
        out = v.copy()
        for i in range(half):
            a = t * cfg["rope_theta"] ** (-2.0 * i / v.shape[-1])
            out[..., i] = v[..., i] * math.cos(a) - v[..., i + half] * math.sin(a)
            out[..., i + half] = (v[..., i + half] * math.cos(a)
                                  + v[..., i] * math.sin(a))
        return out

    h = rms(x, p["ln1"])
    q = np.stack([rope(rms((h[t] @ p["wq"]).reshape(H, Dh), p["q_norm"]), t)
                  for t in range(S)])
    k = np.stack([rope(rms((h[t] @ p["wk"]).reshape(KV, Dh), p["k_norm"]), t)
                  for t in range(S)])
    v = (h @ p["wv"]).reshape(S, KV, Dh)
    ki = h @ p["idx_wk"]
    ki = (ki - ki.mean(-1, keepdims=True)) / np.sqrt(
        ki.var(-1, keepdims=True) + 1e-6) * p["idx_k_norm_g"] + p["idx_k_norm_b"]
    ki = np.stack([rope(ki[t], t) for t in range(S)])
    qi = np.stack([rope((h[t] @ p["idx_wq"]).reshape(IH, ID), t)
                   for t in range(S)])
    w = h @ p["idx_w"]
    attn = np.zeros((S, H * Dh))
    kept = []
    for t in range(S):
        score = [sum(w[t, j] * IH ** -0.5 * max(qi[t, j] @ ki[s], 0.0)
                     * ID ** -0.5 for j in range(IH)) for s in range(t + 1)]
        best = sorted(range(t + 1), key=lambda s: (-score[s], s))
        best = sorted(best[:cfg["index_top_k"]])
        kept.append(best)
        for head in range(H):
            g = head // (H // KV)
            z = np.array([q[t, head] @ k[s, g] / math.sqrt(Dh) for s in best])
            a = np.exp(z - z.max())
            a /= a.sum()
            attn[t, head * Dh:(head + 1) * Dh] = sum(
                a[n] * v[s, g] for n, s in enumerate(best))
    x = x + attn @ p["wo"]
    h2 = rms(x, p["ln2"])
    y = np.zeros_like(x)
    for t in range(S):
        z = h2[t] @ p["router"]
        prob = np.exp(z - z.max())
        prob /= prob.sum()
        top = np.argsort(-prob, kind="stable")[:cfg["top_k"]]
        for e in top:
            j = e - cfg["first_expert"]
            if 0 <= j < p["w_gate"].shape[0]:
                a, b = h2[t] @ p["w_gate"][j], h2[t] @ p["w_up"][j]
                y[t] += prob[e] / prob[top].sum() * (
                    (a / (1 + np.exp(-a)) * b) @ p["w_down"][j])
    x = rms(x + y, np.asarray(params["norm"], np.float64))
    return x @ np.asarray(params["head"], np.float64), kept


def test_reference_matches_a_tiny_case_written_by_hand():
    params, cfg, ids = _tiny()
    logits, _, keeps, _ = REF.forward(params, ids, cfg)
    want, kept = _by_hand(params, cfg, ids)
    for t, best in enumerate(kept):
        assert sorted(np.flatnonzero(np.asarray(keeps[0][t]))) == best
    assert np.allclose(np.asarray(logits), want, rtol=2e-4, atol=2e-5)


def test_reference_is_float32_highest_and_imports_nothing_of_the_program():
    text = open(os.path.join(cells.BENCH, "reference",
                             "sparse_moe_lm.py")).read()
    assert "deeplearning4j_tpu" not in text.split('"""', 2)[2]
    assert "Precision.HIGHEST" in text and "bfloat16" not in text
    params, cfg, ids = _tiny()
    logits, aux, _, _ = REF.forward(params, ids, cfg)
    assert logits.dtype == jnp.float32 and jnp.asarray(aux).dtype == jnp.float32


@pytest.mark.parametrize("S,index_top_k", [(12, 5), (8, 16)])
def test_needed_form_equals_the_dense_form(S, index_top_k):
    """`forward_needed` (gather of the kept keys, experts over sorted pairs)
    gives the dense form's logits when every held pair is counted."""
    params, cfg, ids = _tiny(seed=3, S=S, index_top_k=index_top_k, n_layers=2)
    dense, _, _, _ = REF.forward(params, ids, cfg)
    cfg = dict(cfg, pairs_counted=S * cfg["top_k"])
    needed = REF.forward_needed(params, ids, cfg, rows_block=4)
    assert np.allclose(np.asarray(needed), np.asarray(dense),
                       rtol=1e-4, atol=1e-5)


def test_given_selection_and_routing_are_used():
    params, cfg, ids = _tiny(seed=5)
    _, _, keeps, routes = REF.forward(params, ids, cfg)
    causal = [jnp.tril(jnp.ones_like(keeps[0]))]
    other = [jnp.roll(routes[0], 1, axis=1) * 0 + jnp.asarray([[0, 3]])]
    base = REF.loss(params, ids, ids, cfg)
    assert float(REF.loss(params, ids, ids, cfg, keeps=keeps,
                          routes=routes)) == pytest.approx(float(base))
    assert float(REF.loss(params, ids, ids, cfg, keeps=causal)) != float(base)
    assert float(REF.loss(params, ids, ids, cfg, routes=other)) != float(base)


def test_operations_counted_for_fit_mfu_by_hand():
    """`harness/flops.py` over `forward_needed` at the real widths, one
    layer, against the count written out from the shapes."""
    sizes = dict(SIZES, num_hidden_layers=1)
    cfg = CONFIG.model_cfg(sizes)
    S, D, V = 8192, 2048, 18992
    H, KV, Dh, IH, ID, K = 32, 4, 128, 16, 64, 2048
    E, Eh, F, TK = 128, 16, 768, 8
    shapes = {"embed": (V, D), "norm": (D,), "head": (D, V), "layers": [{
        "ln1": (D,), "ln2": (D,), "wq": (D, H * Dh), "wk": (D, KV * Dh),
        "wv": (D, KV * Dh), "wo": (H * Dh, D), "q_norm": (Dh,),
        "k_norm": (Dh,), "idx_wq": (D, IH * ID), "idx_wk": (D, ID),
        "idx_w": (D, IH), "idx_k_norm_g": (ID,), "idx_k_norm_b": (ID,),
        "router": (D, E), "w_gate": (Eh, D, F), "w_up": (Eh, D, F),
        "w_down": (Eh, F, D)}]}
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), shapes,
        is_leaf=lambda s: isinstance(s, tuple))
    got = flops.forward_macs(lambda p, i: REF.forward_needed(p, i, cfg),
                             params, jax.ShapeDtypeStruct((S,), jnp.int32))
    projections = S * D * (2 * H * Dh + 2 * KV * Dh)
    causal = sum(256 * 256 * (b + 1) for b in range(S // 256))
    indexer = S * D * (IH * ID + ID + IH) + causal * (ID * IH + IH)
    keys = sum(256 * 256 * (b + 1) for b in range(K // 256)) + (S - K) * K
    attention = 2 * keys * H * Dh
    pairs = S * TK * Eh // E
    experts = S * D * E + 3 * pairs * D * F
    head = S * D * V
    assert got == projections + indexer + attention + experts + head
    # 1,824 keys a query are counted where 1,792.1 are needed, and 4,224
    # index scores where 4,096.5 are (causal blocks of 256 rows): 0.8% of a
    # layer's operations too many. The issue's 85.4 MFLOP a token and layer
    # left out the indexer's three projections (4.5).
    assert keys / S == pytest.approx(1824.0)
    assert causal / S == pytest.approx(4224.0)
    per_token_mflop = 2 * (got - head) / S / 1e6
    assert per_token_mflop == pytest.approx(90.9, abs=0.1)


def test_config_file_keeps_every_published_width():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    entry = next(json.loads(l) for l in open(CATALOG)
                 if json.loads(l)["name"] == "Keye-VL-2.0-30B-A3B")
    assert SIZES["source"] == entry["source_url"] == CONFIG.source
    differs = sorted(k for k, v in entry["config"].items() if SIZES.get(k) != v)
    assert differs == sorted(SIZES["reduced"])
    assert (SIZES["num_hidden_layers"], SIZES["num_local_experts"],
            SIZES["vocab_size"]) == (4, 16, 18992)
    assert SIZES["held"] == {"first_expert": 0, "experts": 16, "first_id": 0,
                             "ids": 18992}
    assert {"qk_norm", "indexer", "mrope", "chunk_sizes", "aux_loss",
            "optimizer", "precision", "data"} <= set(SIZES["assumed"])


def test_program_builds_at_the_published_widths():
    """Shapes only: 465 M parameters, the indexer's 2.26 M a layer frozen."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    shapes = jax.eval_shape(
        lambda: ComputationGraph(CONFIG.make_conf(SIZES, 1)).init().params_tree)
    count = {k: sum(math.prod(a.shape) for a in v.values())
             for k, v in shapes.items()}
    assert sum(count.values()) == 465_391_104
    assert count["ffn0"] == 2048 * 128 + 16 * 3 * 2048 * 768
    assert count["emb"] == count["out"] == 18992 * 2048
    indexer = sum(math.prod(shapes["attn0"][n].shape)
                  for n in ("Wiq", "Wik", "Wiw", "gamma_ik", "beta_ik"))
    assert indexer == 2048 * (1024 + 64 + 16) + 128


HLO = '''
HloModule jit_step_fn

%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  ROOT %add.9 = bf16[8]{0} add(%p, %p), metadata={op_name="jit(step_fn)/jit(main)/dsa.attend/add" source_file="x.py" source_line=1}
}

ENTRY %main (a: bf16[8]) -> bf16[8] {
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/jit(main)/transpose(jvp(dsa.attend))/mul" source_file="x.py"}
  %fusion.2 = bf16[8]{0} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/jit(main)/moe.experts/ragged_dot"}
  %sort.3 = bf16[8]{0} sort(%a), metadata={op_name="jit(step_fn)/jit(main)/dsa.select/while/body/reduce_sum"}
  %custom-call.4 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jit(main)/fused_update_adam"}
  ROOT %fusion.5 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/jit(main)/lm.head/dot_general"}
}
'''


class _Exe:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        if self.text is None:
            raise RuntimeError("no text")
        return self.text


class _Tracer:
    def __init__(self, reduced):
        self._reduced = reduced

    def reduced(self, chips):
        return self._reduced


def _context(executables, events):
    from benchmark.harness import trace_reduce

    busy = sum(e - s for s, e in trace_reduce.merge(
        [(s, s + d) for _, s, d in events])) / 1e9
    reduced = {"busy_s": busy, "window_s": busy * 1.25,
               "events": {0: events}} if events else None
    return {"tracer": _Tracer(reduced), "cell": type("C", (), {"chips": 1}),
            "executables": executables}


# `sort.3` stands for a loop: `fusion.1b`, inside it, is not counted twice
EVENTS = [("fusion.1", 0.0, 4e6), ("fusion.2", 4e6, 1e6), ("sort.3", 5e6, 2e6),
          ("fusion.1", 5.5e6, 1e6),
          ("custom-call.4 [tpu_custom_call]", 7e6, 1e6), ("fusion.5", 8e6, 2e6),
          ("copy.77", 10e6, 10e6)]


@pytest.mark.parametrize("metric,share", [
    ("dsa_time_share.fit", 30.0), ("moe_time_share.fit", 5.0),
    ("lm_head_time_share.fit", 10.0)])
def test_scope_readers_on_a_canned_trace(metric, share):
    read = cells.load_module("layer_metrics", metric).read
    assert read(_context([_Exe(HLO)], EVENTS)) == pytest.approx(share)
    # no trace, no program text, or a program without the scopes (the
    # parent's): nothing to read, and no error
    assert read(_context([_Exe(HLO)], [])) is None
    assert read(_context([_Exe(None)], EVENTS)) is None
    assert read(_context([], EVENTS)) is None
    assert read(_context([_Exe(HLO.replace("dsa.", "x.").replace(
        "moe.", "x.").replace("lm.head", "x"))], EVENTS)) is None


def test_op_names_reads_every_instruction_line():
    names = scope_time.op_names([_Exe(HLO), _Exe(None)])
    assert names["fusion.1"].endswith("transpose(jvp(dsa.attend))/mul")
    assert names["add.9"].endswith("dsa.attend/add")
    assert set(names) == {"add.9", "fusion.1", "fusion.2", "sort.3",
                          "custom-call.4", "fusion.5"}


class _Series:
    def __init__(self, value):
        self.value = value

    def get(self):
        return self.value


class _Family:
    def __init__(self, *values):
        self.values = values

    def children(self):
        return [_Series(v) for v in self.values]


@pytest.mark.parametrize("metric,gauge,want", [
    ("moe_expert_load_max_over_mean", "dl4j_moe_expert_load_max_over_mean",
     1.5),
    ("moe_pairs_held_share", "dl4j_moe_pairs_held_share", 100 * 3.25 / 4)])
def test_gauge_readers_read_their_gauge_and_none_without_it(
        monkeypatch, metric, gauge, want):
    from deeplearning4j_tpu import observability as obs

    read = cells.load_module("layer_metrics", metric).read
    monkeypatch.setattr(obs.metrics, "get_family", lambda name: None)
    assert read({}) is None
    monkeypatch.setattr(
        obs.metrics, "get_family",
        lambda name: _Family(1.0, 1.5, 0.5, 0.25) if name == gauge else None)
    assert read({}) == pytest.approx(want)


RUN = [sys.executable, os.path.join("benchmark", "run.py")]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_drives_the_cell_and_is_never_correct(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        RUN + ["--workload", "keye_vl2_30b_a3b.fit_seq8k", "--seed",
               str(2 ** 31 + 977), "--seconds", "2", "--trace", str(trace),
               "--rehearsal"], cwd=cells.ROOT, env=env, timeout=600,
        capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 8 and line["failed"] == 0
    cell = cells.Cell("keye_vl2_30b_a3b.fit_seq8k")
    if trace:
        sources = {m["name"]: m["source"]
                   for m in cells.manifest()["per_layer"]}
        names = set(cell.metric_names("per_layer"))
        traced = {n for n in names if sources[n] in ("device_trace",
                                                     "program_span")}
        assert set(line["metrics"]) == names - traced - {"fit_mfu"}
        assert line["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1
    else:
        assert set(line["metrics"]) == {"fit_samples_per_s", "setup_s"}
    check = info["reference_check"]
    assert set(check["limits"]) == set(CONFIG.LIMITS)
    assert check["loss_rel_given"] < 1e-3 and check["grad_rel_max"] < 0.1
    assert len(check["grad_rel"]) == 14 and check["positions"] == 64
    assert set(check["update_rel"]) == set(check["grad_rel"])
    assert 0 < check["update_rel_max"] < 0.05
    # the warm-up's 2 + 4 steps and the window's, or the cell's fixed count
    # where the window ended short of it (PR 34)
    assert check["steps_before"] == max(info["steps"] + 2 + 4,
                                        check["at_step"])
    assert set(check["grad_norm"]) == set(check["grad_rel"])
    assert info["loss_last"] < info["loss_first"]


@pytest.fixture(scope="module")
def trained():
    """The rehearsal's net after 24 steps, with what puts it back there (a
    check takes one more step, and the step is donated its state)."""
    built = cells.Cell("keye_vl2_30b_a3b.fit_seq8k", rehearsal=True).build(5)
    net = built["net"]
    for _ in range(12):
        net.fit(built["iterator"])
    saved = jax.tree_util.tree_map(
        np.asarray, (net.params_tree, net.opt_state, net.state))

    def check(fault):
        net.params_tree, net.opt_state, net.state = jax.tree_util.tree_map(
            jnp.asarray, saved)
        net.iteration, net._clock = 24, None
        compiled = len(net._get_jit("train_step").executables())
        numbers = built["reference_check"](fault=fault)["numbers"]
        # the check's step is the compiled step of the 24 before it
        assert len(net._get_jit("train_step").executables()) == compiled
        assert net.iteration == (24 if fault == "state_unchanged" else 25)
        return numbers

    return check, check(None)


def test_sound_check_reads_small_and_the_update_is_the_reference_adam_step(
        trained):
    _, sound = trained
    assert sound["steps_before"] == 24
    assert sound["grad_rel_max"] < 0.05 and sound["update_rel_max"] < 0.02
    assert min(sound["selection_overlap"]) > 0.95


@pytest.mark.parametrize("fault", CONFIG.FAULTS)
def test_planted_fault_moves_its_number_far_from_the_sound_reading(
        trained, fault):
    check, sound = trained
    got = check(fault)
    if fault == "state_unchanged":
        assert set(got["update_rel"].values()) == {1.0}
    elif fault == "half_positions":
        assert min(got["update_rel"].values()) > 5 * sound["update_rel_max"]
        assert got["grad_rel"] == sound["grad_rel"]     # the first pass is sound
    elif fault == "fp8":
        assert got["grad_rel_max"] > 3 * sound["grad_rel_max"]
        assert got["logits_rel"] > 3 * sound["logits_rel"]
    else:
        assert max(got["selection_overlap"]) < 0.7
    with pytest.raises(ValueError, match="unknown fault"):
        CONFIG.reference_check(None, SIZES, None, fault="bf16")


def test_norm_scales_reach_the_forward_pass_as_stored():
    """Under `mixed_bfloat16` every matrix is cast to bfloat16 at use and a
    norm's scale is not: 1 + 2^-10 reads 1 in bfloat16, and a fine-tune's
    change to a scale would never be computed with."""
    from deeplearning4j_tpu.nn import params as params_mod
    from deeplearning4j_tpu.nn.conf.layers import (
        DenseLayer, RMSNormalization, SelfAttentionLayer)

    fine = jnp.asarray([1.0 + 2.0 ** -10, 3.0], jnp.float32)
    norm = params_mod.prep_layer_params(
        {"gamma": fine}, jnp.bfloat16, layer=RMSNormalization(n_out=2))
    assert norm["gamma"].dtype == jnp.float32
    attn = params_mod.prep_layer_params(
        {"gamma_q": fine, "Wq": fine[None]}, jnp.bfloat16,
        layer=SelfAttentionLayer(n_out=2, n_heads=1, head_dim=2))
    assert attn["gamma_q"].dtype == jnp.float32
    assert attn["Wq"].dtype == jnp.bfloat16
    dense = params_mod.prep_layer_params(
        {"W": fine[None], "b": fine}, jnp.bfloat16, layer=DenseLayer(n_out=2))
    assert {v.dtype for v in dense.values()} == {jnp.dtype(jnp.bfloat16)}
    from deeplearning4j_tpu.nn.layers import dsa

    x = jnp.asarray([[1.0, -1.0]], jnp.bfloat16)
    assert float(dsa.rms_norm(x, norm["gamma"], 0.0)[0, 0]) == 1.0
    wide = dsa.rms_norm(x.astype(jnp.float32), norm["gamma"], 0.0)
    assert float(wide[0, 0]) == 1.0 + 2.0 ** -10


def test_reference_adam_update_by_hand():
    g, m, v = (jnp.asarray([0.5, -2.0, 0.0]), jnp.asarray([0.1, 0.0, -0.3]),
               jnp.asarray([0.04, 1.0, 0.09]))
    got = np.asarray(REF.adam_update(g, m, v, 3, 0.01, 0.9, 0.95))
    m1 = 0.9 * np.asarray(m) + 0.1 * np.asarray(g)
    v1 = 0.95 * np.asarray(v) + 0.05 * np.asarray(g) ** 2
    want = -0.01 * (m1 / (1 - 0.9 ** 3)) / (
        np.sqrt(v1 / (1 - 0.95 ** 3)) + 1e-8)
    assert np.allclose(got, want, rtol=1e-6)
    assert got[0] < 0 < got[2]       # against the moment where there is one


def test_new_cells_name_files_that_exist_and_only_the_new_cell_has_a_check():
    manifest = cells.manifest()
    by_name = {w["name"]: w for w in manifest["workloads"]}
    assert by_name["keye_vl2_30b_a3b.fit_seq8k"]["chips"] == 1
    spec = cells.load_json("workloads", "keye_vl2_30b_a3b.fit_seq8k")
    assert spec["driver"] == "fit_ref" and spec["check"]["fault"] is None
    # no pin on the whole list (it held that day's two cells and failed once
    # a third existed): the two come first, in their order, and every list
    # that named this cell names it before any later one
    assert list(by_name)[:2] == ["resnet50_b256.fit_cached",
                                 "keye_vl2_30b_a3b.fit_seq8k"]
    new = {"dsa_time_share.fit", "moe_time_share.fit",
           "lm_head_time_share.fit", "moe_expert_load_max_over_mean",
           "moe_pairs_held_share"}
    for m in manifest["per_layer"]:
        if m["name"] in new:
            assert m["workloads"][0] == "keye_vl2_30b_a3b.fit_seq8k"
            assert "resnet50_b256.fit_cached" not in m["workloads"]
            assert m["moves"] == "fit_samples_per_s"
