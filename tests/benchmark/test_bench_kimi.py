"""The `kimi_vl_a3b` configuration's benchmark files: the plain reference
against a tiny case written out by hand, its two forms against each other,
the cell's rehearsal as a command, the five new readers on a canned trace,
`latent_costs` against a brute-force count, the count of operations
`fit_mfu` is computed from, the manifest's older entries in their places,
and the check's planted faults."""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import manifest_checks
from benchmark.harness import cells, fit_check, flops, kernel_costs
from benchmark.harness import latent_costs

CELL = "kimi_vl_a3b.fit_seq8k"
REF = cells.load_module("reference", "mla_moe_lm")
CONFIG = cells.load_module("configs", "kimi_vl_a3b")
SIZES = cells.load_json("configs", "kimi_vl_a3b")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
RUN = [sys.executable, os.path.join("benchmark", "run.py")]


def _tiny(seed=0, S=10, D=8, H=2, R=6, Dn=4, Dr=2, Dv=3, E=4, Eh=2, F=5,
          Fd=7, Fs=6, V=9, top_k=2):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)

    def attn():
        return {"ln1": w(D) + 1, "ln2": w(D) + 1, "wq": w(D, H * (Dn + Dr)),
                "wdkv": w(D, R + Dr), "kv_norm": w(R) + 1,
                "wukv": w(R, H * (Dn + Dv)), "wo": w(H * Dv, D)}

    def expert_layer():
        return dict(attn(), router=w(D, E), router_bias=w(E) * 0.5,
                    w_gate=w(Eh, D, F), w_up=w(Eh, D, F), w_down=w(Eh, F, D),
                    ws_gate=w(D, Fs), ws_up=w(D, Fs), ws_down=w(Fs, D))

    params = {"embed": w(V, D),
              "dense": dict(attn(), w_gate=w(D, Fd), w_up=w(D, Fd),
                            w_down=w(Fd, D)),
              "layers": [expert_layer(), expert_layer()],
              "norm": w(D) + 1, "head": w(D, V)}
    cfg = {"n_heads": H, "kv_lora_rank": R, "qk_nope_head_dim": Dn,
           "qk_rope_head_dim": Dr, "v_head_dim": Dv, "rms_eps": 1e-5,
           "kv_norm_eps": 1e-6, "rope_theta": 1e4, "n_experts": E,
           "top_k": top_k, "n_group": 1, "topk_group": 1, "first_expert": 1,
           "norm_topk_prob": True, "routed_scaling_factor": 2.446,
           "aux_coef": 0.01}
    ids = jnp.asarray(rng.integers(0, V, S), jnp.int32)
    return params, cfg, ids


def _by_hand(params, cfg, ids):
    """The module docstring's equations in numpy float64, one position, one
    head and one expert at a time. Returns (logits, aux)."""
    f = lambda a: np.asarray(a, np.float64)
    H, R = cfg["n_heads"], cfg["kv_lora_rank"]
    Dn, Dr, Dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    S = len(ids)

    def rms(x, g, eps):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * f(g)

    def turn(x, t):
        a = t * cfg["rope_theta"] ** (-np.arange(Dr // 2) * 2.0 / Dr)
        x1, x2 = x[: Dr // 2], x[Dr // 2:]
        return np.concatenate([x1 * np.cos(a) - x2 * np.sin(a),
                               x2 * np.cos(a) + x1 * np.sin(a)])

    def mlp(h, g, u, d):
        a = h @ f(g)
        return (a / (1 + np.exp(-a)) * (h @ f(u))) @ f(d)

    def attend(p, x):
        h = rms(x, p["ln1"], cfg["rms_eps"])
        q = (h @ f(p["wq"])).reshape(S, H, Dn + Dr)
        ckv = h @ f(p["wdkv"])
        c = rms(ckv[:, :R], p["kv_norm"], cfg["kv_norm_eps"])
        kv = (c @ f(p["wukv"])).reshape(S, H, Dn + Dv)
        k_r = np.stack([turn(ckv[t, R:], t) for t in range(S)])
        o = np.zeros((S, H, Dv))
        for t in range(S):
            for head in range(H):
                q_r = turn(q[t, head, Dn:], t)
                s = np.asarray([q[t, head, :Dn] @ kv[u, head, :Dn]
                                + q_r @ k_r[u] for u in range(t + 1)]) \
                    / math.sqrt(Dn + Dr)
                w = np.exp(s - s.max())
                o[t, head] = (w / w.sum()) @ kv[:t + 1, head, Dn:]
        return x + o.reshape(S, H * Dv) @ f(p["wo"])

    x = f(params["embed"])[np.asarray(ids)]
    p = params["dense"]
    x = attend(p, x)
    x = x + mlp(rms(x, p["ln2"], cfg["rms_eps"]), p["w_gate"], p["w_up"],
                p["w_down"])
    aux, E, K = 0.0, cfg["n_experts"], cfg["top_k"]
    for p in params["layers"]:
        x = attend(p, x)
        h2 = rms(x, p["ln2"], cfg["rms_eps"])
        s = 1 / (1 + np.exp(-(h2 @ f(p["router"]))))
        y, counts = np.zeros_like(x), np.zeros(E)
        for t in range(S):
            top = np.argsort(-(s[t] + f(p["router_bias"])),
                             kind="stable")[:K]
            counts[top] += 1
            for e in top:
                j = e - cfg["first_expert"]
                if 0 <= j < p["w_gate"].shape[0]:
                    y[t] += cfg["routed_scaling_factor"] * s[t, e] \
                        / s[t, top].sum() * mlp(h2[t], p["w_gate"][j],
                                                p["w_up"][j], p["w_down"][j])
        aux += np.sum(counts * E / (K * S)
                      * np.mean(s / s.sum(-1, keepdims=True), axis=0))
        x = x + y + mlp(h2, p["ws_gate"], p["ws_up"], p["ws_down"])
    return rms(x, params["norm"], cfg["rms_eps"]) @ f(params["head"]), aux


def test_reference_matches_the_equations_written_out_by_hand():
    params, cfg, ids = _tiny()
    logits, aux, routed = REF.forward(params, ids, cfg)
    want, want_aux = _by_hand(params, cfg, ids)
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-5)
    assert float(aux) == pytest.approx(want_aux, rel=1e-5)
    assert len(routed) == 2 and routed[0].shape == (len(ids), 2)
    # the stacked form (what the check scans) is the same loop
    stacked = fit_check.stack_layers(params)
    logits_s, aux_s, routed_s = REF.forward(stacked, ids, cfg)
    np.testing.assert_allclose(logits_s, logits, rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(routed_s), np.stack(routed))
    labels = jnp.roll(ids, -1)
    assert float(REF.loss(params, ids, labels, cfg, remat=True)) \
        == pytest.approx(float(REF.loss(params, ids, labels, cfg)), rel=1e-6)


def test_reference_is_float32_highest_and_imports_nothing_of_the_program():
    text = open(REF.__file__).read()
    assert "import deeplearning4j_tpu" not in text
    assert "from deeplearning4j_tpu" not in text
    assert "from benchmark" not in text and "import benchmark" not in text
    assert "Precision.HIGHEST" in text and "pallas" not in text
    params, cfg, ids = _tiny()
    assert REF.forward(params, ids, cfg)[0].dtype == jnp.float32


def test_needed_form_equals_the_dense_form():
    params, cfg, ids = _tiny(S=12)
    logits, _, _ = REF.forward(params, ids, cfg)
    # every pair counted: all experts' pairs are within the counted prefix
    cfg_all = dict(cfg, pairs_counted=12 * 2)
    for rows_block in (4, 5, 12):
        np.testing.assert_allclose(
            REF.forward_needed(params, ids, cfg_all, rows_block=rows_block),
            logits, rtol=1e-5, atol=1e-5)


def test_given_routing_is_used_and_the_bias_only_moves_the_choice():
    params, cfg, ids = _tiny()
    _, _, own = REF.forward(params, ids, cfg)
    given = [jnp.flip(r, axis=1) for r in own]
    again, _, used = REF.forward(params, ids, cfg, routes=given)
    assert all(np.array_equal(u, g) for u, g in zip(used, given))
    np.testing.assert_allclose(again, REF.forward(params, ids, cfg)[0],
                               rtol=1e-5, atol=1e-6)
    other = [(r + 1) % cfg["n_experts"] for r in own]
    moved, _, _ = REF.forward(params, ids, cfg, routes=other)
    assert float(jnp.max(jnp.abs(moved - again))) > 1e-3
    # another bias, the same routes given: the same logits to the bit
    no_bias = dict(params, layers=[dict(p, router_bias=p["router_bias"] * 0)
                                   for p in params["layers"]])
    same, _, _ = REF.forward(no_bias, ids, cfg, routes=own)
    assert np.array_equal(np.asarray(same),
                          np.asarray(REF.forward(params, ids, cfg,
                                                 routes=own)[0]))
    assert not all(np.array_equal(np.sort(a, 1), np.sort(b, 1)) for a, b in
                   zip(REF.forward(no_bias, ids, cfg)[2], own))
    grads = jax.grad(REF.loss)(params, ids, jnp.roll(ids, -1), cfg)
    assert all(float(jnp.abs(p["router_bias"]).max()) == 0.0
               for p in grads["layers"])


@pytest.mark.parametrize("nope,rope,v", [(128, 64, 128), (4, 2, 3),
                                         (16, 0, 16)])
def test_latent_costs_against_a_brute_force_count(nope, rope, v):
    """The nine passes written out: which operands each product contracts
    over."""
    passes = [nope + rope, v,                      # forward: q k^T, p v
              nope + rope, v, nope + rope,         # dq: q k^T, do v^T, ds k
              nope + rope, v, v, nope + rope]      # dk/dv: k q^T, p^T do,
    #                                                v do^T, ds^T q
    assert latent_costs.pair_flops(nope, rope, v) == sum(2 * p for p in passes)
    S, H, L = 24, 3, 2
    pairs = sum(1 for t in range(S) for s in range(S) if s <= t)
    sizes = {"seq_len": S, "batch_per_chip": 2, "num_hidden_layers": L,
             "num_attention_heads": H, "qk_nope_head_dim": nope,
             "qk_rope_head_dim": rope, "v_head_dim": v}
    assert latent_costs.attention_step_flops(sizes) \
        == 2 * L * H * pairs * sum(2 * p for p in passes)


def test_attention_flops_of_a_step_at_the_published_sizes():
    """2,944 FLOP a pair and head, 33.56 M pairs, 16 heads: 1.58 TFLOP a
    layer, 7.9 a step of five layers; padded to 256 it would be 4,608."""
    assert latent_costs.pair_flops(128, 64, 128) == 2944
    assert 9 * 2 * 256 == 4608
    pairs = kernel_costs.band_pairs(8192)
    assert pairs == 8192 * 8193 // 2 == 33_558_528
    assert latent_costs.attention_step_flops(SIZES) == 5 * 16 * pairs * 2944
    assert latent_costs.attention_step_flops(SIZES) / 1e12 \
        == pytest.approx(7.90, abs=0.01)


def test_operations_counted_for_fit_mfu_by_hand():
    """`harness/flops.py` over `forward_needed` at the real widths against
    the count written out from the shapes."""
    cfg = CONFIG.model_cfg(SIZES)
    S, D, V = 8192, 2048, 20480
    H, R, Dn, Dr, Dv = 16, 512, 128, 64, 128
    E, Eh, F, TK, Fd, Fs = 64, 8, 1408, 6, 11264, 2816
    attn = {"ln1": (D,), "ln2": (D,), "wq": (D, H * (Dn + Dr)),
            "wdkv": (D, R + Dr), "kv_norm": (R,),
            "wukv": (R, H * (Dn + Dv)), "wo": (H * Dv, D)}
    layer = dict(attn, router=(D, E), router_bias=(E,), w_gate=(Eh, D, F),
                 w_up=(Eh, D, F), w_down=(Eh, F, D), ws_gate=(D, Fs),
                 ws_up=(D, Fs), ws_down=(Fs, D))
    shapes = {"embed": (V, D), "norm": (D,), "head": (D, V),
              "dense": dict(attn, w_gate=(D, Fd), w_up=(D, Fd),
                            w_down=(Fd, D)),
              "layers": [layer] * 4}
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), shapes,
        is_leaf=lambda s: isinstance(s, tuple))
    got = flops.forward_macs(lambda p, i: REF.forward_needed(p, i, cfg),
                             params, jax.ShapeDtypeStruct((S,), jnp.int32))
    projections = S * (D * H * (Dn + Dr) + D * (R + Dr)
                       + R * H * (Dn + Dv) + H * Dv * D)
    blocks = sum(256 * 256 * (b + 1) for b in range(S // 256))
    core = blocks * H * (Dn + Dr + Dv)
    pairs = S * TK * Eh // E
    experts = S * D * E + 3 * pairs * D * F + 3 * S * D * Fs
    dense, head = 3 * S * D * Fd, S * D * V
    assert got == 5 * (projections + core) + 4 * experts + dense + head
    # causal blocks of 256 rows count 128 keys a row too many
    assert blocks / kernel_costs.band_pairs(S) == pytest.approx(1.0311,
                                                                abs=1e-3)
    # 18.9 TFLOP a step under an even router (an eighth of the pairs held)
    assert 3 * 2 * got / 1e12 == pytest.approx(18.86, abs=0.05)
    # the attention core: 6 of the 9 passes' worth a forward pass counts
    # (2 products, times 3), over blocks 3.1% larger than the band
    assert 3 * 2 * 5 * core / 1e12 == pytest.approx(5.32, abs=0.02)


def test_config_file_keeps_every_published_width():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    entry = next(json.loads(l) for l in open(CATALOG)
                 if json.loads(l)["name"] == "Kimi-VL-A3B-Instruct")
    assert SIZES["source"] == entry["source_url"] == CONFIG.source
    differs = sorted(k for k, v in entry["config"].items()
                     if k not in SIZES or SIZES[k] != v)
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert sorted(SIZES["reduced"]) == ["num_hidden_layers",
                                        "num_local_experts", "vocab_size"]
    assert (SIZES["num_hidden_layers"], SIZES["num_local_experts"],
            SIZES["vocab_size"], SIZES["n_routed_experts"]) == (5, 8, 20480,
                                                                64)
    assert (SIZES["hidden_size"], SIZES["num_attention_heads"],
            SIZES["kv_lora_rank"], SIZES["qk_nope_head_dim"],
            SIZES["qk_rope_head_dim"], SIZES["v_head_dim"],
            SIZES["intermediate_size"], SIZES["moe_intermediate_size"],
            SIZES["n_shared_experts"], SIZES["num_experts_per_tok"],
            SIZES["routed_scaling_factor"], SIZES["rope_theta"]) == (
        2048, 16, 512, 128, 64, 128, 11264, 1408, 2, 6, 2.446, 800000)
    assert SIZES["held"] == {"first_expert": 0, "experts": 8, "first_id": 0,
                             "ids": 20480}
    assert {"kv_norm_eps", "rotary", "softmax_scale", "aux_loss",
            "router_bias", "optimizer", "precision", "loss", "data",
            "init"} <= set(SIZES["assumed"])
    assert "8 chips share each layer" in SIZES["deployment"]
    assert "first of the eight" in SIZES["deployment"]
    assert "vision tower" in SIZES["absent"]
    with pytest.raises(ValueError, match="1 chip"):
        CONFIG.build(SIZES, 1, 4)


def test_program_builds_at_the_published_widths():
    """Shapes only: 568.5 M parameters, 13.76 M of attention a layer."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = CONFIG.make_conf(SIZES, 1)
    shapes = jax.eval_shape(lambda: ComputationGraph(conf).init().params_tree)
    count = {k: sum(math.prod(a.shape) for a in v.values())
             for k, v in shapes.items()}
    attention = 2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256 \
        + 2048 * 2048
    assert count["attn0"] == count["attn4"] == attention == 13_763_072
    assert count["ffn0"] == 3 * 2048 * 11264
    assert count["ffn1"] == count["ffn4"] == 2048 * 64 + 64 \
        + 8 * 3 * 2048 * 1408 + 3 * 2048 * 2816
    assert count["emb"] == count["out"] == 20480 * 2048
    assert sum(count.values()) == 568_484_608
    assert round(sum(count.values()) * 16 / 1e9, 2) == 9.1
    mla = conf.vertices["attn3"].layer
    assert (mla.kv_lora_rank, mla.qk_nope_head_dim, mla.qk_rope_head_dim,
            mla.v_head_dim, mla.rope_theta) == (512, 128, 64, 128, 8e5)
    moe = conf.vertices["ffn2"].layer
    assert (moe.n_experts, moe.top_k, moe.experts_held, moe.scoring,
            moe.routed_scaling_factor, moe.shared_hidden,
            moe.aux_loss_weight) == (64, 6, (0, 8), "sigmoid", 2.446, 2816,
                                     0.001)


HLO = '''
HloModule jit_step_fn

ENTRY %main (a: bf16[8]) -> bf16[8] {
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/jvp(mla.project)/dot_general"}
  %custom-call.2 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jit(main)/jvp(mla.attend)/latent_attention_fwd/pallas_call"}
  %fusion.3 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/jvp(moe.shared)/dot_general"}
  %fusion.4 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/transpose(jvp(ffn.dense))/dot_general"}
  %custom-call.5 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jit(main)/transpose(jvp(mla.attend))/latent_attention_dkv/pallas_call"}
  ROOT %fusion.6 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/lm.head/dot_general"}
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
    return {"tracer": _Tracer(reduced), "executables": executables,
            "cell": type("C", (), {"chips": 1, "sizes": SIZES})}


def _step(t0):
    """One step's events from `t0` ms on: 100 ms in all, 15 under
    `mla.project`, 10 + 25 under `mla.attend`, 8 under `moe.shared`, 12
    under `ffn.dense`, the rest elsewhere."""
    ms = 1e6
    return [("fusion.1", (t0 + 0) * ms, 15 * ms),
            ("custom-call.2 [tpu_custom_call]", (t0 + 15) * ms, 10 * ms),
            ("fusion.3", (t0 + 25) * ms, 8 * ms),
            ("fusion.4", (t0 + 33) * ms, 12 * ms),
            ("custom-call.5 [tpu_custom_call]", (t0 + 45) * ms, 25 * ms),
            ("fusion.6", (t0 + 70) * ms, 30 * ms)]


EVENTS = _step(0) + _step(100) + _step(200)
PARENTS = HLO.replace("mla.", "attn.").replace("moe.shared", "moe.x") \
    .replace("ffn.dense", "ffn")


@pytest.mark.parametrize("metric,share", [
    ("mla_time_share.fit", 35.0), ("mla_project_time_share.fit", 15.0),
    ("moe_shared_time_share.fit", 8.0), ("dense_ffn_time_share.fit", 12.0)])
def test_scope_readers_on_a_canned_trace(metric, share):
    read = cells.load_module("layer_metrics", metric).read
    assert read(_context([_Exe(HLO)], EVENTS)) == pytest.approx(share)
    # no trace, no program text, or a program without the scopes (the
    # parent's): nothing to read, and no error
    assert read(_context([_Exe(HLO)], [])) is None
    assert read(_context([_Exe(None)], EVENTS)) is None
    assert read(_context([], EVENTS)) is None
    assert read(_context([_Exe(PARENTS)], EVENTS)) is None


def test_roofline_reader_divides_the_needed_flops_by_the_scoped_time(
        monkeypatch):
    from benchmark.harness import device

    read = cells.load_module("layer_metrics",
                             "latent_attention_roofline.fit").read
    context = _context([_Exe(HLO)], EVENTS)
    assert read(context) is None          # the CPU has no published peak
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(device.CHIP_PEAKS, kind, (197e12, 819e9, "test"))
    # three steps (the heaviest scoped instruction ran three times), 35 ms
    # of `mla.attend` each
    want = 100 * 3 * latent_costs.attention_step_flops(SIZES) / (
        0.105 * 197e12)
    assert read(context) == pytest.approx(want)
    assert read(_context([_Exe(PARENTS)], EVENTS)) is None
    assert read(_context([_Exe(HLO)], [])) is None
    assert read(_context([_Exe(None)], EVENTS)) is None


def test_the_cell_reports_what_the_issue_lists():
    """The metrics the cell's issue listed are among its own, its siblings'
    own are not, and the five it brought name it first and no older cell
    (`manifest_checks.kimi_reports`): no count, so a later metric may list
    it."""
    manifest_checks.kimi_reports(cells.manifest(), cells.BENCH)
    spec = cells.load_json("workloads", CELL)
    assert spec["traffic"] == {"kind": "fit_seq8k", "epochs_per_sync": 4,
                               "trace_seconds": 4.0}
    assert spec["driver"] == "fit_ref" and spec["check"]["fault"] is None
    assert spec["chips"] == 1


def test_the_older_cells_keep_their_entries_and_come_first():
    """The three older cells and configurations are the first three entries
    in their order, and every entry keeps its place and what it said, its
    lists growing at their ends only
    (`manifest_checks.older_entries_in_their_places`)."""
    manifest_checks.older_entries_in_their_places(cells.manifest(),
                                                  cells.BENCH)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_drives_the_cell_and_is_never_correct(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        RUN + ["--workload", CELL, "--seed", str(2 ** 31 + 977), "--seconds",
               "2", "--trace", str(trace), "--rehearsal"], cwd=cells.ROOT,
        env=env, timeout=600, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 8 and line["failed"] == 0
    cell = cells.Cell(CELL)
    if trace:
        sources = {m["name"]: m["source"]
                   for m in cells.manifest()["per_layer"]}
        names = set(cell.metric_names("per_layer"))
        traced = {n for n in names if sources[n] in ("device_trace",
                                                     "program_span")}
        assert set(line["metrics"]) == names - traced - {"fit_mfu"}
    else:
        assert set(line["metrics"]) == {"fit_samples_per_s", "setup_s"}
    check = info["reference_check"]
    assert set(check["limits"]) == set(CONFIG.LIMITS)
    assert check["loss_rel_given"] < 1e-3 and check["grad_rel_max"] < 0.1
    assert len(check["grad_rel"]) == 27 and check["positions"] == 64
    assert {"attn0.Wq", "attn0.Wukv", "ffn0.W_down", "attn1.Wdkv",
            "attn2.Wo", "ffn1.gate_w", "ffn2.w_down", "ffn2.shared_gate",
            "emb.W", "out.W"} <= set(check["grad_rel"])
    assert set(check["update_rel"]) == set(check["grad_rel"])
    assert 0 < check["update_rel_max"] < 0.05
    assert len(check["routing_agreement"]) == 2     # the expert layers
    # the warm-up's 2 + 4 steps and the window's, or the cell's fixed count
    # where the window ended short of it (PR 34)
    assert check["steps_before"] == max(info["steps"] + 2 + 4,
                                        check["at_step"])
    assert info["loss_last"] < info["loss_first"]
    gauges = info["layer_gauges"]["dl4j_moe_pairs_held_share"]
    assert {"ffn1", "ffn2"} <= set(gauges)


@pytest.fixture(scope="module")
def trained():
    """The rehearsal's net after 24 steps, with what puts it back there (a
    check takes one more step, and the step is donated its state)."""
    from deeplearning4j_tpu.nn.layers import dsa
    from deeplearning4j_tpu.parallel import expert

    built = cells.Cell(CELL, rehearsal=True).build(5)
    net = built["net"]
    for _ in range(12):
        net.fit(built["iterator"])
    saved = jax.tree_util.tree_map(
        np.asarray, (net.params_tree, net.opt_state, net.state))
    sound_fns = dsa.rms_norm, expert.route_top_k

    def check(fault):
        net.params_tree, net.opt_state, net.state = jax.tree_util.tree_map(
            jnp.asarray, saved)
        net.iteration, net._clock = 24, None
        compiled = len(net._get_jit("train_step").executables())
        numbers = built["reference_check"](fault=fault)["numbers"]
        # the check's step is the compiled step of the 24 before it
        assert len(net._get_jit("train_step").executables()) == compiled
        assert net.iteration == (24 if fault == "state_unchanged" else 25)
        # a first-pass fault is undone before anything else reads the layers
        assert (dsa.rms_norm, expert.route_top_k) == sound_fns
        for i in (1, 2):
            layer = net.layer_vertices[f"ffn{i}"].layer
            assert layer.routed_scaling_factor == 2.446
        return numbers

    return check, check(None)


def test_sound_check_reads_small_and_the_update_is_the_reference_adam_step(
        trained):
    _, sound = trained
    assert sound["steps_before"] == 24 and sound["fault"] is None
    assert sound["grad_rel_max"] < 0.05 and sound["update_rel_max"] < 0.02
    assert min(sound["routing_agreement"]) > 0.9


@pytest.mark.parametrize("fault", fit_check.FAULTS + CONFIG.FAULTS)
def test_planted_fault_moves_its_number_far_from_the_sound_reading(
        trained, fault):
    check, sound = trained
    got = check(fault)
    assert got["fault"] == fault
    if fault == "state_unchanged":
        assert set(got["update_rel"].values()) == {1.0}
        assert got["grad_rel"] == sound["grad_rel"]     # the first pass is sound
    elif fault == "fp8":
        assert got["grad_rel_max"] > 3 * sound["grad_rel_max"]
        assert got["logits_rel"] > 3 * sound["logits_rel"]
    elif fault == "no_router_bias":
        # the choice moves: the routing disagrees, and the rest (compared
        # under the program's routing) stays sound
        assert min(got["routing_agreement"]) \
            < min(sound["routing_agreement"]) - 0.05
        assert got["loss_rel"] > 3 * sound["loss_rel"] \
            or got["loss_rel"] > 1e-3
    elif fault == "bias_in_weights":
        # a bias of 0.05 beside scores near 0.5 moves a weight by a tenth:
        # the router's own gradient says so first
        for leaf in ("ffn1.gate_w", "ffn2.gate_w"):
            assert got["grad_rel"][leaf] > 2 * sound["grad_rel"][leaf]
        assert got["logits_rel"] > 2 * sound["logits_rel"]
    else:
        assert got["logits_rel"] > 3 * sound["logits_rel"]
        assert got["loss_rel_given"] > 3 * sound["loss_rel_given"]
    with pytest.raises(ValueError, match="unknown fault"):
        CONFIG.reference_check(None, SIZES, None, fault="bf16")
