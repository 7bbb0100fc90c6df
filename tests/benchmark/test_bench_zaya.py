"""The `zaya1_8b` configuration's benchmark files: the plain reference against
one layer written out by hand, its two forms against each other, the
configuration against the catalog's published widths, the count of
operations `fit_mfu` is computed from, the four new readers on a canned
trace, `cca_costs` at the published sizes, the manifest's new entries and its
older ones in their places, the cell's rehearsal as a command, and the
check's planted faults."""

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
from benchmark.harness import cca_costs, cells, fit_check, flops, kernel_costs

CELL = "zaya1_8b.fit_seq8k"
REF = cells.load_module("reference", "cca_moe_lm")
CONFIG = cells.load_module("configs", "zaya1_8b")
SIZES = cells.load_json("configs", "zaya1_8b")
RUN = [sys.executable, os.path.join("benchmark", "run.py")]
NEW = {"cca_time_share.fit", "cca_conv_time_share.fit",
       "cca_attention_roofline.fit", "moe_skip_share"}
JOINED = {"fit_samples_per_s", "fit_dispatch_mean_ms", "hbm_gb_per_step.fit",
          "fit_mfu", "pallas_time_share.fit", "device_idle_share.fit",
          "fit_enqueue_mean_ms", "fit_dispatch_self_mean_ms",
          "idle_in_dispatch_share.fit", "idle_in_input_wait_share.fit",
          "idle_unnamed_share.fit", "unscoped_time_share.fit",
          "updater_time_share.fit", "moe_time_share.fit",
          "lm_head_time_share.fit", "moe_expert_load_max_over_mean",
          "moe_pairs_held_share", "grouped_product_time_share.fit"}


def _tiny(seed=0, S=10, D=8, H=2, KV=1, Dh=4, R=6, E=3, Eh=2, F=5, V=9):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)

    G = H + KV

    def layer(eda):
        p = {"ln1": w(D) + 1, "ln2": w(D) + 1, "wq": w(D, H * Dh),
             "wk": w(D, KV * Dh), "wv1": w(D, KV * Dh // 2),
             "wv2": w(D, KV * Dh // 2), "conv0_w": w(2, G * Dh),
             "conv0_b": w(G * Dh), "conv1_w": w(G, 2, Dh, Dh),
             "conv1_b": w(G * Dh), "temp": w(KV) + 1, "wo": w(H * Dh, D),
             "alpha_a": w(D) + 1, "beta_a": w(D) + 1, "alpha_f": w(D) + 1,
             "beta_f": w(D) + 1, "router_down": w(D, R), "router_w1": w(R, R),
             "router_w2": w(R, R), "router_w3": w(R, E + 1),
             "router_bias": jnp.zeros((E + 1,), jnp.float32),
             "w_gate": w(Eh, D, F), "w_up": w(Eh, D, F), "w_down": w(Eh, F, D)}
        if eda:
            p["eda"] = w(R)
        return p

    params = {"embed": w(V, D), "first": layer(False),
              "layers": [layer(True), layer(True)], "norm": w(D) + 1}
    cfg = {"n_heads": H, "n_kv_heads": KV, "head_dim": Dh, "rotary_dim": 2,
           "rope_theta": 1e4, "rms_eps": 1e-5, "n_experts": E, "top_k": 1,
           "first_expert": 1, "aux_coef": 0.01}
    ids = jnp.asarray(rng.integers(0, V, S), jnp.int32)
    return params, cfg, ids


def _layer_by_hand(p, x, prev, cfg):
    """One layer in float64 loops over positions, heads and experts."""
    f = lambda a: np.asarray(a, np.float64)
    S, D = x.shape
    H, KV, Dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    G, Rr = H + KV, cfg["rotary_dim"]
    rms = lambda v, g: v / np.sqrt(np.mean(v * v, -1, keepdims=True)
                                   + cfg["rms_eps"]) * f(g)
    h = rms(x, p["ln1"])
    u = np.concatenate([h @ f(p["wq"]), h @ f(p["wk"])], -1).reshape(S, G, Dh)
    prev_row = lambda a, t: a[t - 1] if t else np.zeros_like(a[0])
    w0 = f(p["conv0_w"]).reshape(2, G, Dh)
    c = np.stack([w0[0] * u[t] + w0[1] * prev_row(u, t) for t in range(S)]) \
        + f(p["conv0_b"]).reshape(G, Dh)
    c_hat = np.stack([np.stack([
        c[t, g] @ f(p["conv1_w"])[g, 0] + prev_row(c, t)[g]
        @ f(p["conv1_w"])[g, 1] for g in range(G)]) for t in range(S)]) \
        + f(p["conv1_b"]).reshape(G, Dh)
    q_t, k_t = u[:, :H], u[:, H:]
    q = np.stack([c_hat[:, j] + 0.5 * (q_t[:, j] + k_t[:, j // (H // KV)])
                  for j in range(H)], 1)
    k = np.stack([c_hat[:, H + g] + 0.5 * (
        q_t[:, g * (H // KV):(g + 1) * (H // KV)].mean(1) + k_t[:, g])
        for g in range(KV)], 1)
    norm = lambda a: a / np.sqrt(np.sum(a * a, -1, keepdims=True) + 1e-6) \
        * np.sqrt(Dh)
    q, k = norm(q), norm(k) * f(p["temp"])[:, None]
    inv = cfg["rope_theta"] ** (-np.arange(0, Rr, 2) / Rr)
    for t in range(S):
        for a in (q, k):
            cos, sin = np.cos(t * inv), np.sin(t * inv)
            x1, x2 = a[t, :, :Rr // 2].copy(), a[t, :, Rr // 2:Rr].copy()
            a[t, :, :Rr // 2] = x1 * cos - x2 * sin
            a[t, :, Rr // 2:Rr] = x2 * cos + x1 * sin
    v1, v2 = h @ f(p["wv1"]), h @ f(p["wv2"])
    v = np.stack([np.concatenate([v1[t].reshape(KV, -1), prev_row(v2, t)
                                  .reshape(KV, -1)], -1) for t in range(S)])
    o = np.zeros((S, H, Dh))
    for j in range(H):
        g = j // (H // KV)
        for t in range(S):
            s = np.array([q[t, j] @ k[r, g] for r in range(t + 1)]) / np.sqrt(Dh)
            e = np.exp(s - s.max())
            o[t, j] = (e / e.sum()) @ v[:t + 1, g]
    x = f(p["alpha_a"]) * x + f(p["beta_a"]) * (o.reshape(S, -1) @ f(p["wo"]))
    h2 = rms(x, p["ln2"])
    r = h2 @ f(p["router_down"])
    if prev is not None:
        lam = 1 / (1 + np.exp(-f(p["eda"])))
        r = (1 - lam) * r + lam * prev
    gelu = lambda a: 0.5 * a * (1 + np.vectorize(math.erf)(a / np.sqrt(2)))
    z = gelu(gelu(r @ f(p["router_w1"])) @ f(p["router_w2"])) @ f(
        p["router_w3"])
    P = np.exp(z - z.max(-1, keepdims=True))
    P = P / P.sum(-1, keepdims=True)
    m = np.zeros_like(x)
    for t in range(S):
        e = int(np.argmax(z[t]))
        j = e - cfg["first_expert"]
        if 0 <= j < p["w_gate"].shape[0]:
            a = h2[t] @ f(p["w_gate"])[j]
            m[t] = P[t, e] * ((a / (1 + np.exp(-a))) * (h2[t] @ f(
                p["w_up"])[j])) @ f(p["w_down"])[j]
    E = cfg["n_experts"]
    counts = np.bincount(np.argmax(z, -1), minlength=E + 1)[:E] / S
    aux = E * np.sum(counts * P[:, :E].mean(0))
    return f(p["alpha_f"]) * x + f(p["beta_f"]) * m, r, aux


def _forward(cfg, **kw):
    return jax.jit(lambda p, i, routes=None: REF.forward(p, i, cfg, routes,
                                                         **kw))


def test_reference_matches_the_equations_written_out_by_hand():
    params, cfg, ids = _tiny()
    logits, aux, routed = _forward(cfg)(params, ids)
    x, prev, want_aux = np.asarray(params["embed"], np.float64)[ids], None, 0
    for p in [params["first"], *params["layers"]]:
        x, prev, a = _layer_by_hand(p, x, prev, cfg)
        want_aux += a
    x = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-5) * np.asarray(
        params["norm"], np.float64)
    np.testing.assert_allclose(logits, x @ np.asarray(params["embed"]).T,
                               rtol=2e-4, atol=2e-4)
    assert float(aux) == pytest.approx(want_aux, rel=1e-4)
    assert len(routed) == 3 and routed[0].shape == (len(ids), 1)
    # the stacked form (what the check scans) is the same loop
    stacked = fit_check.stack_layers(params)
    logits_s, aux_s, routed_s = _forward(cfg)(stacked, ids)
    np.testing.assert_allclose(logits_s, logits, rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(routed_s), np.stack(routed))
    labels = jnp.roll(ids, -1)
    loss = jax.jit(lambda p, remat: REF.loss(p, ids, labels, cfg,
                                             remat=remat), static_argnums=1)
    assert float(loss(params, True)) == pytest.approx(
        float(loss(params, False)), rel=1e-6)


def test_reference_is_float32_highest_and_imports_nothing_of_the_program():
    text = open(REF.__file__).read()
    assert "import deeplearning4j_tpu" not in text
    assert "from deeplearning4j_tpu" not in text
    assert "from benchmark" not in text and "import benchmark" not in text
    assert "Precision.HIGHEST" in text and "pallas" not in text
    params, cfg, ids = _tiny()
    assert _forward(cfg)(params, ids)[0].dtype == jnp.float32


def test_needed_form_equals_the_dense_form_and_routes_are_used_as_given():
    params, cfg, ids = _tiny(S=12)
    forward = _forward(cfg)
    logits, _, own = forward(params, ids)
    cfg_all = dict(cfg, pairs_counted=12)      # every pair counted
    for rows_block in (5, 12):
        needed = jax.jit(lambda p, i: REF.forward_needed(
            p, i, cfg_all, rows_block=rows_block))
        np.testing.assert_allclose(needed(params, ids), logits, rtol=1e-5,
                                   atol=1e-5)
    other = [(r + 1) % (cfg["n_experts"] + 1) for r in own]
    moved, _, used = forward(params, ids, other)
    assert all(np.array_equal(u, g) for u, g in zip(used, other))
    assert float(jnp.max(jnp.abs(moved - logits))) > 1e-3
    grads = jax.jit(jax.grad(lambda p: REF.loss(p, ids, jnp.roll(ids, -1),
                                                cfg)))(params)
    assert float(jnp.abs(grads["first"]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["layers"][0]["eda"]).max()) > 0.0


def test_config_file_keeps_every_published_width():
    assert SIZES["source"] == CONFIG.source == (
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json")
    assert sorted(SIZES["reduced"]) == ["num_hidden_layers",
                                        "num_local_experts", "vocab_size"]
    assert set(SIZES["reduced_why"]) == set(SIZES["reduced"])
    assert (SIZES["num_hidden_layers"], SIZES["num_local_experts"],
            SIZES["vocab_size"], SIZES["num_experts"]) == (4, 8, 32784, 16)
    assert SIZES["vocab_size"] * 8 == 262272          # an eighth, published
    assert SIZES["layer_types"] == ["hybrid"] * 40    # every layer, as published
    assert (SIZES["tie_word_embeddings"], SIZES["sliding_window"],
            SIZES["rms_norm_eps"], SIZES["hidden_act"]) == (True, None, 1e-5,
                                                            "silu")
    assert (SIZES["hidden_size"], SIZES["num_attention_heads"],
            SIZES["num_key_value_heads"], SIZES["head_dim"],
            SIZES["moe_intermediate_size"], SIZES["router_hidden_size"],
            SIZES["num_experts_per_tok"], SIZES["partial_rotary_factor"],
            SIZES["rope_parameters"]["hybrid"]["rope_theta"],
            SIZES["cca_time0"], SIZES["cca_time1"]) == (
        2048, 8, 2, 128, 2048, 256, 1, 0.5, 5000000, 2, 2)
    assert SIZES["held"] == {"first_expert": 0, "experts": 8, "first_id": 0,
                             "ids": 32784}
    assert {"convolutions", "qk_mean", "qk_norm", "rotary", "value_shift",
            "router", "skip", "residual_gains", "router_bias", "aux_loss",
            "optimizer", "precision", "loss", "data", "init"} <= set(
        SIZES["assumed"])
    assert "8 chips share each layer" in SIZES["deployment"]
    assert "first of the eight" in SIZES["deployment"]
    with pytest.raises(ValueError, match="1 chip"):
        CONFIG.build(SIZES, 1, 4)


def test_program_builds_at_the_published_widths():
    """Shapes only: 106.91 M parameters a layer, 494.8 M held, 7.92 GB at 16
    bytes a parameter."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = CONFIG.make_conf(SIZES, 1)
    shapes = jax.eval_shape(lambda: ComputationGraph(conf).init().params_tree)
    count = {k: sum(math.prod(a.shape) for a in v.values())
             for k, v in shapes.items()}
    assert count["attn0"] == count["attn3"] == 5_242_880 + 332_800 + 2
    assert count["route0"] == 2048 * 256
    assert count["route1"] == count["route3"] == 2048 * 256 + 256
    assert count["ffn2"] == 2 * 256 * 256 + 256 * 17 + 17 \
        + 8 * 3 * 2048 * 2048
    assert count["res_a0"] == count["res_f3"] == 2 * 2048
    assert count["emb"] == 32784 * 2048 and count["out"] == 0
    layer = sum(v for k, v in count.items() if k.endswith("1"))
    assert layer == 106_911_251           # the 17 selection biases included
    # the first layer has no depth average (256 fewer), the final norm 2048
    assert sum(count.values()) == 4 * layer - 256 + 32784 * 2048 + 2048 \
        == 494_788_428
    assert round(sum(count.values()) * 16 / 1e9, 2) == 7.92


def test_cca_flops_of_a_step_at_the_published_sizes():
    """9 passes of 2 x 128 FLOP a pair and query head, 8 heads, 33.56 M pairs,
    four layers: 2.47 TFLOP a step."""
    pairs = kernel_costs.band_pairs(8192)
    assert cca_costs.attention_step_flops(SIZES) == 4 * 9 * 2 * 128 * 8 \
        * pairs
    assert cca_costs.attention_step_flops(SIZES) / 1e12 == pytest.approx(
        2.474, abs=0.001)


def test_operations_counted_for_fit_mfu_by_hand():
    """`harness/flops.py` over `forward_needed` at the real widths against
    the count written out from the shapes."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = CONFIG.make_conf(SIZES, 1)
    params = jax.eval_shape(lambda: ComputationGraph(conf).init().params_tree)
    cfg = CONFIG.model_cfg(SIZES)
    got = flops.forward_macs(
        lambda p, i: REF.forward_needed(CONFIG.reference_params(p, SIZES), i,
                                        cfg),
        params, jax.ShapeDtypeStruct((8192,), jnp.int32))
    S, D, H, KV, Dh, R, F, V = 8192, 2048, 8, 2, 128, 256, 2048, 32784
    projections = S * D * (H * Dh + KV * Dh + KV * Dh) + S * H * Dh * D
    conv = 2 * S * (H + KV) * Dh * Dh
    blocks = sum(256 * 256 * (b + 1) for b in range(S // 256))
    core = blocks * H * 2 * Dh
    router = S * (D * R + 2 * R * R + R * 17)
    experts = 3 * (S * 8 // 17) * D * F
    head = S * D * V
    assert got == 4 * (projections + conv + core + router + experts) + head
    # 7.39 TFLOP a step at an even router (8 of the 17 choices held): the
    # tied head 3.30, the core's 6 passes of 9 over blocks 3.1% larger than
    # the band 1.70, the projections 1.03, the experts 1.16
    assert 3 * 2 * got / 1e12 == pytest.approx(7.39, abs=0.01)
    assert 6 * head / 1e12 == pytest.approx(3.30, abs=0.01)
    assert 6 * 4 * experts / 1e12 == pytest.approx(1.16, abs=0.01)


# ---- the readers -------------------------------------------------------------

HLO = '''
HloModule jit_step_fn

ENTRY %main (a: bf16[8]) -> bf16[8] {
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/jvp(cca.project)/dot_general"}
  %fusion.2 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/jvp(cca.conv)/mul"}
  %custom-call.3 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jit(main)/jvp(cca.attend)/banded_attention_fwd/pallas_call"}
  %fusion.4 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/transpose(jvp(moe.route))/dot_general"}
  %custom-call.5 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jit(main)/transpose(jvp(cca.attend))/banded_attention_dkv/pallas_call"}
  ROOT %fusion.6 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/lm.head/dot_general"}
}
'''


class _Exe:
    def __init__(self, text):
        self.text = text

    def as_text(self):
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
    `cca.project`, 10 under `cca.conv`, 20 + 30 under `cca.attend`, the rest
    elsewhere."""
    ms = 1e6
    return [("fusion.1", (t0 + 0) * ms, 15 * ms),
            ("fusion.2", (t0 + 15) * ms, 10 * ms),
            ("custom-call.3 [tpu_custom_call]", (t0 + 25) * ms, 20 * ms),
            ("fusion.4", (t0 + 45) * ms, 10 * ms),
            ("custom-call.5 [tpu_custom_call]", (t0 + 55) * ms, 30 * ms),
            ("fusion.6", (t0 + 85) * ms, 15 * ms)]


EVENTS = _step(0) + _step(100) + _step(200)
PARENTS = HLO.replace("cca.", "attn.")


@pytest.mark.parametrize("metric,share", [
    ("cca_time_share.fit", 75.0), ("cca_conv_time_share.fit", 10.0)])
def test_scope_readers_on_a_canned_trace(metric, share):
    read = cells.load_module("layer_metrics", metric).read
    assert read(_context([_Exe(HLO)], EVENTS)) == pytest.approx(share)
    # no trace, no program text, or a program without the scopes (the
    # parent's): nothing to read, and no error
    assert read(_context([_Exe(HLO)], [])) is None
    assert read(_context([_Exe(None)], EVENTS)) is None
    assert read(_context([_Exe(PARENTS)], EVENTS)) is None


def test_roofline_reader_divides_the_needed_flops_by_the_scoped_time(
        monkeypatch):
    from benchmark.harness import device

    read = cells.load_module("layer_metrics",
                             "cca_attention_roofline.fit").read
    context = _context([_Exe(HLO)], EVENTS)
    assert read(context) is None          # the CPU has no published peak
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(device.CHIP_PEAKS, kind, (197e12, 819e9, "test"))
    # three steps (the heaviest scoped instruction ran three times), 50 ms
    # of `cca.attend` each
    want = 100 * 3 * cca_costs.attention_step_flops(SIZES) / (0.15 * 197e12)
    assert read(context) == pytest.approx(want)
    assert read(_context([_Exe(PARENTS)], EVENTS)) is None
    assert read(_context([_Exe(HLO)], [])) is None


def test_skip_share_reader_reads_the_gauge_or_nothing(monkeypatch):
    from deeplearning4j_tpu import observability as obs

    read = cells.load_module("layer_metrics", "moe_skip_share").read
    monkeypatch.setattr(obs.metrics, "get_family", lambda name: None)
    assert read({}) is None
    child = lambda v: type("C", (), {"get": lambda self: v})()
    family = type("F", (), {"children": lambda self: [child(0.25),
                                                       child(0.75)]})()
    monkeypatch.setattr(obs.metrics, "get_family",
                        lambda name: family if name == "dl4j_moe_skip_share"
                        else None)
    assert read({}) == pytest.approx(50.0)


# ---- the manifest ------------------------------------------------------------

def test_the_cell_reports_what_the_issue_lists():
    """The four new readers list this cell alone, and the cell joins every
    list the issue named, at its end; no metric that another cell owns
    alone reads it."""
    manifest = cells.manifest()
    config = next(c for c in manifest["configs"] if c["name"] == "zaya1_8b")
    assert config["reduced"] == SIZES["reduced"] and config["source"] \
        == SIZES["source"] and config["file"] == \
        "benchmark/configs/zaya1_8b.json"
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "zaya1_8b", "fit_seq8k", 1)
    for name in NEW:
        m = manifest_checks.entry(manifest, name)
        assert m["workloads"] == [CELL] and m["moves"] == "fit_samples_per_s"
        assert m["layer"] == "kernels" and m["unit"] == "%"
    for name in JOINED:
        assert manifest_checks.entry(manifest, name)["workloads"][-1] == CELL
    # no count: a later metric may list the cell too
    names = set(manifest_checks.names_for(manifest, "per_layer", CELL))
    assert NEW | (JOINED - {"fit_samples_per_s"}) <= names
    assert not (manifest_checks.KIMI_OWN | manifest_checks.MELLUM_OWN
                | {"dsa_time_share.fit"}) & names
    assert manifest_checks.names_for(manifest, "end_to_end", CELL)[:2] == [
        "fit_samples_per_s", "setup_s"]
    spec = cells.load_json("workloads", CELL)
    assert spec["traffic"] == {"kind": "fit_seq8k", "epochs_per_sync": 4,
                               "trace_seconds": 4.0}
    assert spec["driver"] == "fit_ref" and spec["check"]["fault"] is None
    assert "480 tokens" in spec["why"] and "960" in spec["why"]


def test_the_older_entries_keep_their_places_and_come_first():
    """Every entry of the day before keeps its place and what it said, its
    lists growing at their ends only; this configuration, its cell and its
    metrics come after them (`manifest_checks.older_entries_in_their_places`)."""
    manifest = cells.manifest()
    manifest_checks.older_entries_in_their_places(manifest, cells.BENCH)
    configs = [c["name"] for c in manifest["configs"]]
    cells_ = [w["name"] for w in manifest["workloads"]]
    metrics = [m["name"] for m in manifest["per_layer"]]
    # after every entry of the day before, in the order they were appended
    assert configs.index("zaya1_8b") == len(manifest_checks.CONFIGS)
    assert cells_.index(CELL) == len(manifest_checks.CELLS)
    assert set(metrics[len(manifest_checks.ENTRIES["per_layer"]):][:4]) \
        == NEW
    manifest_checks.fixed_count(manifest, cells.BENCH, CELL)


# ---- the cell ----------------------------------------------------------------

def test_rehearsal_drives_the_cell_and_is_never_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        RUN + ["--workload", CELL, "--seed", str(2 ** 31 + 977), "--seconds",
               "2", "--trace", "1", "--rehearsal"], cwd=cells.ROOT,
        env=env, timeout=600, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 8 and line["failed"] == 0
    # off the chip the readers of a trace and of a peak read nothing; the
    # program's own gauges read
    assert {"moe_skip_share", "moe_pairs_held_share",
            "moe_expert_load_max_over_mean"} <= set(line["metrics"])
    assert not {"cca_time_share.fit", "cca_attention_roofline.fit",
                "fit_mfu"} & set(line["metrics"])
    check = info["reference_check"]
    assert set(check["limits"]) == set(CONFIG.LIMITS)
    assert check["loss_rel_given"] < 1e-3 and check["grad_rel_max"] < 0.1
    # every trained leaf of layers 0 and 3 but the temperature, the table,
    # the final norm and layer 3's EDA gate
    assert len(check["grad_rel"]) == 2 * 22 + 3 and check["positions"] == 64
    assert {"emb.W", "ln_out.gamma", "attn0.W_conv1", "attn3.W_conv0",
            "attn0.b_conv0", "attn3.b_conv1", "res_a0.alpha", "res_f3.beta",
            "route3.eda_theta", "ffn3.router_w3", "ffn0.router_w2",
            "route0.W_d", "ln_a0.gamma", "ln_f3.gamma"} <= set(
                check["grad_rel"])
    assert not {"attn0.temp", "attn3.temp"} & set(check["grad_rel"])
    assert set(check["update_rel"]) == set(check["grad_rel"])
    assert len(check["routing_agreement"]) == 4     # every layer routes
    assert check["steps_before"] == max(info["steps"] + 2 + 4,
                                        check["at_step"])


@pytest.fixture(scope="module")
def trained():
    """The rehearsal's net after 24 steps, with what puts it back there (a
    check takes one more step, and the step is donated its state)."""
    from deeplearning4j_tpu.nn.layers import cca

    built = cells.Cell(CELL, rehearsal=True).build(5)
    net = built["net"]
    for _ in range(12):
        net.fit(built["iterator"])
    saved = jax.tree_util.tree_map(
        np.asarray, (net.params_tree, net.opt_state, net.state))
    sound_fns = cca.causal_convs, cca.qk_mean, cca.value_shift

    def check(fault):
        net.params_tree, net.opt_state, net.state = jax.tree_util.tree_map(
            jnp.asarray, saved)
        net.iteration, net._clock = 24, None
        numbers = built["reference_check"](fault=fault)["numbers"]
        assert net.iteration == (24 if fault == "state_unchanged" else 25)
        # a first-pass fault is undone before anything else reads the layers
        assert (cca.causal_convs, cca.qk_mean, cca.value_shift) == sound_fns
        for i in range(int(SIZES["rehearsal"]["num_hidden_layers"])):
            layer = net.layer_vertices[f"attn{i}"].layer
            assert layer.partial_rotary_factor == 0.5
        return numbers

    return check, check(None)


@pytest.mark.parametrize("fault", fit_check.FAULTS + CONFIG.FAULTS)
def test_planted_fault_moves_its_number_far_from_the_sound_reading(
        trained, fault):
    check, sound = trained
    assert sound["grad_rel_max"] < 0.05 and sound["update_rel_max"] < 0.02
    got = check(fault)
    assert got["fault"] == fault
    if fault == "state_unchanged":
        assert set(got["update_rel"].values()) == {1.0}
        assert got["grad_rel"] == sound["grad_rel"]     # the first pass is sound
    elif fault == "no_eda":
        # the first layer has no depth average: its routing stays, the
        # next layers' moves
        assert got["routing_agreement"][0] == sound["routing_agreement"][0]
        assert min(got["routing_agreement"][1:]) \
            < min(sound["routing_agreement"]) - 0.1
    else:
        assert got["logits_rel"] > 3 * sound["logits_rel"]
        assert got["grad_rel_max"] > 3 * sound["grad_rel_max"]
    with pytest.raises(ValueError, match="unknown fault"):
        CONFIG.reference_check(None, SIZES, None, fault="bf16")
