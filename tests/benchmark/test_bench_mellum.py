"""The `mellum2_12b_a2_5b` configuration's benchmark files: the plain
reference against a tiny case written out by hand, its two forms against
each other, the cell's rehearsal as a command, the four new readers on a
canned trace, `kernel_costs` against a brute-force count of the band's
pairs, the count of operations `fit_mfu` is computed from, and the check's
planted faults."""

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

CELL = "mellum2_12b_a2_5b.fit_seq16k"
REF = cells.load_module("reference", "swa_moe_lm")
CONFIG = cells.load_module("configs", "mellum2_12b_a2_5b")
SIZES = cells.load_json("configs", "mellum2_12b_a2_5b")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
RUN = [sys.executable, os.path.join("benchmark", "run.py")]
YARN = {"rope_type": "yarn", "theta": 1e4, "factor": 4.0,
        "original_max_position_embeddings": 64, "beta_fast": 4, "beta_slow": 1,
        "attention_factor": 1.2}


def _tiny(seed=0, S=12, D=8, H=4, KV=2, Dh=4, E=4, Eh=2, F=6, V=10, top_k=2,
          window=5, kinds=("sliding_attention", "full_attention")):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)

    def layer():
        return {"ln1": w(D) + 1, "ln2": w(D) + 1, "wq": w(D, H * Dh),
                "wk": w(D, KV * Dh), "wv": w(D, KV * Dh), "wo": w(H * Dh, D),
                "q_norm": w(Dh) + 1, "k_norm": w(Dh) + 1, "router": w(D, E),
                "w_gate": w(Eh, D, F), "w_up": w(Eh, D, F),
                "w_down": w(Eh, F, D)}

    params = {"embed": w(V, D), "layers": [layer() for _ in kinds],
              "norm": w(D) + 1, "head": w(D, V)}
    cfg = {"n_heads": H, "n_kv_heads": KV, "head_dim": Dh, "rms_eps": 1e-6,
           "layer_types": list(kinds),
           "attention": {
               "sliding_attention": {
                   "window": window,
                   "rope": {"rope_type": "default", "theta": 1e4}},
               "full_attention": {"window": None, "rope": dict(YARN)}},
           "n_experts": E, "top_k": top_k, "first_expert": 1,
           "norm_topk_prob": True, "aux_coef": 0.01}
    ids = jnp.asarray(rng.integers(0, V, S), jnp.int32)
    return params, cfg, ids


def _by_hand(params, cfg, ids):
    """The module docstring's equations in numpy float64, one position, one
    head and one expert at a time."""
    f = lambda a: np.asarray(a, np.float64)
    H, KV, Dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    S = len(ids)

    def rms(x, g):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) * f(g)

    def table(rope):
        inv = rope["theta"] ** (-np.arange(Dh // 2) * 2.0 / Dh)
        if rope["rope_type"] == "default":
            return inv, 1.0
        c = lambda r: Dh * math.log(rope["original_max_position_embeddings"]
                                    / (2 * math.pi * r)) / (
            2 * math.log(rope["theta"]))
        low = max(math.floor(c(rope["beta_fast"])), 0)
        high = min(math.ceil(c(rope["beta_slow"])), Dh - 1)
        ramp = np.clip((np.arange(Dh // 2) - low) / (high - low), 0, 1)
        return (inv * (1 - ramp) + inv / rope["factor"] * ramp,
                rope["attention_factor"])

    def turn(x, t, inv, m):
        a = t * inv
        x1, x2 = x[: Dh // 2], x[Dh // 2:]
        return m * np.concatenate([x1 * np.cos(a) - x2 * np.sin(a),
                                   x2 * np.cos(a) + x1 * np.sin(a)])

    x = f(params["embed"])[np.asarray(ids)]
    for p, kind in zip(params["layers"], cfg["layer_types"]):
        a = cfg["attention"][kind]
        inv, m = table(a["rope"])
        h = rms(x, p["ln1"])
        q = (h @ f(p["wq"])).reshape(S, H, Dh)
        k = (h @ f(p["wk"])).reshape(S, KV, Dh)
        v = (h @ f(p["wv"])).reshape(S, KV, Dh)
        q, k = rms(q, p["q_norm"]), rms(k, p["k_norm"])
        o = np.zeros((S, H, Dh))
        for t in range(S):
            first = 0 if a["window"] is None else max(0, t - a["window"] + 1)
            for head in range(H):
                g = head // (H // KV)
                qt = turn(q[t, head], t, inv, m)
                s = np.asarray([qt @ turn(k[u, g], u, inv, m)
                                for u in range(first, t + 1)]) / math.sqrt(Dh)
                w = np.exp(s - s.max())
                o[t, head] = (w / w.sum()) @ v[first:t + 1, g]
        x = x + o.reshape(S, H * Dh) @ f(p["wo"])
        h2 = rms(x, p["ln2"])
        logits = h2 @ f(p["router"])
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        y = np.zeros_like(x)
        for t in range(S):
            top = np.argsort(-probs[t], kind="stable")[:cfg["top_k"]]
            for e in top:
                j = e - cfg["first_expert"]
                if 0 <= j < p["w_gate"].shape[0]:
                    a_ = h2[t] @ f(p["w_gate"][j])
                    hid = a_ / (1 + np.exp(-a_)) * (h2[t] @ f(p["w_up"][j]))
                    y[t] += probs[t, e] / probs[t, top].sum() * (
                        hid @ f(p["w_down"][j]))
        x = x + y
    return rms(x, params["norm"]) @ f(params["head"])


def test_reference_matches_the_equations_written_out_by_hand():
    params, cfg, ids = _tiny()
    logits, _, _ = REF.forward(params, ids, cfg)
    assert np.allclose(np.asarray(logits), _by_hand(params, cfg, ids),
                       rtol=2e-4, atol=2e-5)
    # the stacked form scans the same layers under the same tables
    stacked = fit_check.stack_layers(params)
    scanned, _, routed = REF.forward(stacked, ids, cfg)
    assert np.allclose(np.asarray(scanned), np.asarray(logits), rtol=1e-5,
                       atol=1e-6)
    assert routed.shape == (2, 12, 2)


def test_reference_is_float32_highest_and_imports_nothing_of_the_program():
    text = open(os.path.join(cells.BENCH, "reference", "swa_moe_lm.py")).read()
    code = text.split('"""', 2)[2]
    assert "deeplearning4j_tpu" not in code and "sparse_moe_lm" not in code
    assert "benchmark" not in code
    assert "Precision.HIGHEST" in text and "bfloat16" not in text
    params, cfg, ids = _tiny()
    logits, aux, _ = REF.forward(params, ids, cfg)
    assert logits.dtype == jnp.float32 and jnp.asarray(aux).dtype == jnp.float32


@pytest.mark.parametrize("S,window", [(12, 5), (8, 16), (12, 1)])
def test_needed_form_equals_the_dense_form(S, window):
    """`forward_needed` (a gather of each query's window, causal blocks
    before it, experts over sorted pairs) gives the dense form's logits
    when every held pair is counted."""
    params, cfg, ids = _tiny(seed=3, S=S, window=window)
    dense, _, _ = REF.forward(params, ids, cfg)
    cfg = dict(cfg, pairs_counted=S * cfg["top_k"])
    needed = REF.forward_needed(params, ids, cfg, rows_block=4)
    assert np.allclose(np.asarray(needed), np.asarray(dense),
                       rtol=1e-4, atol=1e-5)


def test_given_routing_is_used_and_the_window_matters():
    params, cfg, ids = _tiny(seed=5)
    _, _, routes = REF.forward(params, ids, cfg)
    other = [jnp.zeros_like(r) + jnp.asarray([[0, 3]]) for r in routes]
    base = float(REF.loss(params, ids, ids, cfg))
    assert float(REF.loss(params, ids, ids, cfg, routes=routes)) \
        == pytest.approx(base)
    assert float(REF.loss(params, ids, ids, cfg, routes=other)) != base
    wider = dict(cfg, attention=dict(cfg["attention"], sliding_attention=dict(
        cfg["attention"]["sliding_attention"], window=6)))
    assert float(REF.loss(params, ids, ids, wider)) != base
    plain = dict(cfg, attention=dict(cfg["attention"], full_attention={
        "window": None, "rope": {"rope_type": "default", "theta": 1e4}}))
    assert float(REF.loss(params, ids, ids, plain)) != base


@pytest.mark.parametrize("S,window", [(64, None), (64, 1), (64, 7),
                                      (64, 64), (64, 100), (96, 32)])
def test_kernel_costs_against_a_brute_force_count_of_band_pairs(S, window):
    pairs = sum(1 for t in range(S) for s in range(S)
                if s <= t and (window is None or s > t - window))
    assert kernel_costs.band_pairs(S, window) == pairs
    assert kernel_costs.attention_train_flops(S, 4, 16, window) \
        == 9 * 2 * 16 * 4 * pairs


def test_attention_flops_of_a_step_at_the_published_sizes():
    """Three sliding layers and the full one at 16,384 positions: 16.25 M
    and 134.2 M pairs a layer, 9 passes of 256 FLOP over 32 heads."""
    sliding = 1024 * 1025 // 2 + (16384 - 1024) * 1024
    full = 16384 * 16385 // 2
    assert kernel_costs.band_pairs(16384, 1024) == sliding == 16_253_440
    assert kernel_costs.band_pairs(16384) == full == 134_225_920
    assert kernel_costs.attention_step_flops(SIZES) \
        == 9 * 2 * 128 * 32 * (3 * sliding + full)
    assert kernel_costs.attention_step_flops(SIZES) / 1e12 \
        == pytest.approx(13.49, abs=0.01)
    assert kernel_costs.attention_step_flops(
        dict(SIZES, batch_per_chip=2, num_hidden_layers=8)) \
        == 4 * kernel_costs.attention_step_flops(SIZES)


def test_operations_counted_for_fit_mfu_by_hand():
    """`harness/flops.py` over `forward_needed` at the real widths, one
    period, against the count written out from the shapes."""
    cfg = CONFIG.model_cfg(SIZES)
    S, D, V = 16384, 2304, 12288
    H, KV, Dh, W = 32, 4, 128, 1024
    E, Eh, F, TK = 64, 8, 896, 8
    layer = {"ln1": (D,), "ln2": (D,), "wq": (D, H * Dh), "wk": (D, KV * Dh),
             "wv": (D, KV * Dh), "wo": (H * Dh, D), "q_norm": (Dh,),
             "k_norm": (Dh,), "router": (D, E), "w_gate": (Eh, D, F),
             "w_up": (Eh, D, F), "w_down": (Eh, F, D)}
    shapes = {"embed": (V, D), "norm": (D,), "head": (D, V),
              "layers": [layer] * 4}
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), shapes,
        is_leaf=lambda s: isinstance(s, tuple))
    got = flops.forward_macs(lambda p, i: REF.forward_needed(p, i, cfg),
                             params, jax.ShapeDtypeStruct((S,), jnp.int32))
    projections = S * D * (2 * H * Dh + 2 * KV * Dh)
    blocks = lambda rows: sum(256 * 256 * (b + 1) for b in range(rows // 256))
    sliding = 2 * (blocks(W) + (S - W) * W) * H * Dh
    full = 2 * blocks(S) * H * Dh
    pairs = S * TK * Eh // E
    experts = S * D * E + 3 * pairs * D * F
    head = S * D * V
    assert got == 4 * (projections + experts) + 3 * sliding + full + head
    # causal blocks of 256 rows count 128 keys a row too many: 1.6% of the
    # full layer, 0.8% of a sliding layer's first 1,024 rows
    assert blocks(S) / kernel_costs.band_pairs(S) == pytest.approx(1.0156,
                                                                   abs=1e-3)
    assert 3 * 2 * got / 1e12 == pytest.approx(22.9, abs=0.2)


def test_config_file_keeps_every_published_width():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    entry = next(json.loads(l) for l in open(CATALOG)
                 if json.loads(l)["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert SIZES["source"] == entry["source_url"] == CONFIG.source
    differs = sorted(k for k, v in entry["config"].items() if SIZES.get(k) != v)
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert sorted(SIZES["reduced"]) == ["num_hidden_layers",
                                        "num_local_experts", "vocab_size"]
    assert (SIZES["num_hidden_layers"], SIZES["num_local_experts"],
            SIZES["vocab_size"], SIZES["num_experts"]) == (4, 8, 12288, 64)
    assert SIZES["held"] == {"first_expert": 0, "experts": 8, "first_id": 0,
                             "ids": 12288}
    assert CONFIG.layer_types(SIZES) == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert {"qk_norm", "window", "yarn", "aux_loss", "optimizer", "precision",
            "loss", "data", "init"} <= set(SIZES["assumed"])
    assert "8 chips share each layer" in SIZES["deployment"]
    assert "multi-token-prediction" in SIZES["absent"]


def test_program_builds_at_the_published_widths():
    """Shapes only: 340.35 M parameters, 70.93 M a layer."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = CONFIG.make_conf(SIZES, 1)
    shapes = jax.eval_shape(lambda: ComputationGraph(conf).init().params_tree)
    count = {k: sum(math.prod(a.shape) for a in v.values())
             for k, v in shapes.items()}
    assert sum(count.values()) == 340_350_208
    assert count["attn0"] == count["attn3"] == 2 * 2304 * 4096 \
        + 2 * 2304 * 512 + 2 * 128
    assert count["ffn0"] == 2304 * 64 + 8 * 3 * 2304 * 896
    assert count["emb"] == count["out"] == 12288 * 2304
    full = conf.vertices["attn3"].layer
    assert full.sliding_window is None and full.rope_scaling == {
        k: v for k, v in SIZES["rope_parameters"]["full_attention"].items()
        if k != "rope_theta"}
    assert conf.vertices["attn2"].layer.sliding_window == 1024


HLO = '''
HloModule jit_step_fn

ENTRY %main (a: bf16[8]) -> bf16[8] {
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/attn.rope/mul"}
  %custom-call.2 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jit(main)/jvp(attn.sliding)/banded_attention_fwd/pallas_call"}
  %custom-call.3 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jit(main)/jvp(attn.full)/banded_attention_fwd/pallas_call"}
  %fusion.4 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/transpose(jvp(attn.full))/transpose"}
  %custom-call.5 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jit(main)/transpose(jvp(attn.full))/banded_attention_dkv/pallas_call"}
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
    """One step's events from `t0` ms on: 100 ms in all, 10 under
    `attn.sliding`, 40 under `attn.full` (its heaviest instruction,
    `custom-call.5`, 25), 5 of rope, the rest elsewhere."""
    ms = 1e6
    return [("fusion.1", (t0 + 0) * ms, 5 * ms),
            ("custom-call.2 [tpu_custom_call]", (t0 + 5) * ms, 10 * ms),
            ("custom-call.3 [tpu_custom_call]", (t0 + 15) * ms, 10 * ms),
            ("fusion.4", (t0 + 25) * ms, 5 * ms),
            ("custom-call.5 [tpu_custom_call]", (t0 + 30) * ms, 25 * ms),
            ("fusion.6", (t0 + 55) * ms, 45 * ms)]


EVENTS = _step(0) + _step(100) + _step(200)


@pytest.mark.parametrize("metric,share", [
    ("swa_time_share.fit", 10.0), ("full_attn_time_share.fit", 40.0)])
def test_scope_readers_on_a_canned_trace(metric, share):
    read = cells.load_module("layer_metrics", metric).read
    assert read(_context([_Exe(HLO)], EVENTS)) == pytest.approx(share)
    # no trace, no program text, or a program without the scopes (the
    # parent's): nothing to read, and no error
    assert read(_context([_Exe(HLO)], [])) is None
    assert read(_context([_Exe(None)], EVENTS)) is None
    assert read(_context([], EVENTS)) is None
    assert read(_context([_Exe(HLO.replace("attn.", "x."))], EVENTS)) is None


def test_scoped_seconds_and_steps_count_whole_steps_from_the_trace():
    found = kernel_costs.scoped_seconds_and_steps(
        _context([_Exe(HLO)], EVENTS), ("attn.sliding", "attn.full"))
    assert found == (pytest.approx(0.150), 3)
    assert kernel_costs.scoped_seconds_and_steps(
        _context([_Exe(HLO)], EVENTS), ("attn.sliding",)) \
        == (pytest.approx(0.030), 3)
    for context in (_context([_Exe(HLO)], []), _context([_Exe(None)], EVENTS),
                    _context([_Exe(HLO.replace("attn.", "x."))], EVENTS)):
        assert kernel_costs.scoped_seconds_and_steps(
            context, ("attn.sliding", "attn.full")) is None


def test_roofline_reader_divides_the_needed_flops_by_the_scoped_time(
        monkeypatch):
    from benchmark.harness import device

    read = cells.load_module("layer_metrics",
                             "banded_attention_roofline.fit").read
    context = _context([_Exe(HLO)], EVENTS)
    assert read(context) is None          # the CPU has no published peak
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(device.CHIP_PEAKS, kind, (197e12, 819e9, "test"))
    want = 100 * 3 * kernel_costs.attention_step_flops(SIZES) / (
        0.150 * 197e12)
    assert read(context) == pytest.approx(want)
    assert read(_context([_Exe(HLO.replace("attn.", "x."))], EVENTS)) is None
    assert read(_context([_Exe(HLO)], [])) is None


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


def test_fill_share_reader_reads_the_worst_layer_and_none_without_the_gauge(
        monkeypatch):
    from deeplearning4j_tpu import observability as obs

    read = cells.load_module("layer_metrics", "attn_band_fill_share").read
    families = {"dl4j_attn_band_fill_share": _Family(0.667, 0.667, 0.97)}
    monkeypatch.setattr(obs.metrics, "get_family", families.get)
    assert read({}) == pytest.approx(66.7)
    # a layer whose XLA body ran reads 0 (no tiles): nothing to report
    families["dl4j_attn_band_fill_share"] = _Family(0.0, 0.0, 0.0)
    assert read({}) is None
    families.clear()
    assert read({}) is None


def test_the_cell_reports_what_the_issue_lists():
    """The metrics the cell's issue listed are among its own, its sibling's
    own are not, and the four it brought name it first and no older cell
    (`manifest_checks.mellum_reports`): no count, so a later metric may
    list it."""
    manifest_checks.mellum_reports(cells.manifest(), cells.BENCH)


def test_the_cells_the_benchmark_had_keep_their_entries_and_their_places():
    """The two older cells are the first two entries, and every entry keeps
    its place and what it said, its lists growing at their ends only
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
        # off the chip the XLA body runs: no tiles, no fill share
        assert set(line["metrics"]) == names - traced - {
            "fit_mfu", "attn_band_fill_share"}
    else:
        assert set(line["metrics"]) == {"fit_samples_per_s", "setup_s"}
    check = info["reference_check"]
    assert set(check["limits"]) == set(CONFIG.LIMITS)
    assert check["loss_rel_given"] < 1e-3 and check["grad_rel_max"] < 0.1
    assert len(check["grad_rel"]) == 14 and check["positions"] == 64
    assert {"attn0.Wq", "attn3.Wo", "ffn0.gate_w", "ffn3.w_down", "emb.W",
            "out.W"} <= set(check["grad_rel"])
    assert set(check["update_rel"]) == set(check["grad_rel"])
    assert 0 < check["update_rel_max"] < 0.05
    assert len(check["routing_agreement"]) == 4
    # the warm-up's 2 + 4 steps and the window's, or the cell's fixed count
    # where the window ended short of it (PR 34)
    assert check["steps_before"] == max(info["steps"] + 2 + 4,
                                        check["at_step"])
    assert info["loss_last"] < info["loss_first"]


@pytest.fixture(scope="module")
def trained():
    """The rehearsal's net after 24 steps, with what puts it back there (a
    check takes one more step, and the step is donated its state)."""
    built = cells.Cell(CELL, rehearsal=True).build(5)
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
        # a first-pass fault is undone before anything else reads the layers
        layers = [net.layer_vertices[f"attn{i}"].layer for i in range(4)]
        assert [l.sliding_window for l in layers] == [16, 16, 16, None]
        assert layers[3].rope_scaling["attention_factor"] > 1.2
        return numbers

    return check, check(None)


def test_sound_check_reads_small_and_the_update_is_the_reference_adam_step(
        trained):
    _, sound = trained
    assert sound["steps_before"] == 24 and sound["fault"] is None
    assert sound["grad_rel_max"] < 0.05 and sound["update_rel_max"] < 0.02
    assert min(sound["routing_agreement"]) > 0.95


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
    else:
        # the attention of one kind of layer computes something else: the
        # gradient of that kind's query projection says so first
        leaf = "attn0.Wq" if fault == "window_half" else "attn3.Wq"
        assert got["grad_rel"][leaf] > 5 * sound["grad_rel"][leaf]
        assert got["logits_rel"] > 2 * sound["logits_rel"]
    with pytest.raises(ValueError, match="unknown fault"):
        CONFIG.reference_check(None, SIZES, None, fault="bf16")
