"""ZAYA1-8B for `fit`, one chip's share: the network, its staged data, and
the check against the plain reference.

`build(sizes, seed, chips)` returns what the `fit` and `fit_ref` drivers
need. Everything that is a size comes from the JSON beside this file. The
reference (`benchmark/reference/cca_moe_lm.py`) is given the same share: the
held experts and the held slice of the vocabulary.

The check is `fit_check.two_pass_check`. The reference's tree holds the
first layer as its own key (`first`: its router has no predecessor, so no
EDA gate) and the others as `layers`; the head is the embedding (`embed`),
so the tree has no `head` and the reference differentiates the one table
through both of its uses.
"""

from __future__ import annotations

source = "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"

# What the on-chip check compares, each limit set between two readings on the
# chip (PERF.md section 4 has them): the worst the program gave over its
# sound first passes at the cell's count of 672 steps (twelve: eleven runs
# through the harness and the fault process's first pass; one more run
# read at 560) and what it gives under a planted fault, which
# has to come out as not correct: every matrix rounded to float8_e4m3fn (the
# nearest precision below the configuration's bf16), the state left
# unchanged, both convolutions as the identity, no q-k mean, no value shift,
# rotary over the whole head, EDA's gate at 0.
# Why the sound gaps are wider than other configurations': by 672 steps the
# two cached batches are memorised (the loss 0.003 to 0.15 from 10.9), so
# the gradient is a small residual on which bf16's rounding acts. On the
# chip, one seed read its worst gradient gap 0.078 at 64 steps, 0.154 at
# 169 and 0.202 at 336, and the same seed under the float32 policy (XLA's
# products at `highest`) read at most 5e-4 on every leaf at 672, the logits
# 2.7e-6 and the routing 1.
# Sound at 672 (560) | fp8, state unchanged, no_conv, no_qk_mean,
# no_value_shift, full_rotary, no_eda.
LIMITS = {
    # share of tokens whose top-1 choice program and reference agree on,
    # worst layer: 0.9952 to 0.9988 (0.9984) | 0.9514, sound, 0.380, 0.580,
    # 0.570, 0.535, 0.752 (EDA's fault leaves the first layer at 0.997).
    # 4.2 x of room on 1 - the share, fp8 2.4 x past it
    "routing_agreement_min": 0.98,
    # loss, the program's (bf16 compute) against the reference's (float32),
    # with its own routing and given the program's: |difference| /
    # reference. Own 0.0024 to 0.114 (0.0053): the loss is 0.003 to 0.15,
    # so a difference of 0.003 is a tenth of it; given 3.6e-5 to 7.2e-3 |
    # own 9.66, 0.074 (its first pass is sound), 300, 277, 225, 227, 48.2;
    # given 0.180, 2.1e-4, 0.242, 0.696, 0.681, 0.619, 0.471. 4.4 x above
    # the worst sound reading, fp8 19 x past it
    "loss_rel": 0.5,
    # logits of 256 positions, the reference given the program's routing:
    # ||program - reference|| / ||reference||. 0.0196 to 0.0310 (0.0267) |
    # 0.329, sound, 0.711, 0.903, 0.837, 0.888, 0.442. 2.6 x of room, fp8
    # 4.1 x past it
    "logits_rel": 0.08,
    # every compared gradient, worst leaf: 0.060 to 0.491 (0.418; the
    # router's matrices or layer 0's value projections) | 4.63, 0.215
    # (sound), 3.79, 4.69, 5.53, 4.51, 4.98. 2.4 x of room, fp8 3.9 x past
    # it; under sqrt(2), which a gradient of the right norm pointing in an
    # unrelated direction reads
    "grad_rel": 1.2,
    # the change one compiled train step makes to a leaf against the
    # reference's Adam step from the same state, worst leaf (layer 0's value
    # projections, or a gain or norm scale): 0.016 to 0.131 (0.185) |
    # 0.974, 1 on every leaf, 1.15, 1.17, 1.21, 1.23, 1.17. The room is
    # above the reading (2.7 x): fresh seeds read higher; a state left
    # unchanged reads 2 x past it. Leaves near 1 (norm scales, gains, the
    # first convolution's taps) read up to 0.1 under the float32 policy too:
    # a step of lr 1e-5 added to a float32 near 1 is rounded to 6e-8
    "update_rel": 0.5,
}

# The faults this model's check can plant in the program's side of the first
# pass, beside `fit_check.FAULTS` (fp8, the state left unchanged): both
# convolutions as the identity, the q-k mean left out, the value shift left
# out (both halves from the current position), rotary over all 128 channels
# of a head, EDA's gate at 0 (each layer's router state its own).
FAULTS = ("no_conv", "no_qk_mean", "no_value_shift", "full_rotary", "no_eda")


def model_cfg(sizes: dict) -> dict:
    """The reference's `cfg` from the configuration's sizes."""
    D = int(sizes["head_dim"])
    return {
        "n_heads": int(sizes["num_attention_heads"]),
        "n_kv_heads": int(sizes["num_key_value_heads"]),
        "head_dim": D,
        "rotary_dim": int(round(D * float(sizes["partial_rotary_factor"]))),
        "rope_theta": float(sizes["rope_parameters"]["hybrid"]["rope_theta"]),
        "rms_eps": float(sizes["rms_norm_eps"]),
        "n_experts": int(sizes["num_experts"]),
        "top_k": int(sizes["num_experts_per_tok"]),
        "first_expert": int(sizes["held"]["first_expert"]),
        "aux_coef": float(sizes["aux_loss_coef"]),
    }


def make_conf(sizes: dict, seed: int, **over):
    """The program's configuration (`zoo.zaya_moe_lm`) at these sizes."""
    from deeplearning4j_tpu.models import zoo

    n = int(sizes["num_hidden_layers"])
    if set(sizes["layer_types"][:n]) != {"hybrid"} \
            or not sizes["tie_word_embeddings"] \
            or sizes["sliding_window"] is not None \
            or sizes["hidden_act"] != "silu" or sizes["attention_bias"] \
            or sizes["lm_head_bias"] or n < 2:
        raise ValueError("this builder is two or more 'hybrid' layers of "
                         "full CCA, gated SiLU experts, no biases and a "
                         "tied head")
    cfg = model_cfg(sizes)
    kw = dict(
        t=int(sizes["seq_len"]), d_model=int(sizes["hidden_size"]),
        n_blocks=n, n_heads=cfg["n_heads"], n_kv_heads=cfg["n_kv_heads"],
        head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
        partial_rotary_factor=float(sizes["partial_rotary_factor"]),
        cca_time0=int(sizes["cca_time0"]), cca_time1=int(sizes["cca_time1"]),
        n_experts=cfg["n_experts"], top_k=cfg["top_k"],
        expert_hidden=int(sizes["moe_intermediate_size"]),
        router_hidden=int(sizes["router_hidden_size"]),
        experts_held=(cfg["first_expert"], int(sizes["held"]["experts"])),
        rms_eps=cfg["rms_eps"], aux_loss_weight=cfg["aux_coef"],
        embedding_std=float(sizes["embedding_std"]),
        lr=float(sizes["learning_rate"]),
        adam_mean_decay=float(sizes["adam_mean_decay"]),
        adam_var_decay=float(sizes["adam_var_decay"]),
        seed=seed % (2 ** 31 - 1), dtype_policy=dict(sizes["dtype_policy"]))
    kw.update(over)
    return zoo.zaya_moe_lm(int(sizes["held"]["ids"]), **kw)


def make_net(sizes: dict, seed: int, **over):
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    return ComputationGraph(make_conf(sizes, seed, **over)).init()


# reference name -> (program vertex stem, leaf), for every layer
_LAYER = {"ln1": ("ln_a", "gamma"), "ln2": ("ln_f", "gamma"),
          "wq": ("attn", "Wq"), "wk": ("attn", "Wk"), "wv1": ("attn", "Wv1"),
          "wv2": ("attn", "Wv2"), "conv0_w": ("attn", "W_conv0"),
          "conv0_b": ("attn", "b_conv0"), "conv1_w": ("attn", "W_conv1"),
          "conv1_b": ("attn", "b_conv1"), "temp": ("attn", "temp"),
          "wo": ("attn", "Wo"), "alpha_a": ("res_a", "alpha"),
          "beta_a": ("res_a", "beta"), "alpha_f": ("res_f", "alpha"),
          "beta_f": ("res_f", "beta"), "router_down": ("route", "W_d"),
          "router_w1": ("ffn", "router_w1"),
          "router_w2": ("ffn", "router_w2"),
          "router_w3": ("ffn", "router_w3"), "router_bias": ("ffn", "gate_b"),
          "w_gate": ("ffn", "w_gate"), "w_up": ("ffn", "w_up"),
          "w_down": ("ffn", "w_down")}
# ... and every layer but the first
_EDA = {"eda": ("route", "eda_theta")}


def reference_params(tree, sizes: dict) -> dict:
    """The program's parameter tree under the reference's names (the same
    arrays, no copy)."""

    def layer(i):
        names = _LAYER if i == 0 else {**_LAYER, **_EDA}
        return {ref: tree[f"{stem}{i}"][leaf]
                for ref, (stem, leaf) in names.items()}

    return {"embed": tree["emb"]["W"], "first": layer(0),
            "layers": [layer(i) for i in range(
                1, int(sizes["num_hidden_layers"]))],
            "norm": tree["ln_out"]["gamma"]}


# the leaves compared in the first and the last layer: every trained leaf
# of a layer (the frozen selection bias is not trained) but the temperature
# on k. Its gradient, two numbers each a sum over every token and channel,
# nearly cancels by the check's count (6.8e-5 in layer 3 on one seed, 1/500
# of that layer's query projection), so bf16's rounding makes its relative
# gap unbounded (0.84 there); under the float32 policy it read 1e-4 on the
# chip, and the CPU tests compare it in float32.
_COMPARED = tuple(ref for ref in _LAYER if ref not in ("router_bias", "temp"))


def compared_leaves(sizes: dict) -> dict:
    """program leaf (layer, name) -> (path in the reference's stacked tree,
    index among `layers` or None): the tied table, the final norm's scale,
    and the compared leaves (`_COMPARED`) of the first and the last layer,
    and the last layer's EDA gate."""
    last = int(sizes["num_hidden_layers"]) - 1
    out = {("emb", "W"): (("embed",), None),
           ("ln_out", "gamma"): (("norm",), None)}
    for ref in _COMPARED:
        stem, leaf = _LAYER[ref]
        out[f"{stem}0", leaf] = (("first", ref), None)
        out[f"{stem}{last}", leaf] = (("layers", ref), last - 1)
    out[f"route{last}", "eda_theta"] = (("layers", "eda"), last - 1)
    return out


def _plant(fault, net, sizes: dict):
    """Make a first-pass `fault` on the program's side; returns what undoes
    what the harness does not restore itself (it restores `params_tree`)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import cca

    n = int(sizes["num_hidden_layers"])
    if fault == "no_eda":                    # lambda = sigmoid(-1e4) = 0
        tree = dict(net.params_tree)
        for i in range(1, n):
            tree[f"route{i}"] = dict(tree[f"route{i}"], eda_theta=jnp.full_like(
                tree[f"route{i}"]["eda_theta"], -1e4))
        net.params_tree = tree
        return lambda: None
    if fault == "full_rotary":
        layers = [net.layer_vertices[f"attn{i}"].layer for i in range(n)]
        held = [layer.partial_rotary_factor for layer in layers]
        for layer in layers:
            layer.partial_rotary_factor = 1.0

        def undo():
            for layer, value in zip(layers, held):
                layer.partial_rotary_factor = value
        return undo
    name, fake = {
        # c^ = u: the projections as the convolutions' output
        "no_conv": ("causal_convs",
                    lambda u, *weights: u.astype(jnp.promote_types(
                        u.dtype, jnp.float32))),
        "no_qk_mean": ("qk_mean", lambda q_hat, k_hat, *projected:
                       (q_hat, k_hat)),
        "no_value_shift": ("value_shift", lambda v2: v2),
    }[fault]
    sound = getattr(cca, name)
    setattr(cca, name, fake)
    return lambda: setattr(cca, name, sound)


def reference_check(net, sizes: dict, batch, *, positions: int = 256,
                    fault=None) -> dict:
    """`fit_check.two_pass_check` for this model: the routing of every layer
    (its top-1 choice among 16 experts and the skip) is the one set compared
    as a limit of its own. Beside `fit_check.FAULTS`, the first pass can be
    given, on the program's side only, one of `FAULTS`."""
    from benchmark.harness import cells, fit_check

    return fit_check.two_pass_check(
        net, batch, ref=cells.load_module("reference",
                                          sizes["check"]["reference"]),
        cfg=model_cfg(sizes), sizes=sizes, limits=LIMITS,
        leaves=compared_leaves(sizes),
        rparams_of=lambda tree: reference_params(tree, sizes),
        plant=lambda fault: _plant(fault, net, sizes), faults=FAULTS,
        positions=positions, fault=fault)


def build(sizes: dict, seed: int, chips: int) -> dict:
    from benchmark.harness import cells, fit_check

    if chips != 1:
        raise ValueError("this configuration is one chip's share of eight: "
                         "the cell takes 1 chip")
    net = make_net(sizes, seed)
    ref = cells.load_module("reference", sizes["check"]["reference"])
    cfg = model_cfg(sizes)
    # What `fit_mfu` counts multiply-adds from: the reference's forward in
    # the form whose products are the ones the mathematics needs (the causal
    # band's pairs, the held experts' expected pairs with the skip among the
    # 17 choices, the router and the tied head over every token).
    return fit_check.lm_cell(
        net, sizes, seed,
        forward=lambda params, ids: ref.forward_needed(
            reference_params(params, sizes), ids, cfg),
        check=lambda batch, fault: reference_check(net, sizes, batch,
                                                   fault=fault))
