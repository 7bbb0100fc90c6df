"""Kimi-VL-A3B-Instruct's language model for `fit`, one chip's share: the
network, its staged data, and the check against the plain reference.

`build(sizes, seed, chips)` returns what the `fit` and `fit_ref` drivers
need. Everything that is a size comes from the JSON beside this file. The
reference (`benchmark/reference/mla_moe_lm.py`) is given the same share: the
held experts and the held slice of the vocabulary.

The check is `fit_check.two_pass_check`, which scans one kind of layer: the
leading dense layer is the reference tree's own key (`dense`), the expert
layers are its `layers`, and the harness is told their count and how the
program names the i-th of them (`_ExpertLayers`).
"""

from __future__ import annotations

source = ("https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/"
          "config.json")

# What the on-chip check compares, each limit set between two readings on the
# chip (my chip runs, PR 32, calls 1 to 5, all under the cell's own traffic;
# PERF.md section 4 has every reading): the worst the program gave over its
# sound seeds, nineteen at the window's step count (178 to 194 steps before
# the check: eleven with the selection bias drawn from the run's seed, eight
# with it from the configuration's, as it now is), one at twice it (354: `--seconds 102`) and one at three times it
# (514: `--seconds 153`; the loss has then fallen from 10.0 to 0.08, the two
# cached batches memorised), and what it gives under a planted fault run
# through the harness, which has to come out as not correct: every matrix
# rounded to float8_e4m3fn (the nearest precision below the configuration's
# bf16), the state left unchanged, the selection bias left out of the
# choice, the weights taken from score + bias, no scaling factor, no shared
# expert, no norm on the latent. Sound at 1x; 2x; 3x | fp8, no_router_bias,
# bias_in_weights, no_routed_scaling, no_shared_expert, no_latent_norm.
LIMITS = {
    # near-ties at the router's top_k-th place flip under bf16, and a flip
    # in one layer moves the next layer's scores (the routed sum is weighted
    # 2.446 / 6 an expert): share of a token's experts that program and
    # reference agree on, worst layer (the last every time; the first reads
    # 0.979 to 0.984). 0.9342 to 0.9444; 0.9226; 0.9230 | 0.8233 to 0.8317,
    # 0.7359 to 0.7455, 0.9055 to 0.9127, 0.7400, 0.4591, 0.2144
    # **At the cell's fixed count of 288 steps (PR 34; the loss has fallen to
    # 0.83 there, the two batches memorised, and agreement has sunk with
    # it): sound 0.894 to 0.918 over 16 runs on 10 seeds and 3 more
    # first passes at 295 steps | fp8 0.760 to 0.766, no_router_bias
    # 0.695 to 0.700, bias_in_weights 0.852 to 0.854 (not this limit's to
    # catch). 0.90, the limit until PR 34, left a sound run no room at 288;
    # 0.85 is 1.4 x above the worst sound reading and 1.6 x under fp8 on
    # 1 - the share: the two stand 2.2 x apart, fp8 is the others' to catch.**
    "routing_agreement_min": 0.85,
    # loss, the program's (bf16 compute) against the reference's (float32),
    # with its own routing and given the program's: |difference| /
    # reference. The bf16 loss stands 1e-4 to 3e-3 from the float32 one in
    # absolute terms while the loss falls from 3.0 to 0.08, so the ratio
    # rises with the steps, and at one step count it varies tenfold by the
    # seed: 3.0e-5 to 9.4e-4 over nineteen runs; 7.4e-4; 1.4e-3 | 3.1e-2 to
    # 3.3e-2, 8.1e-2 to 1.0e-1, 7.5e-4 to 1.9e-3, 3.3e-1, 1.5, 8.7. The limit is the geometric
    # middle of the worst reading at the window's step count and fp8's: five
    # times of room at 1x, three and a half over the one reading at 3x, six
    # under fp8; `bias_in_weights` passes it and fails by the gradients
    # **At 288 steps (PR 34): sound 1.9e-4 to 3.8e-3 with the reference's own
    # routing, 9.4e-5 to 5.6e-4 given the program's | fp8 0.101 to 0.103
    # own, 0.027 given; no_router_bias 0.207 to 0.220 own. 5e-3, the limit
    # until PR 34, left 1.4 times of room over the worst sound reading;
    # 1.5e-2 is 3.9 x above it, 6.7 x under fp8's own, 1.8 x under its given.**
    "loss_rel": 1.5e-2,
    # logits of 256 positions and every compared gradient, the reference
    # given the program's routing: ||program - reference|| / ||reference||.
    # Logits 0.0070 to 0.0075; 0.0061; 0.0060 | 0.0741 to 0.0748, 0.0074
    # (the bias is only in the choice, and the routing is given), 0.0186 to
    # 0.0228, 0.279, 0.676, 30.9. The worst gradient (an attention layer's
    # `Wo`, `Wukv` or `Wdkv`) 0.021 to 0.060; 0.040; 0.086 | 0.686 to 0.897,
    # 0.018, 0.394 to 0.771, 1.56, 2.29, 2.5e4
    # **At 288 steps (PR 34): logits 0.0061 to 0.0063 | fp8 0.0611 to 0.0621
    # (3.2 x of room, 3.1 x under); the worst gradient 0.026 to 0.067 | fp8
    # 0.50 to 0.57, bias_in_weights 1.12 to 1.32 (3.0 x of room, 2.5 x
    # under): both limits stay.**
    "logits_rel": 0.02,
    "grad_rel": 0.20,
    # the change one compiled train step makes to a leaf against the
    # reference's Adam step from the same state, worst leaf (an attention
    # layer's `Wo`): 0.0076 to 0.0090; 0.0104; 0.0234 | state unchanged 1 on
    # all 27 leaves; fp8 0.174 to 0.205, no_router_bias 0.520 to 0.582,
    # bias_in_weights 0.080 to 0.088, 0.491, 0.734, 0.865. The room is above the reading: fresh seeds and
    # later checks read higher.
    # **At 288 steps (PR 34): 0.0097 to 0.0112 | fp8 0.30 to 0.34,
    # bias_in_weights 0.111 to 0.135 (5.4 x of room, 1.8 x under the
    # faintest fault): the limit stays.**
    "update_rel": 0.06,
}

# The faults this model's check can plant in the program's side of the first
# pass, beside `fit_check.FAULTS` (fp8, the state left unchanged).
FAULTS = ("no_router_bias", "bias_in_weights", "no_routed_scaling",
          "no_shared_expert", "no_latent_norm")


def n_dense(sizes: dict) -> int:
    """Leading layers with a dense MLP among the layers held."""
    return min(int(sizes["first_k_dense_replace"]),
               int(sizes["num_hidden_layers"]))


def model_cfg(sizes: dict) -> dict:
    """The reference's `cfg` from the configuration's sizes."""
    return {
        "n_heads": int(sizes["num_attention_heads"]),
        "kv_lora_rank": int(sizes["kv_lora_rank"]),
        "qk_nope_head_dim": int(sizes["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(sizes["qk_rope_head_dim"]),
        "v_head_dim": int(sizes["v_head_dim"]),
        "rms_eps": float(sizes["rms_norm_eps"]),
        "kv_norm_eps": float(sizes["kv_norm_eps"]),
        "rope_theta": float(sizes["rope_theta"]),
        "n_experts": int(sizes["n_routed_experts"]),
        "top_k": int(sizes["num_experts_per_tok"]),
        "n_group": int(sizes["n_group"]),
        "topk_group": int(sizes["topk_group"]),
        "first_expert": int(sizes["held"]["first_expert"]),
        "norm_topk_prob": bool(sizes["norm_topk_prob"]),
        "routed_scaling_factor": float(sizes["routed_scaling_factor"]),
        "aux_coef": float(sizes["aux_loss_coef"]),
    }


def make_conf(sizes: dict, seed: int, **over):
    """The program's configuration (`zoo.sparse_moe_lm`) at these sizes."""
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.nn.layers import dsa

    if float(sizes["kv_norm_eps"]) != dsa.LATENT_NORM_EPS:
        raise ValueError("the program's latent norm has a fixed eps of "
                         f"{dsa.LATENT_NORM_EPS}")
    if sizes["scoring_func"] != "sigmoid" or int(sizes["n_group"]) != 1 \
            or sizes["q_lora_rank"] is not None \
            or sizes["rope_scaling"] is not None or n_dense(sizes) != 1:
        raise ValueError("this builder is the sigmoid router with one group, "
                         "an uncompressed query projection, plain RoPE and "
                         "one leading dense layer")
    cfg = model_cfg(sizes)
    kw = dict(
        t=int(sizes["seq_len"]), d_model=int(sizes["hidden_size"]),
        n_blocks=int(sizes["num_hidden_layers"]), n_heads=cfg["n_heads"],
        latent_attention={
            "kv_lora_rank": cfg["kv_lora_rank"],
            "qk_nope_head_dim": cfg["qk_nope_head_dim"],
            "qk_rope_head_dim": cfg["qk_rope_head_dim"],
            "v_head_dim": cfg["v_head_dim"]},
        rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_eps"],
        first_dense=n_dense(sizes),
        dense_hidden=int(sizes["intermediate_size"]),
        n_experts=cfg["n_experts"], top_k=cfg["top_k"],
        expert_hidden=int(sizes["moe_intermediate_size"]),
        experts_held=(cfg["first_expert"], int(sizes["held"]["experts"])),
        scoring="sigmoid",
        routed_scaling_factor=cfg["routed_scaling_factor"],
        shared_hidden=int(sizes["n_shared_experts"])
        * int(sizes["moe_intermediate_size"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        aux_loss_weight=cfg["aux_coef"], lr=float(sizes["learning_rate"]),
        adam_mean_decay=float(sizes["adam_mean_decay"]),
        adam_var_decay=float(sizes["adam_var_decay"]),
        seed=seed % (2 ** 31 - 1), dtype_policy=dict(sizes["dtype_policy"]))
    kw.update(over)
    return zoo.sparse_moe_lm(int(sizes["held"]["ids"]), **kw)


def make_net(sizes: dict, seed: int, **over):
    """The initialised network, its routers' selection biases drawn as N(0,
    `router_bias_std`^2) from `router_bias_seed`: a frozen float32 leaf that
    `init` leaves at zero, and one bias for every run as a checkpoint has
    one (drawn from the run's seed it started the share of pairs held at 10%
    to 15% by the seed, and the cell's rate spread by 2.4%: PERF.md
    section 6)."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.graph import ComputationGraph

    net = ComputationGraph(make_conf(sizes, seed, **over)).init()
    rng = np.random.default_rng([int(sizes["router_bias_seed"]), 0xB1A5])
    tree = dict(net.params_tree)
    for i in range(n_dense(sizes), int(sizes["num_hidden_layers"])):
        bias = tree[f"ffn{i}"]["gate_b"]
        tree[f"ffn{i}"] = dict(tree[f"ffn{i}"], gate_b=jnp.asarray(
            rng.normal(0.0, float(sizes["router_bias_std"]), bias.shape),
            bias.dtype))
    net.params_tree = tree
    return net


_ATTENTION = {"ln1": ("ln_a", "gamma"), "ln2": ("ln_f", "gamma"),
              "wq": ("attn", "Wq"), "wdkv": ("attn", "Wdkv"),
              "kv_norm": ("attn", "gamma_kv"), "wukv": ("attn", "Wukv"),
              "wo": ("attn", "Wo")}
_DENSE = {"w_gate": ("ffn", "W_gate"), "w_up": ("ffn", "W_up"),
          "w_down": ("ffn", "W_down")}
_EXPERTS = {"router": ("ffn", "gate_w"), "router_bias": ("ffn", "gate_b"),
            "w_gate": ("ffn", "w_gate"), "w_up": ("ffn", "w_up"),
            "w_down": ("ffn", "w_down"), "ws_gate": ("ffn", "shared_gate"),
            "ws_up": ("ffn", "shared_up"), "ws_down": ("ffn", "shared_down")}


def reference_params(tree, sizes: dict, copy_dense: bool = False) -> dict:
    """The program's parameter tree under the reference's names (the same
    arrays; `copy_dense`: the leading layer's as copies, because
    `two_pass_check` copies only what it stacks and the head's three before
    the train step is donated the program's)."""
    import jax.numpy as jnp

    def layer(i, names):
        return {ref: tree[f"{stem}{i}"][leaf]
                for ref, (stem, leaf) in names.items()}

    first = n_dense(sizes)
    dense = layer(0, {**_ATTENTION, **_DENSE}) if first else None
    if dense is not None and copy_dense:
        dense = {k: jnp.copy(v) for k, v in dense.items()}
    return {"embed": tree["emb"]["W"], "dense": dense,
            "layers": [layer(i, {**_ATTENTION, **_EXPERTS}) for i in range(
                first, int(sizes["num_hidden_layers"]))],
            "norm": tree["ln_out"]["gamma"], "head": tree["out"]["W"]}


def compared_leaves(sizes: dict) -> dict:
    """program leaf (layer, name) -> (path in the reference's stacked tree,
    index among the expert layers or None): the embedding, the head, five
    leaves of the leading dense layer, and ten each of the first and the last
    expert layer."""
    first, last = n_dense(sizes), int(sizes["num_hidden_layers"]) - 1
    out = {("emb", "W"): (("embed",), None), ("out", "W"): (("head",), None)}
    if first:
        for ref in ("wq", "wukv", "wo", "w_gate", "w_down"):
            stem, leaf = {**_ATTENTION, **_DENSE}[ref]
            out[f"{stem}0", leaf] = (("dense", ref), None)
    for i in sorted({first, last}):
        for ref in ("wq", "wdkv", "wukv", "wo", "router", "w_gate", "w_up",
                    "w_down", "ws_gate", "ws_down"):
            stem, leaf = {**_ATTENTION, **_EXPERTS}[ref]
            out[f"{stem}{i}", leaf] = (("layers", ref), i - first)
    return out


class _ExpertLayers:
    """`two_pass_check` formats a collected set's name over `range(n)`: the
    i-th expert layer is the program's block `i + first`."""

    def __init__(self, first: int):
        self.first = first

    def format(self, i: int) -> str:
        return f"ffn{i + self.first}.expert_idx"


def _plant(fault, net, sizes: dict):
    """Make a first-pass `fault` on the program's side; returns what undoes
    what the harness does not restore itself (it restores `params_tree`)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import dsa
    from deeplearning4j_tpu.parallel import expert

    blocks = range(n_dense(sizes), int(sizes["num_hidden_layers"]))
    layers = [net.layer_vertices[f"ffn{i}"].layer for i in blocks]

    def with_leaf(name, fn):
        tree = dict(net.params_tree)
        for i in blocks:
            tree[f"ffn{i}"] = dict(tree[f"ffn{i}"],
                                   **{name: fn(tree[f"ffn{i}"][name])})
        net.params_tree = tree

    if fault == "no_router_bias":            # the choice on s alone
        with_leaf("gate_b", jnp.zeros_like)
    elif fault == "no_shared_expert":        # its output multiplied by zero
        with_leaf("shared_down", jnp.zeros_like)
    elif fault == "no_routed_scaling":
        held = [layer.routed_scaling_factor for layer in layers]
        for layer in layers:
            layer.routed_scaling_factor = 1.0

        def undo():
            for layer, value in zip(layers, held):
                layer.routed_scaling_factor = value
        return undo
    elif fault == "no_latent_norm":          # c used as projected
        sound = dsa.rms_norm
        dsa.rms_norm = lambda x, gamma, eps: x
        return lambda: setattr(dsa, "rms_norm", sound)
    elif fault == "bias_in_weights":         # weights from s + b
        sound = expert.route_top_k

        def biased(gate_w, x, top_k, norm_topk_prob=True, *, scoring,
                   gate_b, routed_scaling_factor):
            probs, _, idx = sound(
                gate_w, x, top_k, norm_topk_prob, scoring=scoring,
                gate_b=gate_b, routed_scaling_factor=routed_scaling_factor)
            acc = probs.dtype
            s = jax.nn.sigmoid(x.astype(acc) @ gate_w.astype(acc))
            gate = jnp.take_along_axis(s + gate_b.astype(acc), idx, axis=-1)
            gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
            return probs, gate * routed_scaling_factor, idx
        expert.route_top_k = biased
        return lambda: setattr(expert, "route_top_k", sound)
    return lambda: None


def reference_check(net, sizes: dict, batch, *, positions: int = 256,
                    fault=None) -> dict:
    """`fit_check.two_pass_check` for this model: the routing E(t) of the
    expert layers is the one set compared as a limit of its own. Beside
    `fit_check.FAULTS`, the first pass can be given, on the program's side
    only: "no_router_bias" (the choice on the scores alone),
    "bias_in_weights" (the weights taken from score + bias),
    "no_routed_scaling" (factor 1), "no_shared_expert" (its output dropped),
    "no_latent_norm" (the compressed latent used without its RMS norm)."""
    from benchmark.harness import cells, fit_check

    first = n_dense(sizes)
    n_expert_layers = int(sizes["num_hidden_layers"]) - first
    routes = ("routes", _ExpertLayers(first)) + fit_check.ROUTES[2:]
    return fit_check.two_pass_check(
        net, batch, ref=cells.load_module("reference",
                                          sizes["check"]["reference"]),
        cfg=model_cfg(sizes),
        # the harness counts the layers it collects a set from and stacks
        sizes=dict(sizes, num_hidden_layers=n_expert_layers),
        limits=LIMITS, leaves=compared_leaves(sizes),
        rparams_of=lambda tree: reference_params(tree, sizes,
                                                 copy_dense=True),
        sets=(routes,), plant=lambda fault: _plant(fault, net, sizes),
        faults=FAULTS, positions=positions, fault=fault)


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
    # band's pairs at 128 + 64 and 128, the held experts' pairs, the shared
    # expert and layer 0's MLP).
    return fit_check.lm_cell(
        net, sizes, seed,
        forward=lambda params, ids: ref.forward_needed(
            reference_params(params, sizes), ids, cfg),
        check=lambda batch, fault: reference_check(net, sizes, batch,
                                                   fault=fault))
