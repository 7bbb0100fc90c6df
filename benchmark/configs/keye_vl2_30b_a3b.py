"""Keye-VL-2.0-30B-A3B's language model for `fit`, one chip's share: the
network, its staged data, and the check against the plain reference.

`build(sizes, seed, chips)` returns what the `fit` and `fit_ref` drivers
need. Everything that is a size comes from the JSON beside this file. The
reference (`benchmark/reference/sparse_moe_lm.py`) is given the same share:
the held experts and the held slice of the vocabulary.
"""

from __future__ import annotations

source = ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
          "config.json")

# What the on-chip check compares, each limit set between two readings on the
# chip, both taken when the program had made the cell's `check.at_step` = 320
# train steps (my chip runs, PR 34, calls A, DE and F; PERF.md section 4): the
# worst the program gave over its sound runs (19 at 320 steps on 11 seeds,
# and the sound first passes of 6 more checks at 323 and 324), and the least
# it gave under a planted fault run through the harness on three seeds, which
# has to come out as not correct: every matrix rounded to float8_e4m3fn (the
# nearest precision below the configuration's bf16), half the keys selected,
# the state left unchanged, half the positions trained on. Every reading
# moves with the steps taken (the loss has fallen from 9.9 to 2.1 by step
# 320, the two cached batches half memorised), which is why the count is
# fixed; PR 26's limits, set at 90 to 162 steps, are in `git show
# f11d091:benchmark/configs/keye_vl2_30b_a3b.py`. "x above" is limit / worst
# sound reading, "x under" least faulty reading / limit; for a floor both
# are taken on 1 - the share.
LIMITS = {
    # bf16 index scores flip near-ties at the index_top_k-th place: smallest
    # overlap of a layer (the last), sound 0.9910 to 0.9915 | fp8 0.9606 to
    # 0.9612, half the keys 0.586: 2.2 x above, 1.9 x under fp8
    "selection_overlap_min": 0.98,
    # and near-ties at the router's top_k-th place: share of a token's
    # experts that program and reference agree on, worst layer (the last),
    # sound 0.9773 to 0.9797 | fp8 0.9179 to 0.9187, half the keys 0.898 to
    # 0.900: 2.0 x above, 1.8 x under fp8
    "routing_agreement_min": 0.955,
    # loss, the program's (bf16 compute) against the reference's (float32),
    # with its own selection and routing and given the program's:
    # |difference| / reference, sound 2.4e-5 to 2.7e-4 | fp8 1.18e-2 to
    # 1.43e-2, half the keys 2.3e-2 with the reference's own selection (and
    # sound given the program's): 5.6 x above, 7.9 x under. At 320 steps the
    # loss separates fp8 from sound sixtyfold, where at 100 to 200 steps
    # (8e-5, sound to 9.2e-5, fp8 from 1.3e-4) it did not
    "loss_rel": 1.5e-3,
    # logits of 256 positions and every compared gradient, the reference
    # given the program's selection and routing: ||program - reference|| /
    # ||reference||. Logits sound 0.0053 to 0.0055 | fp8 0.0527 to 0.0531:
    # 3.7 x above, 2.6 x under. The worst gradient (attn0.Wo every time)
    # sound 0.068 to 0.118 at 320 steps and 0.090 to 0.213 at 323 and 324: it
    # swings threefold by the seed | fp8 2.23 to 2.53: 2.8 x above, 3.7 x
    # under (0.20, the limit until PR 34, is inside the sound readings here)
    "logits_rel": 0.02,
    "grad_rel": 0.6,
    # the change one compiled train step makes to a leaf against the
    # reference's Adam step from the same state, worst leaf (attn0.Wo every
    # time): sound 0.043 to 0.064 | half the positions 0.288 to 0.312 (its
    # best leaf 0.071), half the keys 0.206 to 0.213, state unchanged 1 on
    # every leaf: 2.2 x above, 2.1 x under half the positions; fp8 reads
    # 0.114 to 0.124 and is the other limits' to catch
    "update_rel": 0.14,
}


def model_cfg(sizes: dict) -> dict:
    """The reference's `cfg` from the configuration's sizes."""
    sa = sizes["sa_config"]
    return {
        "n_heads": int(sizes["num_attention_heads"]),
        "n_kv_heads": int(sizes["num_key_value_heads"]),
        "head_dim": int(sizes["head_dim"]),
        "rope_theta": float(sizes["rope_theta"]),
        "rms_eps": float(sizes["rms_norm_eps"]),
        "index_n_heads": int(sa["indexer_num_heads"]),
        "index_head_dim": int(sa["indexer_head_dim"]),
        "index_top_k": int(sa["topk"]),
        "n_experts": int(sizes["num_experts"]),
        "top_k": int(sizes["num_experts_per_tok"]),
        "first_expert": int(sizes["held"]["first_expert"]),
        "norm_topk_prob": bool(sizes["norm_topk_prob"]),
        "aux_coef": float(sizes["aux_loss_coef"]),
    }


def make_conf(sizes: dict, seed: int, **over):
    """The program's configuration (`zoo.sparse_moe_lm`) at these sizes."""
    from deeplearning4j_tpu.models import zoo

    cfg = model_cfg(sizes)
    kw = dict(
        t=int(sizes["seq_len"]), d_model=int(sizes["hidden_size"]),
        n_blocks=int(sizes["num_hidden_layers"]), n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_kv_heads"], head_dim=cfg["head_dim"],
        n_experts=cfg["n_experts"], top_k=cfg["top_k"],
        expert_hidden=int(sizes["moe_intermediate_size"]),
        experts_held=(cfg["first_expert"], int(sizes["held"]["experts"])),
        index_top_k=cfg["index_top_k"], index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"], rope_theta=cfg["rope_theta"],
        rms_eps=cfg["rms_eps"], norm_topk_prob=cfg["norm_topk_prob"],
        aux_loss_weight=cfg["aux_coef"], lr=float(sizes["learning_rate"]),
        adam_mean_decay=float(sizes["adam_mean_decay"]),
        adam_var_decay=float(sizes["adam_var_decay"]),
        seed=seed % (2 ** 31 - 1), dtype_policy=dict(sizes["dtype_policy"]))
    kw.update(over)
    return zoo.sparse_moe_lm(int(sizes["held"]["ids"]), **kw)


def reference_params(tree, n_layers: int) -> dict:
    """The program's parameter tree under the reference's names (the same
    arrays, no copy)."""
    layers = []
    for i in range(n_layers):
        a, f = tree[f"attn{i}"], tree[f"ffn{i}"]
        layers.append({
            "ln1": tree[f"ln_a{i}"]["gamma"], "ln2": tree[f"ln_f{i}"]["gamma"],
            "wq": a["Wq"], "wk": a["Wk"], "wv": a["Wv"], "wo": a["Wo"],
            "q_norm": a["gamma_q"], "k_norm": a["gamma_k"],
            "idx_wq": a["Wiq"], "idx_wk": a["Wik"], "idx_w": a["Wiw"],
            "idx_k_norm_g": a["gamma_ik"], "idx_k_norm_b": a["beta_ik"],
            "router": f["gate_w"], "w_gate": f["w_gate"], "w_up": f["w_up"],
            "w_down": f["w_down"]})
    return {"embed": tree["emb"]["W"], "layers": layers,
            "norm": tree["ln_out"]["gamma"], "head": tree["out"]["W"]}


def stack_layers(rparams: dict) -> dict:
    """The reference's tree with its list of layers as one tree with a
    leading axis over the layers (a copy): the reference then scans one
    compiled layer, which is a quarter of the compile at four layers."""
    import jax
    import jax.numpy as jnp

    return dict(rparams, layers=jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *rparams["layers"]))


# program leaf (layer, name) -> (path in the reference's stacked tree, layer)
def _compared_leaves(n_layers: int):
    out = {("emb", "W"): (("embed",), None), ("out", "W"): (("head",), None)}
    for i in sorted({0, n_layers - 1}):
        out.update({
            (f"attn{i}", "Wq"): (("layers", "wq"), i),
            (f"attn{i}", "Wo"): (("layers", "wo"), i),
            (f"ffn{i}", "gate_w"): (("layers", "router"), i),
            (f"ffn{i}", "w_gate"): (("layers", "w_gate"), i),
            (f"ffn{i}", "w_up"): (("layers", "w_up"), i),
            (f"ffn{i}", "w_down"): (("layers", "w_down"), i)})
    return out


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _set(tree, path, value):
    """A copy of `tree` (dicts and lists, shallow) with `path` replaced."""
    if not path:
        return value
    new = list(tree) if isinstance(tree, list) else dict(tree)
    new[path[0]] = _set(tree[path[0]], path[1:], value)
    return new


def collected_sets(n_layers: int):
    """What `net.loss_and_gradients(collect=...)` is asked for beside the
    logits: the keys each attention layer selected and the experts each
    token was routed to, out of the very pass whose gradients are compared
    (`<layer>.selected_keys`, `<layer>.expert_idx`)."""
    return ([f"attn{i}.selected_keys" for i in range(n_layers)]
            + [f"ffn{i}.expert_idx" for i in range(n_layers)])


def _mean_loss(ref, cfg, params, ids, labels, **given):
    import jax
    import jax.numpy as jnp

    logits, aux, keeps, routes = ref.forward(params, ids, cfg, remat=True,
                                             **given)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    ce = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) - picked)
    return ce + cfg["aux_coef"] * aux, logits, keeps, routes


def reference_own(ref, cfg):
    """jit: (params, ids, labels) -> (loss, [keep], [idx]), the reference's
    own selection and routing. Ids and labels are arguments, so that every
    seed runs the one compiled program."""
    import jax

    def fn(rparams, ids, labels):
        loss, _, keeps, routes = _mean_loss(ref, cfg, rparams, ids, labels)
        return loss, keeps, routes
    return jax.jit(fn)


def reference_given(ref, cfg, paths: dict, step: int):
    """jit: (sub, params, ids, labels, keeps, routes) -> ((loss, logits of
    every `step`-th position), gradients of the leaves `sub` holds), the
    reference given a selection and a routing."""
    import jax

    def fn(sub, rparams, ids, labels, keeps, routes):
        def loss_fn(sub):
            p = rparams
            for key, value in sub.items():
                p = _set(p, paths[key], value)
            loss, logits, _, _ = _mean_loss(ref, cfg, p, ids, labels,
                                            keeps=keeps, routes=routes)
            return loss, logits[::step]
        return jax.value_and_grad(loss_fn, has_aux=True)(sub)
    return jax.jit(fn)


def _half_positions(batch):
    """The batch with the labels mask of its later half of positions zeroed
    (same shapes and dtypes: the compiled step takes it as it is)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet

    mask = jnp.asarray(batch.labels_mask)
    first = jnp.arange(mask.shape[1]) < mask.shape[1] // 2
    return DataSet(batch.features, batch.labels, batch.features_mask,
                   jnp.where(first[None], mask, 0).astype(mask.dtype))


FAULTS = ("fp8", "topk_half", "state_unchanged", "half_positions")


def reference_check(net, sizes: dict, batch, *, positions: int = 256,
                    fault=None) -> dict:
    """Program against reference on one staged batch, from the state the
    net holds now (parameters, Adam moments, step count). Returns
    `{"numbers": {...}, "problems": [...]}`.

    Two passes of the program are compared. (1) `net.loss_and_gradients`:
    the functions its train step differentiates, under its dtype policy,
    and out of the same pass the keys each layer selected and the experts
    each token was routed to. The reference computes (a) its own loss with
    its own selection and routing, (b) loss, logits and gradients given the
    program's selection and routing, so that a near-tie that bf16 scores
    flip (at the indexer's index_top_k-th place, at the router's top_k-th)
    is reported once, as overlap or agreement, and not again in every
    gradient: one token moved to another expert is a small share of that
    expert's tokens and several percent of its gradient. (2) The
    compiled train step itself, the one the window timed, taken once on the
    batch through `net.fit`: the change it makes to each compared leaf
    against the change the reference makes from the same state, by its own
    gradients (those of (b)) and its own Adam step (`ref.adam_update`, the
    hyperparameters from the configuration's file). A state left unchanged
    reads 1 there.

    `fault` (never set by a cell; the builder's proof that the limits bite),
    of the first pass: "fp8" rounds every matrix of the program to
    float8_e4m3fn and back, "topk_half" lets the program select half as
    many keys; of the step: "state_unchanged" does not take it,
    "half_positions" lets it train on the first half of the positions."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import cells

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}: one of {FAULTS}")
    ref = cells.load_module("reference", sizes["check"]["reference"])
    cfg = model_cfg(sizes)
    n_layers = int(sizes["num_hidden_layers"])
    leaves = _compared_leaves(n_layers)
    wrt = {}
    for layer, name in leaves:
        wrt.setdefault(layer, []).append(name)

    # The first pass's faults change the program's side only, and are undone
    # before the reference reads the parameters.
    attn = [net.layer_vertices[f"attn{i}"].layer for i in range(n_layers)]
    held = net.params_tree
    if fault == "topk_half":
        for layer in attn:
            layer.index_top_k = cfg["index_top_k"] // 2
    elif fault == "fp8":
        net.params_tree = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim >= 2 else a, held)
    took, t0 = {}, time.perf_counter()

    def lap(name, *arrays):
        nonlocal t0
        jax.block_until_ready(arrays)
        took[name], t0 = round(time.perf_counter() - t0, 2), time.perf_counter()

    try:
        loss_p, grads_p, values = net.loss_and_gradients(
            batch, wrt=wrt, collect=["out"] + collected_sets(n_layers))
        lap("program", loss_p, grads_p)
    finally:
        net.params_tree = held
        for layer in attn:
            layer.index_top_k = cfg["index_top_k"]
    ids = jnp.asarray(batch.features)[0].astype(jnp.int32)
    labels = jnp.asarray(batch.labels)[0].astype(jnp.int32)
    S = int(ids.shape[0])
    step = max(1, S // positions)
    logits_p = values["out"][0, ::step].astype(jnp.float32)
    keeps_p = jnp.stack([values[f"attn{i}.selected_keys"][0]
                         for i in range(n_layers)])
    routes_p = jnp.stack([values[f"ffn{i}.expert_idx"][0]
                          for i in range(n_layers)])
    del values
    loss_p, grads_p = float(loss_p), jax.device_get(grads_p)

    # The state the step starts from: the reference's own copy of the
    # parameters (the step is donated the program's), the compared leaves'
    # moments and the count of steps taken. The moments wait on the host.
    rparams = reference_params(net.params_tree, n_layers)
    rparams = stack_layers(dict(rparams, **{
        key: jnp.copy(rparams[key]) for key in ("embed", "norm", "head")}))
    moments = {key: tuple(np.asarray(net.opt_state[key[0]][mv][key[1]])
                          for mv in "mv") for key in leaves}
    taken = int(net.iteration)
    lap("snapshot", rparams)
    if fault != "state_unchanged":
        net.fit(_half_positions(batch) if fault == "half_positions" else batch)
    update_p = {}
    for (layer, name), (path, at) in leaves.items():
        before = _get(rparams, path)
        update_p[layer, name] = np.asarray(
            net.params_tree[layer][name] - (before if at is None
                                            else before[at]))
    lap("program_step")

    loss_own, keeps_r, routes_r = reference_own(ref, cfg)(rparams, ids,
                                                          labels)
    lap("reference_own", loss_own)
    overlap = [float(v) for v in jnp.mean(
        jnp.sum(keeps_p & keeps_r, axis=2) / jnp.sum(keeps_r, axis=2), axis=1)]
    agreement = [float(v) for v in jnp.mean(jnp.any(
        routes_p[:, :, :, None] == routes_r[:, :, None, :], axis=3),
        axis=(1, 2))]
    del keeps_r, routes_r
    paths = {".".join(path): path for path, _ in leaves.values()}
    sub = {key: _get(rparams, path) for key, path in paths.items()}
    (loss_given, logits_r), grads_r = reference_given(ref, cfg, paths, step)(
        sub, rparams, ids, labels, keeps_p, routes_p)
    grads_r = jax.device_get(grads_r)
    lap("reference_given", loss_given)

    def rel(a, b):
        a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
        return float(jnp.linalg.norm((a - b).ravel())
                     / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))

    adam = jax.jit(lambda g, m, v: ref.adam_update(
        g, m, v, taken + 1, float(sizes["learning_rate"]),
        float(sizes["adam_mean_decay"]), float(sizes["adam_var_decay"])))
    numbers = {
        "loss_program": loss_p, "loss_reference_own": float(loss_own),
        "loss_reference_given": float(loss_given),
        "loss_rel": abs(loss_p - float(loss_own)) / abs(float(loss_own)),
        "loss_rel_given": abs(loss_p - float(loss_given))
        / abs(float(loss_given)),
        "selection_overlap": overlap, "routing_agreement": agreement,
        "logits_rel": rel(logits_p, logits_r),
        "positions": int(logits_r.shape[0]), "steps_before": taken,
        "grad_rel": {}, "grad_norm": {}, "update_rel": {},
        "limits": dict(LIMITS),
    }
    used = np.unique(np.asarray(ids))[:64]       # embedding rows that trained
    for (layer, name), (path, at) in leaves.items():
        gp, gr = grads_p[layer][name], grads_r[".".join(path)]
        if at is not None:
            gr = gr[at]
        numbers["update_rel"][f"{layer}.{name}"] = rel(
            update_p[layer, name], adam(gr, *moments[layer, name]))
        if layer == "emb":
            gp, gr = gp[used], gr[used]
        elif gp.ndim == 3:                        # one held expert's matrix
            e = min(3, gp.shape[0] - 1)
            gp, gr = gp[e], gr[e]
        numbers["grad_rel"][f"{layer}.{name}"] = rel(gp, gr)
        numbers["grad_norm"][f"{layer}.{name}"] = float(jnp.linalg.norm(gr))
    lap("compare")
    numbers["seconds_by_part"] = took

    problems = []
    if min(overlap) < LIMITS["selection_overlap_min"]:
        problems.append(f"selection overlap {overlap} under "
                        f"{LIMITS['selection_overlap_min']}")
    if min(agreement) < LIMITS["routing_agreement_min"]:
        problems.append(f"routing agreement {agreement} under "
                        f"{LIMITS['routing_agreement_min']}")
    for key in ("loss_rel", "loss_rel_given"):
        if not numbers[key] <= LIMITS["loss_rel"]:
            problems.append(f"{key} {numbers[key]:.3g} over "
                            f"{LIMITS['loss_rel']}")
    if not numbers["logits_rel"] <= LIMITS["logits_rel"]:
        problems.append(f"logits_rel {numbers['logits_rel']:.3g} over "
                        f"{LIMITS['logits_rel']}")
    for key, what in (("grad_rel", "gradient"), ("update_rel", "update")):
        worst = max(numbers[key].items(), key=lambda kv: kv[1]
                    if np.isfinite(kv[1]) else np.inf)
        numbers[f"{key}_max"] = worst[1]
        if not worst[1] <= LIMITS[key]:
            problems.append(f"{what} of {worst[0]}: {worst[1]:.3g} over "
                            f"{LIMITS[key]}")
    return {"numbers": numbers, "problems": problems}


def build(sizes: dict, seed: int, chips: int) -> dict:
    import jax
    import numpy as np

    from benchmark.harness import cells
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import (
        DeviceCacheDataSetIterator)
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    if chips != 1:
        raise ValueError("this configuration is one chip's share of eight: "
                         "the cell takes 1 chip")
    net = ComputationGraph(make_conf(sizes, seed)).init()
    S, V = int(sizes["seq_len"]), int(sizes["held"]["ids"])
    B = int(sizes["batch_per_chip"])
    n_layers = int(sizes["num_hidden_layers"])

    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(int(sizes["staged_batches"])):
        ids = rng.integers(0, V, (B, S + 1)).astype(np.int32)
        # The engine sums the loss over positions and divides by the batch:
        # a labels mask of 1/S makes it the mean over positions.
        batches.append(DataSet(ids[:, :-1], ids[:, 1:], None,
                               np.full((B, S), 1.0 / S, np.float32)))
    iterator = DeviceCacheDataSetIterator(
        batches, transfer_dtype=net.dtype_policy.transfer_dtype)

    ref = cells.load_module("reference", sizes["check"]["reference"])
    cfg = model_cfg(sizes)

    def forward(params, ids):
        # What `fit_mfu` counts multiply-adds from: the reference's forward
        # in the form whose products are the ones the mathematics needs.
        return ref.forward_needed(reference_params(params, n_layers), ids,
                                  cfg)

    def example_input():
        return jax.ShapeDtypeStruct((S,), jax.numpy.int32)

    def check(fault=None):
        return reference_check(net, sizes, next(iter(iterator)), fault=fault)

    return {"net": net, "trainer": net, "iterator": iterator,
            "batches": batches, "samples_per_epoch": B * len(batches),
            "steps_per_epoch": len(batches),
            "forward": forward, "example_input": example_input,
            "reference_check": check}
