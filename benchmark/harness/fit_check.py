"""The check of a `fit_ref` configuration against its plain reference,
whatever the language model: the program's parameter tree under the
reference's names, the leaves compared, the reference's two compiled passes,
the two-pass comparison (`two_pass_check`) and what `build` returns
(`lm_cell`). A configuration's `.py` keeps what differs: its `model_cfg`,
how it builds the program, the faults it can plant in its own layers, and
its `LIMITS`.

`configs/keye_vl2_30b_a3b.py` (PR 26) still carries private twins of all of
this; moving it here is a `benchmark` PR's (PERF.md section 7). What that
takes is one more entry of `sets` (the selected keys, with their overlap as
the score), its indexer's leaves in `reference_params`, and a hook for its
fault of the step's batch.
"""

from __future__ import annotations

import time

# The faults every configuration's check can plant: every matrix of the
# program rounded to float8_e4m3fn and back in the first pass; the train
# step not taken.
FAULTS = ("fp8", "state_unchanged")


def routing_agreement(routes_p, routes_r):
    """Share of a token's experts that program and reference agree on, by
    layer. Both `[layers, S, top_k]`."""
    import jax.numpy as jnp

    return [float(v) for v in jnp.mean(jnp.any(
        routes_p[:, :, :, None] == routes_r[:, :, None, :], axis=3),
        axis=(1, 2))]


# One set the program chooses under bf16 and the reference is then given,
# so that a near-tie that flips is reported once, by its own score, and not
# again in every gradient: (the keyword of `ref.forward` that takes it, the
# name `net.loss_and_gradients(collect=...)` hands layer i's out under, the
# score's name among the numbers, (program's, reference's) -> score by
# layer). A score's limit is `LIMITS[<name>_min]`. `ref.forward` returns
# `(logits, aux, *sets)` in the order a configuration lists them.
ROUTES = ("routes", "ffn{}.expert_idx", "routing_agreement",
          routing_agreement)


def reference_params(tree, n_layers: int) -> dict:
    """The program's parameter tree (`zoo.sparse_moe_lm`) under the
    reference's names (the same arrays, no copy)."""
    layers = []
    for i in range(n_layers):
        a, f = tree[f"attn{i}"], tree[f"ffn{i}"]
        layers.append({
            "ln1": tree[f"ln_a{i}"]["gamma"], "ln2": tree[f"ln_f{i}"]["gamma"],
            "wq": a["Wq"], "wk": a["Wk"], "wv": a["Wv"], "wo": a["Wo"],
            "q_norm": a["gamma_q"], "k_norm": a["gamma_k"],
            "router": f["gate_w"], "w_gate": f["w_gate"], "w_up": f["w_up"],
            "w_down": f["w_down"]})
    return {"embed": tree["emb"]["W"], "layers": layers,
            "norm": tree["ln_out"]["gamma"], "head": tree["out"]["W"]}


def compared_leaves(layers) -> dict:
    """program leaf (layer, name) -> (path in the reference's stacked tree,
    layer): the embedding, the head, and six leaves of each of `layers`."""
    out = {("emb", "W"): (("embed",), None), ("out", "W"): (("head",), None)}
    for i in sorted(set(layers)):
        out.update({
            (f"attn{i}", "Wq"): (("layers", "wq"), i),
            (f"attn{i}", "Wo"): (("layers", "wo"), i),
            (f"ffn{i}", "gate_w"): (("layers", "router"), i),
            (f"ffn{i}", "w_gate"): (("layers", "w_gate"), i),
            (f"ffn{i}", "w_up"): (("layers", "w_up"), i),
            (f"ffn{i}", "w_down"): (("layers", "w_down"), i)})
    return out


def get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def replaced(tree, path, value):
    """A copy of `tree` (dicts and lists, shallow) with `path` replaced."""
    if not path:
        return value
    new = list(tree) if isinstance(tree, list) else dict(tree)
    new[path[0]] = replaced(tree[path[0]], path[1:], value)
    return new


def stack_layers(rparams: dict) -> dict:
    """A reference's tree with its list of layers as one tree with a leading
    axis over the layers (a copy): the reference then scans one compiled
    layer, which is a quarter of the compile at four layers."""
    import jax
    import jax.numpy as jnp

    return dict(rparams, layers=jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *rparams["layers"]))


def rel(a, b) -> float:
    """||a - b|| / ||b|| in float32."""
    import jax.numpy as jnp

    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))


class Laps:
    """Seconds by part: `lap(name, *arrays)` waits for the arrays and books
    the time since the last lap under `name`."""

    def __init__(self):
        self.took, self._t0 = {}, time.perf_counter()

    def lap(self, name, *arrays) -> None:
        import jax

        jax.block_until_ready(arrays)
        now = time.perf_counter()
        self.took[name], self._t0 = round(now - self._t0, 2), now


def worst(readings: dict):
    """(key, value) of the largest reading, a NaN counting as the largest."""
    import math

    return max(readings.items(),
               key=lambda kv: kv[1] if math.isfinite(kv[1]) else math.inf)


def _mean_loss(ref, cfg, params, ids, labels, **given):
    """(mean cross-entropy + the aux term, logits, the sets the forward
    chose or was given)."""
    import jax
    import jax.numpy as jnp

    logits, aux, *chosen = ref.forward(params, ids, cfg, remat=True, **given)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    ce = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) - picked)
    return ce + cfg["aux_coef"] * aux, logits, chosen


def reference_own(ref, cfg):
    """jit: (params, ids, labels) -> (loss, *sets), the reference's own
    choices. Ids and labels are arguments, so that every seed runs the one
    compiled program."""
    import jax

    def fn(rparams, ids, labels):
        loss, _, chosen = _mean_loss(ref, cfg, rparams, ids, labels)
        return (loss, *chosen)
    return jax.jit(fn)


def reference_given(ref, cfg, paths: dict, step: int, names):
    """jit: (sub, params, ids, labels, *sets) -> ((loss, logits of every
    `step`-th position), gradients of the leaves `sub` holds), the reference
    given the sets `names` (keywords of `ref.forward`)."""
    import jax

    def fn(sub, rparams, ids, labels, *given):
        def loss_fn(sub):
            p = rparams
            for key, value in sub.items():
                p = replaced(p, paths[key], value)
            loss, logits, _ = _mean_loss(ref, cfg, p, ids, labels,
                                         **dict(zip(names, given)))
            return loss, logits[::step]
        return jax.value_and_grad(loss_fn, has_aux=True)(sub)
    return jax.jit(fn)


def two_pass_check(net, batch, *, ref, cfg, sizes: dict, limits: dict,
                   leaves: dict, rparams_of, sets=(ROUTES,), plant=None,
                   faults=(), positions: int = 256, fault=None) -> dict:
    """Program against reference on one staged batch, from the state the
    net holds now (parameters, Adam moments, step count). Returns
    `{"numbers": {...}, "problems": [...]}`.

    Two passes of the program are compared. (1) `net.loss_and_gradients`:
    the functions its train step differentiates, under its dtype policy,
    and out of the same pass each of `sets` (the experts each token was
    routed to). The reference computes (a) its own loss with its own
    choices, (b) loss, logits and gradients given the program's, so that a
    near-tie that bf16 flips at the router's top_k-th place is reported
    once, as agreement, and not again in every gradient: one token moved to
    another expert is a small share of that expert's tokens and several
    percent of its gradient. (2) The compiled train step itself, the one the
    window timed, taken once on the batch through `net.fit`: the change it
    makes to each compared leaf against the change the reference makes from
    the same state, by its own gradients (those of (b)) and its own Adam
    step (`ref.adam_update`, the hyperparameters from `sizes`). A state left
    unchanged reads 1 there.

    `fault` (never set by a cell; the builder's proof that the limits bite):
    one of `FAULTS`, or one of the configuration's own `faults`, which
    `plant(fault)` makes on the program's side of the first pass and whose
    return undoes it. `rparams_of(tree)` is the program's tree under the
    reference's names, `leaves` what `compared_leaves` gave."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    known = FAULTS + tuple(faults)
    if fault is not None and fault not in known:
        raise ValueError(f"unknown fault {fault!r}: one of {known}")
    wrt = {}
    for layer, name in leaves:
        wrt.setdefault(layer, []).append(name)
    n_layers = int(sizes["num_hidden_layers"])
    collect = [fmt.format(i) for _, fmt, _, _ in sets
               for i in range(n_layers)]

    # The first pass's faults change the program's side only, and are undone
    # before the reference reads the parameters.
    held = net.params_tree
    undo = plant(fault) if plant is not None and fault in faults else None
    if fault == "fp8":
        net.params_tree = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim >= 2 else a, held)
    laps = Laps()
    try:
        loss_p, grads_p, values = net.loss_and_gradients(
            batch, wrt=wrt, collect=["out"] + collect)
        laps.lap("program", loss_p, grads_p)
    finally:
        net.params_tree = held
        if undo is not None:
            undo()
    ids = jnp.asarray(batch.features)[0].astype(jnp.int32)
    labels = jnp.asarray(batch.labels)[0].astype(jnp.int32)
    step = max(1, int(ids.shape[0]) // positions)
    logits_p = values["out"][0, ::step].astype(jnp.float32)
    sets_p = [jnp.stack([values[fmt.format(i)][0] for i in range(n_layers)])
              for _, fmt, _, _ in sets]
    del values
    loss_p, grads_p = float(loss_p), jax.device_get(grads_p)

    # The state the step starts from: the reference's own copy of the
    # parameters (the step is donated the program's), the compared leaves'
    # moments and the count of steps taken. The moments wait on the host.
    rparams = rparams_of(net.params_tree)
    rparams = stack_layers(dict(rparams, **{
        key: jnp.copy(rparams[key]) for key in ("embed", "norm", "head")}))
    moments = {key: tuple(np.asarray(net.opt_state[key[0]][mv][key[1]])
                          for mv in "mv") for key in leaves}
    taken = int(net.iteration)
    laps.lap("snapshot", rparams)
    if fault != "state_unchanged":
        net.fit(batch)
    update_p = {}
    for (layer, name), (path, at) in leaves.items():
        before = get(rparams, path)
        update_p[layer, name] = np.asarray(
            net.params_tree[layer][name] - (before if at is None
                                            else before[at]))
    laps.lap("program_step")

    loss_own, *sets_r = reference_own(ref, cfg)(rparams, ids, labels)
    laps.lap("reference_own", loss_own)
    scores = {name: score(mine, theirs) for (_, _, name, score), mine, theirs
              in zip(sets, sets_p, sets_r)}
    del sets_r
    paths = {".".join(path): path for path, _ in leaves.values()}
    sub = {key: get(rparams, path) for key, path in paths.items()}
    (loss_given, logits_r), grads_r = reference_given(
        ref, cfg, paths, step, [kw for kw, _, _, _ in sets])(
            sub, rparams, ids, labels, *sets_p)
    grads_r = jax.device_get(grads_r)
    laps.lap("reference_given", loss_given)

    adam = jax.jit(lambda g, m, v: ref.adam_update(
        g, m, v, taken + 1, float(sizes["learning_rate"]),
        float(sizes["adam_mean_decay"]), float(sizes["adam_var_decay"])))
    numbers = {
        "loss_program": loss_p, "loss_reference_own": float(loss_own),
        "loss_reference_given": float(loss_given),
        "loss_rel": abs(loss_p - float(loss_own)) / abs(float(loss_own)),
        "loss_rel_given": abs(loss_p - float(loss_given))
        / abs(float(loss_given)),
        **scores,
        "logits_rel": rel(logits_p, logits_r),
        "positions": int(logits_r.shape[0]), "steps_before": taken,
        "grad_rel": {}, "grad_norm": {}, "update_rel": {},
        "limits": dict(limits), "fault": fault,
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
    laps.lap("compare")
    numbers["seconds_by_part"] = laps.took

    problems = []
    for name, by_layer in scores.items():
        if min(by_layer) < limits[f"{name}_min"]:
            problems.append(f"{name.replace('_', ' ')} {by_layer} under "
                            f"{limits[f'{name}_min']}")
    for key in ("loss_rel", "loss_rel_given"):
        if not numbers[key] <= limits["loss_rel"]:
            problems.append(f"{key} {numbers[key]:.3g} over "
                            f"{limits['loss_rel']}")
    if not numbers["logits_rel"] <= limits["logits_rel"]:
        problems.append(f"logits_rel {numbers['logits_rel']:.3g} over "
                        f"{limits['logits_rel']}")
    for key, what in (("grad_rel", "gradient"), ("update_rel", "update")):
        name, value = worst(numbers[key])
        numbers[f"{key}_max"] = value
        if not value <= limits[key]:
            problems.append(f"{what} of {name}: {value:.3g} over "
                            f"{limits[key]}")
    return {"numbers": numbers, "problems": problems}


def compared(numbers: dict) -> dict:
    """`{name: [reading, limit]}` for every number of a check's `numbers`
    that was held to a limit, under the limit's own name: the least of a
    score that has a floor (`<name>_min`), the worst leaf of a dictionary
    of readings (`<name>_max`), the number itself otherwise;
    `loss_rel_given` stands under `loss_rel`'s limit. What the run's last
    line carries, so that a run that missed says which limit and by how
    much."""
    out = {}
    for name, limit in numbers["limits"].items():
        if name.endswith("_min"):
            reading = min(numbers[name[:-len("_min")]])
        else:
            reading = numbers.get(f"{name}_max", numbers[name])
        out[name] = [float(reading), float(limit)]
    out["loss_rel_given"] = [float(numbers["loss_rel_given"]),
                             float(numbers["limits"]["loss_rel"])]
    return out


def lm_cell(net, sizes: dict, seed: int, *, forward, check) -> dict:
    """What a language model's `build` returns to the `fit` and `fit_ref`
    drivers: `staged_batches` seeded batches of `batch_per_chip` sequences
    of `seq_len` ids uniform over the held slice, labels the next ids,
    cached on the device. `forward(params, ids)` is what `fit_mfu` counts
    multiply-adds from, `check(batch, fault)` the reference check on a
    staged batch."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import (
        DeviceCacheDataSetIterator)

    S, V = int(sizes["seq_len"]), int(sizes["held"]["ids"])
    B = int(sizes["batch_per_chip"])
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
    return {"net": net, "trainer": net, "iterator": iterator,
            "batches": batches, "samples_per_epoch": B * len(batches),
            "steps_per_epoch": len(batches), "forward": forward,
            "example_input": lambda: jax.ShapeDtypeStruct((S,),
                                                          jax.numpy.int32),
            "reference_check": lambda fault=None: check(
                next(iter(iterator)), fault)}
