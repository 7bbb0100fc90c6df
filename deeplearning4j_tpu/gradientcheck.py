"""Finite-difference gradient checking.

Equivalent of the reference's `gradientcheck/GradientCheckUtil.java:76,211` —
the correctness backbone of the whole test suite (SURVEY.md §4): central
differences `(C(w+eps) - C(w-eps)) / 2eps` per parameter vs the analytic
gradient, for both MultiLayerNetwork and ComputationGraph.

Networks should be built with `.dtype("float64")` (and tests enable
jax_enable_x64) — the reference likewise runs gradient checks in double
precision.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet


def _score_fn(net, ds):
    """The net's loss on one batch as a function of its parameters
    (deterministic forward: no dropout, running BatchNorm statistics)."""
    batch = net._batch(net._as_data(ds))
    state = net.state

    def score(params):
        return net._forward_loss(params, state, batch, None, False)[0]

    return score


def check_gradients(
    net,
    data,
    epsilon: float = 1e-6,
    max_rel_error: float = 1e-3,
    min_abs_error: float = 1e-8,
    print_results: bool = False,
    subset: Optional[int] = None,
    seed: int = 12345,
) -> bool:
    """Run the central-difference check. Returns True if every checked
    parameter's relative error is under `max_rel_error` (params whose absolute
    error is under `min_abs_error` pass regardless — reference semantics).

    `subset`: check only N randomly-chosen parameters (for big nets).
    """
    if not isinstance(data, (DataSet, MultiDataSet)):
        data = DataSet(*data)
    score = _score_fn(net, data)

    params = net.params_tree
    score_jit = jax.jit(score)
    grads = jax.jit(jax.grad(score))(params)

    flat_grads, _ = jax.tree_util.tree_flatten(grads)
    flat_params, treedef = jax.tree_util.tree_flatten(params)
    analytic = np.concatenate([np.asarray(g).reshape(-1) for g in flat_grads])
    flat = np.concatenate([np.asarray(p).reshape(-1) for p in flat_params])
    n = flat.size

    idxs = np.arange(n)
    if subset is not None and subset < n:
        idxs = np.random.RandomState(seed).choice(n, subset, replace=False)

    shapes = [np.asarray(p).shape for p in flat_params]
    dtypes = [np.asarray(p).dtype for p in flat_params]

    def rebuild(vec):
        leaves, pos = [], 0
        for s, dt in zip(shapes, dtypes):
            cnt = int(np.prod(s)) if s else 1
            leaves.append(jnp.asarray(vec[pos : pos + cnt].reshape(s), dt))
            pos += cnt
        return jax.tree_util.tree_unflatten(treedef, leaves)

    n_pass = n_fail = 0
    max_err_seen = 0.0
    for i in idxs:
        orig = flat[i]
        flat[i] = orig + epsilon
        plus = float(score_jit(rebuild(flat)))
        flat[i] = orig - epsilon
        minus = float(score_jit(rebuild(flat)))
        flat[i] = orig
        numeric = (plus - minus) / (2 * epsilon)
        a = analytic[i]
        abs_err = abs(a - numeric)
        denom = abs(a) + abs(numeric)
        rel_err = abs_err / denom if denom > 0 else 0.0
        ok = rel_err < max_rel_error or abs_err < min_abs_error
        max_err_seen = max(max_err_seen, rel_err if abs_err >= min_abs_error else 0.0)
        if ok:
            n_pass += 1
        else:
            n_fail += 1
            if print_results:
                print(f"param[{i}] FAIL analytic={a:.8g} numeric={numeric:.8g} relErr={rel_err:.4g}")
    if print_results:
        print(f"GradientCheck: {n_pass} passed, {n_fail} failed, maxRelErr={max_err_seen:.4g}")
    return n_fail == 0
