"""Gradient updaters as composable functional transformations.

TPU-native equivalent of ND4J's `GradientUpdater` family (Adam/Nesterov/AdaGrad/
AdaDelta/RMSProp/SGD), selected by the reference's `nn/updater/LayerUpdater.java:240-272`.
Instead of mutable per-variable updater objects, each updater is an
(init, update) pair over pytrees — the whole optimizer step fuses into the
jitted train step, so there is no per-parameter op dispatch.

`update(state, grads, lr, step)` returns `(new_state, deltas)`; the caller
applies `params = params - deltas` (matching the reference's
`stepFunction.step(params, grad)` subtract semantics,
`optimize/solvers/StochasticGradientDescent.java:58`).

State layout mirrors the param pytree, so updater-state checkpointing and
averaging (reference `updaterState.bin`, `ParallelWrapper.java:198-225`)
serialize the same way params do.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels import fused_update as _fused
from deeplearning4j_tpu.nn.conf.enums import Updater


class GradientUpdater(NamedTuple):
    name: str
    init: Callable[[Any], Any]  # params pytree -> state pytree
    update: Callable[[Any, Any, Any, Any], tuple]  # (state, grads, lr, step) -> (state, deltas)


def _zeros_like_tree(params):
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def sgd() -> GradientUpdater:
    def init(params):
        return ()

    def update(state, grads, lr, step):
        return state, jax.tree_util.tree_map(lambda g: lr * g, grads)

    return GradientUpdater("sgd", init, update)


def none_updater() -> GradientUpdater:
    def init(params):
        return ()

    def update(state, grads, lr, step):
        return state, jax.tree_util.tree_map(jnp.zeros_like, grads)

    return GradientUpdater("none", init, update)


def nesterovs(momentum: float = 0.9) -> GradientUpdater:
    """Nesterov momentum (reference: ND4J Nesterovs, default momentum 0.9).

    The update body lives behind the fused-update dispatch seam
    (`kernels/fused_update.py`): the XLA fallback there is this updater's
    pre-registry tree_map code verbatim (ND4J semantics: applied update =
    -(mu*vPrev) + (1+mu)*v, negated because the caller subtracts deltas).
    The engine calls `update` once per layer; on a TPU the registry fuses a
    layer's leaves into one elementwise kernel when all of them are small,
    and leaves large ones to XLA's per-leaf fusions (PERF.md §6, PR 29)."""

    def init(params):
        return {"v": _zeros_like_tree(params)}

    def update(state, grads, lr, step):
        return _fused.dispatch("nesterovs", state, grads, lr, step,
                               (momentum,))

    return GradientUpdater("nesterovs", init, update)


def adam(beta1: float = 0.9, beta2: float = 0.999, eps: float = 1e-8) -> GradientUpdater:
    def init(params):
        return {"m": _zeros_like_tree(params), "v": _zeros_like_tree(params)}

    def update(state, grads, lr, step):
        # Fused-update dispatch seam (kernels/fused_update.py), once per
        # layer: the XLA body is the pre-registry per-leaf code verbatim and
        # what `auto` takes for large leaves; the Pallas body is for a
        # dispatch of small ones (PERF.md §6, PR 29).
        return _fused.dispatch("adam", state, grads, lr, step,
                               (beta1, beta2, eps))

    return GradientUpdater("adam", init, update)


def adamax(beta1: float = 0.9, beta2: float = 0.999, eps: float = 1e-8) -> GradientUpdater:
    def init(params):
        return {"m": _zeros_like_tree(params), "u": _zeros_like_tree(params)}

    def update(state, grads, lr, step):
        t = step + 1
        m = jax.tree_util.tree_map(lambda m0, g: beta1 * m0 + (1 - beta1) * g, state["m"], grads)
        u = jax.tree_util.tree_map(lambda u0, g: jnp.maximum(beta2 * u0, jnp.abs(g)), state["u"], grads)
        bc1 = 1.0 - beta1 ** t.astype(jnp.float32) if hasattr(t, "astype") else 1.0 - beta1 ** t
        deltas = jax.tree_util.tree_map(lambda m1, u1: lr * (m1 / bc1) / (u1 + eps), m, u)
        return {"m": m, "u": u}, deltas

    return GradientUpdater("adamax", init, update)


def adagrad(eps: float = 1e-6) -> GradientUpdater:
    def init(params):
        return {"h": _zeros_like_tree(params)}

    def update(state, grads, lr, step):
        h = jax.tree_util.tree_map(lambda h0, g: h0 + g * g, state["h"], grads)
        deltas = jax.tree_util.tree_map(lambda h1, g: lr * g / (jnp.sqrt(h1) + eps), h, grads)
        return {"h": h}, deltas

    return GradientUpdater("adagrad", init, update)


def adadelta(rho: float = 0.95, eps: float = 1e-6) -> GradientUpdater:
    """AdaDelta — note: learning rate is NOT used (reference AdaDelta ignores lr)."""

    def init(params):
        return {"msg": _zeros_like_tree(params), "msdx": _zeros_like_tree(params)}

    def update(state, grads, lr, step):
        msg = jax.tree_util.tree_map(lambda a, g: rho * a + (1 - rho) * g * g, state["msg"], grads)
        deltas = jax.tree_util.tree_map(
            lambda a, d, g: g * jnp.sqrt(d + eps) / jnp.sqrt(a + eps), msg, state["msdx"], grads
        )
        msdx = jax.tree_util.tree_map(lambda d, dl: rho * d + (1 - rho) * dl * dl, state["msdx"], deltas)
        return {"msg": msg, "msdx": msdx}, deltas

    return GradientUpdater("adadelta", init, update)


def rmsprop(decay: float = 0.95, eps: float = 1e-8) -> GradientUpdater:
    def init(params):
        return {"g2": _zeros_like_tree(params)}

    def update(state, grads, lr, step):
        # Fused-update dispatch seam, as in `adam` above.
        return _fused.dispatch("rmsprop", state, grads, lr, step,
                               (decay, eps))

    return GradientUpdater("rmsprop", init, update)


def create(updater, *, momentum=0.9, adam_mean_decay=0.9, adam_var_decay=0.999,
           rho=0.95, rms_decay=0.95, epsilon=None) -> GradientUpdater:
    """Build a GradientUpdater from an `Updater` enum + hyperparams.

    Mirrors the reference's `UpdaterCreator`/`LayerUpdater.init()` switch
    (`nn/updater/LayerUpdater.java:240-272`) including its per-updater default
    epsilons.
    """
    u = Updater.of(updater) or Updater.SGD
    if u == Updater.SGD:
        return sgd()
    if u == Updater.NONE:
        return none_updater()
    if u == Updater.NESTEROVS:
        return nesterovs(momentum)
    if u == Updater.ADAM:
        return adam(adam_mean_decay, adam_var_decay, 1e-8 if epsilon is None else epsilon)
    if u == Updater.ADAMAX:
        return adamax(adam_mean_decay, adam_var_decay, 1e-8 if epsilon is None else epsilon)
    if u == Updater.ADAGRAD:
        return adagrad(1e-6 if epsilon is None else epsilon)
    if u == Updater.ADADELTA:
        return adadelta(rho, 1e-6 if epsilon is None else epsilon)
    if u == Updater.RMSPROP:
        return rmsprop(rms_decay, 1e-8 if epsilon is None else epsilon)
    raise ValueError(f"Unknown updater: {updater!r}")
