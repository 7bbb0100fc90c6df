"""`fit_check.two_pass_check` copies every top-level entry of the
reference's tree before the train step is donated the program's arrays,
whatever entries a configuration has: on a tiny model whose head is its
embedding (no `head`), and with a leading layer of its own beside the
stacked ones (as a leading dense layer is), the check runs through a step
that deletes what it was given, and the reference's gradient of the one
tied leaf holds both of its uses. On the CPU, with a fake program in
float32."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import cells, fit_check

S, D, V, L = 12, 8, 10, 3
SIZES = {"num_hidden_layers": L, "learning_rate": 1e-2,
         "adam_mean_decay": 0.9, "adam_var_decay": 0.95}
LIMITS = {"loss_rel": 1e-5, "logits_rel": 1e-5, "grad_rel": 1e-4,
          "update_rel": 1e-4}
LEAVES = {("emb", "W"): (("embed",), None), ("blk1", "W"): (("layers", "w"), 0)}


def _forward(params, ids, cfg, remat=True, embed_out=None):
    """The tied model: the embedding's rows in, its transpose out (or
    `embed_out`, to take the two uses apart); a leading layer of its own,
    then the layers stacked."""
    x = params["embed"][ids]
    x = x + jnp.tanh(x @ params["first"]["w"])
    for w in params["layers"]["w"]:
        x = x + jnp.tanh(x @ w)
    h = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * params["norm"]
    head = params["embed"] if embed_out is None else embed_out
    return h @ head.T, jnp.float32(0.0)


def _adam_update(g, m, v, t, lr, b1, b2):
    m1, v1 = b1 * m + (1 - b1) * g, b2 * v + (1 - b2) * g * g
    return -lr * (m1 / (1 - b1 ** t)) / (jnp.sqrt(v1 / (1 - b2 ** t)) + 1e-8)


REF = types.SimpleNamespace(forward=_forward, adam_update=_adam_update)
CFG = {"aux_coef": 0.0}


def _rparams(tree):
    """The program's tree under the reference's names: no `head`, and a
    `first` layer beside the stacked ones."""
    return {"embed": tree["emb"]["W"], "norm": tree["ln_out"]["gamma"],
            "first": {"w": tree["blk0"]["W"]},
            "layers": [{"w": tree[f"blk{i}"]["W"]} for i in range(1, L)]}


def _loss(tree, ids, labels):
    p = fit_check.stack_layers(_rparams(tree))
    logits, _ = _forward(p, ids, CFG)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.scipy.special.logsumexp(logits, -1) - picked), logits


class _TiedNet:
    """What the check asks of a program, done in float32 with the model
    above: `loss_and_gradients` and one Adam step through `fit`, which
    deletes the arrays it was given, as a donated compiled step does."""

    def __init__(self, seed):
        rng = np.random.default_rng(seed)
        self.params_tree = {
            "emb": {"W": jnp.asarray(rng.normal(size=(V, D)), jnp.float32)},
            "ln_out": {"gamma": jnp.asarray(1 + 0.1 * rng.normal(size=D),
                                            jnp.float32)},
            **{f"blk{i}": {"W": jnp.asarray(0.3 * rng.normal(size=(D, D)),
                                            jnp.float32)} for i in range(L)}}
        self.opt_state = {k: {"m": jax.tree_util.tree_map(
            lambda a: 0.01 * jnp.ones_like(a), v), "v": jax.tree_util.tree_map(
            lambda a: 0.02 * jnp.ones_like(a), v)}
            for k, v in self.params_tree.items()}
        self.iteration = 5

    @staticmethod
    def _ids(batch):
        return (jnp.asarray(batch.features)[0].astype(jnp.int32),
                jnp.asarray(batch.labels)[0].astype(jnp.int32))

    def loss_and_gradients(self, batch, wrt, collect):
        (loss, logits), grads = jax.value_and_grad(_loss, has_aux=True)(
            self.params_tree, *self._ids(batch))
        return loss, {k: {n: grads[k][n] for n in names}
                      for k, names in wrt.items()}, {"out": logits[None]}

    def fit(self, batch):
        grads = jax.grad(lambda t: _loss(t, *self._ids(batch))[0])(
            self.params_tree)
        self.iteration += 1
        b1, b2 = SIZES["adam_mean_decay"], SIZES["adam_var_decay"]
        tree = {}
        for key, leaves in self.params_tree.items():
            tree[key] = {}
            for name, p in leaves.items():
                g = grads[key][name]
                m, v = (self.opt_state[key][mv][name] for mv in "mv")
                tree[key][name] = p + _adam_update(
                    g, m, v, self.iteration, SIZES["learning_rate"], b1, b2)
                self.opt_state[key]["m"][name] = b1 * m + (1 - b1) * g
                self.opt_state[key]["v"][name] = b2 * v + (1 - b2) * g * g
        for leaf in jax.tree_util.tree_leaves(self.params_tree):
            leaf.delete()
        self.params_tree = tree


def _batch(seed):
    rng = np.random.default_rng(seed + 1)
    ids = rng.integers(0, V, S + 1)
    return types.SimpleNamespace(features=ids[None, :-1], labels=ids[None, 1:])


def _check(net, batch, **kw):
    return fit_check.two_pass_check(
        net, batch, ref=REF, cfg=CFG, sizes=SIZES, limits=LIMITS,
        leaves=LEAVES, rparams_of=_rparams, sets=(), **kw)


@pytest.mark.parametrize("seed", [0, 1])
def test_a_tied_head_is_checked_with_the_keys_it_has(seed):
    net, batch = _TiedNet(seed), _batch(seed)
    out = _check(net, batch)
    numbers = out["numbers"]
    assert out["problems"] == []
    assert numbers["steps_before"] == 5 and net.iteration == 6
    # the program is the reference here: the two agree to rounding, the
    # tied leaf's gradient and its Adam step included (an update is read as
    # the difference of two float32 parameters a hundred times larger)
    assert numbers["loss_rel"] < 1e-6 and numbers["logits_rel"] < 1e-6
    assert numbers["grad_rel_max"] < 1e-5 and numbers["update_rel_max"] < 1e-4
    # a step that leaves the tied leaf where it was reads 1 there
    stuck = _check(_TiedNet(seed), batch, fault="state_unchanged")["numbers"]
    assert stuck["update_rel"]["emb.W"] == pytest.approx(1.0)


def test_the_tied_leafs_reference_gradient_holds_both_uses():
    net, batch = _TiedNet(3), _batch(3)
    ids, labels = net._ids(batch)
    rparams = fit_check.stack_layers(_rparams(net.params_tree))
    paths = {"embed": ("embed",)}
    (_, _), grads = fit_check.reference_given(REF, CFG, paths, 1, [])(
        {"embed": rparams["embed"]}, rparams, ids, labels)

    def untied(e_in, e_out):
        logits, _ = _forward(dict(rparams, embed=e_in), ids, CFG,
                             embed_out=e_out)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.scipy.special.logsumexp(logits, -1) - picked)

    g_in, g_out = jax.grad(untied, argnums=(0, 1))(rparams["embed"],
                                                   rparams["embed"])
    tied = np.asarray(grads["embed"])
    np.testing.assert_allclose(tied, np.asarray(g_in + g_out), rtol=1e-5,
                               atol=1e-7)
    # each use alone is far from it: both are in
    for part in (g_in, g_out):
        assert fit_check.rel(part, tied) > 0.1
