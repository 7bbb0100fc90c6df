"""Mixture-of-experts FFN as a first-class DSL layer.

Makes `parallel/expert.py`'s GShard-style routed FFN reachable from the
config DSL: `MoELayer` in a `NeuralNetConfiguration` trains through the
engines with top-1/top-2 routing, capacity dropping, router jitter, and
the load-balance auxiliary loss folded into the network objective (the
engine collects the `_aux_loss` state entry each MoE layer emits and adds
it to the loss — each network class's `_forward_fn` and loss). Under an active
`ParallelContext` with an expert axis, the per-expert einsum batch is
sharding-constrained to that axis, so the SAME DSL model trains
expert-parallel with GSPMD-inserted all-to-alls (no reference equivalent;
the reference predates MoE — SURVEY.md §2.3 extension row).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations
from deeplearning4j_tpu.nn.layers.common import layer_input_dropout
from deeplearning4j_tpu.nn.layers.feedforward import gated_silu_mlp
from deeplearning4j_tpu.parallel.context import current_context


def moe_apply(conf, params, state, x, *, rng=None, train=False, mask=None):
    """x: [B, n_in] or [B, T, n_in] -> same leading shape with n_out.

    Emits `{"_aux_loss": w * aux}` in the returned state — the engines pop
    this reserved key into the training objective (never persisted)."""
    from deeplearning4j_tpu.parallel import expert as expert_mod

    drop_rng = jitter_rng = None
    if rng is not None:
        # Independent streams: dropout and router jitter must not consume
        # the same key (identical bits => correlated draws).
        drop_rng, jitter_rng = jax.random.split(rng)
    router_state = None
    if conf.router_hidden is not None:
        # two inputs: the tokens and the MLP router's state
        x, router_state = x
    x = layer_input_dropout(conf, x, drop_rng, train)
    lead = x.shape[:-1]
    tokens = x.reshape(-1, x.shape[-1])
    if conf.dropless:
        if router_state is not None:
            router_state = router_state.reshape(-1, router_state.shape[-1])
        return _dropless_apply(conf, params, state, tokens, lead, mask,
                               router_state)
    ffn_params = {
        "gate_w": params["gate_w"],
        "w1": params["w1"], "b1": params["b_1"],
        "w2": params["w2"], "b2": params["b_2"],
    }
    ctx = current_context()
    mesh = expert_axis = None
    if ctx is not None and ctx.expert_axis is not None and ctx.axis_size("expert") > 1:
        mesh, expert_axis = ctx.mesh, ctx.expert_axis
    kwargs = dict(
        capacity_factor=conf.capacity_factor, top_k=conf.top_k,
        rng=jitter_rng if train else None, jitter_eps=conf.router_jitter,
        return_aux=True,
    )
    if mesh is not None:
        kwargs.update(mesh=mesh, expert_axis=expert_axis)
    y, aux = expert_mod.moe_ffn(ffn_params, tokens, **kwargs)
    out = activations.resolve(conf.activation)(y.reshape(lead + (conf.n_out,)))
    new_state = dict(state)
    new_state["_aux_loss"] = conf.aux_loss_weight * aux
    return out, new_state, mask


def _dropless_apply(conf, params, state, tokens, lead, mask,
                    router_state=None):
    """Dropless top-k routing (`expert.moe_ffn_dropless`). The layer computes
    the part of the sum that belongs to the experts it holds
    (`conf.experts_held`). Under a `ParallelContext` with an expert axis the
    held tables are split over that axis: each device routes every token
    over all experts, takes its own experts by its index on the axis, and
    the parts are summed across the axis."""
    from deeplearning4j_tpu.parallel import expert as expert_mod

    first, count = conf.held()
    norm = conf.norm_topk_prob is not False
    ctx = current_context()
    kwargs = dict(top_k=conf.top_k, first=first, norm_topk_prob=norm)
    if conf.scoring is not None:
        # sequences: the balance term of this router is taken per sequence
        kwargs.update(
            scoring=conf.scoring, sequences=math.prod(lead[:-1]),
            routed_scaling_factor=conf.routed_scaling_factor or 1.0)
    if router_state is not None:
        kwargs.update(router_state=router_state)
    shared = {k: params[k] for k in conf.SHARED_PARAMS if k in params}
    params = {k: v for k, v in params.items() if k not in shared}
    if (ctx is not None and ctx.expert_axis is not None
            and ctx.axis_size("expert") > 1):
        if router_state is not None:
            raise NotImplementedError("the MLP router has no expert-"
                                      "parallel path: hold the layer's "
                                      "share with experts_held")
        y, aux, stats, idx = expert_mod.moe_ffn_dropless_sharded(
            params, tokens, ctx.mesh, ctx.expert_axis, **kwargs)
    else:
        y, aux, stats, idx = expert_mod.moe_ffn_dropless(params, tokens,
                                                         **kwargs)
    if shared:
        # The shared expert: every token, in token order, beside the routed
        # sum (one gated SiLU MLP of `shared_hidden`; counted once whatever
        # share of the routed experts is held here).
        with jax.named_scope("moe.shared"):
            y = y + gated_silu_mlp(tokens, *shared.values())
    out = activations.resolve(conf.activation)(y.reshape(lead + (conf.n_out,)))
    new_state = dict(state)
    new_state["_aux_loss"] = conf.aux_loss_weight * aux
    new_state["pairs_held_share"], new_state["expert_load_max_over_mean"] = stats
    if router_state is not None:
        new_state["skip_share"] = jnp.mean(
            (idx == conf.n_experts).astype(stats[0].dtype))
    # A by-product like `_selected_keys` (`nn/layers/dsa.py`): the experts
    # each token was routed to, for `loss_and_gradients(collect=...)`.
    new_state["_expert_idx"] = idx.reshape(lead + (conf.top_k,))
    return out, new_state, mask


def router_state_apply(conf, params, state, x, *, rng=None, train=False,
                       mask=None):
    """`RouterStateLayer`: x [.., n_in] (and the previous block's state
    with `carry`) -> the router's state [.., n_out] in >= float32:
    `r = x W_d`, then `lambda * r_prev + (1 - lambda) * r` with `lambda =
    sigmoid(eda_theta)` (exponential depth averaging)."""
    prev = None
    if conf.carry:
        x, prev = x
    acc = jnp.promote_types(x.dtype, jnp.float32)
    r = x.astype(acc) @ params["W_d"].astype(acc)
    if prev is not None:
        lam = jax.nn.sigmoid(params["eda_theta"].astype(acc))
        r = (1.0 - lam) * r + lam * prev.astype(acc)
    return activations.resolve(conf.activation)(r), state, mask
