"""ComputationGraph: the DAG network engine.

Equivalent of the reference's `nn/graph/ComputationGraph.java` (2276 LoC) +
`nn/graph/vertex/` — arbitrary-DAG, multi-input/multi-output networks. The
topological order is computed once from the config (reference `:283,851`) and
the whole graph traverses at trace time into a single jitted program; vertex
objects never exist at runtime.
"""

from __future__ import annotations

import copy
import math
import os
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import activations as activations_mod
from deeplearning4j_tpu.nn import losses as losses_mod
from deeplearning4j_tpu.nn import params as params_mod
from deeplearning4j_tpu.nn.conf.enums import (
    BackpropType,
    OptimizationAlgorithm,
)
from deeplearning4j_tpu.nn.conf.graph import (
    DuplicateToTimeSeriesVertex,
    LastTimeStepVertex,
    LayerVertex,
)
from deeplearning4j_tpu.nn.conf.dtype_policy import resolve_policy
from deeplearning4j_tpu.nn.conf.layers import is_bias_param
from deeplearning4j_tpu.nn.conf.neural_net import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf import preprocessors as preprocessors_mod
from deeplearning4j_tpu.nn.layers import OUTPUT_LAYER_TYPES, get_impl
from deeplearning4j_tpu.ops import grad_norm as grad_norm_mod
from deeplearning4j_tpu.ops import schedules as schedules_mod
from deeplearning4j_tpu.ops import updaters as updaters_mod
from deeplearning4j_tpu.nn import jit_cache as jit_cache_mod
from deeplearning4j_tpu.nn import superstep as _superstep
from deeplearning4j_tpu.nn import transfer as transfer_mod
from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets import staging as _staging
from deeplearning4j_tpu.datasets.iterators import (
    MultiSuperbatch,
    Superbatch,
    SuperbatchIterator,
    maybe_reset,
    transfer_cast,
)
from deeplearning4j_tpu import observability as _obs
from deeplearning4j_tpu.nn.fit_obs import FitObs

# This engine's hot-loop metric series and fit-loop spans.
_FIT = FitObs("graph")


def _layer_scope(layer):
    """`jax.named_scope(layer.scope)` where a layer names one, else nothing
    (the traced program of a net without scopes is unchanged)."""
    return jax.named_scope(layer.scope) if layer.scope else nullcontext()


def _as_mds(data, labels=None) -> MultiDataSet:
    if isinstance(data, MultiDataSet):
        return data
    if isinstance(data, DataSet):
        return MultiDataSet.from_dataset(data)
    return MultiDataSet(features=[np.asarray(data)], labels=[np.asarray(labels)])


def _as_mask_list(masks):
    """Normalize a MultiDataSet mask list for the jitted fns: None when no
    entry is present, else per-entry jnp arrays (None entries preserved)."""
    if masks is None or not any(m is not None for m in masks):
        return None
    return [None if m is None else jnp.asarray(m) for m in masks]


class ComputationGraph:
    """DAG network engine (see module docstring)."""

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.topo_order = conf.topological_order()
        self.layer_vertices = {
            name: v for name, v in conf.vertices.items() if isinstance(v, LayerVertex)
        }
        self.params_tree: Optional[Dict[str, Any]] = None
        self.state: Dict[str, Any] = {}
        self.opt_state: Optional[Dict[str, Any]] = None
        self.iteration = 0
        self.epoch = 0
        self._score = float("nan")
        self.listeners: List[Any] = []
        self._collect_stats = False
        self.last_training_stats: Dict[str, Any] = {}
        self._initialized = False
        # Precision policy (nn/conf/dtype_policy.py): explicit `dtype_policy`
        # wins, else the legacy `dtype` string maps onto the matching preset.
        self.dtype_policy = resolve_policy(conf.global_conf)
        self._compute_dtype = self.dtype_policy.jnp_compute
        self._loss_dtype = (
            jnp.float64
            if self.dtype_policy.resolved_param_dtype == "float64"
            else jnp.float32
        )
        self._output_dtype = self.dtype_policy.jnp_output
        self._jit_cache: Dict[Any, Any] = {}
        self._rnn_state: Dict[str, Any] = {}
        self._clock = None  # on-device (step, rng) carry; see _device_clock


    @property
    def score_value(self) -> float:
        """Loss of the most recent iteration. Reading this syncs with the
        device (the train loop itself never blocks — important over
        high-latency device transports)."""
        v = self._score
        if v is None:
            return float("nan")
        _FIT.publish_layer_stats(self)
        return float(v)

    @score_value.setter
    def score_value(self, v):
        self._score = v

    # ------------------------------------------------------------------ init

    def init(self, params=None) -> "ComputationGraph":
        g = self.conf.global_conf
        pol = self.dtype_policy
        root = jax.random.PRNGKey(g.seed)
        # Low-precision param policies INITIALIZE at f32 (the master copy);
        # stored params are its cast. See MultiLayerNetwork.init.
        pdt = jnp.float32 if pol.low_precision_params else pol.jnp_param
        names = sorted(self.layer_vertices)
        keys = jax.random.split(root, max(len(names), 1))
        master = None
        if params is None:
            params = {
                name: params_mod.init_layer_params(self.layer_vertices[name].layer, keys[i], dtype=pdt)
                for i, name in enumerate(names)
            }
            if pol.low_precision_params:
                master = params
                params = params_mod.cast_floating(params, pol.jnp_param)
        elif pol.low_precision_params:
            master = params_mod.cast_floating(params, jnp.float32)
        self.params_tree = params
        self.state = {
            name: params_mod.init_layer_state(v.layer, dtype=pdt)
            for name, v in self.layer_vertices.items()
            if v.layer.state_shapes()
        }
        self._layer_stat_keys = None  # found anew by fit_obs
        self._updaters = {}
        self._schedules = {}
        for name, v in self.layer_vertices.items():
            layer = v.layer
            self._updaters[name] = updaters_mod.create(
                layer.updater,
                momentum=layer.momentum if layer.momentum is not None else g.momentum,
                adam_mean_decay=layer.adam_mean_decay if layer.adam_mean_decay is not None else g.adam_mean_decay,
                adam_var_decay=layer.adam_var_decay if layer.adam_var_decay is not None else g.adam_var_decay,
                rho=layer.rho if layer.rho is not None else g.rho,
                rms_decay=layer.rms_decay if layer.rms_decay is not None else g.rms_decay,
                epsilon=layer.epsilon if layer.epsilon is not None else g.epsilon,
            )
            self._schedules[name] = schedules_mod.make_schedule(
                float(layer.learning_rate if layer.learning_rate is not None else g.learning_rate),
                g.lr_policy, g.lr_policy_decay_rate, g.lr_policy_power,
                g.lr_policy_steps, g.max_num_iterations, g.lr_schedule,
            )
        # Transfer learning / LoRA (nn/transfer.py): frozen leaves get NO
        # updater state — opt_state is built over the trainable subtree
        # (a fully-frozen vertex's entry is ()). Empty spec (the common
        # case) keeps the structures byte-identical to before.
        self._frozen_spec = transfer_mod.frozen_spec(
            ((n, v.layer) for n, v in self.layer_vertices.items()),
            self.params_tree)
        opt_base = master if master is not None else self.params_tree
        opt_src = (transfer_mod.split_tree(opt_base, self._frozen_spec)[0]
                   if self._frozen_spec else opt_base)
        self.opt_state = {
            name: (() if name in self._frozen_spec and not opt_src[name]
                   else self._updaters[name].init(opt_src[name]))
            for name in self.layer_vertices
        }
        # Reserved opt_state keys (never vertex names): f32 master params
        # and the on-device (scale, good_count) loss-scale carry — see
        # MultiLayerNetwork.init.
        if master is not None:
            self.opt_state["_master"] = master
        if pol.uses_loss_scaling:
            self.opt_state["_ls"] = (
                jnp.float32(pol.initial_loss_scale), jnp.float32(0.0))
        self._train_rng = jax.random.PRNGKey(g.seed ^ 0x5EED)
        self._clock = None
        self._initialized = True
        return self

    def _device_clock(self):
        """On-device (step, rng) carry, advanced inside the jitted train step
        — the hot loop makes zero host->device transfers."""
        if self._clock is None:
            self._clock = (
                jax.device_put(np.float32(self.iteration)),
                self._train_rng,
            )
        return self._clock

    @property
    def _uint8_policies(self) -> Dict[str, str]:
        """Per-network-input uint8 staging policy (see
        `nn/conf/preprocessors.py`): every vertex fed directly by the input
        votes, and a mixed ids/value vote is 'ambiguous' (raises if uint8
        actually arrives)."""
        out: Dict[str, str] = {}
        for name in self.conf.network_inputs:
            consumers = []
            for vname, ins in self.conf.vertex_inputs.items():
                if name in ins:
                    vertex = self.conf.vertices.get(vname)
                    consumers.append(getattr(vertex, "layer", None))
            out[name] = preprocessors_mod.resolve_uint8_policy(consumers)
        return out

    # --------------------------------------------------------------- forward

    def _forward_fn(self, params, state, inputs: Sequence, rng, train: bool,
                    fmasks: Optional[Sequence] = None, keep_rnn_state: bool = False,
                    collect: bool = False):
        """Traverse the DAG in topo order (reference: forward `:1044-1090`)."""
        cdt = self._compute_dtype
        values: Dict[str, jnp.ndarray] = {}
        masks: Dict[str, Optional[jnp.ndarray]] = {}
        policies = self._uint8_policies
        for i, name in enumerate(self.conf.network_inputs):
            # Device-side ImagePreProcessingScaler (see
            # MultiLayerNetwork._forward_fn): bytes over the link, scale
            # 0-255 -> 0-1 on device — but only for value consumers; an
            # input feeding an ids-format EmbeddingLayer is cast, and a
            # uint8 input feeding both kinds raises instead of guessing.
            values[name] = preprocessors_mod.apply_uint8_policy(
                jnp.asarray(inputs[i]), policies[name], cdt)
            masks[name] = None if fmasks is None else fmasks[i]
        new_state: Dict[str, Any] = {}
        aux: Dict[str, Any] = {}
        for vi, name in enumerate(self.topo_order):
            vertex = self.conf.vertices[name]
            in_names = self.conf.vertex_inputs[name]
            in_vals = [values[n] for n in in_names]
            in_masks = [masks[n] for n in in_names]
            if isinstance(vertex, LayerVertex):
                x, mask = in_vals[0], in_masks[0]
                if vertex.preprocessor is not None:
                    x, mask = vertex.preprocessor(x, mask)
                layer = vertex.layer
                if type(layer).__name__ == "CenterLossOutputLayer":
                    aux[f"center_loss_input:{name}"] = x
                    aux[f"centers:{name}"] = state.get(name, {}).get("centers")
                lrng = jax.random.fold_in(rng, vi) if rng is not None else None
                # Params stored at param_dtype, cast (or dequantized) to the
                # policy's compute dtype at use (nn/params.py).
                lparams = params_mod.prep_layer_params(params.get(name, {}),
                                                       cdt, layer=layer)
                with _layer_scope(layer):
                    out, lstate_new, mask = get_impl(layer)(
                        layer, lparams, state.get(name, {}), x,
                        rng=lrng, train=train, mask=mask,
                    )
                if lstate_new and "_aux_loss" in lstate_new:
                    # Reserved key: auxiliary loss terms (MoE load balance)
                    # go into the objective, never persist as state.
                    lstate_new = dict(lstate_new)
                    aux["aux_loss"] = aux.get("aux_loss", 0.0) + \
                        lstate_new.pop("_aux_loss")
                if lstate_new:
                    # `_name`: a layer's by-product (the keys an attention
                    # layer selected, the experts a token was routed to),
                    # never state; a collecting pass hands it out as
                    # `<layer>.<name>`.
                    declared = set(layer.state_shapes())
                    keep = {k: v for k, v in lstate_new.items()
                            if not k.startswith("_")
                            and (k in declared or keep_rnn_state)}
                    if keep:
                        new_state[name] = keep
                    if collect:
                        values.update({f"{name}.{k[1:]}": v
                                       for k, v in lstate_new.items()
                                       if k.startswith("_")})
                values[name] = out
                masks[name] = mask
            elif isinstance(vertex, DuplicateToTimeSeriesVertex):
                ref = values[vertex.input_name]
                values[name] = vertex.apply(in_vals, in_masks, time_steps=ref.shape[1])
                masks[name] = masks.get(vertex.input_name)
            elif isinstance(vertex, LastTimeStepVertex):
                m = masks.get(vertex.mask_array_input) if vertex.mask_array_input else in_masks[0]
                values[name] = vertex.apply(in_vals, [m])
                masks[name] = None
            else:
                values[name] = vertex.apply(in_vals, in_masks)
                masks[name] = in_masks[0] if in_masks else None
        outs = [values[n] for n in self.conf.network_outputs]
        omasks = [masks.get(n) for n in self.conf.network_outputs]
        if collect:
            return outs, new_state, values, aux, omasks
        return outs, new_state, aux, omasks

    def _get_jit(self, kind: str, **static):
        # Key construction/lookup + compile-cache store hook shared with
        # MultiLayerNetwork (see nn/jit_cache.py).
        return jit_cache_mod.get_jit(self, _FIT.jit_hit, _FIT.jit_miss,
                                     kind, **static)

    def warmup(self, data=None, kinds=None, background: bool = False,
               batch_size: int = 32):
        """Pre-compile (or AOT-load) the jit programs for an example
        batch's signature without running them — params/optimizer/RNG are
        untouched. See `compilation.warmup.warmup_net` for the `data` /
        `kinds` / `background` contract."""
        from deeplearning4j_tpu.compilation import warmup as warmup_mod

        return warmup_mod.warmup_net(self, data, kinds=kinds,
                                     background=background,
                                     batch_size=batch_size)

    def _build_jit(self, kind: str, train=False, keep_rnn_state=False,
                   advance=False, collect=False, algo=None, k=None,
                   scan=True, kernels=None):
        # `k`/`scan` select the superstep program shape (`nn/superstep.py`,
        # see MultiLayerNetwork._build_jit): distinct block lengths register
        # as distinct cached programs so StepProfiler attributes a tail
        # block's first call to compile.
        if kind == "solver_step":
            from jax.flatten_util import ravel_pytree

            from deeplearning4j_tpu.optimize import solvers as solvers_mod

            g = self.conf.global_conf
            iterations = max(1, g.iterations)
            mls = max(1, int(g.max_num_line_search_iterations))

            def solver_fn(params, state, inputs, labels, fmasks, lmasks):
                w0, unravel = ravel_pytree(params)

                def loss_flat(w):
                    p = unravel(w)
                    outs, _, aux, omasks = self._forward_fn(
                        p, state, inputs, None, False, fmasks)
                    return self._loss_from_outputs(
                        p, outs, labels, lmasks, aux, omasks)[0]

                w, loss = solvers_mod.minimize(
                    algo, loss_flat, w0, iterations=iterations,
                    max_line_search=mls)
                return unravel(w), loss

            return jax.jit(solver_fn, donate_argnums=(0,))
        if kind == "output":
            def output_fn(params, state, inputs, fmasks, rng):
                outs, new_state, _, _ = self._forward_fn(
                    params, state, inputs, rng, train, fmasks,
                    keep_rnn_state=keep_rnn_state,
                )
                final = []
                for n, o in zip(self.conf.network_outputs, outs):
                    layer = self.layer_vertices.get(n)
                    o = o.astype(self._output_dtype)
                    if layer is not None and type(layer.layer).__name__ in OUTPUT_LAYER_TYPES:
                        o = activations_mod.resolve(layer.layer.activation)(o)
                    final.append(o)
                return final, new_state
            return jax.jit(output_fn)
        if kind == "score":
            def score_fn(params, state, inputs, labels, fmasks, lmasks):
                outs, _, aux, omasks = self._forward_fn(params, state, inputs, None, False, fmasks)
                return self._loss_from_outputs(params, outs, labels, lmasks, aux, omasks)[0]
            return jax.jit(score_fn)
        if kind == "train_step":
            def step_fn(params, state, opt_state, inputs, labels, fmasks, lmasks, clock):
                step, key = clock
                key, sub = jax.random.split(key)
                out = self._train_step(params, state, opt_state, inputs, labels,
                                       fmasks, lmasks, step, sub, carry_rnn=False)
                return out + ((step + 1.0, key),)
            return jax.jit(step_fn, donate_argnums=(0, 2))
        if kind == "train_superstep":
            # K full train iterations as ONE dispatch: a fused loop (`lax.scan`
            # by default, opt-in unrolled — `nn/superstep.py`) over the
            # leading [K] axis of stacked input/label/mask LISTS (lists are
            # pytrees, so the loop slices every entry; None mask entries are
            # empty pytrees and pass through). Clock advance matches the
            # per-batch `train_step` exactly — bit-identical RNG chain.
            # See MultiLayerNetwork's twin + PERF.md §13.
            def step_super(params, state, opt_state, inputs, labels, fmasks,
                           lmasks, clock):
                def body(carry, inp):
                    params, state, opt_state, (step, key) = carry
                    ins, labs, fms, lms = inp
                    key, sub = jax.random.split(key)
                    params, state, opt_state, loss = self._train_step(
                        params, state, opt_state, ins, labs, fms, lms, step,
                        sub, carry_rnn=False)
                    return (params, state, opt_state, (step + 1.0, key)), loss

                (params, state, opt_state,
                 clock), losses = _superstep.superstep_loop(
                    body, (params, state, opt_state, clock),
                    (inputs, labels, fmasks, lmasks), k, scan)
                return params, state, opt_state, losses, clock
            return jax.jit(step_super, donate_argnums=(0, 2))
        if kind == "train_step_stats":
            def step_fn_s(params, state, opt_state, inputs, labels, fmasks, lmasks, clock):
                step, key = clock
                key, sub = jax.random.split(key)
                out = self._train_step(params, state, opt_state, inputs, labels,
                                       fmasks, lmasks, step, sub, carry_rnn=False,
                                       collect_stats=True)
                return out + ((step + 1.0, key),)
            return jax.jit(step_fn_s, donate_argnums=(0, 2))
        if kind == "train_step_tbptt":
            # `advance` static: chunks of one sequence share a step value;
            # only the final chunk ticks the clock. `collect` adds the
            # StatsListener scalars so tBPTT training reports them too.
            def step_fn2(params, state, opt_state, inputs, labels, fmasks, lmasks, clock, ebs):
                step, key = clock
                key, sub = jax.random.split(key)
                out = self._train_step(params, state, opt_state, inputs, labels,
                                       fmasks, lmasks, step, sub, carry_rnn=True,
                                       ebs=ebs, collect_stats=collect)
                new_step = step + 1.0 if advance else step
                return out + ((new_step, key),)
            return jax.jit(step_fn2, donate_argnums=(0, 2))
        if kind == "train_step_tbptt_scan":
            # Whole tBPTT pass as ONE jitted program, mirroring
            # `MultiLayerNetwork`'s `train_step_tbptt_scan` (PERF.md §4):
            # chunk 0 unrolled (creates the rnn carries), middle chunks as a
            # `lax.scan` whose body time-slices the closed-over full
            # sequences with `dynamic_slice` (static 2-D inputs pass
            # through untouched), remainder chunk unrolled at its true
            # length. RNG split chain matches the per-chunk path exactly.
            fwd = int(self.conf.tbptt_fwd_length)

            def step_scan(params, state, opt_state, inputs, labels, fmasks,
                          lmasks, clock, ebs):
                step, key = clock
                t = max(f.shape[1] for f in inputs if f.ndim == 3)
                n_full = t // fwd
                rem = t - n_full * fwd
                subs = []
                for _ in range(n_full + (1 if rem else 0)):
                    key, sub = jax.random.split(key)
                    subs.append(sub)

                def sliced(lst, slicer, is_mask=False):
                    if lst is None:
                        return None
                    out = []
                    for a in lst:
                        seq = a is not None and a.shape[1:2] == (t,) and (
                            a.ndim == 3 or (a.ndim == 2 and (
                                is_mask
                                or jnp.issubdtype(a.dtype, jnp.integer))))
                        out.append(slicer(a) if seq else a)
                    return out

                def static_chunk(args, sl):
                    inputs_c = sliced(args[0], lambda a: a[:, sl])
                    labels_c = sliced(args[1], lambda a: a[:, sl])
                    fm_c = sliced(args[2], lambda a: a[:, sl], True)
                    lm_c = sliced(args[3], lambda a: a[:, sl], True)
                    return inputs_c, labels_c, fm_c, lm_c

                c0 = static_chunk((inputs, labels, fmasks, lmasks),
                                  slice(0, fwd))
                params, state, opt_state, loss = self._train_step(
                    params, state, opt_state, *c0, step, subs[0],
                    carry_rnn=True, ebs=ebs)

                if n_full > 1:
                    def body(carry, inp):
                        params, state, opt_state = carry
                        c, sub = inp
                        off = c * fwd

                        def dyn(a):
                            return jax.lax.dynamic_slice_in_dim(a, off, fwd, 1)

                        inputs_c = sliced(inputs, dyn)
                        labels_c = sliced(labels, dyn)
                        fm_c = sliced(fmasks, dyn, True)
                        lm_c = sliced(lmasks, dyn, True)
                        params, state, opt_state, closs = self._train_step(
                            params, state, opt_state, inputs_c, labels_c,
                            fm_c, lm_c, step, sub, carry_rnn=True, ebs=ebs)
                        return (params, state, opt_state), closs

                    (params, state, opt_state), losses = jax.lax.scan(
                        body, (params, state, opt_state),
                        (jnp.arange(1, n_full), jnp.stack(subs[1:n_full])))
                    loss = losses[-1]
                if rem:
                    cr = static_chunk((inputs, labels, fmasks, lmasks),
                                      slice(n_full * fwd, t))
                    params, state, opt_state, loss = self._train_step(
                        params, state, opt_state, *cr, step, subs[-1],
                        carry_rnn=True, ebs=ebs)
                return (params, state, opt_state, loss, (step + 1.0, key))
            return jax.jit(step_scan, donate_argnums=(0, 2))
        raise ValueError(kind)

    # ----------------------------------------------------------------- loss

    def _l1_l2_penalty(self, params):
        total = 0.0
        for name, v in self.layer_vertices.items():
            layer = v.layer
            l1 = float(layer.l1 or 0.0)
            l2 = float(layer.l2 or 0.0)
            if (l1 == 0.0 and l2 == 0.0) or name not in params:
                continue
            for wk in layer.weight_param_keys():
                if wk not in params[name]:
                    continue
                w = params[name][wk].astype(self._loss_dtype)
                if l2:
                    total = total + 0.5 * l2 * jnp.sum(w * w)
                if l1:
                    total = total + l1 * jnp.sum(jnp.abs(w))
        return total

    def _loss_from_outputs(self, params, outs, labels, lmasks, aux, omasks,
                           ebs=None):
        total = 0.0
        extra_state: Dict[str, Any] = {}
        for i, name in enumerate(self.conf.network_outputs):
            v = self.layer_vertices.get(name)
            if v is None or type(v.layer).__name__ not in OUTPUT_LAYER_TYPES:
                raise ValueError(f"Network output {name!r} is not an output layer")
            layer = v.layer
            preout = outs[i].astype(self._loss_dtype)
            y = labels[i]
            lmask = lmasks[i] if lmasks is not None else None
            if lmask is None and omasks and omasks[i] is not None and preout.ndim == 3:
                lmask = omasks[i]
            # `ebs` overrides the divisors for tBPTT chunks (full-sequence
            # minibatch count, see MultiLayerNetwork._loss_from_preout).
            eb = ebs[i] if ebs is not None else losses_mod.effective_batch_size(y, lmask)
            if i == 0:
                eb0 = eb
            with _layer_scope(layer):
                total = total + losses_mod.score(
                    layer.loss_function, y, preout, layer.activation, lmask,
                    average=False,
                ) / eb
            if type(layer).__name__ == "CenterLossOutputLayer":
                feats = aux[f"center_loss_input:{name}"].astype(self._loss_dtype)
                centers = aux[f"centers:{name}"]
                cls = (jnp.asarray(y, jnp.int32)
                       if jnp.issubdtype(jnp.asarray(y).dtype, jnp.integer)
                       else jnp.argmax(y, axis=-1))
                c = centers[cls]
                # Row weights: labels mask excludes data-parallel padding rows
                # from the center-loss term and the center updates.
                w = jnp.ones(y.shape[0], self._loss_dtype) if lmask is None else (
                    lmask.reshape(y.shape[0], -1)[:, 0].astype(self._loss_dtype))
                total = total + 0.5 * layer.lambda_ * jnp.sum(
                    w * jnp.sum((feats - c) ** 2, axis=-1)) / eb
                diff = (c - feats) * w[:, None]
                num = jax.ops.segment_sum(diff, cls, num_segments=layer.n_out)
                cnt = jax.ops.segment_sum(w.astype(jnp.float32), cls,
                                          num_segments=layer.n_out)
                extra_state[name] = {"centers": centers - layer.alpha * num / (1.0 + cnt)[:, None]}
        if "aux_loss" in aux:
            # Layer-emitted auxiliary objectives (MoE load balance), already
            # scaled per-layer; batch-size-invariant means, not divided by eb.
            total = total + aux["aux_loss"]
        # Penalty divided by minibatch size, matching the reference objective
        # (BaseOutputLayer.java:100-101, LayerUpdater.postApply:104-108).
        return total + self._l1_l2_penalty(params) / eb0, extra_state

    # ----------------------------------------------------------- train step

    def _train_step(self, params, state, opt_state, inputs, labels, fmasks, lmasks,
                    step, rng, carry_rnn=False, ebs=None, collect_stats=False):
        # Transfer learning / LoRA: differentiate the TRAINABLE subtree
        # only — frozen leaves (incl. int8 bases, which jax.grad refuses)
        # close over the loss as constants and re-attach to the outputs
        # as the same arrays. Empty spec: identity, program unchanged.
        spec = getattr(self, "_frozen_spec", None)
        if spec:
            params, frozen_stored = transfer_mod.split_tree(params, spec)
        else:
            frozen_stored = None

        def loss_fn(p):
            if frozen_stored is not None:
                p = transfer_mod.merge_tree(p, frozen_stored)
            outs, new_state, aux, omasks = self._forward_fn(
                p, state, inputs, rng, True, fmasks, keep_rnn_state=carry_rnn
            )
            loss, extra = self._loss_from_outputs(p, outs, labels, lmasks, aux,
                                                  omasks, ebs)
            for n, s in extra.items():
                new_state.setdefault(n, {}).update(s)
            return loss, new_state

        pol = self.dtype_policy
        scaling = pol.uses_loss_scaling
        lowp = pol.low_precision_params

        if scaling:
            # Dynamic loss scaling (f16-class compute): backward on the
            # SCALED loss, f32 unscale after; (scale, good_count) lives in
            # opt_state so a fused superstep scan carries it on device.
            # See MultiLayerNetwork._train_step.
            scale, good = opt_state["_ls"]

            def scaled_loss_fn(p):
                loss, new_state = loss_fn(p)
                return loss * scale.astype(loss.dtype), (loss, new_state)

            (_, (loss, new_state)), grads = jax.value_and_grad(
                scaled_loss_fn, has_aux=True)(params)
            grads = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32) / scale, grads)
            finite = jnp.bool_(True)
            for leaf in jax.tree_util.tree_leaves(grads):
                finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(leaf)))
        else:
            (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            if lowp:
                grads = params_mod.cast_floating(grads, jnp.float32)

        # Low-precision params: updates apply to the f32 MASTER copy; stored
        # params are its cast (no bf16/f16 update underflow).
        base = opt_state["_master"] if lowp else params
        frozen_master = None
        if spec and lowp:
            base, frozen_master = transfer_mod.split_tree(base, spec)
        g = self.conf.global_conf
        sign = 1.0 if g.minimize else -1.0
        new_base, new_opt = {}, {}
        stats: Dict[str, Any] = {}
        for name, v in self.layer_vertices.items():
            layer = v.layer
            lgrads = grads.get(name, {})
            if not lgrads:
                new_base[name] = base.get(name, {})
                new_opt[name] = opt_state.get(name, ())
                continue
            lgrads = grad_norm_mod.normalize_layer_gradients(
                lgrads, layer.gradient_normalization,
                float(layer.gradient_normalization_threshold or 1.0),
            )
            lr = self._schedules[name](step)
            st, deltas = self._updaters[name].update(opt_state[name], lgrads, lr, step)
            base_lr = float(layer.learning_rate if layer.learning_rate is not None else g.learning_rate)
            bias_lr = float(layer.bias_learning_rate if layer.bias_learning_rate is not None else base_lr)
            if bias_lr != base_lr and base_lr != 0.0:
                factor = bias_lr / base_lr
                # Per param TYPE via is_bias_param (b_f/b_b, vb/eb/db, beta),
                # matching reference `LayerUpdater.java:243`.
                deltas = {k: (d * factor if is_bias_param(k) else d)
                          for k, d in deltas.items()}
            new_base[name] = {k: base[name][k] - sign * deltas[k] for k in base[name]}
            new_opt[name] = st
            if collect_stats:
                # In-jit per-param mean magnitudes (only scalars leave the
                # device; reference `BaseStatsListener.java:273` semantics).
                stats[name] = {
                    k: {
                        "grad_mm": jnp.mean(jnp.abs(lgrads[k])),
                        "update_mm": jnp.mean(jnp.abs(deltas[k])),
                        "param_mm": jnp.mean(jnp.abs(new_base[name][k])),
                    }
                    for k in lgrads
                }

        if scaling:
            # Skip-step on non-finite scaled grads: per-leaf select of the
            # OLD values, then scale backoff / growth bookkeeping — all
            # on-device `jnp.where`, superstep-safe.
            def sel(n, o):
                return jnp.where(finite, n, o)

            new_base = jax.tree_util.tree_map(
                sel, new_base, {n: base[n] for n in new_base})
            new_opt = jax.tree_util.tree_map(
                sel, new_opt, {n: opt_state[n] for n in new_opt})
            new_state = {
                n: {k: (sel(v, state[n][k])
                        if n in state and k in state[n] else v)
                    for k, v in s.items()}
                for n, s in new_state.items()
            }
            new_good = jnp.where(finite, good + 1.0, jnp.float32(0.0))
            grow = new_good >= jnp.float32(pol.loss_scale_growth_interval)
            new_scale = jnp.where(
                finite,
                jnp.where(grow,
                          scale * jnp.float32(pol.loss_scale_growth_factor),
                          scale),
                scale * jnp.float32(pol.loss_scale_backoff_factor))
            new_good = jnp.where(grow, jnp.float32(0.0), new_good)

        if lowp:
            new_params = params_mod.cast_floating(new_base, pol.jnp_param)
            if frozen_stored is not None:
                # Frozen STORED leaves pass through untouched (no recast);
                # the master keeps its frozen f32 copies alongside.
                new_params = transfer_mod.merge_tree(new_params, frozen_stored)
                new_opt["_master"] = transfer_mod.merge_tree(
                    new_base, frozen_master)
            else:
                new_opt["_master"] = new_base
        elif frozen_stored is not None:
            new_params = transfer_mod.merge_tree(new_base, frozen_stored)
        else:
            new_params = new_base
        if scaling:
            new_opt["_ls"] = (new_scale, new_good)

        merged_state = dict(state)
        for n, s in new_state.items():
            merged = dict(merged_state.get(n, {}))
            merged.update(s)
            merged_state[n] = merged
        if collect_stats:
            return new_params, merged_state, new_opt, loss, stats
        return new_params, merged_state, new_opt, loss

    # ------------------------------------------------------------------ fit

    def fit(self, data, labels=None):
        """Train (reference: `ComputationGraph.fit` `:671,740`)."""
        if not self._initialized:
            self.init()
        if labels is not None or isinstance(data, (DataSet, MultiDataSet)):
            iterator = [_as_mds(data, labels)]
        else:
            iterator = data
        maybe_reset(iterator)
        for listener in self.listeners:
            listener.on_epoch_start(self)
        with _obs.tracer.span("graph.fit", cat="train", epoch=self.epoch):
            k = self._superstep_k()
            src = self._superstep_wrap(iterator, k) if k > 1 else iterator
            # Overlap host->device transfers with compute: multi-batch
            # epochs stream through a background DeviceStager (single
            # batches and already-staging sources pass through).
            src = _staging.maybe_stage(
                src, net=self, engine="graph",
                transfer_dtype=getattr(self.dtype_policy,
                                       "transfer_dtype", None))
            src_it = iter(src)
            try:
                for item in _FIT.batches(self, src_it):
                    self._fit_dispatch(
                        item if isinstance(item, MultiSuperbatch)
                        else _as_mds(item))
            finally:
                # An abandoned epoch must not leave staged HBM buffers.
                _staging.close_stager(src_it)
                _staging.close_stager(src)
        self.epoch += 1
        _FIT.epochs.inc()
        for listener in self.listeners:
            listener.on_epoch_end(self)
        return self

    def _fit_dispatch(self, mds):
        """tBPTT/plain/superstep dispatch + iterations loop for one staged
        batch (or stacked `MultiSuperbatch`) — shared by `fit()` and
        `ParallelWrapper`. Observability choke point (see
        `MultiLayerNetwork._fit_dispatch`); `StepProfiler` patches this
        method on the instance."""
        tdt = getattr(self.dtype_policy, "transfer_dtype", None)
        if tdt is not None:
            mds = transfer_cast(mds, tdt)
        h2d = _obs.host_nbytes(mds.features, mds.labels,
                               mds.features_masks
                               if hasattr(mds, "features_masks")
                               else mds.features_mask,
                               mds.labels_masks
                               if hasattr(mds, "labels_masks")
                               else mds.labels_mask)
        return _FIT.dispatch(self, mds, h2d, self._fit_dispatch_inner)

    def _fit_dispatch_inner(self, mds):
        if isinstance(mds, (MultiSuperbatch, Superbatch)):
            # Stacked K-block: `_superstep_k` gated out solver / tBPTT /
            # stats / multi-iteration paths before blocks formed.
            return self._fit_superstep(mds)
        g = self.conf.global_conf
        algo = OptimizationAlgorithm.of(g.optimization_algo)
        if algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
            return self._fit_solver(mds, algo)
        tbptt = BackpropType.of(self.conf.backprop_type) == BackpropType.TRUNCATED_BPTT
        for _ in range(max(1, g.iterations)):
            if tbptt and any(
                f.ndim == 3 and f.shape[1] > self.conf.tbptt_fwd_length
                for f in mds.features
            ):
                self._fit_tbptt(mds)
            else:
                self._fit_one(mds)

    def _fit_solver(self, mds: MultiDataSet, algo):
        """Full-batch LBFGS/CG/line-search optimize of one batch (reference:
        `Solver.java:41-110`); see `MultiLayerNetwork._fit_solver`."""
        self._check_sgd_only_policy("solver optimizers (LBFGS/CG/line search)")
        g = self.conf.global_conf
        fn = self._get_jit("solver_step", algo=str(algo))
        fmasks = _as_mask_list(mds.features_masks)
        lmasks = _as_mask_list(mds.labels_masks)
        args = (self.params_tree, self.state,
                [jnp.asarray(f) for f in mds.features],
                [jnp.asarray(l) for l in mds.labels],
                fmasks, lmasks)
        with _FIT.enqueue():
            self.params_tree, loss = fn(*args)
        self._score = loss
        self.iteration += max(1, g.iterations)
        # Stats snapshots are SGD-path only; clear stale ones (see
        # `MultiLayerNetwork._fit_solver`). Listener cadence deviation vs
        # `BaseOptimizer` is documented there too.
        self.last_training_stats = {}
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration)

    # -------------------------------------------------------------- superstep

    def _superstep_k(self) -> int:
        """Effective superstep K (see `MultiLayerNetwork._superstep_k`):
        the config/env knob, gated to 0 for stats listeners, tBPTT, solver
        optimizers, and multi-`iterations` batches."""
        env = os.environ.get("DL4J_TPU_SUPERSTEP_K")
        g = self.conf.global_conf
        try:
            k = int(env) if env else int(getattr(g, "superstep_k", 0) or 0)
        except ValueError:
            return 0
        if (k < 2 or self._collect_stats
                or max(1, g.iterations) != 1
                or BackpropType.of(self.conf.backprop_type)
                == BackpropType.TRUNCATED_BPTT
                or OptimizationAlgorithm.of(g.optimization_algo)
                != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT):
            return 0
        return k

    def _check_sgd_only_policy(self, what: str) -> None:
        pol = self.dtype_policy
        if pol.low_precision_params or pol.uses_loss_scaling:
            raise ValueError(
                f"{what} does not support dtype policy {pol.name!r}: "
                "low-precision param storage (f32 master copies) and "
                "dynamic loss scaling are SGD-train-step features; use a "
                "float32 / float64 / mixed_bfloat16 policy here")

    def _superstep_wrap(self, iterator, k: int):
        """SuperbatchIterator over `iterator`, converting items to
        MultiDataSet BEFORE stacking; the wrapper is cached on the base so
        device-cached epochs restack once (see MultiLayerNetwork twin). The
        policy's `transfer_dtype` rides along so staged superbatches ship
        at the reduced dtype (halved H2D bytes)."""
        tdt = self.dtype_policy.transfer_dtype
        if isinstance(iterator, SuperbatchIterator):
            return iterator
        wrapper = getattr(iterator, "_superbatch_wrapper", None)
        if (isinstance(wrapper, SuperbatchIterator)
                and wrapper.base is iterator and wrapper.k == k
                and getattr(wrapper, "transfer_dtype", None) == tdt):
            wrapper.net = self  # staging budget follows the current net
            return wrapper
        wrapper = SuperbatchIterator(iterator, k, transform=_as_mds,
                                     transfer_dtype=tdt, net=self)
        try:
            iterator._superbatch_wrapper = wrapper
        except (AttributeError, TypeError):
            pass  # lists/tuples/slots: re-wrapped per fit(), still correct
        return wrapper

    def _fit_superstep(self, sb):
        """One dispatch, K train iterations (`train_superstep` scan); the
        `[K]` loss vector fans out to listeners per iteration — same
        (iteration, score) sequence as the per-batch loop."""
        if isinstance(sb, Superbatch):
            # DataSet-shaped block (e.g. from ParallelWrapper): lift to the
            # graph's list-of-parts shape.
            sb = MultiSuperbatch(
                [sb.features], [sb.labels],
                None if sb.features_mask is None else [sb.features_mask],
                None if sb.labels_mask is None else [sb.labels_mask],
                k=sb.k)
        k = int(sb.k)
        if k == 1:  # defensive: SuperbatchIterator yields raw singletons
            return self._fit_one(MultiDataSet(
                features=[f[0] for f in sb.features],
                labels=[l[0] for l in sb.labels],
                features_masks=None if sb.features_masks is None
                else [None if m is None else m[0] for m in sb.features_masks],
                labels_masks=None if sb.labels_masks is None
                else [None if m is None else m[0] for m in sb.labels_masks],
            ))
        step_fn = self._get_jit("train_superstep", k=k,
                                scan=_superstep.use_scan(),
                                kernels=_superstep.kernel_config())
        args = (self.params_tree, self.state, self.opt_state,
                [jnp.asarray(f) for f in sb.features],
                [jnp.asarray(l) for l in sb.labels],
                _as_mask_list(sb.features_masks),
                _as_mask_list(sb.labels_masks),
                self._device_clock())
        with _FIT.enqueue():
            (self.params_tree, self.state, self.opt_state, losses,
             self._clock) = step_fn(*args)
        for i in range(k):
            self._score = losses[i]  # device scalar; sync deferred
            self.iteration += 1
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration)

    def _fit_tbptt(self, mds: MultiDataSet):
        """Truncated BPTT over a DAG (reference: `ComputationGraph` tBPTT path):
        chunk all sequence arrays along time; rnn state carries across chunks."""
        if any(getattr(v.layer, "decode_cache_length", None)
               for v in self.layer_vertices.values()):
            raise ValueError(
                "truncated BPTT carries undeclared layer state across "
                "chunks, which would thread attention KV caches into "
                "training; unset decode_cache_length (it is an inference "
                "feature) or use standard backprop")
        fwd = self.conf.tbptt_fwd_length
        t = max(f.shape[1] for f in mds.features if f.ndim == 3)
        saved_state = self.state
        # Per-output divisors from the FULL-sequence masks (a row masked out
        # of one chunk still counts — reference divide-by-minibatch).
        full_lmasks = mds.labels_masks
        ebs = tuple(
            jax.device_put(np.float32(
                losses_mod.effective_batch_size(
                    l, full_lmasks[i] if full_lmasks is not None else None
                )
            ))
            for i, l in enumerate(mds.labels)
        )
        for lab in mds.labels:
            sparse = (np.issubdtype(np.asarray(lab).dtype, np.integer)
                      and lab.ndim == 2)
            if lab.ndim != 3 and not sparse:
                raise ValueError(
                    "Truncated BPTT requires per-timestep labels: [b, t, c] "
                    "one-hot or [b, t] integer class ids"
                )

        def time_slice(a, sl, is_mask=False):
            # Only 3-D [b, t, f] arrays (and, explicitly, 2-D [b, t] masks
            # or [b, t] integer class-id labels) are sequences; a static
            # 2-D float input whose feature dim happens to equal t must
            # pass through untouched.
            if a is None:
                return None
            if a.ndim == 3 and a.shape[1] == t:
                return a[:, sl]
            if a.ndim == 2 and a.shape[1] == t and (
                    is_mask or np.issubdtype(np.asarray(a).dtype,
                                             np.integer)):
                return a[:, sl]
            return a

        if not self._collect_stats:
            # Fast path: the whole chunk loop is one jitted scan — ONE
            # dispatch per sequence (PERF.md §4); per-chunk dispatch remains
            # only for StatsListener observability.
            step_fn = self._get_jit("train_step_tbptt_scan")
            fmasks = _as_mask_list(mds.features_masks)
            lmasks = _as_mask_list(mds.labels_masks)
            args = (self.params_tree, self.state, self.opt_state,
                    [jnp.asarray(f) for f in mds.features],
                    [jnp.asarray(l) for l in mds.labels],
                    fmasks, lmasks, self._device_clock(), ebs)
            with _FIT.enqueue():
                (self.params_tree, self.state, self.opt_state, loss,
                 self._clock) = step_fn(*args)
            self._score = loss
            return self._finish_tbptt(saved_state)
        n_chunks = math.ceil(t / fwd)
        for ci in range(n_chunks):
            sl = slice(ci * fwd, min((ci + 1) * fwd, t))
            chunk = MultiDataSet(
                features=[time_slice(f, sl) for f in mds.features],
                labels=[time_slice(l, sl) for l in mds.labels],
                features_masks=None if mds.features_masks is None
                else [time_slice(m, sl, is_mask=True) for m in mds.features_masks],
                labels_masks=None if mds.labels_masks is None
                else [time_slice(m, sl, is_mask=True) for m in mds.labels_masks],
            )
            self._fit_one(chunk, tbptt=True, count_iteration=False, ebs=ebs,
                          advance=ci == n_chunks - 1)
        self._finish_tbptt(saved_state)

    def _finish_tbptt(self, saved_state):
        # Drop rnn carries, keep declared (BN) state.
        declared = {n: set(v.layer.state_shapes()) for n, v in self.layer_vertices.items()}
        self.state = {
            n: {k: v for k, v in s.items() if k in declared.get(n, set())}
            for n, s in self.state.items()
        }
        self.state = {n: s for n, s in self.state.items() if s}
        for n, s in saved_state.items():
            self.state.setdefault(n, s)
        self.iteration += 1
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration)

    def _next_rng(self):
        if self._clock is not None:
            # The rng stream's continuation lives in the device clock; pull it
            # back to the host-side attribute before splitting.
            self._train_rng = self._clock[1]
            self._clock = None
        self._train_rng, sub = jax.random.split(self._train_rng)
        return sub

    def _fit_one(self, mds: MultiDataSet, tbptt: bool = False,
                 count_iteration: bool = True, ebs=None, advance=True):
        if tbptt:
            step_fn = self._get_jit("train_step_tbptt", advance=advance,
                                    collect=self._collect_stats)
        else:
            kind = "train_step_stats" if self._collect_stats else "train_step"
            step_fn = self._get_jit(kind)
        fmasks = _as_mask_list(mds.features_masks)
        lmasks = _as_mask_list(mds.labels_masks)
        args = [
            self.params_tree, self.state, self.opt_state,
            [jnp.asarray(f) for f in mds.features],
            [jnp.asarray(l) for l in mds.labels],
            fmasks, lmasks, self._device_clock(),
        ]
        if tbptt:
            args.append(ebs)
        with _FIT.enqueue():
            out = step_fn(*args)
        if len(out) == 6:
            self.params_tree, self.state, self.opt_state, loss, stats, self._clock = out
            self.last_training_stats = stats
        else:
            self.params_tree, self.state, self.opt_state, loss, self._clock = out
        self._score = loss  # device scalar; sync deferred to score_value
        if count_iteration:
            self.iteration += 1
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration)

    # -------------------------------------------------------------- predict

    def output(self, *inputs, train: bool = False, features_masks=None,
               params=None) -> List[np.ndarray]:
        """`params` substitutes another params tree of the same structure
        (e.g. an adapter-merged serving tree — `nn/lora.py`) for this
        net's own; params are jit arguments, so the swap re-uses the
        compiled program."""
        fn = self._get_jit("output", train=train)
        outs, _ = fn(self.params_tree if params is None else params,
                     self.state,
                     [jnp.asarray(x) for x in inputs],
                     features_masks,
                     self._next_rng() if train else jax.random.PRNGKey(0))
        return [np.asarray(o) for o in outs]

    def output_single(self, *inputs, **kw) -> np.ndarray:
        return self.output(*inputs, **kw)[0]

    # ----------------------------------------------------------------- rnn

    def _declared_state(self):
        return {
            name: tuple(v.layer.state_shapes())
            for name, v in self.layer_vertices.items()
        }

    def rnn_time_step(self, *inputs) -> List[np.ndarray]:
        """Stateful single/multi-step inference (reference:
        `ComputationGraph.rnnTimeStep:1386` — same contract as
        `MultiLayerNetwork.rnn_time_step`): hidden state (LSTM carries,
        attention KV caches, positional cursors) persists across calls.
        Accepts [b, f] (one step) or [b, t, f] per input."""
        from deeplearning4j_tpu.nn import rnn_state as rnn_mod

        arrs = []
        squeeze = False
        for x in inputs:
            x = np.asarray(x)
            if x.ndim == 2:
                x = x[:, None, :]
                squeeze = True
            arrs.append(x)
        self._rnn_pos = rnn_mod.check_decode_budget(
            getattr(self, "_rnn_pos", 0), arrs[0].shape[1],
            rnn_mod.decode_capacity(
                v.layer for v in self.layer_vertices.values()))
        fn = self._get_jit("output", train=False, keep_rnn_state=True)
        state = rnn_mod.merge_rnn_state(self.state, self._rnn_state)
        outs, new_state = fn(self.params_tree, state,
                             [jnp.asarray(x) for x in arrs], None,
                             jax.random.PRNGKey(0))
        self._rnn_state = rnn_mod.split_rnn_state(new_state,
                                                  self._declared_state())
        result = []
        for o in outs:
            o = np.asarray(o)
            result.append(o[:, 0] if squeeze and o.ndim == 3 else o)
        return result

    def rnn_clear_previous_state(self):
        self._rnn_state = {}
        self._rnn_pos = 0

    def score(self, data, labels=None) -> float:
        mds = _as_mds(data, labels)
        fn = self._get_jit("score")
        fmasks = _as_mask_list(mds.features_masks)
        lmasks = _as_mask_list(mds.labels_masks)
        return float(fn(
            self.params_tree, self.state,
            [jnp.asarray(f) for f in mds.features],
            [jnp.asarray(l) for l in mds.labels],
            fmasks, lmasks,
        ))

    def loss_and_gradients(self, data, labels=None, wrt=None, collect=()):
        """The training objective and its gradients on one batch, at the
        current parameters and without an update: the very loss the train
        step differentiates (`_forward_fn` with train=True under the net's
        dtype policy, `_loss_from_outputs`), for checks against a reference.

        `wrt`: `{layer: [param names]}` to differentiate (default: every
        trainable leaf). `collect`: names of vertices whose values are
        returned too (an output layer's value is its pre-activation), or
        `<layer>.<name>` for a layer's by-product of this very pass
        (`attn0.selected_keys`, `ffn0.expert_idx`).
        Returns `(loss, {layer: {name: gradient}}, {vertex: value})`. One
        compile per call: not for a loop."""
        mds = _as_mds(data, labels)
        spec = dict(self._frozen_spec or {})
        if wrt is None:
            wrt = {n: [k for k in p if k not in spec.get(n, ())]
                   for n, p in self.params_tree.items() if p}
        wrt = {n: list(ks) for n, ks in wrt.items() if ks}
        for n, ks in wrt.items():
            frozen = set(ks) & set(spec.get(n, ()))
            if frozen:
                raise ValueError(f"{n}: {sorted(frozen)} are frozen")

        def fn(sub, params, state, inputs, labels_, fmasks, lmasks, rng):
            def loss_fn(sub):
                p = {n: ({**lp, **sub[n]} if n in sub else lp)
                     for n, lp in params.items()}
                outs, _, values, aux, omasks = self._forward_fn(
                    p, state, inputs, rng, True, fmasks, collect=True)
                loss, _ = self._loss_from_outputs(p, outs, labels_, lmasks,
                                                  aux, omasks)
                return loss, {n: values[n] for n in collect}

            (loss, vals), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(sub)
            return loss, grads, vals

        sub = {n: {k: self.params_tree[n][k] for k in ks}
               for n, ks in wrt.items()}
        return jax.jit(fn)(
            sub, self.params_tree, self.state, list(mds.features),
            list(mds.labels), _as_mask_list(mds.features_masks),
            _as_mask_list(mds.labels_masks), jax.random.PRNGKey(0))

    def evaluate(self, iterator, top_n: int = 1):
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        ev = Evaluation(top_n=top_n)
        maybe_reset(iterator)
        if isinstance(iterator, (DataSet, MultiDataSet)):
            iterator = [iterator]
        for item in iterator:
            mds = _as_mds(item)
            fmasks = _as_mask_list(mds.features_masks)
            out = self.output(*mds.features, features_masks=fmasks)[0]
            lmask = mds.labels_masks[0] if mds.labels_masks else None
            ev.eval(mds.labels[0], out, mask=lmask)
        return ev

    # ------------------------------------------------------------- params io

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        self._collect_stats = any(
            getattr(l, "requires_training_stats", False) for l in listeners)
        return self

    def num_params(self) -> int:
        return int(sum(params_mod.num_params(v.layer) for v in self.layer_vertices.values()))

    def _param_orders(self):
        return {n: list(v.layer.param_shapes()) for n, v in self.layer_vertices.items()}

    def _param_vertex_order(self):
        return [n for n in self.topo_order if n in self.layer_vertices]

    def params(self) -> np.ndarray:
        return params_mod.flatten_params(
            self.params_tree, self._param_vertex_order(), self._param_orders()
        )

    def set_params(self, flat: np.ndarray):
        self.params_tree = params_mod.unflatten_params(
            np.asarray(flat), self.params_tree, self._param_vertex_order(), self._param_orders()
        )

    def updater_state_flat(self) -> np.ndarray:
        leaves = jax.tree_util.tree_leaves(self.opt_state)
        if not leaves:
            return np.zeros((0,), np.float32)
        return np.concatenate([np.asarray(l).reshape(-1) for l in leaves])

    def set_updater_state_flat(self, flat: np.ndarray):
        leaves, treedef = jax.tree_util.tree_flatten(self.opt_state)
        out, pos = [], 0
        for l in leaves:
            n = int(np.prod(l.shape))
            out.append(jnp.asarray(np.asarray(flat[pos:pos + n]).reshape(l.shape), l.dtype))
            pos += n
        self.opt_state = jax.tree_util.tree_unflatten(treedef, out)

    def clone(self) -> "ComputationGraph":
        """Deep copy with COPIED device buffers (the train step donates the
        source's buffers; aliased arrays would be deleted under the clone)."""
        net = ComputationGraph(copy.deepcopy(self.conf))
        if self._initialized:
            net.init(params=jax.tree_util.tree_map(jnp.copy, self.params_tree))
            net.state = jax.tree_util.tree_map(jnp.copy, self.state)
            net.opt_state = jax.tree_util.tree_map(jnp.copy, self.opt_state)
            net.iteration = self.iteration
            net.epoch = self.epoch
        return net

    def summary(self) -> str:
        lines = ["=" * 78]
        lines.append(f"{'Vertex':<28}{'Type':<28}{'Params':>10}")
        lines.append("-" * 78)
        for name in self.topo_order:
            v = self.conf.vertices[name]
            if isinstance(v, LayerVertex):
                lines.append(
                    f"{name:<28}{type(v.layer).__name__:<28}{params_mod.num_params(v.layer):>10}"
                )
            else:
                lines.append(f"{name:<28}{type(v).__name__:<28}{'-':>10}")
        lines.append("-" * 78)
        lines.append(f"Total params: {self.num_params()}")
        lines.append("=" * 78)
        return "\n".join(lines)
