"""MultiLayerNetwork: the sequential network engine.

Equivalent of the reference's `nn/multilayer/MultiLayerNetwork.java` (2527 LoC)
— but where the reference is a mutable object graph dispatching per-op kernels,
this engine compiles the whole model into pure jitted programs:

- `init()` builds the params/state pytrees (the reference's flattened param
  view `:384-473` is available via `params()`/`set_params()` for checkpoint
  parity, but the pytree is the source of truth);
- `fit()` drives one jitted `train_step` per minibatch: forward + loss +
  autodiff backward + gradient normalization + updater + param update all fuse
  into a single XLA executable with donated buffers (the reference's
  Solver/StochasticGradientDescent/updater/stepFunction stack,
  `optimize/solvers/StochasticGradientDescent.java:51-72`, collapses into it);
- truncated BPTT (`doTruncatedBPTT:1138`) = chunked scan with state carried
  across chunks as data (gradient truncation falls out of step boundaries);
- `rnn_time_step` (`:2230`) = same forward with persistent hidden state.
"""

from __future__ import annotations

import copy
import math
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import activations as activations_mod
from deeplearning4j_tpu.nn import losses as losses_mod
from deeplearning4j_tpu.nn import params as params_mod
from deeplearning4j_tpu.nn.conf.enums import (
    BackpropType,
    LossFunction,
    OptimizationAlgorithm,
)
from deeplearning4j_tpu.nn.conf.dtype_policy import resolve_policy
from deeplearning4j_tpu.nn.conf.layers import CenterLossOutputLayer, is_bias_param
from deeplearning4j_tpu.nn.conf.neural_net import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf import preprocessors as preprocessors_mod
from deeplearning4j_tpu.nn.layers import OUTPUT_LAYER_TYPES, get_impl
from deeplearning4j_tpu.ops import grad_norm as grad_norm_mod
from deeplearning4j_tpu.ops import schedules as schedules_mod
from deeplearning4j_tpu.ops import updaters as updaters_mod
from deeplearning4j_tpu.nn import jit_cache as jit_cache_mod
from deeplearning4j_tpu.nn import superstep as _superstep
from deeplearning4j_tpu.nn import transfer as transfer_mod
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets import staging as _staging
from deeplearning4j_tpu.datasets.iterators import (
    Superbatch,
    SuperbatchIterator,
    maybe_reset,
    transfer_cast,
)
from deeplearning4j_tpu import observability as _obs
from deeplearning4j_tpu.nn.fit_obs import FitObs

# This engine's hot-loop metric series and fit-loop spans.
_FIT = FitObs("mln")


_cast_floating = params_mod.cast_floating

# Keys in `opt_state` that are NOT layer entries: the f32 master param tree
# (low-precision param policies) and the (scale, good_count) loss-scale
# carry. `_apply_updates` iterates layer keys only, so these pass through
# untouched and re-attach after each update.
_RESERVED_OPT_KEYS = ("_master", "_ls")


def _as_dataset(data, labels=None) -> DataSet:
    if isinstance(data, DataSet):
        return data
    if labels is None and isinstance(data, tuple) and len(data) == 2:
        data, labels = data  # score((x, y)) / fit((x, y)) convenience form
    return DataSet(np.asarray(data), None if labels is None else np.asarray(labels))


class MultiLayerNetwork:
    """Sequential network engine (see module docstring)."""

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        self.layer_keys = [f"layer_{i}" for i in range(len(conf.layers))]
        self.params_tree: Optional[Dict[str, Dict[str, jnp.ndarray]]] = None
        self.state: Dict[str, Dict[str, jnp.ndarray]] = {}
        self.opt_state: Optional[Dict[str, Any]] = None
        self.iteration = 0
        self.epoch = 0
        self._score = float("nan")
        self.listeners: List[Any] = []
        self._rnn_state: Dict[str, Dict[str, jnp.ndarray]] = {}
        self._clock = None  # on-device (step, rng) carry; see _device_clock
        self._initialized = False
        self._collect_stats = False
        self.last_training_stats: Dict[str, Any] = {}
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


    @property
    def score_value(self) -> float:
        """Loss of the most recent iteration. Reading this syncs with the
        device (the train loop itself never blocks — important over
        high-latency device transports)."""
        v = self._score
        return float(v) if v is not None else float("nan")

    @score_value.setter
    def score_value(self, v):
        self._score = v

    # ------------------------------------------------------------------ init

    def init(self, params: Optional[Dict[str, Dict[str, jnp.ndarray]]] = None) -> "MultiLayerNetwork":
        g = self.conf.global_conf
        pol = self.dtype_policy
        root = jax.random.PRNGKey(g.seed)
        # Low-precision param policies still INITIALIZE at f32 — the f32
        # draw is the master copy, params are its cast. State (BN running
        # stats) always stays at the master precision.
        pdt = jnp.float32 if pol.low_precision_params else pol.jnp_param
        keys = jax.random.split(root, max(len(self.layers), 1))
        master = None
        if params is None:
            params = {
                lk: params_mod.init_layer_params(layer, keys[i], dtype=pdt)
                for i, (lk, layer) in enumerate(zip(self.layer_keys, self.layers))
            }
            if pol.low_precision_params:
                master = params
                params = _cast_floating(params, pol.jnp_param)
        elif pol.low_precision_params:
            master = _cast_floating(params, jnp.float32)
        self.params_tree = params
        self.state = {
            lk: params_mod.init_layer_state(layer, dtype=pdt)
            for lk, layer in zip(self.layer_keys, self.layers)
            if layer.state_shapes()
        }
        self._updaters = [
            updaters_mod.create(
                layer.updater,
                momentum=layer.momentum if layer.momentum is not None else g.momentum,
                adam_mean_decay=layer.adam_mean_decay if layer.adam_mean_decay is not None else g.adam_mean_decay,
                adam_var_decay=layer.adam_var_decay if layer.adam_var_decay is not None else g.adam_var_decay,
                rho=layer.rho if layer.rho is not None else g.rho,
                rms_decay=layer.rms_decay if layer.rms_decay is not None else g.rms_decay,
                epsilon=layer.epsilon if layer.epsilon is not None else g.epsilon,
            )
            for layer in self.layers
        ]
        self._schedules = [
            schedules_mod.make_schedule(
                float(layer.learning_rate if layer.learning_rate is not None else g.learning_rate),
                g.lr_policy, g.lr_policy_decay_rate, g.lr_policy_power,
                g.lr_policy_steps, g.max_num_iterations, g.lr_schedule,
            )
            for layer in self.layers
        ]
        # Transfer learning / LoRA (nn/transfer.py): frozen leaves get NO
        # updater state — opt_state is built over the trainable subtree
        # (a fully-frozen layer's entry is ()). Empty spec (the common
        # case) keeps the structures byte-identical to before.
        self._frozen_spec = transfer_mod.frozen_spec(
            zip(self.layer_keys, self.layers), self.params_tree)
        base = master if master is not None else self.params_tree
        opt_src = (transfer_mod.split_tree(base, self._frozen_spec)[0]
                   if self._frozen_spec else base)
        self.opt_state = {
            lk: (() if lk in self._frozen_spec and not opt_src[lk]
                 else self._updaters[i].init(opt_src[lk]))
            for i, lk in enumerate(self.layer_keys)
        }
        # Reserved opt_state keys (never layer keys): the f32 master params
        # and the on-device loss-scale carry ride INSIDE opt_state so jit
        # signatures, donation, the superstep scan carry, and checkpoint
        # trees all pick them up without any shape change.
        if master is not None:
            self.opt_state["_master"] = master
        if pol.uses_loss_scaling:
            self.opt_state["_ls"] = (
                jnp.float32(pol.initial_loss_scale), jnp.float32(0.0))
        self._train_rng = jax.random.PRNGKey(g.seed ^ 0x5EED)
        self._clock = None
        self._initialized = True
        return self

    @property
    def _uint8_policy(self) -> str:
        """How a uint8 network input is staged, from the first layer's
        declared structure (see `nn/conf/preprocessors.py`): embedding ids
        are cast, image bytes are /255-scaled."""
        return preprocessors_mod.resolve_uint8_policy(
            [self.layers[0]] if self.layers else [])

    # ------------------------------------------------------------- clock
    # The (step, rng) pair lives ON DEVICE and is advanced inside the jitted
    # train step, so the hot loop never converts a host scalar or transfers:
    # one async dispatch per step, all-device arguments.

    def _device_clock(self):
        if self._clock is None:
            self._clock = (
                jax.device_put(np.float32(self.iteration)),
                self._train_rng,
            )
        return self._clock

    # --------------------------------------------------------------- forward

    def _forward_fn(self, params, state, x, rng, train: bool, fmask,
                    upto: Optional[int] = None, collect: bool = False,
                    keep_rnn_state: bool = False):
        """Pure forward pass (traced). Returns (final, new_state, activations, aux)."""
        cdt = self._compute_dtype
        # Device-side ImagePreProcessingScaler (reference:
        # `ImagePreProcessingScaler.java` scales 0-255 -> 0-1 on HOST):
        # shipping bytes and scaling on device quarters the host->device
        # traffic of streamed image batches (PERF.md §3). The uint8
        # interpretation (image bytes vs embedding ids) is decided by the
        # first layer's declared structure, not sniffed from the dtype.
        x = preprocessors_mod.apply_uint8_policy(
            jnp.asarray(x), self._uint8_policy, cdt)
        mask = fmask
        new_state: Dict[str, Any] = {}
        acts: List[jnp.ndarray] = []
        aux: Dict[str, Any] = {}
        n = len(self.layers) if upto is None else upto
        for i in range(n):
            layer = self.layers[i]
            lk = self.layer_keys[i]
            if i in self.conf.input_preprocessors:
                x, mask = self.conf.input_preprocessors[i](x, mask)
            if isinstance(layer, CenterLossOutputLayer):
                aux["center_loss_input"] = x
                aux["centers"] = state.get(lk, {}).get("centers")
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            # Params stored at param_dtype, cast (or dequantized) to the
            # policy's compute dtype at use (nn/params.py).
            lparams = params_mod.prep_layer_params(params.get(lk, {}), cdt,
                                                   layer=layer)
            lstate = state.get(lk, {})
            x, lstate_new, mask = get_impl(layer)(
                layer, lparams, lstate, x, rng=lrng, train=train, mask=mask
            )
            if lstate_new and "_aux_loss" in lstate_new:
                # Reserved key: auxiliary loss terms (MoE load balance) are
                # collected into the objective, never persisted as state.
                lstate_new = dict(lstate_new)
                aux["aux_loss"] = aux.get("aux_loss", 0.0) + lstate_new.pop(
                    "_aux_loss")
            if lstate_new:
                # Only persist what the layer declares (BN stats) unless the
                # caller wants rnn hidden state carried (tbptt / rnn_time_step).
                declared = set(layer.state_shapes())
                keep = {k: v for k, v in lstate_new.items()
                        if not k.startswith("_")  # a by-product, never state
                        and (k in declared or keep_rnn_state)}
                if keep:
                    new_state[lk] = keep
            if collect:
                acts.append(x)
        return x, new_state, acts, aux

    def _output_activation(self, preout):
        layer = self.layers[-1]
        if type(layer).__name__ in OUTPUT_LAYER_TYPES:
            return activations_mod.resolve(layer.activation)(preout)
        return preout

    def _get_jit(self, kind: str, **static):
        # Key construction/lookup + compile-cache store hook shared with
        # ComputationGraph (see nn/jit_cache.py).
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
        # `k`/`scan` select the superstep program shape (`nn/superstep.py`)
        # and are part of the `_get_jit` cache key: each distinct block
        # length registers as its own cached program, so StepProfiler's
        # jit-cache-growth heuristic classifies a tail block's first call as
        # compile, not steady-state execute. `kernels` is pure program
        # identity (the kernel-registry selection the trace resolves under,
        # `nn/superstep.py::kernel_config`) — never read here.
        if kind == "solver_step":
            from jax.flatten_util import ravel_pytree

            from deeplearning4j_tpu.optimize import solvers as solvers_mod

            g = self.conf.global_conf
            iterations = max(1, g.iterations)
            mls = max(1, int(g.max_num_line_search_iterations))

            def solver_fn(params, state, x, y, fmask, lmask):
                w0, unravel = ravel_pytree(params)

                def loss_flat(w):
                    p = unravel(w)
                    preout, _, _, aux = self._forward_fn(
                        p, state, x, None, False, fmask)
                    return self._loss_from_preout(p, preout, y, lmask, aux)[0]

                w, loss = solvers_mod.minimize(
                    algo, loss_flat, w0, iterations=iterations,
                    max_line_search=mls)
                return unravel(w), loss

            return jax.jit(solver_fn, donate_argnums=(0,))
        if kind == "output":
            def output_fn(params, state, x, fmask, rng):
                final, new_state, _, _ = self._forward_fn(
                    params, state, x, rng, train, fmask, keep_rnn_state=keep_rnn_state
                )
                out = self._output_activation(final.astype(self._output_dtype))
                return out, new_state
            return jax.jit(output_fn)
        if kind == "score":
            def score_fn(params, state, x, y, fmask, lmask):
                preout, _, _, aux = self._forward_fn(params, state, x, None, False, fmask)
                return self._loss_from_preout(params, preout, y, lmask, aux)[0]
            return jax.jit(score_fn)
        if kind == "train_step":
            def step_plain(params, state, opt_state, x, y, fmask, lmask, clock):
                step, key = clock
                key, sub = jax.random.split(key)
                out = self._train_step(params, state, opt_state, x, y, fmask,
                                       lmask, step, sub, carry_rnn=False)
                return out + ((step + 1.0, key),)
            return jax.jit(step_plain, donate_argnums=(0, 2))
        if kind == "train_superstep":
            # K full train iterations as ONE dispatch: a fused loop (`lax.scan` by
            # default, opt-in unrolled — `nn/superstep.py`) over the
            # leading [K] axis of a stacked superbatch, carrying
            # (params, state, opt_state, clock) with donated buffers and
            # returning the K per-step losses as a vector (PERF.md §13).
            # The body advances the clock exactly like `step_plain`
            # (`key, sub = split(key)` then `step + 1.0`), so the RNG split
            # chain — and therefore dropout masks, BN batch-stat order, and
            # updater step counts — is bit-for-bit identical to K
            # sequential `_fit_one` calls.
            def step_super(params, state, opt_state, xs, ys, fmasks, lmasks,
                           clock):
                def body(carry, inp):
                    params, state, opt_state, (step, key) = carry
                    x, y, fm, lm = inp
                    key, sub = jax.random.split(key)
                    params, state, opt_state, loss = self._train_step(
                        params, state, opt_state, x, y, fm, lm, step, sub,
                        carry_rnn=False)
                    return (params, state, opt_state, (step + 1.0, key)), loss

                (params, state, opt_state, clock), losses = _superstep.superstep_loop(
                    body, (params, state, opt_state, clock),
                    (xs, ys, fmasks, lmasks), k, scan)
                return params, state, opt_state, losses, clock
            return jax.jit(step_super, donate_argnums=(0, 2))
        if kind == "train_step_stats":
            def step_stats(params, state, opt_state, x, y, fmask, lmask, clock):
                step, key = clock
                key, sub = jax.random.split(key)
                out = self._train_step(params, state, opt_state, x, y, fmask,
                                       lmask, step, sub, carry_rnn=False,
                                       collect_stats=True)
                return out + ((step + 1.0, key),)
            return jax.jit(step_stats, donate_argnums=(0, 2))
        if kind == "train_step_tbptt":
            # `advance` is static: all chunks of one sequence share the same
            # step value (reference: one optimize iteration per sequence);
            # only the final chunk ticks the clock. `collect` adds the
            # StatsListener scalars (grad/update/param mean magnitudes).
            def step_tbptt(params, state, opt_state, x, y, fmask, lmask, clock, eb):
                step, key = clock
                key, sub = jax.random.split(key)
                out = self._train_step(params, state, opt_state, x, y, fmask,
                                       lmask, step, sub, carry_rnn=True, eb=eb,
                                       collect_stats=collect)
                new_step = step + 1.0 if advance else step
                return out + ((new_step, key),)
            return jax.jit(step_tbptt, donate_argnums=(0, 2))
        if kind == "train_step_tbptt_scan":
            # The WHOLE tBPTT pass as ONE jitted program: chunk 0 unrolled
            # (it CREATES the rnn-carry entries in `state`, so the carry
            # structure is only scan-stable from chunk 1 on), the full-length
            # middle chunks as a `lax.scan`, and any short remainder chunk
            # unrolled at its TRUE length — no padding, so BatchNorm batch
            # stats and masked losses see exactly the data the per-chunk
            # host loop saw. The host loop it replaces pays one dispatch per
            # chunk (what that costs is not measured on the current
            # machine). Note each distinct sequence length t compiles its
            # own program (the old loop reused [B, fwd] chunk programs
            # across t); bucket/pad sequence lengths host-side if feeding
            # many distinct lengths.
            fwd = int(self.conf.tbptt_fwd_length)

            def chunked(a, n):
                if a is None:
                    return None
                # [B, n*fwd, ...] -> [n, B, fwd, ...] (scan axis leading)
                b = a.shape[0]
                a = a.reshape((b, n, fwd) + a.shape[2:])
                return jnp.moveaxis(a, 1, 0)

            def at(a, i):
                return None if a is None else a[i]

            def tslice(a, sl):
                return None if a is None else a[:, sl]

            def step_scan(params, state, opt_state, x, y, fmask, lmask,
                          clock, eb):
                step, key = clock
                t = x.shape[1]
                n_full = t // fwd  # >= 1: _fit_dispatch requires t > fwd
                rem = t - n_full * fwd
                # Same RNG chain as the per-chunk stats path (`step_tbptt`
                # does `key, sub = split(key)` per chunk), so attaching a
                # StatsListener never changes training numerics.
                subs = []
                for _ in range(n_full + (1 if rem else 0)):
                    key, sub = jax.random.split(key)
                    subs.append(sub)

                full = slice(0, n_full * fwd)
                xs, ys = chunked(tslice(x, full), n_full), chunked(tslice(y, full), n_full)
                fs, ls = (chunked(tslice(fmask, full), n_full),
                          chunked(tslice(lmask, full), n_full))

                params, state, opt_state, loss = self._train_step(
                    params, state, opt_state, xs[0], ys[0], at(fs, 0),
                    at(ls, 0), step, subs[0], carry_rnn=True, eb=eb)

                if n_full > 1:
                    def body(carry, inp):
                        params, state, opt_state = carry
                        cx, cy, cf, cl, sub = inp
                        params, state, opt_state, closs = self._train_step(
                            params, state, opt_state, cx, cy, cf, cl, step,
                            sub, carry_rnn=True, eb=eb)
                        return (params, state, opt_state), closs

                    (params, state, opt_state), losses = jax.lax.scan(
                        body, (params, state, opt_state),
                        (at(xs, slice(1, None)), at(ys, slice(1, None)),
                         at(fs, slice(1, None)), at(ls, slice(1, None)),
                         jnp.stack(subs[1:n_full])))
                    loss = losses[-1]
                if rem:
                    tail = slice(n_full * fwd, t)
                    params, state, opt_state, loss = self._train_step(
                        params, state, opt_state, tslice(x, tail),
                        tslice(y, tail), tslice(fmask, tail),
                        tslice(lmask, tail), step, subs[-1],
                        carry_rnn=True, eb=eb)
                return (params, state, opt_state, loss,
                        (step + 1.0, key))
            return jax.jit(step_scan, donate_argnums=(0, 2))
        if kind == "feedforward":
            def ff_fn(params, state, x, fmask, rng):
                _, new_state, acts, _ = self._forward_fn(
                    params, state, x, rng, train, fmask, collect=True
                )
                return acts, new_state
            return jax.jit(ff_fn)
        raise ValueError(kind)

    # ----------------------------------------------------------------- loss

    def _l1_l2_penalty(self, params):
        """L1/L2 terms added at score time (reference: `Layer.calcL1/calcL2`,
        score semantics SURVEY.md §2.4). Applied to weight params only."""
        total = 0.0
        for lk, layer in zip(self.layer_keys, self.layers):
            l1 = float(layer.l1 or 0.0)
            l2 = float(layer.l2 or 0.0)
            if (l1 == 0.0 and l2 == 0.0) or lk not in params:
                continue
            for wk in layer.weight_param_keys():
                if wk not in params[lk]:
                    continue
                w = params[lk][wk].astype(self._loss_dtype)
                if l2:
                    total = total + 0.5 * l2 * jnp.sum(w * w)
                if l1:
                    total = total + l1 * jnp.sum(jnp.abs(w))
        return total

    def _loss_from_preout(self, params, preout, y, lmask, aux, eb=None):
        layer = self.layers[-1]
        name = type(layer).__name__
        if name not in OUTPUT_LAYER_TYPES:
            raise ValueError(
                f"Last layer ({name}) is not an output layer; cannot compute loss"
            )
        preout = preout.astype(self._loss_dtype)
        # `eb` overrides the divisor for tBPTT chunks: a row fully masked
        # within ONE chunk of a variable-length batch still counts toward the
        # reference's divide-by-minibatch (computed from the full-sequence
        # mask in `_fit_tbptt`), while data-parallel padding rows never do.
        if eb is None:
            eb = losses_mod.effective_batch_size(y, lmask)
        data_loss = losses_mod.score(
            layer.loss_function, y, preout, layer.activation, lmask,
            average=False,
        ) / eb
        extra_state = {}
        if isinstance(layer, CenterLossOutputLayer):
            feats = aux["center_loss_input"].astype(self._loss_dtype)
            centers = aux["centers"]
            cls = (jnp.asarray(y, jnp.int32)
                   if jnp.issubdtype(jnp.asarray(y).dtype, jnp.integer)
                   else jnp.argmax(y, axis=-1))
            c = centers[cls]
            # Row weights: the labels mask excludes data-parallel padding rows
            # from both the center-loss term and the center updates.
            w = jnp.ones(y.shape[0], self._loss_dtype) if lmask is None else (
                lmask.reshape(y.shape[0], -1)[:, 0].astype(self._loss_dtype))
            data_loss = data_loss + 0.5 * layer.lambda_ * jnp.sum(
                w * jnp.sum((feats - c) ** 2, axis=-1)
            ) / eb
            # EMA center update (reference: CenterLossOutputLayer center updates)
            diff = (c - feats) * w[:, None]
            num = jax.ops.segment_sum(diff, cls, num_segments=layer.n_out)
            cnt = jax.ops.segment_sum(w.astype(jnp.float32), cls,
                                      num_segments=layer.n_out)
            new_centers = centers - layer.alpha * num / (1.0 + cnt)[:, None]
            extra_state = {self.layer_keys[-1]: {"centers": new_centers}}
        if "aux_loss" in aux:
            # Layer-emitted auxiliary objectives (MoE load balance), already
            # scaled by their layer's weight; batch-size-invariant means, so
            # not divided by eb.
            data_loss = data_loss + aux["aux_loss"]
        # Reference: `score += fullNetworkL1 + fullNetworkL2; score /= miniBatch`
        # (BaseOutputLayer.java:100-101) and the matching gradient
        # `(g + l2*w)/miniBatch` (LayerUpdater.postApply:104-108) — so the
        # penalty is divided by the batch size inside the differentiated loss.
        return data_loss + self._l1_l2_penalty(params) / eb, extra_state

    # ----------------------------------------------------------- train step

    def _train_step(self, params, state, opt_state, x, y, fmask, lmask, step, rng,
                    carry_rnn=False, eb=None, collect_stats=False):
        pol = self.dtype_policy
        scaling = pol.uses_loss_scaling
        lowp = pol.low_precision_params
        # Transfer learning / LoRA: differentiate the TRAINABLE subtree
        # only — frozen leaves (incl. int8 bases, which jax.grad refuses)
        # close over the loss as constants, their grads are never built,
        # and they re-attach to the outputs as the same arrays. Empty
        # spec: identity, the traced program is unchanged.
        spec = getattr(self, "_frozen_spec", None)
        if spec:
            params, frozen_stored = transfer_mod.split_tree(params, spec)
        else:
            frozen_stored = None

        def loss_fn(p):
            if frozen_stored is not None:
                p = transfer_mod.merge_tree(p, frozen_stored)
            preout, new_state, _, aux = self._forward_fn(
                p, state, x, rng, True, fmask, keep_rnn_state=carry_rnn
            )
            loss, extra_state = self._loss_from_preout(p, preout, y, lmask, aux, eb)
            for lk, s in extra_state.items():
                new_state.setdefault(lk, {}).update(s)
            return loss, new_state

        if scaling:
            # Dynamic loss scaling (f16-class compute): backward runs on the
            # SCALED loss so small grads survive the f16 representable range;
            # grads unscale in f32 afterwards. The (scale, good_count) pair is
            # part of opt_state — device-resident, so a fused superstep scan
            # carries it with zero host round-trips.
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
                grads = _cast_floating(grads, jnp.float32)

        # Low-precision params: updates apply to the f32 MASTER copy (and
        # f32 updater state); stored params are its cast, so tiny updates
        # never underflow bf16/f16 quantization.
        base = opt_state["_master"] if lowp else params
        frozen_master = None
        if spec and lowp:
            base, frozen_master = transfer_mod.split_tree(base, spec)
        new_base, new_opt, stats = self._apply_updates(
            base, grads, opt_state, step, collect_stats=collect_stats)

        if scaling:
            # Skip-step on non-finite scaled grads: every updated leaf
            # selects its OLD value (params, updater state, batch stats),
            # then the scale backs off; after `growth_interval` consecutive
            # finite steps it grows. All `jnp.where` on device — no host
            # sync, superstep-safe.
            def sel(n, o):
                return jnp.where(finite, n, o)

            new_base = jax.tree_util.tree_map(sel, new_base, base)
            new_opt = jax.tree_util.tree_map(
                sel, new_opt, {lk: opt_state[lk] for lk in new_opt})
            new_state = {
                lk: {k: (sel(v, state[lk][k])
                         if lk in state and k in state[lk] else v)
                     for k, v in s.items()}
                for lk, s in new_state.items()
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
            new_params = _cast_floating(new_base, pol.jnp_param)
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

        # Merge persistent-state updates (BN stats / rnn carries) over old state.
        merged_state = dict(state)
        for lk, s in new_state.items():
            merged = dict(merged_state.get(lk, {}))
            merged.update(s)
            merged_state[lk] = merged
        if collect_stats:
            return new_params, merged_state, new_opt, loss, stats
        return new_params, merged_state, new_opt, loss

    def _apply_updates(self, params, grads, opt_state, step,
                       collect_stats=False):
        """Per-layer gradient-normalize + updater + param update (traced) —
        the reference's LayerUpdater stack. Shared by `_train_step` and
        `parallel/pipeline_trainer.py`'s pipelined step."""
        g = self.conf.global_conf
        sign = 1.0 if g.minimize else -1.0
        new_params: Dict[str, Any] = {}
        new_opt: Dict[str, Any] = {}
        stats: Dict[str, Any] = {}
        for i, (lk, layer) in enumerate(zip(self.layer_keys, self.layers)):
            lgrads = grads.get(lk, {})
            if not lgrads:
                new_params[lk] = params.get(lk, {})
                new_opt[lk] = opt_state.get(lk, ())
                continue
            lgrads = grad_norm_mod.normalize_layer_gradients(
                lgrads, layer.gradient_normalization,
                float(layer.gradient_normalization_threshold or 1.0),
            )
            lr = self._schedules[i](step)
            st, deltas = self._updaters[i].update(opt_state[lk], lgrads, lr, step)
            base_lr = float(layer.learning_rate if layer.learning_rate is not None else g.learning_rate)
            bias_lr = float(layer.bias_learning_rate if layer.bias_learning_rate is not None else base_lr)
            if bias_lr != base_lr and base_lr != 0.0:
                factor = bias_lr / base_lr
                # is_bias_param covers every bias name (b, b_f/b_b for
                # bidirectional RNNs, vb/eb/db for RBM/VAE, beta for BN) —
                # reference `LayerUpdater.java:243` applies biasLearningRate
                # per param TYPE, not only to params literally named "b".
                deltas = {k: (d * factor if is_bias_param(k) else d)
                          for k, d in deltas.items()}
            new_params[lk] = {
                k: params[lk][k] - sign * deltas[k] for k in params[lk]
            }
            new_opt[lk] = st
            if collect_stats:
                # Per-param mean magnitudes of gradient/update/param, computed
                # in-jit so only scalars cross the device boundary (reference
                # StatsListener "mean magnitudes", BaseStatsListener.java:273).
                stats[lk] = {
                    k: {
                        "grad_mm": jnp.mean(jnp.abs(lgrads[k])),
                        "update_mm": jnp.mean(jnp.abs(deltas[k])),
                        "param_mm": jnp.mean(jnp.abs(new_params[lk][k])),
                    }
                    for k in lgrads
                }
        return new_params, new_opt, stats

    # ------------------------------------------------------------------ fit

    def fit(self, data, labels=None):
        """Train over an iterator/DataSet/(x, y) pair — one pass
        (reference: `MultiLayerNetwork.fit(DataSetIterator)` `:976`)."""
        if not self._initialized:
            self.init()
        if labels is not None or isinstance(data, DataSet) or (
                isinstance(data, tuple) and len(data) == 2
                and not isinstance(data[0], DataSet)):
            # The DataSet guard keeps a 2-element tuple OF DataSets (a valid
            # small iterator) from being misread as an (x, y) pair.
            iterator = [_as_dataset(data, labels)]
        else:
            iterator = data
        maybe_reset(iterator)

        g = self.conf.global_conf
        if self.conf.pretrain:
            if not hasattr(iterator, "reset") and not isinstance(iterator, (list, tuple)):
                # One-shot iterable: materialize so both the pretrain pass and
                # the backprop pass see the data.
                iterator = list(iterator)
            self.pretrain(iterator)
            maybe_reset(iterator)
        for listener in self.listeners:
            listener.on_epoch_start(self)
        with _obs.tracer.span("mln.fit", cat="train", epoch=self.epoch):
            if self.conf.backprop:
                k = self._superstep_k()
                src = self._superstep_wrap(iterator, k) if k > 1 else iterator
                # Overlap host->device transfers with compute: multi-batch
                # epochs stream through a background DeviceStager (single
                # batches and already-staging sources pass through).
                src = _staging.maybe_stage(
                    src, net=self, engine="mln",
                    transfer_dtype=getattr(self.dtype_policy,
                                           "transfer_dtype", None))
                src_it = iter(src)
                try:
                    for ds in _FIT.batches(self, src_it):
                        self._fit_dispatch(ds)
                finally:
                    # An abandoned epoch must not leave staged HBM buffers.
                    _staging.close_stager(src_it)
                    _staging.close_stager(src)
        self.epoch += 1
        _FIT.epochs.inc()
        for listener in self.listeners:
            listener.on_epoch_end(self)
        return self

    def _fit_dispatch(self, ds):
        """tBPTT/plain/superstep dispatch + iterations loop for one staged
        batch (or stacked `Superbatch`) — shared by `fit()` and
        `ParallelWrapper` so sharded training honors the same backprop-type
        config. Also the engine's observability choke point: every training
        path (plain / tBPTT / solver / superstep, local or sharded) stages
        batches through here, and `StepProfiler` patches this method on the
        instance."""
        tdt = getattr(self.dtype_policy, "transfer_dtype", None)
        if tdt is not None:
            ds = transfer_cast(ds, tdt)
        h2d = _obs.host_nbytes(ds.features, ds.labels,
                               ds.features_mask, ds.labels_mask)
        return _FIT.dispatch(self, ds, h2d, self._fit_dispatch_inner)

    def _fit_dispatch_inner(self, ds):
        if isinstance(ds, Superbatch):
            # Stacked K-block: `_superstep_k` already gated out the solver /
            # tBPTT / stats / multi-iteration paths before blocks formed.
            return self._fit_superstep(ds)
        g = self.conf.global_conf
        algo = OptimizationAlgorithm.of(g.optimization_algo)
        if algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
            return self._fit_solver(ds, algo)
        tbptt = BackpropType.of(self.conf.backprop_type) == BackpropType.TRUNCATED_BPTT
        for _ in range(max(1, g.iterations)):
            if tbptt and ds.features.ndim == 3 and ds.features.shape[1] > self.conf.tbptt_fwd_length:
                self._fit_tbptt(ds)
            else:
                self._fit_one(ds)

    # -------------------------------------------------------------- superstep

    def _superstep_k(self) -> int:
        """Effective superstep K for this engine: the `superstep_k` config
        knob (env `DL4J_TPU_SUPERSTEP_K` overrides), gated to 0 — per-batch
        dispatch — whenever a path needs per-iteration host visibility or
        its own dispatch structure: stats-collecting listeners
        (`_collect_stats`, same precedent as the tBPTT scan), truncated
        BPTT (already scan-fused per sequence), solver optimizers, and
        multi-`iterations` batches."""
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
        """Wrap `iterator` in a `SuperbatchIterator`, caching the wrapper on
        the base iterator so a device-cached epoch restacks once, not per
        `fit()` call. The policy's `transfer_dtype` rides along so staged
        superbatches ship at the reduced dtype (halved H2D bytes)."""
        tdt = self.dtype_policy.transfer_dtype
        if isinstance(iterator, SuperbatchIterator):
            return iterator
        wrapper = getattr(iterator, "_superbatch_wrapper", None)
        if (isinstance(wrapper, SuperbatchIterator)
                and wrapper.base is iterator and wrapper.k == k
                and getattr(wrapper, "transfer_dtype", None) == tdt):
            wrapper.net = self  # staging budget follows the current net
            return wrapper
        wrapper = SuperbatchIterator(iterator, k, transfer_dtype=tdt,
                                     net=self)
        try:
            iterator._superbatch_wrapper = wrapper
        except (AttributeError, TypeError):
            pass  # lists/tuples/slots: re-wrapped per fit(), still correct
        return wrapper

    def _fit_superstep(self, sb: Superbatch):
        """One dispatch, K train iterations (see `train_superstep` in
        `_build_jit`). The returned `[K]` loss vector fans out to listeners
        per iteration, so ScoreIterationListener etc. observe the same
        (iteration, score) sequence as the per-batch loop — scores stay
        device scalars until someone reads `score_value`."""
        k = int(sb.k)
        if k == 1:  # defensive: SuperbatchIterator yields raw singletons
            return self._fit_one(DataSet(sb.features[0],
                                         None if sb.labels is None else sb.labels[0],
                                         None if sb.features_mask is None else sb.features_mask[0],
                                         None if sb.labels_mask is None else sb.labels_mask[0]))
        step_fn = self._get_jit("train_superstep", k=k,
                                scan=_superstep.use_scan(),
                                kernels=_superstep.kernel_config())
        args = (
            self.params_tree, self.state, self.opt_state,
            jnp.asarray(sb.features), jnp.asarray(sb.labels),
            None if sb.features_mask is None else jnp.asarray(sb.features_mask),
            None if sb.labels_mask is None else jnp.asarray(sb.labels_mask),
            self._device_clock(),
        )
        with _FIT.enqueue():
            (self.params_tree, self.state, self.opt_state, losses,
             self._clock) = step_fn(*args)
        for i in range(k):
            self._score = losses[i]  # device scalar; sync deferred
            self.iteration += 1
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration)

    def _fit_solver(self, ds: DataSet, algo):
        """Full-batch LBFGS/CG/line-search optimize of one batch (reference:
        `Solver.java:41-110` dispatching to `optimize/solvers/`); the whole
        `iterations`-step solver loop is one jitted XLA computation
        (`optimize/solvers.py`). Deterministic forward (no dropout, BN
        running stats) so the line search sees a stable objective."""
        self._check_sgd_only_policy("solver optimizers (LBFGS/CG/line search)")
        g = self.conf.global_conf
        fn = self._get_jit("solver_step", algo=str(algo))
        args = (
            self.params_tree, self.state,
            jnp.asarray(ds.features), jnp.asarray(ds.labels),
            None if ds.features_mask is None else jnp.asarray(ds.features_mask),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
        )
        with _FIT.enqueue():
            self.params_tree, loss = fn(*args)
        self._score = loss
        self.iteration += max(1, g.iterations)
        # Per-layer grad/update stats are an SGD-path feature; clear any
        # stale snapshot from a previous SGD run so a StatsListener attached
        # on the solver path never reports stats from another optimizer.
        self.last_training_stats = {}
        # Deviation from the reference: `BaseOptimizer` fires listeners once
        # per SOLVER ITERATION; the jitted whole-loop solver surfaces one
        # callback per batch (iteration count still advances by
        # g.iterations), trading listener granularity for an XLA-fused loop.
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration)

    # ------------------------------------------------------------- pretrain

    def pretrain(self, iterator, epochs: int = 1):
        """Layerwise unsupervised pretraining of AE/RBM/VAE layers (reference:
        `MultiLayerNetwork.pretrain()` `:164` — feed data forward to each
        pretrainable layer, optimize that layer's unsupervised loss)."""
        from deeplearning4j_tpu.nn.layers import PRETRAIN_LOSSES

        self._check_sgd_only_policy("layerwise pretraining")
        if not self._initialized:
            self.init()
        if isinstance(iterator, DataSet):
            iterator = [iterator]
        elif not hasattr(iterator, "reset") and not isinstance(iterator, (list, tuple)):
            iterator = list(iterator)  # one-shot iterable: every layer/epoch needs it
        for i, layer in enumerate(self.layers):
            if not layer.is_pretrainable():
                continue
            loss_impl = PRETRAIN_LOSSES.get(type(layer).__name__)
            if loss_impl is None:
                continue
            for _ in range(max(1, epochs)):
                maybe_reset(iterator)
                for ds in iterator:
                    self._pretrain_step(i, layer, loss_impl,
                                        jnp.asarray(ds.features))
        return self

    def _pretrain_step(self, layer_idx: int, layer, loss_impl, x):
        lk = self.layer_keys[layer_idx]
        key = ("pretrain", layer_idx)
        if key not in self._jit_cache:
            prep = self.conf.input_preprocessors.get(layer_idx)

            def step_fn(lparams, opt_state, full_params, state, x, clock):
                step, key = clock
                key, rng = jax.random.split(key)

                def loss_fn(lp):
                    # Forward through the frozen stack below this layer.
                    h, _, _, _ = self._forward_fn(
                        {**full_params, lk: lp}, state, x, None, False, None,
                        upto=layer_idx,
                    )
                    if prep is not None:
                        h, _ = prep(h, None)
                    return loss_impl(layer, lp, h, rng)

                loss, grads = jax.value_and_grad(loss_fn)(lparams)
                lr = self._schedules[layer_idx](step)
                st, deltas = self._updaters[layer_idx].update(opt_state, grads, lr, step)
                new_lp = {k: lparams[k] - deltas[k] for k in lparams}
                return new_lp, st, loss, (step + 1.0, key)

            # No donation: the layer's param buffers also appear inside
            # full_params (arg 2), so they cannot be safely donated.
            self._jit_cache[key] = jax.jit(step_fn)
        step_fn = self._jit_cache[key]
        new_lp, new_opt, loss, self._clock = step_fn(
            self.params_tree[lk], self.opt_state[lk], self.params_tree,
            self.state, x, self._device_clock(),
        )
        self.params_tree = {**self.params_tree, lk: new_lp}
        self.opt_state = {**self.opt_state, lk: new_opt}
        self._score = loss
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

    def _fit_one(self, ds: DataSet):
        collect = self._collect_stats
        step_fn = self._get_jit("train_step_stats" if collect else "train_step")
        args = (
            self.params_tree, self.state, self.opt_state,
            jnp.asarray(ds.features),
            jnp.asarray(ds.labels),
            None if ds.features_mask is None else jnp.asarray(ds.features_mask),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
            self._device_clock(),
        )
        with _FIT.enqueue():
            out = step_fn(*args)
        if collect:
            self.params_tree, self.state, self.opt_state, loss, stats, self._clock = out
            self.last_training_stats = stats  # device scalars, fetched lazily
        else:
            self.params_tree, self.state, self.opt_state, loss, self._clock = out
        self._score = loss  # device scalar; sync deferred to score_value
        self.iteration += 1
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration)

    def _fit_tbptt(self, ds: DataSet):
        """Truncated BPTT (reference: `doTruncatedBPTT:1138`): chunk the time
        axis; rnn state carries across chunks as data (implicit gradient
        truncation at chunk boundaries)."""
        if any(getattr(l, "decode_cache_length", None) for l in self.layers):
            raise ValueError(
                "truncated BPTT carries undeclared layer state across "
                "chunks, which would thread attention KV caches into "
                "training; unset decode_cache_length (it is an inference "
                "feature) or use standard backprop")
        fwd = self.conf.tbptt_fwd_length
        t = ds.features.shape[1]
        n_chunks = math.ceil(t / fwd)
        saved_state = self.state
        # Divisor from the FULL-sequence mask: a row masked out of one chunk
        # (shorter sequence) still counts, reference divide-by-minibatch.
        eb = jax.device_put(np.float32(
            losses_mod.effective_batch_size(ds.features, ds.labels_mask)
        ))
        sparse_labels = (ds.labels is not None
                         and np.issubdtype(np.asarray(ds.labels).dtype,
                                           np.integer)
                         and np.ndim(ds.labels) == 2)
        if ds.labels is None or (np.ndim(ds.labels) != 3
                                 and not sparse_labels):
            raise ValueError(
                "Truncated BPTT requires per-timestep labels: [b, t, c] "
                "one-hot or [b, t] integer class ids "
                "(reference doTruncatedBPTT semantics)"
            )
        if not self._collect_stats:
            # Fast path: the entire chunk loop is one jitted scan — ONE
            # dispatch per sequence instead of one per chunk (PERF.md §4).
            step_fn = self._get_jit("train_step_tbptt_scan")
            args = (
                self.params_tree, self.state, self.opt_state,
                jnp.asarray(ds.features), jnp.asarray(ds.labels),
                None if ds.features_mask is None else jnp.asarray(ds.features_mask),
                None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
                self._device_clock(), eb,
            )
            with _FIT.enqueue():
                (self.params_tree, self.state, self.opt_state, loss,
                 self._clock) = step_fn(*args)
            self._score = loss
            self._finish_tbptt(saved_state)
            return
        # Stats path: per-chunk dispatch (keeps the last chunk's per-layer
        # stats observable, matching the pre-scan behavior).
        for ci in range(n_chunks):
            sl = slice(ci * fwd, min((ci + 1) * fwd, t))
            chunk = DataSet(
                ds.features[:, sl],
                ds.labels[:, sl],
                ds.features_mask[:, sl] if ds.features_mask is not None else None,
                ds.labels_mask[:, sl] if ds.labels_mask is not None else None,
            )
            collect = self._collect_stats
            step_fn = self._get_jit("train_step_tbptt",
                                    advance=ci == n_chunks - 1, collect=collect)
            args = (
                self.params_tree, self.state, self.opt_state,
                jnp.asarray(chunk.features),
                jnp.asarray(chunk.labels),
                None if chunk.features_mask is None else jnp.asarray(chunk.features_mask),
                None if chunk.labels_mask is None else jnp.asarray(chunk.labels_mask),
                self._device_clock(), eb,
            )
            with _FIT.enqueue():
                out = step_fn(*args)
            if collect:
                (self.params_tree, self.state, self.opt_state, loss, stats,
                 self._clock) = out
                self.last_training_stats = stats
            else:
                self.params_tree, self.state, self.opt_state, loss, self._clock = out
            self._score = loss  # device scalar; sync deferred to score_value
        self._finish_tbptt(saved_state)

    def _finish_tbptt(self, saved_state):
        # Reset rnn carries after the sequence; keep persistent (BN) state.
        self.state = {
            lk: {k: v for k, v in s.items() if k in dict(self._declared_state()).get(lk, ())}
            for lk, s in self.state.items()
        }
        self.state = {lk: s for lk, s in self.state.items() if s}
        # Restore any BN stats that were present before if lost (safety).
        for lk, s in saved_state.items():
            self.state.setdefault(lk, s)
        self.iteration += 1
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration)

    def _declared_state(self):
        return {
            lk: tuple(layer.state_shapes())
            for lk, layer in zip(self.layer_keys, self.layers)
        }

    # -------------------------------------------------------------- predict

    def output(self, x, train: bool = False, features_mask=None,
               params=None) -> np.ndarray:
        """Inference forward (reference: `output()` `:1519-1601`).
        `params` substitutes another params tree of the same structure
        (e.g. an adapter-merged serving tree — `nn/lora.py`) for this
        net's own; params are jit arguments, so the swap re-uses the
        compiled program."""
        fn = self._get_jit("output", train=train)
        out, _ = fn(self.params_tree if params is None else params,
                    self.state, jnp.asarray(x),
                    None if features_mask is None else jnp.asarray(features_mask),
                    self._next_rng() if train else jax.random.PRNGKey(0))
        return np.asarray(out)

    def feed_forward(self, x, train: bool = False, features_mask=None) -> List[np.ndarray]:
        """All layer activations (reference: `feedForward()` `:655-760`).
        Note: for output layers the listed activation is the pre-activation."""
        fn = self._get_jit("feedforward", train=train)
        acts, _ = fn(self.params_tree, self.state, jnp.asarray(x),
                     None if features_mask is None else jnp.asarray(features_mask),
                     self._next_rng() if train else jax.random.PRNGKey(0))
        return [np.asarray(a) for a in acts]

    def predict(self, x) -> np.ndarray:
        return np.argmax(self.output(x), axis=-1)

    def score(self, data: Union[DataSet, tuple], labels=None) -> float:
        """Loss on a dataset without updating (reference: `score(DataSet)`)."""
        ds = _as_dataset(data, labels)
        fn = self._get_jit("score")
        return float(fn(
            self.params_tree, self.state,
            jnp.asarray(ds.features), jnp.asarray(ds.labels),
            None if ds.features_mask is None else jnp.asarray(ds.features_mask),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
        ))

    # ----------------------------------------------------------------- rnn

    def rnn_time_step(self, x) -> np.ndarray:
        """Stateful single/multi-step inference (reference: `rnnTimeStep:2230`).
        Accepts [b, f] (one step) or [b, t, f]; hidden state persists across calls."""
        from deeplearning4j_tpu.nn import rnn_state as rnn_mod

        x = np.asarray(x)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        self._rnn_pos = rnn_mod.check_decode_budget(
            getattr(self, "_rnn_pos", 0), x.shape[1],
            rnn_mod.decode_capacity(self.layers))
        fn = self._get_jit("output", train=False, keep_rnn_state=True)
        state = rnn_mod.merge_rnn_state(self.state, self._rnn_state)
        out, new_state = fn(self.params_tree, state, jnp.asarray(x), None,
                            jax.random.PRNGKey(0))
        self._rnn_state = rnn_mod.split_rnn_state(new_state,
                                                  self._declared_state())
        out = np.asarray(out)
        return out[:, 0] if squeeze and out.ndim == 3 else out

    def rnn_clear_previous_state(self):
        self._rnn_state = {}
        self._rnn_pos = 0

    # ------------------------------------------------------------ eval misc

    def evaluate(self, iterator, top_n: int = 1):
        """Classification evaluation (reference: `evaluate(DataSetIterator)`
        `:2406-2506`)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        ev = Evaluation(top_n=top_n)
        maybe_reset(iterator)
        if isinstance(iterator, DataSet):
            iterator = [iterator]
        for ds in iterator:
            out = self.output(ds.features, features_mask=ds.features_mask)
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    # ------------------------------------------------------------- params io

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        # Listeners that consume gradient/update stats (StatsListener) flip
        # the train step to the stats-collecting variant.
        self._collect_stats = any(
            getattr(l, "requires_training_stats", False) for l in listeners)
        return self

    def num_params(self) -> int:
        return int(sum(params_mod.num_params(l) for l in self.layers))

    def _param_orders(self):
        return {
            lk: list(layer.param_shapes())
            for lk, layer in zip(self.layer_keys, self.layers)
        }

    def params(self) -> np.ndarray:
        """Flattened 1-D param view (reference: `Model.params()`)."""
        return params_mod.flatten_params(self.params_tree, self.layer_keys, self._param_orders())

    def set_params(self, flat: np.ndarray):
        self.params_tree = params_mod.unflatten_params(
            np.asarray(flat), self.params_tree, self.layer_keys, self._param_orders()
        )
        if (self.dtype_policy.low_precision_params and self.opt_state
                and "_master" in self.opt_state):
            # Keep the f32 master in lockstep with an externally-set view.
            self.opt_state["_master"] = _cast_floating(
                self.params_tree, jnp.float32)

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

    def clone(self) -> "MultiLayerNetwork":
        """Deep copy. Device buffers are COPIED (jnp.copy), not aliased: the
        source net's train step donates its buffers, which would delete a
        shared array out from under the clone."""
        net = MultiLayerNetwork(copy.deepcopy(self.conf))
        if self._initialized:
            net.init(params=jax.tree_util.tree_map(jnp.copy, self.params_tree))
            net.state = jax.tree_util.tree_map(jnp.copy, self.state)
            net.opt_state = jax.tree_util.tree_map(jnp.copy, self.opt_state)
            net.iteration = self.iteration
            net.epoch = self.epoch
        return net

    def summary(self) -> str:
        lines = ["=" * 70]
        lines.append(f"{'Layer':<28}{'Type':<24}{'Params':>10}")
        lines.append("-" * 70)
        for lk, layer in zip(self.layer_keys, self.layers):
            lines.append(f"{lk:<28}{type(layer).__name__:<24}{params_mod.num_params(layer):>10}")
        lines.append("-" * 70)
        lines.append(f"Total params: {self.num_params()}")
        lines.append("=" * 70)
        return "\n".join(lines)
