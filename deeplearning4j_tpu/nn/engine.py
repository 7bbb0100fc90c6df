"""Engine: what `MultiLayerNetwork` and `ComputationGraph` share.

One train step, one set of jit kinds and one fit loop. A network class
keeps what its topology is and supplies:

- `named_layers()`: `[(key, layer conf), ...]` in update order, and
  `_param_order()`: the keys in flat-parameter order;
- `_draw_params(root, dtype)`: the initial parameter tree;
- `_forward_loss(params, state, batch, rng, train, carry_rnn, ebs)
  -> (loss, new_state)` and `_outputs(...)`, the `output` kind's body;
- how outside data becomes a batch: `_fit_source`, `_as_data`,
  `_host_parts`, `_to_device`, `_tbptt_divisors`;
- `_FIT`, its `FitObs` (the `engine=` label and the span names).

A *batch* is four pytrees, `(inputs, labels, fmasks, lmasks)`: one array or
None each for `MultiLayerNetwork`, lists for `ComputationGraph`. Lists are
pytrees, so `jit`, `lax.scan` and donation treat both alike and nothing
here looks inside one: the code below only maps over a batch's leaves.
"""

from __future__ import annotations

import copy
import math
import os
import re
from contextlib import nullcontext
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import observability as _obs
from deeplearning4j_tpu.datasets import staging as _staging
from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterators import (
    MultiSuperbatch,
    Superbatch,
    SuperbatchIterator,
    maybe_reset,
    transfer_cast,
)
from deeplearning4j_tpu.nn import jit_cache as jit_cache_mod
from deeplearning4j_tpu.nn import params as params_mod
from deeplearning4j_tpu.nn import rnn_state as rnn_mod
from deeplearning4j_tpu.nn import superstep as _superstep
from deeplearning4j_tpu.nn import transfer as transfer_mod
from deeplearning4j_tpu.nn.conf.dtype_policy import resolve_policy
from deeplearning4j_tpu.nn.conf.enums import (
    BackpropType,
    OptimizationAlgorithm,
)
from deeplearning4j_tpu.nn.conf.layers import is_bias_param
from deeplearning4j_tpu.ops import grad_norm as grad_norm_mod
from deeplearning4j_tpu.ops import schedules as schedules_mod
from deeplearning4j_tpu.ops import updaters as updaters_mod


_SCOPE_UNSAFE = re.compile(r"[^A-Za-z0-9_\-]")


def scope(name, prefix: str = ""):
    """The `jax.named_scope` the engine runs a part of a compiled program
    under: the name reaches the `op_name` of every HLO operation traced
    inside, forward and backward (`transpose(jvp(L.attn2))`), so a device
    trace reads by it; metadata only, the program itself does not move.
    The names are contract (PERF.md section 3): `L.<vertex>` around all of
    a vertex (`prefix="L."`: its parameters' cast, its preprocessor, its
    forward, an output layer's loss, and inside `step.update` its updater),
    `step.<phase>` around a phase of the train step (`prefix="step."`), and
    with no prefix a name a layer chose itself (`Layer.scope`), as written.
    A prefixed name keeps letters, digits, `_` and `-` only: a user's vertex
    `moe.x` runs under `L.moe_x`, so no vertex name can read as one of the
    dotted scopes the layer bodies open (`moe.`, `lm.head`, ...). No name,
    no scope."""
    if not name:
        return nullcontext()
    if prefix:
        name = prefix + _SCOPE_UNSAFE.sub("_", str(name))
    return jax.named_scope(name)


def _first(part):
    """A batch part's first entry (the part itself where it is one array);
    None where that entry is absent."""
    leaves = jax.tree_util.tree_leaves(part, is_leaf=lambda a: a is None)
    return leaves[0] if leaves else None


def _seq_len(inputs) -> int:
    return max(f.shape[1] for f in jax.tree_util.tree_leaves(inputs)
               if f.ndim == 3)


def _time_sliced(batch, t: int, slicer):
    """`batch` with `slicer` applied to its sequence leaves. Only 3-D
    [b, t, f] arrays (and, explicitly, 2-D [b, t] masks or [b, t] integer
    class-id labels) are sequences; a static 2-D float input whose feature
    dim happens to equal t must pass through untouched."""
    def part(tree, is_mask):
        def one(a):
            seq = a.shape[1:2] == (t,) and (
                a.ndim == 3 or (a.ndim == 2 and (
                    is_mask or jnp.issubdtype(a.dtype, jnp.integer))))
            return slicer(a) if seq else a
        return jax.tree_util.tree_map(one, tree)

    inputs, labels, fmasks, lmasks = batch
    return (part(inputs, False), part(labels, False), part(fmasks, True),
            part(lmasks, True))


class Engine:
    """Base of both network classes (see module docstring)."""

    def __init__(self, conf):
        self.conf = conf
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
        self._FIT.publish_layer_stats(self)
        return float(v)

    @score_value.setter
    def score_value(self, v):
        self._score = v

    # ------------------------------------------------------------------ init

    def init(self, params: Optional[Dict[str, Any]] = None):
        g = self.conf.global_conf
        pol = self.dtype_policy
        # Low-precision param policies still INITIALIZE at f32 — the f32
        # draw is the master copy, params are its cast. State (BN running
        # stats) always stays at the master precision.
        pdt = jnp.float32 if pol.low_precision_params else pol.jnp_param
        master = None
        if params is None:
            params = self._draw_params(jax.random.PRNGKey(g.seed), pdt)
            if pol.low_precision_params:
                master = params
                params = params_mod.cast_floating(params, pol.jnp_param)
        elif pol.low_precision_params:
            # Leaves handed in at f32 (fresh LoRA factors) are stored at the
            # param dtype like the rest: a leaf shared by the stored tree and
            # the master would be donated twice by the train step.
            master = params_mod.cast_floating(params, jnp.float32)
            params = params_mod.cast_floating(params, pol.jnp_param)
        self.params_tree = params
        layers = self.named_layers()
        self.state = {
            key: params_mod.init_layer_state(layer, dtype=pdt)
            for key, layer in layers if layer.state_shapes()
        }
        self._layer_stat_keys = None  # found anew by fit_obs
        self._updaters = {}
        self._schedules = {}
        for key, layer in layers:
            self._updaters[key] = updaters_mod.create(
                layer.updater,
                momentum=layer.momentum if layer.momentum is not None else g.momentum,
                adam_mean_decay=layer.adam_mean_decay if layer.adam_mean_decay is not None else g.adam_mean_decay,
                adam_var_decay=layer.adam_var_decay if layer.adam_var_decay is not None else g.adam_var_decay,
                rho=layer.rho if layer.rho is not None else g.rho,
                rms_decay=layer.rms_decay if layer.rms_decay is not None else g.rms_decay,
                epsilon=layer.epsilon if layer.epsilon is not None else g.epsilon,
            )
            self._schedules[key] = schedules_mod.make_schedule(
                float(layer.learning_rate if layer.learning_rate is not None else g.learning_rate),
                g.lr_policy, g.lr_policy_decay_rate, g.lr_policy_power,
                g.lr_policy_steps, g.max_num_iterations, g.lr_schedule,
            )
        # Transfer learning / LoRA (nn/transfer.py): frozen leaves get NO
        # updater state — opt_state is built over the trainable subtree
        # (a fully-frozen layer's entry is ()). Empty spec (the common
        # case) keeps the structures byte-identical to before.
        self._frozen_spec = transfer_mod.frozen_spec(layers, self.params_tree)
        base = master if master is not None else self.params_tree
        opt_src = (transfer_mod.split_tree(base, self._frozen_spec)[0]
                   if self._frozen_spec else base)
        self.opt_state = {
            key: (() if key in self._frozen_spec and not opt_src[key]
                  else self._updaters[key].init(opt_src[key]))
            for key, _ in layers
        }
        # Reserved opt_state keys (never layer keys): the f32 master params
        # and the on-device (scale, good_count) loss-scale carry ride INSIDE
        # opt_state so jit signatures, donation, the superstep scan carry,
        # and checkpoint trees all pick them up without any shape change.
        # `_apply_updates` iterates layer keys only, so they pass through
        # untouched and re-attach after each update.
        if master is not None:
            self.opt_state["_master"] = master
        if pol.uses_loss_scaling:
            self.opt_state["_ls"] = (
                jnp.float32(pol.initial_loss_scale), jnp.float32(0.0))
        self._train_rng = jax.random.PRNGKey(g.seed ^ 0x5EED)
        self._clock = None
        self._initialized = True
        return self

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

    def _next_rng(self):
        if self._clock is not None:
            # The rng stream's continuation lives in the device clock; pull it
            # back to the host-side attribute before splitting.
            self._train_rng = self._clock[1]
            self._clock = None
        self._train_rng, sub = jax.random.split(self._train_rng)
        return sub

    # ---------------------------------------------------------- jit programs

    def _get_jit(self, kind: str, **static):
        # Key construction/lookup + the compile-cache store hook live in
        # nn/jit_cache.py.
        return jit_cache_mod.get_jit(self, self._FIT.jit_hit,
                                     self._FIT.jit_miss, kind, **static)

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
        # The functions' names are part of the lowered modules' names and of
        # every operation's `op_name` (`jit(step_fn)/...`), which the
        # benchmark's trace reduction joins on.
        if kind == "solver_step":
            from jax.flatten_util import ravel_pytree

            from deeplearning4j_tpu.optimize import solvers as solvers_mod

            g = self.conf.global_conf
            iterations = max(1, g.iterations)
            mls = max(1, int(g.max_num_line_search_iterations))

            def solver_fn(params, state, inputs, labels, fmasks, lmasks):
                w0, unravel = ravel_pytree(params)

                def loss_flat(w):
                    return self._forward_loss(
                        unravel(w), state, (inputs, labels, fmasks, lmasks),
                        None, False)[0]

                w, loss = solvers_mod.minimize(
                    algo, loss_flat, w0, iterations=iterations,
                    max_line_search=mls)
                return unravel(w), loss

            return jax.jit(solver_fn, donate_argnums=(0,))
        if kind == "output":
            def output_fn(params, state, inputs, fmasks, rng):
                return self._outputs(params, state, inputs, fmasks, rng,
                                     train, keep_rnn_state)
            return jax.jit(output_fn)
        if kind == "score":
            def score_fn(params, state, inputs, labels, fmasks, lmasks):
                return self._forward_loss(
                    params, state, (inputs, labels, fmasks, lmasks), None,
                    False)[0]
            return jax.jit(score_fn)
        if kind == "train_step":
            def step_fn(params, state, opt_state, inputs, labels, fmasks, lmasks, clock):
                step, key = clock
                key, sub = jax.random.split(key)
                out = self._train_step(params, state, opt_state,
                                       (inputs, labels, fmasks, lmasks),
                                       step, sub)
                return out + ((step + 1.0, key),)
            return jax.jit(step_fn, donate_argnums=(0, 2))
        if kind == "train_superstep":
            # K full train iterations as ONE dispatch: a fused loop (`lax.scan`
            # by default, opt-in unrolled — `nn/superstep.py`) over the
            # leading [K] axis of a stacked batch (the loop slices every
            # leaf; None mask entries are empty pytrees and pass through),
            # carrying (params, state, opt_state, clock) with donated
            # buffers and returning the K per-step losses as a vector.
            # The body advances the clock exactly like `step_fn`
            # (`key, sub = split(key)` then `step + 1.0`), so the RNG split
            # chain — and therefore dropout masks, BN batch-stat order, and
            # updater step counts — is bit-for-bit identical to K
            # sequential `_fit_one` calls.
            def step_super(params, state, opt_state, inputs, labels, fmasks,
                           lmasks, clock):
                def body(carry, batch):
                    params, state, opt_state, (step, key) = carry
                    key, sub = jax.random.split(key)
                    params, state, opt_state, loss = self._train_step(
                        params, state, opt_state, batch, step, sub)
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
                out = self._train_step(params, state, opt_state,
                                       (inputs, labels, fmasks, lmasks),
                                       step, sub, collect_stats=True)
                return out + ((step + 1.0, key),)
            return jax.jit(step_fn_s, donate_argnums=(0, 2))
        if kind == "train_step_tbptt":
            # `advance` is static: all chunks of one sequence share the same
            # step value (reference: one optimize iteration per sequence);
            # only the final chunk ticks the clock. `collect` adds the
            # StatsListener scalars (grad/update/param mean magnitudes).
            def step_fn2(params, state, opt_state, inputs, labels, fmasks, lmasks, clock, ebs):
                step, key = clock
                key, sub = jax.random.split(key)
                out = self._train_step(params, state, opt_state,
                                       (inputs, labels, fmasks, lmasks),
                                       step, sub, carry_rnn=True, ebs=ebs,
                                       collect_stats=collect)
                new_step = step + 1.0 if advance else step
                return out + ((new_step, key),)
            return jax.jit(step_fn2, donate_argnums=(0, 2))
        if kind == "train_step_tbptt_scan":
            # The WHOLE tBPTT pass as ONE jitted program: chunk 0 unrolled
            # (it CREATES the rnn-carry entries in `state`, so the carry
            # structure is only scan-stable from chunk 1 on), the full-length
            # middle chunks as a `lax.scan` whose body time-slices the
            # closed-over full sequences with `dynamic_slice`, and any short
            # remainder chunk unrolled at its TRUE length — no padding, so
            # BatchNorm batch stats and masked losses see exactly the data
            # the per-chunk host loop saw. The host loop it replaces pays
            # one dispatch per chunk (what that costs is not measured on the
            # current machine). Note each distinct sequence length t
            # compiles its own program (the old loop reused [B, fwd] chunk
            # programs across t); bucket/pad sequence lengths host-side if
            # feeding many distinct lengths.
            fwd = int(self.conf.tbptt_fwd_length)

            def step_scan(params, state, opt_state, inputs, labels, fmasks,
                          lmasks, clock, ebs):
                step, key = clock
                batch = (inputs, labels, fmasks, lmasks)
                t = _seq_len(inputs)
                n_full = t // fwd  # >= 1: _fit_dispatch_inner requires t > fwd
                rem = t - n_full * fwd
                # Same RNG chain as the per-chunk stats path (`step_fn2`
                # does `key, sub = split(key)` per chunk), so attaching a
                # StatsListener never changes training numerics.
                subs = []
                for _ in range(n_full + (1 if rem else 0)):
                    key, sub = jax.random.split(key)
                    subs.append(sub)

                params, state, opt_state, loss = self._train_step(
                    params, state, opt_state,
                    _time_sliced(batch, t, lambda a: a[:, slice(0, fwd)]),
                    step, subs[0], carry_rnn=True, ebs=ebs)

                if n_full > 1:
                    def body(carry, inp):
                        params, state, opt_state = carry
                        c, sub = inp
                        off = c * fwd

                        def dyn(a):
                            return jax.lax.dynamic_slice_in_dim(a, off, fwd, 1)

                        params, state, opt_state, closs = self._train_step(
                            params, state, opt_state,
                            _time_sliced(batch, t, dyn), step, sub,
                            carry_rnn=True, ebs=ebs)
                        return (params, state, opt_state), closs

                    (params, state, opt_state), losses = jax.lax.scan(
                        body, (params, state, opt_state),
                        (jnp.arange(1, n_full), jnp.stack(subs[1:n_full])))
                    loss = losses[-1]
                if rem:
                    params, state, opt_state, loss = self._train_step(
                        params, state, opt_state,
                        _time_sliced(batch, t,
                                     lambda a: a[:, slice(n_full * fwd, t)]),
                        step, subs[-1], carry_rnn=True, ebs=ebs)
                return (params, state, opt_state, loss, (step + 1.0, key))
            return jax.jit(step_scan, donate_argnums=(0, 2))
        raise ValueError(kind)

    # ----------------------------------------------------------------- loss

    def _l1_l2_penalty(self, params):
        """L1/L2 terms added at score time (reference: `Layer.calcL1/calcL2`,
        score semantics SURVEY.md §2.4). Applied to weight params only."""
        total = 0.0
        for key, layer in self.named_layers():
            l1 = float(layer.l1 or 0.0)
            l2 = float(layer.l2 or 0.0)
            if (l1 == 0.0 and l2 == 0.0) or key not in params:
                continue
            with scope(key, "L."):
                for wk in layer.weight_param_keys():
                    if wk not in params[key]:
                        continue
                    w = params[key][wk].astype(self._loss_dtype)
                    if l2:
                        total = total + 0.5 * l2 * jnp.sum(w * w)
                    if l1:
                        total = total + l1 * jnp.sum(jnp.abs(w))
        return total

    # ----------------------------------------------------------- train step

    def _train_step(self, params, state, opt_state, batch, step, rng,
                    carry_rnn=False, ebs=None, collect_stats=False):
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
            return self._forward_loss(p, state, batch, rng, True, carry_rnn,
                                      ebs)

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
            with scope("grad_cast", "step."):
                grads = jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float32) / scale, grads)
                finite = jnp.bool_(True)
                for leaf in jax.tree_util.tree_leaves(grads):
                    finite = jnp.logical_and(finite,
                                             jnp.all(jnp.isfinite(leaf)))
        else:
            (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            if lowp:
                with scope("grad_cast", "step."):
                    grads = params_mod.cast_floating(grads, jnp.float32)

        # Low-precision params: updates apply to the f32 MASTER copy (and
        # f32 updater state); stored params are its cast, so tiny updates
        # never underflow bf16/f16 quantization.
        base = opt_state["_master"] if lowp else params
        frozen_master = None
        if spec and lowp:
            base, frozen_master = transfer_mod.split_tree(base, spec)
        new_base, new_opt, stats = self._apply_updates(
            base, grads, opt_state, step, collect_stats=collect_stats)

        # `step.store`: what turns the updated master into the stored tree
        # (the skip-step selects, the master -> stored cast, the frozen merge).
        with scope("store", "step."):
            if scaling:
                # Skip-step on non-finite scaled grads: every updated leaf
                # selects its OLD value (params, updater state, batch stats),
                # then the scale backs off; after `growth_interval` consecutive
                # finite steps it grows. All `jnp.where` on device — no host
                # sync, superstep-safe.
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
                    new_params = transfer_mod.merge_tree(new_params,
                                                         frozen_stored)
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
        for n, s in new_state.items():
            merged = dict(merged_state.get(n, {}))
            merged.update(s)
            merged_state[n] = merged
        if collect_stats:
            return new_params, merged_state, new_opt, loss, stats
        return new_params, merged_state, new_opt, loss

    def _apply_updates(self, params, grads, opt_state, step,
                       collect_stats=False):
        """Per-layer gradient-normalize + updater + param update (traced) —
        the reference's LayerUpdater stack. Shared by `_train_step` and
        `parallel/pipeline_trainer.py`'s pipelined step; all of it runs under
        `step.update`, each layer's part under `step.update/L.<key>`."""
        g = self.conf.global_conf
        sign = 1.0 if g.minimize else -1.0
        new_params: Dict[str, Any] = {}
        new_opt: Dict[str, Any] = {}
        stats: Dict[str, Any] = {}
        for key, layer in self.named_layers():
            lgrads = grads.get(key, {})
            if not lgrads:
                new_params[key] = params.get(key, {})
                new_opt[key] = opt_state.get(key, ())
                continue
            with scope("update", "step."), scope(key, "L."):
                lgrads = grad_norm_mod.normalize_layer_gradients(
                    lgrads, layer.gradient_normalization,
                    float(layer.gradient_normalization_threshold or 1.0),
                )
                lr = self._schedules[key](step)
                st, deltas = self._updaters[key].update(
                    opt_state[key], lgrads, lr, step)
                base_lr = float(layer.learning_rate
                                if layer.learning_rate is not None
                                else g.learning_rate)
                bias_lr = float(layer.bias_learning_rate
                                if layer.bias_learning_rate is not None
                                else base_lr)
                if bias_lr != base_lr and base_lr != 0.0:
                    factor = bias_lr / base_lr
                    # is_bias_param covers every bias name (b, b_f/b_b for
                    # bidirectional RNNs, vb/eb/db for RBM/VAE, beta for BN)
                    # — reference `LayerUpdater.java:243` applies
                    # biasLearningRate per param TYPE, not only to params
                    # literally named "b".
                    deltas = {k: (d * factor if is_bias_param(k) else d)
                              for k, d in deltas.items()}
                new_params[key] = {
                    k: params[key][k] - sign * deltas[k] for k in params[key]
                }
                new_opt[key] = st
                if collect_stats:
                    # Per-param mean magnitudes of gradient/update/param,
                    # computed in-jit so only scalars cross the device
                    # boundary (reference StatsListener "mean magnitudes",
                    # BaseStatsListener.java:273).
                    stats[key] = {
                        k: {
                            "grad_mm": jnp.mean(jnp.abs(lgrads[k])),
                            "update_mm": jnp.mean(jnp.abs(deltas[k])),
                            "param_mm": jnp.mean(jnp.abs(new_params[key][k])),
                        }
                        for k in lgrads
                    }
        return new_params, new_opt, stats

    # ------------------------------------------------------------------ fit

    def fit(self, data, labels=None):
        """Train over an iterator / DataSet / MultiDataSet / (x, y) pair —
        one pass (reference: `MultiLayerNetwork.fit(DataSetIterator)`
        `:976`, `ComputationGraph.fit` `:671,740`)."""
        if not self._initialized:
            self.init()
        iterator = self._fit_source(data, labels)
        maybe_reset(iterator)
        for listener in self.listeners:
            listener.on_epoch_start(self)
        engine = self._FIT.engine
        with _obs.tracer.span(f"{engine}.fit", cat="train", epoch=self.epoch):
            if self.conf.backprop:
                k = self._superstep_k()
                src = self._superstep_wrap(iterator, k) if k > 1 else iterator
                # Overlap host->device transfers with compute: multi-batch
                # epochs stream through a background DeviceStager (single
                # batches and already-staging sources pass through).
                src = _staging.maybe_stage(
                    src, net=self, engine=engine,
                    transfer_dtype=getattr(self.dtype_policy,
                                           "transfer_dtype", None))
                src_it = iter(src)
                try:
                    for item in self._FIT.batches(self, src_it):
                        self._fit_dispatch(self._as_data(item))
                finally:
                    # An abandoned epoch must not leave staged HBM buffers.
                    _staging.close_stager(src_it)
                    _staging.close_stager(src)
        self.epoch += 1
        self._FIT.epochs.inc()
        for listener in self.listeners:
            listener.on_epoch_end(self)
        return self

    def _batch(self, ds):
        """One data object as a batch of device arrays."""
        inputs, labels, fmasks, lmasks = self._host_parts(ds)
        dev = self._to_device
        return dev(inputs), dev(labels), dev(fmasks), dev(lmasks)

    def _fit_dispatch(self, ds):
        """tBPTT/plain/superstep dispatch + iterations loop for one staged
        batch (or stacked superbatch) — shared by `fit()` and
        `ParallelWrapper` so sharded training honors the same backprop-type
        config. Also the engine's observability choke point: every training
        path (plain / tBPTT / solver / superstep, local or sharded) stages
        batches through here, and `StepProfiler` patches this method on the
        instance."""
        tdt = getattr(self.dtype_policy, "transfer_dtype", None)
        if tdt is not None:
            ds = transfer_cast(ds, tdt)
        h2d = _obs.host_nbytes(*self._host_parts(ds))
        return self._FIT.dispatch(self, ds, h2d, self._fit_dispatch_inner)

    def _fit_dispatch_inner(self, ds):
        if isinstance(ds, (MultiSuperbatch, Superbatch)):
            # Stacked K-block: `_superstep_k` already gated out the solver /
            # tBPTT / stats / multi-iteration paths before blocks formed.
            return self._fit_superstep(ds)
        g = self.conf.global_conf
        batch = self._batch(ds)
        algo = OptimizationAlgorithm.of(g.optimization_algo)
        if algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
            return self._fit_solver(batch, algo)
        tbptt = BackpropType.of(self.conf.backprop_type) == BackpropType.TRUNCATED_BPTT
        for _ in range(max(1, g.iterations)):
            if tbptt and any(
                f.ndim == 3 and f.shape[1] > self.conf.tbptt_fwd_length
                for f in jax.tree_util.tree_leaves(batch[0])
            ):
                self._fit_tbptt(batch)
            else:
                self._fit_one(batch)

    def _fit_one(self, batch, tbptt: bool = False,
                 count_iteration: bool = True, ebs=None, advance=True):
        if tbptt:
            step_fn = self._get_jit("train_step_tbptt", advance=advance,
                                    collect=self._collect_stats)
        else:
            kind = "train_step_stats" if self._collect_stats else "train_step"
            step_fn = self._get_jit(kind)
        args = (self.params_tree, self.state, self.opt_state, *batch,
                self._device_clock())
        if tbptt:
            args += (ebs,)
        with self._FIT.enqueue():
            out = step_fn(*args)
        if len(out) == 6:
            self.params_tree, self.state, self.opt_state, loss, stats, self._clock = out
            self.last_training_stats = stats  # device scalars, fetched lazily
        else:
            self.params_tree, self.state, self.opt_state, loss, self._clock = out
        self._score = loss  # device scalar; sync deferred to score_value
        if count_iteration:
            self.iteration += 1
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration)

    def _fit_solver(self, batch, algo):
        """Full-batch LBFGS/CG/line-search optimize of one batch (reference:
        `Solver.java:41-110` dispatching to `optimize/solvers/`); the whole
        `iterations`-step solver loop is one jitted XLA computation
        (`optimize/solvers.py`). Deterministic forward (no dropout, BN
        running stats) so the line search sees a stable objective."""
        self._check_sgd_only_policy("solver optimizers (LBFGS/CG/line search)")
        g = self.conf.global_conf
        fn = self._get_jit("solver_step", algo=str(algo))
        with self._FIT.enqueue():
            self.params_tree, loss = fn(self.params_tree, self.state, *batch)
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
        """Wrap `iterator` in a `SuperbatchIterator` (items pass through
        `_as_data` BEFORE stacking), caching the wrapper on the base
        iterator so a device-cached epoch restacks once, not per `fit()`
        call. The policy's `transfer_dtype` rides along so staged
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
        wrapper = SuperbatchIterator(iterator, k, transform=self._as_data,
                                     transfer_dtype=tdt, net=self)
        try:
            iterator._superbatch_wrapper = wrapper
        except (AttributeError, TypeError):
            pass  # lists/tuples/slots: re-wrapped per fit(), still correct
        return wrapper

    def _fit_superstep(self, sb):
        """One dispatch, K train iterations (see `train_superstep` in
        `_build_jit`). The returned `[K]` loss vector fans out to listeners
        per iteration, so ScoreIterationListener etc. observe the same
        (iteration, score) sequence as the per-batch loop — scores stay
        device scalars until someone reads `score_value`."""
        k = int(sb.k)
        batch = self._batch(sb)
        if k == 1:  # defensive: SuperbatchIterator yields raw singletons
            return self._fit_one(
                jax.tree_util.tree_map(lambda a: a[0], batch))
        step_fn = self._get_jit("train_superstep", k=k,
                                scan=_superstep.use_scan(),
                                kernels=_superstep.kernel_config())
        args = (self.params_tree, self.state, self.opt_state, *batch,
                self._device_clock())
        with self._FIT.enqueue():
            (self.params_tree, self.state, self.opt_state, losses,
             self._clock) = step_fn(*args)
        for i in range(k):
            self._score = losses[i]  # device scalar; sync deferred
            self.iteration += 1
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration)

    # ------------------------------------------------------------------ tBPTT

    def _fit_tbptt(self, batch):
        """Truncated BPTT (reference: `doTruncatedBPTT:1138`): chunk every
        sequence leaf along time; rnn state carries across chunks as data
        (implicit gradient truncation at chunk boundaries)."""
        if any(getattr(layer, "decode_cache_length", None)
               for _, layer in self.named_layers()):
            raise ValueError(
                "truncated BPTT carries undeclared layer state across "
                "chunks, which would thread attention KV caches into "
                "training; unset decode_cache_length (it is an inference "
                "feature) or use standard backprop")
        inputs, labels, fmasks, lmasks = batch
        fwd = self.conf.tbptt_fwd_length
        t = _seq_len(inputs)
        saved_state = self.state
        labs = jax.tree_util.tree_leaves(labels)
        if not labs or any(
                lab.ndim != 3 and not (
                    lab.ndim == 2 and jnp.issubdtype(lab.dtype, jnp.integer))
                for lab in labs):
            raise ValueError(
                "Truncated BPTT requires per-timestep labels: [b, t, c] "
                "one-hot or [b, t] integer class ids "
                "(reference doTruncatedBPTT semantics)"
            )
        # Divisors from the FULL-sequence masks: a row masked out of one
        # chunk (shorter sequence) still counts, reference
        # divide-by-minibatch.
        ebs = self._tbptt_divisors(labels, lmasks)
        if not self._collect_stats:
            # Fast path: the entire chunk loop is one jitted scan — ONE
            # dispatch per sequence instead of one per chunk.
            step_fn = self._get_jit("train_step_tbptt_scan")
            args = (self.params_tree, self.state, self.opt_state, *batch,
                    self._device_clock(), ebs)
            with self._FIT.enqueue():
                (self.params_tree, self.state, self.opt_state, loss,
                 self._clock) = step_fn(*args)
            self._score = loss
            return self._finish_tbptt(saved_state)
        # Stats path: per-chunk dispatch (keeps the last chunk's per-layer
        # stats observable).
        n_chunks = math.ceil(t / fwd)
        for ci in range(n_chunks):
            sl = slice(ci * fwd, min((ci + 1) * fwd, t))
            self._fit_one(_time_sliced(batch, t, lambda a: a[:, sl]),
                          tbptt=True, count_iteration=False, ebs=ebs,
                          advance=ci == n_chunks - 1)
        self._finish_tbptt(saved_state)

    def _finish_tbptt(self, saved_state):
        # Reset rnn carries after the sequence; keep persistent (BN) state.
        declared = self._declared_state()
        self.state = {
            n: {k: v for k, v in s.items() if k in declared.get(n, ())}
            for n, s in self.state.items()
        }
        self.state = {n: s for n, s in self.state.items() if s}
        # Restore any BN stats that were present before if lost (safety).
        for n, s in saved_state.items():
            self.state.setdefault(n, s)
        self.iteration += 1
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration)

    def _declared_state(self):
        return {key: tuple(layer.state_shapes())
                for key, layer in self.named_layers()}

    # -------------------------------------------------------------- predict

    def _output_arrays(self, inputs, fmasks, train: bool = False, params=None):
        """The `output` program on device inputs, in the topology's own
        shape (an array / a list). `params` substitutes another params tree
        of the same structure (e.g. an adapter-merged serving tree —
        `nn/lora.py`) for this net's own; params are jit arguments, so the
        swap re-uses the compiled program."""
        fn = self._get_jit("output", train=train)
        out, _ = fn(self.params_tree if params is None else params,
                    self.state, inputs, fmasks,
                    self._next_rng() if train else jax.random.PRNGKey(0))
        return out

    def _rnn_step(self, inputs, t: int):
        """One stateful `output` call on [b, t, ...] device inputs: layer
        state the net does not declare (LSTM carries, attention KV caches,
        positional cursors) persists in `_rnn_state` across calls."""
        self._rnn_pos = rnn_mod.check_decode_budget(
            getattr(self, "_rnn_pos", 0), t,
            rnn_mod.decode_capacity(
                layer for _, layer in self.named_layers()))
        fn = self._get_jit("output", train=False, keep_rnn_state=True)
        state = rnn_mod.merge_rnn_state(self.state, self._rnn_state)
        out, new_state = fn(self.params_tree, state, inputs, None,
                            jax.random.PRNGKey(0))
        self._rnn_state = rnn_mod.split_rnn_state(new_state,
                                                  self._declared_state())
        return out

    def rnn_clear_previous_state(self):
        self._rnn_state = {}
        self._rnn_pos = 0

    def score(self, data, labels=None) -> float:
        """Loss on a dataset without updating (reference: `score(DataSet)`)."""
        fn = self._get_jit("score")
        return float(fn(self.params_tree, self.state,
                        *self._batch(self._as_data(data, labels))))

    def evaluate(self, iterator, top_n: int = 1):
        """Classification evaluation of the first output (reference:
        `evaluate(DataSetIterator)` `:2406-2506`)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        ev = Evaluation(top_n=top_n)
        maybe_reset(iterator)
        if isinstance(iterator, (DataSet, MultiDataSet)):
            iterator = [iterator]
        dev = self._to_device
        for item in iterator:
            inputs, labels, fmasks, lmasks = self._host_parts(
                self._as_data(item))
            out = self._output_arrays(dev(inputs), dev(fmasks))
            ev.eval(_first(labels), np.asarray(_first(out)),
                    mask=_first(lmasks))
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
        return int(sum(params_mod.num_params(layer)
                       for _, layer in self.named_layers()))

    def _param_orders(self):
        return {key: list(layer.param_shapes())
                for key, layer in self.named_layers()}

    def params(self) -> np.ndarray:
        """Flattened 1-D param view (reference: `Model.params()`)."""
        return params_mod.flatten_params(
            self.params_tree, self._param_order(), self._param_orders())

    def set_params(self, flat: np.ndarray):
        self.params_tree = params_mod.unflatten_params(
            np.asarray(flat), self.params_tree, self._param_order(),
            self._param_orders())
        if (self.dtype_policy.low_precision_params and self.opt_state
                and "_master" in self.opt_state):
            # Keep the f32 master in lockstep with an externally-set view.
            self.opt_state["_master"] = params_mod.cast_floating(
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

    def clone(self):
        """Deep copy. Device buffers are COPIED (jnp.copy), not aliased: the
        source net's train step donates its buffers, which would delete a
        shared array out from under the clone."""
        net = type(self)(copy.deepcopy(self.conf))
        if self._initialized:
            net.init(params=jax.tree_util.tree_map(jnp.copy, self.params_tree))
            net.state = jax.tree_util.tree_map(jnp.copy, self.state)
            net.opt_state = jax.tree_util.tree_map(jnp.copy, self.opt_state)
            net.iteration = self.iteration
            net.epoch = self.epoch
        return net
