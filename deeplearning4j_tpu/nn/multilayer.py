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

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import activations as activations_mod
from deeplearning4j_tpu.nn import losses as losses_mod
from deeplearning4j_tpu.nn import params as params_mod
from deeplearning4j_tpu.nn.conf.layers import CenterLossOutputLayer
from deeplearning4j_tpu.nn.conf.neural_net import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf import preprocessors as preprocessors_mod
from deeplearning4j_tpu.nn.engine import Engine, scope
from deeplearning4j_tpu.nn.layers import OUTPUT_LAYER_TYPES, get_impl
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import Superbatch, maybe_reset
from deeplearning4j_tpu.nn.fit_obs import FitObs


def _as_dataset(data, labels=None) -> DataSet:
    if isinstance(data, (DataSet, Superbatch)):
        return data
    if labels is None and isinstance(data, tuple) and len(data) == 2:
        data, labels = data  # score((x, y)) / fit((x, y)) convenience form
    return DataSet(np.asarray(data), None if labels is None else np.asarray(labels))


class MultiLayerNetwork(Engine):
    """Sequential network engine (see module docstring): the layers in
    index order, their forward and their loss; the train step, the jit
    kinds and the fit loop are `nn/engine.py`'s."""

    # This engine's hot-loop metric series and fit-loop spans.
    _FIT = FitObs("mln")
    _as_data = staticmethod(_as_dataset)

    def __init__(self, conf: MultiLayerConfiguration):
        super().__init__(conf)
        self.layers = conf.layers
        self.layer_keys = [f"layer_{i}" for i in range(len(conf.layers))]

    def named_layers(self):
        return list(zip(self.layer_keys, self.layers))

    def _param_order(self):
        return self.layer_keys

    def _draw_params(self, root, dtype):
        keys = jax.random.split(root, max(len(self.layers), 1))
        return {
            lk: params_mod.init_layer_params(layer, keys[i], dtype=dtype)
            for i, (lk, layer) in enumerate(zip(self.layer_keys, self.layers))
        }

    @property
    def _uint8_policy(self) -> str:
        """How a uint8 network input is staged, from the first layer's
        declared structure (see `nn/conf/preprocessors.py`): embedding ids
        are cast, image bytes are /255-scaled."""
        return preprocessors_mod.resolve_uint8_policy(
            [self.layers[0]] if self.layers else [])

    # ----------------------------------------------------------------- data

    def _fit_source(self, data, labels):
        if labels is not None or isinstance(data, DataSet) or (
                isinstance(data, tuple) and len(data) == 2
                and not isinstance(data[0], DataSet)):
            # The DataSet guard keeps a 2-element tuple OF DataSets (a valid
            # small iterator) from being misread as an (x, y) pair.
            iterator = [_as_dataset(data, labels)]
        else:
            iterator = data
        if self.conf.pretrain:
            maybe_reset(iterator)
            if not hasattr(iterator, "reset") and not isinstance(iterator, (list, tuple)):
                # One-shot iterable: materialize so both the pretrain pass and
                # the backprop pass see the data.
                iterator = list(iterator)
            self.pretrain(iterator)
        return iterator

    @staticmethod
    def _host_parts(ds):
        return ds.features, ds.labels, ds.features_mask, ds.labels_mask

    @staticmethod
    def _to_device(a):
        return None if a is None else jnp.asarray(a)

    @staticmethod
    def _tbptt_divisors(labels, lmask):
        return jax.device_put(np.float32(
            losses_mod.effective_batch_size(labels, lmask)))

    # --------------------------------------------------------------- forward

    def _forward_fn(self, params, state, x, rng, train: bool, fmask,
                    upto: Optional[int] = None, collect: bool = False,
                    keep_rnn_state: bool = False):
        """Pure forward pass (traced). Returns (final, new_state, activations, aux)."""
        cdt = self._compute_dtype
        # Device-side ImagePreProcessingScaler (reference:
        # `ImagePreProcessingScaler.java` scales 0-255 -> 0-1 on HOST):
        # shipping bytes and scaling on device quarters the host->device
        # traffic of streamed image batches. The uint8
        # interpretation (image bytes vs embedding ids) is decided by the
        # first layer's declared structure, not sniffed from the dtype.
        with scope("input", "L."):
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
            # A layer runs under `L.<key>`, as a graph's vertex does: the
            # cast of its parameters and its preprocessor too.
            with scope(lk, "L."):
                if i in self.conf.input_preprocessors:
                    x, mask = self.conf.input_preprocessors[i](x, mask)
                if isinstance(layer, CenterLossOutputLayer):
                    aux["center_loss_input"] = x
                    aux["centers"] = state.get(lk, {}).get("centers")
                lrng = jax.random.fold_in(rng, i) if rng is not None else None
                # Params stored at param_dtype, cast (or dequantized) to the
                # policy's compute dtype at use (nn/params.py).
                lparams = params_mod.prep_layer_params(params.get(lk, {}),
                                                       cdt, layer=layer)
                lstate = state.get(lk, {})
                with scope(layer.scope):
                    x, lstate_new, mask = get_impl(layer)(
                        layer, lparams, lstate, x, rng=lrng, train=train,
                        mask=mask)
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

    def _forward_loss(self, params, state, batch, rng, train,
                      carry_rnn=False, ebs=None):
        x, y, fmask, lmask = batch
        preout, new_state, _, aux = self._forward_fn(
            params, state, x, rng, train, fmask, keep_rnn_state=carry_rnn)
        loss, extra_state = self._loss_from_preout(params, preout, y, lmask,
                                                   aux, ebs)
        for lk, s in extra_state.items():
            new_state.setdefault(lk, {}).update(s)
        return loss, new_state

    def _outputs(self, params, state, x, fmask, rng, train, keep_rnn_state):
        final, new_state, _, _ = self._forward_fn(
            params, state, x, rng, train, fmask, keep_rnn_state=keep_rnn_state)
        out = final.astype(self._output_dtype)
        layer = self.layers[-1]
        if type(layer).__name__ in OUTPUT_LAYER_TYPES:
            out = activations_mod.resolve(layer.activation)(out)
        return out, new_state

    def _build_jit(self, kind: str, train=False, **static):
        if kind == "feedforward":
            def ff_fn(params, state, x, fmask, rng):
                _, new_state, acts, _ = self._forward_fn(
                    params, state, x, rng, train, fmask, collect=True
                )
                return acts, new_state
            return jax.jit(ff_fn)
        return super()._build_jit(kind, train=train, **static)

    # ----------------------------------------------------------------- loss

    def _loss_from_preout(self, params, preout, y, lmask, aux, eb=None):
        layer = self.layers[-1]
        name = type(layer).__name__
        if name not in OUTPUT_LAYER_TYPES:
            raise ValueError(
                f"Last layer ({name}) is not an output layer; cannot compute loss"
            )
        # The loss belongs to the output layer: same `L.<key>`.
        with scope(self.layer_keys[-1], "L."):
            preout = preout.astype(self._loss_dtype)
            # `eb` overrides the divisor for tBPTT chunks: a row fully masked
            # within ONE chunk of a variable-length batch still counts toward the
            # reference's divide-by-minibatch (computed from the full-sequence
            # mask in `_fit_tbptt`), while data-parallel padding rows never do.
            if eb is None:
                eb = losses_mod.effective_batch_size(y, lmask)
            with scope(layer.scope):
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
                lr = self._schedules[lk](step)
                st, deltas = self._updaters[lk].update(opt_state, grads, lr, step)
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

    # -------------------------------------------------------------- predict

    def output(self, x, train: bool = False, features_mask=None,
               params=None) -> np.ndarray:
        """Inference forward (reference: `output()` `:1519-1601`); `params`
        as in `Engine._output_arrays`."""
        return np.asarray(self._output_arrays(
            jnp.asarray(x), self._to_device(features_mask), train, params))

    def feed_forward(self, x, train: bool = False, features_mask=None) -> List[np.ndarray]:
        """All layer activations (reference: `feedForward()` `:655-760`).
        Note: for output layers the listed activation is the pre-activation."""
        fn = self._get_jit("feedforward", train=train)
        acts, _ = fn(self.params_tree, self.state, jnp.asarray(x),
                     self._to_device(features_mask),
                     self._next_rng() if train else jax.random.PRNGKey(0))
        return [np.asarray(a) for a in acts]

    def predict(self, x) -> np.ndarray:
        return np.argmax(self.output(x), axis=-1)

    def rnn_time_step(self, x) -> np.ndarray:
        """Stateful single/multi-step inference (reference: `rnnTimeStep:2230`).
        Accepts [b, f] (one step) or [b, t, f]; hidden state persists across calls."""
        x = np.asarray(x)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        out = np.asarray(self._rnn_step(jnp.asarray(x), x.shape[1]))
        return out[:, 0] if squeeze and out.ndim == 3 else out

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
