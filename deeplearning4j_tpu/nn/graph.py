"""ComputationGraph: the DAG network engine.

Equivalent of the reference's `nn/graph/ComputationGraph.java` (2276 LoC) +
`nn/graph/vertex/` — arbitrary-DAG, multi-input/multi-output networks. The
topological order is computed once from the config (reference `:283,851`) and
the whole graph traverses at trace time into a single jitted program; vertex
objects never exist at runtime.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import activations as activations_mod
from deeplearning4j_tpu.nn import losses as losses_mod
from deeplearning4j_tpu.nn import params as params_mod
from deeplearning4j_tpu.nn.conf.graph import (
    DuplicateToTimeSeriesVertex,
    LastTimeStepVertex,
    LayerVertex,
)
from deeplearning4j_tpu.nn.conf.neural_net import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf import preprocessors as preprocessors_mod
from deeplearning4j_tpu.nn.engine import Engine, scope
from deeplearning4j_tpu.nn.layers import OUTPUT_LAYER_TYPES, get_impl
from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterators import MultiSuperbatch, Superbatch
from deeplearning4j_tpu.nn.fit_obs import FitObs


def _as_mds(data, labels=None):
    if isinstance(data, (MultiDataSet, MultiSuperbatch)):
        return data
    if isinstance(data, DataSet):
        return MultiDataSet.from_dataset(data)
    if isinstance(data, Superbatch):
        # DataSet-shaped block (a SuperbatchIterator built without this
        # engine's transform): lift to the graph's list-of-parts shape.
        return MultiSuperbatch(
            [data.features], [data.labels],
            None if data.features_mask is None else [data.features_mask],
            None if data.labels_mask is None else [data.labels_mask],
            k=data.k)
    return MultiDataSet(features=[np.asarray(data)], labels=[np.asarray(labels)])


def _as_mask_list(masks):
    """Normalize a MultiDataSet part for the jitted fns: None when no
    entry is present, else per-entry jnp arrays (None entries preserved)."""
    if masks is None or not any(m is not None for m in masks):
        return None
    return [None if m is None else jnp.asarray(m) for m in masks]


class ComputationGraph(Engine):
    """DAG network engine (see module docstring): the vertices in
    topological order, their forward and their losses; the train step, the
    jit kinds and the fit loop are `nn/engine.py`'s."""

    # This engine's hot-loop metric series and fit-loop spans.
    _FIT = FitObs("graph")
    _as_data = staticmethod(_as_mds)
    _to_device = staticmethod(_as_mask_list)

    def __init__(self, conf: ComputationGraphConfiguration):
        super().__init__(conf)
        self.topo_order = conf.topological_order()
        self.layer_vertices = {
            name: v for name, v in conf.vertices.items() if isinstance(v, LayerVertex)
        }

    def named_layers(self):
        return [(name, v.layer) for name, v in self.layer_vertices.items()]

    def _param_order(self):
        return [n for n in self.topo_order if n in self.layer_vertices]

    def _draw_params(self, root, dtype):
        names = sorted(self.layer_vertices)
        keys = jax.random.split(root, max(len(names), 1))
        return {
            name: params_mod.init_layer_params(self.layer_vertices[name].layer, keys[i], dtype=dtype)
            for i, name in enumerate(names)
        }

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

    # ----------------------------------------------------------------- data

    @staticmethod
    def _fit_source(data, labels):
        if labels is not None or isinstance(data, (DataSet, MultiDataSet)):
            return [_as_mds(data, labels)]
        return data

    @staticmethod
    def _host_parts(mds):
        return mds.features, mds.labels, mds.features_masks, mds.labels_masks

    @staticmethod
    def _tbptt_divisors(labels, lmasks):
        # One divisor per output.
        return tuple(
            jax.device_put(np.float32(losses_mod.effective_batch_size(
                l, lmasks[i] if lmasks is not None else None)))
            for i, l in enumerate(labels))

    # --------------------------------------------------------------- forward

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
            with scope(name, "L."):
                values[name] = preprocessors_mod.apply_uint8_policy(
                    jnp.asarray(inputs[i]), policies[name], cdt)
            masks[name] = None if fmasks is None else fmasks[i]
        new_state: Dict[str, Any] = {}
        aux: Dict[str, Any] = {}
        for vi, name in enumerate(self.topo_order):
            # Every vertex, a layer or not, runs under `L.<name>`: the cast
            # of its parameters and its preprocessor too.
            with scope(name, "L."):
                vertex = self.conf.vertices[name]
                in_names = self.conf.vertex_inputs[name]
                in_vals = [values[n] for n in in_names]
                in_masks = [masks[n] for n in in_names]
                if isinstance(vertex, LayerVertex):
                    x, mask = in_vals[0], in_masks[0]
                    layer = vertex.layer
                    if layer.n_inputs() > 1:
                        # a layer of several inputs takes them all, in the
                        # vertex's order (`Layer.n_inputs`)
                        x = tuple(in_vals)
                    if vertex.preprocessor is not None:
                        x, mask = vertex.preprocessor(x, mask)
                    if type(layer).__name__ == "CenterLossOutputLayer":
                        aux[f"center_loss_input:{name}"] = x
                        aux[f"centers:{name}"] = state.get(name, {}).get("centers")
                    lrng = jax.random.fold_in(rng, vi) if rng is not None else None
                    # Params stored at param_dtype, cast (or dequantized) to the
                    # policy's compute dtype at use (nn/params.py).
                    lparams = params_mod.prep_layer_params(params.get(name, {}),
                                                           cdt, layer=layer)
                    if getattr(layer, "tied_to", None) is not None:
                        # a head tied to an embedding reads its table
                        tied = self.layer_vertices[layer.tied_to].layer
                        lparams = dict(lparams, W=params_mod.prep_layer_params(
                            params[layer.tied_to], cdt, layer=tied)["W"].T)
                    with scope(layer.scope):
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

    def _forward_loss(self, params, state, batch, rng, train,
                      carry_rnn=False, ebs=None):
        inputs, labels, fmasks, lmasks = batch
        outs, new_state, aux, omasks = self._forward_fn(
            params, state, inputs, rng, train, fmasks,
            keep_rnn_state=carry_rnn)
        loss, extra = self._loss_from_outputs(params, outs, labels, lmasks,
                                              aux, omasks, ebs)
        for n, s in extra.items():
            new_state.setdefault(n, {}).update(s)
        return loss, new_state

    def _outputs(self, params, state, inputs, fmasks, rng, train,
                 keep_rnn_state):
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

    # ----------------------------------------------------------------- loss

    def _loss_from_outputs(self, params, outs, labels, lmasks, aux, omasks,
                           ebs=None):
        total = 0.0
        extra_state: Dict[str, Any] = {}
        for i, name in enumerate(self.conf.network_outputs):
            v = self.layer_vertices.get(name)
            if v is None or type(v.layer).__name__ not in OUTPUT_LAYER_TYPES:
                raise ValueError(f"Network output {name!r} is not an output layer")
            layer = v.layer
            # The loss belongs to its output vertex: same `L.<name>`.
            with scope(name, "L."):
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
                with scope(layer.scope):
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

    # -------------------------------------------------------------- predict

    def output(self, *inputs, train: bool = False, features_masks=None,
               params=None) -> List[np.ndarray]:
        """Inference forward, one array per network output; `params` as in
        `Engine._output_arrays`."""
        outs = self._output_arrays([jnp.asarray(x) for x in inputs],
                                   features_masks, train, params)
        return [np.asarray(o) for o in outs]

    def output_single(self, *inputs, **kw) -> np.ndarray:
        return self.output(*inputs, **kw)[0]

    def rnn_time_step(self, *inputs) -> List[np.ndarray]:
        """Stateful single/multi-step inference (reference:
        `ComputationGraph.rnnTimeStep:1386` — same contract as
        `MultiLayerNetwork.rnn_time_step`): hidden state (LSTM carries,
        attention KV caches, positional cursors) persists across calls.
        Accepts [b, f] (one step) or [b, t, f] per input."""
        arrs = []
        squeeze = False
        for x in inputs:
            x = np.asarray(x)
            if x.ndim == 2:
                x = x[:, None, :]
                squeeze = True
            arrs.append(x)
        outs = self._rnn_step([jnp.asarray(x) for x in arrs],
                              arrs[0].shape[1])
        result = []
        for o in outs:
            o = np.asarray(o)
            result.append(o[:, 0] if squeeze and o.ndim == 3 else o)
        return result

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
