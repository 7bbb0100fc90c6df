"""Transfer learning: freeze-by-layer fine-tuning and the LoRA wiring.

Equivalent of the reference's `nn/transferlearning/TransferLearning.java`
builder + `FrozenLayer` wrapper — recast for pytree engines. A frozen
layer here is not a wrapper object but a *spec*: `frozen_spec` computes,
from the layer configs (`Layer.frozen` / `Layer.lora_rank`), the set of
param leaves excluded from training. The engine (`nn/engine.py`)
consumes the spec so:

- updater-state init runs over the TRAINABLE subtree only, so frozen
  leaves get no Adam/RMSProp moments (a fully-frozen layer's opt entry
  is `()`) — the HBM cost of fine-tuning scales with the trainable
  params, not the model;
- `_train_step` differentiates the trainable subtree only (frozen leaves
  are closed over as constants inside the loss), so the backward never
  materializes their grads and XLA prunes the corresponding dead
  backward compute. This is also what makes LoRA-over-int8 possible:
  quantized base leaves are integers, which `jax.grad` refuses — frozen,
  they simply ride along as data.

Frozen stored leaves pass through the train step as the SAME arrays
(bitwise-unchanged, no copy). The spec is empty for ordinary nets, and
every split/merge below is the identity in that case — the pre-transfer
jit programs are byte-identical.

`TransferLearning(net)` is the user-facing builder: freeze a prefix or
named layers, attach LoRA adapters (`nn/lora.py`), and `build()` a new
engine sharing the base param arrays.
"""

from __future__ import annotations

import copy
from typing import Any, Dict, FrozenSet, List, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import lora as lora_mod

FrozenSpec = Dict[str, FrozenSet[str]]


def frozen_spec(layer_items, params_tree) -> FrozenSpec:
    """`{layer_key: frozenset(param names excluded from training)}` from
    the layer configs. Only layers with `frozen=True` or `lora_rank` set
    contribute — an unconfigured net yields `{}` and every consumer
    below degenerates to the identity.

    Within a contributing layer: all base leaves freeze (including
    biases, quantization `__scale` companions and the constant
    `__lora_scale`); the `__lora_a`/`__lora_b` factor pair stays
    trainable unless the layer is ALSO marked `frozen=True` (a fully
    frozen layer, adapters included)."""
    spec: FrozenSpec = {}
    for lk, conf in layer_items:
        lparams = (params_tree or {}).get(lk)
        if not isinstance(lparams, dict) or not lparams:
            continue
        layer_frozen = bool(getattr(conf, "frozen", None))
        has_lora = bool(getattr(conf, "lora_rank", None) or 0)
        if not layer_frozen and not has_lora:
            # Leaves a layer never trains (`Layer.frozen_param_names`: the
            # sparse-attention indexer), the rest of the layer trainable.
            own = getattr(conf, "frozen_param_names", tuple)()
            names = frozenset(n for n in own if n in lparams)
            if names:
                spec[lk] = names
            continue
        names = set()
        for name in lparams:
            if name.endswith((lora_mod.LORA_A, lora_mod.LORA_B)):
                if layer_frozen:
                    names.add(name)
            else:
                names.add(name)
        if names:
            spec[lk] = frozenset(names)
    return spec


def split_tree(tree, spec: FrozenSpec):
    """(trainable, frozen) halves of a params tree. Both keep EVERY layer
    key (empty dicts where a side has nothing), so jit signatures, the
    loss-scaling `tree_map(sel, ...)` selects, and `_apply_updates`' keyed
    iteration all see structure-stable trees. Arrays are never copied."""
    trainable: Dict[str, Any] = {}
    frozen: Dict[str, Any] = {}
    for lk, lparams in tree.items():
        names = spec.get(lk)
        if not names or not isinstance(lparams, dict):
            trainable[lk] = lparams
            frozen[lk] = {}
            continue
        trainable[lk] = {k: a for k, a in lparams.items() if k not in names}
        frozen[lk] = {k: a for k, a in lparams.items() if k in names}
    return trainable, frozen


def merge_tree(trainable, frozen):
    """Inverse of `split_tree`: the full tree, frozen leaves re-attached
    as the same array objects."""
    out: Dict[str, Any] = {}
    for lk, lparams in trainable.items():
        fro = (frozen or {}).get(lk) or {}
        if fro and isinstance(lparams, dict):
            merged = dict(lparams)
            merged.update(fro)
            out[lk] = merged
        else:
            out[lk] = lparams
    return out


def _layer_items(net) -> List[Tuple[str, Any]]:
    """(layer_key, layer conf) pairs in the net's flat-parameter order
    (MLN: index order; graph: topological order of layer vertices)."""
    layers = dict(net.named_layers())
    return [(key, layers[key]) for key in net._param_order()]


class TransferLearning:
    """Builder for a fine-tuning copy of an initialized engine (reference:
    `TransferLearning.Builder` / `.GraphBuilder`).

    >>> tuned = (TransferLearning(base)
    ...          .freeze_up_to("layer_2")      # feature extractor
    ...          .add_lora(rank=8, alpha=16)   # adapters on eligible layers
    ...          .build())

    `build()` returns a NEW engine of the same class: its conf is a deep
    copy with `frozen` / `lora_rank` / `lora_alpha` stamped onto the
    layer configs (so checkpoints, clones and AOT fingerprints carry the
    transfer setup), its base params are COPIES of the source net's (the
    train step donates its param buffers — shared arrays would be
    invalidated under the source net), and fresh LoRA leaves are drawn
    where requested. The source net is never mutated."""

    def __init__(self, net):
        if getattr(net, "params_tree", None) is None:
            raise ValueError(
                "TransferLearning needs an initialized net (call init())")
        self._net = net
        self._items = _layer_items(net)
        self._keys = [k for k, _ in self._items]
        self._freeze: set = set()
        self._lora: Dict[str, Tuple[int, Optional[float]]] = {}

    def _resolve(self, ident) -> str:
        if isinstance(ident, int):
            if not 0 <= ident < len(self._keys):
                raise ValueError(
                    f"layer index {ident} out of range 0..{len(self._keys) - 1}")
            return self._keys[ident]
        key = str(ident)
        if key not in self._keys:
            raise ValueError(
                f"unknown layer {ident!r}; layers: {self._keys}")
        return key

    # ------------------------------------------------------------ freezing

    def freeze_up_to(self, ident) -> "TransferLearning":
        """Freeze every layer up to and including `ident` (the reference's
        `setFeatureExtractor`)."""
        key = self._resolve(ident)
        self._freeze.update(self._keys[: self._keys.index(key) + 1])
        return self

    def freeze(self, *idents) -> "TransferLearning":
        """Freeze specific layers by index or key/vertex name."""
        self._freeze.update(self._resolve(i) for i in idents)
        return self

    # ---------------------------------------------------------------- lora

    def add_lora(self, rank: int, alpha: Optional[float] = None,
                 layers=None) -> "TransferLearning":
        """Attach rank-`r` LoRA adapters (`nn/lora.py`). `layers=None`
        targets every eligible layer (one with 2-D weights); naming an
        ineligible layer explicitly raises. A LoRA layer's base params
        are implicitly frozen — only the adapter factors train."""
        rank = int(rank)
        if rank <= 0:
            raise ValueError(f"lora rank must be positive, got {rank}")
        if layers is None:
            chosen = [k for k, conf in self._items
                      if lora_mod.lora_target_names(conf)]
            if not chosen:
                raise ValueError("no LoRA-eligible layer (2-D weights) found")
        else:
            chosen = []
            for ident in layers:
                key = self._resolve(ident)
                conf = dict(self._items)[key]
                if not lora_mod.lora_target_names(conf):
                    raise ValueError(
                        f"layer {key!r} ({type(conf).__name__}) has no 2-D "
                        f"weight to adapt")
                chosen.append(key)
        for key in chosen:
            self._lora[key] = (rank, alpha)
        return self

    # --------------------------------------------------------------- build

    def build(self):
        # The new net's layer confs are its (copied) configuration's own:
        # stamping them here stamps the configuration.
        new_net = type(self._net)(copy.deepcopy(self._net.conf))
        conf = new_net.conf
        citems = dict(new_net.named_layers())
        for key in self._freeze:
            citems[key].frozen = True
        for key, (rank, alpha) in self._lora.items():
            citems[key].lora_rank = rank
            if alpha is not None:
                citems[key].lora_alpha = float(alpha)

        pol = new_net.dtype_policy
        pdt = jnp.float32 if pol.low_precision_params else pol.jnp_param
        rng = jax.random.PRNGKey(conf.global_conf.seed ^ 0x10A)
        # Copy every base leaf: the jitted train step donates its param
        # buffers, so arrays shared with the source net would be deleted
        # under it on the tuned net's first fit.
        params: Dict[str, Any] = jax.tree_util.tree_map(
            jnp.array, {lk: (dict(lp) if isinstance(lp, dict) else lp)
                        for lk, lp in self._net.params_tree.items()})
        for i, key in enumerate(self._keys):
            if key in self._lora:
                params.setdefault(key, {})
                params[key].update(lora_mod.init_lora_params(
                    citems[key], jax.random.fold_in(rng, i), dtype=pdt))
        new_net.init(params=params)
        # Carry non-trainable state (BN running stats, center-loss centers).
        for lk, s in (self._net.state or {}).items():
            if lk in new_net.state:
                new_net.state[lk] = dict(s)
        return new_net
