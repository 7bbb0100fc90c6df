"""DtypePolicy: first-class precision policy for the config DSL.

One object answers three questions the engines used to hardcode:

- ``param_dtype``   — what the stored parameter leaves are (HBM residency);
- ``compute_dtype`` — what layer math runs in (params are cast at use, the
  dominant matmul/conv traffic — PERF.md §2's HBM-bound lever);
- ``output_dtype``  — what ``output()`` returns to callers.

Two training-side mechanisms hang off the policy:

- **master copies**: when ``param_dtype`` is lower precision than f32, the
  optimizer keeps an f32 master copy of every param (and f32 updater
  state); each step updates the master and re-casts, so repeated tiny
  updates never underflow the low-precision representation. The master
  tree rides inside ``opt_state`` under the reserved ``"_master"`` key —
  jit signatures, checkpoint trees and the superstep scan carry are
  unchanged in shape, they just grow leaves.
- **dynamic loss scaling** (f16-class compute): the loss is multiplied by
  a scale before backward, gradients are unscaled after; a step whose
  scaled grads are non-finite is SKIPPED (params/updater/state keep their
  old values via a ``jnp.where`` select) and the scale halves; after
  ``growth_interval`` consecutive finite steps it doubles. The
  ``(scale, good_count)`` pair lives at ``opt_state["_ls"]`` — carried
  ON-DEVICE so a fused superstep ``lax.scan`` stays one program with no
  host round-trip per iteration.

The default policy is ``"float32"`` and is bit-identical to the engines'
historical behavior (it serializes to *nothing*: ``GlobalConf.to_dict``
omits an unset policy so conf JSON — and therefore AOT compile-cache
fingerprints — are byte-identical to pre-policy builds).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

_CANONICAL = {
    "float32": "float32", "f32": "float32", "fp32": "float32",
    "float64": "float64", "f64": "float64", "double": "float64",
    "bfloat16": "bfloat16", "bf16": "bfloat16",
    "float16": "float16", "f16": "float16", "fp16": "float16",
    "mixed_bfloat16": "mixed_bfloat16",
    "mixed_float16": "mixed_float16",
}

# f16-class dtypes default to dynamic loss scaling; bf16 keeps f32's
# exponent range so it trains unscaled.
_PRESETS = {
    # name: (param, compute, output, dynamic_loss_scale)
    "float32": ("float32", "float32", "float32", False),
    "float64": ("float64", "float64", "float64", False),
    "mixed_bfloat16": ("float32", "bfloat16", "float32", False),
    "mixed_float16": ("float32", "float16", "float32", True),
    "bfloat16": ("bfloat16", "bfloat16", "bfloat16", False),
    "float16": ("float16", "float16", "float16", True),
}

_LOW_PRECISION = ("bfloat16", "float16")


@dataclasses.dataclass(frozen=True)
class DtypePolicy:
    """Immutable precision policy. Build via a preset name
    (``DtypePolicy.of("mixed_bfloat16")``) or field-by-field; unspecified
    fields fall back to the preset the ``name`` selects."""

    name: str = "float32"
    param_dtype: Optional[str] = None
    compute_dtype: Optional[str] = None
    output_dtype: Optional[str] = None
    # Host->device staging cast for superbatch/device-cache tiers
    # (datasets/iterators.py): features/labels ship at this dtype, halving
    # H2D bytes for f32 pipelines.
    transfer_dtype: Optional[str] = None
    # Dynamic loss scaling (None = preset default for the name).
    dynamic_loss_scale: Optional[bool] = None
    initial_loss_scale: float = 2.0 ** 15
    loss_scale_growth_interval: int = 2000
    loss_scale_growth_factor: float = 2.0
    loss_scale_backoff_factor: float = 0.5

    def __post_init__(self):
        name = _CANONICAL.get(str(self.name))
        if name is None:
            raise ValueError(
                f"unknown dtype policy {self.name!r}; presets: "
                f"{sorted(_PRESETS)}")
        object.__setattr__(self, "name", name)

    # ------------------------------------------------------------ resolved
    def _preset(self):
        return _PRESETS[self.name]

    @property
    def resolved_param_dtype(self) -> str:
        return self.param_dtype or self._preset()[0]

    @property
    def resolved_compute_dtype(self) -> str:
        return self.compute_dtype or self._preset()[1]

    @property
    def resolved_output_dtype(self) -> str:
        return self.output_dtype or self._preset()[2]

    @property
    def jnp_param(self):
        import jax.numpy as jnp
        return jnp.dtype(self.resolved_param_dtype)

    @property
    def jnp_compute(self):
        import jax.numpy as jnp
        return jnp.dtype(self.resolved_compute_dtype)

    @property
    def jnp_output(self):
        import jax.numpy as jnp
        return jnp.dtype(self.resolved_output_dtype)

    @property
    def low_precision_params(self) -> bool:
        """True when params are stored below f32 — the optimizer then keeps
        f32 master copies at ``opt_state["_master"]``."""
        return self.resolved_param_dtype in _LOW_PRECISION

    @property
    def uses_loss_scaling(self) -> bool:
        if self.dynamic_loss_scale is not None:
            return bool(self.dynamic_loss_scale)
        return self._preset()[3]

    @property
    def is_default(self) -> bool:
        """Full-f32 with no knobs set — serializes to nothing and must be
        bit-identical to the pre-policy engines."""
        return self == DtypePolicy()

    # ---------------------------------------------------------------- serde
    def to_dict(self) -> dict:
        d: dict = {"name": self.name}
        for f in dataclasses.fields(self):
            if f.name == "name":
                continue
            v = getattr(self, f.name)
            if v is not None and v != f.default:
                d[f.name] = v
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "DtypePolicy":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    @classmethod
    def of(cls, v: Any) -> "DtypePolicy":
        """Coerce str | dict | DtypePolicy | None to a policy."""
        if v is None:
            return cls()
        if isinstance(v, DtypePolicy):
            return v
        if isinstance(v, str):
            return cls(name=v)
        if isinstance(v, dict):
            return cls.from_dict(v)
        raise TypeError(f"cannot build a DtypePolicy from {type(v).__name__}")


def resolve_policy(global_conf) -> DtypePolicy:
    """The one resolution point both engines use. An explicit
    ``dtype_policy`` wins; otherwise the legacy ``GlobalConf.dtype`` string
    maps onto the preset with identical semantics ("bfloat16" historically
    meant bf16 COMPUTE over f32 params — i.e. ``mixed_bfloat16``)."""
    explicit = getattr(global_conf, "dtype_policy", None)
    if explicit is not None:
        return DtypePolicy.of(explicit)
    legacy = getattr(global_conf, "dtype", "float32")
    if legacy == "bfloat16":
        return DtypePolicy(name="mixed_bfloat16")
    if legacy == "float64":
        return DtypePolicy(name="float64")
    return DtypePolicy()
