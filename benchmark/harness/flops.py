"""Operations the mathematics needs, counted from shapes.

`matmul_macs` walks a jaxpr and adds up the multiply-adds of every matrix
multiplication and convolution (`dot_general`, `conv_general_dilated`),
through calls, scans and custom derivatives. Elementwise work is not
counted, and nothing is taken from XLA's cost analysis, which counts what
the compiled program does and not what the model needs.
"""

from __future__ import annotations

import math


def _eqn_macs(eqn) -> int:
    name = eqn.primitive.name
    if name == "dot_general":
        (lc, _), (lb, _) = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        out = eqn.outvars[0].aval.shape
        contract = math.prod(lhs[i] for i in lc)
        return math.prod(out) * contract
    if name == "conv_general_dilated":
        dn = eqn.params["dimension_numbers"]
        rhs = eqn.invars[1].aval.shape
        out = eqn.outvars[0].aval.shape
        kernel_spatial = math.prod(rhs[i] for i in dn.rhs_spec[2:])
        cin_per_group = rhs[dn.rhs_spec[1]]
        return math.prod(out) * kernel_spatial * cin_per_group
    return 0


def _sub_jaxprs(eqn):
    """(jaxpr, times run) for every jaxpr an equation carries."""
    times = int(eqn.params.get("length", 1)) if eqn.primitive.name == "scan" \
        else 1
    for value in eqn.params.values():
        for item in (value if isinstance(value, (list, tuple)) else (value,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner, times


def matmul_macs(jaxpr) -> int:
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0
    for eqn in jaxpr.eqns:
        total += _eqn_macs(eqn)
        for inner, times in _sub_jaxprs(eqn):
            total += times * matmul_macs(inner)
    return total


def forward_macs(fn, *args) -> int:
    """Multiply-adds of one call of `fn(*args)` (arrays or shapes)."""
    import jax

    return matmul_macs(jax.make_jaxpr(fn)(*args))
