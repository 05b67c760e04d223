"""Per-equation flop/byte accounting over jaxprs.

Reference parity: TePDist decorates def-modules with flop costs via
HloCostAnalysis (``Service::BuildRunCost``, reference service/service.cc:697-746)
and the planner's per-instruction flops in GraphSketch. Here the unit of IR is
a jaxpr equation instead of an HLO instruction; rules below cover the
primitives that dominate TPU time (dot_general, conv), with everything
elementwise costed at one flop per output element and memory traffic as the
sum of operand+result bytes (the HBM-bound view).
"""

from __future__ import annotations

import math
from typing import Any

import numpy as np
from jax.extend import core as jcore


def aval_size(aval) -> int:
    """Element count of an abstract value (0 for non-arrays)."""
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(math.prod(shape)) if len(shape) else 1


def aval_bytes(aval) -> int:
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        # Extended dtypes (PRNG keys): size of the underlying key data
        # (threefry: 2 x uint32). np.dtype cannot interpret them.
        import jax

        if jax.dtypes.issubdtype(dtype, jax.dtypes.prng_key):
            shape = getattr(getattr(dtype, "_impl", None), "key_shape",
                            (2,))
            itemsize = 4 * int(np.prod(shape))
        else:
            itemsize = 4
    return aval_size(aval) * itemsize


def _dot_general_flops(eqn) -> float:
    dnums = eqn.params["dimension_numbers"]
    (lc, _rc), (lb, _rb) = dnums
    lhs = eqn.invars[0].aval
    out = eqn.outvars[0].aval
    contract = math.prod(lhs.shape[d] for d in lc) if lc else 1
    return 2.0 * aval_size(out) * contract


def conv_flops(eqn) -> float:
    rhs = eqn.invars[1].aval
    out = eqn.outvars[0].aval
    dnums = eqn.params["dimension_numbers"]
    kernel_spatial = math.prod(rhs.shape[d] for d in dnums.rhs_spec[2:])
    c_in_per_group = rhs.shape[dnums.rhs_spec[1]]
    return 2.0 * aval_size(out) * kernel_spatial * c_in_per_group


# Primitives considered "compute-intensive" — these seed planner cones
# (reference: cone roots = compute-heavy insts, cost_spmd_strategy.h:40-51).
COMPUTE_INTENSIVE = {"dot_general", "conv_general_dilated"}

# Call-like primitives whose cost lives in a sub-jaxpr.
CALL_PRIMITIVES = {
    "pjit", "jit", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "remat", "checkpoint",
    "custom_jvp_call_jaxpr", "remat2",
}


def eqn_flops(eqn) -> float:
    """Estimated FLOPs of one equation (recurses into sub-jaxprs)."""
    name = eqn.primitive.name
    if name == "dot_general":
        return _dot_general_flops(eqn)
    if name == "conv_general_dilated":
        return conv_flops(eqn)
    if name == "pallas_call" and eqn.params.get("cost_estimate") is not None:
        # A kernel that states its own cost (the grouped matmuls do).
        return float(eqn.params["cost_estimate"].flops)
    if name in CALL_PRIMITIVES:
        inner = _sub_jaxpr(eqn)
        return jaxpr_flops(inner) if inner is not None else 0.0
    if name == "scan":
        inner = eqn.params.get("jaxpr")
        length = eqn.params.get("length", 1)
        if inner is not None:
            return jaxpr_flops(inner.jaxpr) * float(length)
        return 0.0
    if name in ("while", "cond"):
        total = 0.0
        for key in ("body_jaxpr", "cond_jaxpr"):
            sub = eqn.params.get(key)
            if sub is not None:
                total += jaxpr_flops(sub.jaxpr)
        for branch in eqn.params.get("branches", ()):  # cond
            total = max(total, jaxpr_flops(branch.jaxpr))
        return total
    # Elementwise / data movement: one flop per output element.
    return float(sum(aval_size(v.aval) for v in eqn.outvars))


def eqn_bytes(eqn) -> float:
    """HBM traffic estimate: operands read + results written."""
    total = 0.0
    for v in eqn.invars:
        if isinstance(v, jcore.Var):
            total += aval_bytes(v.aval)
        elif hasattr(v, "aval"):
            total += aval_bytes(v.aval)
    for v in eqn.outvars:
        total += aval_bytes(v.aval)
    return total


def _sub_jaxpr(eqn):
    p = eqn.params
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = p.get(key)
        if sub is None:
            continue
        return sub.jaxpr if hasattr(sub, "jaxpr") else sub
    return None


def jaxpr_flops(jaxpr) -> float:
    return float(sum(eqn_flops(e) for e in jaxpr.eqns))
