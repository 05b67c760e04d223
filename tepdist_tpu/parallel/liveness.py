"""Liveness optimizer: duplicate cheap long-lived values.

Reference parity: ``HloLivenessOptimizer`` (reference:
parallel/hlo_liveness_optimizer.{h,cc}, ~80 LoC): pre-planning pass that
duplicates cheap instructions with long live ranges (broadcasts, iotas,
constants) so each consumer region regenerates them locally instead of
keeping them alive — shortening live ranges before memory planning.

On TPU, XLA performs this rematerialization during compilation; this pass
exists for the *planner's* benefit: the activation-peak estimator and the
scheduler's memory accounting see the shortened ranges, so micro-batch
counts and schedules are sized against realistic liveness."""

from __future__ import annotations

from typing import Dict, List

from jax.extend import core as jexcore

from tepdist_tpu.graph.jaxpr_graph import JaxprGraph

Var = jexcore.Var

# Cheap, operand-light producers worth duplicating.
_DUPLICABLE = {"broadcast_in_dim", "iota"}


def optimize_liveness(graph: JaxprGraph, min_range: int = 32,
                      min_bytes: int = 1 << 16) -> JaxprGraph:
    """Rewrite the jaxpr duplicating duplicable producers whose consumers
    span more than ``min_range`` equations, one copy per far consumer.
    Returns a new JaxprGraph (the input is untouched)."""
    jaxpr = graph.jaxpr
    new_eqns = []
    # var -> replacement per consumer id
    overrides: Dict[int, Dict[Var, Var]] = {}
    for node in graph.nodes:
        if node.prim not in _DUPLICABLE:
            continue
        if any(isinstance(a, Var) for a in node.invars):
            # keep it simple: only literal/scalar-fed producers
            if not all(len(getattr(a, "aval", None).shape) == 0
                       for a in node.invars if isinstance(a, Var)):
                continue
        if node.out_bytes() < min_bytes:
            continue
        ov = node.outvars[0]
        if not isinstance(ov, Var):
            continue
        far = [u for u in node.users if u.id - node.id > min_range]
        if len(node.users) < 2 or not far:
            continue
        for u in far:
            overrides.setdefault(u.id, {})[ov] = node  # mark for dup

    if not overrides:
        return graph

    def clone_eqn(eqn, out_map):
        new_outs = []
        for o in eqn.outvars:
            if type(o).__name__ == "DropVar":
                new_outs.append(o)
            else:
                fresh = Var(o.aval)
                out_map[o] = fresh
                new_outs.append(fresh)
        return eqn.replace(outvars=new_outs)

    for node in graph.nodes:
        subst = overrides.get(node.id)
        if not subst:
            new_eqns.append(node.eqn)
            continue
        local_map: Dict[Var, Var] = {}
        for v, producer in subst.items():
            dup = clone_eqn(producer.eqn, local_map)
            new_eqns.append(dup)
        new_invars = [local_map.get(a, a) if isinstance(a, Var) else a
                      for a in node.eqn.invars]
        new_eqns.append(node.eqn.replace(invars=new_invars))

    new_jaxpr = jaxpr.replace(eqns=new_eqns)
    closed = jexcore.ClosedJaxpr(new_jaxpr, graph.closed.consts)
    return JaxprGraph(closed, inline=False)
