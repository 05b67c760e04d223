"""Dataflow-graph view over a jaxpr: the planner's IR.

Reference parity: TePDist's planner walks HLO instructions of the whole
training-step module (client sends HLO over RPC). The TPU-native unit of IR is
the *jaxpr* of the training step (JAX's functional IR, one level above
StableHLO): per-equation operand/user adjacency, flops/bytes, and ranks — the
inputs the cone decomposition (cost_spmd_strategy), graph sketch
(hlo_graph_sketch), and sync-free analysis all need.

Call-like equations (jit/pjit, custom_jvp/vjp, remat) are inlined into a flat
equation list first — the analogue of the reference running CallInliner before
AutoParallel (reference: gpu_compiler.cc:265-285 pass ordering).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.extend import core as jexcore

from tepdist_tpu.graph.cost import (
    COMPUTE_INTENSIVE,
    aval_bytes,
    aval_size,
    eqn_bytes,
    eqn_flops,
)

Var = jexcore.Var
Literal = jexcore.Literal

# Call-like primitives to inline, mapped to the param holding the sub-jaxpr.
_INLINE_PRIMS = {
    "pjit": "jaxpr",
    "jit": "jaxpr",
    "closed_call": "call_jaxpr",
    "custom_jvp_call": "call_jaxpr",
    "custom_vjp_call": "call_jaxpr",
    "custom_vjp_call_jaxpr": "fun_jaxpr",
    "remat": "jaxpr",
    "remat2": "jaxpr",
    "checkpoint": "jaxpr",
}


def _as_open_jaxpr(sub) -> Tuple[Any, Sequence[Any]]:
    """Return (jaxpr, consts) for either a Jaxpr or ClosedJaxpr."""
    if hasattr(sub, "jaxpr"):
        return sub.jaxpr, list(sub.consts)
    return sub, []


def inline_calls(jaxpr, max_depth: int = 16):
    """Flatten call-like equations into the parent jaxpr.

    Returns a new ``Jaxpr`` whose equation list contains no _INLINE_PRIMS
    (up to ``max_depth`` nesting). Control-flow primitives (scan/while/cond)
    are intentionally NOT inlined — they stay single nodes with aggregate
    costs, exactly as the reference treats fused/called computations.
    """
    if max_depth <= 0:
        return jaxpr

    new_eqns = []
    # Substitution environment: var in old jaxpr -> var/literal visible now.
    changed = False

    def subst(atom, env):
        if isinstance(atom, Literal):
            return atom
        return env.get(atom, atom)

    env: Dict[Var, Any] = {}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _INLINE_PRIMS and _INLINE_PRIMS[name] in eqn.params:
            changed = True
            sub, consts = _as_open_jaxpr(eqn.params[_INLINE_PRIMS[name]])
            sub = inline_calls(sub, max_depth - 1)
            inner_env: Dict[Var, Any] = {}
            const_vars = list(sub.constvars)
            for cv, cval in zip(const_vars, consts):
                # Bind constvars as literals where possible.
                inner_env[cv] = Literal(cval, cv.aval)
            outer_args = [subst(a, env) for a in eqn.invars]
            # custom_jvp_call passes (fn args...) matching sub invars count;
            # when arity mismatches (e.g. residual-carrying variants), map the
            # trailing invars (primal args are last).
            invars = list(sub.invars)
            if len(outer_args) >= len(invars):
                mapped = outer_args[len(outer_args) - len(invars):]
            else:
                raise ValueError(
                    f"inline {name}: arity mismatch {len(outer_args)} < {len(invars)}"
                )
            for iv, arg in zip(invars, mapped):
                inner_env[iv] = arg
            for sub_eqn in sub.eqns:
                new_invars = [subst(a, inner_env) for a in sub_eqn.invars]
                new_outvars = []
                for ov in sub_eqn.outvars:
                    if type(ov).__name__ == "DropVar":
                        new_outvars.append(ov)
                    else:
                        fresh = Var(ov.aval)
                        inner_env[ov] = fresh
                        new_outvars.append(fresh)
                # The call's own name stack goes before the inlined
                # equation's, which is relative to it: the scopes round a
                # ``jit`` or ``custom_vjp`` call stay on what it held.
                info = sub_eqn.source_info
                new_eqns.append(sub_eqn.replace(
                    invars=new_invars, outvars=new_outvars,
                    source_info=info.replace(
                        name_stack=eqn.source_info.name_stack
                        + info.name_stack)))
            # Wire sub outputs to the call's outvars.
            for call_out, sub_out in zip(eqn.outvars, sub.outvars):
                if type(call_out).__name__ == "DropVar":
                    continue
                env[call_out] = subst(sub_out, inner_env)
        else:
            new_invars = [subst(a, env) for a in eqn.invars]
            # Control-flow sub-jaxprs keep their structure but their BODIES
            # are inlined too (scan bodies otherwise retain jit/custom_jvp
            # eqns whose params — e.g. ctx_mesh — block serialization).
            if name in ("scan", "while", "cond", "shard_map"):
                changed_params = {}
                for key, val in eqn.params.items():
                    if hasattr(val, "jaxpr") and hasattr(val, "consts"):
                        inner = inline_calls(val.jaxpr, max_depth - 1)
                        if inner is not val.jaxpr:
                            changed_params[key] = type(val)(inner, val.consts)
                    elif hasattr(val, "eqns") and hasattr(val, "invars"):
                        # Raw (open) Jaxpr param — shard_map bodies: inline
                        # custom_vjp/jit eqns inside so their WrappedFun
                        # params never reach the serializer.
                        inner = inline_calls(val, max_depth - 1)
                        if inner is not val:
                            changed_params[key] = inner
                    elif key == "branches" and isinstance(val, (tuple, list)):
                        new_branches = []
                        any_b = False
                        for b in val:
                            inner = inline_calls(b.jaxpr, max_depth - 1)
                            any_b = any_b or inner is not b.jaxpr
                            new_branches.append(type(b)(inner, b.consts))
                        if any_b:
                            changed_params[key] = tuple(new_branches)
                if changed_params:
                    changed = True
                    params = dict(eqn.params)
                    params.update(changed_params)
                    new_eqns.append(eqn.replace(invars=new_invars,
                                                params=params))
                    continue
            new_eqns.append(eqn.replace(invars=new_invars))

    if not changed:
        return jaxpr
    new_outvars = [subst(a, env) for a in jaxpr.outvars]
    return jaxpr.replace(eqns=new_eqns, outvars=new_outvars)


@dataclasses.dataclass
class GraphNode:
    """One (inlined) jaxpr equation plus planner metadata."""

    id: int
    eqn: Any
    prim: str
    flops: float
    bytes: float
    operands: List["GraphNode"] = dataclasses.field(default_factory=list)
    users: List["GraphNode"] = dataclasses.field(default_factory=list)
    # Ranks filled by JaxprGraph.compute_ranks (reference: SketchNode asap/alap).
    asap: int = 0
    alap: int = 0
    stage: int = -1

    @property
    def outvars(self):
        return self.eqn.outvars

    @property
    def invars(self):
        return self.eqn.invars

    def out_bytes(self) -> float:
        return float(sum(aval_bytes(v.aval) for v in self.eqn.outvars))

    def is_compute_intensive(self) -> bool:
        return self.prim in COMPUTE_INTENSIVE

    def __hash__(self):
        return self.id

    def __repr__(self):
        return f"<{self.id}:{self.prim}>"


class JaxprGraph:
    """Operand/user adjacency + costs over a flat jaxpr."""

    def __init__(self, closed_jaxpr, inline: bool = True):
        self.closed = closed_jaxpr
        jaxpr = closed_jaxpr.jaxpr
        if inline:
            jaxpr = inline_calls(jaxpr)
        self.jaxpr = jaxpr
        self.invars: List[Var] = list(jaxpr.invars)
        self.outvars: List[Any] = list(jaxpr.outvars)
        self.constvars: List[Var] = list(jaxpr.constvars)

        self.nodes: List[GraphNode] = []
        self.producer: Dict[Var, Tuple[GraphNode, int]] = {}
        self.consumers: Dict[Var, List[GraphNode]] = {}
        for i, eqn in enumerate(jaxpr.eqns):
            node = GraphNode(
                id=i,
                eqn=eqn,
                prim=eqn.primitive.name,
                flops=eqn_flops(eqn),
                bytes=eqn_bytes(eqn),
            )
            self.nodes.append(node)
            for out_idx, ov in enumerate(eqn.outvars):
                if type(ov).__name__ != "DropVar":
                    self.producer[ov] = (node, out_idx)
        for node in self.nodes:
            seen = set()
            for a in node.invars:
                if not isinstance(a, Var):
                    continue
                self.consumers.setdefault(a, []).append(node)
                if a in self.producer:
                    op = self.producer[a][0]
                    if op.id not in seen:
                        seen.add(op.id)
                        node.operands.append(op)
                        op.users.append(node)
        self.compute_ranks()

    # -- queries ----------------------------------------------------------
    def total_flops(self) -> float:
        return float(sum(n.flops for n in self.nodes))

    def compute_intensive_nodes(self) -> List[GraphNode]:
        return [n for n in self.nodes if n.is_compute_intensive()]

    def arg_consumers(self, invar: Var) -> List[GraphNode]:
        return self.consumers.get(invar, [])

    def compute_ranks(self) -> None:
        """ASAP/ALAP levels (reference: GraphSketch rank computation)."""
        for n in self.nodes:  # nodes are in topological (program) order
            n.asap = 1 + max((op.asap for op in n.operands), default=-1)
        max_rank = max((n.asap for n in self.nodes), default=0)
        for n in reversed(self.nodes):
            n.alap = min((u.alap - 1 for u in n.users), default=max_rank)

    def var_aval(self, v) -> Any:
        return v.aval

    def __len__(self):
        return len(self.nodes)


def trace_graph(fn, *example_args, inline: bool = True, **example_kwargs):
    """Trace ``fn`` to a ``JaxprGraph`` plus the I/O pytree structure.

    This is the client's "emit HLO" step (reference: tf2xla bridge emitting
    the whole-graph HloModule) — but staying at jaxpr level keeps shape/dtype
    and primitive semantics that the planner's transfer functions need.
    """
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(
        *example_args, **example_kwargs
    )
    graph = JaxprGraph(closed, inline=inline)
    in_tree = jax.tree_util.tree_structure((example_args, example_kwargs))
    out_tree = jax.tree_util.tree_structure(out_shape)
    return graph, in_tree, out_tree
