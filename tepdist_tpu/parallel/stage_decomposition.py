"""Stage decomposition: physically split the forward graph into per-stage
modules and build the pipelined training step.

Reference parity: ``StageDecomposition`` (reference:
service/parallel/stage_decomposition.{h,cc}) splits CG/GA/GAInit/AG
computations into ``*_SLICE`` DefContexts per pipeline stage and wires
``input_def_map_`` (arg <- (prev_stage, out_idx)) across stages. Here the
split operates on the forward jaxpr: each ``StageModule`` carries its
equation slice, its external inputs (graph args + activations), and an
``input_def_map`` identical in role to the reference's.

Backward stages are NOT carved from a traced backward graph (the reference
mirrors the forward plan; we get the mirror for free): stage i's backward is
``jax.vjp`` of stage i's forward module, which recomputes the stage forward
inside the backward (activation rematerialization — the standard TPU PP
memory trade, cf. jax.checkpoint) and emits cotangents for exactly the
activation edges ``input_def_map`` records.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from jax.extend import core as jexcore

from tepdist_tpu.graph.jaxpr_graph import JaxprGraph

Var = jexcore.Var
Literal = jexcore.Literal


@dataclasses.dataclass
class StageModule:
    """One pipeline stage of the forward graph (a *_SLICE DefContext)."""

    stage_id: int
    eqns: List[Any]
    invars: List[Var]                 # external inputs, fixed order
    outvars: List[Var]                # produced here, consumed downstream
    # arg position -> ("arg", graph invar index) | ("stage", src_stage, out_idx)
    input_def_map: Dict[int, Tuple] = dataclasses.field(default_factory=dict)
    # graph outvar index -> position in self.outvars
    graph_out_map: Dict[int, int] = dataclasses.field(default_factory=dict)

    def param_positions(self) -> List[int]:
        return [i for i, src in self.input_def_map.items() if src[0] == "arg"]

    def activation_positions(self) -> List[int]:
        return [i for i, src in self.input_def_map.items() if src[0] == "stage"]


def _run_eqns(eqns, invars: Sequence[Var], constmap: Dict[Var, Any],
              outvars: Sequence[Var]) -> Callable:
    """Build a callable evaluating an equation slice (jit-friendly)."""

    def fn(*args):
        env: Dict[Var, Any] = dict(constmap)
        for v, a in zip(invars, args):
            env[v] = a

        def read(a):
            if isinstance(a, Literal):
                return a.val
            return env[a]

        for eqn in eqns:
            vals = [read(a) for a in eqn.invars]
            outs = eqn.primitive.bind(*vals, **eqn.params)
            if not eqn.primitive.multiple_results:
                outs = [outs]
            for ov, val in zip(eqn.outvars, outs):
                if type(ov).__name__ != "DropVar":
                    env[ov] = val
        return tuple(env[v] for v in outvars)

    return fn


class StageDecomposition:
    """Split a (forward) JaxprGraph by a per-node stage assignment."""

    def __init__(self, graph: JaxprGraph, stage_assignment: Sequence[int],
                 num_stages: int):
        self.graph = graph
        self.assignment = list(stage_assignment)
        self.num_stages = num_stages
        self.stages: List[StageModule] = []
        self._const_env: Dict[Var, Any] = dict(
            zip(graph.jaxpr.constvars, graph.closed.consts))
        self._build()

    def _build(self) -> None:
        g = self.graph
        invar_index = {v: i for i, v in enumerate(g.invars)}
        produced_by: Dict[Var, Tuple[int, int]] = {}  # var -> (stage, out_idx)
        graph_out_index: Dict[Var, List[int]] = {}
        for oi, a in enumerate(g.outvars):
            if isinstance(a, Var):
                graph_out_index.setdefault(a, []).append(oi)

        for s in range(self.num_stages):
            eqns = [n.eqn for n in g.nodes if self.assignment[n.id] == s]
            produced_here = set()
            for eqn in eqns:
                for ov in eqn.outvars:
                    if type(ov).__name__ != "DropVar":
                        produced_here.add(ov)
            # External inputs in first-use order.
            invars: List[Var] = []
            seen = set()
            for eqn in eqns:
                for a in eqn.invars:
                    if (isinstance(a, Var) and a not in produced_here
                            and id(a) not in seen
                            and a not in self._const_env):
                        seen.add(id(a))
                        invars.append(a)
            module = StageModule(stage_id=s, eqns=eqns, invars=invars,
                                 outvars=[])
            for pos, v in enumerate(invars):
                if v in invar_index:
                    module.input_def_map[pos] = ("arg", invar_index[v])
                elif v in produced_by:
                    src_stage, out_idx = produced_by[v]
                    module.input_def_map[pos] = ("stage", src_stage, out_idx)
                else:
                    raise ValueError(
                        f"stage {s} input {v} produced by a LATER stage — "
                        "stage assignment violates precedence")
            # Outputs: consumed by later stages or graph outputs.
            later_consumers = set()
            for n in g.nodes:
                if self.assignment[n.id] > s:
                    for a in n.eqn.invars:
                        if isinstance(a, Var):
                            later_consumers.add(a)
            for eqn in eqns:
                for ov in eqn.outvars:
                    if type(ov).__name__ == "DropVar":
                        continue
                    if ov in later_consumers or ov in graph_out_index:
                        out_idx = len(module.outvars)
                        module.outvars.append(ov)
                        produced_by[ov] = (s, out_idx)
                        for oi in graph_out_index.get(ov, []):
                            module.graph_out_map[oi] = out_idx
            self.stages.append(module)

    # ------------------------------------------------------------------
    def stage_fn(self, s: int) -> Callable:
        m = self.stages[s]
        return _run_eqns(m.eqns, m.invars, self._const_env, m.outvars)

    def forward_fns(self) -> List[Callable]:
        return [self.stage_fn(s) for s in range(self.num_stages)]

    def stage_closed_jaxpr(self, s: int):
        """Package stage ``s`` as a standalone ClosedJaxpr (the wire form of
        a def-module for TransferModuleAndDefCtx)."""
        from jax._src import core as _core
        from jax.extend import core as jexcore

        m = self.stages[s]
        used_consts = []
        seen = set()
        for eqn in m.eqns:
            for a in eqn.invars:
                if (isinstance(a, Var) and a in self._const_env
                        and id(a) not in seen):
                    seen.add(id(a))
                    used_consts.append(a)
        jaxpr = _core.Jaxpr(constvars=used_consts, invars=list(m.invars),
                            outvars=list(m.outvars), eqns=list(m.eqns))
        consts = [self._const_env[v] for v in used_consts]
        return jexcore.ClosedJaxpr(jaxpr, consts)

    def cross_stage_bytes(self) -> float:
        """Activation traffic of the cut (reference CollectCrossStageInsts)."""
        from tepdist_tpu.graph.cost import aval_bytes
        total = 0.0
        for m in self.stages:
            for pos in m.activation_positions():
                total += aval_bytes(m.invars[pos].aval)
        return total
