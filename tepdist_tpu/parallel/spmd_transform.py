"""SPMD transform: lower planned strategies onto XLA GSPMD.

Reference parity: ``SpmdTransform`` (reference:
service/parallel/spmd_transform.{h,cc}, ~3.1k LoC) rewrote every HLO
instruction's shape by hand and inserted kCustomCollective nodes, which
``CustomCollectiveExpander`` later lowered to kDAPPLE collectives. On TPU both
jobs belong to the XLA SPMD partitioner: we emit
  * ``NamedSharding`` for every input and output, and
  * ``with_sharding_constraint`` at planner-decided interior anchor points
    (cone roots),
then let GSPMD perform the per-op rewrite and insert the ICI collectives
(all-reduce/all-gather/all-to-all/collective-permute). This replaces ~4k LoC
of per-opcode rewriting with the compiler path TPUs are designed for.

The transform works by re-interpreting the planner's inlined jaxpr with
constraints woven in — so the executed program is exactly the analyzed one.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
from jax.extend import core as jexcore
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tepdist_tpu.core.dist_spec import DimStrategy, TensorStrategy
from tepdist_tpu.core.mesh import MeshTopology
from tepdist_tpu.graph.jaxpr_graph import JaxprGraph
from tepdist_tpu.parallel.cost_spmd_strategy import GraphStrategy

Var = jexcore.Var
Literal = jexcore.Literal


def combine_axis_strategies(
    graph: JaxprGraph, strategies: Sequence[GraphStrategy]
) -> Dict[Var, TensorStrategy]:
    """Merge per-axis planning results into one TensorStrategy per var
    (vars covered: graph inputs + every node output)."""
    combined: Dict[Var, TensorStrategy] = {}

    def add(v: Var, axis: str, s: DimStrategy):
        combined.setdefault(v, TensorStrategy()).set(axis, s)

    for gs in strategies:
        for v, s in gs.var_strategies.items():
            add(v, gs.axis_name, s)
        for nid, outs in gs.node_out.items():
            node = graph.nodes[nid]
            for ov, s in zip(node.outvars, outs):
                if isinstance(ov, Var):
                    add(ov, gs.axis_name, s)
    return combined


# --------------------------------------------------------------------------
# Pallas kernels inside a multi-device program. XLA cannot partition a Mosaic
# kernel ("Mosaic kernels cannot be automatically partitioned. Please wrap the
# call in a shard_map"), and the planner prices ``pallas_call`` as opaque —
# operands and results replicated — so each kernel is bound under a shard_map
# whose specs are all replicated: every device runs the whole kernel on
# gathered operands. Kernels nested in control flow (the GA scan, scan over
# layers) are reached by re-tracing those bodies through the same binder.
# --------------------------------------------------------------------------

def _bodies(param) -> tuple:
    """The ClosedJaxprs a scan/while/cond param holds, else ()."""
    if isinstance(param, jexcore.ClosedJaxpr):
        return (param,)
    if (isinstance(param, tuple) and param
            and all(isinstance(b, jexcore.ClosedJaxpr) for b in param)):
        return param
    return ()


def _has_kernel(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return True
        if eqn.primitive.name == "shard_map":
            continue            # already manual: its kernels lower as they are
        if any(_has_kernel(b.jaxpr) for param in eqn.params.values()
               for b in _bodies(param)):
            return True
    return False


def _retrace_for_mesh(closed, mesh: Mesh):
    """The same body with its kernels bound for ``mesh``."""
    if not _has_kernel(closed.jaxpr):
        return closed
    avals = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype,
                                  weak_type=v.aval.weak_type)
             for v in closed.jaxpr.invars]

    def body(*args):
        env: Dict[Var, Any] = dict(zip(closed.jaxpr.constvars, closed.consts))
        env.update(zip(closed.jaxpr.invars, args))

        def read(a):
            return a.val if isinstance(a, Literal) else env[a]

        for eqn in closed.jaxpr.eqns:
            outs = bind_for_mesh(eqn, [read(a) for a in eqn.invars], mesh)
            env.update((ov, o) for ov, o in zip(eqn.outvars, outs)
                       if type(ov).__name__ != "DropVar")
        return [read(a) for a in closed.jaxpr.outvars]

    return jax.make_jaxpr(body)(*avals)


def _under_its_scopes(eqn) -> contextlib.ExitStack:
    """The name stack ``eqn`` was traced under, entered again. An equation
    bound anew takes the name stack of where it is bound, and the graph's own
    (``part_optimizer`` on the update, ``jvp(...)`` where a transform put it)
    would be lost to the compiled step's operation metadata, which is where a
    device trace finds the program's scopes (``models/layers.py:PARTS``)."""
    stack = contextlib.ExitStack()
    for name in filter(None, str(eqn.source_info.name_stack).split("/")):
        stack.enter_context(jax.named_scope(name))
    return stack


def bind_for_mesh(eqn, vals, mesh: Mesh) -> list:
    """``eqn.primitive.bind`` on ``vals``, as a list of outputs, with any
    pallas kernel in it (or in its bodies) bound for ``mesh``, under the
    scopes the equation was traced under."""
    with _under_its_scopes(eqn):
        return _bind_for_mesh(eqn, vals, mesh)


def _bind_for_mesh(eqn, vals, mesh: Mesh) -> list:
    # get_bind_params: staged params -> bindable form (how eval_jaxpr
    # re-binds pjit/shard_map/custom_* eqns).
    subfuns, params = eqn.primitive.get_bind_params(eqn.params)
    name = eqn.primitive.name
    if mesh.size > 1 and name == "pallas_call":
        rep = PartitionSpec()
        return list(jax.shard_map(
            lambda *operands: tuple(eqn.primitive.bind(*operands, **params)),
            mesh=mesh, in_specs=(rep,) * len(vals),
            out_specs=(rep,) * len(eqn.outvars),
            # pallas results carry no vma (same posture as ops/ulysses.py)
            check_vma=False)(*vals))
    if mesh.size > 1 and name != "shard_map":
        retraced = {
            key: (new if isinstance(param, tuple) else new[0])
            for key, param in params.items()
            if (new := tuple(_retrace_for_mesh(b, mesh)
                             for b in _bodies(param)))}
        params = {**params, **retraced}
    outs = eqn.primitive.bind(*subfuns, *vals, **params)
    return list(outs) if eqn.primitive.multiple_results else [outs]


@dataclasses.dataclass
class ShardingPlan:
    """Lowered plan: PartitionSpecs for I/O + interior constraint points."""

    topology: MeshTopology
    in_specs: List[PartitionSpec]              # one per jaxpr invar
    out_specs: List[Optional[PartitionSpec]]   # one per jaxpr outvar
    constraints: Dict[Var, PartitionSpec]      # interior anchors
    var_strategies: Dict[Var, TensorStrategy]
    # outvar idx -> invar idx threading (reference input_output_alias_map_);
    # these invars are safe to donate — the step replaces them.
    state_alias: Optional[Dict[int, int]] = None
    # (axis_name, motifs) pairs from seq-axis strategies: the executable
    # rewrites these eqn clusters into ops.ring_attention instead of
    # letting GSPMD all-gather K/V (parallel/attention_motif.py).
    motifs: Optional[List] = None

    def mesh(self, devices=None) -> Mesh:
        return self.topology.to_jax_mesh(devices)


class SpmdTransform:
    """Build a ShardingPlan and an executable sharded step function."""

    def __init__(self, graph: JaxprGraph, topology: MeshTopology):
        self.graph = graph
        self.topology = topology

    @staticmethod
    def _validate(ts: TensorStrategy, shape, axis_sizes) -> None:
        """Reject shardings GSPMD would pad or misplace: every split dim
        must exist and divide by the product of axis sizes on it (catches
        bad user annotations before an opaque compile error)."""
        per_dim = {}
        for axis, s in ts.strategies.items():
            if not s.is_split():
                continue
            d = s.partition_dim
            if d >= len(shape):
                raise ValueError(
                    f"annotation splits dim {d} of a rank-{len(shape)} "
                    f"tensor (axis {axis!r})")
            per_dim[d] = per_dim.get(d, 1) * axis_sizes.get(axis,
                                                            s.num_splits)
        for d, factor in per_dim.items():
            if shape[d] % factor:
                raise ValueError(
                    f"dim {d} (size {shape[d]}) not divisible by the "
                    f"combined mesh factor {factor}")

    def lower(self, strategies: Sequence[GraphStrategy],
              state_alias: Optional[Dict[int, int]] = None) -> ShardingPlan:
        """``state_alias``: outvar index -> invar index for training-state
        threading (reference input_output_alias_map_): the aliased output is
        forced to its input's sharding so step N's outputs feed step N+1
        without resharding."""
        combined = combine_axis_strategies(self.graph, strategies)
        sizes = {gs.axis_name: gs.num_splits for gs in strategies}
        in_specs = []
        for v in self.graph.invars:
            ts = combined.get(v, TensorStrategy())
            self._validate(ts, v.aval.shape, sizes)
            in_specs.append(ts.partition_spec(len(v.aval.shape)))
        out_specs: List[Optional[PartitionSpec]] = []
        for a in self.graph.outvars:
            if isinstance(a, Var) and a in combined:
                ts = combined[a]
                if ts.has_partial():
                    # psum inserted by GSPMD; the materialized output is
                    # replicated along the partial axes.
                    ts = TensorStrategy({
                        ax: s for ax, s in ts.strategies.items() if not s.partial
                    })
                out_specs.append(ts.partition_spec(len(a.aval.shape)))
            else:
                out_specs.append(None)
        for oi, ii in (state_alias or {}).items():
            if oi < len(out_specs):
                out_specs[oi] = in_specs[ii]
        constraints: Dict[Var, PartitionSpec] = {}
        for node in self.graph.nodes:
            if not node.is_compute_intensive():
                continue
            for ov in node.outvars:
                if not isinstance(ov, Var) or ov not in combined:
                    continue
                ts = combined[ov]
                if ts.has_partial():
                    continue  # partial values are GSPMD's to resolve
                spec = ts.partition_spec(len(ov.aval.shape))
                if spec != PartitionSpec():
                    constraints[ov] = spec
        motif_axes = [(gs.axis_name, gs.motifs) for gs in strategies
                      if getattr(gs, "motifs", None)]
        return ShardingPlan(
            topology=self.topology,
            in_specs=in_specs,
            out_specs=out_specs,
            constraints=constraints,
            var_strategies=combined,
            state_alias=dict(state_alias) if state_alias else None,
            motifs=motif_axes or None,
        )

    # ------------------------------------------------------------------
    def executable(
        self,
        plan: ShardingPlan,
        mesh: Optional[Mesh] = None,
        donate_invars: Sequence[int] = (),
        constrain_interior: bool = True,
    ) -> Callable:
        """JIT the planned program with GSPMD shardings.

        Returns a function over FLAT invars (same order as
        ``graph.invars``) returning flat outputs — runtime layers wrap
        pytrees around it."""
        mesh = mesh or plan.mesh()
        jaxpr = self.graph.jaxpr
        consts = list(self.graph.closed.consts)
        constraints = {
            v: NamedSharding(mesh, spec)
            for v, spec in (plan.constraints.items() if constrain_interior else ())
        }
        # Seq-axis motif rewrites: skip the softmax(QK^T)V eqn clusters and
        # emit ring attention at the PV dot (K/V stay sequence-sharded).
        skip_ids: set = set()
        at_pv: Dict[int, Any] = {}
        for axis_name, motifs in (plan.motifs or ()):
            for m in motifs:
                skip_ids |= m.member_ids
                at_pv[m.pv_id] = (axis_name, m)

        # The function's name is the program's in a device trace
        # (``jit_tepdist_train_step`` on the ``XLA Modules`` line).
        def tepdist_train_step(*flat_args):
            env: Dict[Var, Any] = {}

            def read(a):
                if isinstance(a, Literal):
                    return a.val
                return env[a]

            def write(v, val):
                sh = constraints.get(v)
                if sh is not None:
                    val = jax.lax.with_sharding_constraint(val, sh)
                env[v] = val

            for cv, c in zip(jaxpr.constvars, consts):
                write(cv, c)
            for iv, a in zip(jaxpr.invars, flat_args):
                write(iv, a)
            for i, eqn in enumerate(jaxpr.eqns):
                if i in at_pv:
                    axis_name, m = at_pv[i]
                    from tepdist_tpu.parallel.attention_motif import (
                        bind_motif_outputs,
                        lower_motif_call,
                    )
                    o, lse = lower_motif_call(
                        m, mesh, axis_name, read(m.q), read(m.k), read(m.v))
                    bind_motif_outputs(m, eqn.outvars, o, lse, write)
                    continue
                if i in skip_ids:
                    continue
                outs = bind_for_mesh(
                    eqn, [read(a) for a in eqn.invars], mesh)
                for ov, val in zip(eqn.outvars, outs):
                    if type(ov).__name__ != "DropVar":
                        write(ov, val)
            return tuple(read(a) for a in jaxpr.outvars)

        in_shardings = tuple(NamedSharding(mesh, s) for s in plan.in_specs)
        out_shardings = tuple(
            NamedSharding(mesh, s) if s is not None else None
            for s in plan.out_specs
        )
        return jax.jit(
            tepdist_train_step,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            donate_argnums=tuple(donate_invars),
        )
