"""AutoParallel: the driver pass tying tracer → planner → SPMD transform.

Reference parity: ``AutoParallel::Run`` (reference:
service/parallel/auto_parallel.cc:395) with its three modes:
  * rule mode  (``RULE_MODE``)  → FastSpmdStrategy annotation sweep
  * config mode                 → fixed mesh from the caller, cost planner
  * exploration mode            → enumerate mesh-shape proposals
    (``GenerateSplitProposals``, auto_parallel.cc:132), plan each, keep the
    evaluator-minimal one.

Output is a ``ParallelPlan``: the sharded, jitted training step plus the
full annotation record (the analogue of DistSpec-decorated HLO + DefContext
tree, which later stages — pipeline decomposition, runtime — consume).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
from jax.extend import core as jexcore

from tepdist_tpu.core.dist_spec import DimStrategy, TensorStrategy
from tepdist_tpu.core.mesh import MeshTopology
from tepdist_tpu.core.service_env import ServiceEnv
from tepdist_tpu.graph.jaxpr_graph import JaxprGraph, trace_graph
from tepdist_tpu.parallel.cost_spmd_strategy import CostSpmdStrategy, GraphStrategy
from tepdist_tpu.parallel.fast_spmd_strategy import FastSpmdStrategy
from tepdist_tpu.parallel.spmd_transform import ShardingPlan, SpmdTransform
from tepdist_tpu.telemetry import span

Var = jexcore.Var
log = logging.getLogger(__name__)


@dataclasses.dataclass
class ParallelPlan:
    """A planned + lowered training step."""

    graph: JaxprGraph
    topology: MeshTopology
    strategies: List[GraphStrategy]
    sharding_plan: ShardingPlan
    in_tree: Any
    out_tree: Any
    mode: str
    # The exploration winner's comm-dtype modifier (""/"float32" =
    # fidelity; "bfloat16"/"int8" = compressed gradient collectives).
    # Consumed by train.plan_training when it rebuilds the GA step and by
    # the RPC dispatch plumbing; the plan's OWN jit is dtype-agnostic.
    comm_dtype: str = ""
    # ZeRO weight-update sharding (arXiv:2004.13336): True when the
    # optimizer-state invars were force-split over the data axis
    # (apply_zero_sharding) so GSPMD emits reduce-scatter + sharded apply
    # + updated-param all-gather. Consumed by train.plan_training (state
    # placement + checkpointing) and the plan_meta fleet plumbing.
    zero: bool = False

    _flat_cache: Any = None     # donate tuple -> jitted flat step fn
    _mesh: Any = None

    def mesh(self, devices=None):
        if self._mesh is None:
            self._mesh = self.topology.to_jax_mesh(devices)
        return self._mesh

    def executable(self, devices=None, donate_invars: Sequence[int] = ()):
        """Flat-args jitted step (order = jaxpr invars). Cached per
        donation set — a donating and a non-donating caller must not share
        one jitted fn (the first caller's choice would silently stick)."""
        key = tuple(sorted(donate_invars))
        if self._flat_cache is None:
            self._flat_cache = {}
        if key not in self._flat_cache:
            xform = SpmdTransform(self.graph, self.topology)
            self._flat_cache[key] = xform.executable(
                self.sharding_plan, self.mesh(devices),
                donate_invars=key)
        return self._flat_cache[key]

    def lowering_diagnostics(self, devices=None,
                             donate_invars: Optional[Sequence[int]] = None
                             ) -> List[str]:
        """AOT-compile the plan and return the HLO ops XLA flagged with
        'Involuntary full rematerialization' — the device-order pathology
        no pre-lowering cost model can price (parallel/lowering_check.py).
        [] == cleanly shardable. Compiles the SAME jit the trainer uses
        (state-donating by default), so the diagnostic compile is cached
        and the first real step pays nothing extra."""
        from tepdist_tpu.parallel.lowering_check import involuntary_remats

        if donate_invars is None:
            donate_invars = self.state_donation()
        fn = self.executable(devices=devices, donate_invars=donate_invars)
        args = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                for v in self.graph.invars]
        return involuntary_remats(fn, args)

    def state_donation(self) -> Tuple[int, ...]:
        """Invar indices safe to donate when the caller threads the aliased
        state (outputs replace these inputs): without donation the training
        state is double-buffered every step — at GPT-2 1.5B scale that is
        the difference between fitting a 16 GB chip and OOM. Honors
        DISABLE_BUFFER_ALIAS."""
        from tepdist_tpu.core.service_env import ServiceEnv
        if ServiceEnv.get().disable_buffer_alias:
            return ()
        alias = self.sharding_plan.state_alias or {}
        return tuple(sorted({ii for ii in alias.values() if ii >= 0}))

    def step(self, *args, **kwargs):
        """Pytree-level convenience wrapper around the flat executable."""
        flat, tree = jax.tree_util.tree_flatten((args, kwargs))
        outs = self.executable()(*flat)
        return jax.tree_util.tree_unflatten(self.out_tree, list(outs))

    def input_shardings(self, devices=None):
        from jax.sharding import NamedSharding
        m = self.mesh(devices)
        return [NamedSharding(m, s) for s in self.sharding_plan.in_specs]


def _resolve_fixed(
    graph: JaxprGraph,
    annotations: Optional[Dict[int, Dict[str, DimStrategy]]],
) -> Dict[str, Dict[Var, DimStrategy]]:
    """annotations: flat-arg-index -> {axis: DimStrategy} → per-axis maps."""
    per_axis: Dict[str, Dict[Var, DimStrategy]] = {}
    for idx, spec in (annotations or {}).items():
        v = graph.invars[idx]
        for axis, s in spec.items():
            per_axis.setdefault(axis, {})[v] = s
    return per_axis


def plan_axes(
    graph: JaxprGraph,
    topology: MeshTopology,
    annotations: Optional[Dict[int, Dict[str, DimStrategy]]] = None,
    mode: str = "cost",
    mem_limit_bytes: Optional[float] = None,
) -> List[GraphStrategy]:
    """Run the per-axis planner sequence (reference: per-mesh-level
    CostSpmdStrategy loop in RunExplorationlMode step 2).

    ``mem_limit_bytes``: per-device storage budget enforced INSIDE the
    cost ILP (reference SplitPlanByMemCost integrated into the search) —
    variable sharding (ZeRO/TP) emerges where replication would not fit,
    with split dims chosen by the gather costs already in the objective.
    Applies to the cost mode's whole-graph ILP; the subgraph-DP and greedy
    paths fall back to the post-hoc ``apply_mem_save``."""
    fixed_per_axis = _resolve_fixed(graph, annotations)
    strategies: List[GraphStrategy] = []
    forbidden: Dict[Var, set] = {}
    prior_splits: Dict[Var, int] = {}
    # Annotation pins RESERVE their tensor dim against every OTHER axis up
    # front: an earlier-planned axis must not take a dim a later axis's
    # annotation will pin (e.g. the data axis ZeRO-splitting expert-weight
    # dim 0 that the expert annotation owns — the combined factor would
    # overrun the dim).
    planned_axes = {n for n, sz in topology.device_axes() if sz > 1}
    pinned: Dict[Var, Dict[str, int]] = {}
    for ax_name, fx in fixed_per_axis.items():
        if ax_name not in planned_axes:
            continue    # a size-1 axis never materialises its pin
        for v, s in fx.items():
            if s.is_split():
                pinned.setdefault(v, {})[ax_name] = s.partition_dim
    for name, size in topology.device_axes():
        if size <= 1:
            continue
        fixed = fixed_per_axis.get(name, {})
        axis_forbidden = {v: set(d) for v, d in forbidden.items()}
        for v, by_axis in pinned.items():
            reserved = {d for ax, d in by_axis.items() if ax != name}
            if reserved:
                axis_forbidden[v] = axis_forbidden.get(v, set()) | reserved
        if name == "seq":
            # Reserved: the sequence axis is owned by the ring-attention
            # rewrite (parallel/attention_motif.py). When the graph still
            # carries closed motifs (forward graph) a seq GraphStrategy
            # prices + propagates it and the SPMD transform rewrites the
            # motifs; on an already-rewritten graph the shard_map anchors
            # own the sharding and the axis is skipped.
            from tepdist_tpu.parallel.attention_motif import (
                build_seq_strategy,
                detect_motifs,
            )
            motifs = detect_motifs(graph)
            if not motifs:
                if any(n.prim == "shard_map" for n in graph.nodes):
                    # Already-rewritten graph: the shard_map anchors own
                    # the seq sharding — nothing left to plan here.
                    continue
                raise ValueError(
                    "topology requests a 'seq' axis but the graph has no "
                    "rewritable attention motif (grad graphs hide the "
                    "motif — plan via plan_training, which rewrites "
                    "attention BEFORE differentiation)")
            gs = build_seq_strategy(graph, size, motifs)
        elif mode == "rule":
            gs = FastSpmdStrategy(graph, name, size, fixed).run()
        else:
            gs = CostSpmdStrategy(
                graph, name, size, fixed=fixed,
                forbidden_dims=axis_forbidden,
                mem_limit_bytes=mem_limit_bytes,
                prior_var_splits=prior_splits,
            ).run()
        strategies.append(gs)
        # Later axes may not re-split dims this axis already split.
        for v, s in gs.var_strategies.items():
            if s.is_split():
                forbidden.setdefault(v, set()).add(s.partition_dim)
                prior_splits[v] = prior_splits.get(v, 1) * s.num_splits
    return strategies


def apply_mem_save(
    graph: JaxprGraph,
    strategies: List[GraphStrategy],
    topology: MeshTopology,
    var_mem_limit: int,
    state_invars: Optional[Sequence[int]] = None,
) -> List[int]:
    """ZeRO-style variable splitting for memory (reference:
    ``SplitPlanByMemCost``/``MemSavePlan``, cost_spmd_strategy.h:900-911 +
    the ``VAR_MEM_LIMIT`` env): while per-device variable bytes exceed the
    limit, force-shard the largest still-replicated state variable's storage
    along the biggest mesh axis. GSPMD inserts the gathers where compute
    needs the full value. Returns the invar indices that were split.

    The split DIM is chosen by gather cost, not size (reference integrates
    mem-save into the cost search — SplitPlanByMemCost's per-dim cost
    terms): for each divisible dim, every consumer equation is checked for
    whether a storage split on that dim flows through consistently with the
    planner's already-chosen strategies (StrategyUtil.forward_infer seeded
    with the trial split + the plan's strategies for the other operands).
    Consumers the split flows through cost nothing; every other consumer
    costs the all-gather GSPMD must insert. Ties break to the largest dim."""
    from tepdist_tpu.graph.cost import aval_bytes

    if not strategies:
        return []
    # Shard over the largest device axis (usually 'data' — ZeRO semantics).
    gs = max(strategies, key=lambda g: g.num_splits)
    n = gs.num_splits
    candidates = (list(state_invars) if state_invars is not None
                  else range(len(graph.invars)))

    def per_device_bytes() -> float:
        total = 0.0
        for i in candidates:
            v = graph.invars[i]
            b = aval_bytes(v.aval)
            for g in strategies:
                s = g.var_strategies.get(v)
                if s is not None and s.is_split():
                    b /= s.num_splits
            total += b
        return total

    split: List[int] = []
    order = sorted(
        candidates,
        key=lambda i: -aval_bytes(graph.invars[i].aval))
    for i in order:
        if per_device_bytes() <= var_mem_limit:
            break
        v = graph.invars[i]
        cur = gs.var_strategies.get(v)
        if cur is not None and cur.is_split():
            continue
        shape = v.aval.shape
        # Dims another axis already splits are off-limits (one mesh axis
        # per tensor dim).
        taken = {g.var_strategies[v].partition_dim for g in strategies
                 if g is not gs and (s := g.var_strategies.get(v)) is not None
                 and s.is_split()}
        best = None
        for d in range(len(shape)):
            if d in taken or shape[d] % n or shape[d] < n:
                continue
            c = _mem_save_dim_cost(graph, gs, v, d, n)
            key = (c, -shape[d])
            if best is None or key < best[0]:
                best = (key, d)
        if best is not None:
            gs.var_strategies[v] = DimStrategy.split_on(best[1], n)
            split.append(i)
    return split


def _mem_save_dim_cost(graph: JaxprGraph, gs: GraphStrategy, v: Var,
                       d: int, n: int) -> float:
    """Gather traffic a storage split of ``v`` on dim ``d`` would cause,
    given the consumer demands the planner already fixed (VERDICT r1 weak
    #7: the dim choice must not be cost-blind)."""
    from tepdist_tpu.graph.cost import aval_bytes
    from tepdist_tpu.parallel.performance_utils import PerfUtils, chip_spec
    from tepdist_tpu.parallel.strategy_utils import StrategyUtil

    spec = chip_spec()
    gather = PerfUtils.all_gather_cost(aval_bytes(v.aval), n, spec)
    trial = DimStrategy.split_on(d, n)
    total = 0.0
    for node in graph.consumers.get(v, []):
        eqn = node.eqn
        known = {}
        for idx, a in enumerate(eqn.invars):
            if a is v:
                known[idx] = trial
            elif isinstance(a, Var):
                s = gs.var_strategies.get(a)
                if s is not None and not s.is_glue():
                    known[idx] = s
        res = StrategyUtil.forward_infer(eqn, known, n)
        flows = res is not None
        if flows:
            for ov, s_out in zip(eqn.outvars, res.out_strategies):
                chosen = gs.var_strategies.get(ov)
                if (chosen is not None and s_out is not None
                        and chosen != s_out):
                    flows = False
                    break
        if not flows:
            total += gather
    return total


def apply_zero_sharding(
    graph: JaxprGraph,
    strategies: List[GraphStrategy],
    topology: MeshTopology,
    zero_invars: Sequence[int],
    axis: str = "data",
) -> List[int]:
    """ZeRO-1 realization for the single-jit SPMD path (ISSUE 14,
    arXiv:2004.13336): force-split the OPTIMIZER-STATE invars over the
    data axis in their ORIGINAL shapes. With ``state_alias`` forcing
    out := in specs, GSPMD then lowers the apply as the ZeRO update —
    the gradient psum's output is consumed sliced (reduce-scatter), the
    elementwise optimizer update runs on the local shard only, and the
    updated params (whose storage stays replicated) all-gather.

    Original shapes — NOT a (dp, chunk) re-layout — so the shard extents
    are natural NamedSharding slices: CheckpointUtil writes them as
    ``::shard`` entries and ``restore_resharded`` can reassemble onto ANY
    DP width (a padded flat layout would make the global length
    dp-dependent and break cross-width restore).

    Returns the invar indices actually split (leaves with no dim
    divisible by dp — scalars like Adam's step count — stay replicated;
    they are O(bytes) irrelevant)."""
    axis_names = [nm for nm, sz in topology.device_axes() if sz > 1]
    if axis not in axis_names:
        return []
    gs = strategies[axis_names.index(axis)]
    n = gs.num_splits
    split: List[int] = []
    for i in zero_invars:
        v = graph.invars[i]
        cur = gs.var_strategies.get(v)
        if cur is not None and cur.is_split():
            split.append(i)
            continue   # planner/mem-save already sharded it — same effect
        shape = v.aval.shape
        taken = {s.partition_dim for g in strategies if g is not gs
                 if (s := g.var_strategies.get(v)) is not None
                 and s.is_split()}
        best = None
        for d in range(len(shape)):
            if d in taken or shape[d] % n or shape[d] < n:
                continue
            c = _mem_save_dim_cost(graph, gs, v, d, n)
            key = (c, -shape[d])
            if best is None or key < best[0]:
                best = (key, d)
        if best is not None:
            gs.var_strategies[v] = DimStrategy.split_on(best[1], n)
            split.append(i)
    return split


def align_state_storage(
    graph: JaxprGraph,
    strategies: List[GraphStrategy],
    state_alias: Dict[int, int],
) -> int:
    """Align variable STORAGE shardings with the strategy their updated
    value is naturally produced in.

    ``state_alias`` forces out spec := in spec for training-state threading
    (SpmdTransform). When the planner leaves a variable replicated but its
    update is computed sharded, that forcing inserts an all-gather of the
    updated parameters EVERY step. Adopting the produced sharding as the
    storage sharding removes the gather and shards the optimizer state
    (ZeRO-flavored — the reference's mem-save direction, here driven by
    consistency rather than a memory limit). Returns #vars realigned."""
    changed = 0
    for gs in strategies:
        for oi, ii in state_alias.items():
            if oi >= len(gs.out_strategies) or ii < 0:
                continue
            out_s = gs.out_strategies[oi]
            a = graph.outvars[oi]
            if out_s is None or not out_s.is_split():
                continue
            v = graph.invars[ii]
            cur = gs.var_strategies.get(v)
            if cur is not None and cur.is_split():
                continue  # planner chose a storage split already
            shape = v.aval.shape
            # Dims another axis already splits are off-limits (one mesh
            # axis per tensor dim — adopting dim 0 here while the expert
            # axis pins dim 0 would overrun the dim with the combined
            # factor).
            taken = {s.partition_dim for g in strategies if g is not gs
                     if (s := g.var_strategies.get(v)) is not None
                     and s.is_split()}
            if (out_s.partition_dim < len(shape)
                    and out_s.partition_dim not in taken
                    and shape[out_s.partition_dim] % out_s.num_splits == 0):
                gs.var_strategies[v] = out_s
                changed += 1
    return changed


def auto_parallel(
    fn: Callable,
    topology: MeshTopology,
    *example_args,
    annotations: Optional[Dict[int, Dict[str, DimStrategy]]] = None,
    mode: Optional[str] = None,
    state_alias: Optional[Dict[int, int]] = None,
    var_mem_limit: Optional[int] = None,
    zero_invars: Optional[Sequence[int]] = None,
    **example_kwargs,
) -> ParallelPlan:
    """Plan ``fn`` over ``topology``. Modes: "cost" (default), "rule".

    ``state_alias``: outvar flat index -> invar flat index for training-state
    threading (forces matching shardings across steps). ``var_mem_limit``
    (or the VAR_MEM_LIMIT env): per-device variable-byte budget triggering
    ZeRO-style storage splitting. ``zero_invars``: flat invar indices of
    the OPTIMIZER-STATE leaves to force-shard over the data axis
    (``apply_zero_sharding`` — the exploration winner's ``@zero``
    modifier realized by the planner)."""
    env = ServiceEnv.get()
    if mode is None:
        mode = "rule" if env.rule_mode else "cost"
    if env.ignore_annotation:
        annotations = None
    with span("plan:trace", cat="planner"):
        graph, in_tree, out_tree = trace_graph(fn, *example_args,
                                               **example_kwargs)
    if var_mem_limit is None and env.var_mem_limit > 0:
        var_mem_limit = env.var_mem_limit
    with span("plan:search", cat="planner"):
        strategies, zero_split = _search(
            graph, topology, annotations, mode, state_alias, var_mem_limit,
            zero_invars, env)
    with span("plan:lower", cat="planner"):
        sharding_plan = SpmdTransform(graph, topology).lower(
            strategies, state_alias=state_alias)
    return ParallelPlan(
        graph=graph,
        topology=topology,
        strategies=strategies,
        sharding_plan=sharding_plan,
        in_tree=in_tree,
        out_tree=out_tree,
        mode=mode,
        zero=bool(zero_split),
    )


def _search(graph, topology, annotations, mode, state_alias, var_mem_limit,
            zero_invars, env) -> Tuple[List[GraphStrategy], List[int]]:
    """``auto_parallel``'s strategy search: ``plan_axes`` and the post-passes
    on its result. Returns (strategies, the ZeRO-split invars)."""
    strategies = plan_axes(graph, topology, annotations, mode,
                           mem_limit_bytes=var_mem_limit)
    if state_alias:
        n_aligned = align_state_storage(graph, strategies, state_alias)
        if n_aligned:
            log.info("aligned %d state variables to their produced sharding",
                     n_aligned)
    state_invars = sorted({ii for ii in (state_alias or {}).values()
                           if ii >= 0})
    if var_mem_limit is not None and var_mem_limit > 0:
        # Safety net for plans from the subgraph-DP/greedy paths (the
        # whole-graph ILP already enforced the budget in-search and this
        # becomes a no-op there).
        apply_mem_save(graph, strategies, topology, var_mem_limit,
                       state_invars or None)
    # Param <-> optimizer-slot affinity: slots adopt their param's sharding
    # (reference AUX_AFFINITY) so the apply step never reshards.
    if state_alias and env.aux_affinity:
        from tepdist_tpu.parallel.inst_affinity import (
            build_affinity_groups,
            unify_group_strategies,
        )
        try:
            groups = build_affinity_groups(graph, state_alias)
            unify_group_strategies(graph, strategies, groups)
        except Exception as e:  # noqa: BLE001 — affinity is an optimization
            log.warning("affinity unification skipped: %s", e)
    zero_split: List[int] = []
    if zero_invars:
        # After affinity unification on purpose: ZeRO-1 wants the state
        # slots SPLIT while params stay replicated, the opposite of the
        # slots-adopt-param-sharding affinity default.
        zero_split = apply_zero_sharding(graph, strategies, topology,
                                         zero_invars)
        log.info("ZeRO: sharded %d/%d optimizer-state invars over the "
                 "data axis", len(zero_split), len(zero_invars))
    return strategies, zero_split


def auto_parallel_explore(
    fn: Callable,
    num_devices: int,
    *example_args,
    annotations: Optional[Dict[int, Dict[str, DimStrategy]]] = None,
    state_alias: Optional[Dict[int, int]] = None,
    num_micro_batches: int = 1,
    devices=None,
    **example_kwargs,
) -> Any:
    """Exploration mode (reference: AutoParallel::RunExplorationlMode,
    auto_parallel.cc:236): enumerate proposals, plan each, keep the
    Evaluator-minimal one — over the UNIFIED candidate space
    (parallel/exploration.py), the same one ``train.plan_training`` and
    the service's explore mode search.

    When ``fn`` is a scalar-output loss of the form ``fn(params, *batch)``,
    the space includes sequence-parallel meshes (priced with the
    ring/Ulysses attention cost) and pipeline stage cuts; a pipeline
    winner is returned as a :class:`~tepdist_tpu.parallel.exploration.
    PipelineWinner` (call ``.build(optimizer)`` for the executable).
    Non-scalar ``fn`` (e.g. an explicit grad fn) searches mesh
    factorizations only — stage cuts need loss semantics.

    SPMD/seq winners come back as a lowered :class:`ParallelPlan` with
    ``.cost`` and ``.candidates`` attached."""
    from tepdist_tpu.parallel.exploration import (
        PipelineWinner,
        pipeline_candidates,
        seq_candidates,
        spmd_candidates,
        winner_lowering_postcheck,
    )
    from tepdist_tpu.parallel.spmd_transform import SpmdTransform as _Xform

    graph, in_tree, out_tree = trace_graph(fn, *example_args,
                                           **example_kwargs)
    scalar_loss = (not example_kwargs and len(graph.outvars) == 1
                   and graph.outvars[0].aval.shape == ()
                   and len(example_args) >= 2)
    # Price on the TRUE step graph: for a scalar loss the executed step is
    # grad(fn), and the pipeline/seq candidates already price fwd+bwd —
    # ranking SPMD candidates on the forward-only graph would bias the
    # argmin toward SPMD (its compute would omit the backward ~2/3 and
    # every gradient reduce).
    if scalar_loss:
        price_graph, _, _ = trace_graph(jax.value_and_grad(fn),
                                        *example_args)
    else:
        price_graph = graph
    # This entry point calls the enumerators directly (it lowers its own
    # winner), so it opens its own observatory capture — the report
    # lands on the returned plan as ``plan.exploration_report``.
    from tepdist_tpu.telemetry import observatory
    import time as _time

    with observatory.capture("auto_parallel_explore") as _col:
        _t0 = _time.perf_counter()
        candidates = spmd_candidates(price_graph, num_devices, annotations,
                                     num_micro_batches)
        if _col is not None:
            _col.phase("spmd", _time.perf_counter() - _t0)
        if scalar_loss:
            params, *batch = example_args
            batch_rows = jax.tree_util.tree_leaves(batch)[0].shape[0]
            _t0 = _time.perf_counter()
            candidates += seq_candidates(price_graph, num_devices,
                                         batch_rows)
            if _col is not None:
                _col.phase("seq", _time.perf_counter() - _t0)
            _t0 = _time.perf_counter()
            candidates += pipeline_candidates(
                fn, params, tuple(batch), num_devices, batch_rows,
                num_micro_batches if num_micro_batches > 1 else 4)
            if _col is not None:
                _col.phase("pipeline", _time.perf_counter() - _t0)
    excluded = [] if scalar_loss else ["seq", "pipeline"]
    if not candidates:
        raise RuntimeError("no feasible topology proposal")

    fallbacks = []
    for best in sorted(candidates, key=lambda c: c["cost"].key()):
        try:
            plan = _materialize_explored(
                best, fn, graph, in_tree, out_tree, example_args,
                example_kwargs, annotations, state_alias, devices,
                price_graph is graph, _Xform, PipelineWinner, candidates)
        except Exception as e:  # noqa: BLE001 — fall to the runner-up
            log.warning("winner %s failed to materialize (%s); trying "
                        "the runner-up", best.get("topology", best["kind"]),
                        e)
            fallbacks.append({
                "config": observatory.candidate_config(best),
                "exc_type": type(e).__name__, "message": str(e)[:300]})
            continue
        log.info("exploration winner: %s (duration %.3e s/step) of %d "
                 "proposals", best["kind"], best["cost"].total_duration,
                 len(candidates))
        if _col is not None:
            report = observatory.build_report(
                _col, candidates, best, num_devices,
                excluded_kinds=excluded).to_dict()
            if fallbacks:
                # The cost-minimal proposal(s) that could not be
                # lowered: the report's winner is the argmin over what
                # MATERIALIZED, and the skips are on the record.
                report["materialization_fallbacks"] = fallbacks
            plan.exploration_report = report
        if not isinstance(plan, PipelineWinner):
            # Winner-only lowering post-check (NOTES_NEXT gap #2): pipeline
            # winners have no single lowered jit to diagnose until
            # .build(); SPMD/seq winners compile here anyway.
            winner_lowering_postcheck(plan, devices=devices)
        return plan
    raise RuntimeError("no proposal could be materialized")


def _materialize_explored(best, fn, graph, in_tree, out_tree, example_args,
                          example_kwargs, annotations, state_alias, devices,
                          priced_on_fn_graph, _Xform, PipelineWinner,
                          candidates):
    """Lower one explored candidate into its executable plan form."""
    if best["kind"] == "pipeline":
        params, *batch = example_args
        return PipelineWinner(
            num_stages=best["num_stages"],
            num_micro_batches=best["num_micro_batches"],
            intra_tp=best.get("intra_tp", 1),
            cost=best["cost"], candidates=candidates,
            loss_fn=fn, params=params, example_batch=tuple(batch),
            placement=best.get("placement", "blocked"),
            interleave_groups=best.get("interleave_groups"),
            comm_dtype=best.get("comm_dtype", ""),
            zero=best.get("zero", False))

    topo = best["topology"]
    is_seq = any(n == "seq" and s > 1 for n, s in topo.device_axes())
    # Candidate strategies were planned on the PRICING graph; when that is
    # the fn graph itself (non-scalar fn) they can be reused directly.
    strategies = best.get("strategies") if priced_on_fn_graph else None
    if is_seq:
        # Materialize the seq winner: rewrite the attention motifs to the
        # priced ring/Ulysses algorithm BEFORE planning, so the sequence
        # dim stays sharded through the rewritten collective (the same
        # lowering plan_training applies). Strict motif detection — an
        # escaping motif was priceable but is not rewritable, and the
        # caller loop falls back to the runner-up candidate.
        from tepdist_tpu.parallel.attention_motif import seq_rewritten_loss

        seq_size = dict(topo.device_axes())["seq"]
        mesh = topo.to_jax_mesh(
            list(devices if devices is not None else jax.devices()))
        fn_rw, _impl = seq_rewritten_loss(fn, seq_size, mesh,
                                          *example_args)
        graph, in_tree, out_tree = trace_graph(fn_rw, *example_args)
        strategies = None
    if strategies is None:
        strategies = plan_axes(graph, topo, annotations, "cost")
    xform = _Xform(graph, topo)
    sharding_plan = xform.lower(strategies, state_alias=state_alias)
    plan = ParallelPlan(
        graph=graph, topology=topo, strategies=strategies,
        sharding_plan=sharding_plan, in_tree=in_tree, out_tree=out_tree,
        mode="exploration",
        comm_dtype=best.get("comm_dtype", ""),
        zero=best.get("zero", False),
    )
    plan.cost = best["cost"]
    plan.candidates = candidates
    return plan


def explore_topologies(
    num_devices: int, max_levels: int = 3
) -> List[MeshTopology]:
    """Mesh-shape proposals for exploration mode (reference:
    GenerateSplitProposals — factor device count into <=3 ordinals)."""
    shapes: List[Tuple[Tuple[str, int], ...]] = []
    # 1-level: pure data or pure model.
    shapes.append((("data", num_devices),))
    shapes.append((("model", num_devices),))
    # 2-level factorizations data x model.
    d = 2
    while d * d <= num_devices:
        if num_devices % d == 0:
            shapes.append((("data", num_devices // d), ("model", d)))
            shapes.append((("data", d), ("model", num_devices // d)))
        d += 1
    # 3-level factorizations data x model x model2 (reference proposes up
    # to 3 split ordinals, auto_parallel.cc:132-181).
    if max_levels >= 3:
        a = 2
        while a * 4 <= num_devices:
            rest = num_devices // a
            if num_devices % a == 0:
                b = 2
                while b * b <= rest:
                    if rest % b == 0:
                        shapes.append((("data", a), ("model", rest // b),
                                       ("model2", b)))
                    b += 1
            a += 1
    out = []
    seen = set()
    for axes in shapes:
        key = tuple(axes)
        if key not in seen:
            seen.add(key)
            out.append(MeshTopology(list(axes)))
    return out
