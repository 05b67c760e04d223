"""PipelineExecutable: execute a scheduled TaskDAG on real devices.

Reference parity: ``DAPPLEExecutable`` (reference: pjrt/virtual_client.cc —
per-task-type executors DoInputTask/DoComputeTask/DoSendTask/DoRecvTask/
DoARTask/DoGATask/DoGAInitTask/DoOutputTask and the per-device
``ExecuteTaskList`` loop). TPU-native deltas:

  * Per-device std::threads + CUDA-event barriers are replaced by PJRT async
    dispatch: issuing jitted stage computations in the scheduler's static
    order gives cross-stage overlap because every dispatch returns futures
    and each stage occupies its own device subset.
  * kSend/kRecv NCCL p2p becomes ``jax.device_put`` onto the consumer
    stage's sharding (PJRT routes over ICI/DCN).
  * Variables are server-held: parameters and optimizer state live on their
    owning stage's devices across steps (the reference's server-side
    variable store + VarsCacheInRemote), and ``fetch_variables`` mirrors
    FetchResourceVars.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tepdist_tpu.core.service_env import ServiceEnv
from tepdist_tpu.parallel.pipeline import PipelineProgram
from tepdist_tpu.runtime.execution_plan import (
    PipelinePlanMaps,
    build_pipeline_task_dag,
)
from tepdist_tpu.runtime.task_graph import TaskDAG, TaskType
from tepdist_tpu.runtime.task_scheduler import ScheduleResult, TaskScheduler
from tepdist_tpu.telemetry import _NULL_SPAN, metrics, span, tracer

log = logging.getLogger(__name__)

# Span category per task type (Perfetto's category filter slices by these).
_SPAN_CAT = {
    TaskType.COMPUTE: "compute",
    TaskType.SEND: "send",
    TaskType.RECV: "recv",
    TaskType.GAINIT: "ga",
    TaskType.GA: "ga",
    TaskType.APPLY: "apply",
}


class PipelineExecutable:
    """Owns variables + compiled stage programs; runs scheduled steps."""

    def __init__(
        self,
        prog: PipelineProgram,
        devices: Optional[Sequence] = None,
        optimizer=None,
        intra_stage_dp: bool = True,
        intra_stage_tp: int = 1,
        stage_var_mem_limit: Optional[int] = None,
        placement: str = "blocked",
        interleave_groups: Optional[int] = None,
    ):
        """``intra_stage_dp``: shard the micro-batch dim over each stage's
        device subset (PP x DP hybrid — the reference's nested split
        ordinals, stage x spmd). Params stay replicated within a stage;
        per-micro gradients come out partial and GSPMD inserts the
        intra-stage psum at the GA/apply boundary.

        ``intra_stage_tp``: model-parallel degree WITHIN each stage (the
        reference's stage x spmd nesting with a model ordinal,
        auto_parallel.cc:132-181 + dev_id_util.h:94-192). Each stage gets a
        2-D (intra, model) device grid; the cone/ILP planner runs on the
        stage's forward jaxpr over the ``model`` axis, and the AOT stage
        executables pin every input/output to the planned sharding so GSPMD
        inserts the intra-stage TP collectives. Composes with
        ``intra_stage_dp`` (stage x dp x tp).

        ``stage_var_mem_limit``: per-device byte budget for each stage's
        variables, enforced inside the stage planner's ILP (reference:
        SplitPlanByMemCost / VAR_MEM_LIMIT) — weight TP emerges where
        replication would not fit. Defaults to the VAR_MEM_LIMIT env.

        ``placement``: "blocked" (contiguous device ranges, one stage per
        group) or "interleaved" — VIRTUAL stages: plan MORE stages than
        device groups and assign them round-robin (stage s -> group
        s % G, the multiworker layout in-process); hops between
        co-resident stages are direct edges (no send/recv). S must be a
        multiple of the group count. The scheduler's candidate search
        includes a Megatron chunk-alternating priority for interleaved
        placements and realizes the interleaved-1F1B bubble gain in the
        warmup-dominated regime (deep p, modest M, hops cheap vs stage
        compute — tests/test_interleaved_schedule.py)."""
        self.prog = prog
        S = prog.num_stages
        devices = list(devices if devices is not None else jax.devices())
        if placement not in ("blocked", "interleaved"):
            raise ValueError(f"unknown placement {placement!r}")
        if placement == "interleaved":
            # Group count = ``interleave_groups`` when given (the
            # exploration winner's G — e.g. 8 virtual stages over 4
            # groups of 2 devices), else min(devices, stages); each group
            # hosts S/G virtual stages (round-robin). A non-dividing S
            # would silently unbalance or collapse to G=1 — error like
            # the blocked path's under-provisioning check does.
            G = interleave_groups or min(len(devices), S)
            if len(devices) % G:
                raise ValueError(
                    f"interleaved placement: {len(devices)} devices not "
                    f"divisible into {G} groups")
            if S % G:
                src = ("interleave_groups" if interleave_groups
                       else "min(devices, stages)")
                raise ValueError(
                    f"interleaved placement needs num_stages ({S}) "
                    f"divisible by the group count ({G} from {src}); "
                    "pick a dividing stage count")
            per_g = len(devices) // G
            groups = [tuple(devices[g * per_g:(g + 1) * per_g])
                      for g in range(G)]
            self._stage_group = [s % G for s in range(S)]
            devices_of_stage = [list(groups[self._stage_group[s]])
                                for s in range(S)]
            per = per_g
        else:
            if len(devices) < S:
                raise ValueError(f"need >= {S} devices for {S} stages")
            per = len(devices) // S
            devices_of_stage = [devices[s * per:(s + 1) * per]
                                for s in range(S)]
            self._stage_group = list(range(S))
        tp = max(int(intra_stage_tp), 1)
        if per % tp:
            raise ValueError(
                f"{per} devices/stage not divisible by intra_stage_tp={tp}")
        self.tp = tp
        dp = per // tp
        self.stage_devices: List[Tuple[int, ...]] = []
        self.stage_meshes: List[Mesh] = []
        self.stage_shardings: List[NamedSharding] = []   # replicated
        self.stage_batch_shardings: List[NamedSharding] = []
        micro_rows = None
        if prog.batch_flat_indices:
            b0 = prog.graph.invars[prog.batch_flat_indices[0]]
            micro_rows = b0.aval.shape[prog.batch_dim]
        self._micro_rows = micro_rows
        self.intra_dp = (intra_stage_dp and dp > 1 and micro_rows is not None
                         and micro_rows % dp == 0)
        # ZeRO weight-update sharding (the exploration winner's modifier):
        # each stage's optimizer state shards over its intra-stage data
        # replicas; the apply jit then runs on local shards and GSPMD
        # emits the reduce-scatter/all-gather bracket (arXiv:2004.13336).
        self.zero = bool(getattr(prog, "zero", False)) and dp > 1
        for s in range(S):
            devs = devices_of_stage[s]
            self.stage_devices.append(tuple(d.id for d in devs))
            if tp > 1:
                mesh = Mesh(np.array(devs).reshape(dp, tp),
                            axis_names=("intra", "model"))
            else:
                mesh = Mesh(np.array(devs), axis_names=("intra",))
            self.stage_meshes.append(mesh)
            self.stage_shardings.append(NamedSharding(mesh, PartitionSpec()))
            self.stage_batch_shardings.append(
                NamedSharding(mesh, PartitionSpec("intra"))
                if self.intra_dp else
                NamedSharding(mesh, PartitionSpec()))
        # Per-stage TP plans: pos -> PartitionSpec / out k -> PartitionSpec.
        self._tp_in_specs: List[Optional[List[PartitionSpec]]] = [None] * S
        self._tp_out_specs: List[Optional[List[PartitionSpec]]] = [None] * S
        if stage_var_mem_limit is None:
            env_lim = ServiceEnv.get().var_mem_limit
            stage_var_mem_limit = env_lim if env_lim > 0 else None
        self._stage_var_mem_limit = stage_var_mem_limit
        if tp > 1:
            self._plan_stage_tp()

        self.dag, self.maps = build_pipeline_task_dag(
            prog, self.stage_devices)
        self.schedule: ScheduleResult = TaskScheduler(self.dag).schedule()
        # Rebuild the GC plan for the CHOSEN order (candidate simulations may
        # have left a different order's plan in place).
        self.dag.build_gc_plan(self.schedule.order)
        # Pre-dispatch gate (TEPDIST_VERIFY_PLAN): the explore winner's
        # .build() lands here, so a planner bug is caught before compile.
        from tepdist_tpu.analysis.plan_verify import maybe_verify_plan
        maybe_verify_plan(self.dag, schedule=self.schedule, prog=prog,
                          where="PipelineExecutable")
        self.optimizer = optimizer

        # Param ownership: flat invar idx -> owning stage (first consumer).
        # Shared params (tied embeddings) are broadcast to other consumers
        # each step; their gradients are summed into the owner's APPLY.
        self.param_owner: Dict[int, int] = {}
        self.param_stages: Dict[int, List[int]] = {}
        batch = set(prog.batch_flat_indices)
        for s in range(S):
            mod = prog.stages[s]
            for pos in mod.param_positions():
                i = mod.input_def_map[pos][1]
                if i in batch:
                    continue
                self.param_stages.setdefault(i, [])
                if s not in self.param_stages[i]:
                    self.param_stages[i].append(s)
        for i, stages_of_i in self.param_stages.items():
            self.param_owner[i] = min(stages_of_i)

        self._compile_payloads()
        # Server-held state.
        self.var_store: Dict[int, Any] = {}
        self.opt_states: Dict[int, Any] = {}
        self.params_tree = None
        self.global_step = 0
        self._param_cache: Dict[Tuple[int, int], Tuple[Any, Any]] = {}
        self._apply_jit: Dict[int, Callable] = {}

    # ------------------------------------------------------------------
    def _compose_spec(self, aval, st, allow_intra: bool) -> PartitionSpec:
        """Compose the intra-DP batch rule with the planner's model-axis
        choice into one PartitionSpec (stage x dp x tp nesting)."""
        nd = getattr(aval, "ndim", 0)
        parts: List[Any] = [None] * nd
        if (allow_intra and self.intra_dp and nd >= 1 and self._micro_rows
                and aval.shape[0] == self._micro_rows):
            parts[0] = "intra"
        if (st is not None and st.is_split() and st.partition_dim < nd
                and parts[st.partition_dim] is None
                and aval.shape[st.partition_dim] % self.tp == 0):
            parts[st.partition_dim] = "model"
        while parts and parts[-1] is None:
            parts.pop()
        return PartitionSpec(*parts)

    def _plan_stage_tp(self) -> None:
        """Run the cost planner on each stage's forward jaxpr over the
        ``model`` axis (reference: per-stage SPMD planning under the stage
        split ordinal — CostSpmdStrategy applied inside each DefContext).
        Fills ``_tp_in_specs``/``_tp_out_specs`` (PartitionSpecs per stage
        input position / output index)."""
        from tepdist_tpu.graph.jaxpr_graph import trace_graph
        from tepdist_tpu.parallel.cost_spmd_strategy import CostSpmdStrategy

        prog, tp = self.prog, self.tp
        fwd_fns = prog.decomp.forward_fns()
        batch_set = set(prog.batch_flat_indices)
        for s in range(prog.num_stages):
            mod = prog.stages[s]
            sds = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                   for v in mod.invars]
            g, _, _ = trace_graph(fwd_fns[s], *sds)
            # The intra axis owns the micro-batch dim: the model planner
            # may not re-split dim 0 of ANY micro-row tensor (invars AND
            # interior activations — the batch dim flows through).
            forbidden: Dict[Any, set] = {}
            if self.intra_dp and self._micro_rows:
                from jax.extend import core as jexcore
                allv = list(g.invars)
                for n in g.nodes:
                    allv.extend(ov for ov in n.eqn.outvars
                                if isinstance(ov, jexcore.Var))
                for v in allv:
                    shape = getattr(v.aval, "shape", ())
                    if shape and shape[0] == self._micro_rows:
                        forbidden[v] = {0}
            gs = CostSpmdStrategy(
                g, "model", tp, fixed={}, forbidden_dims=forbidden,
                mem_limit_bytes=self._stage_var_mem_limit).run()
            in_specs, out_specs = [], []
            for pos, v in enumerate(g.invars):
                src = mod.input_def_map[pos]
                allow_intra = (src[0] == "stage"
                               or (src[0] == "arg" and src[1] in batch_set))
                in_specs.append(self._compose_spec(
                    mod.invars[pos].aval, gs.var_strategies.get(v),
                    allow_intra))
            for k in range(len(mod.outvars)):
                st = (gs.out_strategies[k]
                      if k < len(gs.out_strategies) else None)
                out_specs.append(self._compose_spec(
                    mod.outvars[k].aval, st, True))
            self._tp_in_specs[s] = in_specs
            self._tp_out_specs[s] = out_specs
            log.info("stage %d TP plan over model=%d: %d/%d inputs split",
                     s, tp, sum(1 for p in in_specs if "model" in tuple(p)),
                     len(in_specs))

    def _stage_sharding_for(self, s: int, aval) -> NamedSharding:
        """The placement rule every producer/consumer agrees on: micro-batch
        tensors (leading dim == micro rows) shard over the intra axis under
        PP x DP; everything else replicates on the stage's devices."""
        if (self.intra_dp and getattr(aval, "ndim", 0) >= 1):
            micro_rows = self.prog.graph.invars[
                self.prog.batch_flat_indices[0]].aval.shape[
                self.prog.batch_dim]
            if aval.shape[0] == micro_rows:
                return self.stage_batch_shardings[s]
        return self.stage_shardings[s]

    def _pos_sharding(self, s: int, mod, pos: int) -> NamedSharding:
        """Placement of stage input ``pos``: under TP, the stage planner's
        spec; otherwise params replicate, batch args and interior
        activations follow the micro-rows rule."""
        if self._tp_in_specs[s] is not None:
            return NamedSharding(self.stage_meshes[s],
                                 self._tp_in_specs[s][pos])
        src = mod.input_def_map[pos]
        if src[0] == "arg" and src[1] not in set(
                self.prog.batch_flat_indices):
            return self.stage_shardings[s]
        return self._stage_sharding_for(s, mod.invars[pos].aval)

    def _out_sharding(self, s: int, k: int) -> NamedSharding:
        """Placement of stage ``s`` output ``k``."""
        if self._tp_out_specs[s] is not None:
            return NamedSharding(self.stage_meshes[s],
                                 self._tp_out_specs[s][k])
        return self._stage_sharding_for(
            s, self.prog.stages[s].outvars[k].aval)

    def _aot(self, fn: Callable, s: int, in_avals, in_shs, out_avals,
             out_shs, donate: Tuple[int, ...] = ()) -> Callable:
        """AOT-compile ``fn`` with every input/output pinned to an agreed
        placement (reference: per-device static task lists dispatch
        pre-built executables, virtual_client.cc:1662-1807 — no per-call
        tracing, no per-arg resharding). Falls back to plain jit if the
        AOT path rejects the signature."""
        try:
            jfn = jax.jit(fn, out_shardings=out_shs,
                          donate_argnums=donate or None)
            sds = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
                   for a, sh in zip(in_avals, in_shs)]
            return jfn.lower(*sds).compile()
        except Exception as e:  # noqa: BLE001 — keep the jit fallback path
            log.warning("AOT compile fell back to jit for stage %d: %r",
                        s, e)
            return jax.jit(fn)

    def _compile_payloads(self) -> None:
        prog = self.prog
        S = prog.num_stages
        self._fwd_jit: List[Callable] = []
        self._bwd_jit: List[Callable] = []
        self._ga_jit: List[Callable] = []
        self._gainit: List[Callable] = []
        self._bwd_wired: List[List[int]] = []
        fwd_fns = prog.decomp.forward_fns()
        batch_set = set(prog.batch_flat_indices)
        # Param positions per stage EXCLUDING batch args (both are "arg"
        # entries in input_def_map; only trainables join GA/apply).
        self._stage_ppos: List[Tuple[int, ...]] = [
            tuple(p for p in prog.stages[s].param_positions()
                  if prog.stages[s].input_def_map[p][1] not in batch_set)
            for s in range(S)
        ]
        # Graph invar index per GA-accumulator slot, per stage.
        self._stage_pidx: List[Tuple[int, ...]] = [
            tuple(prog.stages[s].input_def_map[p][1]
                  for p in self._stage_ppos[s])
            for s in range(S)
        ]

        # Param placement by (stage, graph invar idx) — under TP this is
        # the planner's spec, not plain replication.
        self._param_sharding: Dict[Tuple[int, int], NamedSharding] = {}
        for s in range(S):
            mod = prog.stages[s]
            for p, i in zip(self._stage_ppos[s], self._stage_pidx[s]):
                self._param_sharding[(s, i)] = self._pos_sharding(s, mod, p)

        # Pre-bound per-task argument templates (ask #8: per-step dict
        # lookups and sharding-rule re-derivation were measurable): one
        # (kind, idx, pos) list per stage plus the batch placement cache.
        batch_set_t = set(prog.batch_flat_indices)
        self._arg_templates: List[List[Tuple[str, Optional[int], int]]] = []
        self._batch_sharding: Dict[Tuple[int, int], NamedSharding] = {}
        for s in range(S):
            mod = prog.stages[s]
            tpl: List[Tuple[str, Optional[int], int]] = []
            for pos in range(len(mod.invars)):
                src = mod.input_def_map[pos]
                if src[0] == "arg":
                    i = src[1]
                    if i in batch_set_t:
                        tpl.append(("batch", i, pos))
                        self._batch_sharding[(s, pos)] = self._pos_sharding(
                            s, mod, pos)
                    else:
                        tpl.append(("param", i, pos))
                else:
                    tpl.append(("wire", None, pos))
            self._arg_templates.append(tpl)

        # Which cot positions are wired per stage (from the DAG build):
        for s in range(S):
            mod = prog.stages[s]
            n_in = len(mod.invars)
            bwd_id = self.maps.bwd_tasks[(s, 0)]
            wired = sorted(
                pos - n_in
                for pos in self.dag.node(bwd_id).input_specs
                if pos >= n_in
            )
            self._bwd_wired.append(wired)

        loss_stage = next(s for s in range(S)
                          if 0 in prog.stages[s].graph_out_map)
        self._loss_stage = loss_stage

        for s in range(S):
            mod = prog.stages[s]
            fwd = fwd_fns[s]
            wired = self._bwd_wired[s]
            out_avals = [v.aval for v in mod.outvars]
            loss_out = (prog.stages[s].graph_out_map.get(0)
                        if s == loss_stage else None)

            def make_bwd(fwd=fwd, wired=tuple(wired), out_avals=tuple(out_avals),
                         loss_out=loss_out, n_in=len(mod.invars),
                         in_avals_=tuple(v.aval for v in mod.invars)):
                def bwd(*args):
                    ins = args[:n_in]
                    cots_in = args[n_in:]
                    cots = []
                    it = iter(cots_in)
                    for k, av in enumerate(out_avals):
                        if k in wired:
                            cots.append(next(it))
                        elif k == loss_out:
                            cots.append(jnp.ones(av.shape, av.dtype))
                        else:
                            cots.append(jnp.zeros(av.shape, av.dtype))
                    _, vjp_fn = jax.vjp(fwd, *ins)
                    grads = vjp_fn(tuple(cots))
                    # VJP emits float0 for integer inputs (token slices);
                    # the wire format carries primal-dtype zeros instead —
                    # the AOT signature is static.
                    return tuple(
                        jnp.zeros(a.shape, a.dtype)
                        if getattr(g, "dtype", None) == jax.dtypes.float0
                        else g
                        for g, a in zip(grads, in_avals_))
                return bwd

            in_avals = [v.aval for v in mod.invars]
            in_shs = [self._pos_sharding(s, mod, p)
                      for p in range(len(in_avals))]
            fwd_out_avals = tuple(v.aval for v in mod.outvars)
            fwd_out_shs = tuple(self._out_sharding(s, k)
                                for k in range(len(mod.outvars)))
            self._fwd_jit.append(self._aot(
                fwd, s, in_avals, in_shs, fwd_out_avals, fwd_out_shs))

            # bwd returns the VJP w.r.t. every stage input (grads for params,
            # cotangents for interior activations) — all placed by the same
            # rule the consumers (GA / SEND / cross-stage RECV) assume.
            bwd_in_avals = in_avals + [mod.outvars[k].aval for k in wired]
            bwd_in_shs = in_shs + [self._out_sharding(s, k) for k in wired]
            bwd_out_avals = tuple(in_avals)
            bwd_out_shs = tuple(in_shs)
            self._bwd_jit.append(self._aot(
                make_bwd(), s, bwd_in_avals, bwd_in_shs,
                bwd_out_avals, bwd_out_shs))

            ppos = self._stage_ppos[s]
            param_avals = tuple(mod.invars[p].aval for p in ppos)
            param_shs = tuple(self._pos_sharding(s, mod, p) for p in ppos)
            # GA flattens (acc tuple, bwd_outs tuple) positionally; the
            # accumulator is donated — only its chain consumes it.
            n_acc = len(param_avals)

            # Winner-planned gradient-contribution compression: the GA
            # add consumes the bwd output through the comm dtype the
            # argmin chose (bf16 down-cast, or int8 chunk-scale
            # stochastic-rounding fake-quant). Fidelity ("") adds the
            # raw contribution — bit-identical to the uncompressed step.
            comm_dtype = getattr(self.prog, "comm_dtype", "") or ""

            def make_ga_flat(ppos=ppos, n_acc=n_acc, s=s, cd=comm_dtype):
                def contrib(g, p):
                    if not cd or not jnp.issubdtype(g.dtype, jnp.floating):
                        return g
                    if cd == "bfloat16":
                        return g.astype(jnp.bfloat16)
                    if cd == "int8":
                        from tepdist_tpu.parallel.quantize import (
                            fake_quant_int8,
                        )
                        key = jax.random.fold_in(
                            jax.random.PRNGKey(0x7e9d), s * 131 + p)
                        return fake_quant_int8(g, key)
                    return g

                def ga(*args):
                    acc = args[:n_acc]
                    bwd_outs = args[n_acc:]
                    return tuple(
                        a + contrib(bwd_outs[p], p).astype(a.dtype)
                        for a, p in zip(acc, ppos))
                return ga

            self._ga_jit.append(self._aot(
                make_ga_flat(), s,
                list(param_avals) + list(in_avals),
                list(param_shs) + list(bwd_out_shs),
                param_avals, param_shs,
                donate=tuple(range(n_acc))))
            self._n_acc = getattr(self, "_n_acc", {})
            self._n_acc[s] = n_acc

            def make_gainit(avals=param_avals):
                def gi():
                    return tuple(jnp.zeros(a.shape, a.dtype) for a in avals)
                return gi

            self._gainit.append(self._aot(
                make_gainit(), s, [], [], param_avals, param_shs))

    # ------------------------------------------------------------------
    # Variable management (server-held; reference RegisteredForVariable /
    # VarsCacheInRemote / FetchResourceVars).
    def load_variables(self, params) -> None:
        flat, tree = jax.tree_util.tree_flatten(params)
        self.params_tree = tree
        self.n_params = len(flat)
        for i, leaf in enumerate(flat):
            s = self.param_owner.get(i)
            if s is None:
                # Unused param: keep on stage 0.
                s = 0
            self.var_store[i] = jax.device_put(
                leaf, self._param_sharding.get((s, i),
                                               self.stage_shardings[s]))
        if self.optimizer is not None:
            for s in range(self.prog.num_stages):
                sub = {i: self.var_store[i]
                       for i in sorted(self.param_owner)
                       if self.param_owner[i] == s}
                self.opt_states[s] = self.optimizer.init(sub)
                if self.zero:
                    self.opt_states[s] = self._shard_opt_state(
                        s, self.opt_states[s])

    def _zero_opt_sharding(self, s: int, val, i: Optional[int] = None):
        """ZeRO: the moment mirroring param ``i`` shards over the intra
        axis on the first dim its planned (TP) spec leaves free and dp
        divides; scalars and indivisible leaves stay replicated."""
        mesh = self.stage_meshes[s]
        dp = int(mesh.shape["intra"])
        shape = tuple(getattr(val, "shape", ()))
        base = self._param_sharding.get((s, i)) if i is not None else None
        parts: List[Any] = list(base.spec) if base is not None else []
        parts += [None] * (len(shape) - len(parts))
        for d, n in enumerate(shape):
            if parts[d] is None and n >= dp and n % dp == 0:
                parts[d] = "intra"
                return NamedSharding(mesh, PartitionSpec(*parts))
        return base or self.stage_shardings[s]

    def _shard_opt_state(self, s: int, st):
        """Re-place stage ``s``'s optimizer state on its ZeRO shardings
        (no-op for leaves already placed there)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(st)
        new = []
        for p, v in flat:
            i = self._leaf_owner_index(p)
            if i is not None and getattr(v, "ndim", 0) >= 1:
                sh = self._zero_opt_sharding(s, v, i)
                if getattr(v, "sharding", None) != sh:
                    v = jax.device_put(v, sh)
            new.append(v)
        return jax.tree_util.tree_unflatten(treedef, new)

    def _stage_param(self, s: int, i: int):
        """Param value for stage ``s``: owner's copy, broadcast if shared.
        Broadcasts are cached per step — params change once per step (at
        APPLY), not once per consuming task."""
        val = self.var_store[i]
        if self.param_owner.get(i, s) != s:
            key = (s, i)
            cached = self._param_cache.get(key)
            if cached is not None and cached[0] is val:
                return cached[1]
            put = jax.device_put(
                val, self._param_sharding.get((s, i),
                                              self.stage_shardings[s]))
            self._param_cache[key] = (val, put)
            return put
        return val

    def _put_stage(self, s: int, val):
        """Place a value on stage ``s``: micro-batch tensors (leading dim ==
        micro rows) shard over the intra axis under PP x DP; everything else
        replicates."""
        if (self.intra_dp and hasattr(val, "ndim") and val.ndim >= 1):
            micro_rows = self.prog.graph.invars[
                self.prog.batch_flat_indices[0]].aval.shape[
                self.prog.batch_dim]
            if val.shape[0] == micro_rows:
                return jax.device_put(val, self.stage_batch_shardings[s])
        return jax.device_put(val, self.stage_shardings[s])

    def fetch_variables(self):
        assert self.params_tree is not None, "load_variables first"
        flat = [jax.device_get(self.var_store[i])
                for i in range(self.n_params)]
        return jax.tree_util.tree_unflatten(self.params_tree, flat)

    # -- global optimizer-state assembly --------------------------------
    # Per-stage optax states are optimizer.init({i: leaf}) over GLOBAL
    # flat param indices, so a whole-run state with the same index-dict
    # structure can be assembled leaf-for-leaf BY TREE PATH: mirroring
    # leaves (mu/nu[i]) come from the owning stage, params-independent
    # scalars (step counts) are identical across stages. The flat leaf
    # ORDER matches optimizer.init(user_params_tree) (index order ==
    # user-tree flatten order), which makes pipeline checkpoints
    # interchangeable with the SPMD runtime's (cross-topology restore
    # with stateful optimizers; reference contract:
    # distributed_checkpoint_utils.h:485-507).

    def _opt_template(self):
        full = {i: jax.ShapeDtypeStruct(
                    tuple(self.var_store[i].shape),
                    self.var_store[i].dtype)
                for i in range(self.n_params)}
        return jax.eval_shape(self.optimizer.init, full)

    @staticmethod
    def _path_map(tree):
        return {jax.tree_util.keystr(path): leaf for path, leaf in
                jax.tree_util.tree_flatten_with_path(tree)[0]}

    def _leaf_owner_index(self, path) -> Optional[int]:
        from jax.tree_util import DictKey
        for k in path:
            if isinstance(k, DictKey) and isinstance(k.key, int):
                return int(k.key)
        return None

    def fetch_opt_state(self):
        """Assemble the per-stage states into ONE optax state over the
        full index dict (flat leaves align with the SPMD runtime's)."""
        assert self.optimizer is not None, "no optimizer"
        template = self._opt_template()
        stage_maps = {s: self._path_map(st)
                      for s, st in self.opt_states.items()}
        extra_map: Dict[str, Any] = {}   # leaves of graph-UNUSED params
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        leaves = []
        for path, _ in flat:
            key = jax.tree_util.keystr(path)
            i = self._leaf_owner_index(path)
            if i is not None:
                owner = stage_maps.get(self.param_owner.get(i, 0), {})
                if key in owner:
                    leaves.append(owner[key])
                else:
                    # Param unused by the graph: no stage state holds its
                    # moments — they are identically their INIT values
                    # (it never updates), so materialise those.
                    if key not in extra_map:
                        extra_map.update(self._path_map(
                            self.optimizer.init({i: self.var_store[i]})))
                    leaves.append(extra_map[key])
            else:
                # Params-independent scalar (e.g. count): any stage's.
                src = next(m for m in stage_maps.values() if key in m)
                leaves.append(src[key])
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def load_opt_state(self, state) -> None:
        """Scatter a global optax state back into the per-stage states
        (inverse of fetch_opt_state; accepts any tree with the same flat
        leaves as the index-dict template)."""
        assert self.optimizer is not None, "no optimizer"
        template = self._opt_template()
        tmpl_flat, tmpl_def = jax.tree_util.tree_flatten_with_path(template)
        state_leaves = jax.tree_util.tree_leaves(state)
        if len(state_leaves) != len(tmpl_flat):
            raise ValueError(
                f"optimizer state has {len(state_leaves)} leaves; "
                f"expected {len(tmpl_flat)}")
        by_key = {jax.tree_util.keystr(path): v for (path, _), v in
                  zip(tmpl_flat, state_leaves)}
        for s, st in self.opt_states.items():
            flat, treedef = jax.tree_util.tree_flatten_with_path(st)
            new = []
            for p, _ in flat:
                i = self._leaf_owner_index(p)
                # Moments adopt their param's PLANNED sharding (under TP a
                # replicated put would blow the memory the split exists
                # for and force an apply-jit recompile).
                sh = (self._param_sharding.get((s, i))
                      if i is not None else None) or self.stage_shardings[s]
                val = by_key[jax.tree_util.keystr(p)]
                if (self.zero and i is not None
                        and getattr(val, "ndim", 0) >= 1):
                    sh = self._zero_opt_sharding(s, val, i)
                new.append(jax.device_put(val, sh))
            self.opt_states[s] = jax.tree_util.tree_unflatten(treedef, new)

    # ------------------------------------------------------------------
    def step(self, *batch) -> Any:
        """Run one scheduled training step; returns the mean loss.

        With DEBUG on, per-task wall-clock is logged with task/stage/micro
        ids (reference: DEBUG-gated NowMicros timing around every task,
        virtual_client.cc:1672-1803) — read from the task's span (DEBUG
        implies tracing; spans are THE timing mechanism)."""
        debug = ServiceEnv.get().debug
        tracing = tracer().enabled
        sp_step = (span("pipeline_step", cat="step",
                        step=self.global_step).__enter__()
                   if tracing else _NULL_SPAN)
        prog = self.prog
        S = prog.num_stages
        M = prog.num_micro_batches
        batch_flat = jax.tree_util.tree_leaves(tuple(batch))
        n_param_leaves = self.n_params
        bdim = prog.batch_dim

        # SPLIT: micro-slice every batch leaf — ONE jitted dispatch per
        # leaf (M separate slice ops serialized the step preamble).
        if not hasattr(self, "_slicers"):
            self._slicers = {}
        micro_slices: Dict[Tuple[int, int], Any] = {}
        for j, leaf in enumerate(batch_flat):
            i = n_param_leaves + j
            sl_key = (i, tuple(leaf.shape), str(getattr(leaf, "dtype", "")))
            if sl_key not in self._slicers:
                msize = leaf.shape[bdim] // M

                def make(msize=msize, bdim=bdim):
                    def slicer(x):
                        return tuple(
                            jax.lax.slice_in_dim(x, m * msize,
                                                 (m + 1) * msize, axis=bdim)
                            for m in range(M))
                    return jax.jit(slicer)

                self._slicers[sl_key] = make()
            for m, sl in enumerate(self._slicers[sl_key](leaf)):
                micro_slices[(m, i)] = sl

        outputs: Dict[int, Tuple] = {}
        losses: List[Any] = []
        batch_set = set(prog.batch_flat_indices)

        def stage_args(s: int, m: int, tid: int) -> List[Any]:
            node = self.dag.node(tid)
            args: List[Any] = []
            for kind, i, pos in self._arg_templates[s]:
                if kind == "param":
                    args.append(self._stage_param(s, i))
                elif kind == "batch":
                    args.append(jax.device_put(
                        micro_slices[(m, i)],
                        self._batch_sharding[(s, pos)]))
                else:
                    pid, oi = node.input_specs[pos]
                    args.append(outputs[pid][oi])
            return args

        for tid in self.schedule.order:
            node = self.dag.node(tid)
            tt = node.task_type
            s, m = node.stage, node.micro
            sp = (span(node.name, cat=_SPAN_CAT.get(tt, "data"),
                       stage=s, micro=m, task=tid,
                       step=self.global_step).__enter__()
                  if tracing else _NULL_SPAN)
            if tt in (TaskType.SPLIT, TaskType.INPUT, TaskType.MERGE):
                outputs[tid] = ()
            elif tt == TaskType.COMPUTE and node.name.startswith("fwd"):
                args = stage_args(s, m, tid)
                outs = self._fwd_jit[s](*args)
                outputs[tid] = outs
                if s == self._loss_stage:
                    losses.append(outs[prog.stages[s].graph_out_map[0]])
            elif tt == TaskType.COMPUTE and node.name.startswith("bwd"):
                mod = prog.stages[s]
                n_in = len(mod.invars)
                args = stage_args(s, m, tid)
                cot_args = [outputs[pid][oi] for pos, (pid, oi) in
                            sorted(node.input_specs.items())
                            if pos >= n_in]
                if self.tp > 1:
                    # Same-device-group cots arrive with the PRODUCER's
                    # sharding; the AOT bwd is pinned to this stage's out
                    # specs (device_put is a no-op when they already match).
                    ks = [pos - n_in for pos in
                          sorted(node.input_specs) if pos >= n_in]
                    cot_args = [jax.device_put(c, self._out_sharding(s, k))
                                for c, k in zip(cot_args, ks)]
                outputs[tid] = self._bwd_jit[s](*args, *cot_args)
            elif tt == TaskType.SEND:
                pid, oi = node.input_specs[0]
                outputs[tid] = (outputs[pid][oi],)
            elif tt == TaskType.RECV:
                pid, oi = node.input_specs[0]
                val = outputs[pid][oi]
                target = self.maps.recv_target.get(tid)
                if target is not None:
                    # Place by the consumer's PLANNED sharding (stage x TP:
                    # the generic replicate rule would gather TP-split
                    # activations on every hop).
                    kind, ts_, ix = target
                    sh = (self._pos_sharding(ts_, self.prog.stages[ts_], ix)
                          if kind == "in" else self._out_sharding(ts_, ix))
                    val = jax.device_put(val, sh)
                else:
                    val = self._put_stage(s, val)
                outputs[tid] = (val,)
            elif tt == TaskType.GAINIT:
                outputs[tid] = (self._gainit[s](),)
            elif tt == TaskType.GA:
                (acc_pid, acc_oi) = node.input_specs[0]
                (bwd_pid, bwd_oi) = node.input_specs[1]
                acc = outputs[acc_pid][acc_oi]
                bwd_outs = outputs[bwd_pid]
                outputs[tid] = (self._ga_jit[s](*acc, *bwd_outs),)
            elif tt == TaskType.APPLY:
                (pid, oi) = node.input_specs[0]
                acc = outputs[pid][oi]
                extras = {}
                for pos, (epid, eoi) in node.input_specs.items():
                    if pos >= 1:
                        extras[pos - 1] = outputs[epid][eoi]  # pos-1 = stage
                self._apply_stage(s, acc, M, extras)
                outputs[tid] = ()
            else:
                outputs[tid] = ()
            if tracing:
                if tt in (TaskType.SEND, TaskType.RECV):
                    sp.set(bytes=sum(
                        int(getattr(v, "nbytes", 0) or 0)
                        for v in outputs.get(tid, ())))
                sp.__exit__(None, None, None)
            if debug:
                log.info("[task] %s stage=%d micro=%d %.3f ms",
                         node.key(), node.stage, node.micro, sp.dur_ms)
            # GC: free buffers whose last consumer just ran.
            for rid in node.mem_to_release:
                outputs.pop(rid, None)

        self.global_step += 1
        # ONE host round trip for all micro losses.
        loss = float(np.sum(jax.device_get(jnp.stack(losses)))) / M
        metrics().counter("pipeline_steps").inc()
        if tracing:
            sp_step.__exit__(None, None, None)
        if debug:
            log.info("[ExecutePlan Duration] step=%d %.3f ms",
                     self.global_step, sp_step.dur_ms)
        return loss

    def _apply_stage(self, s: int, acc: Tuple, M: int,
                     extras: Optional[Dict[int, Tuple]] = None) -> None:
        """Apply gradients for params OWNED by stage ``s``, summing shared
        params' contributions from other stages' GA accumulators. The whole
        update (grad average + optimizer + apply) runs as ONE jitted call
        with donated state (the round-1 version ran optax op-by-op eagerly
        — dozens of dispatches per step)."""
        contrib = tuple(sorted((extras or {}).keys()))
        key = (s, contrib)
        if key not in self._apply_jit:
            idxs_all = self._stage_pidx[s]
            owner = self.param_owner
            pidx_of = {t: self._stage_pidx[t] for t in contrib}
            optimizer = self.optimizer

            def apply(params, opt_state, acc, *eaccs):
                grads = {i: g for i, g in zip(idxs_all, acc)
                         if owner[i] == s}
                for t, eacc in zip(contrib, eaccs):
                    for i, g in zip(pidx_of[t], eacc):
                        if owner.get(i) == s and i in grads:
                            grads[i] = grads[i] + g
                grads = {i: g / M for i, g in grads.items()}
                if optimizer is None:
                    return ({i: params[i] - 0.01 * grads[i]
                             for i in params}, opt_state)
                updates, new_opt = optimizer.update(grads, opt_state, params)
                import optax
                return optax.apply_updates(params, updates), new_opt

            # Nothing is donated here: params may share buffers with the
            # caller's arrays (load_variables device_put aliases when
            # layouts match), and with tied params another stage's APPLY
            # reads this stage's final accumulator as an extra.
            self._apply_jit[key] = jax.jit(apply)

        owned = [i for i in self._stage_pidx[s] if self.param_owner[i] == s]
        params = {i: self.var_store[i] for i in owned}
        # Cross-stage accumulators must land on this stage's devices (under
        # TP: on the owner's PLANNED sharding for that param) before they
        # can join the jitted update.
        eaccs = [tuple(jax.device_put(
                     g, self._param_sharding.get((s, i),
                                                 self.stage_shardings[s]))
                       for i, g in zip(self._stage_pidx[t], extras[t]))
                 for t in contrib] if contrib else []
        new_params, self.opt_states[s] = self._apply_jit[key](
            params, self.opt_states[s], acc, *eaccs)
        if self.zero:
            # The apply jit is free to replicate its outputs; re-pin the
            # state shards so the memory saving survives across steps
            # (no-op when GSPMD already kept them sharded).
            self.opt_states[s] = self._shard_opt_state(s, self.opt_states[s])
        for i in owned:
            val = new_params[i]
            sh = self._param_sharding.get((s, i))
            if sh is not None and getattr(val, "sharding", None) != sh:
                # The apply jit is not AOT-pinned; re-place so next step's
                # AOT stage executables see the exact planned sharding.
                val = jax.device_put(val, sh)
            self.var_store[i] = val
