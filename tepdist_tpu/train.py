"""Unified training entry point: the AutoParallel driver across all modes.

Reference parity: ``AutoParallel::Run``'s mode dispatch (reference:
auto_parallel.cc:395 — RULE_MODE / config mode via NUM_STAGES +
NUM_MICRO_BATCHES / exploration) surfaced as one call:

    plan = plan_training(loss_fn, optimizer, params, batch)
    for _ in range(steps):
        loss = plan.step(batch)

Chooses gradient accumulation from the sync-free analysis (memory-driven or
NUM_MICRO_BATCHES), pipeline stages from NUM_STAGES (task-graph 1F1B
runtime), SPMD sharding from the cone/ILP planner (or exploration over mesh
shapes when no topology is given), and holds training state device-resident
across steps (the server-held-variables model, in-process).
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Any, Callable, Dict, Optional, Sequence

import jax

from tepdist_tpu.core.mesh import MeshTopology
from tepdist_tpu.core.service_env import ServiceEnv
from tepdist_tpu.models.layers import part
from tepdist_tpu.telemetry import metrics, span, traced
from tepdist_tpu.telemetry.trace import STEP_LOG

log = logging.getLogger(__name__)


class TrainingPlan:
    """Common interface over the SPMD and pipeline execution paths."""

    def step(self, *batch) -> float:
        raise NotImplementedError

    def variables(self):
        raise NotImplementedError

    def _device_state(self):
        """Flat state leaves WITHOUT host transfer (the checkpoint writer
        streams them device->host one variable at a time)."""
        return jax.tree_util.tree_leaves(self.variables())

    def save(self, directory: str, step: int, max_to_keep: int = 5,
             block: bool = True):
        """Checkpoint the training state. ``block=False`` snapshots
        device->host now and writes on a background thread; returns an
        AsyncSaveHandle (call .result() before shutdown)."""
        from tepdist_tpu.runtime.checkpoint import CheckpointUtil

        flat = self._device_state()
        # One util per directory so overlapping async saves serialize on
        # its lock (a fresh util per call would sidestep it).
        self._ckpt_utils = getattr(self, "_ckpt_utils", {})
        # ZeRO plans save their state SHARDED: per-shard npz entries +
        # index sidecar, so restore_resharded can land the optimizer
        # shards on any DP width.
        shard = bool(getattr(self, "_ckpt_shard_addressable", False))
        key = (directory, max_to_keep, shard)
        if key not in self._ckpt_utils:
            self._ckpt_utils[key] = CheckpointUtil(
                directory, max_to_keep, shard_addressable=shard)
        util = self._ckpt_utils[key]
        variables = {str(i): l for i, l in enumerate(flat)}
        if block:
            util.save(step, variables)
            return None
        return util.save_async(step, variables)

    def restore(self, directory: str, step: int = -1) -> int:
        from tepdist_tpu.runtime.checkpoint import CheckpointUtil

        data, got = CheckpointUtil(directory).restore(step)
        tree = jax.tree_util.tree_structure(self.variables())
        leaves = [data[str(i)] for i in range(len(data))]
        self._load(jax.tree_util.tree_unflatten(tree, leaves))
        return got

    def _load(self, variables) -> None:
        raise NotImplementedError


class _SpmdTrainingPlan(TrainingPlan):
    def __init__(self, plan, params, opt_state, n_batch_leaves, devices):
        self._plan = plan
        self._steps = 0     # the ``step=<n>`` every span of one step carries
        self._log = STEP_LOG.plan()
        # The plan owns its state arrays and threads outputs back as the
        # next step's inputs, so the aliased state buffers are donated.
        with span("plan:lower", cat="planner"):
            self._step_fn = plan.executable(
                devices=devices, donate_invars=plan.state_donation())
            self._shardings = plan.input_shardings(devices)
        self._state_tree = jax.tree_util.tree_structure((params, opt_state))
        flat_state = jax.tree_util.tree_leaves((params, opt_state))
        self._n_state = len(flat_state)
        # OWNERSHIP TRANSFER: the step donates the state buffers (without
        # donation the training state is double-buffered every step — OOM
        # at GPT-2 1.5B scale on one chip), and device_put shares buffers
        # with compatible inputs. The caller's params/opt_state arrays are
        # therefore moved-from after the first step; read state back via
        # ``variables()``. DISABLE_BUFFER_ALIAS=1 opts out.
        with span("plan:place", cat="planner"):
            self._state = [jax.device_put(v, s) for v, s in
                           zip(flat_state, self._shardings[:self._n_state])]
        self._batch_shardings = self._shardings[self._n_state:]
        self.parallel_plan = plan
        # ZeRO winners keep optimizer-state arrays device-sharded; save
        # them per-shard so restore composes with restore_resharded.
        self._ckpt_shard_addressable = bool(getattr(plan, "zero", False))

    def step(self, *batch) -> float:
        n = self._steps
        # The step's record in the step log is written whether the span
        # recorder is on or off, from the same clock and at the spans'
        # boundaries (telemetry/trace.py: StepLog).
        with span("step", cat="runtime", step=n):
            t0 = self._log.begin()
            with span("step:h2d", cat="runtime", step=n):
                flat_batch = jax.tree_util.tree_leaves(batch)
                flat_batch = [jax.device_put(v, s) for v, s in
                              zip(flat_batch, self._batch_shardings)]
            t1 = time.monotonic_ns()
            # Enqueue only (on the first call also the compile or its
            # cache read); the device works on while this returns.
            with span("step:dispatch", cat="runtime", step=n):
                outs = self._step_fn(*self._state, *flat_batch)
            t2 = time.monotonic_ns()
            self._state = list(outs[1:1 + self._n_state])
            t3 = time.monotonic_ns()
            with span("step:wait", cat="runtime", step=n):
                loss = float(jax.device_get(outs[0]))
            wall_ns = self._log.end(n, t0, t1 - t0, t2 - t1, t3)
            if ServiceEnv.get().debug:
                log.info("[ExecutePlan Duration] %.3f ms", wall_ns / 1e6)
        self._steps = n + 1
        return loss

    def compiled_step_text(self) -> str:
        """Optimized HLO of the step ``step()`` runs (same jitted fn, same
        donation) — what a caller greps for a kernel (``tpu_custom_call``)
        or a collective."""
        args = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                for v in self._plan.graph.invars]
        return self._step_fn.lower(*args).compile().as_text()

    def variables(self):
        return jax.tree_util.tree_unflatten(
            self._state_tree, [jax.device_get(v) for v in self._state])

    def _device_state(self):
        # Raw device arrays: the checkpoint writer fetches one at a time.
        return list(self._state)

    def _load(self, variables) -> None:
        flat = jax.tree_util.tree_leaves(variables)
        self._state = [jax.device_put(v, s) for v, s in
                       zip(flat, self._shardings[:self._n_state])]


class _PipelineTrainingPlan(TrainingPlan):
    def __init__(self, exe, params):
        self._exe = exe
        self._log = STEP_LOG.plan()
        exe.load_variables(params)

    def step(self, *batch) -> float:
        # The step log's record, without the three phases: the executable
        # has tasks, not one h2d, one dispatch and one wait.
        n = self._exe.global_step
        t0 = self._log.begin()
        loss = self._exe.step(*batch)
        self._log.end(n, t0)
        return loss

    def variables(self):
        """Same (params, opt_state) contract as the SPMD plan: per-stage
        optax states are assembled into one global state whose flat
        leaves align with the SPMD runtime's — pipeline checkpoints are
        cross-runtime restorable with STATEFUL optimizers."""
        if self._exe.optimizer is not None:
            return (self._exe.fetch_variables(),
                    self._exe.fetch_opt_state())
        return (self._exe.fetch_variables(),)

    def _load(self, variables) -> None:
        if self._exe.optimizer is not None:
            params, opt_state = variables
            self._exe.load_variables(params)   # re-inits per-stage states
            self._exe.load_opt_state(opt_state)
        else:
            self._exe.load_variables(variables[0])


def explore_parallelism(
    loss_fn: Callable,
    params,
    *example_batch,
    n_devices: int,
    num_micro_batches: int = 4,
    entry_point: str = "explore_parallelism",
) -> Dict[str, Any]:
    """Full exploration over the UNIFIED candidate space — SPMD mesh
    factorizations, seq-parallel meshes, and pipeline stage cuts
    (parallel/exploration.py; reference: RunExplorationlMode over
    DeviceSplitPlan proposals incl. pipeline levels,
    auto_parallel.cc:236)."""
    from tepdist_tpu.parallel.exploration import explore

    return explore(loss_fn, params, *example_batch, n_devices=n_devices,
                   num_micro_batches=num_micro_batches,
                   entry_point=entry_point)


def _in_span(name: str, cat: str):
    """Run the whole of a function under one span."""
    def wrap(fn):
        @functools.wraps(fn)
        def under_span(*args, **kwargs):
            with span(name, cat=cat):
                return fn(*args, **kwargs)
        return under_span
    return wrap


@_in_span("plan", cat="planner")
def plan_training(
    loss_fn: Callable,
    optimizer,
    params,
    *example_batch,
    topology: Optional[MeshTopology] = None,
    num_stages: Optional[int] = None,
    num_micro_batches: Optional[int] = None,
    intra_stage_tp: Optional[int] = None,
    devices: Optional[Sequence] = None,
    mode: Optional[str] = None,
    annotations: Optional[dict] = None,
    var_mem_limit: Optional[int] = None,
    explore: bool = False,
    placement: str = "blocked",
    interleave_groups: Optional[int] = None,
) -> TrainingPlan:
    """Plan + compile a full training loop for ``loss_fn(params, *batch)``
    with an optax ``optimizer``. ``explore=True`` (or OPT_LEVEL=2 with no
    topology/stages given) searches SPMD *and* pipeline proposals.

    Ownership: the returned plan DONATES its state buffers each step, and
    the initial placement may share buffers with ``params``/the derived
    optimizer state — treat them as moved-from after the first ``step()``
    and read state back via ``plan.variables()`` (DISABLE_BUFFER_ALIAS=1
    opts out of donation)."""
    env = ServiceEnv.get()
    devices = list(devices if devices is not None else jax.devices())
    # OPT_LEVEL (reference planner-effort switch): 0 = rule mode,
    # 1 = cost planner on the given/default mesh, 2 = full exploration.
    if mode is None and env.opt_level == 0:
        mode = "rule"
    if (not explore and env.opt_level >= 2 and topology is None
            and num_stages is None):
        explore = True
    explored_winner = None
    comm_dtype = ""
    zero = False
    if explore and topology is None and num_stages is None:
        best = explore_parallelism(
            loss_fn, params, *example_batch, n_devices=len(devices),
            num_micro_batches=num_micro_batches or 4,
            entry_point="plan_training")
        explored_winner = best
        # The winner's comm-dtype modifier: the argmin decided whether
        # compressed gradient collectives pay for themselves on this
        # model x mesh; fidelity winners run the unchanged step.
        comm_dtype = best.get("comm_dtype", "")
        if comm_dtype:
            log.info("exploration winner compresses gradient collectives "
                     "to %s", comm_dtype)
        # The winner's ZeRO modifier: shard optimizer state + the weight
        # update over the data axis (reduce-scatter grads, local apply,
        # all-gather params — arXiv:2004.13336). Fidelity winners keep
        # replicated state.
        zero = best.get("zero", False)
        if zero:
            log.info("exploration winner shards optimizer state over the "
                     "data axis (ZeRO)")
        if best["kind"] == "pipeline":
            num_stages = best["num_stages"]
            num_micro_batches = best["num_micro_batches"]
            if intra_stage_tp is None:
                intra_stage_tp = best.get("intra_tp", 1)
            placement = best.get("placement", placement)
            interleave_groups = best.get("interleave_groups",
                                         interleave_groups)
        else:
            topology = best["topology"]
    if num_stages is None:
        num_stages = env.num_stages if env.num_stages > 0 else 1

    import optax  # noqa: F401 — required peer

    # Sequence axis: rewrite attention motifs into ring attention BEFORE
    # differentiation — value_and_grad of the rewritten forward traces the
    # reverse ring, so the sequence dim stays sharded in both directions
    # (parallel/attention_motif.py; SURVEY §5.7 mandate). Runs before the
    # REMAT wrap: tracing inlines remat2, so wrapping must come after.
    if topology is not None and any(
            n == "seq" and s > 1 for n, s in topology.device_axes()):
        from tepdist_tpu.parallel.attention_motif import seq_rewritten_loss

        # Lower to the PRICED winner (ring vs ulysses, fwd+bwd) — the
        # executed algorithm must match what exploration/pricing assumed.
        seq_size = dict(topology.device_axes())["seq"]
        loss_fn, impl = seq_rewritten_loss(  # noqa: F811 — deliberate
            loss_fn, seq_size, topology.to_jax_mesh(devices),
            params, *example_batch)
        log.info("seq axis -> %s attention", impl)

    # REMAT_POLICY knob: rematerialization trades FLOPs for activation
    # memory (jax.checkpoint; the stage modules already remat via VJP).
    policy = env.remat_policy
    if policy and policy != "none":
        if policy in ("full", "true", "1"):
            loss_fn = jax.checkpoint(loss_fn)
        elif policy == "dots":
            loss_fn = jax.checkpoint(
                loss_fn,
                policy=jax.checkpoint_policies.checkpoint_dots)
        elif policy == "dots_no_batch":
            loss_fn = jax.checkpoint(
                loss_fn,
                policy=jax.checkpoint_policies
                .checkpoint_dots_with_no_batch_dims)
        else:
            log.warning("unknown REMAT_POLICY %r ignored", policy)

    def grad_fn(p, *b):
        return jax.value_and_grad(loss_fn)(p, *b)

    def apply_fn(p, s, g):
        import optax as _o
        with part("optimizer"):
            updates, s = optimizer.update(g, s, p)
            return _o.apply_updates(p, updates), s

    # ---- pipeline path ------------------------------------------------
    if num_stages > 1:
        from tepdist_tpu.parallel.pipeline import plan_pipeline
        from tepdist_tpu.runtime.executor import PipelineExecutable

        M = num_micro_batches or (
            env.num_micro_batches if env.num_micro_batches > 0 else 2)
        prog = plan_pipeline(loss_fn, num_stages, M, params, *example_batch)
        prog.comm_dtype = comm_dtype
        prog.zero = zero
        # Stage x TP nesting: explicit arg, the exploration winner, a
        # 'model' axis on a caller-provided topology, or the
        # INTRA_STAGE_TP env (config mode, like NUM_STAGES).
        tp = intra_stage_tp
        if tp is None and topology is not None:
            tp = dict(topology.device_axes()).get("model", 1)
        if tp is None and env.intra_stage_tp > 0:
            tp = env.intra_stage_tp
        exe = PipelineExecutable(prog, devices=devices, optimizer=optimizer,
                                 intra_stage_tp=tp or 1,
                                 stage_var_mem_limit=var_mem_limit,
                                 placement=placement,
                                 interleave_groups=interleave_groups)
        tplan = _PipelineTrainingPlan(exe, params)
        if explored_winner is not None and "report" in explored_winner:
            tplan.exploration_report = explored_winner["report"]
        return tplan

    # ---- SPMD (+ GA) path ---------------------------------------------
    from tepdist_tpu.graph.jaxpr_graph import trace_graph
    from tepdist_tpu.parallel.auto_parallel import auto_parallel
    from tepdist_tpu.parallel.sync_free import (
        analyze_sync_free,
        build_ga_step,
    )

    with span("plan:place", cat="planner"):
        opt_state = optimizer.init(params)
    if num_micro_batches is None:
        with span("plan:trace", cat="planner"):
            graph, _, _ = trace_graph(grad_fn, params, *example_batch)
        n_param_leaves = len(jax.tree_util.tree_leaves(params))
        batch0 = jax.tree_util.tree_leaves(example_batch)[0]
        res = analyze_sync_free(
            graph, batch_size=batch0.shape[0],
            candidate_args=list(range(
                n_param_leaves,
                n_param_leaves + len(jax.tree_util.tree_leaves(
                    example_batch)))))
        num_micro_batches = res.num_micro_batches
        log.info("sync-free analysis: %d micro batches "
                 "(%.0f%% sync-free flops)", num_micro_batches,
                 100 * res.sync_free_fraction)

    n_batch_args = len(example_batch)
    step_fn = build_ga_step(
        grad_fn, apply_fn, num_micro_batches,
        batch_argnums=tuple(range(1, 1 + n_batch_args)),
        comm_dtype=comm_dtype, loss_fn=loss_fn)

    if topology is None:
        n = len(devices)
        axes = [("data", n)]
        if num_micro_batches > 1:
            topology = MeshTopology(
                [("micro", num_micro_batches)] + axes,
                share_dev_flags=[True] + [False] * len(axes))
        else:
            topology = MeshTopology(axes)

    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))
    state_alias = {1 + k: k for k in range(n_state)}
    # ZeRO winners: the optimizer-state leaves are flat invars
    # n_param..n_state-1 of step_fn(params, opt_state, *batch); the
    # planner force-splits them over the data axis so GSPMD emits the
    # reduce-scatter / sharded-apply / all-gather update.
    zero_invars = None
    if zero:
        n_param = len(jax.tree_util.tree_leaves(params))
        zero_invars = list(range(n_param, n_state))
    plan = auto_parallel(
        step_fn, topology, params, opt_state, *example_batch,
        annotations=annotations, mode=mode, state_alias=state_alias,
        var_mem_limit=var_mem_limit, zero_invars=zero_invars)
    # Set while auto_parallel traced the step: the group's gauges, each
    # described where it is counted (telemetry/traced.py: GROUP).
    log.info("the traced step (%d micro batches): %s", num_micro_batches,
             ", ".join(f"{name}={value:g}"
                       for name, value in traced.values().items() if value)
             or "every gauge 0")
    if (len(devices) > 1 and not plan.sharding_plan.constraints
            and not any(ax for spec in plan.sharding_plan.in_specs
                        for ax in spec)
            and not any(n.prim == "shard_map" for n in plan.graph.nodes)):
        # The planner treats lax.scan as opaque, so a gradient-accumulation
        # step (num_micro_batches > 1) comes out with nothing sharded.
        log.warning(
            "the SPMD plan over %d devices shards nothing: every device "
            "runs the whole step and nothing crosses chips "
            "(num_micro_batches=%d)", len(devices), num_micro_batches)
    # Winner-only lowering post-check: the search loop cannot afford a
    # compile per candidate, but the CHOSEN plan compiles anyway —
    # lowering_diagnostics uses the same state-donating jit
    # _SpmdTrainingPlan steps with. The first real step compiles that jit
    # again through the call path; where the persistent compile cache is
    # on (core/compile_cache.py) it reads this compile back.
    if explored_winner is not None and env.lowering_postcheck:
        try:
            with span("plan:postcheck", cat="planner"):
                remats = plan.lowering_diagnostics(devices=devices)
        except Exception as e:  # noqa: BLE001 — diagnostics only
            log.warning("lowering post-check failed: %r", e)
        else:
            from tepdist_tpu.telemetry import observatory
            observatory.fold_remats(explored_winner.get("report"), remats)
            if remats:
                metrics().counter("involuntary_remat").inc(len(remats))
                log.warning(
                    "explore winner %r (axes=%s): XLA reported %d "
                    "involuntary full rematerialization(s) (%s) — the "
                    "chosen sharding forces recompute the cost model did "
                    "not price; consider a different topology",
                    explored_winner["kind"],
                    list(topology.device_axes()), len(remats),
                    ", ".join(remats[:3]))
    n_batch_leaves = len(jax.tree_util.tree_leaves(example_batch))
    tplan = _SpmdTrainingPlan(plan, params, opt_state, n_batch_leaves,
                              devices)
    if explored_winner is not None and "report" in explored_winner:
        tplan.exploration_report = explored_winner["report"]
    return tplan
