"""Client-side driver for multi-worker pipeline execution.

Reference parity: the master's BuildDistPlan + per-step coordination
(reference: service_rt.cc:175-216 and §3.4/§3.5 of SURVEY.md): ship
def-modules and per-worker task-DAG slices to each worker, push per-step
inputs, trigger ExecuteRemotePlan on every worker concurrently, and collect
the loss. Activations/cotangents flow worker-to-worker directly (the NCCL
p2p path becomes RPC raw-data pushes over DCN).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax

from tepdist_tpu.core.cluster_spec import ClusterSpec
from tepdist_tpu.parallel.pipeline import PipelineProgram
from tepdist_tpu.rpc import protocol
from tepdist_tpu.rpc.client import TepdistClient
from tepdist_tpu.runtime.coordinator import serialize_task
from tepdist_tpu.runtime.execution_plan import build_pipeline_task_dag
from tepdist_tpu.runtime.task_graph import TaskType
from tepdist_tpu.runtime.task_scheduler import TaskScheduler
from tepdist_tpu.telemetry import ledger as wire_ledger
from tepdist_tpu.telemetry import metrics
from tepdist_tpu.telemetry import span

log = logging.getLogger(__name__)


class DistributedPipelineSession:
    """Drive a pipeline across tepdist worker servers."""

    # Monotonic plan-generation counter (per master process): every
    # session/re-dispatch stamps its DispatchPlan and raw-data pushes with
    # a fresh generation, and workers drop pushes from older generations
    # (an evicted-but-alive worker resuming a wedged step cannot inject
    # stale activations into the rebuilt plan — r2 review finding).
    _gen_counter = 0

    def __init__(self, prog: PipelineProgram, cluster: ClusterSpec,
                 learning_rate: float = 0.01, optimizer=None,
                 elastic: bool = False, autosave_every: int = 1,
                 carry_state: bool = False,
                 carry_stages: Optional[Dict[int, List[int]]] = None,
                 wal_dir: Optional[str] = None,
                 master_epoch: Optional[int] = None,
                 adopt: bool = False):
        """``optimizer``: an optax GradientTransformation; its init and
        update functions are TRACED per stage (over that stage's owned
        params) and shipped to workers as serialized jaxprs — any optax
        chain runs worker-side. Falls back to SGD(learning_rate) when None
        (the reference's fixed-update posture).

        ``elastic=True`` arms AUTO re-dispatch (surplus over the reference,
        whose recovery is 'checkpoint + restart the cluster by hand'): the
        session checkpoints every ``autosave_every`` steps, and when a step
        fails on dead workers it rebuilds the WorkerPlans over the
        SURVIVING cluster, restores the union of all workers' shards from
        the shared checkpoint directory, and retries the step — no manual
        ``resume()`` call. Requires a shared TEPDIST_CKPT_DIR (the same
        contract the multi-worker save path already assumes).

        ``carry_state``/``carry_stages`` (live migration, ISSUE 18):
        when this session is the plan-swap half of a live migration, the
        DispatchPlan tells each worker to CARRY the named stages'
        optimizer slots across the plan swap (kept or just-adopted)
        instead of letting the fresh WorkerPlan lazily re-run opt_init.
        ``carry_stages`` maps task_index -> stage indices.

        ``wal_dir`` (control-plane crash safety, ISSUE 20): enable the
        durable write-ahead journal (runtime/controlplane.py) — plan
        dispatches, fleet membership, the per-step commit watermark and
        checkpoint registrations are journaled so a restarted master can
        ``readopt()`` the live fleet. Defaults to the TEPDIST_WAL_DIR
        knob; empty = disabled. Opening the WAL also arms epoch fencing:
        the session claims ``epoch = replayed epoch + 1`` and stamps it
        on every verb. ``master_epoch`` overrides the claimed epoch
        (used by the rebuild paths to keep the current fence).

        ``adopt=True`` (readopt() only): build all master-side plan
        state but ship NOTHING — no module transfer, no DispatchPlan.
        The fleet already holds the modules, the WorkerPlans, and the
        variables; the caller reconciles ``_plan_gen``/``_step`` from
        the WAL + Ping probes."""
        from tepdist_tpu.rpc.jaxpr_serde import serialize_closed_jaxpr

        self.prog = prog
        self.cluster = cluster
        self.lr = learning_rate
        # Wire compression for MASTER-dispatch envelopes (batch slices in
        # ExecuteStepSlice / TransferHostRawData): the TEPDIST_WIRE_DTYPE
        # knob, or the exploration winner's planned comm dtype. Latched
        # at construction like the workers latch theirs; floats only —
        # encode_literal never casts integer payloads.
        from tepdist_tpu.core.service_env import ServiceEnv as _SE
        self._wire_dtype = (_SE.get().tepdist_wire_dtype
                            or getattr(prog, "comm_dtype", "") or None)
        DistributedPipelineSession._gen_counter += 1
        self._plan_gen = DistributedPipelineSession._gen_counter
        self._optimizer = optimizer
        self._elastic = elastic
        self._autosave_every = autosave_every
        self._params_template = None
        S = prog.num_stages
        W = cluster.num_workers
        self.stage_worker = [cluster.workers[s % W].task_index
                             for s in range(S)]
        self.clients: Dict[int, TepdistClient] = {
            w.task_index: TepdistClient(w.address)
            for w in cluster.workers
        }
        # Control-plane WAL + epoch fence (ISSUE 20). The WAL opens (and
        # the epoch is claimed + durably journaled) BEFORE any RPC ships,
        # so a crash mid-construction still leaves the claimed epoch on
        # disk and a takeover cannot regress it.
        from tepdist_tpu.runtime import controlplane
        self._wal: Optional[controlplane.ControlPlaneWAL] = None
        self._epoch: Optional[int] = master_epoch
        wal_dir = wal_dir or _SE.get().tepdist_wal_dir or None
        self._wal_dir = wal_dir
        # An explicit master_epoch means the CALLER owns the WAL + fence
        # (rebuild paths hand theirs across the session swap; readopt
        # opens its own after replay) — never open a second writer here.
        if wal_dir and not adopt and master_epoch is None:
            env0 = _SE.get()
            state0 = controlplane.replay(wal_dir)
            self._wal = controlplane.ControlPlaneWAL(
                wal_dir,
                segment_bytes=env0.tepdist_wal_segment_mb * (1 << 20),
                snapshot_every=env0.tepdist_wal_snapshot_every,
                fsync=env0.tepdist_wal_fsync,
                on_error=self._wal_error)
            if self._epoch is None:
                self._epoch = state0.epoch + 1
            controlplane.log_epoch(self._wal, self._epoch)
        if self._epoch is not None:
            for c in self.clients.values():
                c.epoch = self._epoch
        # Pseudo device groups: one per worker (cross-worker placement).
        stage_devices = [(self.stage_worker[s],) for s in range(S)]
        self.dag, self.maps = build_pipeline_task_dag(prog, stage_devices)
        # Kept for fidelity reporting: dump_trace() embeds the predicted
        # per-task timeline so the merged trace is a self-contained
        # predicted-vs-measured input (telemetry/fidelity.py).
        self.schedule = TaskScheduler(self.dag).schedule()
        sched = self.schedule
        order = sched.order
        # Pre-dispatch gate (TEPDIST_VERIFY_PLAN): a broken DAG must not
        # reach the fleet — verify before any DispatchPlan ships.
        from tepdist_tpu.analysis.plan_verify import maybe_verify_plan
        maybe_verify_plan(self.dag, schedule=sched, prog=prog,
                          where="DistributedPipelineSession")

        # Per-worker ordered task lists + send routing.
        batch_set = set(prog.batch_flat_indices)
        self._batch_stages: Dict[int, List[int]] = {}
        for s in range(S):
            mod = prog.stages[s]
            for p in mod.param_positions():
                gi = mod.input_def_map[p][1]
                if gi in batch_set:
                    self._batch_stages.setdefault(s, []).append(gi)

        send_routes: Dict[int, Tuple[int, str]] = {}
        recv_keys: Dict[int, str] = {}
        for n in self.dag.nodes:
            if n.task_type == TaskType.RECV:
                send_id = n.input_specs[0][0]
                send_node = self.dag.node(send_id)
                if n.device_group != send_node.device_group:
                    key = f"t{send_id}"
                    send_routes[send_id] = (n.device_group[0], key)
                    recv_keys[n.id] = key

        self.loss_stage = next(s for s in range(S)
                               if 0 in prog.stages[s].graph_out_map)
        self.loss_worker = self.stage_worker[self.loss_stage]

        # Shared parameters are only summable when every consuming stage
        # lives on the OWNER's worker (the GA->APPLY gradient transfer has
        # no cross-worker Send/Recv yet); refuse silently-wrong plans.
        consumers: Dict[int, set] = {}
        for s in range(S):
            mod = prog.stages[s]
            for p in mod.param_positions():
                gi = mod.input_def_map[p][1]
                if gi not in batch_set:
                    consumers.setdefault(gi, set()).add(self.stage_worker[s])
        # Cross-worker shared params are handled by grad Send/Recv pairs in
        # the task DAG (build_pipeline_task_dag inserts them when the
        # sharing stages' device groups differ).
        self._param_consumers = consumers

        # Stage meta + module shipping. Owner stage of each param = min
        # consuming stage (matches build_pipeline_task_dag + executor).
        owner_stage: Dict[int, int] = {}
        for s in range(S):
            mod = prog.stages[s]
            for p in mod.param_positions():
                gi = mod.input_def_map[p][1]
                if gi not in batch_set:
                    owner_stage[gi] = min(owner_stage.get(gi, s), s)
        wired = self._wired_cots()
        for s in range(S):
            mod = prog.stages[s]
            ppos = [p for p in mod.param_positions()
                    if mod.input_def_map[p][1] not in batch_set]
            meta = {
                "owned_global_idx": [
                    mod.input_def_map[p][1] for p in ppos
                    if owner_stage[mod.input_def_map[p][1]] == s],
                "n_invars": len(mod.invars),
                "input_def_map": {str(k): list(v)
                                  for k, v in mod.input_def_map.items()},
                "batch_indices": sorted(
                    mod.input_def_map[p][1] for p in mod.param_positions()
                    if mod.input_def_map[p][1] in batch_set),
                "param_positions": ppos,
                "param_global_idx": [mod.input_def_map[p][1] for p in ppos],
                "param_avals": [
                    [list(mod.invars[p].aval.shape),
                     str(np.dtype(mod.invars[p].aval.dtype))]
                    for p in ppos],
                "loss_out": mod.graph_out_map.get(0, -1),
                "wired_cots": wired[s],
            }
            module = serialize_closed_jaxpr(
                prog.decomp.stage_closed_jaxpr(s), inline=False)
            blobs = [module]
            if optimizer is not None:
                owned_ppos = [p for p in ppos
                              if owner_stage[mod.input_def_map[p][1]] == s]
                owned_avals = [jax.ShapeDtypeStruct(
                    mod.invars[p].aval.shape, mod.invars[p].aval.dtype)
                    for p in owned_ppos]
                if owned_avals:
                    import optax as _optax

                    def opt_init(plist):
                        return optimizer.init(list(plist))

                    def opt_update(plist, state, glist):
                        updates, new_state = optimizer.update(
                            list(glist), state, list(plist))
                        return (_optax.apply_updates(list(plist), updates),
                                new_state)

                    init_closed = jax.make_jaxpr(opt_init)(owned_avals)
                    state_shape = jax.eval_shape(opt_init, owned_avals)
                    update_closed = jax.make_jaxpr(opt_update)(
                        owned_avals, state_shape, owned_avals)
                    meta["n_opt_state"] = len(
                        jax.tree_util.tree_leaves(state_shape))
                    blobs.append(serialize_closed_jaxpr(init_closed))
                    blobs.append(serialize_closed_jaxpr(update_closed))
            if not adopt:
                self.clients[self.stage_worker[s]].call(
                    "TransferModuleAndDefCtx",
                    {"module_id": s, "stage_meta": meta}, blobs)

        # Dispatch per-worker plans in global schedule order, with the GC
        # plan computed for that order (workers prune via mem_to_release).
        self.dag.build_gc_plan(order)
        pos = {tid: i for i, tid in enumerate(order)}
        for w in cluster.workers:
            ti = w.task_index
            tasks = sorted(
                (n for n in self.dag.nodes
                 if n.device_group and n.device_group[0] == ti),
                key=lambda n: pos[n.id])
            stage_param_gi = {}
            for s2 in range(S):
                mod2 = prog.stages[s2]
                stage_param_gi[str(s2)] = [
                    mod2.input_def_map[p][1]
                    for p in mod2.param_positions()
                    if mod2.input_def_map[p][1] not in batch_set]
            micro_rows = None
            if prog.batch_flat_indices:
                b0 = prog.graph.invars[prog.batch_flat_indices[0]]
                micro_rows = int(b0.aval.shape[prog.batch_dim])
            plan_meta = {
                "task_index": ti,
                "stage_param_gi": stage_param_gi,
                "micro_rows": micro_rows,
                "num_micro_batches": prog.num_micro_batches,
                "cluster": {"workers": [
                    {"ip": x.ip, "port": x.port,
                     "task_index": x.task_index}
                    for x in cluster.workers]},
                "send_routes": {str(k): list(v)
                                for k, v in send_routes.items()},
                "recv_keys": recv_keys,
                "learning_rate": learning_rate,
                # The winner's comm dtype rides to every worker: peer
                # host_push frames encode at this dtype when the local
                # TEPDIST_WIRE_DTYPE knob is unset.
                "comm_dtype": getattr(prog, "comm_dtype", "") or "",
                # ZeRO modifier: workers with >1 local data replica shard
                # their stage's optimizer state and bracket the apply
                # with reduce-scatter/all-gather.
                "zero": bool(getattr(prog, "zero", False)),
            }
            # client.call attaches the idempotency token: a retried
            # DispatchPlan whose original landed (response lost) must not
            # re-run — it would discard the fresh RawStore and any data
            # already pushed into it.
            dispatch_hdr = {
                "tasks": [serialize_task(n) for n in tasks],
                "plan_meta": plan_meta,
                "plan_gen": self._plan_gen,
            }
            if carry_state:
                dispatch_hdr["carry_state"] = True
                if carry_stages is not None:
                    dispatch_hdr["carry_stages"] = sorted(
                        carry_stages.get(ti, ()))
            if not adopt:
                self.clients[ti].call("DispatchPlan", dispatch_hdr)
        if not adopt:
            self._wal_log_plan()
        self._step = 0
        self._step_attempts = 0
        # Live migration state (ISSUE 18): revived workers queue here
        # (via the health monitor's on_revive hook) and are folded back
        # into the plan at the next step boundary; _known_workers keeps
        # every spec ever seen so a revived task_index can be re-dialed
        # after migrations shrank self.cluster past it.
        self._pending_rejoin: set = set()
        self._known_workers = {w.task_index: w for w in cluster.workers}
        self._last_step_wall_ms = 0.0
        self.last_migration: Optional[Dict[str, Any]] = None
        # Heartbeat monitor (surplus over the reference, which had no
        # failure detection at all — SURVEY §5.3).
        from tepdist_tpu.runtime.health import HealthMonitor
        self.health = HealthMonitor(self.clients,
                                    on_revive=self._note_revive)
        # Training-health sentinel: always on (the loss is already on
        # host each step, the check is a few float compares). The poller
        # thread is opt-in via TEPDIST_WATCH.
        from tepdist_tpu.core.service_env import ServiceEnv
        from tepdist_tpu.telemetry import watchtower
        env = ServiceEnv.get()
        self.sentinel = watchtower.TrainingSentinel(
            halt=env.tepdist_watch_halt)
        self._last_worker_ms: Dict[int, float] = {}
        self.watchtower: Optional[watchtower.Watchtower] = None
        if env.tepdist_watch:
            self.watchtower = watchtower.Watchtower(
                clients=[self.clients[ti]
                         for ti in sorted(self.clients)],
                interval_s=env.tepdist_watch_interval,
                slo_path=env.tepdist_slo_file or None,
                halt=env.tepdist_watch_halt)
            self.watchtower.sentinel = self.sentinel
            watchtower.set_active(self.watchtower)
            self.watchtower.start()

    def _wired_cots(self) -> List[List[int]]:
        out = []
        for s in range(self.prog.num_stages):
            mod = self.prog.stages[s]
            n_in = len(mod.invars)
            bwd_id = self.maps.bwd_tasks[(s, 0)]
            out.append(sorted(
                pos - n_in
                for pos in self.dag.node(bwd_id).input_specs
                if pos >= n_in))
        return out

    # ------------------------------------------------------------------
    def _assign_owners(self, params_template) -> Dict[int, set]:
        flat = jax.tree_util.tree_leaves(params_template)
        self._n_params = len(flat)
        self._params_tree = jax.tree_util.tree_structure(params_template)
        worker0 = self.cluster.workers[0].task_index
        self._owner = {}
        placement: Dict[int, set] = {}
        for gi in range(self._n_params):
            workers = self._param_consumers.get(gi) or {worker0}
            self._owner[gi] = min(workers)
            for ti in workers:
                placement.setdefault(ti, set()).add(gi)
        return placement

    # ------------------------------------------------------------------
    # Control-plane WAL helpers (ISSUE 20).
    def _plan_fingerprint(self) -> str:
        """Stable digest of what the fleet is running — enough for a
        re-adopting master to detect a WAL that describes a DIFFERENT
        program than the one it was handed."""
        import hashlib
        import json as _json
        payload = _json.dumps({
            "stages": self.prog.num_stages,
            "micro": self.prog.num_micro_batches,
            "stage_worker": list(self.stage_worker),
            "members": sorted(self.clients),
            "comm_dtype": getattr(self.prog, "comm_dtype", "") or "",
            "zero": bool(getattr(self.prog, "zero", False)),
        }, sort_keys=True).encode()
        return hashlib.blake2b(payload, digest_size=8).hexdigest()

    def _wal_log_plan(self) -> None:
        if self._wal is None:
            return
        from tepdist_tpu.runtime import controlplane
        prog = self.prog
        controlplane.log_plan(
            self._wal,
            plan_gen=self._plan_gen,
            fingerprint=self._plan_fingerprint(),
            plan_meta={"num_micro_batches": prog.num_micro_batches,
                       "comm_dtype": getattr(prog, "comm_dtype", "")
                       or "",
                       "zero": bool(getattr(prog, "zero", False))},
            stage_worker=list(self.stage_worker),
            members={w.task_index: w.address
                     for w in self.cluster.workers})

    def _wal_error(self, exc: BaseException) -> None:
        """ControlPlaneWAL on_error hook (writer thread): a journal that
        stops journaling silently would turn the next takeover into a
        rollback — surface it loudly on the alert board."""
        from tepdist_tpu.telemetry import watchtower
        watchtower.control_plane_alert(
            f"control-plane WAL write failed: {exc!r}",
            wal_dir=self._wal_dir or "")

    def load_variables(self, params) -> None:
        flat = jax.tree_util.tree_leaves(params)
        placement = self._assign_owners(params)
        self._params_template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
            params)
        for ti, gis in placement.items():
            for gi in sorted(gis):
                self.clients[ti].transfer_to_server_host(
                    np.asarray(flat[gi]), gi, variable=True)

    def fetch_variables(self):
        by_owner: Dict[int, List[int]] = {}
        for gi in range(self._n_params):
            by_owner.setdefault(self._owner[gi], []).append(gi)
        flat: Dict[int, Any] = {}
        for ti, gis in by_owner.items():
            fetched = self.clients[ti].fetch_resource_vars(gis)
            flat.update(fetched)
        leaves = [flat[gi] for gi in range(self._n_params)]
        return jax.tree_util.tree_unflatten(self._params_tree, leaves)

    # ------------------------------------------------------------------
    def step(self, *batch) -> float:
        # The ledger step window brackets the WHOLE master-side step —
        # including recovery re-execution, which widens the same window —
        # and tags this thread's pack/rpc records with step=. The
        # master_step span gives the fidelity attribution the same frame:
        # without it, host serde on the push path (before any worker's
        # run_step opens) would be clamped out of the step window.
        # A revived (or newly registered) worker folds back into the plan
        # HERE, at the step boundary — the join half of live migration.
        if self._elastic and self._pending_rejoin:
            self._absorb_rejoin()
        step = self._step
        self._last_worker_ms = {}
        t0 = time.monotonic()
        with wire_ledger.step_scope(step), \
                span("master_step", cat="step", step=step):
            loss = self._step_body(*batch)
        # Watchtower feed: step wall + per-worker dispatch walls (the
        # straggler scorer's primary signal) — one histogram observe and
        # a deque append per step when the watchtower is active.
        wall_ms = (time.monotonic() - t0) * 1e3
        self._last_step_wall_ms = wall_ms
        m = metrics()
        m.histogram("step_time_ms").observe(wall_ms)
        for ti, ms in self._last_worker_ms.items():
            m.histogram(f"worker_step_ms:{ti}").observe(ms)
        from tepdist_tpu.telemetry import watchtower
        watchtower.observe_step(step, wall_ms,
                                dict(self._last_worker_ms))
        return loss

    def _step_body(self, *batch) -> float:
        from tepdist_tpu.core.service_env import ServiceEnv
        if ServiceEnv.get().tepdist_batch_dispatch:
            return self._step_coalesced(batch)
        return self._step_per_verb(batch)

    def _step_coalesced(self, batch) -> float:
        """Coalesced dispatch (TEPDIST_BATCH_DISPATCH, default on): ONE
        ExecuteStepSlice RPC per worker carries its whole per-step task
        slice — every micro-batch slice it consumes plus the execute
        trigger — and its losses come back in the same reply envelope
        (cf. coalesced MPMD dispatch, arXiv:2412.14374). Per-worker
        envelopes are sliced + encoded on THIS thread and each worker's
        dispatch thread starts immediately after its pack, so packing
        worker k+1 overlaps the RPC and compute of workers <= k
        (send-side overlap; the legacy path packed everything before
        triggering anything). Push and execute failures land in ONE
        errors dict feeding the same _recover_step ladder — batch slices
        re-encode on retry, and the worker-side completed-step cache +
        idempotent keyed puts keep replays bit-identical."""
        prog = self.prog
        M = prog.num_micro_batches
        bdim = prog.batch_dim
        leaves = jax.tree_util.tree_leaves(batch)
        step = self._step
        by_worker: Dict[int, List[int]] = {}
        for s, gis in self._batch_stages.items():
            by_worker.setdefault(self.stage_worker[s], []).extend(gis)
        results: Dict[int, dict] = {}
        errors: Dict[int, Exception] = {}
        threads: List[threading.Thread] = []

        def run(ti, client, header, blobs):
            t0 = time.monotonic()
            try:
                resp = client.call("ExecuteStepSlice", header, blobs)
                r, _ = protocol.unpack(resp)
                if not r.get("ok", False):
                    raise RuntimeError(
                        f"worker {ti} dropped step {step}: stale plan "
                        f"generation {r.get('stale_plan_gen')}")
                results[ti] = r
                self._last_worker_ms[ti] = (time.monotonic() - t0) * 1e3
            except Exception as e:  # noqa: BLE001
                errors[ti] = e

        with wire_ledger.client_scope("master:dispatch"):
            for ti, client in self.clients.items():
                entries: List[dict] = []
                blobs: List[bytes] = []
                for gi in by_worker.get(ti, ()):
                    leaf = np.asarray(leaves[gi - self._n_params])
                    msize = leaf.shape[bdim] // M
                    for m in range(M):
                        sl = np.take(leaf,
                                     range(m * msize, (m + 1) * msize),
                                     axis=bdim)
                        meta, blob = protocol.encode_literal(
                            sl, wire_dtype=self._wire_dtype)
                        entries.append(
                            {"raw_key": f"batch:{step}:{m}:{gi}",
                             "literal": meta})
                        blobs.append(blob)
                t = threading.Thread(
                    target=run,
                    args=(ti, client,
                          {"step": step, "plan_gen": self._plan_gen,
                           "raw_multi": entries}, blobs),
                    daemon=True)
                threads.append(t)
                t.start()
            self._join_with_heartbeat(threads, errors)
        # Snapshot: abandoned daemon threads (still blocked past the grace
        # join) may write into `errors` while we iterate it below.
        errors = dict(errors)
        if errors:
            return self._recover_step(errors, batch, threads=threads)
        return self._finish_step(results)

    def _step_per_verb(self, batch) -> float:
        """Legacy per-verb dispatch (TEPDIST_BATCH_DISPATCH=0): one
        TransferHostRawData push per consuming (stage, leaf), then one
        ExecuteRemotePlan per worker. Kept both as the coalescing
        baseline (bench: dispatch_coalesce_x) and as the fallback knob."""
        prog = self.prog
        M = prog.num_micro_batches
        bdim = prog.batch_dim
        leaves = jax.tree_util.tree_leaves(batch)
        step = self._step
        # Push micro-batch slices to the workers whose stages consume them.
        # A dead worker surfaces HERE first (connection refused) — route it
        # through the same failure path as execution errors so elastic
        # re-dispatch can react before anything runs.
        push_errors: Dict[int, Exception] = {}
        # The ledger "master:*" scopes are dispatch envelopes, not wire
        # verbs: they attribute the master's own Python (slicing, header
        # assembly, thread fan-out, completion wait) to the
        # rpc_orchestration bucket of the gap table instead of leaving it
        # unattributed. Nested real-verb scopes still win for their span.
        with wire_ledger.client_scope("master:push"):
            for s, gis in self._batch_stages.items():
                ti = self.stage_worker[s]
                if ti in push_errors:
                    continue
                for gi in gis:
                    leaf = np.asarray(leaves[gi - self._n_params])
                    msize = leaf.shape[bdim] // M
                    try:
                        # All M micro slices in ONE RPC (per-micro round
                        # trips dominated the fleet step time).
                        entries, blobs = [], []
                        for m in range(M):
                            sl = np.take(leaf,
                                         range(m * msize, (m + 1) * msize),
                                         axis=bdim)
                            meta, blob = protocol.encode_literal(
                                sl, wire_dtype=self._wire_dtype)
                            entries.append(
                                {"raw_key": f"batch:{step}:{m}:{gi}",
                                 "literal": meta})
                            blobs.append(blob)
                        self.clients[ti].call(
                            "TransferHostRawData",
                            {"raw_multi": entries, "step": step,
                             "plan_gen": self._plan_gen}, blobs)
                    except Exception as e:  # noqa: BLE001
                        push_errors[ti] = e
                        break
        if push_errors:
            # Same transient/permanent ladder as the execute path below: a
            # push can fail transiently without the worker being gone, and
            # re-pushing the same keys is idempotent.
            return self._recover_step(push_errors, batch)
        # Run every worker's plan concurrently.
        results: Dict[int, dict] = {}
        errors: Dict[int, Exception] = {}

        def run(ti, client):
            t0 = time.monotonic()
            try:
                resp = client.call("ExecuteRemotePlan", {"step": step})
                results[ti], _ = protocol.unpack(resp)
                self._last_worker_ms[ti] = (time.monotonic() - t0) * 1e3
            except Exception as e:  # noqa: BLE001
                errors[ti] = e

        threads = [threading.Thread(target=run, args=(ti, c), daemon=True)
                   for ti, c in self.clients.items()]
        with wire_ledger.client_scope("master:execute"):
            for t in threads:
                t.start()
            self._join_with_heartbeat(threads, errors)
        # Snapshot: abandoned daemon threads (still blocked past the grace
        # join) may write into `errors` while we iterate it below.
        errors = dict(errors)
        if errors:
            return self._recover_step(errors, batch, threads=threads)
        return self._finish_step(results)

    def _finish_step(self, results: Dict[int, dict]) -> float:
        from tepdist_tpu.telemetry.watchtower import WatchHalt
        self._step += 1
        self._redispatch_attempts = 0   # a full step succeeded: reset cap
        self._step_attempts = 0
        if self._wal is not None:
            # Async group commit: the step record rides the next fsync
            # batch off the critical path. Losing the tail record on a
            # crash resumes ONE step early — absorbed bit-identically by
            # the workers' completed-step caches.
            from tepdist_tpu.runtime import controlplane
            controlplane.log_step(self._wal, self._step - 1)
        losses = results[self.loss_worker].get("losses", [])
        if (self._elastic and self._autosave_every > 0
                and self._step % self._autosave_every == 0):
            self.save()
        loss = float(sum(losses) / max(len(losses), 1))
        # Training-health sentinel: advisory alerts publish to the board
        # and keep training; in halt mode (TEPDIST_WATCH_HALT=nan) a
        # non-finite loss fences the fleet through the AbortStep path —
        # the same fence the transient-fault retry uses, so workers
        # return at fence latency and stay restartable — before the halt
        # propagates to the caller.
        try:
            self.sentinel.observe(self._step - 1, loss)
        except WatchHalt:
            log.error("watchtower halt at step %d (loss=%r): fencing "
                      "fleet", self._step - 1, loss)
            self._reset_fleet_step()
            raise
        return loss

    # ------------------------------------------------------------------
    # Transient-vs-permanent recovery ladder (ISSUE pr3): a mid-step fault
    # whose workers all still answer Ping is TRANSIENT — fence the fleet,
    # clear the abort latch, and re-execute the SAME step from in-memory
    # variables (worker-side staged commits + completed-step caches make
    # the re-run bit-identical, zero checkpoint rollback). Only a
    # heartbeat-dead worker escalates to elastic re-dispatch / raise.
    max_step_retries: int = 3

    def _recover_step(self, errs: Dict[int, Exception], batch,
                      threads=()) -> float:
        from tepdist_tpu.rpc import retry as _retry

        status = self.health.check_once()
        newly_dead = {ti for ti in errs if not status.get(ti, False)}
        self.health.mark_dead(newly_dead)
        if self._wal is not None and newly_dead:
            from tepdist_tpu.runtime import controlplane
            for ti in sorted(newly_dead):
                w = self._known_workers.get(ti)
                controlplane.log_member(
                    self._wal, ti, w.address if w else "", action="dead")
        # A straggler thread still alive here means some ExecuteRemotePlan
        # may STILL be running server-side; likewise a deadline-exceeded
        # execute on a ping-alive worker. Re-executing concurrently with
        # the original would double-run the step, so neither qualifies as
        # a safe transient retry.
        stragglers = any(t.is_alive() for t in threads)
        deadline_errs = any(_retry._is_deadline_exc(e)
                            for e in errs.values())
        if not newly_dead and not stragglers and not deadline_errs:
            if self._step_attempts < self.max_step_retries:
                self._step_attempts += 1
                metrics().counter("step_retries").inc()
                log.warning(
                    "step %d fault looks transient (all pings ok); fencing "
                    "fleet and re-executing same step from in-memory state "
                    "(attempt %d/%d): %s", self._step, self._step_attempts,
                    self.max_step_retries,
                    {ti: repr(e) for ti, e in errs.items()})
                self._reset_fleet_step()
                return self.step(*batch)
            raise RuntimeError(
                f"step {self._step} still failing after "
                f"{self._step_attempts} transient retries: {errs}")
        if self._elastic:
            attempts = getattr(self, "_redispatch_attempts", 0)
            if attempts >= self.cluster.num_workers:
                raise RuntimeError(
                    f"elastic re-dispatch gave up after {attempts} "
                    f"attempts; worker failures: {errs}")
            self._redispatch_attempts = attempts + 1
            # Recovery rung 1: LIVE migration — replan over the survivors
            # and reshard in place (worker→worker shard moves, no
            # checkpoint round-trip, no rollback). Rung 2 on any failure:
            # the checkpoint-restore re-dispatch.
            try:
                self._live_migrate()
            except Exception as e:  # noqa: BLE001 — rung 2 handles it
                from tepdist_tpu.runtime.migration import (
                    MigrationInfeasible,
                )
                lvl = (log.warning if isinstance(e, MigrationInfeasible)
                       else log.exception)
                lvl("live migration failed (%r); falling back to "
                    "checkpoint re-dispatch", e)
                self._auto_redispatch()
            return self.step(*batch)   # retry on the new plan
        raise RuntimeError(
            f"worker failures: {errs}; dead={sorted(self.health.dead)}"
            " — restore the cluster and resume from checkpoint")

    def _fence_fleet(self) -> None:
        """AbortStep every live worker: wakes recv waits blocked on data a
        failed peer will never send, so their ExecuteRemotePlan RPCs
        return now instead of at recv-timeout."""
        for ti, client in self.clients.items():
            if ti in self.health.dead:
                continue
            try:
                client.call("AbortStep", {}, timeout=self.health.timeout,
                            max_attempts=2)
            except Exception:  # noqa: BLE001 — dying too; classified later
                pass

    def _reset_fleet_step(self) -> None:
        """Fence then clear: AbortStep latches the abort flag (waking any
        remaining blocked recv), then ``reset`` clears it WITHOUT dropping
        the raw store's data — the retry re-executes from already-received
        inputs, and workers that finished the step serve their cached
        result instead of re-running."""
        for ti, client in self.clients.items():
            if ti in self.health.dead:
                continue
            for hdr in ({}, {"reset": True}):
                try:
                    client.call("AbortStep", hdr,
                                timeout=self.health.timeout, max_attempts=2)
                except Exception:  # noqa: BLE001 — best-effort; the retry
                    pass           # itself surfaces anything still broken

    # ------------------------------------------------------------------
    abort_grace_s: float = 10.0   # how long to wait for aborted RPCs

    def _join_with_heartbeat(self, threads, errors: Dict[int, Exception],
                             grace_s: Optional[float] = None) -> None:
        """Join the per-worker ExecuteRemotePlan threads, heartbeating the
        fleet while they run. Without this, a worker dying MID-step is only
        noticed when some RPC times out (recv timeout 60s / RPC timeout
        300s). With it, the heartbeat declares the worker dead within
        ~interval*max_misses seconds, AbortStep wakes the surviving
        workers' blocked recvs, and the elastic path reacts immediately.
        Reference parity: none — the reference has no mid-step failure
        detection at all (SURVEY §5.3)."""
        if grace_s is None:
            grace_s = self.abort_grace_s
        # Cap the poll so a worker ERROR (not just a death) fences peers at
        # ~poll latency rather than recv-timeout latency; Pings are cheap.
        poll = max(min(self.health.interval, 2.0), 0.25)
        while True:
            alive = [t for t in threads if t.is_alive()]
            if not alive:
                return
            alive[0].join(timeout=poll)
            if any(t.is_alive() for t in threads):
                if errors:
                    # Some worker already failed while peers still run:
                    # their recvs may block on data the failed worker will
                    # never send. Fence NOW; _recover_step classifies the
                    # fault as transient (retry) or permanent (elastic).
                    self._fence_fleet()
                    deadline = time.time() + grace_s
                    for t in threads:
                        t.join(timeout=max(0.0, deadline - time.time()))
                    return
                before = set(self.health.dead)
                self.health.check_once()
                newly_dead = self.health.dead - before
                if newly_dead:
                    for ti in self.health.dead:
                        errors.setdefault(ti, RuntimeError(
                            "worker died mid-step (heartbeat)"))
                    # Wake survivors' recv waits so their RPCs return now.
                    self._fence_fleet()
                    deadline = time.time() + grace_s
                    for t in threads:
                        t.join(timeout=max(0.0, deadline - time.time()))
                    return

    # ------------------------------------------------------------------
    def _auto_redispatch(self) -> None:
        """Rebuild WorkerPlans over the surviving cluster and restore from
        the last shared checkpoint (VERDICT r1 item 8: dead-worker
        callback -> automatic rebuild + restore, no manual resume). The
        surviving workers adopt the dead workers' stages; variable
        placement is re-derived from the parameter template; each survivor
        restores the UNION of all workers' checkpoint shards."""
        metrics().counter("elastic_redispatch").inc()
        dead = set(self.health.dead)
        survivors = [w for w in self.cluster.workers
                     if w.task_index not in dead]
        if not survivors:
            raise RuntimeError("no surviving workers to re-dispatch onto")
        if self._params_template is None:
            raise RuntimeError("elastic recovery requires load_variables "
                               "to have been called")
        log.warning("elastic re-dispatch: dead=%s survivors=%s",
                    sorted(dead), [w.task_index for w in survivors])
        self.health.stop()
        for c in self.clients.values():
            try:
                c.close()
            except Exception:  # noqa: BLE001
                pass
        template = self._params_template
        elastic, autosave = self._elastic, self._autosave_every
        attempts = getattr(self, "_redispatch_attempts", 0)
        wal, epoch, wdir = self._wal, self._epoch, self._wal_dir
        fresh = DistributedPipelineSession(
            self.prog, ClusterSpec(survivors),
            learning_rate=self.lr, optimizer=self._optimizer,
            elastic=False,   # avoid recursion while adopting
            master_epoch=epoch)   # keep the fence; caller owns the WAL
        self.__dict__.update(fresh.__dict__)
        self._elastic, self._autosave_every = elastic, autosave
        self._redispatch_attempts = attempts
        self._params_template = template
        self._wal, self._epoch, self._wal_dir = wal, epoch, wdir
        self._wal_log_plan()
        self._assign_owners(template)
        restored = -1
        for c in self.clients.values():
            restored = c.do_remote_restore(global_step=-1, all_shards=True)
        lost = self._step - max(restored, 0)
        self._step = restored if restored >= 0 else 0
        if lost > 0:
            metrics().counter("checkpoint_rollback_steps").inc(lost)
            log.warning(
                "elastic re-dispatch ROLLED BACK %d step(s) to the last "
                "checkpoint (step %d): updates since then are discarded "
                "and those step indices will be re-run (autosave_every=%d "
                "bounds the rollback)", lost, self._step,
                self._autosave_every)
        log.warning("elastic re-dispatch complete: resumed at step %d",
                    self._step)

    # ------------------------------------------------------------------
    # Live plan migration (ISSUE 18): replan + reshard in place on fleet
    # shape change — no checkpoint round-trip, no rollback. The heavy
    # lifting (dirty probe, source-selection ladder, move planning) lives
    # in runtime/migration.py; shard moves execute worker→worker over the
    # FetchShard/AdoptShard verbs.
    def _note_revive(self, ti: int) -> None:
        """HealthMonitor on_revive hook: queue the worker for rejoin at
        the next step boundary (never migrate from the heartbeat
        thread — migration swaps the plan under the stepping thread)."""
        if self._elastic:
            self._pending_rejoin.add(ti)
            log.warning("worker %d revived: queued for rejoin at the "
                        "next step boundary", ti)

    def _absorb_rejoin(self) -> None:
        rejoin = sorted(self._pending_rejoin)
        self._pending_rejoin.clear()
        have = {w.task_index for w in self.cluster.workers}
        specs = [self._known_workers[ti] for ti in rejoin
                 if ti in self._known_workers and ti not in have]
        for ti in rejoin:
            self.health.revive(ti)
        if not specs:
            return
        try:
            self.migrate_to_fleet(
                ClusterSpec(list(self.cluster.workers) + specs))
        except Exception as e:  # noqa: BLE001 — rejoin is opportunistic
            log.warning("rejoin migration failed (%r); continuing on the "
                        "current fleet", e)

    def register_worker(self, spec) -> Dict[str, Any]:
        """Fold a NEW (or returned) worker into the running plan via live
        migration. ``spec``: a WorkerSpec whose server is already up."""
        self._known_workers[spec.task_index] = spec
        workers = [w for w in self.cluster.workers
                   if w.task_index != spec.task_index] + [spec]
        return self.migrate_to_fleet(ClusterSpec(workers))

    def _live_migrate(self) -> Dict[str, Any]:
        from tepdist_tpu.runtime.migration import MigrationInfeasible
        dead = set(self.health.dead)
        survivors = [w for w in self.cluster.workers
                     if w.task_index not in dead]
        if not survivors:
            raise MigrationInfeasible("no surviving workers to migrate "
                                      "onto")
        return self.migrate_to_fleet(ClusterSpec(survivors))

    def _migration_budget_ms(self, moved_bytes: float) -> float:
        """Stall budget ≈ one step wall + shard-move time (the ISSUE 18
        target); the watchtower's stalled escalation fires past it. The
        move term assumes a conservative 50 MB/s DCN floor."""
        step_ms = self._last_step_wall_ms or 1000.0
        return max(step_ms + moved_bytes / 50e6 * 1e3 + 2000.0, 5000.0)

    def _replan_driver(self, new_cluster: ClusterSpec) -> Optional[str]:
        """Re-run exploration on the new fleet shape (when this session
        carries an exploration report) and name WHY the winner moved via
        plan_diff; sessions built directly from a prog fall back to the
        stage-remap driver (the s % W map itself changed)."""
        report = getattr(self, "exploration_report", None)
        if report:
            try:
                from tepdist_tpu.parallel.exploration import (
                    replan_for_fleet,
                )
                new_report, diff = replan_for_fleet(
                    report, new_cluster.total_devices,
                    n_workers=new_cluster.num_workers)
                self.exploration_report = new_report
                return diff.get("driver")
            except Exception as e:  # noqa: BLE001 — driver is advisory
                log.warning("fleet replan failed (%r); using stage-remap "
                            "driver", e)
        if new_cluster.num_workers != self.cluster.num_workers:
            return "candidate_set_change"
        return None

    def migrate_to_fleet(self, new_cluster: ClusterSpec) -> Dict[str, Any]:
        """Migrate the running plan onto ``new_cluster`` in place: fence,
        probe dirty workers, plan the shard moves, stream them
        worker→worker (AdoptShard), then swap the plan (fresh dispatch
        with carry_state) and resume at the SAME step — bit-exact
        trajectory when no wire compression is configured (comm_dtype
        set => banded, see TUTORIAL §20). Returns the migration record
        (also kept as ``self.last_migration``)."""
        from tepdist_tpu.runtime import migration
        from tepdist_tpu.telemetry import watchtower
        if self._params_template is None:
            raise migration.MigrationInfeasible(
                "live migration requires load_variables to have been "
                "called")
        t0 = time.monotonic()
        self._migration_seq = getattr(self, "_migration_seq", 0) + 1
        mig_id = f"mig{self._migration_seq}-step{self._step}"
        driver = self._replan_driver(new_cluster)
        template_flat = jax.tree_util.tree_leaves(self._params_template)
        moved_bytes = sum(
            float(np.prod(t.shape)) * np.dtype(t.dtype).itemsize
            for t in template_flat)
        watchtower.migration_started(
            mig_id,
            detail=(f"{self.cluster.num_workers} -> "
                    f"{new_cluster.num_workers} workers at step "
                    f"{self._step}"),
            driver=driver,
            budget_ms=self._migration_budget_ms(moved_bytes))
        try:
            stats = self._do_migrate(new_cluster, mig_id)
        except Exception as e:  # noqa: BLE001 — alert then re-raise
            watchtower.migration_completed(mig_id, failed=True,
                                           detail=repr(e))
            raise
        stall_ms = (time.monotonic() - t0) * 1e3
        m = metrics()
        m.counter("elastic_migrations").inc()
        m.gauge("migration_stall_ms").set(stall_ms)
        m.histogram("migration_stall_ms").observe(stall_ms)
        watchtower.migration_completed(mig_id, stall_ms=stall_ms)
        self.last_migration = {"id": mig_id, "stall_ms": stall_ms,
                               "driver": driver, "step": self._step,
                               **stats}
        log.warning("live migration %s complete in %.0f ms: %s", mig_id,
                    stall_ms, stats)
        return self.last_migration

    def _do_migrate(self, new_cluster: ClusterSpec,
                    mig_id: str) -> Dict[str, Any]:
        from tepdist_tpu.runtime import migration
        prog = self.prog
        S = prog.num_stages
        dead = set(self.health.dead)
        template_flat = jax.tree_util.tree_leaves(self._params_template)
        templates = [(tuple(t.shape), np.dtype(t.dtype).name)
                     for t in template_flat]
        # 1. Fence: latch the abort flag fleet-wide so any straggler
        # still inside the fenced step abandons its STAGED writes — the
        # dirty probe below then sees a stable committed/dirty split.
        self._fence_fleet()
        # 2. Dirty probe: survivors that already committed the fenced
        # step locally are ahead of the agreed state.
        dirty, unreachable, ckpt_steps = migration.probe_dirty(
            self.clients, self._step, dead)
        dead |= unreachable
        new_workers = [w for w in new_cluster.workers
                       if w.task_index not in dead]
        if not new_workers:
            raise migration.MigrationInfeasible(
                "every destination worker is dead")
        new_cluster = ClusterSpec(new_workers)
        # 3. Checkpoint availability at EXACTLY the fenced step (the
        # elastic autosave writes one per committed step) — the fallback
        # source for state only dead/dirty workers hold. Probed through
        # the workers' eyes (their shared checkpoint dir), not the
        # master's filesystem.
        ckpt_step = self._step if (self._step > 0
                                   and self._step in ckpt_steps) else -1
        # 4. Old/new fleet snapshots (placement re-derived with the same
        # owner rule _assign_owners uses).
        cons = migration.stage_param_consumers(prog)
        n_params = len(template_flat)
        old_pl, old_owner = migration.placement_for(
            self.stage_worker, cons, n_params,
            self.cluster.workers[0].task_index)
        old = migration.FleetSnapshot(
            list(self.stage_worker), old_pl, old_owner,
            {w.task_index: w.address for w in self.cluster.workers})
        W2 = new_cluster.num_workers
        new_sw = [new_cluster.workers[s % W2].task_index
                  for s in range(S)]
        new_pl, new_owner = migration.placement_for(
            new_sw, cons, n_params, new_cluster.workers[0].task_index)
        new = migration.FleetSnapshot(
            new_sw, new_pl, new_owner,
            {w.task_index: w.address for w in new_cluster.workers})
        # 5. Move plan: per-destination AdoptShard lists + the stages
        # whose optimizer slots ride the DispatchPlan carry.
        moves, carry = migration.plan_moves(
            old, new, templates, dirty, dead, self._step, ckpt_step,
            wire_dtype=self._wire_dtype)
        # 6. Stream the shards worker→worker BEFORE the plan swap: the
        # sources still hold the old plan's state, and adopted optimizer
        # slots stage server-side for the carry merge.
        adopt_errors: Dict[int, Exception] = {}

        def adopt(ti: int, addr: str) -> None:
            cli = self.clients.get(ti)
            owned = cli is None
            try:
                if cli is None:   # joining worker: not in the old fleet
                    cli = TepdistClient(addr)
                cli.adopt_shard(moves[ti], migration_id=mig_id)
            except Exception as e:  # noqa: BLE001
                adopt_errors[ti] = e
            finally:
                if owned and cli is not None:
                    cli.close()

        threads = [threading.Thread(target=adopt,
                                    args=(ti, new.addresses[ti]),
                                    daemon=True)
                   for ti in sorted(moves)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if adopt_errors:
            raise migration.MigrationInfeasible(
                f"shard adoption failed: "
                f"{ {ti: repr(e) for ti, e in adopt_errors.items()} }")
        # 7. Plan swap: fresh dispatch over the new fleet with
        # carry_state (variables persist server-side; carried/adopted
        # optimizer slots survive the WorkerPlan swap). Same
        # session-rebuild dance as _auto_redispatch — WITHOUT the
        # checkpoint restore and WITHOUT touching self._step.
        self.health.stop()
        for c in self.clients.values():
            try:
                c.close()
            except Exception:  # noqa: BLE001
                pass
        template = self._params_template
        saved_step = self._step
        elastic, autosave = self._elastic, self._autosave_every
        attempts = getattr(self, "_redispatch_attempts", 0)
        mig_seq = self._migration_seq
        pending = set(self._pending_rejoin) - {w.task_index
                                              for w in new_cluster.workers}
        known = dict(self._known_workers)
        known.update({w.task_index: w for w in new_cluster.workers})
        report = getattr(self, "exploration_report", None)
        wal, epoch, wdir = self._wal, self._epoch, self._wal_dir
        fresh = DistributedPipelineSession(
            prog, new_cluster, learning_rate=self.lr,
            optimizer=self._optimizer, elastic=False,
            carry_state=True, carry_stages=carry,
            master_epoch=epoch)   # keep the fence; caller owns the WAL
        self.__dict__.update(fresh.__dict__)
        self._elastic, self._autosave_every = elastic, autosave
        self._redispatch_attempts = attempts
        self._params_template = template
        self._step = saved_step
        self._migration_seq = mig_seq
        self._pending_rejoin = pending
        self._known_workers = known
        self._wal, self._epoch, self._wal_dir = wal, epoch, wdir
        self._wal_log_plan()
        if report is not None:
            self.exploration_report = report
        self._assign_owners(template)
        # Re-bind the revive hook to THIS session (fresh's hook is gated
        # off by its elastic=False construction).
        self.health.on_revive = self._note_revive
        stats = migration.summarize(moves)
        stats.update({"dirty": sorted(dirty), "dead": sorted(dead),
                      "ckpt_step": ckpt_step,
                      "carried_stages": sum(map(len, carry.values())),
                      "new_workers": [w.task_index
                                      for w in new_cluster.workers]})
        return stats

    # ------------------------------------------------------------------
    # Checkpoint + elastic recovery (beyond the reference: SURVEY §5.3
    # documents recovery there as "checkpoint + restart the cluster" with
    # no detection; here detection is HealthMonitor and resumption is one
    # call against a repaired cluster).
    def save(self, max_to_keep: int = 5) -> None:
        """Every worker persists its own variables (per-worker shards,
        reference: per-worker BundleWriter files)."""
        for c in self.clients.values():
            c.do_remote_save(max_to_keep=max_to_keep,
                             global_step=self._step)
        if self._wal is not None:
            from tepdist_tpu.runtime import controlplane
            controlplane.log_ckpt(self._wal, self._step)
            self._wal.maybe_snapshot()

    def restore(self, global_step: int = -1) -> None:
        for c in self.clients.values():
            c.do_remote_restore(global_step=global_step)

    def dump_trace(self, path=None, clear: bool = False,
                   include_predicted: bool = True):
        """Pull every worker's span buffer + metrics (GetTelemetry),
        clock-align them (NTP-midpoint offset from the round-trip), and
        write ONE merged Perfetto-loadable timeline. ``path=None``
        lands in ``$TEPDIST_DUMP_DIR``; returns the written path or None.
        Dead workers are skipped, not fatal. The simulator's predicted
        timeline rides in the trace metadata (``fidelity.predicted``) so
        tools/fidelity_report.py and trace_summary.py can join
        predicted-vs-measured offline from the file alone."""
        from tepdist_tpu.telemetry import dump_merged_trace
        live = [c for ti, c in sorted(self.clients.items())
                if ti not in self.health.dead]
        extra = {}
        if include_predicted:
            extra["fidelity"] = {
                "predicted": self.schedule.predicted_timeline(self.dag),
                "makespan_ms": self.schedule.makespan * 1e3,
                "policy": self.schedule.policy,
            }
        # When the program came out of exploration, the decision record
        # (telemetry/observatory.py) rides next to the fidelity payload:
        # one trace file feeds both plan_explain and fidelity_report.
        report = getattr(self, "exploration_report", None)
        if report:
            extra["exploration"] = report
        return dump_merged_trace(live, path=path, name="trace",
                                 clear=clear,
                                 extra_metadata=extra or None)

    @classmethod
    def resume(cls, prog, cluster, params_template, optimizer=None,
               learning_rate=0.01, global_step: int = -1
               ) -> "DistributedPipelineSession":
        """Rebuild a session against a repaired cluster and restore every
        worker's variables from its local checkpoint shards.
        ``params_template``: pytree (values or ShapeDtypeStructs) giving the
        parameter structure for ownership/fetch routing."""
        sess = cls(prog, cluster, learning_rate=learning_rate,
                   optimizer=optimizer)
        sess._assign_owners(params_template)
        sess.restore(global_step)
        return sess

    @classmethod
    def readopt(cls, prog, cluster, params_template, optimizer=None,
                learning_rate=0.01, wal_dir: Optional[str] = None,
                elastic: bool = False, autosave_every: int = 1
                ) -> "DistributedPipelineSession":
        """Re-adopt a LIVE fleet after a master crash (ISSUE 20): replay
        the control-plane WAL, claim the next epoch (fencing out the old
        master if it revives), Ping the still-running workers to learn
        the fleet's actual plan generation / completed steps, and resume
        at the journaled watermark — WITHOUT re-shipping modules, plans,
        or weights. The fleet's RawStores, WorkerPlans and variables are
        all still server-side; workers ahead of the watermark serve
        their completed-step caches (bit-identical re-run), workers
        blocked in recvs are unwedged by the fence+reset.

        Unreachable workers fall to the existing elastic ladder (live
        migration, then checkpoint re-dispatch via restore_resharded
        move planning). Records ``master_recover_ms`` (gauge + attr) and
        bumps ``master_takeovers``."""
        from tepdist_tpu.core.service_env import ServiceEnv
        from tepdist_tpu.runtime import controlplane
        t0 = time.monotonic()
        env = ServiceEnv.get()
        wal_dir = wal_dir or env.tepdist_wal_dir or None
        if not wal_dir:
            raise ValueError(
                "readopt requires a WAL directory (wal_dir argument or "
                "TEPDIST_WAL_DIR)")
        state = controlplane.replay(wal_dir)
        epoch = state.epoch + 1
        # adopt=True: full master-side plan state, ZERO fleet mutation.
        sess = cls(prog, cluster, learning_rate=learning_rate,
                   optimizer=optimizer, elastic=elastic,
                   autosave_every=autosave_every,
                   wal_dir=wal_dir, master_epoch=epoch, adopt=True)
        sess._wal = controlplane.ControlPlaneWAL(
            wal_dir,
            segment_bytes=env.tepdist_wal_segment_mb * (1 << 20),
            snapshot_every=env.tepdist_wal_snapshot_every,
            fsync=env.tepdist_wal_fsync,
            on_error=sess._wal_error)
        controlplane.log_epoch(sess._wal, epoch)
        metrics().counter("master_takeovers").inc()
        # Probe the fleet: the FIRST fenced verb each worker sees latches
        # the new epoch; Ping itself is unfenced, so probe via the reply
        # fields instead.
        statuses: Dict[int, Dict[str, Any]] = {}
        unreachable: set = set()
        for ti, c in sess.clients.items():
            try:
                statuses[ti] = c.ping(want_ckpt_steps=True)
            except Exception:  # noqa: BLE001 — dead worker, ladder below
                unreachable.add(ti)
        fleet_gens = {int(g) for st in statuses.values()
                      if (g := st.get("plan_gen")) is not None and g > 0}
        # The fleet's gen is authoritative over the WAL's (a crash after
        # DispatchPlan but before the plan record landed): adopt it, and
        # advance the class counter so future re-dispatches stay ahead.
        if len(fleet_gens) == 1:
            sess._plan_gen = fleet_gens.pop()
        elif state.plan_gen:
            sess._plan_gen = state.plan_gen
        cls._gen_counter = max(cls._gen_counter, sess._plan_gen)
        sess._step = state.step
        sess._assign_owners(params_template)
        sess._params_template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                           np.asarray(x).dtype)
            if not isinstance(x, jax.ShapeDtypeStruct) else x,
            params_template)
        # Unwedge stragglers blocked in recvs on data a peer already
        # sent to the dead master's plan: abort + reset keeps RawStore
        # data, so the watermark re-run hits caches / kept inputs.
        sess._reset_fleet_step()
        if unreachable or len(fleet_gens) > 1:
            # Inconsistent or shrunken fleet: the standard ladder — live
            # migration over survivors, checkpoint re-dispatch fallback.
            sess.health.mark_dead(unreachable)
            if sess._wal is not None:
                for ti in sorted(unreachable):
                    w = sess._known_workers.get(ti)
                    controlplane.log_member(
                        sess._wal, ti, w.address if w else "",
                        action="dead")
            try:
                sess._live_migrate()
            except Exception as e:  # noqa: BLE001 — rung 2 handles it
                log.warning("readopt live migration failed (%r); falling "
                            "back to checkpoint re-dispatch", e)
                sess._auto_redispatch()
        else:
            sess._wal_log_plan()   # adopted plan under the new epoch
        ms = (time.monotonic() - t0) * 1e3
        m = metrics()
        m.gauge("master_recover_ms").set(ms)
        m.histogram("master_recover_ms").observe(ms)
        sess.last_recover_ms = ms
        log.warning("master re-adoption complete in %.0f ms: epoch=%d "
                    "plan_gen=%d step=%d unreachable=%s", ms, epoch,
                    sess._plan_gen, sess._step, sorted(unreachable))
        return sess

    def close(self) -> None:
        if self.watchtower is not None:
            from tepdist_tpu.telemetry import watchtower
            self.watchtower.stop()
            if watchtower.get_active() is self.watchtower:
                watchtower.set_active(None)
        self.health.stop()
        for c in self.clients.values():
            c.close()
        if getattr(self, "_wal", None) is not None:
            self._wal.close()
            self._wal = None
