"""Client session: the JAX frontend over the RPC service.

Reference parity: the modified TF client's compile/run flow
(reference: jit/kernels/xla_ops.{h,cc}): XlaCompileOp sends the whole-graph
module via BuildExecutionPlan; XlaRunOp separates data args from variable
args, transfers variables ONCE (cached server-side handles —
``VarsCacheInRemote``), per-step inputs each step, calls ExecutePlan, and
fetches resource variables every ``FETCH_RESOURCE_VAR_STEPS`` steps.

The JAX version traces ``step_fn(params, opt_state, *batch)`` client-side,
serializes the inlined jaxpr, and lets the SERVER plan/compile/execute on
its devices — the client needs no accelerator.

Robustness: every RPC issued here rides ``TepdistClient.call`` and thus
inherits rpc/retry.py's policy (per-verb deadlines, exponential backoff,
transport-vs-fatal classification). ``run``/``run_async``'s ExecutePlan
carries an idempotency token, so a retried step whose original response
was lost is answered from the server's dedup cache instead of advancing
``global_step`` twice — safe to call under lossy networks or an active
``TEPDIST_FAULT_SPEC`` fault plan.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import numpy as np

import jax

from tepdist_tpu.core.service_env import ServiceEnv
from tepdist_tpu.rpc.client import TepdistClient
from tepdist_tpu.rpc.jaxpr_serde import serialize_closed_jaxpr


def _is_abstract(tree) -> bool:
    leaves = jax.tree_util.tree_leaves(tree)
    return bool(leaves) and isinstance(leaves[0], jax.ShapeDtypeStruct)


class TepdistSession:
    def __init__(self, address: Optional[str] = None,
                 mesh_axes: Sequence = (), mode: str = "cost"):
        self.client = TepdistClient(address)
        self.mesh_axes = list(mesh_axes)
        self.mode = mode
        self.handle: Optional[int] = None
        self._out_tree = None
        self._state_tree = None
        self._n_state = 0
        self._batch_leaf_idx: Sequence[int] = ()
        self._step_count = 0
        self.fetch_every = ServiceEnv.get().fetch_resource_var_steps
        # Training-health sentinel (telemetry/watchtower.py): the loss is
        # already on host each run(), so the NaN watchdog + loss-spike
        # detector cost a few float compares. Advisory unless
        # TEPDIST_WATCH_HALT promotes them.
        from tepdist_tpu.telemetry.watchtower import TrainingSentinel
        self.sentinel = TrainingSentinel(
            halt=ServiceEnv.get().tepdist_watch_halt)

    # ------------------------------------------------------------------
    def compile_train_step(self, step_fn: Callable, params, opt_state,
                           *example_batch,
                           annotations: Optional[dict] = None,
                           init_specs: Optional[dict] = None,
                           init_seed: int = 0,
                           _explore_extras: Optional[dict] = None) -> Dict:
        """Trace + ship the whole training step; transfer initial state.

        ``step_fn(params, opt_state, *batch) -> (loss, params, opt_state)``.

        ``init_specs``: {flat state index: {shape, dtype, distribution,
        scale, mean, fan_in_scaling}} — variables are created SERVER-side
        with shard-consistent RNG and never transferred (reference:
        init_from_remote). ``params``/``opt_state`` may then be
        jax.ShapeDtypeStruct pytrees. Indices absent from init_specs that
        have real values are transferred; zero-init is assumed for abstract
        optimizer slots."""
        closed, out_shape = jax.make_jaxpr(step_fn, return_shape=True)(
            params, opt_state, *example_batch)
        module = serialize_closed_jaxpr(closed)

        state_leaves = jax.tree_util.tree_leaves((params, opt_state))
        self._state_tree = jax.tree_util.tree_structure((params, opt_state))
        self._params_tree = jax.tree_util.tree_structure(params)
        self._n_params = len(jax.tree_util.tree_leaves(params))
        self._n_state = len(state_leaves)
        n_batch = len(jax.tree_util.tree_leaves(example_batch))
        self._batch_leaf_idx = list(range(self._n_state,
                                          self._n_state + n_batch))
        self._out_tree = jax.tree_util.tree_structure(out_shape)

        # outs = (loss, new_params..., new_opt...) -> alias onto state invars
        state_alias = {1 + k: k for k in range(self._n_state)}

        ann_wire = None
        if annotations:
            ann_wire = {
                str(i): {ax: {"partition_dim": s.partition_dim,
                              "num_splits": s.num_splits,
                              "partial": s.partial,
                              "replicated": s.replicated}
                         for ax, s in spec.items()}
                for i, spec in annotations.items()
            }
        init_specs = dict(init_specs or {})
        if init_specs:
            # Abstract optimizer slots default to zero init server-side.
            for i, leaf in enumerate(state_leaves):
                if i not in init_specs and not hasattr(leaf, "dtype"):
                    raise TypeError(f"state leaf {i} has no dtype")
                if i not in init_specs and isinstance(
                        leaf, jax.ShapeDtypeStruct):
                    init_specs[i] = {"shape": list(leaf.shape),
                                     "dtype": str(leaf.dtype),
                                     "distribution": "zeros"}
        resp = self.client.build_execution_plan(
            module,
            mesh_axes=self.mesh_axes,
            variable_indices=list(range(self._n_state)),
            state_alias=state_alias,
            mode=self.mode,
            annotations=ann_wire,
            init_specs=init_specs or None,
            init_seed=init_seed,
            **(_explore_extras or {}),
        )
        self.handle = resp["handle"]

        # Variables not initialized remotely are transferred once; the
        # server holds them across steps either way.
        for i, leaf in enumerate(state_leaves):
            if i in init_specs:
                continue
            self.client.transfer_to_server_host(np.asarray(leaf), i,
                                                variable=True)
        self.client.transfer_var_arg_map(
            {i: i for i in range(self._n_state)})
        # Server-side exploration's decision record (telemetry/
        # observatory.py) — kept for dump_trace() metadata embedding.
        self.exploration_report = (
            (resp["summary"].get("explored") or {}).get("report"))
        return resp["summary"]

    # ------------------------------------------------------------------
    def compile_training(self, loss_fn, optimizer, params, *example_batch,
                         num_micro_batches: int = 1,
                         annotations=None, init_specs=None,
                         init_seed: int = 0,
                         optimizer_spec: Optional[dict] = None,
                         explore: Optional[bool] = None):
        """Remote counterpart of ``plan_training``: give a loss function
        and an optax optimizer; the full training step (gradients + GA scan
        + optimizer apply) is composed client-side, traced, and shipped —
        the server plans/compiles/executes it and holds all state.

        FULLY AUTOMATIC planning (reference: the service's exploration
        mode, auto_parallel.cc:236): when the session has NO mesh_axes
        (and mode is not "rule"), the loss jaxpr rides along and the
        SERVER explores SPMD meshes, seq meshes, and pipeline stage cuts,
        compiling the Evaluator-minimal winner. Pass ``optimizer_spec``
        (tepdist_tpu.optim.optimizer_spec) so the server can materialize
        pipeline/seq winners (those re-compose the step server-side; an
        opaque optax object cannot travel). ``explore=False`` opts out."""
        import optax

        from tepdist_tpu.parallel.sync_free import build_ga_step

        def grad_fn(p, *b):
            return jax.value_and_grad(loss_fn)(p, *b)

        def apply_fn(p, s, g):
            updates, s = optimizer.update(g, s, p)
            return optax.apply_updates(p, updates), s

        n_batch = len(example_batch)
        step_fn = build_ga_step(
            grad_fn, apply_fn, num_micro_batches,
            batch_argnums=tuple(range(1, 1 + n_batch)), loss_fn=loss_fn)
        opt_state = (optimizer.init(params)
                     if not _is_abstract(params)
                     else jax.eval_shape(optimizer.init, params))
        if explore is None:
            explore = not self.mesh_axes and self.mode != "rule"
        extras = None
        if explore:
            loss_closed = jax.make_jaxpr(loss_fn)(params, *example_batch)
            extras = {
                "explore": True,
                "loss_module": serialize_closed_jaxpr(loss_closed),
                "n_param_leaves": len(jax.tree_util.tree_leaves(params)),
                "optimizer_spec": optimizer_spec,
                "num_micro_batches": num_micro_batches,
            }
            b0 = jax.tree_util.tree_leaves(example_batch)[0]
            if num_micro_batches > 1 and b0.shape[0] % num_micro_batches == 0:
                # Micro-shape loss trace for the server's pipeline
                # proposals (jaxpr constants bake the trace shape —
                # plan_pipeline's micro-trace contract, same helper).
                from tepdist_tpu.parallel.pipeline import (
                    micro_abstract_batch,
                )

                micro_batch = micro_abstract_batch(example_batch,
                                                   num_micro_batches)
                extras["micro_loss_module"] = serialize_closed_jaxpr(
                    jax.make_jaxpr(loss_fn)(params, *micro_batch))
        return self.compile_train_step(
            step_fn, params, opt_state, *example_batch,
            annotations=annotations, init_specs=init_specs,
            init_seed=init_seed, _explore_extras=extras)

    # ------------------------------------------------------------------
    def run(self, *batch) -> float:
        """One training step: per-step inputs ride inline with ExecutePlan
        (reference: per-step TransferToServerHost + ExecutePlan)."""
        assert self.handle is not None, "compile_train_step first"
        leaves = jax.tree_util.tree_leaves(batch)
        inline = {idx: np.asarray(v)
                  for idx, v in zip(self._batch_leaf_idx, leaves)}
        fetch = (self.fetch_every > 0 and
                 (self._step_count + 1) % self.fetch_every == 0)
        result = self.client.execute_plan(
            self.handle, inline_args=inline,
            fetch_resource_variables=fetch)
        self._step_count += 1
        loss = float(np.asarray(result["outputs"][0]))
        self.sentinel.observe(self._step_count - 1, loss)
        return loss

    # ------------------------------------------------------------------
    def compile_generate(self, gen_fn: Callable, params,
                         *example_args) -> Dict:
        """Trace + ship an inference/sampling function that reads the
        SERVER-HELD weights (reference: predict_fns.py — predictions run
        on the estimator's trained weights, nothing is fetched).

        ``gen_fn(params, *args) -> tokens``; ``params`` must have the SAME
        leaf order as the training step's (store indices 0..n_params-1 —
        the invariant compile_train_step established). ``example_args``
        (prompt, key, ...) ride inline per ``generate`` call. Rule-mode
        planning: a decode scan is bandwidth-bound; the cost ILP buys
        nothing over the training plan's sharding."""
        closed, out_shape = jax.make_jaxpr(gen_fn, return_shape=True)(
            params, *example_args)
        assert self.handle is not None, "compile_train_step first"
        n_params = len(jax.tree_util.tree_leaves(params))
        assert n_params == self._n_params, (
            f"gen_fn params have {n_params} leaves; the training step "
            f"registered {self._n_params}")
        n_args = len(jax.tree_util.tree_leaves(example_args))
        resp = self.client.build_execution_plan(
            serialize_closed_jaxpr(closed),
            mesh_axes=self.mesh_axes,
            variable_indices=list(range(n_params)),
            state_alias={},
            mode="rule",
        )
        self._gen_handle = resp["handle"]
        self._gen_arg_idx = list(range(n_params, n_params + n_args))
        self._gen_out_tree = jax.tree_util.tree_structure(out_shape)
        return resp["summary"]

    def generate(self, *args):
        """Run the compiled sampler on the server's current weights and
        return the decoded tokens."""
        assert getattr(self, "_gen_handle", None) is not None, \
            "compile_generate first"
        leaves = jax.tree_util.tree_leaves(args)
        inline = {idx: np.asarray(v)
                  for idx, v in zip(self._gen_arg_idx, leaves)}
        result = self.client.execute_plan(self._gen_handle,
                                          inline_args=inline,
                                          inference=True)
        return jax.tree_util.tree_unflatten(
            self._gen_out_tree, [np.asarray(o) for o in result["outputs"]])

    # ------------------------------------------------------------------
    def run_async(self, *batch):
        """Pipelined step submission (reference: the optional async RPC path
        bounded by a semaphore — num_parallel_rpc_steps, xla_ops.h:229-232).

        The batch is ENCODED on the caller's thread immediately (that is the
        client-side work overlappable with execution — inline literals ride
        with ExecutePlan, there is no separate transfer RPC); the RPC itself
        is dispatched from a single-worker queue, so step order is preserved
        while step N+1's encoding overlaps step N's server execution. At
        most 2 steps are in flight; the permit is released by the future's
        done callback (which also fires on cancellation, so cancelled
        futures cannot leak permits)."""
        import concurrent.futures
        import threading

        assert self.handle is not None, "compile_train_step first"
        if not hasattr(self, "_pool"):
            self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
            self._inflight = threading.Semaphore(2)

        # Encode now, on the caller thread.
        leaves = jax.tree_util.tree_leaves(batch)
        inline = {idx: np.asarray(v)
                  for idx, v in zip(self._batch_leaf_idx, leaves)}
        fetch = (self.fetch_every > 0 and
                 (self._step_count + 1) % self.fetch_every == 0)
        self._step_count += 1

        self._inflight.acquire()

        def go():
            result = self.client.execute_plan(
                self.handle, inline_args=inline,
                fetch_resource_variables=fetch)
            return float(np.asarray(result["outputs"][0]))

        try:
            future = self._pool.submit(go)
        except Exception:
            self._inflight.release()
            raise
        future.add_done_callback(lambda _f: self._inflight.release())
        return future

    # ------------------------------------------------------------------
    def variables(self):
        """Fetch (params, opt_state) back from the server
        (reference FetchResourceVars)."""
        fetched = self.client.fetch_resource_vars(
            list(range(self._n_state)))
        leaves = [fetched[i] for i in range(self._n_state)]
        return jax.tree_util.tree_unflatten(self._state_tree, leaves)

    def params(self):
        state = self.variables()
        return state[0]

    def save(self, max_to_keep: int = 5) -> None:
        self.client.do_remote_save(max_to_keep=max_to_keep)

    def restore(self, global_step: int = -1) -> None:
        self.client.do_remote_restore(global_step=global_step)

    def dump_trace(self, path: Optional[str] = None,
                   clear: bool = False) -> Optional[str]:
        """Pull the server's span buffer + metrics (GetTelemetry),
        clock-align them against this client's own spans, and write ONE
        merged Perfetto-loadable trace. ``path=None`` lands in
        ``$TEPDIST_DUMP_DIR`` (core/debug_dump.py policy). Returns the
        written path, or None if the dump could not be written. Requires
        ``TEPDIST_TRACE=1`` (or DEBUG) on both processes for a non-empty
        timeline. When the plan came from server-side exploration, the
        decision record rides in ``metadata.exploration`` (next to
        ``metadata.fidelity``) so the trace file is a self-contained
        plan_explain/fidelity input."""
        from tepdist_tpu.telemetry import dump_merged_trace
        extra = None
        report = getattr(self, "exploration_report", None)
        if report:
            extra = {"exploration": report}
        return dump_merged_trace([self.client], path=path, name="trace",
                                 extra_metadata=extra)

    def close(self) -> None:
        # Drain queued async steps before the channel goes away.
        pool = getattr(self, "_pool", None)
        if pool is not None:
            pool.shutdown(wait=True)
        self.client.close()
