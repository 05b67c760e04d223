"""Pipeline planning + pipelined training-step construction.

Ties together GraphSketch (stage ILP), StageDecomposition (per-stage forward
modules + input_def_map), and VJP-mirrored backward stages into a gradient-
accumulating pipelined training step (reference: the PIPELINE par type —
GraphSketch::StagePlan + StageDecomposition + the GA/GAInit machinery, with
the 1F1B order produced by TaskScheduler; here the semantics function below
is the *correctness anchor*, while the task-graph runtime executes the same
stage modules in 1F1B order across device subsets).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from tepdist_tpu.core.service_env import ServiceEnv
from tepdist_tpu.graph.jaxpr_graph import JaxprGraph, trace_graph
from tepdist_tpu.parallel.graph_sketch import GraphSketch
from tepdist_tpu.parallel.stage_decomposition import StageDecomposition


@dataclasses.dataclass
class PipelineProgram:
    """A planned pipeline: stage modules + wiring + batch info."""

    graph: JaxprGraph
    decomp: StageDecomposition
    num_stages: int
    num_micro_batches: int
    batch_flat_indices: List[int]   # graph invar indices carrying batch dim
    batch_dim: int
    in_tree: Any
    # The exploration winner's comm-dtype modifier for this program's
    # collectives/wire (""/"float32" = fidelity). Set by the winner's
    # build path; consumed by the task-dag builder (SEND/RECV tagging)
    # and the executor's gradient-accumulate payloads.
    comm_dtype: str = ""
    # ZeRO weight-update sharding modifier: when True each stage's
    # optimizer state is sharded over the intra-stage data axis
    # (reduce-scatter grads, local apply, all-gather params). Set by the
    # exploration winner; consumed by the executor, the task-dag builder
    # and the fleet plan_meta.
    zero: bool = False

    @property
    def stages(self):
        return self.decomp.stages

    def stage_flops(self) -> List[float]:
        flops = [0.0] * self.num_stages
        for n in self.graph.nodes:
            s = self.decomp.assignment[n.id]
            if s >= 0:
                flops[s] += n.flops
        return flops

    # ------------------------------------------------------------------
    def forward_backward_micro(self) -> Callable:
        """Build ``(flat_args) -> (loss, flat_grads)`` for ONE micro batch,
        running stage fwds in order then VJP bwds in reverse (the fwd/bwd
        task bodies the runtime schedules)."""
        decomp = self.decomp
        S = self.num_stages
        fwd_fns = decomp.forward_fns()

        batch_set = set(self.batch_flat_indices)

        def run(flat_args: Sequence[Any]):
            stage_inputs: List[Tuple] = [None] * S
            stage_outputs: List[Tuple] = [None] * S
            for s in range(S):
                m = decomp.stages[s]
                ins = []
                for pos in range(len(m.invars)):
                    src = m.input_def_map[pos]
                    if src[0] == "arg":
                        ins.append(flat_args[src[1]])
                    else:
                        ins.append(stage_outputs[src[1]][src[2]])
                stage_inputs[s] = tuple(ins)
                stage_outputs[s] = fwd_fns[s](*ins)
            # Loss = graph outvar 0.
            loss_stage = None
            for s in range(S):
                if 0 in decomp.stages[s].graph_out_map:
                    loss_stage = s
                    break
            assert loss_stage is not None, "loss not produced by any stage"
            loss = stage_outputs[loss_stage][
                decomp.stages[loss_stage].graph_out_map[0]]

            # Backward sweep.
            cot: Dict[Tuple[int, int], Any] = {}
            cot[(loss_stage, decomp.stages[loss_stage].graph_out_map[0])] = (
                jnp.ones_like(loss))
            grads: Dict[int, Any] = {}
            for s in range(S - 1, -1, -1):
                m = decomp.stages[s]
                outs_cot = []
                any_cot = False
                for k, ov in enumerate(m.outvars):
                    c = cot.get((s, k))
                    if c is None:
                        c = jnp.zeros(ov.aval.shape, ov.aval.dtype)
                    else:
                        any_cot = True
                    outs_cot.append(c)
                if not any_cot:
                    continue
                _, vjp_fn = jax.vjp(fwd_fns[s], *stage_inputs[s])
                in_cots = vjp_fn(tuple(outs_cot))
                for pos, c in enumerate(in_cots):
                    src = m.input_def_map[pos]
                    if src[0] == "arg":
                        i = src[1]
                        if i in batch_set:
                            continue  # int batch args yield float0 cots
                        grads[i] = c if i not in grads else jax.tree_util.tree_map(
                            jnp.add, grads[i], c)
                    else:
                        key = (src[1], src[2])
                        cot[key] = c if key not in cot else cot[key] + c
            return loss, grads

        return run

    # ------------------------------------------------------------------
    def reference_step(self, apply_fn: Callable) -> Callable:
        """Sequential-semantics pipelined GA step (the correctness anchor):
        ``step(params, opt_state, *batch) -> (loss, params, opt_state)``.

        Numerically identical to what the 1F1B runtime computes — micro
        grads accumulate; optimizer applies the mean."""
        micro_fn = self.forward_backward_micro()
        M = self.num_micro_batches
        bset = set(self.batch_flat_indices)
        bdim = self.batch_dim

        def step(params, opt_state, *batch):
            flat, _ = jax.tree_util.tree_flatten(((params,) + tuple(batch), {}))
            param_leaf_count = len(jax.tree_util.tree_leaves(params))
            loss_sum = jnp.zeros(())
            grad_acc: Dict[int, Any] = {}
            for mb in range(M):
                mb_flat = list(flat)
                for i in bset:
                    b = flat[i]
                    msize = b.shape[bdim] // M
                    mb_flat[i] = jax.lax.dynamic_slice_in_dim(
                        b, mb * msize, msize, axis=bdim)
                loss, grads = micro_fn(mb_flat)
                loss_sum = loss_sum + loss
                for i, g in grads.items():
                    grad_acc[i] = g if i not in grad_acc else grad_acc[i] + g
            inv = 1.0 / M
            params_flat = flat[:param_leaf_count]
            grads_flat = []
            for i in range(param_leaf_count):
                g = grad_acc.get(i)
                grads_flat.append(
                    jnp.zeros_like(params_flat[i]) if g is None else g * inv)
            params_tree = jax.tree_util.tree_structure(params)
            grads_tree = jax.tree_util.tree_unflatten(params_tree, grads_flat)
            new_params, new_opt = apply_fn(params, opt_state, grads_tree)
            return loss_sum * inv, new_params, new_opt

        return step


def micro_abstract_batch(batch, num_micro_batches: int, batch_dim: int = 0):
    """Batch pytrees shrunk to MICRO-batch shapes (divide the batch dim by
    M where divisible) — THE micro-shape trace contract: plan_pipeline
    traces stage modules at these shapes, and the RPC client ships its
    micro loss jaxpr traced at exactly these shapes (jaxpr constants like
    mean denominators bake the trace shape)."""

    def micro(leaf):
        shape = list(leaf.shape)
        if shape and shape[batch_dim] % num_micro_batches == 0:
            shape[batch_dim] //= num_micro_batches
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

    return tuple(jax.tree_util.tree_map(micro, b) for b in batch)


def plan_pipeline(
    loss_fn: Callable,
    num_stages: int,
    num_micro_batches: int,
    params,
    *batch,
    batch_dim: int = 0,
) -> PipelineProgram:
    """Trace, ILP-cut, and decompose ``loss_fn(params, *batch)`` into a
    pipeline program (reference: AutoParallel pipeline path steps 3-5).

    The graph is traced at MICRO-batch shapes — the stage modules are the
    per-micro-batch CG slices (reference: SyncFreeDecomposition builds CG
    over micro-batch shapes), so baked constants like mean denominators are
    correct per micro batch."""

    micro_batch = micro_abstract_batch(batch, num_micro_batches, batch_dim)
    graph, in_tree, _ = trace_graph(loss_fn, params, *micro_batch)
    if any(n.prim == "pallas_call" for n in graph.nodes):
        # Stage programs differentiate the staged forward with jax.vjp,
        # and tracing inlined the kernel's custom_vjp wrapper: what is
        # left is a raw pallas_call, whose generic JVP rule fails deep
        # inside jax. Refuse here, where exploration records it as a
        # pruned proposal and a direct caller reads why.
        raise NotImplementedError(
            "pipeline stages cannot differentiate a pallas kernel (e.g. "
            "attn='flash'): its custom_vjp was inlined away when the loss "
            "was traced. Use an SPMD plan, or einsum attention, for this "
            "model")
    sketch = GraphSketch(graph)
    assignment = sketch.stage_plan(num_stages)
    decomp = StageDecomposition(graph, assignment, num_stages)
    decomp.assignment = assignment
    # Batch leaves: flat indices belonging to the batch args (everything
    # after the params leaves).
    n_param_leaves = len(jax.tree_util.tree_leaves(params))
    batch_flat = list(range(n_param_leaves, len(graph.invars)))
    return PipelineProgram(
        graph=graph,
        decomp=decomp,
        num_stages=num_stages,
        num_micro_batches=num_micro_batches,
        batch_flat_indices=batch_flat,
        batch_dim=batch_dim,
        in_tree=in_tree,
    )
