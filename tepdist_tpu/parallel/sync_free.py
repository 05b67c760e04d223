"""Sync-free (gradient accumulation / micro-batching) analysis + transform.

Reference parity: ``SyncFreeSplittingAnalysis`` finds a batch-dim split whose
largest subgraph (fwd+bwd up to the gradient sync points) runs per-micro-batch
without cross-replica synchronization and decides ``num_micro_batches``;
``SyncFreeDecomposition`` then physically splits ENTRY into CG (per-micro
compute), GAInit (zero buffers), GA (accumulate), and AG (apply gradients)
computations wired through DefContexts (reference:
service/parallel/sync_free_splitting_analysis.{h,cc},
sync_free_decomposition.{h,cc}, sync_free_chain.h).

TPU-native mechanism: the decomposition is *constructed*, not carved out of a
traced module — ``build_ga_step`` emits one jit-able function where
  GAInit = tree-zeros carry init, CG = per-micro value_and_grad inside
  ``lax.scan``, GA = carry add, AG = the optimizer apply after the scan.
XLA sees the whole thing and overlaps micro-batches with the GA adds; the
micro ordinal is a *time* axis (share_dev_flags=true in the reference's
terms), so no devices are consumed.

The *analysis* half stays: it detects the sync-free batch dim on the traced
graph and sizes the micro-batch count from the activation-memory estimate
(reference decided it from sync-point structure + memory, too).
"""

from __future__ import annotations

import collections
import dataclasses
import logging
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend import core as jexcore

from tepdist_tpu.core.service_env import ServiceEnv
from tepdist_tpu.graph.cost import aval_bytes
from tepdist_tpu.graph.jaxpr_graph import JaxprGraph
from tepdist_tpu.models.layers import BlockGradSink, part
from tepdist_tpu.parallel.performance_utils import chip_spec
from tepdist_tpu.parallel.strategy_utils import StrategyUtil
from tepdist_tpu.core.dist_spec import DimStrategy
from tepdist_tpu.telemetry import traced

Var = jexcore.Var
log = logging.getLogger(__name__)

traced.declare(
    "ga_fused_bytes", "parameter bytes whose gradients a gradient-"
    "accumulation step adds inside the loss's own layer loop")
traced.declare(
    "ga_unfused_bytes", "parameter bytes whose gradients it adds by the "
    "tree-wide add (both 0: one micro batch)")


@dataclasses.dataclass
class SyncFreeResult:
    """Decision record of the analysis."""

    batch_arg_indices: List[int]     # flat invar indices carrying the batch dim
    batch_dims: Dict[int, int]       # arg index -> batch dim
    sync_free_fraction: float        # fraction of flops in the sync-free set
    num_micro_batches: int
    peak_activation_bytes: float


def find_sync_free_split(
    graph: JaxprGraph, candidate_args: Optional[List[int]] = None
) -> Optional[Tuple[Dict[int, int], float]]:
    """Find batch dims on data args such that forward propagation reaches a
    maximal flop fraction with partials only at gradient-shaped sinks
    (reference: SearchForMostSyncFreeInsts).

    Tries dim 0 of each non-matrix arg set; returns ({arg: dim}, fraction)."""
    n_probe = 2  # split factor used only for feasibility probing
    best: Optional[Tuple[Dict[int, int], float]] = None
    indices = candidate_args
    if indices is None:
        indices = list(range(len(graph.invars)))
    # Group candidate args by their dim-0 size: batch args share it.
    by_size: Dict[int, List[int]] = {}
    for i in indices:
        shape = graph.invars[i].aval.shape
        if len(shape) >= 1 and shape[0] % n_probe == 0:
            by_size.setdefault(shape[0], []).append(i)
    for size, args in by_size.items():
        # Args whose dim 0 merely coincides with the batch size (e.g. a
        # [batch_like, d] weight) poison the split: drop any arg whose
        # inclusion lowers the sync-free fraction.
        assign = {i: 0 for i in args}
        frac = _probe_fraction(graph, assign, n_probe)
        for i in list(assign):
            if len(assign) == 1:
                break
            trial = {k: v for k, v in assign.items() if k != i}
            trial_frac = _probe_fraction(graph, trial, n_probe)
            if trial_frac > frac:
                assign, frac = trial, trial_frac
        if frac > 0 and (best is None or frac > best[1]):
            best = (assign, frac)
    return best


def _probe_fraction(graph: JaxprGraph, assign: Dict[int, int], n: int) -> float:
    """Forward-propagate the candidate split; return flop fraction of nodes
    that stay split or partial (i.e. run per-micro-batch sync-free)."""
    value: Dict[Var, DimStrategy] = {}
    for i, d in assign.items():
        v = graph.invars[i]
        value[v] = DimStrategy.split_on(d, n)
    covered = 0.0
    total = graph.total_flops() or 1.0
    for node in graph.nodes:
        known = {}
        for k, a in enumerate(node.invars):
            if isinstance(a, Var) and a in value and (
                    value[a].is_split() or value[a].partial):
                known[k] = value[a]
        if not known:
            continue
        r = StrategyUtil.forward_infer(node.eqn, known, n)
        if r is None and len(known) > 1:
            r = StrategyUtil.forward_infer(
                node.eqn, dict([next(iter(known.items()))]), n)
        if r is None:
            continue
        moved = False
        for ov, s in zip(node.outvars, r.out_strategies):
            if isinstance(ov, Var) and (s.is_split() or s.partial):
                value[ov] = s
                moved = True
        if moved:
            covered += node.flops
    return covered / total


def estimate_peak_activation_bytes(graph: JaxprGraph) -> float:
    """Liveness-based peak estimate: sweep program order, tracking bytes of
    values whose last use is later (reference: memory feasibility input to
    the analysis / Evaluator)."""
    last_use: Dict[Var, int] = {}
    for node in graph.nodes:
        for a in node.invars:
            if isinstance(a, Var):
                last_use[a] = node.id
    for a in graph.outvars:
        if isinstance(a, Var):
            last_use[a] = len(graph.nodes) + 1
    live = 0.0
    peak = 0.0
    expiry: Dict[int, float] = {}
    for node in graph.nodes:
        for ov in node.outvars:
            if isinstance(ov, Var) and ov in last_use:
                b = aval_bytes(ov.aval)
                live += b
                expiry[last_use[ov]] = expiry.get(last_use[ov], 0.0) + b
        peak = max(peak, live)
        live -= expiry.pop(node.id, 0.0)
    return peak


def choose_num_micro_batches(
    graph: JaxprGraph,
    batch_size: int,
    hbm_budget_bytes: Optional[float] = None,
    usage_ratio: float = 0.6,
) -> int:
    env = ServiceEnv.get()
    if env.num_micro_batches > 0:
        return env.num_micro_batches
    if hbm_budget_bytes is None:
        hbm_budget_bytes = chip_spec().hbm_gb * 1e9
    peak = estimate_peak_activation_bytes(graph)
    budget = hbm_budget_bytes * usage_ratio
    n = 1
    while peak / n > budget and n < batch_size:
        n *= 2
    while batch_size % n != 0 and n > 1:
        n //= 2
    return max(1, n)


def analyze_sync_free(
    graph: JaxprGraph,
    batch_size: int,
    candidate_args: Optional[List[int]] = None,
    hbm_budget_bytes: Optional[float] = None,
) -> SyncFreeResult:
    # Liveness pre-pass (reference: HloLivenessOptimizer runs before the
    # planner): the peak estimate below sees shortened live ranges for
    # cheap duplicable producers, as XLA's remat will at compile time.
    try:
        from tepdist_tpu.parallel.liveness import optimize_liveness
        graph = optimize_liveness(graph)
    except Exception:  # noqa: BLE001 — estimation aid only
        pass
    found = find_sync_free_split(graph, candidate_args)
    if found is None:
        return SyncFreeResult([], {}, 0.0, 1, estimate_peak_activation_bytes(graph))
    assign, frac = found
    n = choose_num_micro_batches(graph, batch_size, hbm_budget_bytes)
    return SyncFreeResult(
        batch_arg_indices=sorted(assign),
        batch_dims=assign,
        sync_free_fraction=frac,
        num_micro_batches=n,
        peak_activation_bytes=estimate_peak_activation_bytes(graph),
    )


# --------------------------------------------------------------------------
# The decomposition (constructive form)
# --------------------------------------------------------------------------

def _zero_pad_flat(x, dp: int):
    """Flatten ``x`` and zero-pad to a multiple of ``dp`` — the canonical
    ZeRO shard layout: contiguous 1/dp rows of the padded flat vector."""
    flat = x.reshape(-1)
    pad = (-flat.size) % dp
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def zero_pad_params(params, zero_dp: int):
    """Params tree re-laid-out as padded flat leaves (``_zero_pad_flat``).
    ``optimizer.init`` on this tree yields the GLOBAL optimizer state for
    the explicit ZeRO GA path: each moment leaf is a flat (dp*chunk,)
    vector whose contiguous 1/dp rows are one replica's shard — pass it
    into shard_map with ``P(axis)`` partitioning on the leaves."""
    return jax.tree_util.tree_map(
        lambda p: _zero_pad_flat(p, zero_dp), params)


def walked_leaves(loss_fn: Callable, params, *batch) -> Tuple[int, ...]:
    """Flat indices of the leaves of ``params`` whose gradient a GA step may
    accumulate inside the loss's own layer loop: those ``loss_fn(params,
    *batch)`` hands to ``models/layers.py:scan_blocks`` as they are (not
    through a ``jit`` or ``checkpoint`` of the whole loss, not cast or
    sliced), once, and uses nowhere else. Found by tracing the loss once at
    the shapes given, with every such walk stubbed out."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    walks: List[Tuple[int, ...]] = []

    def probe(leaves, *batch):
        with BlockGradSink({id(a): i for i, a in enumerate(leaves)}) as sink:
            loss = loss_fn(treedef.unflatten(leaves), *batch)
        walks.extend(sink.walks)
        return loss

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    jaxpr = jax.make_jaxpr(probe)(shapes(leaves), *shapes(batch)).jaxpr
    used = {v for eqn in jaxpr.eqns for v in eqn.invars if isinstance(v, Var)}
    used.update(v for v in jaxpr.outvars if isinstance(v, Var))
    times = collections.Counter(i for walk in walks for i in walk)
    whole = [walk for walk in walks if all(
        times[i] == 1 and jaxpr.invars[i] not in used for i in walk)]
    return tuple(sorted(i for walk in whole for i in walk))


def _report_ga_bytes(fused: int, unfused: int) -> None:
    """How the step being built accumulates its parameters' gradients:
    bytes added inside the layer loop / by the tree-wide add. Every other
    gauge of the group that is set while a step is traced
    (``telemetry/traced.py``: what the walks keep, the kernels' calls)
    starts from 0 and is counted as the step is traced."""
    traced.reset()
    traced.note("ga_fused_bytes", fused)
    traced.note("ga_unfused_bytes", unfused)


def build_ga_step(
    grad_fn: Callable,
    apply_fn: Callable,
    num_micro_batches: int,
    batch_argnums: Tuple[int, ...] = (1,),
    batch_dim: int = 0,
    comm_dtype: str = "",
    zero_dp: int = 0,
    zero_axis_name: str = "",
    loss_fn: Optional[Callable] = None,
) -> Callable:
    """Construct the sync-free GA training step (reference decomposition
    ENTRY -> {GAINIT, CG, GA, AG} as one scanned program).

    Args:
      grad_fn: ``(params, *batch) -> (loss, grads)`` per-micro-batch.
      apply_fn: ``(params, opt_state, grads) -> (new_params, new_opt_state)``.
      num_micro_batches: micro ordinal size (a time axis: no devices).
      batch_argnums: positions (in the step signature after params/opt_state)
        of batch-carrying args to split along ``batch_dim``.
      comm_dtype: the exploration winner's comm-dtype modifier. ""/
        "float32" = fidelity (bit-identical to the pre-compression step);
        "bfloat16" = down-cast the per-micro gradient contributions (the
        FP16_COMM path); "int8" = chunk-scale fake-quant with STOCHASTIC
        rounding (parallel/quantize.py) so the quantization error is
        zero-mean across steps.
      zero_dp / zero_axis_name: the explicit ZeRO-1 weight-update path
        (arXiv:2004.13336) for named-axis (shard_map) contexts: the
        accumulated gradient is reduce-scattered over ``zero_axis_name``
        (``lax.psum_scatter`` — the apply sees the cross-replica SUM on
        its local 1/dp shard; fold your own 1/dp for mean semantics),
        ``apply_fn`` runs on the padded-flat param/grad SHARDS (init the
        optimizer on :func:`zero_pad_params`), and the updated params
        all-gather back to full shapes. Composes with ``comm_dtype``:
        the reduce-scatter wire follows the gradient dtype, the param
        all-gather uses :func:`~tepdist_tpu.parallel.performance_utils.
        param_wire_dtype` (bf16 cap — params are never int8-quantized).
        The single-jit SPMD path does NOT use this: there the planner
        realizes ZeRO by sharding the optimizer-state invars and GSPMD
        emits the equivalent collectives (auto_parallel ``zero_invars``).

      loss_fn: the loss ``grad_fn`` is ``jax.value_and_grad`` of. Given,
        and with nothing compressing a micro batch's gradient, the step
        differentiates it itself so that the stacked blocks it walks with
        ``models/layers.py:scan_blocks`` get their gradients added into the
        accumulator inside the backward layer loop (:func:`walked_leaves`
        finds them by tracing the loss once); the other leaves keep
        ``acc + g``, and a loss that walks nothing keeps ``grad_fn`` and
        the tree-wide add. Same values either way. The gauges
        ``ga_fused_bytes`` / ``ga_unfused_bytes`` say how the parameter
        bytes split.

    Returns ``step(params, opt_state, *batch) -> (mean_loss, params, opt)``.
    """
    # FP16_COMM (reference knob; bf16 on TPU): compress the per-micro
    # gradient contributions before accumulation/all-reduce — halves the
    # cross-replica reduction bytes at bf16 rounding cost. The planner's
    # comm_dtype="bfloat16" winner takes the same path; "int8" quantizes
    # through chunk scales instead.
    compress = ServiceEnv.get().fp16_comm or comm_dtype == "bfloat16"
    int8 = comm_dtype == "int8"
    zero = zero_dp > 1 and bool(zero_axis_name)

    def zero_apply(params, opt_state, grads):
        """RS -> local shard apply -> AG (the ZeRO-1 update). ``grads``
        are full-shape accumulated means; ``opt_state`` is the LOCAL
        shard state (flat-leaf moments under shard_map P(axis))."""
        from tepdist_tpu.parallel.performance_utils import param_wire_dtype

        def rs(g):
            flat = _zero_pad_flat(g, zero_dp)
            if compress and jnp.issubdtype(flat.dtype, jnp.floating):
                # The bf16 wire: psum_scatter reduces at the wire dtype,
                # the shard dequantizes back (int8 grads were already
                # fake-quanted per micro batch in the scan).
                return lax.psum_scatter(
                    flat.astype(jnp.bfloat16), zero_axis_name,
                    scatter_dimension=0, tiled=True).astype(g.dtype)
            return lax.psum_scatter(flat, zero_axis_name,
                                    scatter_dimension=0, tiled=True)

        idx = lax.axis_index(zero_axis_name)

        def shard(p):
            flat = _zero_pad_flat(p, zero_dp)
            chunk = flat.size // zero_dp
            return lax.dynamic_slice_in_dim(flat, idx * chunk, chunk)

        g_shards = jax.tree_util.tree_map(rs, grads)
        p_shards = jax.tree_util.tree_map(shard, params)
        new_shards, opt_state = apply_fn(p_shards, opt_state, g_shards)
        ag_bf16 = param_wire_dtype(comm_dtype) == "bfloat16"

        def ag(s, p):
            if ag_bf16 and jnp.issubdtype(s.dtype, jnp.floating):
                s = s.astype(jnp.bfloat16)
            full = lax.all_gather(s, zero_axis_name, tiled=True)
            return full.astype(p.dtype)[:p.size].reshape(p.shape)

        return jax.tree_util.tree_map(ag, new_shards, params), opt_state

    do_apply = zero_apply if zero else apply_fn

    def maybe_compress(g, micro_index):
        if int8:
            from tepdist_tpu.parallel.quantize import fake_quant_grads
            key = jax.random.fold_in(jax.random.PRNGKey(0x7e9d), micro_index)
            return fake_quant_grads(g, key)
        if not compress:
            return g
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, g)

    if num_micro_batches <= 1:
        _report_ga_bytes(0, 0)

        def step1(params, opt_state, *batch):
            loss, grads = grad_fn(params, *batch)
            if int8 or compress:
                grads = jax.tree_util.tree_map(
                    lambda g, p: g.astype(p.dtype)
                    if hasattr(g, "astype") else g,
                    maybe_compress(grads, jnp.zeros((), jnp.uint32)),
                    params)
            params, opt_state = do_apply(params, opt_state, grads)
            return loss, params, opt_state
        return step1

    def step(params, opt_state, *batch):
        def resplit(i, b):
            if i + 1 not in batch_argnums:  # argnums count params as 0
                return b
            shape = b.shape
            m = shape[batch_dim] // num_micro_batches
            new_shape = (
                shape[:batch_dim]
                + (num_micro_batches, m)
                + shape[batch_dim + 1:]
            )
            b = b.reshape(new_shape)
            # scan consumes leading axis
            return jnp.moveaxis(b, batch_dim, 0)

        micro_batches = tuple(resplit(i, b) for i, b in enumerate(batch))

        # GAInit: zero accumulators shaped like the gradients (fp32 even
        # under FP16_COMM: only the per-micro contributions are compressed).
        with part("optimizer"):
            acc0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        walked = ()
        if loss_fn is not None and not (int8 or compress):
            walked = walked_leaves(
                loss_fn, params, *(mb[0] for mb in micro_batches))
        rest = [i for i in range(len(leaves)) if i not in walked]
        fused_bytes = sum(aval_bytes(leaves[i]) for i in walked)
        _report_ga_bytes(fused_bytes, sum(
            aval_bytes(a) for a in leaves) - fused_bytes)

        def body(carry, xs):  # CG + GA
            micro_index, mb = xs
            acc, loss_sum = carry
            loss, grads = grad_fn(params, *mb)
            with part("optimizer"):
                grads = maybe_compress(grads, micro_index)
                acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(a.dtype), acc, grads)
            return (acc, loss_sum + loss), None

        def body_walked(carry, xs):
            """``body`` with the walked leaves' gradients added to their
            accumulators inside ``scan_blocks``' backward layer loop: the
            accumulators go into the differentiation and come out of it as
            their own cotangents; the walked parameters are closed over."""
            _, mb = xs
            acc, loss_sum = carry
            acc = treedef.flatten_up_to(acc)

            def loss_of(rest_leaves, walked_acc):
                merged = list(leaves)
                for i, leaf in zip(rest, rest_leaves):
                    merged[i] = leaf
                sink = BlockGradSink({id(leaves[i]): i for i in walked},
                                     dict(zip(walked, walked_acc)))
                with sink:
                    loss = loss_fn(treedef.unflatten(merged), *mb)
                if sorted(k for w in sink.walks for k in w) != list(walked):
                    raise RuntimeError(
                        "the loss walked other stacked blocks than when it "
                        "was first traced; their gradients would be lost")
                return loss

            loss, (grads, walked_acc) = jax.value_and_grad(
                loss_of, argnums=(0, 1))(
                    [leaves[i] for i in rest], [acc[i] for i in walked])
            with part("optimizer"):
                for i, g in zip(rest, grads):
                    acc[i] = acc[i] + g.astype(acc[i].dtype)
            for i, a in zip(walked, walked_acc):
                acc[i] = a
            return (treedef.unflatten(acc), loss_sum + loss), None

        micro_index = jnp.arange(num_micro_batches, dtype=jnp.uint32)
        (acc, loss_sum), _ = lax.scan(
            body_walked if walked else body, (acc0, jnp.zeros(())),
            (micro_index, micro_batches))
        inv = 1.0 / num_micro_batches
        with part("optimizer"):
            grads = jax.tree_util.tree_map(lambda g: g * inv, acc)
        # AG: apply-gradients slice (or the ZeRO RS->apply->AG update).
        params, opt_state = do_apply(params, opt_state, grads)
        return loss_sum * inv, params, opt_state

    return step
