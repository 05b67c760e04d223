"""Attention-motif detection + the planner-proposable sequence axis.

VERDICT r1 item 4 / SURVEY §5.7 mandate: the reference only reserves a
slot for "token parallel" (another split ordinal, README.md:16); the
TPU build makes sequence parallelism a first-class *planner* strategy:

1. ``detect_motifs`` recognizes the softmax(QK^T)V pattern in a jaxpr
   graph (dot_general -> scale/mask/softmax chain -> dot_general).
2. ``build_seq_strategy`` plans a ``seq`` mesh axis: Q/K/V/O split on
   the sequence dim, propagated through the rest of the graph with the
   shared transfer functions, priced with the ring-attention cost
   ((P-1) K/V neighbor hops over ICI).
3. The SPMD transform consumes ``GraphStrategy.motifs`` to REWRITE each
   motif into ``ops.ring_attention`` (shard_map + ppermute) — GSPMD
   alone would all-gather K/V; the ring keeps the sequence sharded.

Layout assumption: Q/K/V are [B, H, T, D] (dims (0,1) batch, contraction
over D for QK^T and over T_k for PV) — what einsum attention traces to.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Set, Tuple

import numpy as np
from jax.extend import core as jexcore

from tepdist_tpu.core.dist_spec import DimStrategy
from tepdist_tpu.graph.jaxpr_graph import JaxprGraph

Var = jexcore.Var

# Elementwise / shape / softmax / mask prims allowed inside the motif.
_CHAIN_PRIMS = {
    "convert_element_type", "mul", "div", "sub", "add", "exp", "max", "min",
    "reduce_max", "reduce_sum", "broadcast_in_dim", "stop_gradient",
    "select_n", "ge", "gt", "le", "lt", "iota", "reshape", "and", "or",
    "integer_pow", "neg", "eq", "ne", "squeeze", "expand_dims", "transpose",
    "custom_jvp_call", "custom_vjp_call", "pjit", "jit",
}

_NEG_FILL = -1e8      # select fill must be at least this negative


@dataclasses.dataclass
class AttentionMotif:
    """One softmax(QK^T)V occurrence — einsum form, or a tagged flash
    pallas_call (the kernel self-describes via its name param:
    ``tepdist_flash_fwd__c{causal}__s{scale}``)."""

    qk_id: int                 # dot_general producing [B,H,Tq,Tk]
    pv_id: int                 # dot_general producing [B,H,Tq,D]
    member_ids: Set[int]       # every eqn replaced by the rewrite
    q: Var
    k: Var
    v: Var
    out: Var
    causal: bool
    scale: float
    seq_len: int
    flash: bool = False        # single tagged pallas_call node
    seq_dim: int = 2           # T position: 2 in [B,H,T,D], 1 in [BH,T,D]
    n_head: Optional[int] = None   # known for einsum + tagged-flash motifs
    # Chosen sequence-parallel algorithm: "ring" (K/V rotation, hops
    # overlap block compute) or "ulysses" (head<->seq all-to-alls, full
    # local sequence) — picked per plan by comparing priced comm.
    impl: str = "ring"


def _is_qk_dot(node) -> bool:
    if node.prim != "dot_general":
        return False
    dn = node.eqn.params.get("dimension_numbers")
    if dn != (((3,), (3,)), ((0, 1), (0, 1))):
        return False
    return (len(node.invars) == 2
            and all(isinstance(a, Var) and len(a.aval.shape) == 4
                    for a in node.invars))


def _is_pv_dot(node) -> bool:
    if node.prim != "dot_general":
        return False
    dn = node.eqn.params.get("dimension_numbers")
    return dn == (((3,), (2,)), ((0, 1), (0, 1)))


def _is_plain_iota(graph: JaxprGraph, a, depth: int = 0) -> bool:
    """True when ``a`` is an (un-shifted) position index: iota, possibly
    broadcast/converted, possibly offset by a literal ZERO."""
    if depth > 6:
        return False
    if isinstance(a, jexcore.Literal):
        return np.ndim(a.val) == 0      # scalar literal operand is fine
    prod = graph.producer.get(a)
    if prod is None:
        return False
    node, _ = prod
    if node.prim == "iota":
        return True
    if node.prim in ("broadcast_in_dim", "convert_element_type", "reshape",
                     "squeeze", "expand_dims"):
        return _is_plain_iota(graph, node.invars[0], depth + 1)
    if node.prim in ("add", "sub"):
        lit = [x for x in node.invars if isinstance(x, jexcore.Literal)]
        others = [x for x in node.invars
                  if not isinstance(x, jexcore.Literal)]
        if len(lit) == 1 and float(lit[0].val) == 0.0 and len(others) == 1:
            return _is_plain_iota(graph, others[0], depth + 1)
        return False
    return False


_PASS_THROUGH_PRIMS = {"reshape", "convert_element_type", "squeeze",
                       "expand_dims", "broadcast_in_dim", "transpose"}


def _flash_lse_escapes(graph: JaxprGraph, node) -> bool:
    """True when the flash node's LSE output has LIVE consumers beyond
    pure shape plumbing — the signature of a grad graph (backward kernels
    read the residual)."""
    if len(node.outvars) < 2 or not isinstance(node.outvars[1], Var):
        return False
    out_set = {id(a) for a in graph.jaxpr.outvars}
    stack = [node.outvars[1]]
    while stack:
        v = stack.pop()
        if id(v) in out_set:
            return True
        for user in graph.arg_consumers(v):
            if user.prim not in _PASS_THROUGH_PRIMS:
                return True
            stack.extend(ov for ov in user.outvars
                         if isinstance(ov, Var)
                         and type(ov).__name__ != "DropVar")
    return False


def lower_motif_call(m: "AttentionMotif", mesh, axis_name: str, q, k, v):
    """Lower one motif to its chosen sequence-parallel algorithm (shared
    by the two rewrite paths: attention_motif.build_ring_rewritten and
    SpmdTransform.executable). Returns (o, lse_or_None): flash motifs run
    the PALLAS inner on their [B*H, T, D] layout and (ring only) return
    the global LSE so a live residual consumer can be re-bound."""
    from tepdist_tpu.ops.ring_attention import ring_attention
    from tepdist_tpu.ops.ulysses import ulysses_attention

    if m.impl == "ulysses":
        if m.flash:
            # Un-flatten [B*H, T, D] via the tagged head count so the
            # head<->seq all-to-all has a head dim to split; the pallas
            # inner returns (o, lse) so a live residual consumer can be
            # re-bound just like the ring path.
            from tepdist_tpu.ops.pallas.flash_attention import (
                flash_attention_with_lse,
            )
            BH, T, D = q.shape
            H = m.n_head
            q4, k4, v4 = (x.reshape(BH // H, H, T, D) for x in (q, k, v))
            o4, lse4 = ulysses_attention(
                q4, k4, v4, mesh, axis_name, causal=m.causal,
                scale=m.scale, return_lse=True,
                inner=lambda a, b, c: flash_attention_with_lse(
                    a, b, c, causal=m.causal, scale=m.scale))
            return o4.reshape(BH, T, D), lse4.reshape(BH, T)
        return ulysses_attention(q, k, v, mesh, axis_name,
                                 causal=m.causal, scale=m.scale), None
    if m.flash:
        ob, lseb = ring_attention(q[None], k[None], v[None], mesh,
                                  axis_name, causal=m.causal, scale=m.scale,
                                  inner="flash", return_lse=True)
        return ob[0], lseb[0]
    return ring_attention(q, k, v, mesh, axis_name, causal=m.causal,
                          scale=m.scale), None


def bind_motif_outputs(m: "AttentionMotif", node_outvars, o, lse, write):
    """Bind a lowered motif's outputs: the primary output always, the LSE
    onto the flash node's second outvar when it is live."""
    write(m.out, o.astype(m.out.aval.dtype))
    if (m.flash and lse is not None and len(node_outvars) > 1
            and type(node_outvars[1]).__name__ != "DropVar"):
        lse_var = node_outvars[1]
        write(lse_var, lse[..., None].astype(
            lse_var.aval.dtype).reshape(lse_var.aval.shape))


def detect_motifs(graph: JaxprGraph,
                  allow_escape: bool = False) -> List[AttentionMotif]:
    """Find all rewritable softmax(QK^T)V motifs.

    A motif is accepted only when the whole chain between the two dots is
    closed (no intermediate escapes to outside consumers) and any masking
    is a locally-generated iota comparison with a large-negative fill —
    i.e. the exact family of programs ``ops.ring_attention`` computes.

    ``allow_escape=True`` skips the closure check — used for *pricing* a
    seq proposal on a grad graph (the backward consumes the softmax
    probs, so fwd motifs there are never closed); actual rewriting always
    happens pre-differentiation on the closed forward graph."""
    motifs: List[AttentionMotif] = []
    claimed: Set[int] = set()
    # Flash call sites (VERDICT r3 weak #3): the kernel tags its forward
    # pallas_call with a self-describing name, so a flash model — where
    # the softmax(QK^T)V chain is fused inside the kernel and invisible
    # to the einsum matcher below — still gets a seq plan. Operands are
    # [B*H, T, D] (the kernel's flattened layout), so seq_dim=1.
    for node in graph.nodes:
        if node.prim != "pallas_call":
            continue
        name = node.eqn.params.get("name") or ""
        if not name.startswith("tepdist_flash_fwd"):
            continue
        try:
            parts = name.split("__")
            causal = bool(int(parts[1][1:]))
            scale = float(parts[2][1:])
            n_head = (int(parts[3][1:]) if len(parts) > 3
                      and parts[3].startswith("h") else None)
        except (IndexError, ValueError):
            continue
        if len(parts) > 4:
            # A window (``__w2048``) or fewer key/value heads (``__kv4``):
            # not the plain attention a ring or Ulysses rewrite computes.
            continue
        if len(node.invars) < 3 or not all(
                isinstance(a, Var) and len(a.aval.shape) == 3
                for a in node.invars[:3]):
            continue
        # Closure analogue of the einsum matcher's check: in a GRAD graph
        # the lse residual feeds the hand-written backward kernels (which
        # consume full-T K/V) — only the pre-differentiation forward
        # graph is rewritable; grad graphs see flash motifs solely in
        # pricing mode (allow_escape).
        if not allow_escape and _flash_lse_escapes(graph, node):
            continue
        q_var, k_var, v_var = node.invars[:3]
        motifs.append(AttentionMotif(
            qk_id=node.id, pv_id=node.id, member_ids={node.id},
            q=q_var, k=k_var, v=v_var, out=node.outvars[0],
            causal=causal, scale=scale,
            seq_len=int(q_var.aval.shape[1]), flash=True, seq_dim=1,
            n_head=n_head))
        claimed.add(node.id)
    for pv in graph.nodes:
        if not _is_pv_dot(pv) or pv.id in claimed:
            continue
        probs_var = pv.invars[0]
        v_var = pv.invars[1]
        if not isinstance(probs_var, Var) or not isinstance(v_var, Var):
            continue
        # Walk producers back from probs to the QK dot.
        members: Set[int] = set()
        qk = None
        stack = [probs_var]
        seen_vars: Set[int] = set()
        ok = True
        scale = 1.0
        has_mask = False
        n_compares = 0
        while stack and ok:
            cur = stack.pop()
            if id(cur) in seen_vars:
                continue
            seen_vars.add(id(cur))
            prod = graph.producer.get(cur)
            if prod is None:
                ok = False       # reaches a graph input: not a closed chain
                break
            node, _ = prod
            if node.id in members:
                continue
            if _is_qk_dot(node):
                if qk is not None and qk.id != node.id:
                    ok = False
                    break
                qk = node
                members.add(node.id)
                continue
            if node.prim not in _CHAIN_PRIMS:
                ok = False
                break
            members.add(node.id)
            if node.prim in ("mul", "div"):
                # Scalar-literal scaling of the logits. A huge-magnitude
                # literal is NOT a scale — it is an additive mask
                # (mask * -1e9) we cannot express: reject the motif
                # rather than silently corrupt the softmax temperature.
                for a in node.invars:
                    if isinstance(a, jexcore.Literal) and np.ndim(a.val) == 0:
                        val = float(a.val)
                        if abs(val) >= abs(_NEG_FILL):
                            ok = False
                            break
                        if node.prim == "mul":
                            scale *= val
                        elif a is node.invars[1]:   # div by literal only
                            scale /= val
            if node.prim in ("ge", "gt", "le", "lt"):
                n_compares += 1
                # The comparison must be between plain iotas (zero-offset;
                # jnp.tril emits ge(add(iota, 0), iota)): banded/windowed
                # masks shift or combine positions and are NOT plain
                # causal.
                for a in node.invars:
                    if not _is_plain_iota(graph, a):
                        ok = False
            if node.prim in ("and", "or", "eq", "ne"):
                ok = False       # composite masks are not plain causal
            if node.prim == "select_n":
                has_mask = True
                # A scalar-literal fill must be very negative (causal
                # mask), not an arbitrary blend.
                for a in node.invars[1:]:
                    if (isinstance(a, jexcore.Literal)
                            and np.ndim(a.val) == 0
                            and float(a.val) > _NEG_FILL):
                        ok = False
            for a in node.invars:
                if isinstance(a, Var):
                    stack.append(a)
        if not ok or qk is None or n_compares > 1:
            continue
        if has_mask and n_compares != 1:
            continue             # masked but not by a single iota compare
        q_var, k_var = qk.invars[0], qk.invars[1]
        # Closure: every member's outputs are consumed inside the motif
        # (or by the PV dot).
        inside = members | {pv.id}
        closed = True
        for nid in members:
            for ov in graph.nodes[nid].outvars:
                if not isinstance(ov, Var):
                    continue
                for user in graph.arg_consumers(ov):
                    if user.id not in inside:
                        closed = False
        if not closed and not allow_escape:
            continue
        members.add(pv.id)
        motifs.append(AttentionMotif(
            qk_id=qk.id, pv_id=pv.id, member_ids=members,
            q=q_var, k=k_var, v=v_var, out=pv.outvars[0],
            causal=has_mask, scale=scale,
            seq_len=int(q_var.aval.shape[2]),
            n_head=int(q_var.aval.shape[1])))
        claimed.update(members)
    return motifs


def ring_comm_cost(motifs: List[AttentionMotif], num_splits: int,
                   spec=None, with_backward: bool = False) -> float:
    """EXPOSED ring-attention comm per motif.

    The ring schedule overlaps each K/V neighbor hop with the attention
    compute of the previous block (per-hop pipelining is structural in
    ops/ring_attention.py: ppermute is dispatched before the block math).
    Per hop, only max(alpha, hop_bytes/bw - block_compute) is exposed —
    this is why ring attention wins at long T: block compute grows as
    (T/P)^2 while hop bytes grow as T/P. ``with_backward`` adds the
    reverse ring (2x messages: K,V and dK,dV; ~2x block compute)."""
    from tepdist_tpu.graph.cost import aval_bytes
    from tepdist_tpu.parallel.performance_utils import (
        ALPHA_S,
        PerfUtils,
        chip_spec,
    )

    spec = spec or chip_spec()
    t = 0.0
    for m in motifs:
        if num_splits <= 1:
            continue
        kv_bytes = (aval_bytes(m.k.aval) + aval_bytes(m.v.aval)) / num_splits
        hop = PerfUtils.ppermute_cost(kv_bytes, spec)
        shape = m.q.aval.shape
        if len(shape) == 4:
            B, H, T, D = shape
        else:                       # flash layout [B*H, T, D]
            BH, T, D = shape
            B, H = 1, BH
        blk = T // num_splits
        # QK^T + PV per block pair: 4*B*H*blk^2*D flops.
        block_compute = PerfUtils.compute_time(4.0 * B * H * blk * blk * D,
                                               spec)
        t += (num_splits - 1) * max(ALPHA_S, hop - block_compute)
        if with_backward:
            t += (num_splits - 1) * max(ALPHA_S,
                                        2.0 * hop - 2.0 * block_compute)
    return t


def ulysses_comm_cost(motifs: List[AttentionMotif], num_splits: int,
                      spec=None, with_backward: bool = False) -> float:
    """Ulysses comm per motif: 4 head<->seq all-to-alls forward (q, k, v
    in; o out), fully EXPOSED (a2a -> compute -> a2a is serial, unlike the
    ring's overlapped hops); the backward's transposed a2as double it.
    inf when any motif's head count does not divide."""
    from tepdist_tpu.graph.cost import aval_bytes
    from tepdist_tpu.parallel.performance_utils import PerfUtils, chip_spec

    spec = spec or chip_spec()
    t = 0.0
    for m in motifs:
        if num_splits <= 1:
            continue
        if not m.n_head or m.n_head % num_splits:
            return float("inf")
        local_bytes = aval_bytes(m.q.aval) / num_splits
        one = PerfUtils.all_to_all_cost(local_bytes, num_splits, spec)
        t += 4.0 * one
        if with_backward:
            t += 4.0 * one
    return t


def best_seq_comm(motifs: List[AttentionMotif], num_splits: int,
                  spec=None, with_backward: bool = False
                  ) -> Tuple[str, float]:
    """(impl, seconds): the cheaper of ring and ulysses for this motif
    set. Ring usually wins (hops overlap block compute and it moves only
    K/V); ulysses can win at short sequence / many heads / large P where
    the ring's (P-1) serialized latencies dominate."""
    ring = ring_comm_cost(motifs, num_splits, spec,
                          with_backward=with_backward)
    uly = ulysses_comm_cost(motifs, num_splits, spec,
                            with_backward=with_backward)
    return ("ulysses", uly) if uly < ring else ("ring", ring)


def build_seq_strategy(graph: JaxprGraph, num_splits: int,
                       motifs: Optional[List[AttentionMotif]] = None,
                       chip=None) -> "GraphStrategy":
    """Plan the ``seq`` axis: sequence-split attention via ring rewrite,
    token-dim propagation elsewhere (shared transfer functions)."""
    from tepdist_tpu.parallel.cost_spmd_strategy import GraphStrategy
    from tepdist_tpu.parallel.fast_spmd_strategy import FastSpmdStrategy

    if motifs is None:
        motifs = detect_motifs(graph)
    if not motifs:
        raise ValueError("seq axis proposed but no attention motif found")
    for m in motifs:
        if m.seq_len % num_splits:
            raise ValueError(
                f"seq len {m.seq_len} not divisible by seq={num_splits}")

    seeds: Dict[Var, DimStrategy] = {}
    for m in motifs:
        split_t = DimStrategy(partition_dim=m.seq_dim,
                              num_splits=num_splits)
        for v in (m.q, m.k, m.v, m.out):
            seeds[v] = split_t
    gs = FastSpmdStrategy(graph, "seq", num_splits, seeds).run()
    # The motif interiors are replaced by the ring rewrite — their
    # strategies must not leak GSPMD constraints ([B,H,Tq,Tk] logits
    # would otherwise be constrained on a dim the rewrite removes).
    for m in motifs:
        for nid in m.member_ids:
            if nid != m.pv_id:
                gs.node_out.pop(nid, None)
    # Choose AND price fwd+bwd: the lowered rewrite is differentiated
    # (both directions run), and the exploration path prices rival
    # candidates with_backward=True — a fwd-only argmin here could pick
    # an algorithm the candidate was not priced with.
    impl, comm = best_seq_comm(motifs, num_splits, chip,
                               with_backward=True)
    for m in motifs:
        m.impl = impl
    gs.motifs = motifs
    gs.comm_cost = comm
    gs.ilp_status = f"seq-{impl}"
    return gs


def build_ring_rewritten(graph: JaxprGraph, motifs: List[AttentionMotif],
                         mesh, axis_name: str = "seq"):
    """Return a differentiable callable over the graph's FLAT invars that
    computes the same program with every motif replaced by
    ``ops.ring_attention`` (shard_map + ppermute over ``axis_name``).

    Runs pre-differentiation: ``jax.value_and_grad`` of the result traces
    ring attention's own backward (a reverse ring), so the full training
    step keeps the sequence dimension sharded in both directions —
    reference parity: none (SURVEY §5.7: the reference has only the
    'token parallel' slot, no algorithm)."""
    from jax.extend.core import Literal

    skip: Set[int] = set()
    for m in motifs:
        skip |= m.member_ids
    at_pv = {m.pv_id: m for m in motifs}
    jaxpr = graph.jaxpr
    consts = list(graph.closed.consts)

    def run(*flat_args):
        import jax

        env: Dict[Var, object] = {}

        def read(a):
            return a.val if isinstance(a, Literal) else env[a]

        for cv, c in zip(jaxpr.constvars, consts):
            env[cv] = c
        for iv, a in zip(jaxpr.invars, flat_args):
            env[iv] = a
        def write(v, val):
            env[v] = val

        for i, eqn in enumerate(jaxpr.eqns):
            if i in at_pv:
                m = at_pv[i]
                o, lse = lower_motif_call(m, mesh, axis_name, read(m.q),
                                          read(m.k), read(m.v))
                bind_motif_outputs(m, graph.nodes[i].outvars, o, lse, write)
                continue
            if i in skip:
                continue
            vals = [read(a) for a in eqn.invars]
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            outs = eqn.primitive.bind(*subfuns, *vals, **bind_params)
            if not eqn.primitive.multiple_results:
                outs = [outs]
            for ov, val in zip(eqn.outvars, outs):
                if type(ov).__name__ != "DropVar":
                    env[ov] = val
        return tuple(read(a) for a in jaxpr.outvars)

    return run


def seq_rewritten_loss(loss_fn, seq_size: int, mesh, *example_args,
                       impl: Optional[str] = None):
    """Rewrite ``loss_fn``'s attention motifs to the priced ring/Ulysses
    algorithm for a ``seq`` axis of ``seq_size`` — the ONE seq-lowering
    composition shared by plan_training, the library explorer, and the
    RPC service's explore mode (SURVEY §5.7; the rewrite runs BEFORE
    differentiation so value_and_grad traces the reverse ring and the
    sequence dim stays sharded in both directions).

    Returns ``(rewritten_fn, impl)`` where ``rewritten_fn`` takes the same
    positional args as ``loss_fn``. Raises ValueError when no closed
    motif is rewritable (escaping motifs are priceable, not lowerable)."""
    import jax as _jax

    from tepdist_tpu.graph.jaxpr_graph import trace_graph

    g_loss, _, _ = trace_graph(loss_fn, *example_args)
    motifs = detect_motifs(g_loss)
    if not motifs:
        raise ValueError("topology has a 'seq' axis but the loss has "
                         "no rewritable attention motif")
    if impl is None:
        impl, _ = best_seq_comm(motifs, seq_size, with_backward=True)
    for m in motifs:
        m.impl = impl
    rw = build_ring_rewritten(g_loss, motifs, mesh, "seq")

    def rewritten(*args, _rw=rw):
        flat, _ = _jax.tree_util.tree_flatten((args, {}))
        return _rw(*flat)[0]

    return rewritten, impl
