"""Pre-dispatch static plan verifier: gate every TaskDAG before it ships.

Reference parity: NONE (deliberate surplus). TePDist's pitch is that the
*system* decides the split — which means a planner bug silently ships a
wrong or deadlock-prone task DAG to the whole fleet. GSPMD
(arXiv:2105.04663) treats sharding-annotation consistency as a checkable
propagation invariant; the MPMD pipeline-parallel work (arXiv:2412.14374)
shows cross-stage send/recv matching is exactly where hand-rolled
distributed runtimes deadlock. This module machine-checks both families
of invariants at plan time, before anything runs:

  1. **structure** — node ids match indices, parents/children mirror each
     other, every input spec is wired from an actual parent.
  2. **acyclic** — the dataflow graph is a DAG; a violation carries the
     cycle's task ids as the counterexample.
  3. **transfer pairing** — every SEND has exactly one matching RECV
     (same byte count, different device groups) and vice versa; orphans
     and mismatches name the offending task(s).
  4. **wait-cycle (deadlock)** — over the COMBINED graph of dataflow
     edges + per-device serialized execution order (each device runs its
     task list sequentially; a RECV blocks until the peer's SEND ran), a
     cycle means the fleet deadlocks at runtime. The counterexample is
     the wait cycle's task ids.
  5. **exactly-once writes** — per stage exactly one INPUT/GAINIT/APPLY,
     per (stage, micro) exactly one fwd/bwd/GA, one SPLIT source and one
     MERGE sink: a duplicated writer names the double-writer pair, a
     missing one names the hole.
  6. **signature consistency** — with the :class:`PipelineProgram` in
     hand, every cross-stage ``input_def_map`` entry must point at an
     existing producer output whose aval (shape + dtype) matches the
     consumer's invar (the DistSpec/sub-module signature invariant).
  7. **static peak HBM** — replay the scheduled order tracking live
     output bytes per device (the liveness discipline of
     ``parallel/liveness.py`` applied to the task graph, mirroring
     ``TaskScheduler._memory_account`` without mutating the DAG's GC
     plan) and reject plans whose simulated peak exceeds the chip's HBM.

Violations raise :class:`PlanVerificationError` (a typed
``TaskGraphError``) carrying ``kind`` + the minimal counterexample task
ids. The gate is wired into ``PipelineExecutable`` (the explore-winner
build path), ``DistributedPipelineSession`` (fleet dispatch) and
``LoadServable`` (serving), behind the ``TEPDIST_VERIFY_PLAN`` knob — on
by default under pytest; off it is a no-op
(``tests/test_plan_verify.py::test_gate_is_a_noop_when_disabled``).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

from tepdist_tpu.runtime.task_graph import (
    TaskDAG,
    TaskGraphError,
    TaskNode,
    TaskType,
)


class PlanVerificationError(TaskGraphError):
    """A statically-detected plan defect. ``kind`` names the violated
    invariant; ``tasks`` is the minimal counterexample (the cycle's task
    ids, the orphan SEND, the double-writer pair, ...)."""


@dataclasses.dataclass
class PlanVerifyReport:
    """What a clean verification looked at (returned on success)."""

    n_tasks: int
    n_edges: int
    checks: List[str]
    peak_bytes: Dict[int, float]          # per device, from the replay
    hbm_limit_bytes: Optional[float]
    verify_ms: float
    where: str = ""

    def summary(self) -> str:
        peak = max(self.peak_bytes.values(), default=0.0)
        return (f"plan verified [{', '.join(self.checks)}] "
                f"{self.n_tasks} tasks / {self.n_edges} edges, "
                f"peak {peak / 1e6:.2f} MB/dev, {self.verify_ms:.2f} ms")


# ---------------------------------------------------------------------
# individual checks (each raises PlanVerificationError on violation)
# ---------------------------------------------------------------------

def _check_structure(dag: TaskDAG) -> int:
    """Ids match indices; parent/child lists mirror; input specs wired
    from actual parents. Returns the edge count."""
    n_edges = 0
    n_nodes = len(dag.nodes)
    for i, n in enumerate(dag.nodes):
        if n.id != i:
            raise PlanVerificationError(
                "structure", f"node at index {i} carries id {n.id}",
                tasks=(n.id,))
        for c in n.children:
            if not 0 <= c < n_nodes:
                raise PlanVerificationError(
                    "structure", f"{n.key()} has out-of-range child {c}",
                    tasks=(n.id,))
            if n.id not in dag.nodes[c].parents:
                raise PlanVerificationError(
                    "structure",
                    f"edge {n.key()} -> {dag.nodes[c].key()} is not "
                    f"mirrored in the child's parents",
                    tasks=(n.id, c))
            n_edges += 1
        for p in n.parents:
            if not 0 <= p < n_nodes or n.id not in dag.nodes[p].children:
                raise PlanVerificationError(
                    "structure",
                    f"{n.key()} lists parent {p} that does not list it "
                    f"as a child", tasks=(n.id, p))
        for pos, (pid, _oi) in n.input_specs.items():
            if pid not in n.parents:
                raise PlanVerificationError(
                    "structure",
                    f"{n.key()} arg {pos} wired from non-parent task "
                    f"{pid}", tasks=(n.id, pid))
    return n_edges


def _find_cycle(succ: Dict[int, Sequence[int]]) -> Optional[List[int]]:
    """Iterative DFS over ``succ``; returns one cycle's node ids (in
    order) or None."""
    WHITE, GREY, BLACK = 0, 1, 2
    color = {v: WHITE for v in succ}
    for root in succ:
        if color[root] != WHITE:
            continue
        stack: List[Tuple[int, int]] = [(root, 0)]
        path: List[int] = []
        color[root] = GREY
        path.append(root)
        while stack:
            v, idx = stack[-1]
            kids = succ.get(v, ())
            if idx < len(kids):
                stack[-1] = (v, idx + 1)
                c = kids[idx]
                if color.get(c, BLACK) == GREY:
                    # Found: slice the grey path from c onward.
                    return path[path.index(c):] + [c]
                if color.get(c, BLACK) == WHITE:
                    color[c] = GREY
                    stack.append((c, 0))
                    path.append(c)
            else:
                color[v] = BLACK
                stack.pop()
                path.pop()
    return None


def _check_acyclic(dag: TaskDAG) -> None:
    succ = {n.id: list(n.children) for n in dag.nodes}
    cycle = _find_cycle(succ)
    if cycle is not None:
        names = " -> ".join(dag.nodes[t].key() for t in cycle)
        raise PlanVerificationError(
            "cycle", f"dataflow cycle: {names}", tasks=cycle[:-1])


def _check_transfer_pairing(dag: TaskDAG) -> None:
    for n in dag.nodes:
        if n.task_type == TaskType.SEND:
            recvs = [c for c in n.children
                     if dag.nodes[c].task_type == TaskType.RECV]
            if not recvs:
                raise PlanVerificationError(
                    "orphan_send",
                    f"{n.key()} has no matching RECV consumer",
                    tasks=(n.id,))
            if len(recvs) > 1 or len(n.children) != 1:
                raise PlanVerificationError(
                    "send_fanout",
                    f"{n.key()} must feed exactly one RECV, has "
                    f"children {sorted(n.children)}",
                    tasks=[n.id] + sorted(n.children))
            r = dag.nodes[recvs[0]]
            if r.input_specs.get(0, (None, None))[0] != n.id:
                raise PlanVerificationError(
                    "transfer_wiring",
                    f"{r.key()} arg 0 is not wired from its SEND "
                    f"{n.key()}", tasks=(n.id, r.id))
            if abs(n.out_bytes - r.out_bytes) > 0.5:
                raise PlanVerificationError(
                    "transfer_bytes_mismatch",
                    f"{n.key()} ships {n.out_bytes:.0f} B but "
                    f"{r.key()} expects {r.out_bytes:.0f} B "
                    f"(shape/dtype disagreement)", tasks=(n.id, r.id))
            if tuple(n.device_group) == tuple(r.device_group) \
                    and n.device_group:
                raise PlanVerificationError(
                    "transfer_same_group",
                    f"{n.key()} -> {r.key()} transfers within one device "
                    f"group {n.device_group} (should be a direct edge)",
                    tasks=(n.id, r.id))
        elif n.task_type == TaskType.RECV:
            sends = [p for p in n.parents
                     if dag.nodes[p].task_type == TaskType.SEND]
            if len(sends) != 1:
                raise PlanVerificationError(
                    "orphan_recv",
                    f"{n.key()} must have exactly one SEND producer, "
                    f"has {len(sends)}", tasks=[n.id] + sends)


def _device_chains(dag: TaskDAG, order: Sequence[int]
                   ) -> Dict[int, List[int]]:
    """Per-device serialized execution order implied by ``order`` (a
    device runs every task whose group contains it, in order)."""
    chains: Dict[int, List[int]] = {}
    for tid in order:
        for d in dag.nodes[tid].device_group:
            chains.setdefault(d, []).append(tid)
    return chains


def _check_wait_cycles(dag: TaskDAG, order: Sequence[int]) -> None:
    """Deadlock check: dataflow edges + per-device serialization edges
    must still form a DAG. A cycle here is a real runtime wait cycle:
    task A waits for B's data while B's device won't reach B until A's
    device releases it."""
    if len(order) != len(dag.nodes) or set(order) != set(
            n.id for n in dag.nodes):
        raise PlanVerificationError(
            "order", f"serialized order covers {len(set(order))} of "
            f"{len(dag.nodes)} tasks", tasks=())
    succ: Dict[int, List[int]] = {n.id: list(n.children)
                                  for n in dag.nodes}
    for _dev, chain in _device_chains(dag, order).items():
        for a, b in zip(chain, chain[1:]):
            if b not in succ[a]:
                succ[a].append(b)
    cycle = _find_cycle(succ)
    if cycle is not None:
        names = " -> ".join(dag.nodes[t].key() for t in cycle)
        raise PlanVerificationError(
            "wait_cycle",
            f"cross-worker wait cycle (deadlock) over serialized order "
            f"+ transfer edges: {names}", tasks=cycle[:-1])


def _is_fwd(n: TaskNode) -> bool:
    return n.task_type == TaskType.COMPUTE and "bwd" not in n.name


def _check_exactly_once(dag: TaskDAG) -> None:
    """Per-step write coverage: every stage's variables applied by
    exactly one APPLY, every (stage, micro)'s gradient accumulated by
    exactly one GA, every compute slot filled exactly once."""
    per_stage: Dict[Tuple[TaskType, int], List[int]] = {}
    per_sm: Dict[Tuple[str, int, int], List[int]] = {}
    sources, sinks = [], []
    for n in dag.nodes:
        if n.task_type in (TaskType.INPUT, TaskType.GAINIT, TaskType.APPLY):
            per_stage.setdefault((n.task_type, n.stage), []).append(n.id)
        elif n.task_type == TaskType.GA:
            per_sm.setdefault(("ga", n.stage, n.micro), []).append(n.id)
        elif n.task_type == TaskType.COMPUTE:
            kind = "fwd" if _is_fwd(n) else "bwd"
            per_sm.setdefault((kind, n.stage, n.micro), []).append(n.id)
        elif n.task_type == TaskType.SPLIT:
            sources.append(n.id)
        elif n.task_type == TaskType.MERGE:
            sinks.append(n.id)
    for (ty, stage), ids in per_stage.items():
        if len(ids) > 1:
            names = ", ".join(dag.nodes[t].key() for t in ids)
            raise PlanVerificationError(
                "double_write",
                f"stage {stage} written by {len(ids)} {ty.value} tasks "
                f"({names}); exactly one may write per step", tasks=ids)
    stages = {s for (_ty, s) in per_stage}
    for ty in (TaskType.INPUT, TaskType.GAINIT, TaskType.APPLY):
        for s in stages:
            if (ty, s) not in per_stage:
                raise PlanVerificationError(
                    "missing_writer",
                    f"stage {s} has no {ty.value} task", tasks=())
    for (kind, stage, micro), ids in per_sm.items():
        if len(ids) > 1:
            names = ", ".join(dag.nodes[t].key() for t in ids)
            raise PlanVerificationError(
                "double_write",
                f"(stage {stage}, micro {micro}) has {len(ids)} {kind} "
                f"tasks ({names}); exactly one may write its slot",
                tasks=ids)
    for role, ids in (("SPLIT source", sources), ("MERGE sink", sinks)):
        if len(ids) > 1:
            raise PlanVerificationError(
                "double_write", f"plan has {len(ids)} {role} tasks",
                tasks=ids)


def _check_signatures(dag: TaskDAG, prog) -> None:
    """Cross-stage signature consistency on the PipelineProgram: every
    ``input_def_map`` entry of the form ("stage", t, k) must name an
    existing output of stage t whose aval matches the consumer invar."""
    S = prog.num_stages
    for s in range(S):
        mod = prog.stages[s]
        for pos in range(len(mod.invars)):
            src = mod.input_def_map.get(pos)
            if not src or src[0] != "stage":
                continue
            t, k = src[1], src[2]
            if not 0 <= t < S:
                raise PlanVerificationError(
                    "signature",
                    f"stage {s} arg {pos} consumes from non-existent "
                    f"stage {t} (plan has {S} stages)", tasks=())
            outs = prog.stages[t].outvars
            if not 0 <= k < len(outs):
                raise PlanVerificationError(
                    "signature",
                    f"stage {s} arg {pos} consumes output {k} of stage "
                    f"{t}, which has only {len(outs)} outputs", tasks=())
            pa, ca = outs[k].aval, mod.invars[pos].aval
            if tuple(pa.shape) != tuple(ca.shape) or pa.dtype != ca.dtype:
                raise PlanVerificationError(
                    "signature",
                    f"stage {t} out {k} is {pa.shape}/{pa.dtype} but "
                    f"stage {s} arg {pos} expects {ca.shape}/{ca.dtype}",
                    tasks=())


def _replay_peak_bytes(dag: TaskDAG, order: Sequence[int]
                       ) -> Tuple[Dict[int, float], Dict[int, int]]:
    """Liveness replay of the scheduled order (same accounting as
    ``TaskScheduler._memory_account``, without mutating the DAG's GC
    plan): a producer's output bytes stay live until its LAST consumer
    in the order completes. Returns (per-device peak bytes, per-device
    task id holding the most bytes at that device's peak)."""
    pos = {tid: i for i, tid in enumerate(order)}
    last_consumer: Dict[int, int] = {}
    for n in dag.nodes:
        for (pid, _oi) in n.input_specs.values():
            cur = last_consumer.get(pid)
            if cur is None or pos[n.id] > pos[cur]:
                last_consumer[pid] = n.id
    release_at: Dict[int, List[int]] = {}
    for pid, cid in last_consumer.items():
        release_at.setdefault(cid, []).append(pid)
    live: Dict[int, float] = {}
    peak: Dict[int, float] = {}
    share: Dict[int, float] = {}
    top_task: Dict[int, int] = {}
    biggest: Dict[int, Tuple[float, int]] = {}   # dev -> (bytes, tid) live
    for tid in order:
        n = dag.nodes[tid]
        share[tid] = n.out_bytes / max(len(n.device_group), 1)
        for d in n.device_group:
            live[d] = live.get(d, 0.0) + share[tid]
            if share[tid] >= biggest.get(d, (0.0, -1))[0]:
                biggest[d] = (share[tid], tid)
            if live[d] > peak.get(d, 0.0):
                peak[d] = live[d]
                top_task[d] = biggest[d][1]
        for rid in release_at.get(tid, ()):
            rshare = share.get(rid, 0.0)
            for d in dag.nodes[rid].device_group:
                live[d] = live.get(d, 0.0) - rshare
    return peak, top_task


def _check_peak_hbm(dag: TaskDAG, order: Sequence[int],
                    limit_bytes: float) -> Dict[int, float]:
    peak, top_task = _replay_peak_bytes(dag, order)
    for d in sorted(peak):
        if peak[d] > limit_bytes:
            tid = top_task.get(d, -1)
            culprit = (dag.nodes[tid].key() if tid >= 0 else "?")
            raise PlanVerificationError(
                "hbm_overflow",
                f"device {d} peaks at {peak[d] / 1e9:.3f} GB > HBM "
                f"capacity {limit_bytes / 1e9:.3f} GB (largest live "
                f"buffer: {culprit})",
                tasks=[tid] if tid >= 0 else [])
    return peak


# ---------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------

def verify_plan(dag: TaskDAG, *, order: Optional[Sequence[int]] = None,
                schedule=None, prog=None,
                hbm_limit_bytes: Optional[float] = None,
                chip=None, where: str = "") -> PlanVerifyReport:
    """Run every static check against ``dag``. Raises
    :class:`PlanVerificationError` carrying a minimal counterexample on
    the first violation; returns a :class:`PlanVerifyReport` when clean.

    ``order``/``schedule``: the serialized execution order (a
    ``ScheduleResult`` wins over a bare id list); without either, the
    node-id topological order is assumed. ``prog``: the
    ``PipelineProgram``, enabling the cross-stage signature check.
    ``hbm_limit_bytes``: per-device capacity for the peak-memory check
    (default: the scheduler's chip spec; when the spec comes from an
    ``HBM_GB`` env override the check is advisory-only, since that knob
    emulates a cost-model regime rather than real capacity; pass
    0/negative to skip)."""
    t0 = time.perf_counter()
    if schedule is not None and order is None:
        order = schedule.order
    checks = []
    n_edges = _check_structure(dag)
    checks.append("structure")
    _check_acyclic(dag)
    checks.append("acyclic")
    _check_transfer_pairing(dag)
    checks.append("transfer_pairing")
    if order is None:
        order = [n.id for n in dag.topo_order()]
    _check_wait_cycles(dag, order)
    checks.append("wait_cycle")
    _check_exactly_once(dag)
    checks.append("exactly_once")
    if prog is not None:
        _check_signatures(dag, prog)
        checks.append("signature")
    hbm_advisory = False
    if hbm_limit_bytes is None:
        from tepdist_tpu.parallel.performance_utils import chip_spec
        spec = chip or chip_spec()
        hbm_limit_bytes = spec.hbm_gb * 1e9
        # HBM_GB is a cost-model *emulation* knob (tests shrink it to
        # force pipeline cuts on CPU); the explore planner treats memory
        # as a soft cost term, so its winner may legitimately exceed the
        # emulated capacity. Record the peak, don't reject.
        hbm_advisory = chip is None and "HBM_GB" in os.environ
    peak: Dict[int, float] = {}
    if hbm_limit_bytes > 0:
        if hbm_advisory:
            peak, _ = _replay_peak_bytes(dag, order)
            checks.append("peak_hbm(advisory)")
        else:
            peak = _check_peak_hbm(dag, order, hbm_limit_bytes)
            checks.append("peak_hbm")
    verify_ms = (time.perf_counter() - t0) * 1e3
    from tepdist_tpu.telemetry import metrics
    metrics().counter("plan_verified").inc()
    return PlanVerifyReport(
        n_tasks=len(dag.nodes), n_edges=n_edges, checks=checks,
        peak_bytes=peak, hbm_limit_bytes=hbm_limit_bytes,
        verify_ms=verify_ms, where=where)


def verify_enabled() -> bool:
    from tepdist_tpu.core.service_env import ServiceEnv
    return bool(ServiceEnv.get().tepdist_verify_plan)


def maybe_verify_plan(dag: TaskDAG, *, schedule=None, prog=None,
                      where: str = "") -> Optional[PlanVerifyReport]:
    """The dispatch-path gate: verify when ``TEPDIST_VERIFY_PLAN`` is on
    (default under pytest), no-op otherwise. A violation always raises —
    shipping a provably-broken plan to the fleet is never the right
    outcome once it has been detected."""
    if not verify_enabled():
        return None
    return verify_plan(dag, schedule=schedule, prog=prog, where=where)


# ---------------------------------------------------------------------
# serving-plan gate (LoadServable)
# ---------------------------------------------------------------------

def verify_servable(cfg, *, slots: int, max_len: int,
                    buckets: Sequence[int],
                    hbm_limit_bytes: Optional[float] = None,
                    dtype_bytes: Optional[int] = None,
                    kv_mode: str = "slots",
                    page_size: Optional[int] = None,
                    n_pages: Optional[int] = None,
                    where: str = "") -> None:
    """Static pre-load check for a serving plan: bucket shape sanity and
    the KV + weight HBM budget — slot mode counts slots x max_len token
    rows, paged mode counts the page pool (n_pages x page_size tokens,
    which must at least fit one max_len request). The serving analogue
    of the training peak-HBM gate; gated by the same
    ``TEPDIST_VERIFY_PLAN`` knob at the call site."""
    if kv_mode not in ("slots", "paged"):
        raise PlanVerificationError(
            "servable", f"unknown kv_mode {kv_mode!r}")
    if kv_mode == "slots" and slots < 1:
        raise PlanVerificationError(
            "servable", f"need at least one KV slot, got {slots}")
    if max_len < 1:
        raise PlanVerificationError(
            "servable", f"max_len must be positive, got {max_len}")
    if kv_mode == "paged":
        if page_size is None or page_size < 1:
            raise PlanVerificationError(
                "servable", f"paged KV needs a positive page_size, "
                            f"got {page_size}")
        min_pages = -(-max_len // page_size)
        if n_pages is None or n_pages < min_pages:
            raise PlanVerificationError(
                "servable",
                f"page pool of {n_pages} pages x {page_size} tokens "
                f"cannot hold one max_len={max_len} request "
                f"(needs >= {min_pages} pages)")
    bs = list(buckets)
    if not bs or sorted(bs) != bs or len(set(bs)) != len(bs):
        raise PlanVerificationError(
            "servable",
            f"prefill buckets must be strictly increasing, got {bs}")
    if bs[-1] > max_len:
        raise PlanVerificationError(
            "servable",
            f"largest prefill bucket {bs[-1]} exceeds max_len {max_len}")
    if hbm_limit_bytes is None:
        from tepdist_tpu.parallel.performance_utils import chip_spec
        hbm_limit_bytes = chip_spec().hbm_gb * 1e9
    if dtype_bytes is None:
        try:
            import numpy as np
            dtype_bytes = int(np.dtype(getattr(cfg, "dtype",
                                               "float32")).itemsize)
        except TypeError:
            dtype_bytes = 4
    n_layer = int(getattr(cfg, "n_layer", 0))
    d_model = int(getattr(cfg, "d_model", getattr(cfg, "n_embd", 0)))
    if kv_mode == "paged":
        # +1: physical page 0 is the reserved trash page.
        kv_tokens = (n_pages + 1) * page_size
        kv_what = f"{n_pages}+1 pages x {page_size} tokens"
    else:
        kv_tokens = slots * max_len
        kv_what = f"{slots} slots x {max_len}"
    kv_bytes = 2.0 * kv_tokens * n_layer * d_model * dtype_bytes
    vocab = int(getattr(cfg, "vocab_size", 0))
    weight_bytes = float(12 * n_layer * d_model * d_model
                         + vocab * d_model) * dtype_bytes
    if hbm_limit_bytes > 0 and kv_bytes + weight_bytes > hbm_limit_bytes:
        raise PlanVerificationError(
            "hbm_overflow",
            f"servable KV cache ({kv_bytes / 1e9:.3f} GB = {kv_what} "
            f"x 2 x {n_layer} layers x {d_model}) + weights "
            f"({weight_bytes / 1e9:.3f} GB) exceed HBM "
            f"{hbm_limit_bytes / 1e9:.3f} GB{' at ' + where if where else ''}")
    from tepdist_tpu.telemetry import metrics
    metrics().counter("plan_verified").inc()


def verify_sharded_servable(cfg, *, stages, max_len: int,
                            hbm_limit_bytes: Optional[float] = None,
                            dtype_bytes: Optional[int] = None,
                            where: str = "") -> Dict[int, float]:
    """The sharded arm of ``verify_servable`` (ISSUE 19): per-STAGE fit
    instead of whole-model fit. ``stages`` is a sequence of
    ``(lo, hi, first, last)`` layer ranges — the fleet loader passes all
    of them, a worker receiving one stage passes just its own. Per stage:
    12*d^2 transformer weights per layer, the embedding tables where they
    physically live (wte+wpe on the FIRST stage; wte again plus ln_f on
    the LAST — the tied logits matmul needs its own copy), and a
    [layers, 1, n_head, max_len, head_dim] k/v cache pair. Raises
    ``hbm_overflow`` naming the offending stage; returns the per-stage
    byte footprints for the planner's records."""
    if max_len < 1:
        raise PlanVerificationError(
            "servable", f"max_len must be positive, got {max_len}")
    if hbm_limit_bytes is None:
        from tepdist_tpu.parallel.performance_utils import chip_spec
        hbm_limit_bytes = chip_spec().hbm_gb * 1e9
    if dtype_bytes is None:
        try:
            import numpy as np
            dtype_bytes = int(np.dtype(getattr(cfg, "dtype",
                                               "float32")).itemsize)
        except TypeError:
            dtype_bytes = 4
    d_model = int(getattr(cfg, "d_model", getattr(cfg, "n_embd", 0)))
    vocab = int(getattr(cfg, "vocab_size", 0))
    n_ctx = int(getattr(cfg, "n_ctx", max_len))
    out: Dict[int, float] = {}
    for s, (lo, hi, first, last) in enumerate(stages):
        layers = int(hi) - int(lo)
        if layers < 1:
            raise PlanVerificationError(
                "servable", f"stage {s} has empty layer range "
                            f"[{lo}, {hi})")
        weight_bytes = float(12 * layers * d_model * d_model
                             + 13 * layers * d_model) * dtype_bytes
        if first:
            weight_bytes += float(vocab * d_model
                                  + n_ctx * d_model) * dtype_bytes
        if last:
            weight_bytes += float(vocab * d_model + 2 * d_model) \
                * dtype_bytes
        kv_bytes = 2.0 * max_len * layers * d_model * dtype_bytes
        out[s] = kv_bytes + weight_bytes
        if hbm_limit_bytes > 0 and out[s] > hbm_limit_bytes:
            raise PlanVerificationError(
                "hbm_overflow",
                f"stage {s} (layers [{lo}, {hi})) KV "
                f"({kv_bytes / 1e9:.4f} GB) + weights "
                f"({weight_bytes / 1e9:.4f} GB) exceed per-device HBM "
                f"{hbm_limit_bytes / 1e9:.4f} GB"
                f"{' at ' + where if where else ''}")
    from tepdist_tpu.telemetry import metrics
    metrics().counter("plan_verified").inc()
    return out
