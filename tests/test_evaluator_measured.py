"""Measured validation of the exploration ranking (VERDICT r1 item 3 /
r2 next #7): the Evaluator's analytic cost must agree with the work the
plans' compiled executables do on the CPU mesh, for plans it is asked to
rank — specifically on the property exploration actually consumes, the
argmin. No case reads a clock: the suite's workers share their machine, and
a step of 50 ms alone read 255-382 ms beside five others, in the order of
the load and not of the plans (``_compiled_work`` below is the ruler).

Three genuinely different single-axis plans of the same training step
(annotation-forced, so the cost planner cannot collapse them into one):

  dp   — batch-dim split of the tokens arg (grad psums at apply)
  tp   — every >=2D weight split on its LAST dim (activation psums)
  tp0  — every >=2D weight split on dim 0 (forces input gathers)

Asserted: the evaluator's cheapest plan is also the cheapest by what the
compiled executables say of themselves (``_compiled_work``: a ruler no other
test worker's load can bend), the evaluator's costs genuinely discriminate (not degenerate — the
r2 state where every topology priced identically because comm collapsed
to zero), and every comm-bearing plan reports nonzero exposed collective
time.

Cross-axis conflicts (split on mesh axis x produced, split on y
demanded — GSPMD resolves them with involuntary rematerialization) are
PRICED since r5: the evaluator's hidden-gather pass charges the
all-gather GSPMD performs for a split input consumed by a node left
replicated on that axis, and entangled partition-dim changes upgrade to
full-remat pricing (evaluator.py:_hidden_gather_time/_reshard_time;
asserted below in test_cross_axis_conflict_priced_and_loses).
"""

import collections
import functools
import math
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from tepdist_tpu.core.dist_spec import DimStrategy
from tepdist_tpu.core.mesh import MeshTopology
from tepdist_tpu.graph.jaxpr_graph import trace_graph
from tepdist_tpu.models import gpt2
from tepdist_tpu.parallel.auto_parallel import auto_parallel, plan_axes
from tepdist_tpu.parallel.evaluator import Evaluator

# Level 1: the ruler's readings (``_work_of``) stay the programs' they
# were taken from.
pytestmark = pytest.mark.usefixtures("optimized_programs")

CFG = gpt2.GPT2Config(vocab_size=4096, n_ctx=128, n_embd=256, n_layer=2,
                      n_head=8, dtype=jnp.float32)
BATCH, SEQ = 16, 128


def _plans(params):
    leaves = jax.tree_util.tree_leaves(params)
    n = len(leaves)
    dp = {n: {"x": DimStrategy.split_on(0, 8)}}
    tp = {i: {"x": DimStrategy.split_on(leaf.ndim - 1, 8)}
          for i, leaf in enumerate(leaves)
          if leaf.ndim >= 2 and leaf.shape[-1] % 8 == 0}
    tp0 = {i: {"x": DimStrategy.split_on(0, 8)}
           for i, leaf in enumerate(leaves)
           if leaf.ndim >= 2 and leaf.shape[0] % 8 == 0}
    return {"dp": dp, "tp": tp, "tp0": tp0}


# The second ruler of the first test: what the compiled executable itself
# says it does, not a clock (a clock that six test workers share bends: the
# three plans stand 5-13% apart alone and the run's load moved them more).
# One device's program by XLA's own ``cost_analysis()`` (flops, bytes
# accessed) plus the bytes its collectives move, read from the optimized HLO,
# priced at a flop rate and a byte rate. ``_RATES[0]`` is one virtual CPU
# device's to an order of magnitude (0.5 bytes a flop; alone on this machine
# the clock reads dp 194, tp0 205, tp 220 ms and these rates 284, 289, 308:
# the same order). The rates decide nothing: the test asks the same of the
# others, 0.005 bytes a flop (an accelerator's) to 1, and the cheapest plan
# would be another only past 3.2 bytes a flop, which no machine moves
# (``dp`` has the fewest bytes, by 7.5%, the fewest collective bytes, by
# 20%, and 0.4% more flops than ``tp0``).
_RATES = ((2e10, 1e10), (2e12, 1e10), (2e11, 1e10), (1e10, 1e10))
_COLLECTIVE = re.compile(
    r"= (\(.*?\)|\S+) (?:all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|all-to-all)(?:-start)?\(")
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def _collective_bytes(hlo_text):
    """Bytes of the results of every collective of an optimized module."""
    total = 0
    for shapes in _COLLECTIVE.findall(hlo_text):
        for dtype, dims in _ARRAY.findall(shapes):
            # f32, bf16, s32, u8, pred: the digits are the element's bits.
            total += math.prod(int(d) for d in dims.split(",") if d) \
                * max(int("".join(filter(str.isdigit, dtype)) or 8) // 8, 1)
    return total


def _work_of(compiled):
    """(flops, bytes moved) of one device's part of a compiled executable."""
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    # A program that only writes (a stage's zeroed accumulators) has no flops.
    return cost.get("flops", 0.0), (cost["bytes accessed"]
                                    + _collective_bytes(compiled.as_text()))


def _compiled_work(step, flat):
    """(flops, bytes moved) of one device's step, by the ruler above."""
    return _work_of(step.lower(*flat).compile())


def _seconds(work, flops_per_s, bytes_per_s):
    flops, moved = work
    return flops / flops_per_s + moved / bytes_per_s


def _spmd_step_work(plan, *batch):
    """One device's work in the one executable an SPMD training plan steps."""
    flat_batch = [jax.device_put(v, s) for v, s in
                  zip(jax.tree_util.tree_leaves(batch),
                      plan._batch_shardings)]
    return _compiled_work(plan._step_fn, [*plan._state, *flat_batch])


def _pipeline_step_work(plan, *batch):
    """Each device's work in one step of a pipeline training plan.

    The pipeline runtime steps no single executable: it walks its schedule
    and calls one compiled program a task (``runtime/executor.py:step``). A
    device's account is the sum, over the tasks the schedule gives it, of the
    task program's own work, plus its share of the bytes its SEND and RECV
    tasks move (the wire value is split over the stage's devices). Left out:
    the four slices of the batch and the sum of the four losses."""
    from tepdist_tpu.runtime.task_graph import TaskType

    exe = plan._exe
    # A stage's APPLY is jitted at its first call, not compiled ahead like
    # the other task programs: step once, then keep what the next step's
    # calls compile to.
    plan.step(*batch)
    applied = {}
    for key, fn in list(exe._apply_jit.items()):    # (stage, contributors)
        def keep(*args, stage=key[0], fn=fn):
            applied[stage] = fn.lower(*args).compile()
            return fn(*args)
        exe._apply_jit[key] = keep
    plan.step(*batch)
    programs = {"fwd": exe._fwd_jit, "bwd": exe._bwd_jit,
                TaskType.GAINIT: exe._gainit, TaskType.GA: exe._ga_jit,
                TaskType.APPLY: applied}
    work_of = functools.cache(_work_of)     # a stage's program runs M times
    per_device = collections.defaultdict(lambda: [0.0, 0.0])
    for tid in exe.schedule.order:
        node = exe.dag.node(tid)
        kind = node.task_type
        if kind == TaskType.COMPUTE:
            kind = node.name[:3]
        if kind in (TaskType.SEND, TaskType.RECV):
            flops, moved = 0.0, node.out_bytes / len(node.device_group)
        elif kind in programs:
            flops, moved = work_of(programs[kind][node.stage])
        else:
            assert kind in (TaskType.SPLIT, TaskType.INPUT,
                            TaskType.MERGE), node.name     # no program
            continue
        for device in node.device_group:
            per_device[device][0] += flops
            per_device[device][1] += moved
    return [tuple(w) for w in per_device.values()]


def test_exploration_ranking_matches_measured_argmin(devices):
    if len(devices) < 8:
        pytest.skip("needs the 8-device mesh")
    params = gpt2.init_params(CFG, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(CFG, BATCH, SEQ)
    tx = optax.sgd(1e-3)
    opt_state = tx.init(params)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: gpt2.loss_fn(p, tokens, CFG))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    graph, _, _ = trace_graph(
        lambda p, t: jax.value_and_grad(
            lambda q: gpt2.loss_fn(q, t, CFG))(p), params, tokens)
    topo = MeshTopology([("x", 8)])
    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))

    evals, work = {}, {}
    for name, ann in _plans(params).items():
        strategies = plan_axes(graph, topo, ann, "cost")
        cost = Evaluator(topo).run(graph, strategies)
        evals[name] = cost
        plan = auto_parallel(train_step, topo, params, opt_state, tokens,
                             annotations=ann,
                             state_alias={1 + k: k for k in range(n_state)})
        step = plan.executable()
        flat, _ = jax.tree_util.tree_flatten(
            ((params, opt_state, tokens), {}))
        flat = [jax.device_put(x, s)
                for x, s in zip(flat, plan.input_shardings())]
        work[name] = _compiled_work(step, flat)

    # 1. The property exploration consumes: the evaluator's winner must be
    # (close to) the winner by the second ruler, the compiled executables'
    # own account. The bar is the established one (test_evaluator.py:400):
    # the evaluator's pick stands within 20% of the true best.
    eval_best = min(evals, key=lambda k: evals[k].total_duration)
    for flops_per_s, bytes_per_s in _RATES:
        meas = {k: _seconds(w, flops_per_s, bytes_per_s)
                for k, w in work.items()}
        said = (f"evaluator picked {eval_best}: eval="
                f"{ {k: round(v.total_duration, 8) for k, v in evals.items()} }"
                f" meas={ {k: round(v * 1e3, 1) for k, v in meas.items()} }")
        assert meas[eval_best] <= 1.2 * min(meas.values()), said
        # A ruler no load bends needs no margin: the three programs differ
        # (the two cheapest stand 0.8-8.5% apart) and the pick is the cheapest outright,
        # whichever the rates.
        assert len({round(v, 9) for v in meas.values()}) == 3, said
        assert eval_best == min(meas, key=meas.get), said

    # 2. Costs discriminate (the r2 degenerate state priced all equal).
    durs = [c.total_duration for c in evals.values()]
    assert max(durs) / min(durs) >= 1.5

    # 3. Comm-bearing plans expose nonzero collective time.
    for name, c in evals.items():
        assert c.coll_ratio > 0.0, f"{name} priced zero comm"


@pytest.mark.parametrize("n_devices,tol", [(2, 0.25), (4, 0.25), (8, 0.15)])
def test_explore_candidate_ranking_vs_measured(devices, n_devices, tol):
    """VERDICT r3 ask #9: the PIPELINE-vs-SPMD exploration ranking
    (train.explore_parallelism's candidate list) validated against
    what the candidates' compiled programs do on the CPU mesh, on three
    topologies per device count, with tolerance TIGHTENING as devices grow
    (a wrong call costs more at scale). For each n, three genuinely
    different candidates are measured — pure dp, dp x model, and a 2-stage
    pipeline — by the first test's ruler (the two SPMD candidates step one
    executable each: ``_spmd_step_work``; the pipeline one a task:
    ``_pipeline_step_work``), and the evaluator's argmin must measure
    within tol of the true best at every pair of ``_RATES``.

    n=4 carries the n=2 tolerance: on a clock dp and data2xmodel2 read
    ~20% apart and the gap flapped with host load (both ways across
    rounds). By the ruler it does not flap: data2xmodel2 moves a third
    fewer bytes than dp at 1% fewer flops, the evaluator picks dp, and
    where bytes weigh (the two rates of 0.05 and 0.005 bytes a flop) dp
    stands 29% and 44% over it: the case fails, and ROADMAP D6 has the
    readings. The tolerance is the clock's and stays."""
    if len(devices) < n_devices:
        pytest.skip(f"needs {n_devices} devices")
    from tepdist_tpu.core.service_env import ServiceEnv
    from tepdist_tpu.train import explore_parallelism, plan_training

    params = gpt2.init_params(CFG, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(CFG, BATCH, SEQ)
    tx = optax.sgd(1e-3)
    loss = lambda p, t: gpt2.loss_fn(p, t, CFG)

    # Calibrate the schedule model to the fabric being MEASURED: on the
    # CPU mesh every task pays a ~0.4 ms Python dispatch floor (pinned
    # protocol: ~24 ms/step over ~40 tasks at S=2 M=4, of which the
    # device model prices only a fraction). TASK_OVERHEAD_US=0 (the TPU
    # default) models overheads as overlapped by long device compute.
    ServiceEnv.reset({"TASK_OVERHEAD_US": 400.0})
    try:
        best = explore_parallelism(loss, params, tokens,
                                   n_devices=n_devices,
                                   num_micro_batches=4)
    finally:
        ServiceEnv.reset()
    cands = best["candidates"]

    def find_spmd(axes):
        for c in cands:
            if (c["kind"] == "spmd"
                    and list(c["topology"].device_axes()) == axes):
                return c
        return None

    def find_pipe(S, M, tp=1):
        for c in cands:
            if (c["kind"] == "pipeline" and c["num_stages"] == S
                    and c["num_micro_batches"] == M
                    and c.get("intra_tp", 1) == tp):
                return c
        return None

    chosen = {}
    c = find_spmd([("data", n_devices)])
    if c is not None:
        chosen["dp"] = c
    if n_devices >= 4:
        c = find_spmd([("data", n_devices // 2), ("model", 2)])
    else:
        c = find_spmd([("model", n_devices)])
    if c is not None:
        chosen["mixed"] = c
    c = find_pipe(2, 4)
    if c is not None:
        chosen["pipe"] = c
    assert len(chosen) >= 3, f"missing candidates: {sorted(chosen)}"

    def devices_work(c):
        """Each device's (flops, bytes moved) of one step of candidate c."""
        import numpy as _np
        fresh = jax.tree_util.tree_map(_np.array, params)
        if c["kind"] == "spmd":
            plan = plan_training(loss, tx, fresh, tokens,
                                 topology=c["topology"],
                                 num_micro_batches=1,
                                 devices=devices[:n_devices])
            return [_spmd_step_work(plan, tokens)]
        plan = plan_training(loss, tx, fresh, tokens,
                             num_stages=c["num_stages"],
                             num_micro_batches=c["num_micro_batches"],
                             intra_stage_tp=c.get("intra_tp", 1),
                             devices=devices[:n_devices])
        return _pipeline_step_work(plan, tokens)

    work = {k: devices_work(c) for k, c in chosen.items()}
    evals = {k: c["cost"].total_duration for k, c in chosen.items()}
    eval_best = min(evals, key=evals.get)
    for rates in _RATES:
        # A step lasts as long as its busiest device works.
        meas = {k: max(_seconds(w, *rates) for w in per_device)
                for k, per_device in work.items()}
        assert meas[eval_best] <= (1.0 + tol) * min(meas.values()), (
            f"n={n_devices} at {rates}: evaluator picked {eval_best}; "
            f"eval={ {k: round(v, 6) for k, v in evals.items()} } "
            f"meas={ {k: round(v * 1e3, 1) for k, v in meas.items()} }")
    # The analytic costs must discriminate across the candidate kinds
    # (the r2 degenerate state priced ALL candidates identically). The
    # bar is non-collapse, not a fixed spread: r5's balanced stage cuts +
    # async transport model legitimately pulled the pipeline candidate
    # within ~8% of dp at n=2.
    assert max(evals.values()) / min(evals.values()) >= 1.02


def test_cross_axis_conflict_priced_and_loses(devices):
    """VERDICT r4 #6: a hybrid plan with a cross-axis produced/demanded
    conflict — h produced col-split on axis y (w1 pinned y-col) while its
    consumer's split lives on axis x (w2 pinned x-col) — must price ABOVE
    the clean plan and lose the measured argmin at n=8 (measured by the
    first test's ruler: the conflicted executable does more work at every
    pair of ``_RATES``). The pricing comes
    from the r5 machinery: the y-gather of h is charged (hidden-gather
    pass / the planner's own comm objective, which the pass floors), and
    entangled partition-dim changes upgrade to full-remat pricing.

    Remaining documented gap (NOT the original caveat, which this test
    retires): when the lowered COMPOSITION of per-axis shardings forces a
    device-ORDER permutation (e.g. w2 pinned x-ROW-split composed with
    state-storage alignment on y produces a transposed tile assignment
    XLA remats), the pathology is created inside lowering and is invisible
    to any pre-lowering cost model on this architecture."""
    import optax

    if len(devices) < 8:
        pytest.skip("needs the 8-device mesh")

    def loss(params, x, y):
        h = x @ params["w1"]
        o = h @ params["w2"]
        return jnp.mean((o - y) ** 2)

    k = jax.random.PRNGKey(0)
    D, B = 512, 64
    params = {"w1": jax.random.normal(k, (D, D)) * 0.05,
              "w2": jax.random.normal(k, (D, D)) * 0.05}
    x = jax.random.normal(k, (B, D))
    y = jnp.zeros((B, D))
    graph, _, _ = trace_graph(jax.value_and_grad(loss), params, x, y)
    topo = MeshTopology([("x", 2), ("y", 4)])
    conflict = {0: {"y": DimStrategy.split_on(1, 4)},
                1: {"x": DimStrategy.split_on(1, 2)}}
    # Clean comparator: plain DP on x (batch split), nothing conflicted.
    clean = {2: {"x": DimStrategy.split_on(0, 2)},
             3: {"x": DimStrategy.split_on(0, 2)}}

    ev = Evaluator(topo)
    costs = {}
    for name, ann in [("conflict", conflict), ("clean", clean)]:
        strategies = plan_axes(graph, topo, ann, "cost")
        costs[name] = ev.run(graph, strategies)

    # Ranked correctly, with a decisive margin: the conflict's cross-axis
    # comm (h gathered over y every step, w-grads resharded) prices above
    # clean DP's grad psums.
    assert (costs["conflict"].total_duration
            > 1.20 * costs["clean"].total_duration), (
        costs["conflict"].total_duration, costs["clean"].total_duration)
    # And the conflict's collective time is genuinely nonzero (the
    # original caveat's failure mode was comm priced ~0 for plans whose
    # measured step is comm-dominated).
    assert costs["conflict"].coll_ratio > 0.3

    # And the compiled executables agree: the conflict plan loses. By the
    # first test's ruler, not a clock (two steps of 5-12 ms on a machine
    # that six test workers share read 11.6 ms clean against 4.7 conflicted).
    tx = optax.sgd(0.01)
    opt_state = tx.init(params)

    def train_step(params, opt_state, x, y):
        l, g = jax.value_and_grad(loss)(params, x, y)
        u, opt_state = tx.update(g, opt_state, params)
        return l, optax.apply_updates(params, u), opt_state

    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))
    work = {}
    for name, ann in [("conflict", conflict), ("clean", clean)]:
        plan = auto_parallel(train_step, topo, params, opt_state, x, y,
                             annotations=ann,
                             state_alias={1 + i: i
                                          for i in range(n_state)})
        flat, _ = jax.tree_util.tree_flatten(
            ((params, opt_state, x, y), {}))
        flat = [jax.device_put(v, s)
                for v, s in zip(flat, plan.input_shardings())]
        work[name] = _compiled_work(plan.executable(), flat)
    for rates in _RATES:
        meas = {k: _seconds(w, *rates) for k, w in work.items()}
        assert meas["conflict"] > meas["clean"], (
            f"at {rates}: meas="
            f"{ {k: round(v * 1e3, 4) for k, v in meas.items()} } "
            f"work={work}")


def test_lowering_diagnostics_see_involuntary_remat(devices):
    """The device-order pathology the cost model cannot price (created
    INSIDE lowering by the composed shardings) is surfaced by the
    lowering diagnostics: XLA's 'Involuntary full rematerialization'
    warnings are captured at AOT compile. The known conflict plan
    reports at least one; the clean DP plan reports none."""
    if len(devices) < 8:
        pytest.skip("needs the 8-device mesh")

    def loss(params, x, y):
        h = x @ params["w1"]
        o = h @ params["w2"]
        return jnp.mean((o - y) ** 2)

    k = jax.random.PRNGKey(0)
    D, B = 512, 64
    params = {"w1": jax.random.normal(k, (D, D)) * 0.05,
              "w2": jax.random.normal(k, (D, D)) * 0.05}
    x = jax.random.normal(k, (B, D))
    y = jnp.zeros((B, D))
    topo = MeshTopology([("x", 2), ("y", 4)])
    conflict = {0: {"y": DimStrategy.split_on(1, 4)},
                1: {"x": DimStrategy.split_on(0, 2)}}
    clean = {2: {"x": DimStrategy.split_on(0, 2)},
             3: {"x": DimStrategy.split_on(0, 2)}}

    tx = optax.sgd(0.01)
    opt_state = tx.init(params)

    def train_step(params, opt_state, x, y):
        l, g = jax.value_and_grad(loss)(params, x, y)
        u, opt_state = tx.update(g, opt_state, params)
        return l, optax.apply_updates(params, u), opt_state

    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))
    diags = {}
    for name, ann in [("conflict", conflict), ("clean", clean)]:
        plan = auto_parallel(train_step, topo, params, opt_state, x, y,
                             annotations=ann,
                             state_alias={1 + i: i
                                          for i in range(n_state)})
        diags[name] = plan.lowering_diagnostics()
    assert diags["conflict"], diags
    assert diags["clean"] == [], diags
