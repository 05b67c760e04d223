"""The step log (``telemetry/trace.py``: ``StepLog``, ``PlanSteps``,
``telemetry.step_log()``): one record for every finished
``TrainingPlan.step()``, written whether the span recorder is on or off.
One tiny loss is planned for the whole file; the cases that need a step of a
given length put a stub in the plan's ``_step_fn``."""

import gc
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tepdist_tpu import telemetry
from tepdist_tpu.telemetry import metrics
from tepdist_tpu.telemetry import trace as trace_mod
from tepdist_tpu.train import plan_training

X = np.ones((4, 8), np.float32)


@pytest.fixture(scope="module")
def plan():
    """Four steps on the tiny loss with the recorder off; inside the third
    a function nobody has jitted yet is compiled."""
    was = telemetry.enabled()
    telemetry.configure(enabled=False)
    params = {"w": 0.1 * jnp.ones((8, 8)), "b": jnp.zeros((8,))}
    plan = plan_training(
        lambda p, x: jnp.mean((x @ p["w"] + p["b"]) ** 2), optax.sgd(1e-2),
        params, X, devices=jax.devices()[:1], explore=False,
        num_micro_batches=2)
    start = len(telemetry.step_log())
    plan.step(X)
    time.sleep(0.002)
    plan.step(X)
    real = plan._step_fn

    def compiling(*args):
        jax.jit(lambda v: v * 3 + 1)(jnp.ones((3,))).block_until_ready()
        return real(*args)
    plan._step_fn = compiling
    plan.step(X)
    plan._step_fn = real
    plan.step(X)
    plan.first_four = [r for r in telemetry.step_log()[start:]
                       if r["plan"] == plan._log.plan]
    yield plan
    telemetry.configure(enabled=was)


class _Stub:
    """A step of a chosen length in a real plan's place: the state goes
    through unchanged, the loss is a host float."""

    def __init__(self, plan):
        self.n_state = plan._n_state
        self.seconds = 0.01
        self.inside = None

    def __call__(self, *args):
        time.sleep(self.seconds)
        if self.inside is not None:
            self.inside()
        return (np.float32(1.0),) + args[:self.n_state]


@pytest.fixture()
def stubbed(plan):
    """The plan with a stub for its step and a pen of its own, as a plan
    that has never stepped; both put back after the case."""
    real, pen = plan._step_fn, plan._log
    stub = _Stub(plan)
    plan._step_fn, plan._log = stub, trace_mod.STEP_LOG.plan()
    yield plan, stub
    plan._step_fn, plan._log = real, pen


def _records(plan):
    return [r for r in telemetry.step_log() if r["plan"] == plan._log.plan]


def test_every_step_leaves_one_record_in_order(plan):
    recs = plan.first_four
    assert [r["step"] for r in recs] == [0, 1, 2, 3]
    assert [r["ts"] for r in recs] == sorted(r["ts"] for r in recs)
    for r in recs:
        assert r["wall"] >= r["h2d"] + r["dispatch"] + r["wait"] > 0
        assert min(r["h2d"], r["dispatch"], r["wait"], r["gc"]) >= 0
    assert recs[0]["between"] is None
    assert all(r["between"] > 0 for r in recs[1:])
    assert recs[1]["between"] >= 2000           # the 2 ms the caller slept
    # A step starts after the one before it returned: on one axis.
    for a, b in zip(recs, recs[1:]):
        assert b["ts"] >= a["ts"] + a["wall"] - 1


def test_compiles_inside_a_step_are_counted_there(plan):
    recs = plan.first_four
    assert recs[0]["compiles"] >= 1             # the step program itself
    assert recs[2]["compiles"] >= 1             # the function jitted inside
    assert recs[3]["compiles"] == 0             # a level step


@pytest.mark.parametrize("on", [False, True])
def test_the_log_is_written_recorder_on_or_off(plan, on):
    before = len(_records(plan))
    count = metrics().histogram("step_time_ms").to_dict()["count"]
    telemetry.configure(enabled=on)
    try:
        plan.step(X)
        plan.step(X)
    finally:
        telemetry.configure(enabled=False)
    recs = _records(plan)
    assert len(recs) == before + 2
    assert recs[-1]["step"] == recs[-2]["step"] + 1
    assert recs[-1]["wall"] >= recs[-1]["h2d"] + recs[-1]["dispatch"] \
        + recs[-1]["wait"]
    assert metrics().histogram("step_time_ms").to_dict()["count"] \
        == count + 2


def test_a_record_and_the_spans_of_its_step_agree(plan):
    tracer = telemetry.configure(enabled=True)
    tracer.clear()
    try:
        plan.step(X)
    finally:
        telemetry.configure(enabled=False)
    rec = _records(plan)[-1]
    spans = {s["name"]: s for s in tracer.snapshot()
             if s["args"].get("step") == rec["step"]
             and s["cat"] == "runtime"}
    assert set(spans) == {"step", "step:h2d", "step:dispatch", "step:wait"}
    for name in ("h2d", "dispatch", "wait"):
        assert rec[name] == pytest.approx(spans["step:" + name]["dur"],
                                          abs=200), name
    # One clock: the record lies inside the step's span.
    whole = spans["step"]
    assert whole["ts"] <= rec["ts"]
    assert rec["ts"] + rec["wall"] <= whole["ts"] + whole["dur"] + 1
    assert rec["wall"] == pytest.approx(whole["dur"], abs=200)


def test_the_ring_keeps_the_newest_and_counts_the_rest():
    log = trace_mod.StepLog()
    assert log.ring.cap == trace_mod.STEP_LOG_CAPACITY == 4096
    pen = log.plan()
    extra = 10
    for n in range(4096 + extra):
        pen.end(n, pen.begin())
    recs = log.snapshot()
    assert len(recs) == 4096 and log.dropped == extra
    assert [r["step"] for r in recs] == list(range(extra, 4096 + extra))
    assert recs[0]["h2d"] is None and recs[0]["between"] is not None
    assert log.snapshot(clear=True) == recs
    assert log.snapshot() == [] and log.dropped == 0
    assert log.plan().plan == pen.plan + 1      # a small integer a plan


def test_a_stalled_step_says_so_and_level_steps_do_not(stubbed, caplog):
    plan, stub = stubbed
    stalled = metrics().counter("steps_stalled")
    before = stalled.value
    with caplog.at_level("WARNING", logger="tepdist_tpu.telemetry.trace"):
        for _ in range(4):
            plan.step(X)
        assert stalled.value == before and not caplog.records
        # The fifth step is the first the check looks at.
        stub.seconds *= 2
        plan.step(X)
    assert stalled.value == before + 1
    (line,) = [r.getMessage() for r in caplog.records]
    rec = _records(plan)[-1]
    assert f"step {rec['step']} of plan {rec['plan']} stalled" in line
    for word in ("wall", "h2d", "dispatch", "wait", "between", "compiles",
                 "gc"):
        assert word in line
    median = statistics.median(r["wall"] for r in _records(plan)[:-1])
    assert f"{rec['wall'] / median:.2f} times the median" in line
    # The stub sleeps inside the dispatch: the line names the phase.
    assert rec["dispatch"] > 0.9 * rec["wall"] - 500
    assert trace_mod.STALL_RATIO == 1.25 and trace_mod.STALL_HISTORY == 32


def test_a_collection_inside_a_step_is_in_its_record(stubbed):
    plan, stub = stubbed
    plan.step(X)
    stub.inside = gc.collect
    plan.step(X)
    quiet, collected = _records(plan)[-2:]
    assert collected["gc"] > 0
    assert collected["gc"] <= collected["dispatch"]
    assert quiet["gc"] <= collected["gc"]


def test_the_pipeline_plan_leaves_the_phases_out():
    """``_PipelineTrainingPlan.step`` through an executable that does
    nothing: wall, between, compiles and gc, the three phases None."""
    from tepdist_tpu.train import _PipelineTrainingPlan

    class Exe:
        global_step = 7
        optimizer = None

        def load_variables(self, params):
            pass

        def step(self, *batch):
            self.global_step += 1
            return 0.5

    plan = _PipelineTrainingPlan(Exe(), {})
    assert plan.step(X) == 0.5 and plan.step(X) == 0.5
    first, second = _records(plan)
    assert (first["step"], second["step"]) == (7, 8)
    assert first["between"] is None and second["between"] >= 0
    for r in (first, second):
        assert r["h2d"] is r["dispatch"] is r["wait"] is None
        assert r["wall"] > 0 and r["compiles"] == 0 and r["gc"] >= 0


def test_the_record_path_costs_microseconds():
    """As ``test_telemetry.py::test_disabled_span_overhead_is_noop_sized``:
    a generous ceiling that only catches an accident (a lock, a sort of the
    whole ring, a file). Past the fourth record the stall check's median
    runs too, and its warning where a wall of a microsecond wanders."""
    pen = trace_mod.StepLog().plan()
    n = 2000

    def timed_ns():
        t0 = time.perf_counter_ns()
        for i in range(n):
            t = pen.begin()
            pen.end(i, t, 1, 1, t)
        return (time.perf_counter_ns() - t0) / n

    cost = min(timed_ns() for _ in range(3))
    assert cost < 50_000, f"a step's record costs {cost:.0f} ns"
