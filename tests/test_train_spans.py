"""The spans and counters on the one-chip training path (ISSUE 24):
``plan_training`` and ``_SpmdTrainingPlan.step`` under the recorder, the
compile counter, and the start/stop control of the device trace. The tiny
GPT-2 of tests/test_models.py on one CPU device; ``jax.profiler`` runs on
the CPU too. One parametrised test, each case counting."""

import glob
import os

import jax
import optax
import pytest

from tepdist_tpu import telemetry
from tepdist_tpu.core.service_env import ServiceEnv
from tepdist_tpu.models import gpt2
from tepdist_tpu.telemetry import _NULL_SPAN, compile_stats
from tepdist_tpu.telemetry import trace as trace_mod
from tepdist_tpu.train import plan_training

PLAN_CHILDREN = ("plan:trace", "plan:search", "plan:lower", "plan:place")
STEP_CHILDREN = ("step:h2d", "step:dispatch", "step:wait")


@pytest.fixture()
def recorder():
    """A private tracer in the module global's place, off to begin with."""
    prev = trace_mod.tracer()
    t = trace_mod.Tracer(capacity=4096, enabled=False)
    trace_mod._TRACER = t
    yield t
    trace_mod._TRACER = prev


def _plan(devices):
    cfg = gpt2.CONFIGS["test"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, 8, 32)
    plan = plan_training(lambda p, t: gpt2.loss_fn(p, t, cfg),
                         optax.adam(1e-3), params, tokens,
                         devices=devices[:1], num_micro_batches=2)
    return plan, tokens


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _inside(child, parent):
    return (child["ts"] >= parent["ts"] and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 1.0)    # ts is whole us


def case_plan_spans_nest_and_cover(recorder, devices, tmp_path):
    # OPT_LEVEL 1: the planner on the given mesh, no exploration (which
    # has spans of its own, next case).
    ServiceEnv.reset({"OPT_LEVEL": "1"})
    try:
        _plan(devices)      # imports and first-use caches are not the plan's
        recorder.enabled = True
        recorder.clear()
        _plan(devices)
    finally:
        ServiceEnv.reset()
    spans = recorder.snapshot()
    (plan,) = _named(spans, "plan")
    assert plan["cat"] == "planner"
    covered = 0.0
    for name in PLAN_CHILDREN:
        found = _named(spans, name)
        assert found, f"no {name} span"
        assert all(_inside(s, plan) for s in found), name
        covered += sum(s["dur"] for s in found)
    assert covered >= 0.9 * plan["dur"], (covered, plan["dur"])
    assert not _named(spans, "plan:postcheck")


def case_explored_plan_has_the_postcheck_span(recorder, devices, tmp_path):
    recorder.enabled = True
    _plan(devices)          # OPT_LEVEL 2, the default: explores
    spans = recorder.snapshot()
    (plan,) = _named(spans, "plan")
    for name in ("explore:trace", "explore:spmd", "plan:postcheck"):
        (found,) = _named(spans, name)
        assert _inside(found, plan), name


def case_step_spans_share_the_step_number(recorder, devices, tmp_path):
    plan, tokens = _plan(devices)
    recorder.enabled = True
    recorder.clear()
    plan.step(tokens)
    plan.step(tokens)
    spans = recorder.snapshot()
    steps = _named(spans, "step")
    assert [s["args"]["step"] for s in steps] == [0, 1]
    assert all(s["cat"] == "runtime" for s in steps)
    for name in STEP_CHILDREN:
        found = _named(spans, name)
        assert [s["args"]["step"] for s in found] == [0, 1], name
        assert all(_inside(c, p) for c, p in zip(found, steps)), name


def case_recorder_off_records_nothing(recorder, devices, tmp_path):
    plan, tokens = _plan(devices)
    plan.step(tokens)
    assert trace_mod.span("step", cat="runtime", step=0) is _NULL_SPAN
    assert len(recorder) == 0


def case_compile_counter_rises_on_the_first_step_only(recorder, devices,
                                                      tmp_path):
    plan, tokens = _plan(devices)
    recorder.enabled = True
    planned = compile_stats()
    plan.step(tokens)
    first = compile_stats()
    plan.step(tokens)
    second = compile_stats()
    assert first["backend_n"] > planned["backend_n"]
    assert first["seconds"] > planned["seconds"]
    assert second == first
    compiled = [s["args"] for s in _named(recorder.snapshot(),
                                          "lower:compile")]
    assert {"phase": "backend",
            "program": "jit(tepdist_train_step)"} in compiled


def _host_events(log_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    return [ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("tepdist:")]


def case_device_trace_control_twice(recorder, devices, tmp_path):
    plan, tokens = _plan(devices)
    plan.step(tokens)
    for k, was_on in enumerate((False, True)):
        recorder.enabled = was_on
        log_dir = str(tmp_path / f"trace{k}")
        telemetry.start_device_trace(log_dir)
        with pytest.raises(RuntimeError):
            telemetry.start_device_trace(log_dir)
        assert recorder.enabled
        plan.step(tokens)
        telemetry.stop_device_trace()
        assert recorder.enabled is was_on
        names = _host_events(log_dir)
        for name in ("step",) + STEP_CHILDREN:
            assert names.count("tepdist:" + name) == 1, (k, name)
    with pytest.raises(RuntimeError):
        telemetry.stop_device_trace()


def case_annotation_only_while_a_device_trace_runs(recorder, devices,
                                                   tmp_path):
    made = []

    class Annotation:
        def __init__(self, name, **kwargs):
            made.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    recorder.enabled = False
    assert trace_mod.span("a") is _NULL_SPAN
    recorder.enabled = True
    with trace_mod.span("a", cat="x", k=1) as sp:
        assert not isinstance(sp, trace_mod._AnnotatedSpan)
    assert made == []
    trace_mod._ANNOTATION = Annotation
    try:
        with trace_mod.span("a", cat="x", k=1) as sp:
            assert isinstance(sp, trace_mod._AnnotatedSpan)
            assert sp.elapsed_ms >= 0.0
    finally:
        trace_mod._ANNOTATION = None
    assert made == [("tepdist:a", {"k": 1})]
    assert [s["name"] for s in recorder.snapshot()] == ["a", "a"]


CASES = [case_plan_spans_nest_and_cover,
         case_explored_plan_has_the_postcheck_span,
         case_step_spans_share_the_step_number,
         case_recorder_off_records_nothing,
         case_compile_counter_rises_on_the_first_step_only,
         case_device_trace_control_twice,
         case_annotation_only_while_a_device_trace_runs]


@pytest.mark.parametrize("case", CASES,
                         ids=[c.__name__[len("case_"):] for c in CASES])
def test_train_spans(case, recorder, devices, tmp_path):
    case(recorder, devices, tmp_path)
