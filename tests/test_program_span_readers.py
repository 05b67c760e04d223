"""The readers of the program's own spans (``benchmark/layer_metrics/
_program_spans.py`` and the three ``.train`` readers built on it) on a
recorded v5e trace, ``benchmark/testdata/spans.xplane.pb``: three steps of
the tiny GPT-2 through ``plan_training``, traced through
``telemetry.start_device_trace`` by ``testdata/record_spans.py``. The numbers
beside it are what the readers read on the chip when it was recorded."""

import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "benchmark", "testdata")


@pytest.fixture(scope="module")
def read(tmp_path_factory):
    """The readers' findings, from a directory that holds this trace only
    (``find_xplane`` takes the newest trace under the one it is given)."""
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.testdata import record_spans
    trace_dir = tmp_path_factory.mktemp("spans")
    shutil.copy(os.path.join(DATA, "spans.xplane.pb"), trace_dir)
    return record_spans.read_all(str(trace_dir))


@pytest.fixture(scope="module")
def want():
    with open(os.path.join(DATA, "spans.expected.json")) as f:
        return json.load(f)


def test_program_spans_are_found_on_the_profilers_clock(read, want):
    spans = read["spans"]
    assert set(spans) == {"step", "step:h2d", "step:dispatch", "step:wait"}
    for name, found in spans.items():
        assert found["count"] == want["steps"], name
        assert found["whole_s"] == pytest.approx(
            want["spans"][name]["whole_s"], rel=1e-6), name
    # The step's children nest in it and leave little of it uncovered.
    children = sum(spans[k]["whole_s"] for k in spans if k != "step")
    assert children <= spans["step"]["whole_s"]
    assert spans["step"]["self_s"] == pytest.approx(
        spans["step"]["whole_s"] - children, abs=1e-9)
    assert read["window_s"] == pytest.approx(want["window_s"], rel=1e-6)


@pytest.mark.parametrize("name", ["step_host_ms.train",
                                  "step_device_ms.train",
                                  "idle_attributed_share.train"])
def test_reader_on_the_recorded_trace(read, want, name):
    assert read[name] == pytest.approx(want[name], rel=1e-6)
    spans = read["spans"]
    mean_step_ms = 1e3 * spans["step"]["whole_s"] / spans["step"]["count"]
    if name == "step_host_ms.train":
        assert read[name] == pytest.approx(
            1e3 * (spans["step:h2d"]["whole_s"]
                   + spans["step:dispatch"]["whole_s"]) / want["steps"])
    elif name == "step_device_ms.train":
        # The device's run of a step lies inside the step's span.
        assert 0 < read[name] < mean_step_ms
    else:
        # A 20 ms pause follows every step under no span of the program,
        # so a good part of the idle time lies under none.
        assert 0 < read[name] < 100


def test_setup_compile_s_reads_the_step_program_only(capsys):
    """``setup_compile_s`` is the lowering layer's: of the compile
    counter's ``lower:compile`` spans it sums those of the step program,
    every phase, and prints the benchmark's and the planner's programs."""
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.layer_metrics import setup_compile_s as reader

    def span(phase, program, seconds, name="lower:compile"):
        return {"name": name, "cat": "lower", "dur": seconds * 1e6,
                "args": {"phase": phase, "program": program}}

    spans = [span("trace", "tepdist_train_step", 0.25),
             span("lower", "jit(tepdist_train_step)", 0.5),
             span("lower", "jit(tepdist_train_step)", 0.5),
             span("backend", "jit(tepdist_train_step)", 0.125),
             span("lower", "jit(make)", 1.5),          # the benchmark's
             span("trace", "<lambda>", 2.0),           # the planner's
             span("trace", "tepdist_train_step", 9.0, name="plan:trace")]
    ours, others = reader.split(spans)
    assert ours == {"trace": 0.25, "lower": 1.0, "backend": 0.125}
    assert others == {"jit(make)": 1.5, "<lambda>": 2.0}
    host = {"program_spans": spans, "program_compiles": {"seconds": 4.875}}
    assert reader.read(None, host, None) == pytest.approx(1.375)
    assert "jit(make)" in capsys.readouterr().out
    # A program without the spans (the parent's): nothing to read.
    assert reader.read(None, {"program_compiles": {"seconds": 1.0}},
                       None) is None
