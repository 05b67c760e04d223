"""The trace reduction: interval arithmetic on hand-made planes, and the
recorded v5e trace kept in ``testdata/``."""

import json
import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.lib import intervals as iv

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(os.path.dirname(HERE), "testdata")


def test_interval_arithmetic():
    assert iv.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert iv.total([(0, 2), (1, 3), (5, 6)]) == 4
    assert iv.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert iv.gaps([(1, 2), (4, 9)], 0, 10) == [(0, 1), (2, 4), (9, 10)]


def _planes():
    """Two devices over a 10 s window. Device 0: a while loop holding two
    fusions, then a collective half hidden under nothing, then idle under
    the host's ``pick`` span."""
    dev0 = {"XLA Ops": [
        ("while.1", 1.0, 5.0),            # container: children 1..2, 3..5
        ("fusion.1", 1.0, 2.0),
        ("fusion.2", 3.0, 5.0),
        ("all-reduce.7", 5.0, 6.0),
        ("custom-call.3", 6.0, 7.0)],
        "XLA Modules": [("jit_step(1)", 1.0, 7.0)]}
    dev1 = {"XLA Ops": [
        ("fusion.1", 1.0, 3.0),
        ("all-reduce.7", 2.5, 4.0)],     # 0.5 s hidden under fusion.1
        "XLA Modules": [("jit_step(1)", 1.0, 4.0)]}
    host = {"python": [
        ("bench:window", 0.0, 10.0),
        ("bench:step", 0.5, 7.5),
        ("bench:pick", 7.0, 9.0),
        ("unrelated", 0.0, 10.0)]}
    return {"/device:TPU:0": dev0, "/device:TPU:1": dev1,
            "/host:CPU": host}


def test_reduce_hand_made_planes():
    s = tr.reduce_planes(_planes())
    assert s.window == (0.0, 10.0) and s.window_s == 10.0
    d0, d1 = s.devices
    # while.1 is a container: busy is its children, 1-2 and 3-5, then 5-7.
    assert d0.busy_s == pytest.approx(1 + 2 + 1 + 1)
    assert d0.op_self_s["while.1"] == pytest.approx(1.0)   # 2..3 uncovered
    assert d0.op_self_s["fusion.2"] == pytest.approx(2.0)
    assert d0.collective_s == pytest.approx(1.0)
    assert d0.collective_exposed_s == pytest.approx(1.0)
    assert d1.collective_s == pytest.approx(1.5)
    assert d1.collective_exposed_s == pytest.approx(1.0)
    assert d1.busy_s == pytest.approx(3.0)
    assert s.busy_s == pytest.approx((5.0 + 3.0) / 2)
    assert s.idle_share == pytest.approx(1 - 4.0 / 10)
    assert d0.module_iv == {"jit_step(1)": [(1.0, 7.0)]}
    assert s.module_runs(lambda n: "step" in n) == [6.0]
    assert s.module_runs_within("step") == [6.0]       # 1..7 in 0.5..7.5
    assert s.module_runs_within("pick") == []
    gaps = dict(s.idle_gaps)
    # device 0: 0-1 (step covers half: wins), 2-3 step, 7-10 pick covers
    # 2 of 3. device 1: 0-1 step, 4-10 step covers 3.5.
    assert gaps["pick"] == pytest.approx(3.0 / 2)
    assert gaps["step"] == pytest.approx((1 + 1 + 1 + 6) / 2)
    assert sum(gaps.values()) == pytest.approx(10 - s.busy_s)
    b = tr.breakdown(s)
    assert b["device_ops"][0][0] in ("fusion.1", "fusion.2")
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    json.dumps(b)


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_planes({"/host:CPU": {"python": [("x", 0.0, 1.0)]}})


def test_collective_names():
    assert tr.is_collective("all-reduce.12")
    assert tr.is_collective("all-gather-start.3")
    assert tr.is_collective("%collective-permute-done.1")
    assert not tr.is_collective("fusion.4")
    assert not tr.is_collective("reduce.4")


@pytest.mark.skipif(not os.path.exists(os.path.join(DATA, "small.xplane.pb")),
                    reason="no recorded trace")
def test_recorded_v5e_trace():
    """A few steps of a small jitted program on one v5e chip with a 50 ms
    host pause under a ``bench:pause`` span between steps, recorded by
    ``testdata/record.py``; the numbers beside it are what the reduction
    read on the chip when it was recorded."""
    with open(os.path.join(DATA, "small.expected.json")) as f:
        want = json.load(f)
    s = tr.reduce_file(os.path.join(DATA, "small.xplane.pb"))
    assert len(s.devices) == 1
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-6)
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < s.busy_s < s.window_s
    gaps = dict(s.idle_gaps)
    # Four pauses of 50 ms each, all idle, all under the span.
    assert gaps["pause"] >= 0.2
    assert gaps["pause"] == pytest.approx(want["idle_gaps"]["pause"],
                                          rel=1e-6)
    runs = s.module_runs(lambda n: "small_step" in n)
    assert len(runs) == want["module_runs"]
    top = dict(tr.breakdown(s)["device_ops"])
    assert set(top) == set(want["top_ops"])
