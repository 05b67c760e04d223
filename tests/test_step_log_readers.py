"""The readers of the program's step log (``benchmark/layer_metrics/
_step_log.py`` and the three ``window_*.train`` readers built on it) on
made-up logs: which records are the measured window in a ``--trace 1`` and in
a ``--trace 2`` run, the check against the harness's clock, and the three
metrics' arithmetic. No chip and no plan."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.layer_metrics import _step_log  # noqa: E402
from benchmark.lib import cells  # noqa: E402

NAMES = ("window_step_ms.train", "window_slowest_step_excess.train",
         "window_between_steps_share.train")
STEP_US, BETWEEN_US = 1_000_000.0, 2_000.0


def _log(walls_us, plan=3, first_step=0):
    """One plan's records as ``telemetry.step_log()`` spells them."""
    out, ts = [], 1_700_000_000_000_000
    for n, wall in enumerate(walls_us):
        out.append({"plan": plan, "step": first_step + n, "ts": ts,
                    "wall": wall, "between": None if n == 0 else BETWEEN_US,
                    "h2d": 300.0, "dispatch": 700.0, "wait": wall - 1_100.0,
                    "compiles": 1 if n == 0 else 0, "gc": 0.0})
        ts += int(wall + BETWEEN_US)
    return out


def _elapsed_s(window_walls_us):
    """What the driver's clock reads: the walls, the waits between the steps
    and the batch it makes before the window's first step."""
    return 1e-6 * (sum(window_walls_us)
                   + BETWEEN_US * len(window_walls_us))


def _cell(trace_steps=1):
    return types.SimpleNamespace(
        traffic={"trace_steps": trace_steps}, facts={"program_events": {}},
        bench_dir=os.path.join(ROOT, "benchmark"))


class _NoTrace:
    window = (0.0, 1.0)

    def module_runs(self, match):
        return []


def _read_all(monkeypatch, records, host, cell=None):
    from tepdist_tpu import telemetry
    monkeypatch.setattr(telemetry, "step_log", lambda: records)
    cell = cell or _cell()
    readers = {m.NAME: m for m in cells.layer_metric_modules(cell.bench_dir)
               if m.NAME in NAMES}
    assert set(readers) == set(NAMES)
    return {name: readers[name].read(_NoTrace(), host, cell)
            for name in NAMES}


SETUP = [60e6, 1.3e6]               # the first step compiles, the second settles
LEVEL = [STEP_US] * 8


@pytest.mark.parametrize("shape, walls, steps", [
    ("trace 2", SETUP + LEVEL + [1.002e6], 8),
    ("trace 1", SETUP + [1.002e6], 1),
])
def test_the_window_is_chosen_by_the_logs_shape(shape, walls, steps):
    records = _log(walls)
    want = records[2:2 + steps]
    got, why_not = _step_log.choose(
        records, steps, 1, _elapsed_s([r["wall"] for r in want]))
    assert why_not is None and got == want, shape


def test_another_plans_records_and_older_steps_are_left_out(monkeypatch,
                                                            capsys):
    """``window`` takes the newest plan's records; the table it prints holds
    the window's steps and no other."""
    records = _log([5e6] * 4, plan=2) + _log(SETUP + LEVEL + [1.002e6])
    got = _read_all(monkeypatch, records,
                    {"steps": 8, "elapsed_s": _elapsed_s(LEVEL)})
    assert got["window_step_ms.train"] == pytest.approx(1000.0)
    out = capsys.readouterr().out
    rows = [line.split() for line in out.splitlines()
            if line.startswith("  ")]
    assert [int(r[0]) for r in rows] == list(range(2, 10))
    assert rows[0][1:6] == ["1000.000", "0.300", "0.700", "998.900", "2.000"]
    assert out.count("step log of the window (8 steps; ms)") == 1  # once a run
    assert "median wait" in out and "mean h2d + dispatch" in out


@pytest.mark.parametrize("records, host, says", [
    # A step of the window is missing from the log: the count disagrees.
    (_log(SETUP + LEVEL[:-1] + [1.002e6]), {}, "records of the"),
    # ... and an older step stands in its place, so the count fits: the
    # 1% check against the harness's clock refuses it.
    (_log(SETUP + [1.3e6] + LEVEL[:-1] + [1.002e6]), {}, "add up to"),
    # Every record there, the harness's clock 2% off.
    (_log(SETUP + LEVEL + [1.002e6]),
     {"elapsed_s": _elapsed_s(LEVEL) * 0.98}, "add up to"),
    # A window of 8 steps is no ``--trace 1`` window (``trace_steps`` 1).
    (_log(SETUP + LEVEL), {}, "records of the"),
    ([], {}, "holds no record"),
])
def test_a_log_that_disagrees_with_the_harness_reads_nothing(
        monkeypatch, capsys, records, host, says):
    host = {"steps": 8, "elapsed_s": _elapsed_s(LEVEL), **host}
    got = _read_all(monkeypatch, records, host)
    assert got == dict.fromkeys(NAMES)
    out = capsys.readouterr().out
    assert out.count("step log:") == 1 and says in out


def test_a_program_without_a_step_log_reads_nothing_and_says_nothing(
        monkeypatch, capsys):
    from tepdist_tpu import telemetry
    monkeypatch.delattr(telemetry, "step_log")
    cell = _cell()
    readers = [m for m in cells.layer_metric_modules(cell.bench_dir)
               if m.NAME in NAMES]
    host = {"steps": 8, "elapsed_s": 8.0}
    assert [m.read(_NoTrace(), host, cell) for m in readers] == [None] * 3
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("walls, excess", [
    (LEVEL, 0.0),
    (LEVEL[:3] + [1.1 * STEP_US] + LEVEL[4:], 10.0),
])
def test_the_three_readers_arithmetic(monkeypatch, walls, excess):
    got = _read_all(monkeypatch, _log(SETUP + walls + [1.002e6]),
                    {"steps": 8, "elapsed_s": _elapsed_s(walls)})
    assert got["window_step_ms.train"] == pytest.approx(1000.0)
    assert got["window_slowest_step_excess.train"] == pytest.approx(excess)
    waits = 7 * BETWEEN_US
    assert got["window_between_steps_share.train"] == pytest.approx(
        100.0 * waits / (sum(walls) + waits))


def test_a_window_of_one_traced_step(monkeypatch):
    """``--trace 1``: the traced steps are the window; one step has no
    step before it to wait after."""
    got = _read_all(monkeypatch, _log(SETUP + [1.002e6]),
                    {"steps": 1, "elapsed_s": _elapsed_s([1.002e6])})
    assert got == {"window_step_ms.train": pytest.approx(1002.0),
                   "window_slowest_step_excess.train": 0.0,
                   "window_between_steps_share.train": 0.0}


def test_benchmark_json_lists_the_three_for_every_train_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "train_tokens_per_s_chip")
    assert len(rate["workloads"]) == 13
    entries = {m["name"]: m for m in bench["per_layer"] if m["name"] in NAMES}
    readers = {m.NAME: m for m in cells.layer_metric_modules(
        os.path.join(ROOT, "benchmark")) if m.NAME in NAMES}
    assert set(entries) == set(readers) == set(NAMES)
    for name, entry in entries.items():
        assert entry["workloads"] == rate["workloads"], name
        assert entry["unit"] == readers[name].UNIT, name
        assert (entry["layer"], entry["moves"], entry["better"],
                entry["source"]) == (readers[name].LAYER,
                                     readers[name].MOVES, "lower",
                                     readers[name].SOURCE), name
