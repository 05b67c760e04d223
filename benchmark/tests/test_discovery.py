"""A configuration, a traffic mix, a cell and a per-layer metric are each
added as new files plus one ``BENCHMARK.json`` entry; no harness file is
edited and none holds their names."""

import json
import os
import shutil

import pytest

from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HARNESS = ("run.py", "check_control.py", "sweep.py", "trace_reduce.py",
           "lib", "drivers")


@pytest.fixture
def checkout(tmp_path):
    """A copy of the benchmark (not of the program) that a 'later PR' adds
    files to."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def _add(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def test_added_files_are_found(checkout):
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bd = checkout / "benchmark"
    before = {p: (bd / p).stat().st_mtime_ns for p in
              ("run.py", "lib/cells.py", "drivers/train_steps.py")}
    base_cfg = json.loads((bd / "configs" / (
        bench["configs"][0]["name"] + ".json")).read_text())
    _add(bd / "configs" / "new-model.json",
         dict(base_cfg, name="new-model",
              model=dict(base_cfg["model"], n_layer=2)))
    _add(bd / "traffic" / "new-mix.json",
         {"kind": "train", "driver": "train_steps", "batch": 4, "seq": 64,
          "num_micro_batches": 1, "explore": False, "trace_steps": 1})
    _add(bd / "workloads" / "new-model.new-mix.json",
         {"config": "new-model", "traffic": "new-mix", "chips": 1,
          "end_to_end": ["train_tokens_per_s_chip", "setup_s"],
          "correct": {"sample_sequences": 1, "limits": {}}})
    (bd / "layer_metrics" / "steps_counted.py").write_text(
        'NAME, UNIT, LAYER = "steps_counted", "steps", "runtime"\n'
        'MOVES = "train_tokens_per_s_chip"\nKINDS = ("train",)\n'
        'SOURCE = "program_counter"\n'
        'def read(trace, host, cell):\n    return host["steps"]\n')
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": "benchmark/configs/new-model.json",
                             "reduced": ["n_layer"], "why": "t"})
    bench["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "t"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s_chip":
            m["workloads"].append("new-model.new-mix")
    bench["per_layer"].append(
        {"name": "steps_counted", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "runtime",
         "moves": "train_tokens_per_s_chip",
         "workloads": ["new-model.new-mix"]})
    _add(checkout / "BENCHMARK.json", bench)

    cell = cells.load_cell("new-model.new-mix", str(checkout))
    assert cell.config["model"]["n_layer"] == 2
    assert cell.traffic["batch"] == 4 and cell.kind == "train"
    assert cells.driver_for(cell).__name__.endswith("train_steps")
    assert hasattr(cells.builder_for(cell), "make_params")
    assert "steps_counted" in {m["name"] for m in cell.per_layer}
    # Metrics without a ``workloads`` key follow the end-to-end metric they
    # move into the new cell.
    assert "device_idle_share.train" in {m["name"] for m in cell.per_layer}

    class Trace:
        idle_share, window_s, devices = 0.25, 2.0, [object()]

        def op_seconds(self, match):
            return 0.0

    host = {"steps": 7, "peaks": {}, "spans": None}
    only = [m for m in cell.per_layer
            if m["name"] in ("steps_counted", "device_idle_share.train")]
    cell.per_layer = only
    got = cells.read_layer_metrics(cell, Trace(), host)
    assert got == {"steps_counted": {"value": 7.0, "unit": "steps"},
                   "device_idle_share.train": {"value": 25.0, "unit": "%"}}
    for p, t in before.items():
        assert (bd / p).stat().st_mtime_ns == t      # nothing edited


def test_every_listed_cell_loads_and_every_metric_has_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    readers = {m.NAME: m for m in cells.layer_metric_modules(
        os.path.join(ROOT, "benchmark"))}
    for m in bench["per_layer"]:
        r = readers[m["name"]]
        assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"], ROOT)
        assert cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert cell.kind in readers[m["name"]].KINDS


def test_harness_files_hold_no_cell_model_or_metric_file_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [w["name"] for w in bench["workloads"]] \
        + [c["name"] for c in bench["configs"]] \
        + [w["traffic"] for w in bench["workloads"]]
    bd = os.path.join(ROOT, "benchmark")
    for entry in HARNESS:
        path = os.path.join(bd, entry)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
            if f.endswith(".py")]
        for fpath in files:
            text = open(fpath).read()
            for n in names:
                assert n not in text, (fpath, n)


def test_missing_cell_file_is_a_bench_error(checkout):
    os.remove(checkout / "benchmark" / "workloads" / (
        json.loads((checkout / "BENCHMARK.json").read_text())
        ["workloads"][0]["name"] + ".json"))
    with pytest.raises(cells.BenchError):
        cells.load_cell(json.loads(
            (checkout / "BENCHMARK.json").read_text())["workloads"][0]
            ["name"], str(checkout))
