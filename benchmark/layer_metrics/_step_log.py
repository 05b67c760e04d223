"""Shared by the readers of the program's step log
(``tepdist_tpu.telemetry.step_log()``): one record for every finished
``plan.step()``, which the program writes whether its span recorder is on or
off, so the steps of the measured window, which runs with spans and profiler
off, are in it.

The driver hands nothing over; the log is asked for here. Of the newest
plan's records the last ``trace_steps`` are the traced steps of a
``--trace 2`` run and the ``host["steps"]`` before them the measured window;
in a ``--trace 1`` run the traced steps are the window (``host["steps"]`` is
``trace_steps``) and nothing is set aside. Which of the two shapes a log has
is read off its length: before the window the driver makes ``SETUP_STEPS``
steps in every mode. The choice is
held to the harness's own clock: the window's walls and the waits between
its steps must add up to ``host["elapsed_s"]`` within ``TOLERANCE``,
otherwise every reader returns None and a line says which count or sum
disagreed. A program without a step log (the parent of the PR that added
it) gives None without a line.

Once a run the window's table is printed, and the line that tells a slow
device from a slow host when a whole window is slow: the window's median
``wait`` beside the traced ``step_device_ms.train`` and its mean
``h2d + dispatch`` beside the traced ``step_host_ms.train``.
"""

import contextlib
import io
import os
import statistics

from benchmark.lib import cells

SETUP_STEPS = 2         # drivers/train_steps.py: the first step, the settle
TOLERANCE = 0.01
ROWS = 40
_KEY = "step_log_window"


def choose(records: list, steps: int, trace_steps: int, elapsed_s: float):
    """``(window records, None)`` or ``(None, what disagreed)``. ``records``
    are one plan's, oldest first, durations in microseconds."""
    spare = len(records) - steps - SETUP_STEPS
    traced_in_place = spare == 0 and steps == trace_steps       # --trace 1
    if not (spare == trace_steps or traced_in_place):
        return None, (
            f"{len(records)} records of the newest plan, expected "
            f"{SETUP_STEPS} of set-up + {steps} of the window (+ "
            f"{trace_steps} traced after it)")
    window = records[len(records) - spare - steps:len(records) - spare]
    log_s = 1e-6 * (sum(r["wall"] for r in window)
                    + sum(r["between"] for r in window[1:]))
    if abs(log_s - elapsed_s) > TOLERANCE * elapsed_s:
        return None, (
            f"the window's {steps} records add up to {log_s:.6f} s of walls "
            f"and waits between steps, the harness's clock read "
            f"{elapsed_s:.6f} s")
    return window, None


def summary(window: list) -> dict:
    """The three metrics' arithmetic and what the printed lines need."""
    walls = [r["wall"] for r in window]
    between = sum(r["between"] for r in window[1:])
    median = statistics.median(walls)
    phases = [r for r in window if r["wait"] is not None]
    return {
        "step_ms": 1e-3 * median,
        "slowest_excess": 100.0 * (max(walls) / median - 1.0),
        "between_share": 100.0 * between / (sum(walls) + between),
        "slowest_step": max(window, key=lambda r: r["wall"])["step"],
        "wait_ms": 1e-3 * statistics.median(r["wait"] for r in phases)
        if phases else None,
        "host_ms": 1e-3 * statistics.mean(r["h2d"] + r["dispatch"]
                                          for r in phases)
        if phases else None,
        "compiles": sum(r["compiles"] for r in window),
        "gc_ms": 1e-3 * sum(r["gc"] for r in window),
    }


def _ms(us) -> str:
    return "-" if us is None else f"{1e-3 * us:.3f}"


def _traced_reading(cell, name: str, trace, host):
    """What another metric's reader reads of this run's traced window, its
    own lines not printed a second time."""
    reader = cells.load_module(
        os.path.join(cell.bench_dir, "layer_metrics", name + ".py"),
        "bench_layer_metric_" + name.replace(".", "_"))
    with contextlib.redirect_stdout(io.StringIO()):
        return reader.read(trace, host, cell)


def _beside(what: str, ours, name: str, theirs) -> str:
    if ours is None or theirs is None:
        return f"{what} or the traced {name} not read"
    return (f"{what} {ours:.4f} ms beside the traced {name} {theirs:.4f} ms "
            f"({100.0 * (ours / theirs - 1.0):+.3f}%)")


def _print(window: list, s: dict, trace, host, cell) -> None:
    print(f"step log of the window ({len(window)} steps; ms): step wall h2d "
          f"dispatch wait between compiles gc", flush=True)
    for r in window[:ROWS]:
        print("  " + " ".join(
            [str(r["step"])] + [_ms(r[k]) for k in
                                ("wall", "h2d", "dispatch", "wait", "between")]
            + [str(r["compiles"]), _ms(r["gc"])]), flush=True)
    if len(window) > ROWS:
        print(f"  ... {len(window) - ROWS} more steps", flush=True)
    print(f"step log of the window: median wall {s['step_ms']:.4f} ms, "
          f"largest {s['step_ms'] * (1 + s['slowest_excess'] / 100):.4f} ms "
          f"(step {s['slowest_step']}, {s['slowest_excess']:.4f}% over), "
          f"between steps {s['between_share']:.4f}% of the window, compiles "
          f"{s['compiles']}, gc {s['gc_ms']:.3f} ms", flush=True)
    print("step log of the window: " + _beside(
        "median wait", s["wait_ms"], "step_device_ms.train",
        _traced_reading(cell, "step_device_ms.train", trace, host))
          + "; " + _beside(
        "mean h2d + dispatch", s["host_ms"], "step_host_ms.train",
        _traced_reading(cell, "step_host_ms.train", trace, host)),
          flush=True)


def window(trace, host: dict, cell):
    """The ``summary`` of the measured window's records, or None; found,
    checked and printed once a run (kept in ``cell.facts``)."""
    if _KEY in cell.facts:
        return cell.facts[_KEY]
    cell.facts[_KEY] = None
    from tepdist_tpu import telemetry
    step_log = getattr(telemetry, "step_log", None)
    if step_log is None:
        return None
    records = step_log()
    if not records:
        print("step log: the program's log holds no record", flush=True)
        return None
    newest = max(r["plan"] for r in records)
    chosen, why_not = choose(
        [r for r in records if r["plan"] == newest], int(host["steps"]),
        int(cell.traffic["trace_steps"]), float(host["elapsed_s"]))
    if chosen is None:
        print("step log: no window found: " + why_not, flush=True)
        return None
    cell.facts[_KEY] = summary(chosen)
    _print(chosen, cell.facts[_KEY], trace, host, cell)
    return cell.facts[_KEY]
