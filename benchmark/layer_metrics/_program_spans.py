"""Shared by the readers of the program's own spans (``tepdist_tpu.telemetry``).

Two sources, because set-up runs before any profiler is started:

* ``recorded(host)``: what the program's recorder held when the driver took
  its snapshot (``out["host"]["program_spans"]``): set-up's spans, on the
  recorder's own clock. Good for durations, not for laying under the
  device's timeline.
* ``traced(cell)``: inside a traced window every program span is also a
  ``jax.profiler.TraceAnnotation`` named ``tepdist:<span name>``, so it is
  in the ``.xplane.pb`` on the device trace's clock. ``trace_reduce.py``
  keeps only the benchmark's ``bench:`` events, so these are read here,
  once per run, from the planes of ``cell.facts["trace_path"]``.

Parent and child are found by nesting on one thread, as
``trace_reduce.self_times`` does; the spans carry no parent field. A reader
that finds nothing (a program without these spans) returns None.
"""

from benchmark import trace_reduce

PREFIX = "tepdist:"
# The layers whose spans nest inside each other on the measured path. The
# compile counter's ``lower:compile`` spans (cat ``lower``) lie inside
# whichever span triggered the compile and are another cut of the same
# seconds, so they are not taken for children.
NESTING_CATS = ("planner", "runtime")


def recorded(host: dict) -> dict:
    """thread -> events ``(name, start_s, end_s)`` of the recorder's
    snapshot, planner and runtime spans only."""
    by_thread = {}
    for s in host.get("program_spans") or ():
        if s["cat"] in NESTING_CATS:
            start = s["ts"] * 1e-6
            by_thread.setdefault(s["tid"], []).append(
                (s["name"], start, start + s["dur"] * 1e-6))
    return by_thread


def traced(cell) -> dict:
    """host line -> the ``tepdist:`` events of the traced window,
    ``(span name, start_s, end_s)``; {} when the run was not traced."""
    if "program_events" not in cell.facts:
        lines = {}
        path = cell.facts.get("trace_path")
        if path:
            planes = trace_reduce.read_planes(trace_reduce.find_xplane(path))
            for pname, plane in planes.items():
                if not pname.startswith(trace_reduce.HOST_PLANE_PREFIX):
                    continue
                for lname, events in plane.items():
                    found = [(n[len(PREFIX):], s, e) for n, s, e in events
                             if n.startswith(PREFIX)]
                    if found:
                        lines[pname + "/" + lname] = found
        cell.facts["program_events"] = lines
    return cell.facts["program_events"]


def within(lines: dict, window) -> dict:
    lo, hi = window
    return {k: [e for e in evs if e[1] >= lo and e[2] <= hi]
            for k, evs in lines.items()}


def self_seconds(lines: dict) -> dict:
    """span name -> (self seconds, whole seconds, count), summed over the
    threads; a span's self time is what its children leave uncovered."""
    out = {}
    for events in lines.values():
        for name, self_s, dur in trace_reduce.self_times(events):
            a, b, n = out.get(name, (0.0, 0.0, 0))
            out[name] = (a + self_s, b + dur, n + 1)
    return out


def self_intervals(lines: dict) -> list:
    """``(span name, [intervals])``: for every span the parts of it that no
    child covers, so that a point in time has one innermost span a thread."""
    from benchmark.lib import intervals as iv
    out = []
    for events in lines.values():
        for name, s, e in events:
            inside = [(s2, e2) for _, s2, e2 in events
                      if (s2, e2) != (s, e) and s2 >= s and e2 <= e]
            out.append((name, iv.subtract([(s, e)], inside)))
    return out
