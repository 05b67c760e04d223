"""``.xplane.pb`` -> what the per-layer metrics read.

The reduction every PR shares, so that a gain and its baseline are counted
the same way. Reads a profile with nothing but ``jax.profiler.ProfileData``
and returns a ``TraceSummary``:

* per device: the seconds in which any operation ran (union of the events on
  the device's operation lines, clipped to the traced window), self time by
  operation name, time by program (``XLA Modules``), collective time and the
  part of it during which no other operation ran on that device;
* the longest idle gaps, each attributed to the benchmark's own host span
  (``jax.profiler.TraceAnnotation`` named ``bench:<what>``) that covers most
  of it, so an idle share says what the host was doing.

The traced window is the ``bench:window`` host annotation when the trace has
one (the drivers emit it around the steps they trace) and otherwise the
extent of the device events.

Checked against ``testdata/`` by ``tests/test_trace_reduce.py``.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.lib import intervals as iv

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all",
                       "collective-broadcast", "ragged-all-to-all")
# Operations that only hold others (their time is their children's).
CONTAINER_PREFIXES = ("while", "conditional", "call")

MAX_LABEL = 120                           # characters of HLO text in a label

Event = Tuple[str, float, float]          # name, start_s, end_s


@dataclass
class DeviceSummary:
    name: str
    busy_s: float
    op_self_s: Dict[str, float]           # operation name -> self seconds
    op_calls: Dict[str, int]
    op_text: Dict[str, str]               # operation name -> its HLO text
    module_iv: Dict[str, List[iv.Interval]]   # program name -> its runs
    collective_s: float
    collective_exposed_s: float
    busy: List[iv.Interval] = field(repr=False, default_factory=list)


@dataclass
class TraceSummary:
    window_s: float
    window: iv.Interval
    devices: List[DeviceSummary]
    host_spans: List[Event]
    idle_gaps: List[Tuple[str, float]]    # host span name -> idle seconds

    @property
    def busy_s(self) -> float:
        """Mean over the devices of the seconds an operation ran."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops(self, match) -> List[Tuple[str, float, float]]:
        """``(HLO text, self seconds, calls)`` of the operations whose HLO
        text ``match`` accepts, seconds and calls as means over the
        devices."""
        n = len(self.devices)
        return [(d.op_text[k], s / n, d.op_calls[k] / n)
                for d in self.devices for k, s in d.op_self_s.items()
                if match(d.op_text[k])]

    def op_seconds(self, match) -> float:
        return sum(s for _, s, _ in self.ops(match))

    def module_runs(self, match) -> List[float]:
        """Seconds of every run of the programs ``match`` accepts, on the
        first device that ran one."""
        for d in self.devices:
            runs = [e - s for n, ts in d.module_iv.items() if match(n)
                    for s, e in ts]
            if runs:
                return runs
        return []

    def module_runs_within(self, span: str) -> List[float]:
        """For each host span ``bench:<span>``, the seconds of the longest
        program run that lies wholly inside it on the first device (a
        program the program under test does not name is found by the
        benchmark's own span around the call that waits for it)."""
        d = self.devices[0]
        runs = sorted((s, e) for ts in d.module_iv.values() for s, e in ts)
        out = []
        for n, s0, e0 in self.host_spans:
            if n != SPAN_PREFIX + span:
                continue
            inside = [e - s for s, e in runs if s >= s0 and e <= e0]
            if inside:
                out.append(max(inside))
        return out

    def top_ops(self, n: int = 10) -> List[List]:
        acc: Dict[str, float] = {}
        for d in self.devices:
            for name, s in d.op_self_s.items():
                label = d.op_text[name][:MAX_LABEL]
                acc[label] = acc.get(label, 0.0) + s / len(self.devices)
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def short_name(text: str) -> str:
    """An event on the operation line is named by the instruction's whole
    HLO text (``%fusion.4 = bf16[8,128]{...} fusion(...), kind=...``); the
    instruction's own name is its first word."""
    return text.split(" ", 1)[0]


def _base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``%all-reduce.4 = ...`` -> ``all-reduce``
    (grouping key for 'is this a collective')."""
    name = short_name(name).lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def is_collective(name: str) -> bool:
    return _base_name(name).startswith(COLLECTIVE_PREFIXES)


def _is_container(name: str) -> bool:
    base = _base_name(name)
    return any(base == p or base.startswith(p + ".") or
               base.startswith(p + "-") for p in CONTAINER_PREFIXES)


def read_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """plane name -> line name -> events ``(name, start_s, end_s)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                start = ev.start_ns * 1e-9
                events.append((ev.name, start,
                               start + ev.duration_ns * 1e-9))
    return planes


def self_times(events: Iterable[Event]) -> List[Tuple[str, float, float]]:
    """``(name, self_seconds, duration)`` for each event of one line, where
    an event that encloses others keeps only what they leave uncovered."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    stack: List[list] = []                 # [name, start, end, child_cover]

    def close(upto: float):
        # Times are nanoseconds turned into seconds: an event that starts
        # where its neighbour ends can read 1e-17 s early. A picosecond of
        # tolerance keeps neighbours from being taken for parent and child.
        while stack and stack[-1][2] <= upto + 1e-12:
            name, s, e, covered = stack.pop()
            out.append((name, max(0.0, (e - s) - covered), e - s))

    for name, s, e in evs:
        close(s)
        if stack:
            # Direct children only: a grandchild is already inside its
            # parent's span, which the grandparent subtracts whole.
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    close(float("inf"))
    return out


def _summarise_device(name: str, lines: Dict[str, List[Event]],
                      window: iv.Interval) -> DeviceSummary:
    lo, hi = window
    ops = [e for ln in OP_LINES for e in lines.get(ln, [])
           if e[2] > lo and e[1] < hi]
    ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops]
    leaves = [(s, e) for n, s, e in ops if not _is_container(n)]
    busy = iv.union(leaves)
    op_self: Dict[str, float] = {}
    op_calls: Dict[str, int] = {}
    op_text: Dict[str, str] = {}
    for text, self_s, _ in self_times(ops):
        n = short_name(text)
        op_self[n] = op_self.get(n, 0.0) + self_s
        op_calls[n] = op_calls.get(n, 0) + 1
        op_text[n] = text
    module_iv: Dict[str, List[iv.Interval]] = {}
    for ln in MODULE_LINES:
        for n, s, e in lines.get(ln, []):
            if s >= lo and e <= hi:
                module_iv.setdefault(n, []).append((s, e))
    coll = [(s, e) for n, s, e in ops if is_collective(n)]
    other = [(s, e) for n, s, e in ops
             if not is_collective(n) and not _is_container(n)]
    return DeviceSummary(
        name=name, busy_s=iv.total(busy), op_self_s=op_self,
        op_calls=op_calls, op_text=op_text, module_iv=module_iv,
        collective_s=iv.total(coll),
        collective_exposed_s=iv.total(iv.subtract(coll, other)), busy=busy)


def _host_spans(planes) -> List[Event]:
    spans = []
    for pname, lines in planes.items():
        if not pname.startswith(HOST_PLANE_PREFIX):
            continue
        for events in lines.values():
            spans += [e for e in events if e[0].startswith(SPAN_PREFIX)]
    return sorted(spans, key=lambda e: e[1])


def _attribute_gaps(devices: List[DeviceSummary], spans: List[Event],
                    window: iv.Interval) -> List[Tuple[str, float]]:
    """Idle seconds (mean over devices) by the host span that covers most
    of each gap; the innermost span wins a tie by being shorter."""
    named = [s for s in spans if s[0] != WINDOW_SPAN]
    acc: Dict[str, float] = {}
    for d in devices:
        for gap in iv.gaps(d.busy, *window):
            best, best_key = "unattributed", (0.0, 0.0)
            for n, s, e in named:
                if s >= gap[1]:
                    break
                ov = iv.overlap(gap, (s, e))
                key = (ov, -(e - s))
                if ov > 0 and key > best_key:
                    best, best_key = n[len(SPAN_PREFIX):], key
            acc[best] = acc.get(best, 0.0) + (gap[1] - gap[0]) / len(devices)
    return sorted(acc.items(), key=lambda kv: -kv[1])


def reduce_planes(planes: Dict[str, Dict[str, List[Event]]]
                  ) -> TraceSummary:
    device_names = sorted(
        (p for p in planes if p.startswith(DEVICE_PLANE_PREFIX)
         and any(planes[p].get(ln) for ln in OP_LINES)),
        key=lambda p: int(p[len(DEVICE_PLANE_PREFIX):].split()[0]))
    if not device_names:
        raise ValueError("the trace has no device plane with operations: "
                         f"planes are {sorted(planes)}")
    spans = _host_spans(planes)
    win = next(((s, e) for n, s, e in spans if n == WINDOW_SPAN), None)
    if win is None:
        evs = [e for p in device_names for ln in OP_LINES
               for e in planes[p].get(ln, [])]
        win = (min(e[1] for e in evs), max(e[2] for e in evs))
    devices = [_summarise_device(p, planes[p], win) for p in device_names]
    return TraceSummary(window_s=win[1] - win[0], window=win,
                        devices=devices, host_spans=spans,
                        idle_gaps=_attribute_gaps(devices, spans, win))


def reduce_file(path: str) -> TraceSummary:
    return reduce_planes(read_planes(path))


def breakdown(summary: TraceSummary, n: int = 10) -> dict:
    """The ``breakdown`` key of a traced run's result line."""
    return {"device_ops": summary.top_ops(n),
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:n]]}
