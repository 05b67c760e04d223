"""Telemetry unit tests: the disabled no-op contract, ring buffer, metrics
registry + fleet merge, chrome-trace export, and tools/trace_summary.py.

No servers here — the real GetTelemetry / merged-trace path is covered in
tests/test_multiworker.py::test_merged_fleet_trace.
"""

import json
import os
import sys
import time

import pytest

from tepdist_tpu.telemetry import (
    _NULL_SPAN,
    MetricsRegistry,
    Span,
    Tracer,
    build_trace,
    to_chrome_events,
    write_trace,
)
from tepdist_tpu.telemetry import trace as trace_mod


@pytest.fixture()
def private_tracer():
    """Swap a private tracer in for the module global so tests neither
    observe nor disturb the process-wide ring (other tests, DEBUG runs)."""
    prev = trace_mod.tracer()
    t = Tracer(capacity=64, enabled=False)
    trace_mod._TRACER = t
    yield t
    trace_mod._TRACER = prev


# ---------------------------------------------------------------------------
# span(): disabled fast path


def test_disabled_span_is_the_shared_singleton(private_tracer):
    # The contract instrumented hot paths rely on: no allocation, no
    # recording — the SAME object every call.
    assert trace_mod.span("a", cat="compute") is _NULL_SPAN
    assert trace_mod.span("b") is trace_mod.span("c")
    with trace_mod.span("d", cat="rpc", step=3) as sp:
        assert sp is _NULL_SPAN
        sp.set(bytes=123)  # must be a no-op, not an error
    assert sp.dur_us == 0.0 and sp.dur_ms == 0.0 and sp.elapsed_ms == 0.0
    assert len(private_tracer) == 0


def test_disabled_span_overhead_is_noop_sized(private_tracer):
    """Micro-benchmark (tier-1-fast): the disabled path must cost no more
    than a function call + branch. What is asserted does not depend on the
    host's load: off, every call hands back the shared singleton and
    nothing is recorded; plus a very generous absolute ceiling so a real
    regression (e.g. allocating a Span before checking `enabled`) fails
    even on a loaded 1-core host. (A race against the C recording path,
    ``disabled_ns < enabled_ns``, lost under six xdist workers.)"""
    n = 10000

    def timed_ns():
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with trace_mod.span("bench", cat="bench") as sp:
                pass
        assert (sp is _NULL_SPAN) is (not private_tracer.enabled)
        return (time.perf_counter_ns() - t0) / n

    private_tracer.enabled = False
    disabled_ns = min(timed_ns() for _ in range(3))
    assert len(private_tracer) == 0

    private_tracer.enabled = True
    timed_ns()
    assert len(private_tracer) > 0

    assert disabled_ns < 50_000, f"disabled span costs {disabled_ns:.0f} ns"


# ---------------------------------------------------------------------------
# span(): enabled recording


def test_enabled_span_records_fields(private_tracer):
    private_tracer.enabled = True
    before_us = time.time_ns() // 1000
    with trace_mod.span("stage0_fwd", cat="compute", stage=0) as sp:
        assert sp is not _NULL_SPAN  # a real recording span
        assert sp.elapsed_ms >= 0.0  # live-readable mid-block
        sp.set(bytes=4096)
    rec = private_tracer.snapshot()[-1]
    assert rec["name"] == "stage0_fwd"
    assert rec["cat"] == "compute"
    assert rec["args"] == {"stage": 0, "bytes": 4096}
    # Epoch microseconds (cross-process comparable), not perf_counter.
    assert before_us <= rec["ts"] <= time.time_ns() // 1000
    assert rec["dur"] >= 0.0
    assert rec["tid"]  # recording thread's name


def test_ring_capacity_drops_oldest():
    t = Tracer(capacity=4, enabled=True)
    for i in range(10):
        with Span(t, f"s{i}", "misc", {}):
            pass
    names = [r["name"] for r in t.snapshot()]
    assert names == ["s6", "s7", "s8", "s9"]


def test_snapshot_clear_drains(private_tracer):
    private_tracer.enabled = True
    with trace_mod.span("x"):
        pass
    assert len(private_tracer) == 1
    out = private_tracer.snapshot(clear=True)
    assert len(out) == 1 and len(private_tracer) == 0


def test_configure_toggles_and_rerings():
    prev = trace_mod.tracer()
    try:
        t = trace_mod.configure(enabled=True, capacity=8)
        assert t.enabled and t.capacity == 8
        assert trace_mod.span("y") is not _NULL_SPAN
        t2 = trace_mod.configure(enabled=False)
        assert t2 is t and trace_mod.span("z") is _NULL_SPAN
    finally:
        trace_mod._TRACER = prev


# ---------------------------------------------------------------------------
# metrics


def test_metrics_registry_snapshot():
    r = MetricsRegistry()
    r.counter("steps").inc()
    r.counter("steps").inc(4)
    r.gauge("rtt").set(2.5)
    r.histogram("lat").observe(1.0)
    r.histogram("lat").observe(3.0)
    snap = r.snapshot()
    assert snap["counters"] == {"steps": 5}
    assert snap["gauges"] == {"rtt": 2.5}
    h = snap["histograms"]["lat"]
    assert h["count"] == 2 and h["sum"] == 4.0 and h["mean"] == 2.0
    assert h["min"] == 1.0 and h["max"] == 3.0
    json.dumps(snap)  # must be wire-safe (travels in GetTelemetry header)
    r.reset()
    assert r.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_metrics_merge_policy():
    a = MetricsRegistry()
    a.counter("bytes").inc(10)
    a.gauge("rtt").set(1.0)
    a.histogram("lat").observe(1.0)
    b = MetricsRegistry()
    b.counter("bytes").inc(7)
    b.counter("only_b").inc()
    b.gauge("rtt").set(3.0)
    b.gauge("unset")  # value None: must not poison the merge
    b.histogram("lat").observe(5.0)
    m = MetricsRegistry.merge([a.snapshot(), b.snapshot(), {}])
    assert m["counters"] == {"bytes": 17, "only_b": 1}
    assert m["gauges"] == {"rtt": 3.0}  # max: conservative fleet read
    h = m["histograms"]["lat"]
    assert h["count"] == 2 and h["sum"] == 6.0 and h["mean"] == 3.0
    assert h["min"] == 1.0 and h["max"] == 5.0


# ---------------------------------------------------------------------------
# chrome-trace export


def _fake_spans(t0_us, tid="MainThread"):
    return [
        {"name": "run_step", "cat": "step", "ts": t0_us, "dur": 100.0,
         "tid": tid, "args": {"step": 1}},
        {"name": "stage0", "cat": "compute", "ts": t0_us + 5, "dur": 40.0,
         "tid": tid, "args": {}},
    ]


def test_to_chrome_events_offset_and_metadata():
    evs = to_chrome_events(_fake_spans(1000.0), pid=1, offset_us=100.0,
                           label="worker1")
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in meta}
    assert meta[0]["args"]["name"] == "worker1"
    xs = [e for e in evs if e["ph"] == "X"]
    assert [e["ts"] for e in xs] == [900.0, 905.0]  # clock-aligned
    assert all(e["pid"] == 1 for e in xs)


def test_build_trace_merges_workers_and_metrics():
    r0, r1 = MetricsRegistry(), MetricsRegistry()
    r0.counter("worker_steps").inc(2)
    r1.counter("worker_steps").inc(3)
    trace = build_trace([
        {"pid": 0, "label": "worker0", "spans": _fake_spans(0.0),
         "offset_us": 0.0, "metrics": r0.snapshot()},
        {"pid": 1, "label": "worker1", "spans": _fake_spans(10.0),
         "offset_us": 0.0, "metrics": r1.snapshot()},
    ])
    assert trace["displayTimeUnit"] == "ms"
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    for e in xs:  # the shape Perfetto requires of complete events
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    assert trace["metadata"]["metrics"]["counters"]["worker_steps"] == 5
    json.dumps(trace)


def test_write_trace_explicit_path_and_dump_dir(tmp_path, monkeypatch):
    trace = build_trace([{"pid": 0, "spans": _fake_spans(0.0)}])
    p = write_trace(trace, path=str(tmp_path / "sub" / "t.json"))
    assert p and json.load(open(p))["traceEvents"]
    # path=None: the debug_dump policy ($TEPDIST_DUMP_DIR)
    monkeypatch.setenv("TEPDIST_DUMP_DIR", str(tmp_path / "dumps"))
    p2 = write_trace(trace, name="steptrace")
    assert p2 == str(tmp_path / "dumps" / "steptrace.json")
    assert json.load(open(p2))["traceEvents"]


# ---------------------------------------------------------------------------
# tools/trace_summary.py


def test_trace_summary_busy_and_bubble(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import trace_summary

    # Worker 0 over a 100 ms window: compute 40+30 ms (overlap-free),
    # send 10 ms, plus a run_step ENVELOPE that must not count as busy.
    us = 1000.0
    spans = [
        {"name": "run_step", "cat": "step", "ts": 0.0, "dur": 100 * us},
        {"name": "c0", "cat": "compute", "ts": 0.0, "dur": 40 * us},
        {"name": "send", "cat": "send", "ts": 40 * us, "dur": 10 * us},
        {"name": "c1", "cat": "compute", "ts": 60 * us, "dur": 30 * us},
        # Overlapping compute (another thread): union, not double-count.
        {"name": "c1b", "cat": "compute", "ts": 70 * us, "dur": 10 * us},
    ]
    trace = build_trace([{"pid": 0, "label": "worker0", "spans": spans}])
    s = trace_summary.summarize(trace)
    assert s["n_events"] == 5
    assert s["category_ms"]["compute"] == pytest.approx(80.0)  # 40+30+10 raw
    w = s["workers"]["0"]
    assert w["label"] == "worker0"
    assert w["window_ms"] == pytest.approx(100.0)
    assert w["busy_ms"] == pytest.approx(80.0)     # union: 40+10+30
    assert w["compute_ms"] == pytest.approx(70.0)  # union: 40+30
    assert w["bubble_fraction"] == pytest.approx(0.3)

    path = str(tmp_path / "t.json")
    write_trace(trace, path=path)
    assert trace_summary.summarize(trace_summary.load_trace(path)) == s
    with pytest.raises(ValueError):
        json.dump({"nope": 1}, open(str(tmp_path / "bad.json"), "w"))
        trace_summary.load_trace(str(tmp_path / "bad.json"))


# ---------------------------------------------------------------------------
# histogram reservoir percentiles + Prometheus exposition


def test_histogram_reservoir_percentiles():
    from tepdist_tpu.telemetry.metrics import Histogram

    h = Histogram()
    for v in range(1, 101):  # below RESERVOIR_SIZE: sample is exact
        h.observe(float(v))
    d = h.to_dict()
    assert d["p50"] == pytest.approx(50.5)
    assert d["p95"] == pytest.approx(95.05)
    assert d["p99"] == pytest.approx(99.01)
    assert len(d["reservoir"]) == 100
    json.dumps(d)  # travels in the GetTelemetry header


def test_histogram_reservoir_caps_and_stays_deterministic():
    from tepdist_tpu.telemetry.metrics import Histogram

    def fill():
        h = Histogram()
        for v in range(10_000):
            h.observe(float(v))
        return h.to_dict()

    a, b = fill(), fill()
    assert len(a["reservoir"]) == Histogram.RESERVOIR_SIZE
    assert a == b  # seeded RNG: snapshots are reproducible
    # A uniform sample of 0..9999 must put p50 near the middle.
    assert 3000 < a["p50"] < 7000


def test_merge_pools_reservoirs_and_recomputes_percentiles():
    a, b = MetricsRegistry(), MetricsRegistry()
    for v in range(1, 51):
        a.histogram("lat").observe(float(v))       # 1..50
    for v in range(51, 101):
        b.histogram("lat").observe(float(v))       # 51..100
    m = MetricsRegistry.merge([a.snapshot(), b.snapshot()])
    h = m["histograms"]["lat"]
    assert h["count"] == 100
    # Percentiles span BOTH workers, not either one alone.
    assert h["p50"] == pytest.approx(50.5)
    assert h["p95"] == pytest.approx(95.05)
    assert len(h["reservoir"]) == 100


def test_merge_thins_pooled_reservoir_to_cap():
    from tepdist_tpu.telemetry.metrics import Histogram

    regs = []
    for w in range(4):
        r = MetricsRegistry()
        for v in range(200):
            r.histogram("lat").observe(float(w * 200 + v))
        regs.append(r.snapshot())
    m = MetricsRegistry.merge(regs)
    h = m["histograms"]["lat"]
    assert h["count"] == 800
    # Repeated merges must not grow the wire payload past the cap.
    assert len(h["reservoir"]) == Histogram.RESERVOIR_SIZE
    assert h["reservoir"] == sorted(h["reservoir"])


def test_to_prometheus_exposition():
    from tepdist_tpu.telemetry.export import to_prometheus

    r = MetricsRegistry()
    r.counter("worker_steps").inc(5)
    r.counter("rpc_ms:RunStep")  # name needs sanitizing
    r.gauge("serve_queue_depth").set(3.0)
    for v in (1.0, 2.0, 3.0, 4.0):
        r.histogram("serve_ttft_ms").observe(v)
    text = to_prometheus(r.snapshot())
    assert "# TYPE tepdist_worker_steps counter" in text
    assert "tepdist_worker_steps 5" in text
    assert "tepdist_rpc_ms_RunStep 0" in text  # ':' sanitized
    assert "# TYPE tepdist_serve_queue_depth gauge" in text
    assert "tepdist_serve_queue_depth 3.0" in text
    assert "# TYPE tepdist_serve_ttft_ms summary" in text
    assert 'tepdist_serve_ttft_ms{quantile="0.5"}' in text
    assert 'tepdist_serve_ttft_ms{quantile="0.99"}' in text
    assert "tepdist_serve_ttft_ms_sum 10.0" in text
    assert "tepdist_serve_ttft_ms_count 4" in text
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# spans_dropped: the ring-overflow truth-teller


def test_tracer_counts_drops_and_resets():
    t = Tracer(capacity=4, enabled=True)
    for i in range(10):
        with Span(t, f"s{i}", "misc", {}):
            pass
    assert t.dropped == 6
    t.snapshot(clear=False)
    assert t.dropped == 6         # non-draining read keeps the count
    t.snapshot(clear=True)
    assert t.dropped == 0         # drain resets: drops are per-window
    with Span(t, "s", "misc", {}):
        pass
    t.clear()
    assert t.dropped == 0 and len(t) == 0


def test_build_trace_surfaces_spans_dropped():
    trace = build_trace([
        {"pid": 0, "label": "worker0", "spans": _fake_spans(0.0),
         "spans_dropped": 3},
        {"pid": 1, "label": "worker1", "spans": _fake_spans(10.0),
         "spans_dropped": 0},
    ])
    assert trace["metadata"]["spans_dropped"] == {"worker0": 3}
    lossless = build_trace([{"pid": 0, "spans": _fake_spans(0.0)}])
    assert "spans_dropped" not in lossless.get("metadata", {})
