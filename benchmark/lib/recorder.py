"""The program's span recorder (``tepdist_tpu.telemetry``) as the harness
switches it, both switches in one place. ``run.py`` switches it on at
process start, so set-up leaves its spans in every trace mode; a driver
switches it off as its measured window opens, so the window runs with spans
and profiler off, as the driver measures. A traced window switches it on
again through the program's own control (``lib/tracing.py``)."""

from __future__ import annotations


def on() -> None:
    from tepdist_tpu import telemetry
    telemetry.configure(enabled=True)


def off_for_window() -> dict:
    """Called by a driver as its window opens: what the program traced,
    lowered and compiled during set-up, by its own counter
    (``telemetry.compile_stats()``), and from here on no span is recorded."""
    from tepdist_tpu import telemetry
    stats = telemetry.compile_stats()
    telemetry.configure(enabled=False)
    return stats
