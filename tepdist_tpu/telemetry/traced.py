"""The gauges that are set while a step is traced: what the traced program
holds (a kernel's forward calls a micro batch, bytes kept from a forward pass
to its backward), known from the trace alone, before anything runs.

One group, so that who builds a step zeroes all of it before the trace
(``parallel/sync_free.py:build_ga_step``: :func:`reset`) and who planned one
reports all of it (``train.py:plan_training``: :func:`values`) without
naming a gauge. A gauge joins where the module that counts it is imported
(:func:`declare`, beside the kernel, with the sentence on what it counts) and
stays a gauge of ``metrics()`` under its name.

A walk over stacked blocks traces its body once for all its layers
(``models/layers.py:scan_blocks``): it traces the body inside
:func:`stands_for`, and a :func:`count` inside counts once a layer."""

from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional

from tepdist_tpu.telemetry.metrics import metrics

GROUP: Dict[str, str] = {}      # name -> what it counts, in joining order
_LAYERS = contextvars.ContextVar("tepdist_traced_layers", default=1)


def declare(name: str, what: str) -> None:
    """``name`` joins the group; ``what``: one sentence on what it counts."""
    GROUP[name] = what


@contextlib.contextmanager
def stands_for(layers):
    """One trace of the code inside stands for ``layers`` runs of it; inside
    another ``stands_for`` for that many of each of the outer's. (1 / 2
    round a ``lax.cond``: each of two traced branches, of which one runs.)"""
    token = _LAYERS.set(_LAYERS.get() * layers)
    try:
        yield
    finally:
        _LAYERS.reset(token)


def stood_for():
    """The runs one trace stands for where this is called (1 outside any
    walk). A ``custom_vjp`` reads it where it is called and hands it to its
    rules, which JAX may trace after the walk's body has returned."""
    return _LAYERS.get()


def count(name: str, times: int = 1, layers: Optional[int] = None) -> None:
    """Adds ``times`` x the layers stood for (``layers``: as read earlier by
    :func:`stood_for`) to the gauge ``name``, which joins the group."""
    GROUP.setdefault(name, "")
    gauge = metrics().gauge(name)
    gauge.set((gauge.value or 0)
              + times * (stood_for() if layers is None else layers))


def note(name: str, value: float) -> None:
    """Sets the gauge ``name``, which joins the group."""
    GROUP.setdefault(name, "")
    metrics().gauge(name).set(value)


def reset() -> None:
    """Zeroes every gauge of the group: a kernel the next traced step does
    not call reads 0, not what the last step left."""
    for name in GROUP:
        metrics().gauge(name).set(0)


def values() -> Dict[str, float]:
    """name -> value (0 where never set), in the order the names joined."""
    return {name: metrics().gauge(name).value or 0 for name in GROUP}
