"""Bytes a sub-layer under manifold-constrained hyper-connections has to
move, from its shapes.

``flash_cost.py``'s kind of count (what any form needs, not what a
particular one happens to do) for ``tepdist_tpu/models/layers.py``'s
``hyper_maps`` + ``hyper_read`` and ``hyper_write`` round a sub-layer ``F``
on a stream of ``n`` lanes of ``d`` channels. ``F`` stands between the two
and nothing fuses across it, so the least is two passes over the stream:

* **read** (the maps and the sub-layer's input): the stream in once, ``n d``
  values a token, and the input out, ``d``;
* **write** (the stream after the sub-layer): the stream in once more, ``n
  d``, the sub-layer's output in, ``d``, and the new stream out, ``n d``;

``(3 n + 2) d`` values a token in the stream's dtype, 14 d at four lanes
(ISSUE 61 lists these five arrays and writes their sum as 10 d, which leaves
the stream's write out). The maps themselves (``n^2 + 2n`` float32 a token,
written by the read and read by the write), ``phi`` and the cotangents of the
maps are a hundredth of that and are not counted. The backward pass reads
what the forward read and the cotangent of what it wrote, and writes the
cotangent of what it read: twice the forward's bytes at the least.
Operations: the product with ``phi`` (``2 n d (n^2 + 2n)`` a token) and ``2
n (n + 1) d`` multiply-adds of the mixing are a thousandth of what the chip
does in the time the bytes take; the bound is HBM's and only bytes are
returned beside them.
"""

from __future__ import annotations

from benchmark.kernels.flash_cost import roofline_seconds  # noqa: F401


def _ops(tokens: int, n: int, d: int) -> float:
    return 2.0 * tokens * n * d * (n * n + 2 * n)


def read(tokens: int, n: int, d: int, dtype_bytes: int = 2) -> dict:
    """``hyper_maps`` + ``hyper_read``: the stream in, the input out."""
    return {"ops": _ops(tokens, n, d),
            "bytes": float(tokens * (n + 1) * d * dtype_bytes)}


def write(tokens: int, n: int, d: int, dtype_bytes: int = 2) -> dict:
    """``hyper_write``: the stream and the sub-layer's output in, the new
    stream out."""
    return {"ops": 2.0 * tokens * n * (n + 1) * d,
            "bytes": float(tokens * (2 * n + 1) * d * dtype_bytes)}


def backward(forward: dict) -> dict:
    """Of either pass: twice its forward's bytes and operations."""
    return {k: 2.0 * v for k, v in forward.items()}
