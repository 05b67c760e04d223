"""Operations and bytes of the flash attention kernels under a window and
with fewer key/value heads than query heads, from their shapes.

``flash_cost.py``'s counts (what the algorithm needs for one call, not what
a particular kernel happens to do) with two more facts of the call:

* ``window``: under a causal mask key j is visible to query i iff ``0 <= i
  - j < window``. The live (query, key) pairs of one head are then ``W*T -
  W*W/2`` for ``W = min(window, T)`` (the first W queries see a growing
  triangle, ``W*W/2``; every later query W keys), which is ``T*T/2``, the
  causal triangle, once the window reaches every earlier key. Blocks the
  kernels visit half empty at the window's edges are their choice and are
  not counted.
* ``kv_heads``: k and v (and dk, dv) cross HBM at their own head count,
  ``B * kv_heads * T * D`` each; q, o, dO and dq at the query heads'. That
  the dK/dV kernel writes a part a query head, summed afterwards, is its
  choice and is not counted.

With ``window=None`` and ``kv_heads`` equal to the query heads every
function returns ``flash_cost.py``'s numbers exactly (a test pins it).
"""

from __future__ import annotations

from typing import Optional

from benchmark.kernels.flash_cost import roofline_seconds  # noqa: F401


def pairs(B, H, T, causal: bool = True,
          window: Optional[int] = None) -> float:
    """Live (query, key) pairs of ``B * H`` heads."""
    if not causal:
        return float(B * H * T * T)
    if window is None or window >= T:
        return B * H * T * T * 0.5
    return B * H * (window * T - window * window * 0.5)


def _io(shape, kv_heads, dtype_bytes):
    B, H, T, D = shape
    return (B * H * T * D * dtype_bytes,
            B * (kv_heads or H) * T * D * dtype_bytes, B * H * T * 4.0)


def forward(shape, dtype_bytes: int = 2, causal: bool = True,
            window: Optional[int] = None,
            kv_heads: Optional[int] = None) -> dict:
    """Two matmuls a pair; q in and o out, k and v in, the float32
    log-sum-exp out."""
    B, H, T, D = shape
    q_io, kv_io, rows = _io(shape, kv_heads, dtype_bytes)
    return {"ops": 4.0 * D * pairs(B, H, T, causal, window),
            "bytes": 2.0 * q_io + 2.0 * kv_io + rows}


def backward_dq(shape, dtype_bytes: int = 2, causal: bool = True,
                window: Optional[int] = None,
                kv_heads: Optional[int] = None) -> dict:
    """3/7 of the backward pass's five matmuls a pair (``flash_cost.py``
    says why); q and dO in, dq out, k and v in, the two float32 rows."""
    B, H, T, D = shape
    q_io, kv_io, rows = _io(shape, kv_heads, dtype_bytes)
    return {"ops": 3.0 / 7.0 * (10.0 * D * pairs(B, H, T, causal, window)),
            "bytes": 3.0 * q_io + 2.0 * kv_io + 2.0 * rows}


def backward_dkv(shape, dtype_bytes: int = 2, causal: bool = True,
                 window: Optional[int] = None,
                 kv_heads: Optional[int] = None) -> dict:
    """4/7 of the five matmuls a pair; q and dO in, k and v in, dk and dv
    out, the two float32 rows."""
    B, H, T, D = shape
    q_io, kv_io, rows = _io(shape, kv_heads, dtype_bytes)
    return {"ops": 4.0 / 7.0 * (10.0 * D * pairs(B, H, T, causal, window)),
            "bytes": 2.0 * q_io + 4.0 * kv_io + 2.0 * rows}
