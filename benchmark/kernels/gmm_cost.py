"""Operations and bytes of a grouped matmul, from its shapes.

What the mathematics needs for one call, not what a particular kernel
happens to do: ``rows`` rows, each routed to one of ``groups`` experts, times
that expert's ``[K, N]`` matrix.

* operations: ``2 * rows * K * N`` for the rows routed. Rows a layout pads
  in, tiles computed twice at a group's edge and masked lanes are the
  kernel's choice and are not counted.
* bytes: every operand and result across HBM once: the rows in
  (``rows * K``), the rows out (``rows * N``) and each expert's weights once
  a call (``groups * K * N``), whether they are read (forward, input
  gradient) or written (weight gradient).

The three forms of a training step are the same count with the roles
exchanged, so one function serves them: forward ``[rows, K] x [G, K, N] ->
[rows, N]``; input gradient ``[rows, N] x [G, K, N]^T -> [rows, K]``; weight
gradient ``[rows, K]^T x [rows, N] -> [G, K, N]``.

The roofline time of a call is the larger of operations over the peak rate
and bytes over the peak bandwidth; ``bound`` says which.
"""

from __future__ import annotations

from benchmark.kernels.flash_cost import roofline_seconds  # noqa: F401


def grouped_matmul(rows: int, K: int, N: int, groups: int,
                   dtype_bytes: int = 2) -> dict:
    return {"ops": 2.0 * rows * K * N,
            "bytes": float(dtype_bytes) * (rows * K + rows * N
                                           + groups * K * N)}
