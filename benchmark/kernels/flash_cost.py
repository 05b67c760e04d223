"""Operations and bytes of the flash attention kernels, from their shapes.

What the algorithm needs for one call, not what a particular kernel happens
to do: a causal attention over ``[B, H, T, D]`` has ``B*H*T*T/2`` live
(query, key) pairs (half of the square, diagonal blocks counted whole would
add ``T*block/2``; the kernels skip blocks above the diagonal, and the
count here keeps to the triangle).

* forward: two matmuls per pair, ``QK^T`` and ``PV``: ``4*D`` operations.
* backward: five matmuls per pair, ``QK^T`` again, ``dO V^T``, ``P^T dO``,
  ``dS K`` and ``dS^T Q``: ``10*D`` operations. The program splits the
  backward into two kernels (dQ; dK and dV) that each recompute ``QK^T``
  and ``dO V^T``; that repetition is the kernels' choice and is not
  counted, so a backward call pair is charged the five matmuls once.
* bytes: each operand and result crosses HBM once. Forward reads q, k, v and
  writes o and the float32 log-sum-exp; backward reads q, k, v, o (through
  ``delta``), dO, the log-sum-exp and delta, and writes dq, dk, dv.

The roofline time of a call is the larger of operations over the peak rate
and bytes over the peak bandwidth; ``bound`` says which.
"""

from __future__ import annotations


def _pairs(B, H, T, causal: bool) -> float:
    return B * H * T * T * (0.5 if causal else 1.0)


def forward(shape, dtype_bytes: int = 2, causal: bool = True) -> dict:
    B, H, T, D = shape
    io = B * H * T * D * dtype_bytes
    return {"ops": 4.0 * D * _pairs(B, H, T, causal),
            "bytes": 4.0 * io + B * H * T * 4.0}


def backward(shape, dtype_bytes: int = 2, causal: bool = True) -> dict:
    """The whole backward pass of one call: both kernels together."""
    B, H, T, D = shape
    io = B * H * T * D * dtype_bytes
    return {"ops": 10.0 * D * _pairs(B, H, T, causal),
            "bytes": 8.0 * io + 2.0 * B * H * T * 4.0}


def backward_dq(shape, dtype_bytes: int = 2, causal: bool = True) -> dict:
    """The dQ kernel's part. Of the five matmuls the backward pass needs,
    this kernel runs three (``QK^T``, ``dO V^T``, ``dS K``) and the other
    four, two of them the same: each is charged its share, 3/7 and 4/7, of
    the five. Bytes are the kernel's own operands and result, once each:
    q, k, v, dO in, dq out, and the two float32 rows."""
    B, H, T, D = shape
    io = B * H * T * D * dtype_bytes
    return {"ops": 3.0 / 7.0 * backward(shape, dtype_bytes, causal)["ops"],
            "bytes": 5.0 * io + 2.0 * B * H * T * 4.0}


def backward_dkv(shape, dtype_bytes: int = 2, causal: bool = True) -> dict:
    """The dK/dV kernel's part: 4/7 of the five matmuls; q, k, v, dO in,
    dk and dv out, and the two float32 rows."""
    B, H, T, D = shape
    io = B * H * T * D * dtype_bytes
    return {"ops": 4.0 / 7.0 * backward(shape, dtype_bytes, causal)["ops"],
            "bytes": 6.0 * io + 2.0 * B * H * T * 4.0}


def roofline_seconds(cost: dict, peaks: dict) -> dict:
    """Least time the chip could take for ``cost``, and which peak sets it."""
    t_ops = cost["ops"] / peaks["bf16_flops_per_s"]
    t_mem = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_mem),
            "bound": "compute" if t_ops >= t_mem else "memory"}
