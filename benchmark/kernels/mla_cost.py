"""Operations and bytes of the latent-attention kernels, from their shapes.

``flash_cost.py``'s counts (what the algorithm needs for one call, not what
a particular kernel happens to do) for attention whose heads have **two
widths and a shared key part** (``tepdist_tpu/ops/pallas/mla_attention.py``):
a head's score is ``q_nope . k_nope + q_rope . k_rope`` over ``Dn + Dr``
channels, ``k_rope`` is one ``[T, Dr]`` array a batch row that every head
reads, and values and output are ``Dv`` wide. ``widths = (Dn, Dr, Dv)``;
``heads = (B, H, T)``.

* a live (query, key) pair (``B*H*T*T/2`` of them under the causal mask)
  costs the forward its two matmuls, ``QK^T`` over ``Dn + Dr`` and ``PV``
  over ``Dv``: ``2 (Dn + Dr + Dv)`` operations; the backward its five,
  ``QK^T`` again, ``dS K`` and ``dS^T Q`` over ``Dn + Dr``, ``dO V^T`` and
  ``P^T dO`` over ``Dv``: ``2 (3 (Dn + Dr) + 2 Dv)``, divided between the
  dQ and the dK/dV kernel as ``flash_cost.py`` divides its own, 3/7 and 4/7
  (each kernel's recomputation of ``QK^T`` and ``dO V^T`` is its choice);
* each operand and result crosses HBM once at its own width and head count:
  ``q``, ``k_nope``, ``v``, ``o``, ``dO`` and their gradients a head,
  **``k_rope`` and its gradient once a batch row, not once a head** (that the
  dK/dV kernel writes a part a head, summed afterwards, is its choice), the
  float32 log-sum-exp and ``delta`` a row.

With ``Dr = 0`` and ``Dn = Dv = D`` every function returns ``flash_cost.py``'s
numbers exactly (a test pins it).
"""

from __future__ import annotations

from benchmark.kernels.flash_cost import roofline_seconds  # noqa: F401


def pairs(B, H, T, causal: bool = True) -> float:
    """Live (query, key) pairs of ``B * H`` heads."""
    return B * H * T * T * (0.5 if causal else 1.0)


def _io(heads, widths, dtype_bytes):
    """Bytes of (a head's q, its k_nope, the shared k_rope, a head's v, the
    float32 rows)."""
    B, H, T = heads
    Dn, Dr, Dv = widths
    a_head = B * H * T * dtype_bytes
    return (a_head * (Dn + Dr), a_head * Dn, B * T * Dr * dtype_bytes,
            a_head * Dv, B * H * T * 4.0)


def _backward_ops(heads, widths, causal):
    Dn, Dr, Dv = widths
    return 2.0 * (3 * (Dn + Dr) + 2 * Dv) * pairs(*heads, causal)


def forward(heads, widths, dtype_bytes: int = 2, causal: bool = True) -> dict:
    """q, k_nope, k_rope and v in, o and the log-sum-exp out."""
    Dn, Dr, Dv = widths
    q, kn, kr, v, rows = _io(heads, widths, dtype_bytes)
    return {"ops": 2.0 * (Dn + Dr + Dv) * pairs(*heads, causal),
            "bytes": q + kn + kr + 2.0 * v + rows}


def backward_dq(heads, widths, dtype_bytes: int = 2,
                causal: bool = True) -> dict:
    """3/7 of the five matmuls; q, k_nope, k_rope, v and dO in, dq out, the
    two float32 rows."""
    q, kn, kr, v, rows = _io(heads, widths, dtype_bytes)
    return {"ops": 3.0 / 7.0 * _backward_ops(heads, widths, causal),
            "bytes": 2.0 * q + kn + kr + 2.0 * v + 2.0 * rows}


def backward_dkv(heads, widths, dtype_bytes: int = 2,
                 causal: bool = True) -> dict:
    """4/7 of the five matmuls; q, k_nope, k_rope, v and dO in, dk_nope,
    dk_rope (once a batch row) and dv out, the two float32 rows."""
    q, kn, kr, v, rows = _io(heads, widths, dtype_bytes)
    return {"ops": 4.0 / 7.0 * _backward_ops(heads, widths, causal),
            "bytes": q + 2.0 * kn + 2.0 * kr + 3.0 * v + 2.0 * rows}
