"""Operations and bytes of Kimi Delta Attention (the gated delta rule with a
decay for every key channel), from its shapes.

What the operation needs for one call, whatever implements it: for ``T``
tokens of ``H`` heads with ``K`` key and ``V`` value channels,

    Sd  = Diag(alpha_t) S_{t-1}          r = Sd^T k_t
    S_t = Sd + beta_t k_t (v_t - r)^T    o_t = S_t^T q_t

* bytes, every operand and result across HBM once. Forward: ``q``, ``k``
  (``K`` a head) and ``v`` (``V``) at the activations' width, the log decays
  ``g`` ``[T, H, K]`` and ``beta`` ``[T, H]`` in float32 in, ``o`` (``V``)
  out. Backward: those and ``d o`` in, ``dq, dk, dv`` at the activations'
  width and ``dg, dbeta`` in float32 out. **Not counted**, because they are
  the implementation's: the states before each chunk that a backward writes
  and reads again.
* operations, the recurrence's own products with the state, two a
  multiply-add. Forward ``6 K V`` a token and head: the read ``Sd^T k``, the
  rank-one update, ``S^T q``. Backward ``14 K V``, the same recurrence
  transposed: ``dq = S do``; ``dS += q do^T``; through the update ``du = dS^T
  k`` and ``dk += dS u`` (``u = beta (v - r)``); through the read ``dk += Sd
  dr`` and ``dSd = dS + k dr^T`` (``dr = -beta du``); and the decays'
  gradient ``dg = alpha * rowsum(dSd * S_{t-1})``. The element-wise decay of
  the state, a chunked form's products inside a chunk (``2 C K`` a token,
  head and matmul), its triangular solve, its float32 operands' second
  parts and the states a backward makes again are the implementation's and
  not counted, so a share cannot pass 100% by them.

The roofline time of a call is the larger of operations over the matrix
unit's bf16 peak and bytes over HBM's (``peaks.json``); ``bound`` says
which. By those peaks the operation is bound by HBM: forward 12 bytes a key
channel against ``6 V`` = 768 operations at ``V`` = 128, 0.49 ms against
0.13 ms a call at 8,192 tokens of 32 heads.
"""

from __future__ import annotations

from benchmark.kernels.flash_cost import roofline_seconds  # noqa: F401


def forward(T: int, H: int, K: int, V: int, act_bytes: int = 2) -> dict:
    return {"ops": 6.0 * T * H * K * V,
            "bytes": T * H * (act_bytes * (2 * K + 2 * V) + 4.0 * K + 4.0)}


def backward(T: int, H: int, K: int, V: int, act_bytes: int = 2) -> dict:
    return {"ops": 14.0 * T * H * K * V,
            "bytes": T * H * (act_bytes * (4 * K + 4 * V) + 8.0 * K + 8.0)}
