"""Operations and bytes of Gated DeltaNet's mixing (the gated delta rule with
one decay a value head and token, ``Hk`` key heads under ``Hv`` value heads),
from its shapes.

What the operation needs for one call, whatever implements it: for ``T``
tokens, ``Hk`` key heads of ``K`` channels and ``Hv`` value heads of ``V``,
value head ``h`` on key head ``h // (Hv / Hk)``,

    Sd  = alpha_t S_{t-1}                r = Sd^T k_t
    S_t = Sd + beta_t k_t (v_t - r)^T    o_t = S_t^T q_t

* bytes, every operand and result across HBM once. Forward: ``q``, ``k``
  (``Hk K`` a token) and ``v`` (``Hv V``) at the activations' width, the log
  decays ``g`` and ``beta`` ``[T, Hv]`` in float32 in, ``o`` (``Hv V``) out.
  Backward: those and ``d o`` in, ``dq, dk, dv`` at the activations' width and
  ``dg, dbeta`` in float32 out. **Not counted**, because they are the
  implementation's: the states before each chunk and the chunks' inverses
  that a forward writes and a backward reads again.
* operations, the recurrence's own products with the state, two a
  multiply-add, a token and VALUE head. Forward ``6 K V``: the read ``Sd^T
  k``, the rank-one update, ``S^T q``. Backward ``14 K V``, the same
  recurrence transposed: ``dq = S do``; ``dS += q do^T``; through the update
  ``du = dS^T k`` and ``dk += dS u`` (``u = beta (v - r)``); through the read
  ``dk += Sd dr`` and ``dSd = dS + k dr^T`` (``dr = -beta du``); and the
  decay's gradient ``dg = alpha * sum(dSd * S_{t-1})``. The element-wise
  decay of the state, a chunked form's products inside a chunk (``q k^T``,
  ``k k^T``, ``2 C K`` a token, head and matmul), its triangular solve, its
  float32 operands' second parts, the kept states and the inverses are the
  implementation's and not counted, so a share cannot pass 100% by them.

The roofline time of a call is the larger of operations over the matrix
unit's bf16 peak and bytes over HBM's (``peaks.json``); ``bound`` says
which. By those peaks the operation is bound by HBM at the published heads:
forward 8 bytes a key channel and 4 a value channel against ``6 K`` = 768
operations a value channel, 0.31 ms against 0.13 ms a call at 8,192 tokens
of 16 key and 32 value heads of 128.
"""

from __future__ import annotations

from benchmark.kernels.flash_cost import roofline_seconds  # noqa: F401


def forward(T: int, Hk: int, Hv: int, K: int, V: int,
            act_bytes: int = 2) -> dict:
    return {"ops": 6.0 * T * Hv * K * V,
            "bytes": T * (act_bytes * (2 * Hk * K + 2 * Hv * V) + 8.0 * Hv)}


def backward(T: int, Hk: int, Hv: int, K: int, V: int,
             act_bytes: int = 2) -> dict:
    return {"ops": 14.0 * T * Hv * K * V,
            "bytes": T * (act_bytes * (4 * Hk * K + 4 * Hv * V) + 16.0 * Hv)}
