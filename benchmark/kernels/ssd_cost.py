"""Operations and bytes of Mamba-2's mixing (the state-space rule with one
decay a head and token, ``H`` heads over ``G`` groups' ``B`` and ``C``), from
its shapes.

What the operation needs for one call, whatever implements it: for ``T``
tokens, ``H`` heads of ``P`` channels over ``N`` states, head ``h`` on group
``h // (H / G)``,

    S_t = exp(Delta_t A) S_{t-1} + Delta_t u_t B_t^T        y_t = S_t C_t + D u_t

* bytes, every operand and result across HBM once. Forward: ``u`` (``H P`` a
  token) and ``B``, ``C`` (``G N`` each) at the activations' width and the
  steps ``Delta`` ``[T, H]`` in float32 in, ``y`` (``H P``) out. Backward:
  those and ``d y`` in, ``du``, ``dB``, ``dC`` at the activations' width and
  ``dDelta`` in float32 out. **Not counted**, because they are the
  implementation's: ``Delta A`` as a second ``[T, H]`` operand and the states
  before each chunk that a forward writes and a backward reads again.
* operations, the recurrence's own products with the state, two a
  multiply-add, a token and head. Forward ``4 N P``: the rank-one update
  ``Delta u B^T`` and the read ``S C``. Backward ``8 N P``, the same
  recurrence transposed: ``dS += dy C^T`` and ``dC += S^T dy`` through the
  read, ``d(Delta u) = dS B`` and ``dB += dS^T (Delta u)`` through the
  update. The element-wise decay of the state and its gradient, a chunked
  form's products inside a chunk (``C B^T`` and the masked product with
  ``Delta u``, ``2 C (N / r + P)`` a token and head), its float32 operands'
  second parts and the kept states are the implementation's and not counted,
  so a share cannot pass 100% by them.

The roofline time of a call is the larger of operations over the matrix
unit's bf16 peak and bytes over HBM's (``peaks.json``); ``bound`` says which.
By those peaks the operation is bound by HBM at the published heads: forward
4 bytes a channel of ``u`` and ``y`` and a quarter of that again for ``B``
and ``C`` against ``4 N`` = 512 operations a channel, 0.21 ms against 0.09
ms a call at 8,192 tokens of 64 heads of 64 over 8 groups of 128 states.
"""

from __future__ import annotations

from benchmark.kernels.flash_cost import roofline_seconds  # noqa: F401


def forward(T: int, H: int, P: int, G: int, N: int,
            act_bytes: int = 2) -> dict:
    return {"ops": 4.0 * T * H * P * N,
            "bytes": T * (act_bytes * (2 * H * P + 2 * G * N) + 4.0 * H)}


def backward(T: int, H: int, P: int, G: int, N: int,
             act_bytes: int = 2) -> dict:
    return {"ops": 8.0 * T * H * P * N,
            "bytes": T * (act_bytes * (3 * H * P + 4 * G * N) + 8.0 * H)}
