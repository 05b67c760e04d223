"""Operations and bytes of a decayed linear attention (Lightning
Attention), from its shapes.

What the operation needs for one call, whatever implements it: for ``T``
tokens of ``H`` heads of ``D`` channels,

    S_t = lam_h * S_{t-1} + k_t^T v_t          o_t = q_t S_t

* bytes, every operand and result across HBM once. Forward: ``q``, ``k``,
  ``v`` in and ``o`` out, ``[T, H, D]`` each at the activations' width.
  Backward: ``q``, ``k``, ``v``, ``d o`` in and the three gradients out.
  The ``[H]`` decays are nothing beside them. **Not counted**, because they
  are the implementation's: chunk-boundary states, a second read of ``k``
  and ``v`` by a backward that is two sweeps.
* operations, the recurrence's own, two a multiply-add: forward ``4 D^2`` a
  token and head (the outer product into the state, ``2 D^2``, and ``q_t
  S_t``, ``2 D^2``); backward ``8 D^2`` (``d q = d o S^T``, the state's
  gradient ``+= q^T d o``, ``d k = v d S^T``, ``d v = k d S``). A chunked
  form's products inside a chunk (``2 C D`` a token and head and matmul,
  more than the recurrence's from a chunk of ``D`` up) and the states a
  backward computes again are the implementation's and not counted, so a
  share cannot pass 100% by them.

The roofline time of a call is the larger of operations over the matrix
unit's bf16 peak and bytes over HBM's (``peaks.json``); ``bound`` says
which. By those peaks the operation is bound by HBM: forward 8 bytes a
channel against ``4 D`` = 512 operations at ``D`` = 128, 1.31 ms against
0.35 ms a call at 32,768 tokens of 32 heads.
"""

from __future__ import annotations

from benchmark.kernels.flash_cost import roofline_seconds  # noqa: F401


def forward(T: int, H: int, D: int, act_bytes: int = 2) -> dict:
    return {"ops": 4.0 * T * H * D * D, "bytes": 4.0 * T * H * D * act_bytes}


def backward(T: int, H: int, D: int, act_bytes: int = 2) -> dict:
    return {"ops": 8.0 * T * H * D * D, "bytes": 7.0 * T * H * D * act_bytes}
