"""Operations and bytes of a selective scan (Mamba-1's recurrence), from its
shapes.

What the operation needs for one call, whatever implements it: for ``T``
tokens of ``Di`` channels with ``N`` states a channel,

    h_t = exp(delta_t A) * h_{t-1} + (delta_t * c_t) B_t^T
    out_t = (h_t C_t + D * c_t) * silu(z_t)

* bytes, every operand and result across HBM once. Forward: ``c``, ``z`` in
  and ``out`` out at the activations' width, ``delta`` in at its own (float32
  in this program), ``B``, ``C`` ``[T, N]``, ``A`` ``[Di, N]`` and ``D``
  ``[Di]`` in float32. Backward: those operands again, ``d out`` in, and the
  five wide gradients out (``c``, ``delta``, ``z`` ``[T, Di]``; ``B``, ``C``
  ``[T, N]``), with ``A``'s and ``D``'s. **Not counted**, because they are
  the implementation's: the states at chunk boundaries, a saved pre-gate
  ``y``, ``B`` and ``C`` laid out wider than ``[T, N]``. A kernel that fuses
  the gate or the conv in, or moves fewer bytes some other way, is still
  held to this count, so its share cannot pass 100% by that.
* operations, a stated count a state element (one channel's one state at one
  token), an ``exp`` counted as one: forward ``FWD_OPS`` = 7 (``delta * A``,
  its ``exp``, the decay times the state, the input times ``B``, their sum,
  the state times ``C``, and its sum into ``y``); backward ``BWD_OPS`` = 17
  (into the state's gradient 2, ``C``'s gradient 2, the decay again 2 and
  the decay's gradient 2, into ``delta``'s 2, ``A``'s 2, the input's 2,
  ``B``'s 2, and the gradient carried to the step before 1). The states a
  chunked backward computes again are the implementation's and not counted.

The roofline time of a call is the larger of operations over the peak rate
and bytes over the peak bandwidth (``peaks.json``: the matrix unit's bf16
peak and HBM; it has no vector-unit peak); ``bound`` says which. By those
peaks the scan is bound by HBM: forward 10 bytes a channel and token against
16 x 7 operations, 51e3 bytes (62 ns) against 0.57e6 operations (2.9 ns) a
token at 5120 channels.
"""

from __future__ import annotations

from benchmark.kernels.flash_cost import roofline_seconds  # noqa: F401

FWD_OPS, BWD_OPS = 7, 17


def _narrow(T: int, Di: int, N: int, act_bytes: int) -> float:
    """``B`` and ``C`` at the activations' width; ``A`` and ``D`` float32."""
    return 2.0 * T * N * act_bytes + 4.0 * (Di * N + Di)


def forward(T: int, Di: int, N: int, act_bytes: int = 2,
            delta_bytes: int = 4) -> dict:
    return {"ops": float(FWD_OPS) * T * Di * N,
            "bytes": T * Di * (3.0 * act_bytes + delta_bytes)
            + _narrow(T, Di, N, act_bytes)}


def backward(T: int, Di: int, N: int, act_bytes: int = 2,
             delta_bytes: int = 4) -> dict:
    return {"ops": float(BWD_OPS) * T * Di * N,
            "bytes": T * Di * (5.0 * act_bytes + 2.0 * delta_bytes)
            + 2.0 * _narrow(T, Di, N, act_bytes)}
