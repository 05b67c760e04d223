"""Operations and bytes of the compressed attention's mixing kernels, from
their shapes.

``flash_cost.py``'s kind of count (what the algorithm needs for one call, not
what a particular kernel happens to do) for the sequence mixing of
``tepdist_tpu/ops/pallas/cca_mix.py``: over ``rows = batch x positions`` rows
of ``N`` heads of ``D`` channels a depth-wise conv of 2 taps, a conv of 2
taps that mixes a head's channels (a ``[D, D]`` matrix a tap and head) and
the q-k mean.

* a head and row costs the forward its two ``[D] x [D, D]`` products, ``2 x
  2 D D`` operations; the backward twice that (the input's gradient through
  the transposed matrices, and the matrices' gradients as the sum over rows
  of two outer products). The depth-wise conv, the mean and their transposes
  are a dozen element-wise operations a channel beside ``4 D`` and ``8 D`` and
  are not counted;
* the forward reads the latents and writes the result once; the backward reads
  the latents and the cotangent and writes the latents' gradient once; the
  weights (two ``[D, D]`` matrices a head in the activations' dtype, the two
  taps and two biases a channel in float32) cross once a head, and so do the
  float32 sums of their gradients.
"""

from __future__ import annotations

from benchmark.kernels.flash_cost import roofline_seconds  # noqa: F401


def _weights(N: int, D: int, dtype_bytes: int) -> float:
    """Bytes of W2 and of the per-channel w1 [2], b1, b2 (float32)."""
    return N * (2.0 * D * D * dtype_bytes + 4 * D * 4.0)


def forward(rows: int, N: int, D: int, dtype_bytes: int = 2) -> dict:
    """``u`` in and the result out once, the weights once a head."""
    return {"ops": 2.0 * 2 * D * D * rows * N,
            "bytes": 2.0 * rows * N * D * dtype_bytes
            + _weights(N, D, dtype_bytes)}


def backward(rows: int, N: int, D: int, dtype_bytes: int = 2) -> dict:
    """``u`` and the cotangent in and ``du`` out once, the weights in and
    the float32 sums of their gradients out once a head."""
    return {"ops": 2.0 * forward(rows, N, D, dtype_bytes)["ops"],
            "bytes": 3.0 * rows * N * D * dtype_bytes
            + _weights(N, D, dtype_bytes) + N * (2.0 * D * D + 4 * D) * 4.0}
