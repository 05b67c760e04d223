"""Operations and bytes of attention over key blocks chosen per query token
(InfLLM-v2's sparse attention, the kernels alone), from its shapes.

For ``T`` query tokens of ``H`` heads of ``D`` channels over ``G`` key/value
heads, each (token, group) attending to the keys at or before it in ``K``
chosen blocks of ``block_size`` keys, the set sorted so that the query's own
block is its last:

* the keys a query visits are a function of the geometry alone
  (:func:`keys_visited`): query ``t`` has ``min(K, t // block_size + 1)``
  blocks, all of each but its own, which it sees up to itself; the sum over
  ``t`` is 124.9e6 at 32,768 tokens, 64 blocks of 64 (3,812 a query, 23%
  of plain causal attention's 16,384.5);
* operations, two a multiply-add, over the visited (query, key) pairs of
  every head: forward the scores and the values' sum, ``4 D`` a pair;
  backward the scores again, ``d p``, ``d q``, ``d k`` and ``d v``, ``10 D``;
* bytes, every operand and result across HBM once: forward ``q`` in and
  ``o`` out ``[T, H, D]``, ``k``, ``v`` ``[T, G, D]`` in, the sets ``[T, G,
  K]`` int32 in and the log-sum-exp ``[T, H]`` float32 out; backward ``q``,
  ``o``, ``d o`` in and ``d q`` out, ``k``, ``v`` in and their gradients
  out, the sets and the log-sum-exp in. **A chosen block is counted once,
  not once a query that chose it**: the issue that asked for the kernels
  reckoned a gather a query (2 MB a token and group, 0.16 s a call at HBM's
  peak), which is one mechanism's traffic; the program's keeps a group's
  keys and values in VMEM for a sweep, and an operation's least time cannot
  hold bytes that an implementation need not move. Counted so, the
  operation is bound by the matrix unit (forward 2.05e12 operations, 10.4
  ms, against 0.6e9 bytes, 0.7 ms), and a kernel that brings 16 rows a
  query to it reads well under 100.

The choice itself (compressed keys, the scoring softmax, the top-k) is not
in these counts: it runs outside the kernels.
"""

from __future__ import annotations

from benchmark.kernels.flash_cost import roofline_seconds  # noqa: F401


def keys_visited(T: int, block_size: int, K: int) -> int:
    """Sum over queries ``t < T`` of the keys ``t`` visits."""
    total = 0
    for first in range(0, T, block_size):
        blocks = min(K, first // block_size + 1)
        # Each of the block's queries: whole blocks but its own, then 1..bs.
        total += block_size * (blocks - 1) * block_size \
            + block_size * (block_size + 1) // 2
    return total


def _narrow(T, H, G, D, K, act_bytes):
    """``k`` and ``v``, the sets and the log-sum-exp."""
    return 2.0 * T * G * D * act_bytes + 4.0 * T * G * K + 4.0 * T * H


def forward(T: int, H: int, G: int, D: int, block_size: int, K: int,
            act_bytes: int = 2) -> dict:
    return {"ops": 4.0 * D * H * keys_visited(T, block_size, K),
            "bytes": 2.0 * T * H * D * act_bytes
            + _narrow(T, H, G, D, K, act_bytes)}


def backward(T: int, H: int, G: int, D: int, block_size: int, K: int,
             act_bytes: int = 2) -> dict:
    return {"ops": 10.0 * D * H * keys_visited(T, block_size, K),
            "bytes": 4.0 * T * H * D * act_bytes
            + _narrow(T, H, G, D, K, act_bytes) + 2.0 * T * G * D * act_bytes}
