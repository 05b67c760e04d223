"""Plain float32 Nemotron-H (NVIDIA ``NVIDIA-Nemotron-3-Nano-30B-A3B``,
``model_type: nemotron_h``): forward, loss and gradients.

The yardstick the nemotron-3-nano-30b-a3b cell's ``correct`` is decided
against. Straight ``jax.numpy`` following the published ``config.json`` of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 and, for what its keys name and do
not spell out, Nemotron-H (arXiv:2504.03624), Mamba-2 (arXiv:2405.21060; the
``transformers`` ``mamba2`` module's ``torch_forward`` for the order of conv,
gate and norm) and DeepSeek-V3's router (arXiv:2412.19437); the configuration
file lists under ``assumed`` what none of them fixes. **A layer is one part
alone**, ``x = x + part(rms(x; w))`` with a plain-gain RMSNorm; its kind is
its letter in ``hybrid_override_pattern``. With ``a = rms(x; w)``:

``M``, Mamba-2 (``H`` heads of ``P`` channels, ``G`` groups of ``N`` states,
head ``h`` on group ``h // (H / G)``)

    z, xBC, dt = a Wz, a Wxbc, a Wdt
    [u | B | C] = silu(conv(xBC) + b)   one depth-wise causal conv over the
                        joined H P + 2 G N channels, taps [taps, C], rows
                        before the sequence zeros
    Delta_h = softplus(dt_h + dt_bias_h)        A_h = -exp(A_log_h)
    S_t = exp(Delta_t A) S_{t-1} + Delta_t u_t B_t^T        S [P, N], S_0 = 0
    y_t = S_t C_t + D_h u_t
    r   = y * silu(z)                   the gate before the norm
    out = (r / rms_group(r) * g) Wout   rms over each group's H P / G channels

``*``, attention (``Hq`` query heads over ``Hkv`` key/value heads of ``hd``
channels, no positional embedding, no QK-norm)

    q, k, v = a Wq, a Wk, a Wv
    o_h = softmax_causal(q_h k_j^T hd^-0.5) v_j           j = h // (Hq / Hkv)
    out = concat_h(o_h) Wo

``E``, experts

    s = sigmoid(a Wr);  e = top_k(s + b);  w = s[e] / (sum s[e] + 1e-20) * c
    out = sum_j w_j expert_{e_j}(a) + shared(a)     expert(a) = relu(a Wup)^2
                                                               Wdown

the final RMSNorm, the untied head and the cross entropy; no auxiliary loss.
No kernel, no chunked form, no sort, no layout, no grouped matmul, no import
from the program under test: **the state-space rule is the recurrence above
a token at a time** (one ``lax.scan`` step a token, all heads at once on a
float32 ``[H, P, N]`` state, the groups' ``B`` and ``C`` repeated to the
heads), the conv a sum of shifted copies, the group norm written out,
attention an explicit mask over explicit scores in blocks of queries, and
**every held expert is applied to every token**, its result multiplied by
the router's weight where the expert is among the token's top k, by zero
elsewhere.

Everything is computed in float32 with ``precision=highest``. Departures
from the published description:

* memory, not arithmetic: weights arrive in the dtype they are trained in
  and are widened where they are used; each layer, each block of queries and
  each expert is wrapped in ``jax.checkpoint``, queries are taken
  ``QUERY_BLOCK`` at a time (``lax.map``), the head with its loss
  ``TOKEN_BLOCK`` tokens at a time, experts are walked one at a time
  (``lax.scan``), and the recurrence's scan is checkpointed ``SCAN_BLOCK``
  tokens at a time;
* **the share of the experts**: the weights that come are the held experts'
  (``Hyper.held = (first, count)`` of the router's). What an expert
  elsewhere would add is left out, and that partial result goes on to the
  next layer, in the program alike. ``(0, E)`` is the uncut layer;
* ``Wz``, ``Wxbc`` and ``Wdt`` are the published ``in_proj``'s columns in
  their published order, cut where the module splits its result;
* ``cast`` is applied to both operands of every matmul, the router's and
  the recurrence's products with the state included, and to the conv's
  operands. The identity gives the reference; the control
  (``reference/gpt2.py:fp8_cast``) puts the reference in the program's
  place one precision step below bf16.

Parameter layout: ``tok_emb`` [V, d], ``norm_f`` [d], ``lm_head`` [V, d], and
the layers as ``layers``, a list of per-layer dicts, or in **units** as
``run0``, ``run1``, ...: a unit is a few consecutive layers whose leaves lie
side by side in one dict (a leaf's name says its part), ``Hyper.units`` names
each unit's layers (``("ME", "ME", "M*E", "ME")``), and a run of equal units
is one dict of leaves stacked on a leading axis, in the model's order.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
SCAN_BLOCK = 128


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes."""
    heads: int                           # Mamba-2's
    groups: int
    n_head: int                          # the attention layers'
    n_kv_head: int
    top_k: int
    held: Tuple[int, int]                # (first, count) of the router's E
    units: Tuple[str, ...]               # each unit's layers' kinds, in order
    route_scale: float = 2.5
    eps: float = 1e-5


def identity(x):
    return x


def _mm(a, b, cast):
    return jnp.matmul(cast(a.astype(F32)), cast(b.astype(F32)),
                      precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def conv_silu(u, taps, bias, cast=identity):
    """u [T, C], taps [n, C], bias [C] -> ``silu(bias + sum_j taps[j] * u[t
    - (n - 1) + j])``, rows before the sequence zeros."""
    n, T = taps.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((n - 1, u.shape[1]), F32),
                              cast(u.astype(F32))])
    taps = cast(taps.astype(F32))
    return jax.nn.silu(bias.astype(F32)
                       + sum(taps[j] * padded[j:j + T] for j in range(n)))


def recurrence(u, B, C, delta, A, D, cast=identity):
    """The state-space rule a token at a time: u [T, H, P], B, C [T, G, N],
    delta [T, H], A, D [H] -> y [T, H, P], float32; head ``h`` reads group
    ``h // (H / G)``. ``S`` [H, P, N] starts at 0."""
    T, H, P = u.shape
    G, N = B.shape[1:]
    B, C = (jnp.repeat(x.astype(F32), H // G, axis=1) for x in (B, C))
    A, D = A.astype(F32), D.astype(F32)

    def token(S, x):
        u, b, c, dl = x
        S = S * jnp.exp(dl * A)[:, None, None] \
            + cast(dl[:, None] * u)[:, :, None] * cast(b)[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", cast(S), cast(c),
                             precision=HIGHEST) + D[:, None] * u

    @jax.checkpoint
    def tokens(S, xs):
        return jax.lax.scan(token, S, xs)

    n = SCAN_BLOCK if T % SCAN_BLOCK == 0 else T
    xs = tuple(x.astype(F32).reshape(T // n, n, *x.shape[1:])
               for x in (u, B, C, delta))
    _, y = jax.lax.scan(tokens, jnp.zeros((H, P, N), F32), xs)
    return y.reshape(T, H, P)


def _mamba(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> the Mamba-2 mixer's
    output [T, d]."""
    T, H, G = a.shape[0], hp.heads, hp.groups
    inner = blk["w_z"].shape[-1]
    P = inner // H
    N = (blk["w_xbc"].shape[-1] - inner) // (2 * G)
    xbc = conv_silu(_mm(a, blk["w_xbc"], cast), blk["conv"], blk["conv_b"],
                    cast)
    delta = jax.nn.softplus(_mm(a, blk["w_dt"], cast)
                            + blk["dt_bias"].astype(F32))
    y = recurrence(xbc[:, :inner].reshape(T, H, P),
                   xbc[:, inner:inner + G * N].reshape(T, G, N),
                   xbc[:, inner + G * N:].reshape(T, G, N), delta,
                   -jnp.exp(blk["A_log"].astype(F32)), blk["D"], cast)
    r = (y.reshape(T, inner) * jax.nn.silu(_mm(a, blk["w_z"], cast))) \
        .reshape(T, G, inner // G)
    r = r * jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True) + hp.eps)
    return _mm(r.reshape(T, inner) * blk["ssm_norm"].astype(F32),
               blk["w_out"], cast)


def _attention(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> the attention's output
    [T, d]."""
    T = a.shape[0]
    H, Hkv = hp.n_head, hp.n_kv_head
    hd = blk["wk"].shape[-1] // Hkv

    def heads(t, n):
        return t.reshape(T, n, -1).transpose(1, 0, 2)

    q = heads(_mm(a, blk["wq"], cast), H)
    k = heads(_mm(a, blk["wk"], cast), Hkv)
    v = heads(_mm(a, blk["wv"], cast), Hkv)
    k, v = (jnp.repeat(t, H // Hkv, axis=0) for t in (k, v))
    qb = min(QUERY_BLOCK, T)
    if T % qb:
        raise ValueError(f"{T} positions do not split into blocks of {qb}")
    keys = jnp.arange(T)

    @jax.checkpoint
    def query_block(args):
        start, qs = args                                 # qs [H, qb, hd]
        seen = (start + jnp.arange(qb))[:, None] >= keys[None, :]
        s = jnp.einsum("hqd,hkd->hqk", cast(qs), cast(k),
                       precision=HIGHEST) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", cast(p), cast(v),
                          precision=HIGHEST)

    o = jax.lax.map(query_block, (
        jnp.arange(0, T, qb),
        q.reshape(H, T // qb, qb, hd).transpose(1, 0, 2, 3)))
    return _mm(o.transpose(0, 2, 1, 3).reshape(T, H * hd), blk["wo"], cast)


def _scores(blk, h, cast):
    return jax.nn.sigmoid(_mm(h, blk["router"], cast))


def router_scores(blk, x, hp: Hyper, cast=identity):
    """The residual stream x [T, d] before an expert layer -> its router's
    sigmoid scores [T, E], the selection bias not added."""
    return _scores(blk, _rms(x, blk["moe_ln"], hp.eps), cast)


def route(blk, h, hp: Hyper, cast):
    """h [T, d] -> (weights [T, k], expert ids [T, k]): the top k of the
    sigmoid scores plus the selection bias, the weights from the unbiased
    scores, normalised over the k chosen and scaled."""
    s = _scores(blk, h, cast)
    _, experts = jax.lax.top_k(s + blk["router_bias"].astype(F32), hp.top_k)
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    return chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) \
        * hp.route_scale, experts


def _relu2(h, w_up, w_down, cast):
    return _mm(jnp.square(jax.nn.relu(_mm(h, w_up, cast))), w_down, cast)


def _moe(blk, h, hp: Hyper, cast):
    """One sequence: h [T, d] -> (the held routed experts' part plus the
    shared expert's output [T, d], expert ids [T, k])."""
    E = blk["router"].shape[-1]
    first, count = hp.held
    weights, experts = route(blk, h, hp, cast)
    chosen = jax.nn.one_hot(experts, E, dtype=F32)                # [T, k, E]
    gate = jnp.sum(chosen * weights[..., None], axis=1)           # [T, E]
    gate = gate[:, first:first + count]      # an expert elsewhere: left out

    @jax.checkpoint
    def expert(w_up, w_down, g):
        return _relu2(h, w_up, w_down, cast) * g[:, None]

    def step(y, e):
        return y + expert(*e), None

    y, _ = jax.lax.scan(step, jnp.zeros_like(h),
                        (blk["w_up"], blk["w_down"], gate.T))
    return y + _relu2(h, blk["shared_up"], blk["shared_down"], cast), experts


def layer(blk, x, kind: str, hp: Hyper, cast=identity):
    """One layer of ``kind``: (the residual stream after it, its expert ids
    or None)."""
    if kind == "E":
        y, experts = _moe(blk, _rms(x, blk["moe_ln"], hp.eps), hp, cast)
        return x + y, experts
    if kind == "*":
        return x + _attention(blk, _rms(x, blk["attn_ln"], hp.eps), hp,
                              cast), None
    return x + _mamba(blk, _rms(x, blk["ssm_ln"], hp.eps), hp, cast), None


def layers_of(params, hp: Hyper) -> list:
    """(a dict that holds the layer's leaves, the layer's kind) of every
    layer in the model's order, whichever layout came."""
    kinds = "".join(hp.units)
    if "layers" in params:
        return list(zip(params["layers"], kinds))
    units, r = [], 0
    while f"run{r}" in params:
        stack = params[f"run{r}"]
        n = next(iter(stack.values())).shape[0]
        units += [{k: v[i] for k, v in stack.items()} for i in range(n)]
        r += 1
    return [(blk, kind) for blk, unit in zip(units, hp.units)
            for kind in unit]


def hidden(params, tokens, hp: Hyper, cast=identity):
    """One sequence: tokens int32 [T] -> (final normalised hidden [T, d],
    the expert layers' expert ids [expert layers, T, k])."""
    x = params["tok_emb"][tokens].astype(F32)
    chosen = []
    for blk, kind in layers_of(params, hp):
        x, experts = jax.checkpoint(
            lambda b, h, kind=kind: layer(b, h, kind, hp, cast))(blk, x)
        if experts is not None:
            chosen.append(experts)
    return _rms(x, params["norm_f"], hp.eps), jnp.stack(chosen)


def logits(params, tokens, hp: Hyper, cast=identity):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    return jnp.stack([_mm(hidden(params, t, hp, cast)[0],
                          params["lm_head"].T, cast) for t in tokens])


def loss(params, tokens, hp: Hyper, cast=identity, weights=None):
    """The training loss, the cross entropy alone, of tokens [B, T+1]: the
    mean over the batch of each sequence's own, or with ``weights`` [B] the
    sum weighted by them (a batch that repeats sequences is then computed
    from the distinct ones)."""
    B = tokens.shape[0]
    if weights is None:
        weights = jnp.full((B,), 1.0 / B, F32)

    def cross_entropy(x, targets):
        T = x.shape[0]
        tb = TOKEN_BLOCK if T % TOKEN_BLOCK == 0 else T

        @jax.checkpoint
        def part(args):          # never a [T, V] array
            xc, tc = args
            lg = _mm(xc, params["lm_head"].T, cast)
            gold = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

        return jnp.sum(jax.lax.map(part, (
            x.reshape(T // tb, tb, -1), targets.reshape(T // tb, tb)))) / T

    total = 0.0
    for i in range(B):
        x, _ = hidden(params, tokens[i, :-1], hp, cast)
        total = total + weights[i] * cross_entropy(x, tokens[i, 1:])
    return total
