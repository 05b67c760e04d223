"""Nemotron-H (NVIDIA ``NVIDIA-Nemotron-3-Nano-30B-A3B``, ``model_type:
nemotron_h``: 52 layers, hidden 2688, ``hybrid_override_pattern``
``MEMEM*EMEMEM*E...``: 23 Mamba-2 layers ``M``, 23 expert layers ``E`` of 128
routed squared-relu experts of 1856, 6 a token, beside one shared expert of
3712, and 6 attention layers ``*`` of 32 query heads over 2 of 128 with no
positional embedding; vocabulary 131,072, untied).

**A layer is one part alone**: ``x = x + part(rms(x; w))``, one plain-gain
RMSNorm and one residual a layer, no layer both a mixer and a feed-forward
part. With ``a = rms(x; w)``:

``M``, **Mamba-2** (``H`` = 64 heads of ``P`` = 64 channels, ``G`` = 8 groups
of ``N`` = 128 states; head ``h`` reads group ``h // 8``):

    z, xBC, dt = a Wz, a Wxbc, a Wdt       the published in_proj's columns
    [u | B | C] = silu(conv4(xBC) + b)     one depth-wise causal conv over the
                                           joined 6144 channels, with a bias
    Delta_h = softplus(dt_h + dt_bias_h)   float32      A_h = -exp(A_log_h)
    S_t = exp(Delta_t A) S_{t-1} + Delta_t u_t B_t^T    S [64, 128] float32
    y_t = S_t C_t + D_h u_t                (``ops/pallas/ssd_attention.py``)
    r   = y * silu(z)                      the gate BEFORE the norm
    out = (r / rms_group(r) * g) Wout      rms over each group's 512 channels

``*``, **attention**: ``models/layers.py:gqa_heads`` (no rotary, no QK-norm),
through ``Wo``. ``E``, **experts**: Trinity's router (``models/afmoe.py:
router``: ``top_6(sigmoid(h Wr) + b)``, weights from the unbiased scores,
normalised over the chosen, times ``routed_scaling_factor``; the selection
bias ``b`` has no gradient and takes the step's counts where one would be)
over the experts held here (``experts_held``), ``expert(h) = relu(h Wup)^2
Wdown`` with **no gate matrix** (``ops/grouped_matmul.py:routed_experts`` on
two stacks), plus the shared expert of the same form, ungated. ``x0 =
tok_emb[tokens]``, a final RMSNorm, an untied head, the cross entropy alone.

The mixer is :func:`mamba2`, which takes the heads whose leaves it is handed:
every head here (:func:`mamba`), a rank's share of them in a model whose
mixers are divided over the ranks of a layer (``models/granite_hybrid.py``).

bf16 weights, activations and residual stream; norms, ``Delta``, the state,
the router's sigmoid and the loss in float32. Parameters: ``l{i}`` per-layer
dicts (``init_params``), or **the layers in units** a walk takes as its
layers (``models/decoder.py:units``: the layers up to and including the next
``E``: ``MEMEM*EME`` is ``ME ME M*E ME``, three walks where the layers alone
are nine), a unit's parts side by side in one dict (every leaf's name says
its part) and **a stack a run of equal units** (``stacked_init_params``:
``run{r}``), each walked with ``models/layers.py:scan_blocks`` in the
published order. ``loss_fn`` takes either layout.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from tepdist_tpu.models import afmoe, decoder
from tepdist_tpu.models.decoder import (
    fake_batch,  # noqa: F401 (the model's, as every decoder's)
    held_weights,
    layer_dicts,
    run_stacks,
    stack_layers,
    walk_layers,
)
from tepdist_tpu.models.layers import (
    cross_entropy,
    gqa_heads,
    part,
    rms_norm,
    scaled_by_head,
)
from tepdist_tpu.ops.grouped_matmul import routed_experts
from tepdist_tpu.ops.pallas.causal_conv import causal_conv
from tepdist_tpu.ops.pallas.ssd_attention import CHUNK, ssd_attention
from tepdist_tpu.telemetry import traced

traced.declare(
    "ssd_state_bytes", "bytes of the float32 state [heads, channels, states] "
    "one Mamba-2 layer leaves a sequence: what a stage hands on or a decode "
    "keeps")

MAMBA, EXPERTS, ATTN = "M", "E", "*"
# An expert of two matrices: what ``walk_layers`` hands the kernels in place.
EXPERT_LEAVES = ("w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = \
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    moe_intermediate_size: int = 1856    # one routed expert's width
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128          # the router's width
    experts_held: Tuple[int, int] = (0, 128)   # (first, count) held here
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Flash attention tile sizes (0 = kernel default), the state-space
    # kernels' chunk, every unit rematerialised in the backward pass
    # (layers.scan_blocks) and the loss chunk: gpt2.GPT2Config's vocabulary.
    flash_block_q: int = 0
    flash_block_k: int = 0
    ssd_chunk: int = CHUNK
    remat: bool = False
    loss_chunk: int = 0
    # Rows of a grouped-matmul tile; every expert's rows are padded to it.
    moe_tile_m: int = 128

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Every layer's part, in order."""
        return tuple(self.hybrid_override_pattern)

    @property
    def units(self) -> Tuple[str, ...]:
        """The layers as the stacked layout walks them."""
        return decoder.units(self.kinds, EXPERTS)

    @property
    def num_hidden_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    # What the shared router and the held-share code read
    # (``models/afmoe.py:router``, ``models/decoder.py``).
    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def route_scale(self) -> float:
        return self.routed_scaling_factor


CONFIGS: Dict[str, NemotronHConfig] = {
    "3-nano-30b-a3b": NemotronHConfig(),
    # The published pattern's first nine layers small: four Mamba-2, four
    # expert layers and the attention layer (units ME ME M*E ME), two heads
    # a group, 16 query heads a key/value head, 6 experts a token, a rank's
    # 8 of 32 experts.
    "test": NemotronHConfig(
        vocab_size=512, hidden_size=64, hybrid_override_pattern="MEMEM*EME",
        mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
        num_attention_heads=16, num_key_value_heads=1, head_dim=8,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
        n_routed_experts=32, experts_held=(8, 8), dtype=jnp.float32,
        ssd_chunk=16, moe_tile_m=8),
}
CONFIGS["test_bf16"] = dataclasses.replace(CONFIGS["test"],
                                           dtype=jnp.bfloat16)
# Small around the published head widths (Mamba-2 heads of 64, two a lane
# block, over states of 128; attention heads of 128, which the kernels compile
# for on the chip): ``chip_smoke.py``'s.
CONFIGS["smoke"] = dataclasses.replace(
    CONFIGS["test"], vocab_size=2048, hidden_size=256, mamba_num_heads=8,
    mamba_head_dim=64, n_groups=2, ssm_state_size=128, num_attention_heads=2,
    num_key_value_heads=1, head_dim=128, moe_intermediate_size=128,
    moe_shared_expert_intermediate_size=256, dtype=jnp.bfloat16,
    ssd_chunk=CHUNK, remat=True, loss_chunk=256, moe_tile_m=64)

_OUTSIDE_BLOCKS = ("tok_emb", "norm_f", "lm_head")
GROUPS = ("run",)


def _part_params(cfg: NemotronHConfig, kind: str, keys, norm):
    """One layer's leaves. A leaf's name says its part: the parts of a unit
    lie side by side in one dict."""
    d = cfg.hidden_size
    f32 = jnp.float32
    if kind == ATTN:
        H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        return {"attn_ln": jnp.ones((d,), f32),
                "wq": norm(keys[0], (d, H * hd)),
                "wk": norm(keys[1], (d, Hkv * hd)),
                "wv": norm(keys[2], (d, Hkv * hd)),
                "wo": norm(keys[3], (H * hd, d))}
    if kind == EXPERTS:
        f, fs = cfg.moe_intermediate_size, \
            cfg.moe_shared_expert_intermediate_size
        E, G = cfg.n_routed_experts, cfg.experts_held[1]
        return {"moe_ln": jnp.ones((d,), f32),
                "router": norm(keys[0], (d, E)),
                "router_bias": jnp.zeros((E,), f32),
                "shared_up": norm(keys[1], (d, fs)),
                "shared_down": norm(keys[2], (fs, d)),
                "w_up": norm(keys[3], (G, d, f)),
                "w_down": norm(keys[4], (G, f, d))}
    H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    wide = H * P + 2 * cfg.n_groups * cfg.ssm_state_size
    # As the published module starts them: A = 1 .. H, D = 1, and the step's
    # bias the inverse softplus of exp(U(log min, log max)), floored.
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        keys[5], (H,), f32, jnp.log(cfg.time_step_min),
        jnp.log(cfg.time_step_max))), cfg.time_step_floor)
    return {"ssm_ln": jnp.ones((d,), f32),
            "w_z": norm(keys[0], (d, H * P)),
            "w_xbc": norm(keys[1], (d, wide)),
            "w_dt": norm(keys[2], (d, H)),
            "conv": jax.random.uniform(
                keys[3], (cfg.conv_kernel, wide), f32, -0.5, 0.5).astype(
                    cfg.dtype),
            "conv_b": norm(keys[6], (wide,)),
            "A_log": jnp.log(jnp.arange(1, H + 1, dtype=f32)),
            "D": jnp.ones((H,), f32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "ssm_norm": jnp.ones((H * P,), f32),
            "w_out": norm(keys[4], (H * P, d))}


def init_params(cfg: NemotronHConfig, key, std: float = 0.02):
    """normal(std) matrices, conv taps U(-1/2, 1/2) (a conv of fan-in 4 as
    the published module starts it), unit norm gains, zero selection bias,
    ``A_log``, ``D`` and ``dt_bias`` as above; ``l{i}`` per-layer dicts."""
    d = cfg.hidden_size
    keys = jax.random.split(key, 2 + cfg.num_hidden_layers)

    def norm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            cfg.dtype)

    params: Dict[str, Any] = {
        "tok_emb": norm(keys[0], (cfg.vocab_size, d)),
        "norm_f": jnp.ones((d,), jnp.float32),
        "lm_head": norm(keys[1], (cfg.vocab_size, d)),
    }
    for i, kind in enumerate(cfg.kinds):
        params[f"l{i}"] = _part_params(
            cfg, kind, jax.random.split(keys[2 + i], 7), norm)
    return params


def in_units(params, cfg: NemotronHConfig):
    """``l{i}`` a layer -> ``l{i}`` a unit: a unit's layers' leaves side by
    side in one dict."""
    out = {k: params[k] for k in _OUTSIDE_BLOCKS}
    layer = 0
    for n, unit in enumerate(cfg.units):
        out[f"l{n}"] = {k: v for i in range(layer, layer + len(unit))
                        for k, v in params[f"l{i}"].items()}
        layer += len(unit)
    return out


def stacked_init_params(cfg: NemotronHConfig, key, std: float = 0.02):
    """``init_params`` in units, each run of equal units stacked, [units of
    the run, ...] a leaf, under ``run{r}``."""
    return stack_layers(in_units(init_params(cfg, key, std), cfg),
                        run_stacks(cfg.units), _OUTSIDE_BLOCKS, GROUPS)


def rank_share(params, cfg: NemotronHConfig, experts_held: Tuple[int, int]):
    """From the ``l{i}`` parameters of ``cfg`` (which holds every expert)
    what a rank holding ``experts_held`` has of them, and that rank's
    configuration: the held experts' weights and everything else (mixers,
    router, the shared expert) whole."""
    first, count = experts_held
    out = {k: params[k] for k in _OUTSIDE_BLOCKS}
    for i in range(cfg.num_hidden_layers):
        out[f"l{i}"] = {
            k: v[first:first + count] if k in EXPERT_LEAVES else v
            for k, v in params[f"l{i}"].items()}
    return out, dataclasses.replace(cfg, experts_held=tuple(experts_held))


def gated_group_norm(y, z, gain, groups: int, eps: float, axis_name=None):
    """``rms_group(y * silu(z)) * gain``: the gate before the norm, the norm
    over each of the ``groups`` groups of channels, one gain a channel;
    float32 inside, back in y's dtype.

    ``axis_name``: the mapped axis (``jax.vmap(..., axis_name=)``,
    ``shard_map``) over whose ranks the one group's channels are divided, a
    rank holding ``y``'s (a mixer that holds a share of the heads of a model
    with one group, ``models/granite_hybrid.py``): the sum of squares goes
    through ``lax.psum`` over it and the mean is over every rank's channels,
    the one number a token that a divided mixer exchanges before its output
    projection. None: the channels that are here."""
    r = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    if axis_name is None:
        scaled = scaled_by_head(r, groups, eps, mean=True)
    else:
        if groups != 1:
            raise ValueError("a gated norm divided over ranks has one group")
        total = jax.lax.psum(jnp.sum(r * r, axis=-1, keepdims=True),
                             axis_name)
        scaled = r * jax.lax.rsqrt(
            total / jax.lax.psum(r.shape[-1], axis_name) + eps)
    return (scaled * gain.astype(jnp.float32)).astype(y.dtype)


def mamba2(blk, a, *, heads: int, head_dim: int, groups: int, states: int,
           chunk: int, eps: float, axis_name=None):
    """a [B, T, d] (the normed input) -> the Mamba-2 mixer's output through
    ``w_out``, for the ``heads`` heads whose leaves ``blk`` holds: a whole
    mixer (Nemotron-H's), or the share of the heads a rank of a divided
    mixer holds (``models/granite_hybrid.py``: the held heads' columns of
    ``w_z``, ``w_dt`` and of ``w_xbc``'s ``u``, every group's ``B`` and
    ``C``, the held rows of ``w_out``; what comes out is that rank's partial
    sum). ``axis_name``: :func:`gated_group_norm`'s."""
    B, T, _ = a.shape
    H, P, G, N = heads, head_dim, groups, states
    traced.note("ssd_state_bytes", B * H * P * N * 4)
    with jax.named_scope("ssd_in"):
        z, xbc = a @ blk["w_z"], a @ blk["w_xbc"]
        delta = jax.nn.softplus(
            jnp.dot(a, blk["w_dt"], preferred_element_type=jnp.float32)
            + blk["dt_bias"])
    with jax.named_scope("ssd_conv"):
        xbc = causal_conv(xbc, blk["conv"], blk["conv_b"])
    with jax.named_scope("ssd_rule"):
        y = ssd_attention(
            xbc[..., :H * P], xbc[..., H * P:H * P + G * N],
            xbc[..., H * P + G * N:], delta,
            -jnp.exp(blk["A_log"].astype(jnp.float32)), blk["D"], groups=G,
            chunk=chunk)
    with jax.named_scope("ssd_norm_out"):
        return gated_group_norm(y, z, blk["ssm_norm"], G, eps,
                                axis_name) @ blk["w_out"]


def mamba(blk, a, cfg: NemotronHConfig):
    """a [B, T, d] (the normed input) -> the Mamba-2 mixer's output through
    ``w_out``: every head, whole on every rank."""
    return mamba2(blk, a, heads=cfg.mamba_num_heads,
                  head_dim=cfg.mamba_head_dim, groups=cfg.n_groups,
                  states=cfg.ssm_state_size, chunk=cfg.ssd_chunk,
                  eps=cfg.layer_norm_epsilon)


def attention(blk, a, cfg: NemotronHConfig):
    """a [B, T, d] (the normed input) -> the heads through ``wo``: causal,
    no positional embedding."""
    o = gqa_heads(
        blk, a, n_head=cfg.num_attention_heads,
        n_kv_head=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        eps=cfg.layer_norm_epsilon, window=0, windowed=False,
        block_q=cfg.flash_block_q, block_k=cfg.flash_block_k)
    with jax.named_scope("attn_out"):
        return o @ blk["wo"]


def relu2_mlp(h, w_up, w_down):
    """``relu(h w_up)^2 w_down``: an expert with no gate matrix, dense."""
    u = jnp.maximum((h @ w_up).astype(jnp.float32), 0.0)
    return (u * u).astype(h.dtype) @ w_down


def moe(blk, x, cfg: NemotronHConfig):
    """x [B, T, d] -> the held routed experts' part of the layer's output
    plus the shared expert's."""
    B, T, d = x.shape
    h = x.reshape(B * T, d)
    with jax.named_scope("moe_router"):
        _, weights, experts = afmoe.router(blk, h, cfg)
        weights = held_weights(weights, experts, cfg.experts_held,
                               cfg.num_experts)
    y = routed_experts(h, weights, experts, None, blk["w_up"], blk["w_down"],
                       cfg.num_experts, cfg.moe_tile_m, held=cfg.experts_held)
    with jax.named_scope("moe_shared"):
        y = relu2_mlp(h, blk["shared_up"], blk["shared_down"]) + y
    return y.reshape(B, T, d)


# kind -> (the step's part, the layer's norm, the part's function)
_PARTS = {MAMBA: ("mixer", "ssm_ln", mamba),
          ATTN: ("mixer", "attn_ln", attention),
          EXPERTS: ("moe", "moe_ln", moe)}


def block(blk, x, cfg: NemotronHConfig, kinds: str):
    """The layers of ``kinds`` (one letter: a layer; several: a unit, whose
    leaves ``blk`` holds side by side), each ``x + part(rms(x))``."""
    for kind in kinds:
        scope, ln, fn = _PARTS[kind]
        with part(scope):
            x = x + fn(blk, rms_norm(x, blk[ln], cfg.layer_norm_epsilon), cfg)
    return x


def _rows(params, cfg: NemotronHConfig):
    """What each ``l{i}`` (a layer, or a unit: ``in_units``) or each stacked
    unit holds, read from the leaves' names: a layer's dict holds one part's
    norm, a unit's one a layer (``ssm_ln`` beside ``moe_ln``)."""
    norms = {ln for _, ln, _ in _PARTS.values()}
    in_layers = "l0" in params and all(
        len(norms & params[f"l{i}"].keys()) == 1
        for i in range(len(cfg.units)))
    return cfg.kinds if in_layers else cfg.units


def hidden_states(params, tokens, cfg: NemotronHConfig):
    """tokens int32 [B, T] -> final normalised hidden [B, T, d]."""
    with part("embed"):
        x = params["tok_emb"][tokens].astype(cfg.dtype)
    x = walk_layers(lambda blk, h, kinds: block(blk, h, cfg, kinds), x,
                    params, run_stacks(cfg.units), _rows(params, cfg),
                    cfg.remat, GROUPS, experts=EXPERT_LEAVES)
    with part("head_loss"):
        return rms_norm(x, params["norm_f"], cfg.layer_norm_epsilon)


def forward(params, tokens, cfg: NemotronHConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    x = hidden_states(params, tokens, cfg)
    return (x @ params["lm_head"].T).astype(jnp.float32)


def loss_fn(params, tokens, cfg: NemotronHConfig):
    """Cross entropy of tokens [B, T+1]; the router's bias receives its
    step's counts where its gradient would be (``afmoe.count_choices``)."""
    x = hidden_states(params, tokens[:, :-1], cfg)
    return cross_entropy(x, params["lm_head"], tokens[:, 1:], cfg.loss_chunk)


def expert_choices(params, tokens, cfg: NemotronHConfig):
    """tokens int32 [B, T] -> the expert ids every expert layer's router
    chose, int32 [expert layers, B * T, k]; the forward pass alone, no host
    value in it (it can be jitted)."""
    x = params["tok_emb"][tokens].astype(cfg.dtype)
    S = x.shape[0] * x.shape[1]
    ids = []
    rows = _rows(params, cfg)
    blocks = layer_dicts(params, run_stacks(cfg.units), GROUPS) \
        if "l0" not in params else [params[f"l{i}"] for i in range(len(rows))]
    for blk, kinds in zip(blocks, rows):
        for kind in kinds:
            if kind == EXPERTS:
                h = rms_norm(x, blk["moe_ln"], cfg.layer_norm_epsilon)
                ids.append(afmoe.router(blk, h.reshape(S, -1), cfg)[2])
            x = block(blk, x, cfg, kind)
    return jnp.stack(ids)


# What the routers did with ``tokens`` [B, T+1], outside any step
# (``models/decoder.py:routing_stats`` over this model's choices): the rows
# each held expert got and the live share of the tiles laid out.
routing_stats = functools.partial(decoder.routing_stats, expert_choices)
