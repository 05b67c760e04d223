"""Qwen3-Next family (Qwen Qwen3-Next-80B-A3B): weights from the seed, and the
hand-over to the program.

As ``builders/kimi_linear.py``: ``make_params`` draws the weights on the
device in one jitted call, from the seed alone, in the dtype they are trained
in and in the layout the reference reads (``reference/qwen3_next.py``: a
stack a run of consecutive layers of one kind, ``run{r}``), which is also the
program's, so ``to_program`` hands the same tree on. The rest of this file is
the only place where the benchmark touches the program's model code:
building its ``Qwen3NextConfig`` from the configuration file, its loss
function and its optimizer. The program's model is imported with this file,
so that a program without it is refused before any weight is drawn.

The configuration file holds the published ``config.json`` keys at its top
level and is read under those names. ``num_experts`` there counts the experts
**held on this chip** (``experts_held_first`` says from which on) and
``router_num_experts`` is the router's published width; layer ``i`` (from 0)
is full attention where ``(i + 1) % full_attention_interval == 0``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.builders.gpt2 import (  # noqa: F401 — the builder interface
    DTYPES,
    _key,
    _seed_words,
    _tokens,
    program_optimizer,
)
from benchmark.lib.cells import BenchError

try:
    from tepdist_tpu.models import qwen3_next as program
except ImportError as e:
    # A program from before the model (the parent of the PR that brought
    # it): say so at once, before weights are drawn or anything compiles.
    raise BenchError("the program under test has no tepdist_tpu.models."
                     "qwen3_next: it cannot run a Qwen3-Next "
                     "configuration") from e

GDN, ATTN = program.GDN, program.ATTN


def mixers(config: dict) -> tuple:
    """The held layers' mixers in order."""
    n = config["full_attention_interval"]
    return tuple(ATTN if (i + 1) % n == 0 else GDN
                 for i in range(config["num_hidden_layers"]))


def runs(config: dict) -> list:
    """(mixer, layers) of each run of consecutive layers of one kind, in the
    model's order."""
    out = []
    for mixer in mixers(config):
        if out and out[-1][0] == mixer:
            out[-1][1] += 1
        else:
            out.append([mixer, 1])
    return [(mixer, n) for mixer, n in out]


def model_sizes(config: dict) -> dict:
    if config["linear_key_head_dim"] != config["linear_value_head_dim"] \
            or config["decoder_sparse_step"] != 1 \
            or config["mlp_only_layers"] or not config["norm_topk_prob"] \
            or config.get("rope_scaling") is not None:
        raise BenchError(
            "an expert layer in every layer (decoder_sparse_step 1, no "
            "mlp_only_layers), the chosen weights normalised, key and value "
            "heads of one width and a plain rotary table are what is built "
            "here")
    return {"V": config["vocab_size"], "d": config["hidden_size"],
            "f": config["moe_intermediate_size"],
            "fs": config["shared_expert_intermediate_size"],
            "L": config["num_hidden_layers"],
            "Hk": config["linear_num_key_heads"],
            "Hv": config["linear_num_value_heads"],
            "D": config["linear_key_head_dim"],
            "taps": config["linear_conv_kernel_dim"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "hd": config["head_dim"],
            "E": config["router_num_experts"], "G": config["num_experts"],
            "k": config["num_experts_per_tok"]}


def _mixer_shapes(s: dict, mixer: str) -> dict:
    """name -> shape of a mixer's matrices and conv taps."""
    d = s["d"]
    if mixer == ATTN:
        return {"wq": (d, s["H"] * s["hd"]), "wa": (d, s["H"] * s["hd"]),
                "wk": (d, s["Hkv"] * s["hd"]), "wv": (d, s["Hkv"] * s["hd"]),
                "wo": (s["H"] * s["hd"], d)}
    wide = (2 * s["Hk"] + s["Hv"]) * s["D"]
    return {"wqkv": (d, wide), "wz": (d, s["Hv"] * s["D"]),
            "wba": (d, 2 * s["Hv"]), "conv": (s["taps"], wide),
            "wo": (s["Hv"] * s["D"], d)}


def _expert_shapes(s: dict) -> dict:
    d, f, fs = s["d"], s["f"], s["fs"]
    return {"router": (d, s["E"]), "shared_gate": (d, fs),
            "shared_up": (d, fs), "shared_down": (fs, d),
            "shared_expert_gate": (d, 1),
            "w_gate": (s["G"], d, f), "w_up": (s["G"], d, f),
            "w_down": (s["G"], f, d)}


def _vectors(s: dict, mixer: str) -> int:
    """A layer's parameters that are no matrix: norm leaves, the decays'
    ``A_log`` and ``dt_bias``."""
    return 2 * s["d"] + (2 * s["hd"] if mixer == ATTN
                         else 2 * s["Hv"] + s["D"])


def num_params(config: dict) -> int:
    """Every weight resident on the chip."""
    s = model_sizes(config)
    total = 2 * s["V"] * s["d"] + s["d"]
    for mixer, n in runs(config):
        total += n * (_vectors(s, mixer) + sum(map(math.prod, (
            *_mixer_shapes(s, mixer).values(),
            *_expert_shapes(s).values()))))
    return total


def active_matmul_params(config: dict) -> int:
    """Parameters one token meets in a matmul HERE: a mixer's projections
    (the conv is no matmul), the router, the shared expert with its gate and
    the routed experts at what this chip expects of a token's k (its share
    G / E of them: 1.25 of a choice of 10), and the head (the embedding is a
    lookup)."""
    s = model_sizes(config)
    d, f = s["d"], s["f"]
    total = s["V"] * d
    for mixer, n in runs(config):
        mix = sum(math.prod(shape) for name, shape in
                  _mixer_shapes(s, mixer).items() if name != "conv")
        total += n * (mix + d * s["E"] + d + 3 * d * s["fs"]
                      + 3 * d * f * s["k"] * s["G"] / s["E"])
    return int(total)


def make_params(config: dict, seed: int):
    """normal(0.02) matrices and conv taps, zero-centred norm leaves at 0,
    the gated norm's gain at 1, ``A_log = log U(1, 16)`` and ``dt_bias`` the
    inverse softplus of ``exp(U(log 1e-3, log 1e-1))`` a value head, drawn
    on the device; ``run{r}`` is one dict of ``[layers, ...]`` arrays."""
    s = model_sizes(config)
    dt = DTYPES[config["dtype"]]
    d = s["d"]
    f32 = jnp.float32
    held = runs(config)

    def make(lo, hi, stream):
        top = jax.random.split(_key(lo, hi, stream), 2 + len(held))

        def norm(k, shape):
            return (jax.random.normal(k, shape, f32) * 0.02).astype(dt)

        out = {"tok_emb": norm(top[0], (s["V"], d)),
               "norm_f": jnp.zeros((d,), f32),
               "lm_head": norm(top[1], (s["V"], d))}
        for r, (mixer, n) in enumerate(held):
            shapes = {**_mixer_shapes(s, mixer), **_expert_shapes(s)}
            ks = jax.random.split(top[2 + r], len(shapes) + 2)
            run = {"input_ln": jnp.zeros((n, d), f32),
                   "post_attn_ln": jnp.zeros((n, d), f32)}
            for k, (name, shape) in zip(ks, shapes.items()):
                run[name] = norm(k, (n,) + shape)
            if mixer == ATTN:
                run["q_norm"] = jnp.zeros((n, s["hd"]), f32)
                run["k_norm"] = jnp.zeros((n, s["hd"]), f32)
            else:
                step = jnp.exp(jax.random.uniform(
                    ks[-2], (n, s["Hv"]), f32, jnp.log(1e-3), jnp.log(1e-1)))
                run["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
                run["A_log"] = jnp.log(jax.random.uniform(
                    ks[-1], (n, s["Hv"]), f32, 1.0, 16.0))
                run["o_norm"] = jnp.ones((n, s["D"]), f32)
            out[f"run{r}"] = run
        return out

    return jax.jit(make)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets), drawn
    from the vocabulary's slice."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   config["vocab_size"])


def to_program(params: dict, config: dict) -> dict:
    """``tepdist_tpu.models.qwen3_next`` reads the same names."""
    return dict(params)


def program_config(config: dict):
    """The program's ``Qwen3NextConfig`` at this configuration's sizes."""
    p, s = config["program"], model_sizes(config)
    return program.Qwen3NextConfig(
        vocab_size=s["V"], hidden_size=s["d"], num_hidden_layers=s["L"],
        full_attention_interval=config["full_attention_interval"],
        linear_num_key_heads=s["Hk"], linear_num_value_heads=s["Hv"],
        linear_key_head_dim=s["D"], linear_conv_kernel_dim=s["taps"],
        num_attention_heads=s["H"], num_key_value_heads=s["Hkv"],
        head_dim=s["hd"],
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        moe_intermediate_size=s["f"],
        shared_expert_intermediate_size=s["fs"], num_experts=s["E"],
        experts_held=(int(config["experts_held_first"]), s["G"]),
        num_experts_per_tok=s["k"],
        rms_norm_eps=float(config["rms_norm_eps"]),
        dtype=DTYPES[config["dtype"]],
        flash_block_q=int(p.get("flash_block_q", 0)),
        flash_block_k=int(p.get("flash_block_k", 0)),
        gdn_chunk=int(p["gdn_chunk"]),
        remat=bool(p.get("remat")),
        loss_chunk=int(p.get("loss_chunk", 0)),
        moe_tile_m=int(p["moe_tile_m"]))


def program_loss_fn(config: dict):
    """``loss(params, tokens)`` of the program under test."""
    cfg = program_config(config)
    return lambda p, t: program.loss_fn(p, t, cfg)


def reference_hyper(config: dict):
    from benchmark.reference import qwen3_next as ref
    s = model_sizes(config)
    return ref.Hyper(
        key_heads=s["Hk"], value_heads=s["Hv"], n_head=s["H"],
        n_kv_head=s["Hkv"],
        rotary_dim=int(s["hd"] * float(config["partial_rotary_factor"])),
        top_k=s["k"], held=(int(config["experts_held_first"]), s["G"]),
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]))


# -- what the checks compare ------------------------------------------------

# The leaves outside the layers: every layer's error, the routers' choices
# among them, reaches the embedding, and the loss's the head and the final
# norm, so their gradients stand for the whole step.
PROBE = ("tok_emb", "lm_head", "norm_f")


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/qwen3_next.py``,
    in float32, ``chunk`` sequences at a time. ``cast`` swaps in the
    control's precision."""
    from benchmark.reference import qwen3_next as ref
    hp = reference_hyper(config)
    cast = cast or ref.identity

    @jax.jit
    def part(params, probe, tokens, weights):
        return jax.value_and_grad(lambda pr: ref.loss(
            {**params, **pr}, tokens, hp, cast, weights))(probe)

    def run(params, tokens, weights):
        if tokens.shape[0] % chunk:
            raise ValueError(f"{tokens.shape[0]} sequences do not split "
                             f"into chunks of {chunk}")
        probe = {k: params[k].astype(jnp.float32) for k in PROBE}
        weights = jnp.asarray(weights, jnp.float32)
        loss, grads = 0.0, None
        for i in range(0, tokens.shape[0], chunk):
            part_loss, g = part(params, probe, tokens[i:i + chunk],
                                weights[i:i + chunk])
            loss = loss + part_loss
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        return loss, grads
    return run


def train_facts(config: dict) -> dict:
    """``n_params`` is what ``run.py``'s MFU line multiplies by six: the
    parameters a token meets in a matmul on THIS chip (the routed experts at
    the expected 1.25 of a choice of its 10 that the held eighth gets), not
    the weights resident (``resident_params``) and not the whole model's.
    The delta rule's and the attention's own products are not in it."""
    return {"n_params": active_matmul_params(config),
            "resident_params": num_params(config)}
