"""Mellum2 family (JetBrains ``model_type: mellum``): weights from the
seed, and the hand-over to the program.

As ``builders/afmoe.py``: ``make_params`` draws the weights on the device in
one jitted call, from the seed alone, in the dtype they are trained in and in
the layout the reference reads (``reference/mellum.py``: the layers stacked
as ``blocks``), which is also the program's, so ``to_program`` hands the same
tree on. The rest of this file is the only place where the benchmark touches
the program's model code: building its ``MellumConfig`` from the
configuration file, its loss function and its optimizer. The program's model
is imported with this file, so that a program without it is refused before
any weight is drawn.

The configuration file holds the published ``config.json`` keys at its top
level and is read under those names. ``num_experts`` there counts the experts
**held on this chip** (``experts_held_first`` says from which on);
``router_num_experts`` is the router's published width.
"""

from __future__ import annotations

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
    from tepdist_tpu.models import mellum as program
except ImportError as e:
    # A program from before the model (the parent of the PR that brought
    # it): say so at once, before weights are drawn or anything compiles.
    raise BenchError("the program under test has no tepdist_tpu.models."
                     "mellum: it cannot run a Mellum2 configuration") from e


def model_sizes(config: dict) -> dict:
    return {"V": config["vocab_size"], "d": config["hidden_size"],
            "f": config["moe_intermediate_size"],
            "L": config["num_hidden_layers"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "hd": config["head_dim"],
            "E": config["router_num_experts"], "G": config["num_experts"],
            "k": config["num_experts_per_tok"]}


def _attention_params(s: dict) -> int:
    d, hd = s["d"], s["hd"]
    return 2 * d * s["H"] * hd + 2 * d * s["Hkv"] * hd      # q, o; k, v


def num_params(config: dict) -> int:
    """Every weight resident on the chip."""
    s = model_sizes(config)
    d = s["d"]
    layer = _attention_params(s) + 2 * d + 2 * s["hd"] + d * s["E"] \
        + 3 * d * s["f"] * s["G"]
    return 2 * s["V"] * d + d + s["L"] * layer


def active_matmul_params(config: dict) -> int:
    """Parameters one token meets in a matmul HERE: the four attention
    projections, the router, the routed experts at what this chip expects
    of a token's k (its share G / E of them: 2 of 8), and the head (the
    embedding is a lookup)."""
    s = model_sizes(config)
    d = s["d"]
    routed = s["k"] * s["G"] / s["E"]
    layer = _attention_params(s) + d * s["E"] + 3 * d * s["f"] * routed
    return int(s["L"] * layer + s["V"] * d)


def make_params(config: dict, seed: int):
    """normal(0.02) matrices and unit RMSNorm gains, drawn on the device;
    ``blocks`` is one dict of ``[layers, ...]`` arrays."""
    s = model_sizes(config)
    dt = DTYPES[config["dtype"]]
    d, hd, f, L = s["d"], s["hd"], s["f"], s["L"]
    H, Hkv, E, G = s["H"], s["Hkv"], s["E"], s["G"]
    f32 = jnp.float32

    def make(lo, hi, stream):
        ks = jax.random.split(_key(lo, hi, stream), 10)

        def norm(k, shape):
            return (jax.random.normal(k, shape, f32) * 0.02).astype(dt)

        blocks = {
            "input_ln": jnp.ones((L, d), f32),
            "post_attn_ln": jnp.ones((L, d), f32),
            "q_norm": jnp.ones((L, hd), f32),
            "k_norm": jnp.ones((L, hd), f32),
            "wq": norm(ks[2], (L, d, H * hd)),
            "wk": norm(ks[3], (L, d, Hkv * hd)),
            "wv": norm(ks[4], (L, d, Hkv * hd)),
            "wo": norm(ks[5], (L, H * hd, d)),
            "router": norm(ks[6], (L, d, E)),
            "w_gate": norm(ks[7], (L, G, d, f)),
            "w_up": norm(ks[8], (L, G, d, f)),
            "w_down": norm(ks[9], (L, G, f, d))}
        return {"tok_emb": norm(ks[0], (s["V"], d)),
                "norm_f": jnp.ones((d,), f32),
                "lm_head": norm(ks[1], (s["V"], d)),
                "blocks": blocks}

    return jax.jit(make)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets), drawn
    from the vocabulary's slice."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   config["vocab_size"])


def to_program(params: dict, config: dict) -> dict:
    """``tepdist_tpu.models.mellum`` reads the same names."""
    return dict(params)


def _rope(config: dict) -> tuple:
    """(theta of both kinds, the full-attention layers' YaRN parameters) of
    the published ``rope_parameters``."""
    window = config["rope_parameters"]["sliding_attention"]
    full = config["rope_parameters"]["full_attention"]
    if window["rope_type"] != "default" or full["rope_type"] != "yarn" \
            or window["rope_theta"] != full["rope_theta"]:
        raise BenchError("rope_parameters: a plain table on the window "
                         "layers and a YaRN table of the same theta on the "
                         "full ones is what is built here")
    return float(full["rope_theta"]), full


def program_config(config: dict):
    """The program's ``MellumConfig`` at this configuration's sizes."""
    p = config["program"]
    theta, yarn = _rope(config)
    return program.MellumConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        layer_types=tuple(config["layer_types"]),
        num_experts=config["router_num_experts"],
        experts_held=(int(config["experts_held_first"]),
                      int(config["num_experts"])),
        num_experts_per_tok=config["num_experts_per_tok"],
        sliding_window=config["sliding_window"],
        rope_theta=theta,
        yarn_factor=float(yarn["factor"]),
        yarn_original_max_position=int(
            yarn["original_max_position_embeddings"]),
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_attention_factor=yarn.get("attention_factor"),
        rms_norm_eps=float(config["rms_norm_eps"]),
        dtype=DTYPES[config["dtype"]],
        flash_block_q=int(p.get("flash_block_q", 0)),
        flash_block_k=int(p.get("flash_block_k", 0)),
        remat=bool(p.get("remat")),
        loss_chunk=int(p.get("loss_chunk", 0)),
        moe_tile_m=int(p["moe_tile_m"]))


def program_loss_fn(config: dict):
    """``loss(params, tokens)`` of the program under test."""
    cfg = program_config(config)
    return lambda p, t: program.loss_fn(p, t, cfg)


def reference_hyper(config: dict):
    from benchmark.reference import mellum as ref
    theta, yarn = _rope(config)
    return ref.Hyper(
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        top_k=config["num_experts_per_tok"],
        layer_types=tuple(config["layer_types"]),
        window=config["sliding_window"],
        held=(int(config["experts_held_first"]), int(config["num_experts"])),
        rope_theta=theta,
        yarn=ref.Yarn(float(yarn["factor"]),
                      int(yarn["original_max_position_embeddings"]),
                      float(yarn["beta_fast"]), float(yarn["beta_slow"]),
                      yarn.get("attention_factor")),
        eps=float(config["rms_norm_eps"]))


# -- what the checks compare ------------------------------------------------

# The leaves outside the layers: every layer's error, the routers' choices
# among them, reaches the embedding, and the loss's the head and the final
# norm, so their gradients stand for the whole step.
PROBE = ("tok_emb", "lm_head", "norm_f")


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/mellum.py``, in
    float32, ``chunk`` sequences at a time. ``cast`` swaps in the control's
    precision."""
    from benchmark.reference import mellum as ref
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
    parameters a token meets in a matmul on THIS chip (the routed experts
    at the expected 2 of its 8 choices that the held quarter gets), not the
    weights resident (``resident_params``) and not the whole model's."""
    return {"n_params": active_matmul_params(config),
            "resident_params": num_params(config)}
