"""Sarvam MLA family (sarvamai sarvam-105b): weights from the seed, and the
hand-over to the program.

As ``builders/afmoe.py``: ``make_params`` draws the weights on the device in
one jitted call, from the seed alone, in the dtype they are trained in and in
the layout the reference reads (``reference/sarvam_mla.py``: the leading
dense layer stacked as ``dense``, the expert layers as ``blocks``), which is
also the program's, so ``to_program`` hands the same tree on. The rest of
this file is the only place where the benchmark touches the program's model
code: building its ``SarvamMLAConfig`` from the configuration file, its loss
function and its optimizer. The program's model is imported with this file,
so that a program without it is refused before any weight is drawn.

The configuration file holds the published ``config.json`` keys at its top
level and is read under those names. ``num_experts`` there counts the experts
and ``num_attention_heads`` the heads **held on this chip**
(``experts_held_first`` / ``heads_held_first`` say from which on);
``router_num_experts`` is the router's published width and
``reduced_from.num_attention_heads`` the model's heads.
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
    from tepdist_tpu.models import sarvam_mla as program
except ImportError as e:
    # A program from before the model (the parent of the PR that brought
    # it): say so at once, before weights are drawn or anything compiles.
    raise BenchError("the program under test has no tepdist_tpu.models."
                     "sarvam_mla: it cannot run a Sarvam MLA "
                     "configuration") from e


def model_sizes(config: dict) -> dict:
    return {"V": config["vocab_size"], "d": config["hidden_size"],
            "I": config["intermediate_size"],
            "f": config["moe_intermediate_size"],
            "L": config["num_hidden_layers"],
            "Ld": config["first_k_dense_replace"],
            "Hh": config["num_attention_heads"],
            "R": config["kv_lora_rank"], "Dn": config["qk_nope_head_dim"],
            "Dr": config["qk_rope_head_dim"], "Dv": config["v_head_dim"],
            "E": config["router_num_experts"], "G": config["num_experts"],
            "k": config["num_experts_per_tok"],
            "shared": config["num_shared_experts"]}


def _attention_params(s: dict) -> int:
    """q, the down-projection, the up-projection and o, at the heads held."""
    d, Hh = s["d"], s["Hh"]
    return d * Hh * (s["Dn"] + s["Dr"]) + d * (s["R"] + s["Dr"]) \
        + s["R"] * Hh * (s["Dn"] + s["Dv"]) + Hh * s["Dv"] * d


def num_params(config: dict) -> int:
    """Every weight resident on the chip."""
    s = model_sizes(config)
    d, f = s["d"], s["f"]
    every = _attention_params(s) + 2 * d + s["R"]
    dense = every + 3 * d * s["I"]
    expert = every + d * s["E"] + s["E"] + 3 * d * f * (s["shared"] + s["G"])
    return 2 * s["V"] * d + d + s["Ld"] * dense + (s["L"] - s["Ld"]) * expert


def active_matmul_params(config: dict) -> int:
    """Parameters one token meets in a matmul HERE: the four attention
    projections at the heads held, the dense layer's MLP or the router, the
    shared expert and the routed experts at what this chip expects of a
    token's k (its share G / E of them: half a choice of 8), and the head
    (the embedding is a lookup)."""
    s = model_sizes(config)
    d, f = s["d"], s["f"]
    routed = s["k"] * s["G"] / s["E"]
    expert = _attention_params(s) + d * s["E"] + 3 * d * f * (
        s["shared"] + routed)
    return int(s["Ld"] * (_attention_params(s) + 3 * d * s["I"])
               + (s["L"] - s["Ld"]) * expert + s["V"] * d)


def make_params(config: dict, seed: int):
    """normal(0.02) matrices, unit RMSNorm gains and a zero selection bias,
    drawn on the device; ``dense`` and ``blocks`` are one dict each of
    ``[layers, ...]`` arrays."""
    s = model_sizes(config)
    dt = DTYPES[config["dtype"]]
    d, f, I, R = s["d"], s["f"], s["I"], s["R"]
    Hh, Dn, Dr, Dv, E, G = s["Hh"], s["Dn"], s["Dr"], s["Dv"], s["E"], s["G"]
    fs = f * s["shared"]
    f32 = jnp.float32

    def make(lo, hi, stream):
        top = jax.random.split(_key(lo, hi, stream), 4)

        def norm(k, shape):
            return (jax.random.normal(k, shape, f32) * 0.02).astype(dt)

        def layers(key, n, mlp):
            ks = jax.random.split(key, 4 + len(mlp))
            out = {"input_ln": jnp.ones((n, d), f32),
                   "post_attn_ln": jnp.ones((n, d), f32),
                   "kv_ln": jnp.ones((n, R), f32),
                   "wq": norm(ks[0], (n, d, Hh * (Dn + Dr))),
                   "wkva": norm(ks[1], (n, d, R + Dr)),
                   "wkvb": norm(ks[2], (n, R, Hh * (Dn + Dv))),
                   "wo": norm(ks[3], (n, Hh * Dv, d))}
            for k, (name, shape) in zip(ks[4:], mlp.items()):
                out[name] = norm(k, (n,) + shape)
            return out

        blocks = layers(top[3], s["L"] - s["Ld"], {
            "router": (d, E), "shared_gate": (d, fs), "shared_up": (d, fs),
            "shared_down": (fs, d), "w_gate": (G, d, f), "w_up": (G, d, f),
            "w_down": (G, f, d)})
        blocks["router_bias"] = jnp.zeros((s["L"] - s["Ld"], E), f32)
        return {"tok_emb": norm(top[0], (s["V"], d)),
                "norm_f": jnp.ones((d,), f32),
                "lm_head": norm(top[1], (s["V"], d)),
                "dense": layers(top[2], s["Ld"], {
                    "w_gate": (d, I), "w_up": (d, I), "w_down": (I, d)}),
                "blocks": blocks}

    return jax.jit(make)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets), drawn
    from the vocabulary's slice."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   config["vocab_size"])


def to_program(params: dict, config: dict) -> dict:
    """``tepdist_tpu.models.sarvam_mla`` reads the same names."""
    return dict(params)


def _yarn(config: dict) -> dict:
    yarn = config["rope_scaling"]
    if yarn["type"] != "deepseek_yarn":
        raise BenchError("rope_scaling: a deepseek_yarn table is what is "
                         f"built here, not {yarn['type']!r}")
    return yarn


def program_config(config: dict):
    """The program's ``SarvamMLAConfig`` at this configuration's sizes."""
    p, yarn = config["program"], _yarn(config)
    return program.SarvamMLAConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_attention_heads=int(
            config["reduced_from"]["num_attention_heads"]),
        heads_held=(int(config["heads_held_first"]),
                    int(config["num_attention_heads"])),
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        num_hidden_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        num_experts=config["router_num_experts"],
        experts_held=(int(config["experts_held_first"]),
                      int(config["num_experts"])),
        num_experts_per_tok=config["num_experts_per_tok"],
        num_shared_experts=config["num_shared_experts"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        yarn_factor=float(yarn["factor"]),
        yarn_original_max_position=int(
            yarn["original_max_position_embeddings"]),
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_mscale=float(yarn["mscale"]),
        yarn_mscale_all_dim=float(yarn["mscale_all_dim"]),
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
    from benchmark.reference import sarvam_mla as ref
    yarn = _yarn(config)
    return ref.Hyper(
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        kv_lora_rank=config["kv_lora_rank"],
        top_k=config["num_experts_per_tok"],
        held=(int(config["experts_held_first"]), int(config["num_experts"])),
        route_scale=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        yarn=ref.Yarn(float(yarn["factor"]),
                      int(yarn["original_max_position_embeddings"]),
                      float(yarn["beta_fast"]), float(yarn["beta_slow"]),
                      float(yarn["mscale"]), float(yarn["mscale_all_dim"])),
        eps=float(config["rms_norm_eps"]))


# -- what the checks compare ------------------------------------------------

# The leaves outside the layers: every layer's error, the routers' choices
# among them, reaches the embedding, and the loss's the head and the final
# norm, so their gradients stand for the whole step.
PROBE = ("tok_emb", "lm_head", "norm_f")


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/sarvam_mla.py``,
    in float32, ``chunk`` sequences at a time. ``cast`` swaps in the
    control's precision."""
    from benchmark.reference import sarvam_mla as ref
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
    parameters a token meets in a matmul on THIS chip (the held heads'
    projections, the routed experts at the expected half of a choice of its
    8 that the held sixteenth gets), not the weights resident
    (``resident_params``) and not the whole model's."""
    return {"n_params": active_matmul_params(config),
            "resident_params": num_params(config)}
