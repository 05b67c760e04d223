"""OLMoE family: weights from the seed, and the hand-over to the program.

As ``builders/gpt2.py``: ``make_params`` draws the weights on the device in
one jitted call, from the seed alone, in the dtype they are trained in and in
the layout the reference reads (``reference/olmoe.py``), which is also the
program's, so ``to_program`` hands the same tree on. The rest of this file
is the only place where the benchmark touches the program's model code:
building its ``OlmoeConfig`` from the configuration file, its loss function
and its optimizer. The program's model is imported with this file, so that
a program without it is refused before any weight is drawn.

The configuration file holds the published ``config.json`` keys at its top
level (``hidden_size``, ``num_experts``, ...) and is read under those names.
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
    from tepdist_tpu.models import olmoe as program
except ImportError as e:
    # A program from before the model (the parent of the PR that brought
    # it): say so at once, before weights are drawn or anything compiles.
    raise BenchError("the program under test has no tepdist_tpu.models."
                     "olmoe: it cannot run an OLMoE configuration") from e


def model_sizes(config: dict) -> dict:
    return {"V": config["vocab_size"], "d": config["hidden_size"],
            "f": config["intermediate_size"],
            "L": config["num_hidden_layers"],
            "H": config["num_attention_heads"], "E": config["num_experts"],
            "k": config["num_experts_per_tok"]}


def num_params(config: dict) -> int:
    """Every weight resident on the chip."""
    s = model_sizes(config)
    d, f, E = s["d"], s["f"], s["E"]
    return 2 * s["V"] * d + d + s["L"] * (
        4 * d * d + 4 * d + d * E + 3 * E * d * f)


def active_matmul_params(config: dict) -> int:
    """Parameters one token meets in a matmul: the four attention
    projections, the router and k of the E experts a layer, and the head
    (the embedding is a lookup)."""
    s = model_sizes(config)
    d = s["d"]
    return s["L"] * (4 * d * d + d * s["E"] + 3 * s["k"] * d * s["f"]) \
        + s["V"] * d


def make_params(config: dict, seed: int):
    """normal(0.02) matrices and unit RMSNorm gains, drawn on the device;
    ``blocks`` is one dict of ``[L, ...]`` arrays."""
    s = model_sizes(config)
    dt = DTYPES[config["dtype"]]
    d, f, L, E = s["d"], s["f"], s["L"], s["E"]
    f32 = jnp.float32

    def make(lo, hi, stream):
        ks = jax.random.split(_key(lo, hi, stream), 10)

        def norm(k, shape):
            return (jax.random.normal(k, shape, f32) * 0.02).astype(dt)

        blocks = {
            "attn_norm": jnp.ones((L, d), f32),
            "q_norm": jnp.ones((L, d), f32), "k_norm": jnp.ones((L, d), f32),
            "wq": norm(ks[2], (L, d, d)), "wk": norm(ks[3], (L, d, d)),
            "wv": norm(ks[4], (L, d, d)), "wo": norm(ks[5], (L, d, d)),
            "ffn_norm": jnp.ones((L, d), f32),
            "router": norm(ks[6], (L, d, E)),
            "w_gate": norm(ks[7], (L, E, d, f)),
            "w_up": norm(ks[8], (L, E, d, f)),
            "w_down": norm(ks[9], (L, E, f, d)),
        }
        return {"tok_emb": norm(ks[0], (s["V"], d)),
                "norm_f": jnp.ones((d,), f32),
                "lm_head": norm(ks[1], (s["V"], d)), "blocks": blocks}

    return jax.jit(make)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets)."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   config["vocab_size"])


def to_program(params: dict, config: dict) -> dict:
    """``tepdist_tpu.models.olmoe`` reads the same names."""
    return dict(params)


def program_config(config: dict):
    """The program's ``OlmoeConfig`` at this configuration's sizes."""
    p, a = config["program"], config["assumed"]
    return program.OlmoeConfig(
        vocab_size=config["vocab_size"],
        max_position_embeddings=config["max_position_embeddings"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        lb_coef=float(a["load_balancing_loss_coef"]),
        z_coef=float(a["router_z_loss_coef"]),
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
    from benchmark.reference import olmoe as ref
    a = config["assumed"]
    return ref.Hyper(
        n_head=config["num_attention_heads"],
        top_k=config["num_experts_per_tok"],
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        lb_coef=float(a["load_balancing_loss_coef"]),
        z_coef=float(a["router_z_loss_coef"]))


# -- what the checks compare ------------------------------------------------

# The leaves outside the blocks: every layer's error, the router's choices
# among them, reaches the embedding, and the loss's the head and the final
# norm, so their gradients stand for the whole step.
PROBE = ("tok_emb", "lm_head", "norm_f")


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/olmoe.py``, in
    float32, ``chunk`` sequences at a time. ``cast`` swaps in the control's
    precision."""
    from benchmark.reference import olmoe as ref
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
    parameters ACTIVE per token that sit in a matmul, not the weights
    resident (``resident_params``), or an expert model's MFU would count
    56 experts a token that never ran."""
    return {"n_params": active_matmul_params(config),
            "resident_params": num_params(config)}
