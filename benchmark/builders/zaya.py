"""ZAYA family (Zyphra ZAYA1-8B): weights from the seed, and the hand-over to
the program.

As ``builders/sarvam_mla.py``: ``make_params`` draws the weights on the
device in one jitted call, from the seed alone, in the dtype they are trained
in and in the layout the reference reads (``reference/zaya.py``: the layers
stacked as ``blocks``), which is also the program's, so ``to_program`` hands
the same tree on. The rest of this file is the only place where the benchmark
touches the program's model code: building its ``ZayaConfig`` from the
configuration file, its loss function and its optimizer. The program's model
is imported with this file, so that a program without it is refused before
any weight is drawn.

The configuration file holds the published ``config.json`` keys at its top
level and is read under those names.
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
    from tepdist_tpu.models import zaya as program
except ImportError as e:
    # A program from before the model (the parent of the PR that brought
    # it): say so at once, before weights are drawn or anything compiles.
    raise BenchError("the program under test has no tepdist_tpu.models."
                     "zaya: it cannot run a ZAYA configuration") from e

# The depth-wise taps' standard deviation (the configuration file's
# ``assumed.initialisation``).
TAP_STD = 0.5


def model_sizes(config: dict) -> dict:
    return {"V": config["vocab_size"], "d": config["hidden_size"],
            "L": config["num_hidden_layers"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "R": config["router_hidden_size"], "E": config["num_experts"],
            "k": config["num_experts_per_tok"],
            "f": config["moe_intermediate_size"]}


def _attention_matmul_params(s: dict) -> int:
    """q, k, the two value halves and o, and the heads' two conv taps."""
    d, D, H, Hkv = s["d"], s["D"], s["H"], s["Hkv"]
    return d * H * D + 2 * d * Hkv * D + H * D * d \
        + 2 * (H + Hkv) * D * D


def _router_matmul_params(s: dict) -> int:
    return s["d"] * s["R"] + 2 * s["R"] * s["R"] + s["R"] * s["E"]


def layer_params(config: dict) -> int:
    """Every weight of one layer: the attention sublayer (projections, both
    convs with their biases, the temperature, the two sublayers' norm
    gains), the router (with its norm, ``gamma`` and bias) and the
    experts."""
    s = model_sizes(config)
    N, D = s["H"] + s["Hkv"], s["D"]
    attention = _attention_matmul_params(s) + 4 * N * D + s["Hkv"] \
        + 2 * s["d"]
    router = _router_matmul_params(s) + 2 * s["R"] + s["E"]
    return attention + router + s["E"] * 3 * s["d"] * s["f"]


def num_params(config: dict) -> int:
    """Every weight resident on the chip (the head is the tied embedding)."""
    s = model_sizes(config)
    return s["L"] * layer_params(config) + s["V"] * s["d"] + s["d"]


def active_matmul_params(config: dict) -> int:
    """Parameters one token meets in a matmul: the attention projections,
    every head's two conv taps, the router's matrices, its one expert, and
    the head (the embedding is a lookup)."""
    s = model_sizes(config)
    return s["L"] * (_attention_matmul_params(s) + _router_matmul_params(s)
                     + s["k"] * 3 * s["d"] * s["f"]) + s["V"] * s["d"]


def make_params(config: dict, seed: int):
    """normal(0.02) matrices (the router's MLP in float32), depth-wise taps
    normal(0.5), unit RMSNorm gains and temperature, ``gamma`` 0.5, zero conv
    biases and selection bias, drawn on the device; ``blocks`` is one dict of
    ``[layers, ...]`` arrays."""
    s = model_sizes(config)
    dt = DTYPES[config["dtype"]]
    d, D, H, Hkv, R, E, f, n = s["d"], s["D"], s["H"], s["Hkv"], s["R"], \
        s["E"], s["f"], s["L"]
    N = H + Hkv
    f32 = jnp.float32

    def make(lo, hi, stream):
        top = jax.random.split(_key(lo, hi, stream), 2)
        ks = jax.random.split(top[1], 14)

        def norm(k, shape, dtype=dt, std=0.02):
            return (jax.random.normal(k, shape, f32) * std).astype(dtype)

        blocks = {
            "attn_ln": jnp.ones((n, d), f32), "moe_ln": jnp.ones((n, d), f32),
            "wq": norm(ks[0], (n, d, H * D)),
            "wk": norm(ks[1], (n, d, Hkv * D)),
            "wva": norm(ks[2], (n, d, Hkv // 2 * D)),
            "wvb": norm(ks[3], (n, d, Hkv // 2 * D)),
            "wo": norm(ks[4], (n, H * D, d)),
            "conv_w1": norm(ks[5], (n, 2, N * D), f32, TAP_STD),
            "conv_b1": jnp.zeros((n, N * D), f32),
            "conv_w2": norm(ks[6], (n, 2, N, D, D)),
            "conv_b2": jnp.zeros((n, N * D), f32),
            "tau": jnp.ones((n, Hkv), f32),
            "router_down": norm(ks[7], (n, d, R)),
            "router_ln": jnp.ones((n, R), f32),
            "router_gamma": jnp.full((n, R), 0.5, f32),
            "router_w1": norm(ks[8], (n, R, R), f32),
            "router_w2": norm(ks[9], (n, R, R), f32),
            "router_w3": norm(ks[10], (n, R, E), f32),
            "router_bias": jnp.zeros((n, E), f32),
            "w_gate": norm(ks[11], (n, E, d, f)),
            "w_up": norm(ks[12], (n, E, d, f)),
            "w_down": norm(ks[13], (n, E, f, d))}
        return {"tok_emb": norm(top[0], (s["V"], d)),
                "norm_f": jnp.ones((d,), f32), "blocks": blocks}

    return jax.jit(make)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets), drawn
    from the vocabulary's slice."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   config["vocab_size"])


def to_program(params: dict, config: dict) -> dict:
    """``tepdist_tpu.models.zaya`` reads the same names."""
    return dict(params)


def _rope(config: dict) -> dict:
    """The rotary parameters of the layers' one type."""
    kinds = set(config["layer_types"])
    if kinds != {"hybrid"}:
        raise BenchError(f"layer_types {sorted(kinds)}: every layer of "
                         "what is built here is 'hybrid'")
    return config["rope_parameters"]["hybrid"]


def program_config(config: dict):
    """The program's ``ZayaConfig`` at this configuration's sizes."""
    p, rotary = config["program"], _rope(config)
    return program.ZayaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        cca_time0=config["cca_time0"], cca_time1=config["cca_time1"],
        partial_rotary_factor=float(rotary["partial_rotary_factor"]),
        rope_theta=float(rotary["rope_theta"]),
        router_hidden_size=config["router_hidden_size"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
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
    from benchmark.reference import zaya as ref
    rotary = _rope(config)
    D = config["head_dim"]
    return ref.Hyper(
        head_dim=D, rotary_dim=int(D * rotary["partial_rotary_factor"]),
        rope_theta=float(rotary["rope_theta"]),
        eps=float(config["rms_norm_eps"]))


# -- what the checks compare ------------------------------------------------

# The leaves outside the layers: every layer's error, the routers' choices
# among them, reaches the embedding, which is also the head, and the loss's
# the final norm, so their gradients stand for the whole step.
PROBE = ("tok_emb", "norm_f")


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/zaya.py``, in
    float32, ``chunk`` sequences at a time. ``cast`` swaps in the control's
    precision."""
    from benchmark.reference import zaya as ref
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
    parameters a token meets in a matmul (one expert of the 16, every head's
    conv taps, the head), not the weights resident
    (``resident_params``)."""
    return {"n_params": active_matmul_params(config),
            "resident_params": num_params(config)}
