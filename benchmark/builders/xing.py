"""Xing4.0 family (XingChen-AGI Xing4.0-29B-A4B): weights from the seed, and
the hand-over to the program.

As ``builders/sarvam_mla.py``: ``make_params`` draws the weights on the
device in one jitted call, from the seed alone (the program under test helps
make none of them), in the dtype they are trained in and in the layout the
reference reads (``reference/xing.py``: the leading dense layer stacked as
``dense``, the expert layers as ``blocks``, the prediction module's layer as
``mtp``, each stack's hyper-connection leaves beside it as ``hc`` + its
name), which is also the program's, so ``to_program`` hands the same tree on.
The rest of this file is the only place where the benchmark touches the
program's model code: building its ``XingConfig`` from the configuration
file, its loss function and its optimizer. The program's model is imported
with this file, so that a program without it is refused before any weight is
drawn.

The configuration file holds the published ``config.json`` keys at its top
level and is read under those names. ``n_routed_experts`` there counts the
experts **held on this chip** (``experts_held_first`` says from which on);
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
    from tepdist_tpu.models import xing as program
except ImportError as e:
    # A program from before the model (the parent of the PR that brought
    # it): say so at once, before weights are drawn or anything compiles.
    raise BenchError("the program under test has no tepdist_tpu.models."
                     "xing: it cannot run a Xing4.0 configuration") from e


def model_sizes(config: dict) -> dict:
    n = config["hc_mult"]
    return {"V": config["vocab_size"], "d": config["hidden_size"],
            "I": config["intermediate_size"],
            "f": config["moe_intermediate_size"],
            "L": config["num_hidden_layers"],
            "Ld": config["first_k_dense_replace"],
            "M": config["num_nextn_predict_layers"],
            "H": config["num_attention_heads"],
            "Rq": config["q_lora_rank"], "R": config["kv_lora_rank"],
            "Dn": config["qk_nope_head_dim"],
            "Dr": config["qk_rope_head_dim"], "Dv": config["v_head_dim"],
            "E": config["router_num_experts"],
            "G": config["n_routed_experts"],
            "k": config["num_experts_per_tok"],
            "shared": config["n_shared_experts"],
            "n": n, "maps": n * n + 2 * n}


def _attention_params(s: dict) -> int:
    """The query's two projections, the down-projection, the up-projection
    and o."""
    d, H = s["d"], s["H"]
    return d * s["Rq"] + s["Rq"] * H * (s["Dn"] + s["Dr"]) \
        + d * (s["R"] + s["Dr"]) + s["R"] * H * (s["Dn"] + s["Dv"]) \
        + H * s["Dv"] * d


def _maps_params(s: dict) -> int:
    """A layer's two sub-layers' phi, b and three alpha."""
    return 2 * (s["n"] * s["d"] * s["maps"] + s["maps"] + 3)


def _layer_params(s: dict, dense: bool) -> int:
    d, f = s["d"], s["f"]
    every = _attention_params(s) + s["Rq"] + s["R"] + 2 * d + _maps_params(s)
    if dense:
        return every + 3 * d * s["I"]
    return every + d * s["E"] + s["E"] + 3 * d * f * (s["shared"] + s["G"])


def num_params(config: dict) -> int:
    """Every weight resident on the chip."""
    s = model_sizes(config)
    d = s["d"]
    return 2 * s["V"] * d + d + s["Ld"] * _layer_params(s, True) \
        + (s["L"] - s["Ld"]) * _layer_params(s, False) \
        + s["M"] * (2 * d * d + 3 * d + _layer_params(s, False))


def active_matmul_params(config: dict) -> int:
    """Parameters one token meets in a matmul HERE: the five attention
    projections and the two maps' ``phi`` of every layer, the dense layer's
    MLP or the router, the shared expert and the routed experts at what this
    chip expects of a token's k (its share G / E of them: half a choice of
    4), the prediction module's ``mtp_eh`` and layer, and the head once a
    loss (the embedding is a lookup)."""
    s = model_sizes(config)
    d, f = s["d"], s["f"]
    every = _attention_params(s) + 2 * s["n"] * d * s["maps"]
    expert = every + d * s["E"] + 3 * d * f * (
        s["shared"] + s["k"] * s["G"] / s["E"])
    return int(s["Ld"] * (every + 3 * d * s["I"])
               + (s["L"] - s["Ld"]) * expert
               + s["M"] * (2 * d * d + expert) + (1 + s["M"]) * s["V"] * d)


def make_params(config: dict, seed: int):
    """normal(0.02) matrices, unit RMSNorm gains and a zero selection bias,
    drawn on the device; ``dense``, ``blocks`` and ``mtp`` are one dict each
    of ``[layers, ...]`` arrays, their maps' leaves beside them
    (``hcdense``, ``hcblocks``, ``hcmtp``) at the start the configuration
    states (``assumed.mhc_start``): ``phi`` normal(0.02 / sqrt(n)), the
    three ``alpha`` 1, ``b`` normal(1) with 2 more on ``H_res``'s
    diagonal."""
    s = model_sizes(config)
    if s["M"] != 1:
        raise BenchError("one prediction module is what is built here, not "
                         f"num_nextn_predict_layers = {s['M']}")
    dt = DTYPES[config["dtype"]]
    d, f, I, R, Rq = s["d"], s["f"], s["I"], s["R"], s["Rq"]
    H, Dn, Dr, Dv, E, G = s["H"], s["Dn"], s["Dr"], s["Dv"], s["E"], s["G"]
    n, wide, fs = s["n"], s["maps"], s["f"] * s["shared"]
    f32 = jnp.float32
    dense_mlp = {"w_gate": (d, I), "w_up": (d, I), "w_down": (I, d)}
    experts = {"router": (d, E), "shared_gate": (d, fs), "shared_up": (d, fs),
               "shared_down": (fs, d), "w_gate": (G, d, f), "w_up": (G, d, f),
               "w_down": (G, f, d)}

    def make(lo, hi, stream):
        top = jax.random.split(_key(lo, hi, stream), 6)

        def norm(k, shape, std=0.02):
            return (jax.random.normal(k, shape, f32) * std).astype(dt)

        def layers(key, count, mlp):
            ks = jax.random.split(key, 9 + len(mlp))
            out = {"input_ln": jnp.ones((count, d), f32),
                   "post_attn_ln": jnp.ones((count, d), f32),
                   "kv_ln": jnp.ones((count, R), f32),
                   "q_ln": jnp.ones((count, Rq), f32),
                   "wqa": norm(ks[0], (count, d, Rq)),
                   "wqb": norm(ks[1], (count, Rq, H * (Dn + Dr))),
                   "wkva": norm(ks[2], (count, d, R + Dr)),
                   "wkvb": norm(ks[3], (count, R, H * (Dn + Dv))),
                   "wo": norm(ks[4], (count, H * Dv, d))}
            for k, (name, shape) in zip(ks[9:], mlp.items()):
                out[name] = norm(k, (count,) + shape)
            if "router" in mlp:
                out["router_bias"] = jnp.zeros((count, E), f32)
            towards_identity = jnp.concatenate(
                [jnp.zeros((2 * n,), f32), 2.0 * jnp.eye(n).reshape(-1)])
            maps = {}
            for j, sub in enumerate(("attn", "mlp")):
                maps[f"phi_{sub}"] = norm(ks[5 + 2 * j],
                                          (count, n * d, wide),
                                          0.02 / n ** 0.5)
                maps[f"b_{sub}"] = jax.random.normal(
                    ks[6 + 2 * j], (count, wide), f32) + towards_identity
                maps[f"alpha_{sub}"] = jnp.ones((count, 3), f32)
            return out, maps

        out = {"tok_emb": norm(top[0], (s["V"], d)),
               "norm_f": jnp.ones((d,), f32),
               "lm_head": norm(top[1], (s["V"], d)),
               "mtp_eh": norm(top[2], (2 * d, d)),
               "mtp_hnorm": jnp.ones((d,), f32),
               "mtp_enorm": jnp.ones((d,), f32),
               "mtp_norm": jnp.ones((d,), f32)}
        for key, name, count, mlp in (
                (top[3], "dense", s["Ld"], dense_mlp),
                (top[4], "blocks", s["L"] - s["Ld"], experts),
                (top[5], "mtp", s["M"], experts)):
            out[name], out["hc" + name] = layers(key, count, mlp)
        return out

    return jax.jit(make)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets), drawn
    from the vocabulary's slice."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   config["vocab_size"])


def to_program(params: dict, config: dict) -> dict:
    """``tepdist_tpu.models.xing`` reads the same names."""
    return dict(params)


def _yarn(config: dict) -> dict:
    yarn = config["rope_scaling"]
    if yarn["type"] != "yarn" or "mscale_all_dim" not in yarn:
        raise BenchError("rope_scaling: DeepSeek-V3's yarn table (type yarn "
                         "with mscale_all_dim) is what is built here, not "
                         f"{yarn!r}")
    return yarn


def program_config(config: dict):
    """The program's ``XingConfig`` at this configuration's sizes."""
    p, yarn = config["program"], _yarn(config)
    if config["n_group"] != 1 or config["topk_group"] != 1 \
            or not config["norm_topk_prob"] \
            or config["scoring_func"] != "sigmoid":
        raise BenchError("the router built here is noaux_tc's with one "
                         "group, sigmoid scores and normalised weights")
    return program.XingConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_attention_heads=config["num_attention_heads"],
        heads_held=(0, int(config["num_attention_heads"])),
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        num_hidden_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        num_experts=config["router_num_experts"],
        experts_held=(int(config["experts_held_first"]),
                      int(config["n_routed_experts"])),
        num_experts_per_tok=config["num_experts_per_tok"],
        num_shared_experts=config["n_shared_experts"],
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
        hc_mult=int(config["hc_mult"]),
        hc_sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]),
        mhc_h_res_clamp=(float(config["mhc_h_res_clamp_min"]),
                         float(config["mhc_h_res_clamp_max"])),
        num_nextn_predict_layers=int(config["num_nextn_predict_layers"]),
        mtp_loss_weight=float(config["mtp_loss_weight"]),
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
    from benchmark.reference import xing as ref
    yarn = _yarn(config)
    return ref.Hyper(
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        kv_lora_rank=config["kv_lora_rank"],
        top_k=config["num_experts_per_tok"],
        held=(int(config["experts_held_first"]),
              int(config["n_routed_experts"])),
        route_scale=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        yarn=ref.Yarn(float(yarn["factor"]),
                      int(yarn["original_max_position_embeddings"]),
                      float(yarn["beta_fast"]), float(yarn["beta_slow"]),
                      float(yarn["mscale"]), float(yarn["mscale_all_dim"])),
        eps=float(config["rms_norm_eps"]),
        lanes=int(config["hc_mult"]),
        sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]),
        clamp=(float(config["mhc_h_res_clamp_min"]),
               float(config["mhc_h_res_clamp_max"])),
        mtp_weight=float(config["mtp_loss_weight"]))


# -- what the checks compare ------------------------------------------------

# The leaves outside the layers (every layer's error, the routers' choices
# among them, reaches the embedding; the losses' the head and the norm) and,
# so that neither new path can be wrong unseen, the prediction module's
# ``mtp_eh`` (only the second loss reaches it) and the expert layers' maps:
# ``hcblocks`` holds the four layers' ``phi``, ``b`` and ``alpha`` of both
# sub-layers as one group of stacked leaves, the first expert layer's
# attention ``phi`` among them (a layer of a stack cannot be named apart).
PROBE = ("tok_emb", "lm_head", "norm_f", "mtp_eh", "hcblocks")


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/xing.py``, in
    float32, ``chunk`` sequences at a time. ``cast`` swaps in the control's
    precision."""
    from benchmark.reference import xing as ref
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
        probe = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), {k: params[k] for k in PROBE})
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
    the expected half of a choice of its 4 that the held eighth gets, the
    head once a loss), not the weights resident (``resident_params``) and
    not the whole model's."""
    return {"n_params": active_matmul_params(config),
            "resident_params": num_params(config)}
