"""Nemotron-H family (NVIDIA NVIDIA-Nemotron-3-Nano-30B-A3B): weights from the
seed, and the hand-over to the program.

As ``builders/qwen3_next.py``: ``make_params`` draws the weights on the
device in one jitted call, from the seed alone, in the dtype they are trained
in and in the layout the reference reads (``reference/nemotron_h.py``: the
layers in units, a stack a run of equal units, ``run{r}``), which is also the
program's, so ``to_program`` hands the same tree on. The rest of this file is
the only place where the benchmark touches the program's model code:
building its ``NemotronHConfig`` from the configuration file, its loss
function and its optimizer. The program's model is imported with this file,
so that a program without it is refused before any weight is drawn.

The configuration file holds the published ``config.json`` keys at its top
level and is read under those names. ``n_routed_experts`` there counts the
experts **held on this chip** (``experts_held_first`` says from which on) and
``router_num_experts`` is the router's published width;
``hybrid_override_pattern`` names each held layer's kind.
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
    from tepdist_tpu.models import nemotron_h as program
except ImportError as e:
    # A program from before the model (the parent of the PR that brought
    # it): say so at once, before weights are drawn or anything compiles.
    raise BenchError("the program under test has no tepdist_tpu.models."
                     "nemotron_h: it cannot run a Nemotron-H "
                     "configuration") from e

MAMBA, EXPERTS, ATTN = "M", "E", "*"


def units(config: dict) -> tuple:
    """The held layers in the units the layout stacks: a unit ends with an
    expert layer, or before a kind it already holds."""
    out = [""]
    for kind in config["hybrid_override_pattern"]:
        if out[-1].endswith(EXPERTS) or kind in out[-1]:
            out.append("")
        out[-1] += kind
    return tuple(u for u in out if u)


def runs(config: dict) -> list:
    """(unit, units) of each run of equal units, in the model's order."""
    out = []
    for unit in units(config):
        if out and out[-1][0] == unit:
            out[-1][1] += 1
        else:
            out.append([unit, 1])
    return [(unit, n) for unit, n in out]


def model_sizes(config: dict) -> dict:
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] \
            or set(pattern) - {MAMBA, EXPERTS, ATTN} \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or not config["norm_topk_prob"] or not config["use_conv_bias"] \
            or config["mlp_hidden_act"] != "relu2" \
            or config["n_shared_experts"] != 1 \
            or config["mamba_proj_bias"] or config["attention_bias"] \
            or config["mlp_bias"] or config["residual_in_fp32"]:
        raise BenchError(
            "a pattern of M, E and * a layer long, a router whose group "
            "limit chooses nothing, the chosen weights normalised, a conv "
            "with its bias, squared-relu experts beside one shared one, no "
            "other bias and a bf16 residual are what is built here")
    return {"V": config["vocab_size"], "d": config["hidden_size"],
            "H": config["mamba_num_heads"], "P": config["mamba_head_dim"],
            "Gs": config["n_groups"], "N": config["ssm_state_size"],
            "taps": config["conv_kernel"],
            "Hq": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "hd": config["head_dim"],
            "f": config["moe_intermediate_size"],
            "fs": config["moe_shared_expert_intermediate_size"],
            "E": config["router_num_experts"],
            "G": config["n_routed_experts"],
            "k": config["num_experts_per_tok"]}


def _matrices(s: dict, kind: str) -> dict:
    """name -> shape of a layer's matrices (normal(0.02))."""
    d = s["d"]
    if kind == ATTN:
        return {"wq": (d, s["Hq"] * s["hd"]), "wk": (d, s["Hkv"] * s["hd"]),
                "wv": (d, s["Hkv"] * s["hd"]), "wo": (s["Hq"] * s["hd"], d)}
    if kind == EXPERTS:
        return {"router": (d, s["E"]), "shared_up": (d, s["fs"]),
                "shared_down": (s["fs"], d), "w_up": (s["G"], d, s["f"]),
                "w_down": (s["G"], s["f"], d)}
    inner = s["H"] * s["P"]
    wide = inner + 2 * s["Gs"] * s["N"]
    return {"w_z": (d, inner), "w_xbc": (d, wide), "w_dt": (d, s["H"]),
            "conv_b": (wide,), "w_out": (inner, d)}


def _others(s: dict, kind: str) -> int:
    """A layer's parameters that are none of ``_matrices``: its norm, and a
    Mamba-2 layer's conv taps, ``dt_bias``, ``A_log``, ``D`` and the gated
    norm's gain; an expert layer's selection bias (the published count has
    it)."""
    inner = s["H"] * s["P"]
    if kind == EXPERTS:
        return s["d"] + s["E"]
    return s["d"] + (s["taps"] * (inner + 2 * s["Gs"] * s["N"]) + 3 * s["H"]
                     + inner if kind == MAMBA else 0)


def num_params(config: dict) -> int:
    """Every weight resident on the chip."""
    s = model_sizes(config)
    return 2 * s["V"] * s["d"] + s["d"] + sum(
        _others(s, kind) + sum(map(math.prod, _matrices(s, kind).values()))
        for kind in config["hybrid_override_pattern"])


def active_matmul_params(config: dict) -> int:
    """Parameters one token meets in a matmul HERE: a mixer's projections
    (the conv is no matmul), the router, the shared expert and the routed
    experts at what this chip expects of a token's k (its share G / E of
    them: 0.75 of a choice of 6), and the head (the embedding is a
    lookup)."""
    s = model_sizes(config)
    d, f = s["d"], s["f"]
    total = s["V"] * d
    for kind in config["hybrid_override_pattern"]:
        if kind == EXPERTS:
            total += d * s["E"] + 2 * d * s["fs"] \
                + 2 * d * f * s["k"] * s["G"] / s["E"]
        else:
            total += sum(math.prod(shape) for name, shape in
                         _matrices(s, kind).items() if name != "conv_b")
    return int(total)


# The routers' selection biases start where the published rule settles on
# one seeded sequence (``balanced``). The schedule is chosen here, no source
# publishes one: the rate falls from FIRST_RATE by DECAY a round to 0.0006,
# about the optimizer's ``bias_rate``.
SETTLE_TOKENS, SETTLE_ROUNDS, FIRST_RATE, DECAY = 8192, 48, 0.03, 0.92


def _settled(scores, k: int):
    """The selection bias [E] after SETTLE_ROUNDS rounds of the published
    rule on these scores [T, E]: ``b += delta - mean(delta)``, ``delta =
    rate x sign(mean(n) - n)`` over the counts ``n`` of ``top_k(scores +
    b)``."""
    E = scores.shape[-1]

    def one_round(i, b):
        _, ids = jax.lax.top_k(scores + b, k)
        counts = jnp.sum(ids.reshape(-1, 1) == jnp.arange(E), axis=0,
                         dtype=jnp.float32)
        rate = FIRST_RATE * DECAY ** i.astype(jnp.float32)
        delta = rate * jnp.sign(counts.mean() - counts)
        return b + delta - delta.mean()

    return jax.lax.fori_loop(0, SETTLE_ROUNDS, one_round,
                             jnp.zeros((E,), jnp.float32))


def balanced(params: dict, config: dict, key):
    """``params`` with **the routers' selection biases where the model's own
    update leaves them on a balanced load**, from the seed and the
    benchmark's own float32 forward alone (``reference/nemotron_h.py``; the
    program under test is not asked): one seeded sequence of SETTLE_TOKENS
    tokens walks the reference's layers once, and before each expert layer
    its bias is settled on the scores that layer's router gives the sequence
    (:func:`_settled`), so a later layer is settled on what the earlier
    ones, settled, pass on. What a trained model's routers have and random
    weights lack: with ``b = 0`` the squared relu's common mode makes some
    experts' scores high for every token, the held eighth gets 10.5 to 13.6%
    of the choices from seed to seed, and six seeds' rates spread by 0.68%
    (``assumed.routing`` in the configuration file). Inside the jitted
    maker."""
    from benchmark.reference import nemotron_h as ref
    hp = reference_hyper(config)
    tokens = jax.random.randint(key, (SETTLE_TOKENS,), 0,
                                config["vocab_size"], jnp.int32)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    biases = []
    for blk, kind in ref.layers_of(params, hp):
        if kind == EXPERTS:
            biases.append(_settled(ref.router_scores(blk, x, hp), hp.top_k))
            blk = {**blk, "router_bias": biases[-1]}
        x, _ = ref.layer(blk, x, kind, hp)
    out = dict(params)
    for r, (unit, n) in enumerate(runs(config)):
        if EXPERTS in unit:       # a unit holds one expert layer at most
            out[f"run{r}"] = {**params[f"run{r}"],
                              "router_bias": jnp.stack(biases[:n])}
            del biases[:n]
    return out


def make_params(config: dict, seed: int):
    """normal(0.02) matrices and conv bias, conv taps U(-1/2, 1/2), unit
    norm gains, ``A_log = log(1 .. H)``, ``D`` = 1 and ``dt_bias`` the
    inverse softplus of ``max(exp(U(log time_step_min, log time_step_max)),
    time_step_floor)`` a head, drawn on the device, and the routers'
    selection biases balanced (:func:`balanced`); ``run{r}`` is one dict of
    ``[units, ...]`` arrays."""
    s = model_sizes(config)
    dt = DTYPES[config["dtype"]]
    d, H = s["d"], s["H"]
    inner = H * s["P"]
    wide = inner + 2 * s["Gs"] * s["N"]
    f32 = jnp.float32
    held = runs(config)
    lo_step, hi_step = (math.log(config[k]) for k in
                        ("time_step_min", "time_step_max"))

    def make(lo, hi, stream):
        top = jax.random.split(_key(lo, hi, stream), 2 + len(held))

        def norm(k, shape):
            return (jax.random.normal(k, shape, f32) * 0.02).astype(dt)

        out = {"tok_emb": norm(top[0], (s["V"], d)),
               "norm_f": jnp.ones((d,), f32),
               "lm_head": norm(top[1], (s["V"], d))}
        for r, (unit, n) in enumerate(held):
            run = {}
            for kind, key in zip(unit, jax.random.split(top[2 + r],
                                                        len(unit))):
                shapes = _matrices(s, kind)
                ks = jax.random.split(key, len(shapes) + 2)
                for k, (name, shape) in zip(ks, shapes.items()):
                    run[name] = norm(k, (n,) + shape)
                if kind == ATTN:
                    run["attn_ln"] = jnp.ones((n, d), f32)
                elif kind == EXPERTS:
                    run["moe_ln"] = jnp.ones((n, d), f32)
                    run["router_bias"] = jnp.zeros((n, s["E"]), f32)
                else:
                    step = jnp.maximum(jnp.exp(jax.random.uniform(
                        ks[-2], (n, H), f32, lo_step, hi_step)),
                        config["time_step_floor"])
                    run.update({
                        "ssm_ln": jnp.ones((n, d), f32),
                        "conv": jax.random.uniform(
                            ks[-1], (n, s["taps"], wide), f32, -0.5,
                            0.5).astype(dt),
                        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                        "A_log": jnp.tile(jnp.log(jnp.arange(
                            1, H + 1, dtype=f32)), (n, 1)),
                        "D": jnp.ones((n, H), f32),
                        "ssm_norm": jnp.ones((n, inner), f32)})
            out[f"run{r}"] = run
        return balanced(out, config, _key(lo, hi, stream + 1))

    return jax.jit(make)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets), drawn
    from the vocabulary's slice."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   config["vocab_size"])


def to_program(params: dict, config: dict) -> dict:
    """``tepdist_tpu.models.nemotron_h`` reads the same names."""
    return dict(params)


def program_config(config: dict):
    """The program's ``NemotronHConfig`` at this configuration's sizes."""
    p, s = config["program"], model_sizes(config)
    return program.NemotronHConfig(
        vocab_size=s["V"], hidden_size=s["d"],
        hybrid_override_pattern=config["hybrid_override_pattern"],
        mamba_num_heads=s["H"], mamba_head_dim=s["P"], n_groups=s["Gs"],
        ssm_state_size=s["N"], conv_kernel=s["taps"],
        time_step_min=float(config["time_step_min"]),
        time_step_max=float(config["time_step_max"]),
        time_step_floor=float(config["time_step_floor"]),
        num_attention_heads=s["Hq"], num_key_value_heads=s["Hkv"],
        head_dim=s["hd"], moe_intermediate_size=s["f"],
        moe_shared_expert_intermediate_size=s["fs"],
        n_routed_experts=s["E"],
        experts_held=(int(config["experts_held_first"]), s["G"]),
        num_experts_per_tok=s["k"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        layer_norm_epsilon=float(config["layer_norm_epsilon"]),
        dtype=DTYPES[config["dtype"]],
        flash_block_q=int(p.get("flash_block_q", 0)),
        flash_block_k=int(p.get("flash_block_k", 0)),
        ssd_chunk=int(p["ssd_chunk"]),
        remat=bool(p.get("remat")),
        loss_chunk=int(p.get("loss_chunk", 0)),
        moe_tile_m=int(p["moe_tile_m"]))


def program_loss_fn(config: dict):
    """``loss(params, tokens)`` of the program under test."""
    cfg = program_config(config)
    return lambda p, t: program.loss_fn(p, t, cfg)


def reference_hyper(config: dict):
    from benchmark.reference import nemotron_h as ref
    s = model_sizes(config)
    return ref.Hyper(
        heads=s["H"], groups=s["Gs"], n_head=s["Hq"], n_kv_head=s["Hkv"],
        top_k=s["k"], held=(int(config["experts_held_first"]), s["G"]),
        units=units(config),
        route_scale=float(config["routed_scaling_factor"]),
        eps=float(config["layer_norm_epsilon"]))


# -- what the checks compare ------------------------------------------------

# The leaves outside the layers: every layer's error, the routers' choices
# among them, reaches the embedding, and the loss's the head and the final
# norm, so their gradients stand for the whole step.
PROBE = ("tok_emb", "lm_head", "norm_f")


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/nemotron_h.py``,
    in float32, ``chunk`` sequences at a time. ``cast`` swaps in the
    control's precision."""
    from benchmark.reference import nemotron_h as ref
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
    the expected 0.75 of a choice of its 6 that the held eighth gets), not
    the weights resident (``resident_params``) and not the whole model's.
    The state-space rule's and the attention's own products are not in it."""
    return {"n_params": active_matmul_params(config),
            "resident_params": num_params(config)}
