"""Granite 4.0-H family (ibm-granite granite-4.0-h-small): weights from the
seed, and the hand-over to the program.

As ``builders/nemotron_h.py``: ``make_params`` draws the weights on the device
in one jitted call, from the seed alone, in the dtype they are trained in and
in the layout the reference reads (``reference/granite_hybrid.py``: the layers
stacked a run of one mixer, ``run{r}`` beside ``vec{r}`` and ``out{r}``),
which is also the program's, so ``to_program`` hands the same tree on. The
rest of this file is the only place where the benchmark touches the program's
model code: building its ``GraniteHybridConfig`` from the configuration file,
its loss function and its optimizer. The program's model is imported with
this file, so that a program without it is refused before any weight is drawn.

The configuration file holds the published ``config.json`` keys at its top
level and is read under those names. What counts a rank's share counts what
is **held on this chip**: ``mamba_n_heads``, ``num_attention_heads``,
``num_key_value_heads``, ``num_local_experts`` (``experts_held_first`` says
from which on; ``router_num_experts`` is the router's published width),
``vocab_size``; the shared MLP is whole on every rank at its published
``shared_intermediate_size``; ``layer_types`` names each held layer's mixer.
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
    from tepdist_tpu.models import granite_hybrid as program
except ImportError as e:
    # A program from before the model (the parent of the PR that brought
    # it): say so at once, before weights are drawn or anything compiles.
    raise BenchError("the program under test has no tepdist_tpu.models."
                     "granite_hybrid: it cannot run a Granite 4.0-H "
                     "configuration") from e

MAMBA, ATTN = "mamba", "attention"
# Where a run's Mamba-2 leaves lie that are not under ``run{r}``.
GROUP_OF = {"A_log": "vec", "D": "vec", "dt_bias": "vec", "conv_b": "vec",
            "w_out": "out"}
# The published module's fixed start of the steps (``time_step_min`` /
# ``time_step_max`` in its ``__init__``) and the Mamba-2 reference's floor.
STEP_MIN, STEP_MAX, STEP_FLOOR = 0.001, 0.1, 1e-4


def runs(config: dict) -> list:
    """(mixer, layers) of each run of one mixer, in the model's order."""
    out = []
    for kind in config["layer_types"]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(kind, n) for kind, n in out]


def model_sizes(config: dict) -> dict:
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] \
            or set(kinds) - {MAMBA, ATTN} or config["mamba_n_groups"] != 1 \
            or not config["mamba_conv_bias"] or config["mamba_proj_bias"] \
            or config["attention_bias"] or config["hidden_act"] != "silu" \
            or config["position_embedding_type"] != "nope" \
            or not config["tie_word_embeddings"] \
            or config["normalization_function"] != "rmsnorm":
        raise BenchError(
            "a mixer a layer of mamba or attention, one group of states, a "
            "conv with its bias, no other bias, silu, no positional "
            "embedding, RMSNorm and a tied head are what is built here")
    return {"V": config["vocab_size"], "d": config["hidden_size"],
            "H": config["mamba_n_heads"], "P": config["mamba_d_head"],
            "N": config["mamba_d_state"], "taps": config["mamba_d_conv"],
            "Hq": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "hd": config["head_dim"],
            "f": config["intermediate_size"],
            "fs": config["shared_intermediate_size"],
            "E": config["router_num_experts"],
            "G": config["num_local_experts"],
            "k": config["num_experts_per_tok"]}


def _matrices(s: dict, kind: str) -> dict:
    """name -> shape of a layer's matrices (normal(0.02)): its mixer's, then
    its expert part's."""
    d = s["d"]
    if kind == ATTN:
        mixer = {"wq": (d, s["Hq"] * s["hd"]), "wk": (d, s["Hkv"] * s["hd"]),
                 "wv": (d, s["Hkv"] * s["hd"]), "wo": (s["Hq"] * s["hd"], d)}
    else:
        inner = s["H"] * s["P"]
        wide = inner + 2 * s["N"]
        mixer = {"w_z": (d, inner), "w_xbc": (d, wide), "w_dt": (d, s["H"]),
                 "conv_b": (wide,), "w_out": (inner, d)}
    return {**mixer, "router": (d, s["E"]), "shared_gate": (d, s["fs"]),
            "shared_up": (d, s["fs"]), "shared_down": (s["fs"], d),
            "w_gate": (s["G"], d, s["f"]), "w_up": (s["G"], d, s["f"]),
            "w_down": (s["G"], s["f"], d)}


def _others(s: dict, kind: str) -> int:
    """A layer's parameters that are none of ``_matrices``: its two norms,
    and a Mamba-2 mixer's conv taps, ``dt_bias``, ``A_log``, ``D`` and the
    gated norm's gain."""
    inner = s["H"] * s["P"]
    return 2 * s["d"] + (s["taps"] * (inner + 2 * s["N"]) + 3 * s["H"]
                         + inner if kind == MAMBA else 0)


def num_params(config: dict) -> int:
    """Every weight resident on the chip (the head is the embedding)."""
    s = model_sizes(config)
    return s["V"] * s["d"] + s["d"] + sum(
        _others(s, kind) + sum(map(math.prod, _matrices(s, kind).values()))
        for kind in config["layer_types"])


def active_matmul_params(config: dict) -> int:
    """Parameters one token meets in a matmul HERE: a mixer's projections
    (the conv is no matmul), the router, the shared MLP (whole here) and
    the routed experts at what this chip expects of a token's k (its share
    G / E of them: 1.25 of a choice of 10), and the head (the embedding is a
    lookup)."""
    s = model_sizes(config)
    total = s["V"] * s["d"]
    for kind in config["layer_types"]:
        shapes = _matrices(s, kind)
        routed = sum(math.prod(shapes[k]) for k in
                     ("w_gate", "w_up", "w_down")) / s["G"]
        total += sum(math.prod(shape) for name, shape in shapes.items()
                     if name not in ("conv_b", "w_gate", "w_up", "w_down")) \
            + routed * s["k"] * s["G"] / s["E"]
    return int(total)


# The routers start level on one seeded sequence of this many tokens
# (``level``): the mean of 2,048 normed rows lies within 1.2% of a token's own
# spread of the mean of all, and the walk's seconds go with the tokens.
SETTLE_TOKENS = 2048


def level(params: dict, config: dict, key):
    """``params`` with **every router's columns orthogonal to the mean of
    what it reads**, from the seed and the benchmark's own float32 forward
    alone (``reference/granite_hybrid.py``; the program under test is not
    asked): one seeded sequence of SETTLE_TOKENS tokens walks the reference's
    layers once, one ``lax.scan`` a run of one mixer, and before each expert
    part runs, its router ``W_r`` becomes ``W_r - m (m^T W_r) / (m^T m)``
    with ``m`` the mean over the sequence of the normed hidden state the
    router reads, so that no expert's logit has an offset every token
    shares; a later layer is levelled on what the earlier ones, levelled,
    pass on. What a trained model's routers have (an auxiliary loss balanced
    them) and random weights lack: the gated mixers and the SwiGLU parts all
    add a component every token shares, the random ``W_r`` turns it into a
    fixed offset an expert, and the softmax router has no bias that could
    take it out (``assumed.routing`` in the configuration's file has the
    readings). Inside the jitted maker."""
    from benchmark.reference import granite_hybrid as ref
    hp = reference_hyper(config)
    tokens = jax.random.randint(key, (SETTLE_TOKENS,), 0,
                                config["vocab_size"], jnp.int32)
    x = hp.embedding_multiplier * params["tok_emb"][tokens].astype(
        jnp.float32)
    out = dict(params)
    for r, (kind, _) in enumerate(runs(config)):
        def one(x, blk, kind=kind):
            x = ref.after_mixer(blk, x, kind, hp)
            m = jnp.mean(ref.experts_input(blk, x, hp), axis=0)
            w = blk["router"].astype(jnp.float32)
            w = (w - jnp.outer(m, m @ w) / (m @ m)).astype(
                blk["router"].dtype)
            return ref.after_experts({**blk, "router": w}, x, hp)[0], w

        x, routers = jax.lax.scan(one, x, {
            k: v for g in ref.GROUPS
            for k, v in params.get(f"{g}{r}", {}).items()})
        out[f"run{r}"] = {**params[f"run{r}"], "router": routers}
    return out


def drawn(config: dict):
    """``make(lo, hi, stream)``: the weights as they are drawn, before the
    routers are levelled (:func:`make_params`)."""
    s = model_sizes(config)
    dt = DTYPES[config["dtype"]]
    d, H = s["d"], s["H"]
    inner = H * s["P"]
    wide = inner + 2 * s["N"]
    f32 = jnp.float32
    held = runs(config)

    def make(lo, hi, stream):
        top = jax.random.split(_key(lo, hi, stream), 1 + len(held))

        def norm(k, shape):
            return (jax.random.normal(k, shape, f32) * 0.02).astype(dt)

        out = {"tok_emb": norm(top[0], (s["V"], d)),
               "norm_f": jnp.ones((d,), f32)}
        for r, (kind, n) in enumerate(held):
            shapes = _matrices(s, kind)
            ks = jax.random.split(top[1 + r], len(shapes) + 2)
            run = {name: norm(k, (n,) + shape)
                   for k, (name, shape) in zip(ks, shapes.items())}
            run.update(input_ln=jnp.ones((n, d), f32),
                       post_attn_ln=jnp.ones((n, d), f32))
            if kind == MAMBA:
                step = jnp.maximum(jnp.exp(jax.random.uniform(
                    ks[-2], (n, H), f32, math.log(STEP_MIN),
                    math.log(STEP_MAX))), STEP_FLOOR)
                run.update({
                    "conv": jax.random.uniform(
                        ks[-1], (n, s["taps"], wide), f32, -0.5,
                        0.5).astype(dt),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "A_log": jnp.tile(jnp.log(jnp.arange(
                        1, H + 1, dtype=f32)), (n, 1)),
                    "D": jnp.ones((n, H), f32),
                    "ssm_norm": jnp.ones((n, inner), f32)})
            for name, leaf in run.items():
                out.setdefault(f"{GROUP_OF.get(name, 'run')}{r}",
                               {})[name] = leaf
        return out

    return make


def make_params(config: dict, seed: int):
    """normal(0.02) matrices and conv bias, conv taps U(-1/2, 1/2), unit
    norm gains, ``A_log = log(1 .. H)`` (the held heads are the model's
    first), ``D`` = 1 and ``dt_bias`` the inverse softplus of ``max(exp(U(log
    0.001, log 0.1)), 1e-4)`` a head, drawn on the device, and the routers
    levelled (:func:`level`); a run's leaves ``[layers, ...]`` arrays under
    ``run{r}``, ``vec{r}`` and ``out{r}``."""
    make = drawn(config)

    def levelled(lo, hi, stream):
        return level(make(lo, hi, stream), config, _key(lo, hi, stream + 1))

    return jax.jit(levelled)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets), drawn
    from the vocabulary's slice."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   config["vocab_size"])


def to_program(params: dict, config: dict) -> dict:
    """``tepdist_tpu.models.granite_hybrid`` reads the same names."""
    return dict(params)


def program_config(config: dict):
    """The program's ``GraniteHybridConfig`` at this configuration's sizes."""
    p, s = config["program"], model_sizes(config)
    return program.GraniteHybridConfig(
        vocab_size=s["V"], hidden_size=s["d"],
        layer_types=tuple(config["layer_types"]), mamba_n_heads=s["H"],
        mamba_d_head=s["P"], mamba_n_groups=config["mamba_n_groups"],
        mamba_d_state=s["N"], mamba_d_conv=s["taps"],
        num_attention_heads=s["Hq"], num_key_value_heads=s["Hkv"],
        head_dim=s["hd"], intermediate_size=s["f"],
        shared_intermediate_size=s["fs"], num_experts=s["E"],
        experts_held=(int(config["experts_held_first"]), s["G"]),
        num_experts_per_tok=s["k"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
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
    from benchmark.reference import granite_hybrid as ref
    s = model_sizes(config)
    return ref.Hyper(
        heads=s["H"], n_head=s["Hq"], n_kv_head=s["Hkv"], top_k=s["k"],
        held=(int(config["experts_held_first"]), s["G"]),
        kinds=tuple(config["layer_types"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        eps=float(config["rms_norm_eps"]))


# -- what the checks compare ------------------------------------------------

# The leaves outside the layers (every layer's error, the routers' choices
# among them, reaches the tied embedding, through the lookup and through the
# head, and the loss's the final norm), and of what is new here the first
# run's Mamba-2 mixers' own leaves: their float32 vectors (``A_log``, ``D``,
# ``dt_bias``, the conv's bias) and their output projections, whose gradients
# the state-space kernels and the gated norm make themselves.
PROBE = ("tok_emb", "norm_f", "vec0", "out0")


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/granite_hybrid.py``,
    in float32, ``chunk`` sequences at a time. ``cast`` swaps in the
    control's precision."""
    from benchmark.reference import granite_hybrid as ref
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
    the expected 1.25 of a choice of its 10 that the held eighth gets), not
    the weights resident (``resident_params``) and not the whole model's.
    The state-space rule's and the attention's own products are not in it."""
    return {"n_params": active_matmul_params(config),
            "resident_params": num_params(config)}
