"""Kimi Linear family (moonshotai Kimi-Linear-48B-A3B): weights from the
seed, and the hand-over to the program.

As ``builders/sarvam_mla.py``: ``make_params`` draws the weights on the
device in one jitted call, from the seed alone, in the dtype they are trained
in and in the layout the reference reads (``reference/kimi_linear.py``: a
stack a run of consecutive layers of one kind, ``run{r}``), which is also the
program's, so ``to_program`` hands the same tree on. The rest of this file is
the only place where the benchmark touches the program's model code:
building its ``KimiLinearConfig`` from the configuration file, its loss
function and its optimizer. The program's model is imported with this file,
so that a program without it is refused before any weight is drawn.

The configuration file holds the published ``config.json`` keys at its top
level and is read under those names. ``num_experts`` there counts the experts
**held on this chip** (``experts_held_first`` says from which on) and
``router_num_experts`` is the router's published width;
``linear_attn_config`` names the held layers' mixers, numbered from 1 as
published.
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
    from tepdist_tpu.models import kimi_linear as program
except ImportError as e:
    # A program from before the model (the parent of the PR that brought
    # it): say so at once, before weights are drawn or anything compiles.
    raise BenchError("the program under test has no tepdist_tpu.models."
                     "kimi_linear: it cannot run a Kimi Linear "
                     "configuration") from e

KDA, MLA = program.KDA, program.MLA


def mixers(config: dict) -> tuple:
    """The held layers' mixers in order, from ``linear_attn_config``'s two
    lists (published numbering, from 1)."""
    lin = config["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    L = config["num_hidden_layers"]
    if kda & full or kda | full != set(range(1, L + 1)):
        raise BenchError(
            f"linear_attn_config: kda_layers {sorted(kda)} and "
            f"full_attn_layers {sorted(full)} do not name layers 1..{L} "
            "once each")
    return tuple(KDA if i in kda else MLA for i in range(1, L + 1))


def runs(config: dict) -> list:
    """((mixer, dense?), layers) of each run of consecutive layers of one
    kind, in the model's order."""
    out = []
    for i, mixer in enumerate(mixers(config)):
        kind = (mixer, i < config["first_k_dense_replace"])
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(kind, n) for kind, n in out]


def model_sizes(config: dict) -> dict:
    lin = config["linear_attn_config"]
    return {"V": config["vocab_size"], "d": config["hidden_size"],
            "I": config["intermediate_size"],
            "f": config["moe_intermediate_size"],
            "L": config["num_hidden_layers"],
            "Hk": lin["num_heads"], "Dk": lin["head_dim"],
            "taps": lin["short_conv_kernel_size"],
            "Hh": config["num_attention_heads"],
            "R": config["kv_lora_rank"], "Dn": config["qk_nope_head_dim"],
            "Dr": config["qk_rope_head_dim"], "Dv": config["v_head_dim"],
            "E": config["router_num_experts"], "G": config["num_experts"],
            "k": config["num_experts_per_token"],
            "shared": config["num_shared_experts"]}


def _mixer_shapes(s: dict, mixer: str) -> dict:
    """name -> shape of a mixer's matrices and conv taps."""
    d = s["d"]
    if mixer == MLA:
        return {"wq": (d, s["Hh"] * (s["Dn"] + s["Dr"])),
                "wkva": (d, s["R"] + s["Dr"]),
                "wkvb": (s["R"], s["Hh"] * (s["Dn"] + s["Dv"])),
                "wo": (s["Hh"] * s["Dv"], d)}
    P, D = s["Hk"] * s["Dk"], s["Dk"]
    return {"wq": (d, P), "wk": (d, P), "wv": (d, P),
            "conv_q": (s["taps"], P), "conv_k": (s["taps"], P),
            "conv_v": (s["taps"], P), "wfa": (d, D), "wfb": (D, P),
            "wb": (d, s["Hk"]), "wga": (d, D), "wgb": (D, P), "wo": (P, d)}


def _follows_shapes(s: dict, dense: bool) -> dict:
    d, f = s["d"], s["f"]
    if dense:
        return {"w_gate": (d, s["I"]), "w_up": (d, s["I"]),
                "w_down": (s["I"], d)}
    fs = f * s["shared"]
    return {"router": (d, s["E"]), "shared_gate": (d, fs),
            "shared_up": (d, fs), "shared_down": (fs, d),
            "w_gate": (s["G"], d, f), "w_up": (s["G"], d, f),
            "w_down": (s["G"], f, d)}


def _vectors(s: dict, mixer: str, dense: bool) -> int:
    """A layer's parameters that are no matrix: norm gains, the decays'
    ``A_log`` and ``dt_bias``, the selection bias."""
    n = 2 * s["d"] + (s["R"] if mixer == MLA
                      else s["Hk"] + s["Hk"] * s["Dk"] + s["Dk"])
    return n + (0 if dense else s["E"])


def num_params(config: dict) -> int:
    """Every weight resident on the chip."""
    s = model_sizes(config)
    total = 2 * s["V"] * s["d"] + s["d"]
    for (mixer, dense), n in runs(config):
        total += n * (_vectors(s, mixer, dense) + sum(map(math.prod, (
            *_mixer_shapes(s, mixer).values(),
            *_follows_shapes(s, dense).values()))))
    return total


def active_matmul_params(config: dict) -> int:
    """Parameters one token meets in a matmul HERE: a mixer's projections
    (the convs are no matmul), the dense layer's MLP or the router, the
    shared expert and the routed experts at what this chip expects of a
    token's k (its share G / E of them: half a choice of 8), and the head
    (the embedding is a lookup)."""
    s = model_sizes(config)
    d, f = s["d"], s["f"]
    total = s["V"] * d
    for (mixer, dense), n in runs(config):
        mix = sum(math.prod(shape) for name, shape in
                  _mixer_shapes(s, mixer).items()
                  if not name.startswith("conv_"))
        follows = 3 * d * s["I"] if dense else d * s["E"] + 3 * d * f * (
            s["shared"] + s["k"] * s["G"] / s["E"])
        total += n * (mix + follows)
    return int(total)


def make_params(config: dict, seed: int):
    """normal(0.02) matrices and conv taps, unit RMSNorm gains, a zero
    selection bias, ``A_log = log U(1, 16)`` a head and ``dt_bias`` the
    inverse softplus of ``exp(U(log 1e-3, log 1e-1))`` a channel, drawn on
    the device; ``run{r}`` is one dict of ``[layers, ...]`` arrays."""
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
               "norm_f": jnp.ones((d,), f32),
               "lm_head": norm(top[1], (s["V"], d))}
        for r, ((mixer, dense), n) in enumerate(held):
            shapes = {**_mixer_shapes(s, mixer), **_follows_shapes(s, dense)}
            ks = jax.random.split(top[2 + r], len(shapes) + 2)
            run = {"input_ln": jnp.ones((n, d), f32),
                   "post_attn_ln": jnp.ones((n, d), f32)}
            for k, (name, shape) in zip(ks, shapes.items()):
                run[name] = norm(k, (n,) + shape)
            if mixer == MLA:
                run["kv_ln"] = jnp.ones((n, s["R"]), f32)
            else:
                P = s["Hk"] * s["Dk"]
                step = jnp.exp(jax.random.uniform(
                    ks[-2], (n, P), f32, jnp.log(1e-3), jnp.log(1e-1)))
                run["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
                run["A_log"] = jnp.log(jax.random.uniform(
                    ks[-1], (n, s["Hk"]), f32, 1.0, 16.0))
                run["o_norm"] = jnp.ones((n, s["Dk"]), f32)
            if not dense:
                run["router_bias"] = jnp.zeros((n, s["E"]), f32)
            out[f"run{r}"] = run
        return out

    return jax.jit(make)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets), drawn
    from the vocabulary's slice."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   config["vocab_size"])


def to_program(params: dict, config: dict) -> dict:
    """``tepdist_tpu.models.kimi_linear`` reads the same names."""
    return dict(params)


def program_config(config: dict):
    """The program's ``KimiLinearConfig`` at this configuration's sizes."""
    p, s = config["program"], model_sizes(config)
    if config.get("rope_scaling") is not None or not config["mla_use_nope"]:
        raise BenchError("a latent layer without positions (mla_use_nope "
                         "true, no rope_scaling) is what is built here")
    return program.KimiLinearConfig(
        vocab_size=s["V"], hidden_size=s["d"], intermediate_size=s["I"],
        moe_intermediate_size=s["f"], mixers=mixers(config),
        first_k_dense_replace=config["first_k_dense_replace"],
        kda_num_heads=s["Hk"], kda_head_dim=s["Dk"],
        short_conv_kernel_size=s["taps"], num_attention_heads=s["Hh"],
        kv_lora_rank=s["R"], qk_nope_head_dim=s["Dn"],
        qk_rope_head_dim=s["Dr"], v_head_dim=s["Dv"], num_experts=s["E"],
        experts_held=(int(config["experts_held_first"]), s["G"]),
        num_experts_per_tok=s["k"], num_shared_experts=s["shared"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        dtype=DTYPES[config["dtype"]],
        flash_block_q=int(p.get("flash_block_q", 0)),
        flash_block_k=int(p.get("flash_block_k", 0)),
        kda_chunk=int(p["kda_chunk"]),
        remat=bool(p.get("remat")),
        loss_chunk=int(p.get("loss_chunk", 0)),
        moe_tile_m=int(p["moe_tile_m"]))


def program_loss_fn(config: dict):
    """``loss(params, tokens)`` of the program under test."""
    cfg = program_config(config)
    return lambda p, t: program.loss_fn(p, t, cfg)


def reference_hyper(config: dict):
    from benchmark.reference import kimi_linear as ref
    s = model_sizes(config)
    return ref.Hyper(
        kda_heads=s["Hk"], qk_nope_head_dim=s["Dn"],
        qk_rope_head_dim=s["Dr"], v_head_dim=s["Dv"], kv_lora_rank=s["R"],
        top_k=s["k"], held=(int(config["experts_held_first"]), s["G"]),
        route_scale=float(config["routed_scaling_factor"]),
        eps=float(config["rms_norm_eps"]))


# -- what the checks compare ------------------------------------------------

# The leaves outside the layers: every layer's error, the routers' choices
# among them, reaches the embedding, and the loss's the head and the final
# norm, so their gradients stand for the whole step.
PROBE = ("tok_emb", "lm_head", "norm_f")


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/kimi_linear.py``,
    in float32, ``chunk`` sequences at a time. ``cast`` swaps in the
    control's precision."""
    from benchmark.reference import kimi_linear as ref
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
    the expected half of a choice of its 8 that the held sixteenth gets),
    not the weights resident (``resident_params``) and not the whole
    model's. The delta rule's and the attention's own products are not in
    it."""
    return {"n_params": active_matmul_params(config),
            "resident_params": num_params(config)}
