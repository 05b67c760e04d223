"""Jamba family (AI21 ``model_type: jamba``): weights from the seed, and the
hand-over to the program.

As ``builders/mellum.py``: ``make_params`` draws the weights on the device in
one jitted call, from the seed alone, in the dtype they are trained in and in
the layout the reference reads (``reference/jamba.py``: a stack a run of
consecutive layers of one kind, its leaves in the groups ``run{r}``,
``vec{r}``, ``decay{r}``), which is also the program's, so ``to_program``
hands the same tree on. The rest of this file is
the only place where the benchmark touches the program's model code: building
its ``JambaConfig`` from the configuration file, its loss function and its
optimizer. The program's model is imported with this file, so that a program
without it is refused before any weight is drawn.

The configuration file holds the published ``config.json`` keys at its top
level and is read under those names.
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
    from tepdist_tpu.models import jamba as program
except ImportError as e:
    # A program from before the model (the parent of the PR that brought
    # it): say so at once, before weights are drawn or anything compiles.
    raise BenchError("the program under test has no tepdist_tpu.models."
                     "jamba: it cannot run a Jamba configuration") from e


def model_sizes(config: dict) -> dict:
    d = config["hidden_size"]
    return {"V": config["vocab_size"], "d": d,
            "f": config["intermediate_size"],
            "L": config["num_hidden_layers"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"],
            "hd": d // config["num_attention_heads"],
            "Di": config["mamba_expand"] * d, "N": config["mamba_d_state"],
            "K": config["mamba_d_conv"], "R": config["mamba_dt_rank"],
            "period": config["attn_layer_period"],
            "offset": config["attn_layer_offset"]}


def runs(config: dict) -> list:
    """(attention?, layers) of each run of consecutive layers of one kind,
    in the model's order: layer ``i`` is attention iff ``i % period ==
    offset``."""
    s = model_sizes(config)
    out = []
    for i in range(s["L"]):
        attention = i % s["period"] == s["offset"]
        if out and out[-1][0] == attention:
            out[-1][1] += 1
        else:
            out.append([attention, 1])
    return [tuple(r) for r in out]


def _mixer_matmul_params(s: dict, attention: bool) -> int:
    if attention:
        d, hd = s["d"], s["hd"]
        return 2 * d * s["H"] * hd + 2 * d * s["Hkv"] * hd      # q, o; k, v
    # in_proj, x_proj, dt_proj, out_proj
    return s["d"] * 2 * s["Di"] + s["Di"] * (s["R"] + 2 * s["N"]) \
        + s["R"] * s["Di"] + s["Di"] * s["d"]


def _layer_params(s: dict, attention: bool) -> int:
    other = 2 * s["d"]                                         # two norms
    if not attention:
        # the conv and its bias, dt_proj's bias, A_log, D, three inner norms
        other += s["Di"] * s["K"] + s["Di"] + s["Di"] + s["Di"] * s["N"] \
            + s["Di"] + s["R"] + 2 * s["N"]
    return _mixer_matmul_params(s, attention) + 3 * s["d"] * s["f"] + other


def num_params(config: dict) -> int:
    """Every weight resident on the chip (the head is the tied embedding)."""
    s = model_sizes(config)
    return s["V"] * s["d"] + s["d"] + sum(
        n * _layer_params(s, attention) for attention, n in runs(config))


def block_params(config: dict) -> int:
    """The weights inside the layers: what a gradient-accumulation step adds
    inside the backward layer loop."""
    s = model_sizes(config)
    return num_params(config) - s["V"] * s["d"] - s["d"]


def active_matmul_params(config: dict) -> int:
    """Parameters one token meets in a matmul: every mixer's projections,
    every MLP and the head (the embedding is a lookup; the conv, the scan and
    the norms are not matmuls and are not counted)."""
    s = model_sizes(config)
    return s["V"] * s["d"] + sum(
        n * (_mixer_matmul_params(s, attention) + 3 * s["d"] * s["f"])
        for attention, n in runs(config))


def make_params(config: dict, seed: int):
    """normal(0.02) matrices, unit RMSNorm gains, zero conv bias, ``A_log =
    log(1..N)`` for every channel, ``D = 1`` and ``dt_bias`` the inverse
    softplus of ``dt = exp(U(log 1e-3, log 1e-1))`` (Mamba-1's published
    initialisation), drawn on the device; every leaf of a run is one
    ``[layers of the run, ...]`` array, in the run's groups."""
    from benchmark.reference.jamba import split_groups
    s = model_sizes(config)
    dt = DTYPES[config["dtype"]]
    d, f, Di, N, R, K = s["d"], s["f"], s["Di"], s["N"], s["R"], s["K"]
    H, Hkv, hd = s["H"], s["Hkv"], s["hd"]
    f32 = jnp.float32

    def make(lo, hi, stream):
        key = _key(lo, hi, stream)

        def norm(k, shape):
            return (jax.random.normal(k, shape, f32) * 0.02).astype(dt)

        out = {"tok_emb": norm(jax.random.fold_in(key, 0), (s["V"], d)),
               "norm_f": jnp.ones((d,), f32)}
        for r, (attention, n) in enumerate(runs(config)):
            ks = jax.random.split(jax.random.fold_in(key, 1 + r), 9)
            run = {"input_ln": jnp.ones((n, d), f32),
                   "ff_ln": jnp.ones((n, d), f32),
                   "w_gate": norm(ks[0], (n, d, f)),
                   "w_up": norm(ks[1], (n, d, f)),
                   "w_down": norm(ks[2], (n, f, d))}
            if attention:
                run.update(wq=norm(ks[3], (n, d, H * hd)),
                           wk=norm(ks[4], (n, d, Hkv * hd)),
                           wv=norm(ks[5], (n, d, Hkv * hd)),
                           wo=norm(ks[6], (n, H * hd, d)))
            else:
                step = jnp.exp(jax.random.uniform(
                    ks[8], (n, Di), f32, math.log(1e-3), math.log(1e-1)))
                run.update(
                    in_proj=norm(ks[3], (n, d, 2 * Di)),
                    conv_w=norm(ks[4], (n, K, Di)),
                    conv_b=jnp.zeros((n, Di), dt),
                    x_proj=norm(ks[5], (n, Di, R + 2 * N)),
                    dt_norm=jnp.ones((n, R), f32),
                    b_norm=jnp.ones((n, N), f32),
                    c_norm=jnp.ones((n, N), f32),
                    dt_proj=norm(ks[6], (n, R, Di)),
                    dt_bias=step + jnp.log(-jnp.expm1(-step)),
                    A_log=jnp.broadcast_to(
                        jnp.log(jnp.arange(1, N + 1, dtype=f32)),
                        (n, Di, N)),
                    D=jnp.ones((n, Di), f32),
                    out_proj=norm(ks[7], (n, Di, d)))
            out.update(split_groups(run, r))
        return out

    return jax.jit(make)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets), drawn
    from the vocabulary (or its slice)."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   config["vocab_size"])


def to_program(params: dict, config: dict) -> dict:
    """``tepdist_tpu.models.jamba`` reads the same names."""
    return dict(params)


def program_config(config: dict):
    """The program's ``JambaConfig`` at this configuration's sizes."""
    p = config["program"]
    # The scan kernel's tiling, where a file sets it (a tiny test size does).
    more = {k: int(p[k]) for k in ("ssm_chunk", "ssm_block_d") if k in p}
    return program.JambaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_dt_rank=config["mamba_dt_rank"],
        mamba_expand=config["mamba_expand"],
        rms_norm_eps=float(config["rms_norm_eps"]),
        dtype=DTYPES[config["dtype"]],
        flash_block_q=int(p.get("flash_block_q", 0)),
        flash_block_k=int(p.get("flash_block_k", 0)),
        remat=bool(p.get("remat")),
        loss_chunk=int(p.get("loss_chunk", 0)), **more)


def program_loss_fn(config: dict):
    """``loss(params, tokens)`` of the program under test."""
    cfg = program_config(config)
    return lambda p, t: program.loss_fn(p, t, cfg)


def reference_hyper(config: dict):
    from benchmark.reference import jamba as ref
    return ref.Hyper(
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        d_state=config["mamba_d_state"], dt_rank=config["mamba_dt_rank"],
        eps=float(config["rms_norm_eps"]))


# -- what the checks compare ------------------------------------------------

# The leaves outside the layers (every layer's error reaches the embedding,
# which is also the head, and the loss's the final norm) and, inside each of
# one period's three walks, the small groups: a Mamba run's per-channel
# leaves and its ``A_log``, the attention layer's two norm gains. Their
# gradients are added inside the walks' backward layer loops, so a walk whose
# accumulation or update goes wrong shows in its own slot.
PROBE = ("tok_emb", "norm_f", "vec0", "decay0", "vec1", "vec2", "decay2")


def hold_the_scan(config: dict, tokens) -> dict:
    """The program's selective scan alone, at this configuration's sizes and
    the check batch's length, against the sequential float32 scan
    (``kernels/ssm_check.py``, operands from the batch's first tokens):
    ``{name: distance}``, printed. The gradient of ``A``, which only the
    state reaches and which is float32 on both sides, has to stay under the
    configuration's ``scan_check`` limit: a scan whose state, ``delta``,
    ``exp`` or accumulation is not the float32 the configuration states is
    refused here, since no number of the step check tells it."""
    from benchmark.kernels import ssm_check
    cfg = program_config(config)
    seed = int(tokens[0, 0]) * config["vocab_size"] + int(tokens[0, 1])
    inputs = ssm_check.make_inputs(
        (1, tokens.shape[1] - 1, cfg.d_inner), cfg.mamba_d_state, cfg.dtype,
        seed)
    read = ssm_check.against_sequential(
        lambda *a: program.selective_scan(
            *a, chunk=cfg.ssm_chunk, block_d=cfg.ssm_block_d), inputs)
    limit = float(config["scan_check"]["dA_rel_err"])
    print(f"scan check: {read} (dA's limit {limit})", flush=True)
    if not read["dA"] <= limit:
        raise BenchError(
            f"the program's selective scan stands {read['dA']:.3e} from the "
            f"sequential float32 scan in the gradient of A (limit {limit}): "
            "its state, delta, exp or accumulation is below the float32 "
            "the configuration states")
    return read


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/jamba.py``, in
    float32, ``chunk`` sequences at a time. ``cast`` swaps in the control's
    precision. The reference itself (no ``cast``) first holds the program's
    scan kernel to the sequential scan, once (:func:`hold_the_scan`)."""
    from benchmark.reference import jamba as ref
    hp = reference_hyper(config)
    held = cast is not None
    cast = cast or ref.identity

    @jax.jit
    def part(params, probe, tokens, weights):
        return jax.value_and_grad(lambda pr: ref.loss(
            {**params, **pr}, tokens, hp, cast, weights))(probe)

    def run(params, tokens, weights):
        nonlocal held
        if tokens.shape[0] % chunk:
            raise ValueError(f"{tokens.shape[0]} sequences do not split "
                             f"into chunks of {chunk}")
        if not held:
            hold_the_scan(config, tokens)
            held = True
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
    parameters a token meets in a matmul (the mixers' projections, the MLPs,
    the head). The selective scan's operations are not matmuls and are not in
    it; ``resident_params`` is every weight on the chip."""
    return {"n_params": active_matmul_params(config),
            "resident_params": num_params(config)}
