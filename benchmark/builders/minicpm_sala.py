"""MiniCPM-SALA family (openbmb ``model_type: minicpm_sala``): weights from
the seed, and the hand-over to the program.

As ``builders/jamba.py``: ``make_params`` draws the weights on the device in
one jitted call, from the seed alone, in the dtype they are trained in and in
the layout the reference reads (``reference/minicpm_sala.py``: a stack a run
of consecutive layers of one kind, its leaves in the groups ``run{r}`` and
``vec{r}``), which is also the program's, so ``to_program`` hands the same
tree on. The rest of this file is the only place where the benchmark touches
the program's model code: building its ``MiniCPMSALAConfig`` from the
configuration file, its loss function and its optimizer. The program's model
is imported with this file, so that a program without it is refused before
any weight is drawn.

The configuration file holds the published ``config.json`` keys at its top
level and is read under those names; InfLLM-v2's geometry, which the
published config does not carry, under ``sparse_config`` (MiniCPM4's), and
where the held layers stand among the published ones under ``held``.
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
    from tepdist_tpu.models import minicpm_sala as program
    from tepdist_tpu.ops.pallas import block_topk_attention, \
        lightning_attention
except ImportError as e:
    # A program from before the model (the parent of the PR that brought
    # it): say so at once, before weights are drawn or anything compiles.
    raise BenchError("the program under test has no tepdist_tpu.models."
                     "minicpm_sala: it cannot run a MiniCPM-SALA "
                     "configuration") from e

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_GEOMETRY = ("block_size", "kernel_size", "kernel_stride", "init_blocks",
             "window_size", "topk", "dense_len")


def model_sizes(config: dict) -> dict:
    return {"V": config["vocab_size"], "d": config["hidden_size"],
            "f": config["intermediate_size"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "hd": config["head_dim"],
            "Hl": config["lightning_nh"], "hdl": config["lightning_head_dim"]}


def runs(config: dict) -> list:
    """(kind, layers) of each run of consecutive layers of one kind, in the
    order ``mixer_types`` gives."""
    kinds = list(config["mixer_types"])
    if len(kinds) != config["num_hidden_layers"] \
            or set(kinds) - {SPARSE, LIGHTNING}:
        raise BenchError(f"mixer_types {kinds} for "
                         f"{config['num_hidden_layers']} layers")
    out = []
    for kind in kinds:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [tuple(r) for r in out]


def _mixer_matmul_params(s: dict, kind: str) -> int:
    if kind == SPARSE:       # q, o, the output gate; k, v
        return 3 * s["d"] * s["H"] * s["hd"] + 2 * s["d"] * s["Hkv"] * s["hd"]
    return 5 * s["d"] * s["Hl"] * s["hdl"]       # q, k, v, o, the gate


def _layer_params(s: dict, kind: str) -> int:
    # two pre-norms, the QK-norm gains, a lightning layer's output norm
    other = 2 * s["d"] + (2 * s["hd"] if kind == SPARSE
                          else 2 * s["hdl"] + s["Hl"] * s["hdl"])
    return _mixer_matmul_params(s, kind) + 3 * s["d"] * s["f"] + other


def num_params(config: dict) -> int:
    """Every weight resident on the chip (embedding and untied head)."""
    s = model_sizes(config)
    return 2 * s["V"] * s["d"] + s["d"] + sum(
        n * _layer_params(s, kind) for kind, n in runs(config))


def block_params(config: dict) -> int:
    """The weights inside the layers: what a gradient-accumulation step adds
    inside the backward layer loop."""
    s = model_sizes(config)
    return num_params(config) - 2 * s["V"] * s["d"] - s["d"]


def active_matmul_params(config: dict) -> int:
    """Parameters one token meets in a matmul: every mixer's projections,
    every MLP and the head (the embedding is a lookup; the norms, the
    linear-attention state and the attention's scores are not parameters)."""
    s = model_sizes(config)
    return s["V"] * s["d"] + sum(
        n * (_mixer_matmul_params(s, kind) + 3 * s["d"] * s["f"])
        for kind, n in runs(config))


def make_params(config: dict, seed: int):
    """normal(0.02) matrices, unit RMSNorm gains, drawn on the device; every
    leaf of a run is one ``[layers of the run, ...]`` array, in the run's
    groups."""
    from benchmark.reference.minicpm_sala import split_groups
    s = model_sizes(config)
    dt = DTYPES[config["dtype"]]
    d, f = s["d"], s["f"]
    f32 = jnp.float32

    def make(lo, hi, stream):
        key = _key(lo, hi, stream)

        def norm(k, shape):
            return (jax.random.normal(k, shape, f32) * 0.02).astype(dt)

        out = {"tok_emb": norm(jax.random.fold_in(key, 0), (s["V"], d)),
               "lm_head": norm(jax.random.fold_in(key, 1), (s["V"], d)),
               "norm_f": jnp.ones((d,), f32)}
        for r, (kind, n) in enumerate(runs(config)):
            ks = jax.random.split(jax.random.fold_in(key, 2 + r), 8)
            hd = s["hd"] if kind == SPARSE else s["hdl"]
            q_dim = (s["H"] if kind == SPARSE else s["Hl"]) * hd
            kv_dim = s["Hkv"] * hd if kind == SPARSE else q_dim
            run = {"input_ln": jnp.ones((n, d), f32),
                   "ff_ln": jnp.ones((n, d), f32),
                   "w_gate": norm(ks[0], (n, d, f)),
                   "w_up": norm(ks[1], (n, d, f)),
                   "w_down": norm(ks[2], (n, f, d)),
                   "wq": norm(ks[3], (n, d, q_dim)),
                   "wk": norm(ks[4], (n, d, kv_dim)),
                   "wv": norm(ks[5], (n, d, kv_dim)),
                   "wg": norm(ks[6], (n, d, q_dim)),
                   "wo": norm(ks[7], (n, q_dim, d)),
                   "q_norm": jnp.ones((n, hd), f32),
                   "k_norm": jnp.ones((n, hd), f32)}
            if kind == LIGHTNING:
                run["o_norm"] = jnp.ones((n, q_dim), f32)
            out.update(split_groups(run, r))
        return out

    return jax.jit(make)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets), drawn
    from the vocabulary (or its slice)."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   config["vocab_size"])


def to_program(params: dict, config: dict) -> dict:
    """``tepdist_tpu.models.minicpm_sala`` reads the same names."""
    return dict(params)


def program_config(config: dict):
    """The program's ``MiniCPMSALAConfig`` at this configuration's sizes."""
    p, held = config["program"], config["held"]
    return program.MiniCPMSALAConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        lightning_nh=config["lightning_nh"],
        lightning_head_dim=config["lightning_head_dim"],
        mixer_types=tuple(config["mixer_types"]),
        first_layer=int(held["first_layer"]),
        published_layers=int(held["published_layers"]),
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        scale_emb=float(config["scale_emb"]),
        scale_depth=float(config["scale_depth"]),
        dim_model_base=int(config["dim_model_base"]),
        sparse=block_topk_attention.BlockGeometry(
            **{k: int(config["sparse_config"][k]) for k in _GEOMETRY}),
        dtype=DTYPES[config["dtype"]],
        flash_block_q=int(p.get("flash_block_q", 0)),
        flash_block_k=int(p.get("flash_block_k", 0)),
        remat=bool(p.get("remat")),
        loss_chunk=int(p.get("loss_chunk", 0)))


def program_loss_fn(config: dict):
    """``loss(params, tokens)`` of the program under test."""
    cfg = program_config(config)
    return lambda p, t: program.loss_fn(p, t, cfg)


def reference_hyper(config: dict):
    from benchmark.reference import minicpm_sala as ref
    held = config["held"]
    return ref.Hyper(
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        lightning_heads=config["lightning_nh"],
        mixer_types=tuple(config["mixer_types"]),
        first_layer=int(held["first_layer"]),
        published_layers=int(held["published_layers"]),
        scale_emb=float(config["scale_emb"]),
        scale_depth=float(config["scale_depth"]),
        dim_model_base=int(config["dim_model_base"]),
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        **{k: int(config["sparse_config"][k]) for k in _GEOMETRY})


# -- what the checks compare ------------------------------------------------

# The leaves outside the layers (every layer's error reaches the embedding,
# the loss's the head and the final norm) and, inside each of one period's
# two walks, the norm gains: the sparse layer's (two pre-norms, the QK-norm)
# and the lightning run's (those and the output norm). Their gradients are
# added inside the walks' backward layer loops, so a walk whose accumulation
# or update goes wrong shows in its own slot.
PROBE = ("tok_emb", "lm_head", "norm_f", "vec0", "vec1")


def hold_the_kernels(config: dict, tokens) -> dict:
    """The program's linear-attention kernels alone, at this configuration's
    heads and the check batch's length, against the sequential float32
    recurrence (``kernels/lightning_check.py``, operands from the batch's
    first tokens, results asked for in float32): ``{name: distance}``,
    printed. The largest has to stay under the configuration's
    ``lightning_check`` limit: kernels whose state or accumulation is not
    the float32 the configuration states are refused here, since no number
    of the step check tells them."""
    from benchmark.kernels import lightning_check
    from benchmark.reference import minicpm_sala as ref
    H, D = config["lightning_nh"], config["lightning_head_dim"]
    seed = int(tokens[0, 0]) * config["vocab_size"] + int(tokens[0, 1])
    inputs = lightning_check.make_inputs(
        (1, tokens.shape[1] - 1, H, D), DTYPES[config["dtype"]], seed)
    layer = int(config["held"]["first_layer"]) \
        + list(config["mixer_types"]).index(LIGHTNING)
    f32 = jnp.float32

    def kernels(q, k, v, log_decay, do):
        return (lightning_attention.forward(q, k, v, log_decay,
                                            out_dtype=f32),) \
            + lightning_attention.backward(q, k, v, log_decay, do,
                                           out_dtype=f32)

    read = lightning_check.against_sequential(
        kernels, inputs, ref.decays(reference_hyper(config), layer))
    limit = float(config["lightning_check"]["rel_err"])
    print(f"lightning check: {read} (limit {limit})", flush=True)
    worst = max(read.values())
    if not worst <= limit:
        raise BenchError(
            f"the program's linear-attention kernels stand {worst:.3e} from "
            f"the sequential float32 recurrence (limit {limit}): their "
            "state or accumulation is below the float32 the configuration "
            "states")
    return read


def sets_differing(config: dict, params, tokens):
    """Share of the first sequence's (query, group) pairs whose chosen sets
    differ between the program (bf16 projections, ``select_blocks``) and the
    float32 reference (``chosen_blocks``), where the first held layer is a
    sparse one and the sequence is past ``dense_len``; else None. Printed: a
    set that differs moves the step's result discretely, so it is part of
    what the step check's sound readings hold."""
    from benchmark.reference import minicpm_sala as ref
    hp, cfg = reference_hyper(config), program_config(config)
    T = tokens.shape[1] - 1
    if config["mixer_types"][0] != SPARSE or T <= hp.dense_len:
        return None
    blk = {k: v[0] for g in ref.GROUPS for k, v in params[f"{g}0"].items()}
    emb = params["tok_emb"][tokens[0, :-1]]

    @jax.jit
    def theirs(blk, emb):
        x = hp.scale_emb * emb.astype(jnp.float32)
        q, k, _ = ref.sparse_heads(
            blk, ref._rms_norm(x, blk["input_ln"], hp.eps), hp, ref.identity)
        return ref.chosen_blocks(q, k, hp)

    @jax.jit
    def ours(blk, emb):
        x = (emb.astype(jnp.float32) * cfg.scale_emb).astype(cfg.dtype)[None]
        a = program.rms_norm(x, blk["input_ln"], cfg.rms_norm_eps)
        q, k, _ = program.mixer_inputs(blk, a, cfg, SPARSE, 0)
        idx = block_topk_attention.select_blocks(q, k, cfg.sparse)[0]
        G = idx.shape[0]
        return jnp.zeros((G, T, T // cfg.sparse.block_size), bool).at[
            jnp.arange(G)[:, None, None], jnp.arange(T)[None, :, None],
            idx].set(True)

    share = float(jnp.mean(jnp.any(theirs(blk, emb) != ours(blk, emb),
                                   axis=-1)))
    print(f"sets check: {share:.6f} of the first sequence's (query, group) "
          "pairs choose another set in the bf16 program than in the float32 "
          "reference", flush=True)
    return share


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/minicpm_sala.py``,
    in float32, ``chunk`` sequences at a time. ``cast`` swaps in the
    control's precision. The reference itself (no ``cast``) first holds the
    program's linear-attention kernels to the sequential recurrence and
    counts the sparse layer's differing sets, once
    (:func:`hold_the_kernels`, :func:`sets_differing`)."""
    from benchmark.reference import minicpm_sala as ref
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
            hold_the_kernels(config, tokens)
            sets_differing(config, params, tokens)
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
    the head). The attention's and the linear attention's own products are
    not in it; ``resident_params`` is every weight on the chip."""
    return {"n_params": active_matmul_params(config),
            "resident_params": num_params(config)}
