"""GPT-2 family: weights from the seed, and the hand-over to the program.

The benchmark owns the inputs: ``make_params`` draws the weights on the
device in one jitted call, from the seed alone, in the dtype they are
trained or served in and in the layout the reference reads
(``reference/gpt2.py``). ``to_program`` re-labels that tree for
``tepdist_tpu.models.gpt2`` without copying. The rest of this file is the
only place where the benchmark touches the program's model code: building
its ``GPT2Config`` from the configuration file, its loss function, its
optimizer and its serving engine.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _seed_words(seed: int, stream: int):
    """Any whole seed, also one that 32 signed bits do not hold, as three
    small integers a jitted function takes as arguments."""
    seed = int(seed)
    return (np.int32(seed % (2 ** 31)), np.int32(seed // (2 ** 31)),
            np.int32(stream))


def _key(lo, hi, stream):
    """The random key of (seed, stream); runs inside the jitted makers so
    that drawing weights or a batch is one dispatch."""
    key = jax.random.fold_in(jax.random.key(lo), hi)
    return jax.random.fold_in(key, stream)


def model_sizes(config: dict) -> dict:
    m = config["model"]
    return {"V": m["n_vocab"], "n_ctx": m["n_ctx"], "d": m["n_embd"],
            "L": m["n_layer"], "H": m["n_head"]}


def num_params(config: dict) -> int:
    """Weights counted once (the output head is the tied embedding)."""
    s = model_sizes(config)
    d = s["d"]
    return s["V"] * d + s["n_ctx"] * d + s["L"] * (12 * d * d + 13 * d) \
        + 2 * d


def make_params(config: dict, seed: int):
    """GPT-2's initialisation (normal 0.02, residual projections scaled by
    1/sqrt(2L), unit LayerNorm gains, zero biases), drawn on the device.
    Layout: ``blocks`` is one dict of ``[L, ...]`` arrays when the
    configuration's program scans its layers, else a list of L dicts."""
    s = model_sizes(config)
    dt = DTYPES[config["dtype"]]
    stacked = bool(config["program"].get("stacked"))
    d, L = s["d"], s["L"]
    std, resid = 0.02, 0.02 / math.sqrt(2 * L)
    f32 = jnp.float32

    def make(lo, hi, stream):
        ks = jax.random.split(_key(lo, hi, stream), 6)

        def norm(k, shape, sd):
            return (jax.random.normal(k, shape, f32) * sd).astype(dt)

        blocks = {
            "ln1_g": jnp.ones((L, d), f32), "ln1_b": jnp.zeros((L, d), f32),
            "attn_qkv_w": norm(ks[2], (L, d, 3 * d), std),
            "attn_qkv_b": jnp.zeros((L, 3 * d), dt),
            "attn_proj_w": norm(ks[3], (L, d, d), resid),
            "attn_proj_b": jnp.zeros((L, d), dt),
            "ln2_g": jnp.ones((L, d), f32), "ln2_b": jnp.zeros((L, d), f32),
            "mlp_fc_w": norm(ks[4], (L, d, 4 * d), std),
            "mlp_fc_b": jnp.zeros((L, 4 * d), dt),
            "mlp_proj_w": norm(ks[5], (L, 4 * d, d), resid),
            "mlp_proj_b": jnp.zeros((L, d), dt),
        }
        if not stacked:
            blocks = [{k: v[i] for k, v in blocks.items()}
                      for i in range(L)]
        return {"wte": norm(ks[0], (s["V"], d), std),
                "wpe": norm(ks[1], (s["n_ctx"], d), std),
                "ln_f_g": jnp.ones((d,), f32),
                "ln_f_b": jnp.zeros((d,), f32), "blocks": blocks}

    return jax.jit(make)(*_seed_words(seed, 1))


def make_tokens(config: dict, seed: int, stream: int, batch: int, seq: int):
    """``[batch, seq + 1]`` token ids (inputs and shifted targets)."""
    return _tokens(*_seed_words(seed, stream), batch, seq + 1,
                   model_sizes(config)["V"])


def _tokens_impl(lo, hi, stream, batch, length, vocab):
    return jax.random.randint(_key(lo, hi, stream), (batch, length), 0,
                              vocab, jnp.int32)


_tokens = jax.jit(_tokens_impl, static_argnums=(3, 4, 5))


def to_program(params: dict, config: dict) -> dict:
    """The same arrays under the names ``tepdist_tpu.models.gpt2`` reads."""
    if isinstance(params["blocks"], dict):
        return dict(params)
    out = {k: v for k, v in params.items() if k != "blocks"}
    out.update({f"h{i}": blk for i, blk in enumerate(params["blocks"])})
    return out


def program_config(config: dict):
    """The program's ``GPT2Config`` at this configuration's sizes."""
    from tepdist_tpu.models import gpt2
    s, p = model_sizes(config), config["program"]
    return gpt2.GPT2Config(
        vocab_size=s["V"], n_ctx=s["n_ctx"], n_embd=s["d"], n_layer=s["L"],
        n_head=s["H"], dtype=DTYPES[config["dtype"]],
        attn=p.get("attn", "einsum"), remat=bool(p.get("remat")),
        remat_policy=p.get("remat_policy", "full"),
        flash_block_q=int(p.get("flash_block_q", 0)),
        flash_block_k=int(p.get("flash_block_k", 0)),
        loss_chunk=int(p.get("loss_chunk", 0)))


def program_loss_fn(config: dict):
    """``loss(params, tokens)`` of the program under test."""
    from tepdist_tpu.models import gpt2
    cfg = program_config(config)
    inner = gpt2.loss_fn_stacked if config["program"].get("stacked") \
        else gpt2.loss_fn
    return lambda p, t: inner(p, t, cfg)


def program_optimizer(config: dict):
    from tepdist_tpu.optim import make_optimizer
    return make_optimizer(dict(config["optimizer"]))


def serving_engine(config: dict, traffic: dict, params_program):
    """The paged engine in this process; nothing outlives the run."""
    from tepdist_tpu.serving.engine import ServingEngine
    e = traffic["engine"]
    return ServingEngine(
        params_program, program_config(config), kv_mode="paged",
        page_size=int(e["page_size"]),
        hbm_budget_bytes=float(e["hbm_budget_bytes"]),
        prefix_cache=bool(e["prefix_cache"]),
        prefill_chunk=e.get("prefill_chunk_tokens"),
        max_queue=int(e["max_queue"]), name="bench")


# -- what the checks compare ------------------------------------------------

# The leaves outside the blocks: the tied embedding sees every layer's
# error and the loss's, so their gradients stand for the whole step.
PROBE = ("wte", "wpe", "ln_f_g", "ln_f_b")


def reference_step_fn(config: dict, chunk: int, cast=None):
    """``(params, tokens [U, T+1], weights [U]) -> (loss, gradients of the
    PROBE leaves)`` of the weighted loss from ``reference/gpt2.py``, in
    float32, ``chunk`` sequences at a time so that a 1.5B model's backward
    pass fits beside its weights. ``cast`` swaps in the control's
    precision."""
    from benchmark.reference import gpt2 as ref
    n_head = model_sizes(config)["H"]
    cast = cast or ref.identity

    @jax.jit
    def part(params, probe, tokens, weights):
        return jax.value_and_grad(lambda pr: ref.loss(
            {**params, **pr}, tokens, n_head, cast, weights))(probe)

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
    """What the per-layer readers need to know about a training cell."""
    return {"n_params": num_params(config)}
