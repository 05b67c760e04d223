"""The granite-4.0-h-small cell's own files: the cell loads with its readers
and the published widths, every number of the catalog's row is in the
configuration but the seven cut counts, the builder draws what the reference
and the program both read and counts 1,221,088,944 parameters (32.2e9 whole),
the routers levelled from the seed and the reference alone, the planned step
passes where the fp8 control fails (the first run's Mamba-2 leaves among what
is compared), the state-space cost at the held mixer's
shape, and the two new readers over a table of operations and scopes."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import trace_reduce
from benchmark.kernels import ssd_cost
from benchmark.layer_metrics import _scopes, _ssd
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CONFIG, CELL = "granite-4.0-h-small", "granite-4.0-h-small.train.s8192"
NEW_READERS = ("moe_rows_time_share.train", "moe_shared_time_share.train")
REDUCED = ["num_hidden_layers", "layer_types", "num_local_experts",
           "mamba_n_heads", "num_attention_heads", "num_key_value_heads",
           "vocab_size"]
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# The catalog's row (model-configs guide, ``architectures.jsonl``:
# granite-4.0-h-small, its ``config``), every key.
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}


@pytest.fixture(scope="module")
def builder():
    """The cell's builder, its levelling sequence short for the CPU."""
    module = cells.load_module(
        os.path.join(BENCH, "builders", "granite_hybrid.py"),
        "bench_builder_granite_hybrid")
    assert module.SETTLE_TOKENS == 2048
    module.SETTLE_TOKENS = 256
    return module


def tiny_config(dtype="float32"):
    """The published structure small: two Mamba-2 layers before and one
    after an attention layer, a rank's 4 of 8 Mamba-2 heads over one group,
    4 query heads over 1, 8 of 16 experts held from the ninth on, 10 a
    token."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    config.update(
        vocab_size=256, hidden_size=64, num_hidden_layers=4,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        mamba_n_heads=4, mamba_d_head=32, mamba_d_state=64,
        num_attention_heads=4, num_key_value_heads=1, head_dim=8,
        intermediate_size=24, shared_intermediate_size=16,
        num_local_experts=8,
        router_num_experts=16, experts_held_first=8, dtype=dtype,
        program={"stacked": True, "remat": True, "loss_chunk": 16,
                 "moe_tile_m": 8, "ssd_chunk": 16})
    return config


def test_the_cell_loads_with_its_readers_and_published_widths():
    cell = cells.load_cell(CELL, ROOT)
    names = {m["name"] for m in cell.per_layer}
    scopes = {f"scope_{p}_share.train" for p in (
        "embed", "mixer", "moe", "head_loss", "optimizer", "unscoped",
        "recompute")}
    assert {*NEW_READERS, *scopes, "device_idle_share.train",
            "gmm_time_share.train", "attn_time_share.train",
            "attn_mixed_roofline_share.train", "ssd_time_share.train",
            "ssd_roofline_share.train", "step_device_ms.train",
            "step_host_ms.train", "idle_attributed_share.train", "plan_s",
            "plan_trace_s", "plan_search_s", "plan_place_s", "first_step_s",
            "setup_compile_s"} == names
    found = {m.NAME for m in cells.layer_metric_modules(cell.bench_dir)}
    assert set(NEW_READERS) <= found
    t, c = cell.traffic, cell.config
    assert (t["batch"], t["seq"], t["num_micro_batches"], t["explore"],
            t["trace_steps"]) == (8, 8192, 8, False, 1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"] if e["name"] == CONFIG)
    assert entry["source"] == c["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == c["reduced"] == REDUCED
    for m in (m for m in bench["per_layer"] if m["name"] in NEW_READERS):
        assert m["workloads"] == [CELL] and m["layer"] == "models" \
            and m["moves"] == "train_tokens_per_s_chip" \
            and m["source"] == "device_trace" and m["unit"] == "%" \
            and m["better"] == "lower"
    listed = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (listed["chips"], listed["traffic"]) == (1, "train-b8-s8192-ga8")
    assert len(listed["why"]) <= 200 and len(entry["why"]) <= 200
    assert not [w for w in bench["workloads"] if w["chips"] != 1]
    limit = cell.spec["correct"]["limits"]["step_state_rel_err"]
    assert 0.0 < limit < 1.0 and cell.spec["correct"]["unique_sequences"] == 4


def test_every_number_of_the_catalog_row_but_the_seven_cut_counts():
    c = cells.load_cell(CELL, ROOT).config
    kept = {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: c[k] for k in kept} == kept
    assert {k: c["reduced_from"][k] for k in REDUCED} \
        == {k: CATALOG[k] for k in REDUCED}
    assert "eight chips share each layer" in c["reduced_from"]["deployment"]
    # Published layers 0-9 (one whole period), and an eighth of what a
    # layer's ranks divide: heads, experts, rows. The shared MLP is whole.
    assert (c["num_hidden_layers"], c["layer_types"], c["mamba_n_heads"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["num_local_experts"], c["router_num_experts"],
            c["experts_held_first"], c["shared_intermediate_size"],
            c["vocab_size"], c["head_dim"]) \
        == (10, PERIOD, 16, 4, 1, 9, 72, 0, 1536, 12544, 128)
    # No width is cut: a reduced key counts heads, experts, layers or rows.
    for width in ("hidden_size", "intermediate_size",
                  "shared_intermediate_size", "mamba_d_head",
                  "mamba_d_state", "mamba_d_conv", "mamba_expand",
                  "mamba_n_groups", "num_experts_per_tok",
                  "attention_multiplier", "embedding_multiplier",
                  "residual_multiplier", "logits_scaling"):
        assert width not in c["reduced"] and c[width] == CATALOG[width]
    assert set(c["assumed"]) >= {
        "sources", "equations", "auxiliary_loss", "mamba_equations",
        "held_mixer", "start_values", "attention", "experts", "unread_keys",
        "initialisation", "dtype", "optimizer", "tokens", "routing"}
    assert "DEPARTURE" in c["assumed"]["start_values"] \
        and "NOT LINEAR" in c["what_the_cut_distorts"]
    assert "1,221,088,944" in c["deployment"] \
        and "32,207,337,984" in c["deployment"]
    assert c["optimizer"] == {"name": "adamw_bf16", "learning_rate": 1e-05}


def test_parameter_counts(builder):
    """The issue's table."""
    cell = cells.load_cell(CELL, ROOT)
    d = 4096
    mixer = d * (1024 + 1280 + 16) + 5 * 1280 + 3 * 16 + 1024 + 1024 * d
    attn = d * 512 + 2 * d * 128 + 512 * d
    shared, router, one = 3 * d * 1536, d * 72, 3 * d * 768
    assert (mixer, attn, shared, router, 9 * one) == (
        13_704_496, 5_242_880, 18_874_368, 294_912, 84_934_936 - 280)
    layer = shared + router + 2 * d + 9 * one
    assert (mixer + layer, attn + layer) == (117_816_624, 109_355_008)
    assert builder.num_params(cell.config) == 1_221_088_944 \
        == 9 * (mixer + layer) + attn + layer + 12544 * d + d
    whole = dict(cell.config, **{k: cell.config["reduced_from"][k]
                                 for k in REDUCED})
    assert builder.num_params(whole) == 32_207_337_984
    assert builder.runs(cell.config) == [("mamba", 5), ("attention", 1),
                                         ("mamba", 4)]
    facts = builder.train_facts(cell.config)
    assert facts["resident_params"] == 1_221_088_944
    # What a token meets in a matmul: 1.25 of a routed expert, and the head.
    mixer_mm = d * (1024 + 1280 + 16) + 1024 * d
    assert facts["n_params"] == 9 * mixer_mm + attn + 10 * (
        shared + router + one * 10 * 9 // 72) + 12544 * d == 489_553_920
    tiny = tiny_config()
    params = builder.make_params(tiny, 7)
    assert builder.num_params(tiny) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    again = builder.make_params(tiny, 7)
    other = builder.make_params(tiny, 2_500_000_008)
    assert jnp.array_equal(params["tok_emb"], again["tok_emb"])
    assert not jnp.array_equal(params["tok_emb"], other["tok_emb"])
    assert set(params) == {"tok_emb", "norm_f", "run0", "vec0", "out0",
                           "run1", "run2", "vec2", "out2"}
    run, vec = params["run0"], params["vec0"]
    assert run["w_xbc"].shape == (2, 64, 128 + 128) \
        and run["w_z"].shape == (2, 64, 128) \
        and run["w_dt"].shape == (2, 64, 4) \
        and run["conv"].shape == (2, 4, 256) \
        and vec["conv_b"].shape == (2, 256) \
        and params["out0"]["w_out"].shape == (2, 128, 64) \
        and run["w_gate"].shape == (2, 8, 64, 24) \
        and run["router"].shape == (2, 64, 16) \
        and run["shared_up"].shape == (2, 64, 16)
    assert "wq" in params["run1"] and "wq" not in params["run2"]
    np.testing.assert_allclose(np.exp(np.asarray(vec["A_log"])),
                               np.tile(np.arange(1, 5), (2, 1)), rtol=1e-6)
    assert not (np.asarray(vec["D"]) - 1).any()
    step = jax.nn.softplus(vec["dt_bias"])
    assert 0.00099 < float(step.min()) and float(step.max()) < 0.1001
    assert int(builder.make_tokens(tiny, 3, 2, 4, 16).max()) < 256
    cfg = builder.program_config(cell.config)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
            cfg.mamba_d_state, cfg.mamba_d_conv, cfg.ssd_chunk) \
        == (16, 64, 1, 128, 4, cell.config["program"]["ssd_chunk"])
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.shared_intermediate_size, cfg.intermediate_size) \
        == (4, 1, 128, 1536, 768)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) \
        == (72, (0, 9), 10)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) \
        == (12.0, 1 / 128, 0.22, 16.0)
    assert cfg.layer_types == tuple(PERIOD) and cfg.remat
    hp = builder.reference_hyper(cell.config)
    assert (hp.heads, hp.n_head, hp.n_kv_head, hp.top_k, hp.held, hp.kinds,
            hp.attention_multiplier, hp.eps) \
        == (16, 4, 1, 10, (0, 9), tuple(PERIOD), 1 / 128, 1e-5)
    with pytest.raises(cells.BenchError, match="group"):
        builder.model_sizes(dict(cell.config, mamba_n_groups=2))


def test_the_routers_are_levelled_from_the_seed_and_the_reference_alone(
        builder, monkeypatch):
    """``make_params`` asks the program under test for nothing; on the
    sequence they were levelled on no expert's logit has an offset every
    token shares, where the routers as drawn have one, and nothing but the
    routers differs from what was drawn."""
    from benchmark.reference import granite_hybrid as ref

    class Untouched:
        def __getattr__(self, name):
            raise AssertionError(f"make_params read program.{name}")

    tiny = tiny_config()
    with monkeypatch.context() as m:
        m.setattr(builder, "program", Untouched())
        params = builder.make_params(tiny, 11)
    drawn = jax.jit(builder.drawn(tiny))(*builder._seed_words(11, 1))
    hp = builder.reference_hyper(tiny)
    tokens = jax.random.randint(              # make_params' own sequence
        builder._key(*builder._seed_words(11, 2)),
        (builder.SETTLE_TOKENS,), 0, tiny["vocab_size"], jnp.int32)

    def offsets(p):
        """The largest mean logit over the sequence, a layer."""
        x = hp.embedding_multiplier * p["tok_emb"][tokens].astype(
            jnp.float32)
        out = []
        for blk, kind in ref.layers_of(p, hp):
            x = ref.after_mixer(blk, x, kind, hp)
            h = ref.experts_input(blk, x, hp)
            out.append(float(jnp.abs(jnp.mean(
                h @ blk["router"].astype(jnp.float32), axis=0)).max()))
            x, _ = ref.after_experts(blk, x, hp)
        return out

    assert max(offsets(params)) < 1e-6 and min(offsets(drawn)) > 1e-2
    for path, a in jax.tree_util.tree_flatten_with_path(drawn)[0]:
        b = params
        for key in path:
            b = b[key.key]
        assert jax.tree_util.keystr(path).endswith("['router']") \
            != bool(jnp.array_equal(a, b)), jax.tree_util.keystr(path)


def test_reference_step_agrees_with_the_program(builder):
    """A batch that repeats sequences, from the distinct ones and their
    shares; float32 against float32: rounding only. The compared leaves
    hold the first run's Mamba-2 vectors and output projections."""
    config = tiny_config()
    params = builder.make_params(config, 2_500_000_001)
    unique = builder.make_tokens(config, 5, 2, 4, 32)
    index = np.array([0, 1, 1, 2, 3, 3, 3, 0])
    shares = np.bincount(index) / len(index)
    with jax.default_matmul_precision("highest"):
        loss, grads = builder.reference_step_fn(config, 2)(params, unique,
                                                           shares)
        p_loss, p_grads = jax.jit(jax.value_and_grad(
            builder.program_loss_fn(config)))(
            builder.to_program(params, config), unique[index])
    assert abs(float(loss) - float(p_loss)) < 1e-5 * float(p_loss)
    assert builder.PROBE == ("tok_emb", "norm_f", "vec0", "out0")
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        want = p_grads
        for key in path:
            want = want[key.key]
        np.testing.assert_allclose(
            g, want, rtol=0, atol=2e-5 * float(np.abs(want).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


def test_the_control_fails_where_the_planned_step_passes(builder):
    """The plan's own step in bf16 (gradient accumulation over 4 micro
    batches, the kernels interpreted, ``adamw_bf16``) against the float32
    reference, and the fp8 control in its place: read as
    ``check_control.py`` reads them on the chip, a slot each for the leaves
    outside the layers, the Mamba-2 vectors and the output projections."""
    from benchmark.lib.host import HostLog
    config = tiny_config("bfloat16")
    traffic = {"kind": "train", "driver": "train_steps", "batch": 8,
               "seq": 32, "num_micro_batches": 4, "explore": False,
               "trace_steps": 1}
    spec = {"correct": {"unique_sequences": 4, "reference_chunk": 2,
                        "limits": {"step_state_rel_err": 0.0}}}
    cell = cells.Cell(name="tiny", chips=1, why="", config=config,
                      traffic=traffic, spec=spec, end_to_end=[],
                      per_layer=[], root=ROOT, bench_dir=BENCH)
    rows = list(cells.driver_for(cell).readings(
        cell, builder, jax.devices()[:1], [1, 2], [1, 2], HostLog()))
    slots = {k.split(".", 1)[1] for k in rows[0] if k.startswith("state")}
    assert slots >= {"mu", "mu['vec0']", "mu['out0']", "nu", "nu['vec0']",
                     "nu['out0']"}, rows[0]
    sound = [r["step_state_rel_err"] for r in rows if r["side"] == "program"]
    control = [r["step_state_rel_err"] for r in rows
               if r["side"] == "control"]
    assert len(sound) == len(control) == 2
    assert min(control) > 2 * max(sound), rows


def test_the_state_space_cost_at_the_held_mixers_shape():
    """``[1, 8192]`` tokens of the rank's 16 heads of 64 over ONE group of
    128 states: ``_ssd.parse`` reads them from the call's name and operands,
    and ``B`` and ``C``, whole on every rank, are a fifth of the bytes where
    the whole mixer's are a thirty-third."""
    text = ("%tepdist_ssd_fwd__g1.3 = bf16[1,8192,1024]{2,1,0} custom-call("
            "bf16[1,8192,1024]{2,1,0} %u), custom_call_target="
            "\"tpu_custom_call\", operand_layout_constraints={"
            "bf16[1,8192,1024]{2,1,0}, bf16[1,8192,128]{2,1,0}, "
            "bf16[1,8192,128]{2,1,0}, f32[1,8192,16]{2,1,0}, "
            "f32[1,8192,16]{2,1,0}, f32[1,1024]{1,0}}")
    parsed = _ssd.parse(text)
    assert parsed == ("forward", 8192, 16, 64, 1, 128, 2)
    T, H, P, G, N = parsed[1:6]
    fwd = _ssd.call_cost(parsed)
    assert fwd == ssd_cost.forward(T, H, P, G, N)
    assert fwd["ops"] == 4 * N * P * T * H
    assert fwd["bytes"] == T * (2 * (2 * H * P + 2 * N) + 4 * H)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert ssd_cost.roofline_seconds(fwd, peaks)["bound"] == "memory"
    assert 2 * N / (2 * H * P + 2 * N) == pytest.approx(1 / 9)


class _Table:
    """A trace as the two readers see it: a window, and the devices' self
    seconds by operation."""

    def __init__(self, window_s, *devices):
        self.window_s = window_s
        self.devices = [types.SimpleNamespace(op_self_s=d) for d in devices]


def test_the_two_readers_over_operations_and_their_scopes(monkeypatch):
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)
               if m.NAME in NEW_READERS}
    assert set(readers) == set(NEW_READERS)
    walk = "jit(tepdist_train_step)/while/body/closed_call/"
    scopes = {
        "%fusion.1": walk + "walk_fwd/part_moe/moe_router/dot_general:",
        "%tepdist_router_choice.2": walk + "walk_bwd/transpose(jvp("
        "part_moe/moe_router))/tepdist_router_choice/pallas_call:",
        "%sort.3": walk + "walk_recompute/part_moe/moe_dispatch/sort:",
        "%tepdist_rows_sum.4": walk + "walk_fwd/part_moe/moe_combine/"
        "tepdist_rows_sum/pallas_call:",
        "%fusion.5": walk + "walk_fwd/part_moe/moe_shared/dot_general:",
        "%tepdist_gmm_fwd.6": walk + "walk_fwd/part_moe/moe_experts/"
        "tepdist_gmm_fwd/pallas_call:",
        "%fusion.7": walk + "walk_fwd/part_mixer/ssd_in/dot_general:",
        "%copy.8": ""}
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda path: path)
    monkeypatch.setattr(_scopes, "operation_scopes", lambda path: scopes)
    seconds = dict(zip(scopes, (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8)))
    trace = _Table(10.0, seconds, {k: 3 * v for k, v in seconds.items()})
    cell = types.SimpleNamespace(facts={"trace_path": "a trace"})
    # Mean over the two devices: twice one device's seconds.
    assert readers["moe_rows_time_share.train"].read(trace, None, cell) \
        == pytest.approx(100 * 2 * (0.1 + 0.2 + 0.3 + 0.4) / 10.0)
    assert readers["moe_shared_time_share.train"].read(trace, None, cell) \
        == pytest.approx(100 * 2 * 0.5 / 10.0)
    # A program without the scopes (the parent's), or a run not traced:
    # nothing to read, and nothing raises.
    monkeypatch.setattr(_scopes, "operation_scopes", lambda path: {
        "%fusion.1": walk + "walk_fwd/part_mixer/attn_qkv/dot_general:"})
    untraced = types.SimpleNamespace(facts={})
    cell = types.SimpleNamespace(facts={"trace_path": "another trace"})
    for reader in readers.values():
        assert reader.read(trace, None, cell) is None
        assert reader.read(trace, None, untraced) is None
