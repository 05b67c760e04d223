"""The nemotron-3-nano-30b-a3b cell's own files: the cell loads with its
readers and the published widths, every number of the catalog's row is in the
configuration but the four cut ones, the builder draws what the reference and
the program both read and counts 986,254,848 parameters (31.58e9 whole), the
planned step passes where the fp8 control fails, ``ssd_cost.py`` by hand at
the cell's shape, and both new readers on an excerpt of a trace of the cell
from the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_jamba_cell import SavedTrace

from benchmark.kernels import ssd_cost
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "nemotron-3-nano-30b-a3b.train.s8192"
NEW_READERS = ("ssd_time_share.train", "ssd_roofline_share.train")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# The catalog's row (model-configs guide, ``architectures.jsonl``:
# NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, its ``config``), every key.
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


@pytest.fixture(scope="module")
def builder():
    """The cell's builder, its balancing sequence short for the CPU."""
    module = cells.load_module(
        os.path.join(BENCH, "builders", "nemotron_h.py"),
        "bench_builder_nemotron_h")
    assert module.SETTLE_TOKENS == 8192
    module.SETTLE_TOKENS = 64
    return module


def tiny_config(dtype="float32"):
    """The published structure small: the first nine layers ``MEMEM*EME``,
    two heads a group, 16 query heads a key/value head, 8 of 32 experts held
    from the ninth on, 6 a token."""
    with open(os.path.join(BENCH, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    config.update(
        vocab_size=512, hidden_size=64, mamba_num_heads=4, mamba_head_dim=16,
        n_groups=2, ssm_state_size=16, num_attention_heads=16,
        num_key_value_heads=1, head_dim=8, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=64, n_routed_experts=8,
        router_num_experts=32, experts_held_first=8, dtype=dtype,
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
            "attn_mixed_roofline_share.train", "step_device_ms.train",
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
    entry = next(e for e in bench["configs"]
                 if e["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/nemotron-3-nano-30b-a3b.json"
    assert entry["reduced"] == c["reduced"] == REDUCED
    for m in (m for m in bench["per_layer"] if m["name"] in NEW_READERS):
        assert m["workloads"] == [CELL] and m["layer"] == "kernels" \
            and m["moves"] == "train_tokens_per_s_chip" \
            and m["source"] == "device_trace" and m["unit"] == "%"
    # A faster kernel lowers its share of the step and raises its share of
    # its roofline, as every sibling's pair reads.
    assert {m["name"]: m["better"] for m in bench["per_layer"]
            if m["name"] in NEW_READERS} == {
        "ssd_time_share.train": "lower", "ssd_roofline_share.train": "higher"}
    listed = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (listed["chips"], listed["traffic"]) == (1, "train-b8-s8192-ga8")
    assert len(listed["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(bench["workloads"]) == 11 \
        and not [w for w in bench["workloads"] if w["chips"] != 1]
    limit = cell.spec["correct"]["limits"]["step_state_rel_err"]
    assert 0.0 < limit < 1.0 and cell.spec["correct"]["unique_sequences"] == 4


def test_every_number_of_the_catalog_row_but_the_four_cut_ones():
    c = cells.load_cell(CELL, ROOT).config
    kept = {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: c[k] for k in kept} == kept
    assert c["reduced_from"] == {k: CATALOG[k] for k in REDUCED}
    # Published layers 0-8 (one whole period by the driver's count), 16 of
    # the 128 scored experts, an eighth of the table.
    assert (c["num_hidden_layers"], c["hybrid_override_pattern"],
            c["n_routed_experts"], c["router_num_experts"],
            c["experts_held_first"], c["vocab_size"]) \
        == (9, PATTERN[:9], 16, 128, 0, 131072 // 8)
    for width in ("hidden_size", "head_dim", "mamba_head_dim",
                  "mamba_num_heads", "n_groups", "ssm_state_size",
                  "conv_kernel", "moe_intermediate_size",
                  "moe_shared_expert_intermediate_size",
                  "num_attention_heads", "num_key_value_heads",
                  "num_experts_per_tok", "routed_scaling_factor", "expand"):
        assert width not in c["reduced"] and c[width] == CATALOG[width]
    assert set(c["assumed"]) >= {
        "sources", "layer_pattern", "norms", "mamba_equations",
        "column_order", "start_values", "attention", "experts",
        "auxiliary_loss", "initialisation", "dtype", "optimizer", "tokens",
        "routing", "unread_keys"}
    assert "2405.21060" in c["assumed"]["sources"] \
        and "2504.03624" in c["assumed"]["sources"]
    assert "log(1..64)" in c["assumed"]["start_values"] \
        and "DEPARTURE" not in c["assumed"]["start_values"]
    assert "rope_theta" in c["assumed"]["unread_keys"]
    assert "one rank of eight" in c["deployment"] \
        and "986,254,848" in c["deployment"] and "31.58e9" in c["deployment"]
    assert c["optimizer"] == {"name": "adamw_bf16_router_bias",
                              "learning_rate": 1e-05, "bias_rate": 0.001}


def test_parameter_counts(builder):
    """The issue's table."""
    cell = cells.load_cell(CELL, ROOT)
    d = 2688
    mamba = d * (4096 + 6144 + 64) + 5 * 6144 + 192 + 4096 + 4096 * d + d
    attn = d * 4096 + 2 * d * 256 + 4096 * d + d
    experts = d * 128 + 128 + 2 * d * 3712 + d
    one = 2 * d * 1856
    assert (mamba, attn, experts, one) \
        == (38_744_896, 23_399_040, 20_302_592, 9_977_856)
    assert builder.num_params(cell.config) == 986_254_848 \
        == 4 * mamba + attn + 4 * (experts + 16 * one) + 2 * 16384 * d + d
    whole = dict(cell.config, **cell.config["reduced_from"],
                 router_num_experts=128)
    assert builder.num_params(whole) == 23 * mamba + 6 * attn + 23 * (
        experts + 128 * one) + 2 * 131072 * d + d == 31_577_940_288
    assert builder.units(cell.config) == ("ME", "ME", "M*E", "ME")
    assert builder.runs(cell.config) == [("ME", 2), ("M*E", 1), ("ME", 1)]
    facts = builder.train_facts(cell.config)
    assert facts["resident_params"] == 986_254_848
    # What a token meets in a matmul: 0.75 of a routed expert, and the head.
    mamba_mm = d * (4096 + 6144 + 64) + 4096 * d
    assert facts["n_params"] == 4 * mamba_mm + (attn - d) + 4 * (
        d * 128 + 2 * d * 3712 + one * 6 // 8) + 16384 * d == 333_398_016
    tiny = tiny_config()
    params = builder.make_params(tiny, 7)
    assert builder.num_params(tiny) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    again = builder.make_params(tiny, 7)
    other = builder.make_params(tiny, 2_500_000_008)
    assert jnp.array_equal(params["tok_emb"], again["tok_emb"])
    assert not jnp.array_equal(params["tok_emb"], other["tok_emb"])
    assert set(params) == {"tok_emb", "norm_f", "lm_head", "run0", "run1",
                           "run2"}
    run = params["run0"]
    assert run["w_xbc"].shape == (2, 64, 128) \
        and run["w_z"].shape == (2, 64, 64) \
        and run["w_dt"].shape == (2, 64, 4) \
        and run["conv"].shape == (2, 4, 128) \
        and run["conv_b"].shape == (2, 128) \
        and run["w_up"].shape == (2, 8, 64, 32) \
        and run["w_down"].shape == (2, 8, 32, 64) \
        and run["router"].shape == (2, 64, 32) \
        and run["shared_up"].shape == (2, 64, 64)
    assert "w_gate" not in run and "shared_gate" not in run
    assert "wq" in params["run1"] and "wq" not in params["run2"]
    # The published start values: A = 1 .. H, D = 1, steps of 0.001 to 0.1;
    # conv taps U(-1/2, 1/2); unit gains; a zero selection bias.
    np.testing.assert_allclose(np.exp(np.asarray(run["A_log"])),
                               np.tile(np.arange(1, 5), (2, 1)), rtol=1e-6)
    assert run["A_log"].dtype == jnp.float32 \
        and not (np.asarray(run["D"]) - 1).any()
    step = jax.nn.softplus(run["dt_bias"])
    assert 0.00099 < float(step.min()) and float(step.max()) < 0.1001
    assert 0.4 < float(jnp.abs(run["conv"]).max()) <= 0.5
    assert np.asarray(run["ssm_ln"]).min() == 1.0 \
        and np.asarray(run["ssm_norm"]).min() == 1.0
    # The selection biases after the balancing rounds: centred a layer,
    # moved, and by no more than the rounds' rates add up to.
    bias = np.asarray(run["router_bias"])
    assert bias.shape == (2, 32) and bias.any(-1).all() \
        and np.abs(bias.sum(-1)).max() < 1e-5 \
        and np.abs(bias).max() <= 2 * builder.FIRST_RATE / (
            1 - builder.DECAY)
    assert int(builder.make_tokens(tiny, 3, 2, 4, 16).max()) < 512
    cfg = builder.program_config(cell.config)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
            cfg.ssm_state_size, cfg.conv_kernel, cfg.ssd_chunk) \
        == (64, 64, 8, 128, 4, cell.config["program"]["ssd_chunk"])
    assert cfg.kinds == tuple("MEMEM*EME") \
        and cfg.units == builder.units(cell.config)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (32, 2, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.route_scale, cfg.moe_tile_m) == (
                128, (0, 16), 6, 2.5, cell.config["program"]["moe_tile_m"])
    assert cfg.num_hidden_layers == 9 and cfg.remat
    hp = builder.reference_hyper(cell.config)
    assert (hp.heads, hp.groups, hp.n_head, hp.n_kv_head, hp.top_k, hp.held,
            hp.units, hp.route_scale, hp.eps) \
        == (64, 8, 32, 2, 6, (0, 16), ("ME", "ME", "M*E", "ME"), 2.5, 1e-5)
    bad = dict(cell.config, n_group=2)
    with pytest.raises(cells.BenchError, match="group"):
        builder.model_sizes(bad)


def test_the_biases_come_from_the_seed_and_the_reference_alone(builder,
                                                               monkeypatch):
    """``make_params`` asks the program under test for nothing, and on the
    sequence they were settled on the reference's own choices are level
    where a zero bias leaves them skewed."""
    from benchmark.reference import nemotron_h as ref

    class Untouched:
        def __getattr__(self, name):
            raise AssertionError(f"make_params read program.{name}")

    tiny = tiny_config()
    with monkeypatch.context() as m:
        m.setattr(builder, "program", Untouched())
        params = builder.make_params(tiny, 11)
    hp = builder.reference_hyper(tiny)
    tokens = jax.random.randint(              # make_params' own sequence
        builder._key(*builder._seed_words(11, 2)),
        (builder.SETTLE_TOKENS,), 0, tiny["vocab_size"], jnp.int32)

    def uneven(p):
        ids = np.asarray(ref.hidden(p, tokens, hp)[1])       # [layers, T, k]
        counts = np.stack([np.bincount(layer.ravel(), minlength=32)
                           for layer in ids])
        return (counts.max(-1) - counts.min(-1)).max()

    flat = {k: ({**v, "router_bias": jnp.zeros_like(v["router_bias"])}
                if k.startswith("run") else v) for k, v in params.items()}
    # 12 choices an expert if level: 3 to 5 apart settled, 16 to 23 at zero
    # over four seeds.
    assert uneven(params) <= 6 and uneven(flat) >= 12


def test_reference_step_agrees_with_the_program(builder):
    """A batch that repeats sequences, from the distinct ones and their
    shares; float32 against float32: rounding only."""
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
    for k in builder.PROBE:
        np.testing.assert_allclose(grads[k], p_grads[k], rtol=2e-3,
                                   atol=1e-7)


def test_the_control_fails_where_the_planned_step_passes(builder):
    """The plan's own step in bf16 (gradient accumulation over 4 micro
    batches, the kernels interpreted, ``adamw_bf16_router_bias``) against
    the float32 reference, and the fp8 control in its place: read as
    ``check_control.py`` reads them on the chip."""
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
    sound = [r["step_state_rel_err"] for r in rows if r["side"] == "program"]
    control = [r["step_state_rel_err"] for r in rows
               if r["side"] == "control"]
    assert len(sound) == len(control) == 2
    assert min(control) > 2 * max(sound), rows


def test_ssd_cost_by_hand_at_the_cells_shape():
    """``[1, 8192]`` tokens of 64 heads of 64 channels over 8 groups of 128
    states in bf16: a token and head costs the forward the recurrence's two
    products with the state, ``4 x 128 x 64`` operations, and the backward
    twice that; the operands and results cross HBM once, ``Delta`` ``[T,
    64]`` in float32; both bound by HBM."""
    T, H, P, G, N = 8192, 64, 64, 8, 128
    fwd = ssd_cost.forward(T, H, P, G, N)
    bwd = ssd_cost.backward(T, H, P, G, N)
    assert fwd["ops"] == 4 * N * P * T * H == 17_179_869_184
    assert bwd["ops"] == 2 * fwd["ops"]
    wide, key, head = T * H * P, T * G * N, T * H
    assert fwd["bytes"] == (2 * wide + 2 * key) * 2 + head * 4 \
        == 169_869_312
    assert bwd["bytes"] == (3 * wide + 4 * key) * 2 + 2 * head * 4
    least = {k: ssd_cost.roofline_seconds(c, PEAKS)
             for k, c in (("fwd", fwd), ("bwd", bwd))}
    assert least["fwd"]["bound"] == least["bwd"]["bound"] == "memory"
    assert least["fwd"]["seconds"] == pytest.approx(fwd["bytes"] / 819e9)
    assert 205e-6 < least["fwd"]["seconds"] < 210e-6
    assert 330e-6 < least["bwd"]["seconds"] < 335e-6
    # The operations alone would take 0.09 ms forward.
    assert 86e-6 < fwd["ops"] / 197e12 < 88e-6
    # float32 activations: more bytes, the same operations.
    assert ssd_cost.forward(T, H, P, G, N, 4)["ops"] == fwd["ops"]
    assert ssd_cost.forward(T, H, P, G, N, 4)["bytes"] > 1.9 * fwd["bytes"]


def test_both_readers_on_an_excerpt_of_the_cells_trace(capsys):
    """``testdata/nemotron_h.ops.json``: operations of one traced step of
    the cell on a v5e (PR 58's chip run), the kernels and a few of their
    neighbours, with the window they came from."""
    from benchmark.layer_metrics import _ssd
    cell = cells.load_cell(CELL, ROOT)
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    trace = SavedTrace(os.path.join(BENCH, "testdata",
                                    "nemotron_h.ops.json"))
    host = {"peaks": PEAKS}
    got = {name: readers[name].read(trace, host, cell)
           for name in (*NEW_READERS, "gmm_time_share.train",
                        "attn_time_share.train",
                        "attn_mixed_roofline_share.train")}
    for name, value in got.items():
        assert isinstance(value, float) and 0.0 < value < 100.0, (name, got)
    printed = capsys.readouterr().out
    assert "state-space rule roofline" in printed \
        and "bytes, bound by memory" in printed
    by_kind = {}
    for text, s, calls in trace.ops(_ssd.is_ssd):
        parsed = _ssd.parse(text)
        assert parsed[1:] == (8192, 64, 64, 8, 128, 2), parsed
        by_kind.setdefault(parsed[0], []).append(
            (s, calls, _ssd.call_cost(parsed)))
    assert set(by_kind) == {"forward", "backward"}
    # 8 micro batches x 4 Mamba-2 layers: the forward twice (a unit's
    # forward and its recomputation), the backward once.
    assert sum(calls for _, calls, _ in by_kind["forward"]) == 64
    assert sum(calls for _, calls, _ in by_kind["backward"]) == 32
    for _, _, cost in by_kind["forward"]:
        assert cost == ssd_cost.forward(8192, 64, 64, 8, 128)
    for _, _, cost in by_kind["backward"]:
        assert cost == ssd_cost.backward(8192, 64, 64, 8, 128)
    taken = sum(s for s, _, _ in sum(by_kind.values(), []))
    assert got["ssd_time_share.train"] == pytest.approx(
        100 * taken / trace.window_s)
    least = sum(
        calls * ssd_cost.roofline_seconds(cost, PEAKS)["seconds"]
        for _, calls, cost in sum(by_kind.values(), []))
    assert got["ssd_roofline_share.train"] == pytest.approx(
        100 * least / taken)
    # The flash kernels at 32 query heads over 2 key/value heads.
    names = {text.split(" ", 1)[0] for text, _, _ in trace.ops(
        lambda t: "tepdist_flash_" in t.split(" ", 1)[0])}
    assert names and all("__h32__kv2" in n for n in names), names


def test_the_new_readers_return_nothing_without_the_kernels():
    """The parent's trace, or any other model's: nothing is returned and
    nothing raises; an event whose operands are not the kernels' is not
    costed."""
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    dense = ("%fusion.9 = bf16[8192,2688]{1,0:T(8,128)(2,1)} fusion("
             "bf16[8192,2688]{1,0:T(8,128)(2,1)} %h)")
    gdn = ("%tepdist_gdn_fwd.1 = bf16[1,8192,4096]{2,1,0} custom-call("
           "bf16[1,8192,2048]{2,1,0} %q), custom_call_target="
           "\"tpu_custom_call\", operand_layout_constraints={"
           "bf16[1,8192,2048]{2,1,0}, bf16[1,8192,2048]{2,1,0}, "
           "bf16[1,8192,4096]{2,1,0}, f32[1,8192,32]{2,1,0}, "
           "f32[1,8192,32]{2,1,0}}")
    odd = ("%jvp_tepdist_ssd_fwd__g8_.1 = bf16[1,128,256]{2,1,0} "
           "custom-call(bf16[1,128,256]{2,1,0} %a), custom_call_target="
           "\"tpu_custom_call\", operand_layout_constraints={bf16[1,128,256]"
           "{2,1,0}}")

    class Trace:
        window_s = 2.0

        def __init__(self, *ops):
            self._ops = ops

        def ops(self, match):
            return [op for op in self._ops if match(op[0])]

        def op_seconds(self, match):
            return sum(s for _, s, _ in self.ops(match))

    host = {"peaks": PEAKS}
    for name in (CELL, "gpt2-1.5b.train.b48",
                 "qwen3-next-80b-a3b.train.s8192"):
        cell = cells.load_cell(name, ROOT)
        for reader in NEW_READERS:
            assert readers[reader].read(
                Trace((dense, 0.5, 9), (gdn, 0.1, 3)), host, cell) is None
    cell = cells.load_cell(CELL, ROOT)
    assert readers["ssd_roofline_share.train"].read(
        Trace((odd, 0.1, 3)), host, cell) is None
    assert readers["ssd_time_share.train"].read(
        Trace((odd, 0.1, 3)), host, cell) == pytest.approx(5.0)
