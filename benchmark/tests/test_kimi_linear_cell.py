"""The kimi-linear-48b-a3b cell's own files: the cell loads with its readers
and the published widths, every number of the catalog's row is in the
configuration but the four cut ones, the builder draws what the reference
and the program both read and counts 828,926,848 parameters, the planned
step passes where the fp8 control fails, ``kda_cost.py`` by hand at the
cell's shape, and both new readers on an excerpt of a trace of the cell from
the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_jamba_cell import SavedTrace

from benchmark.kernels import kda_cost
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "kimi-linear-48b-a3b.train.s8192"
NEW_READERS = ("kda_time_share.train", "kda_roofline_share.train")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"]
# The catalog's row (model-configs guide, ``architectures.jsonl``:
# Kimi-Linear-48B-A3B-Instruct, its ``config``), every key.
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


@pytest.fixture(scope="module")
def builder():
    return cells.load_module(
        os.path.join(BENCH, "builders", "kimi_linear.py"),
        "bench_builder_kimi_linear")


def tiny_config(dtype="float32"):
    """The published structure small: five layers, three KDA to one latent
    after the dense one, 16 of 32 experts held from the ninth on."""
    with open(os.path.join(BENCH, "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        config = json.load(f)
    config.update(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=2, kv_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
        num_experts=16, router_num_experts=32, experts_held_first=8,
        dtype=dtype,
        linear_attn_config=dict(config["linear_attn_config"], num_heads=4,
                                head_dim=32),
        program={"stacked": True, "remat": True, "loss_chunk": 16,
                 "moe_tile_m": 8, "kda_chunk": 16})
    return config


def test_the_cell_loads_with_its_readers_and_published_widths():
    cell = cells.load_cell(CELL, ROOT)
    names = {m["name"] for m in cell.per_layer}
    scopes = {f"scope_{p}_share.train" for p in (
        "embed", "mixer", "mlp", "moe", "head_loss", "optimizer", "unscoped",
        "recompute")}
    assert {*NEW_READERS, *scopes, "device_idle_share.train",
            "gmm_time_share.train", "mla_time_share.train",
            "mla_roofline_share.train", "step_device_ms.train",
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
                 if e["name"] == "kimi-linear-48b-a3b")
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/kimi-linear-48b-a3b.json"
    assert entry["reduced"] == c["reduced"] == REDUCED
    assert bench["configs"][-1] == entry
    for m in (m for m in bench["per_layer"] if m["name"] in NEW_READERS):
        assert m["workloads"] == [CELL] and m["layer"] == "kernels" \
            and m["moves"] == "train_tokens_per_s_chip" \
            and m["source"] == "device_trace" and m["unit"] == "%"
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW_READERS)
    listed = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (listed["chips"], listed["traffic"]) == (1, "train-b8-s8192-ga8")
    assert bench["workloads"][-1] == listed       # appended, nothing moved
    assert len(listed["why"]) <= 200 and len(entry["why"]) <= 200
    limit = cell.spec["correct"]["limits"]["step_state_rel_err"]
    assert 0.0 < limit < 1.0 and cell.spec["correct"]["unique_sequences"] == 4


def test_every_number_of_the_catalog_row_but_the_four_cut_ones():
    c = cells.load_cell(CELL, ROOT).config
    kept = {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: c[k] for k in kept} == kept
    assert c["reduced_from"] == {k: CATALOG[k] for k in REDUCED}
    # Published layers 1-5: three KDA layers to the one latent, 16 of the
    # 256 scored experts, an eighth of the table.
    lin = c["linear_attn_config"]
    assert (c["num_hidden_layers"], lin["kda_layers"],
            lin["full_attn_layers"]) == (5, [1, 2, 3, 5], [4])
    assert (c["num_experts"], c["router_num_experts"],
            c["experts_held_first"], c["vocab_size"]) \
        == (16, 256, 0, 163840 // 8)
    # No width is cut, inside the nested group either.
    for width in ("num_heads", "head_dim", "short_conv_kernel_size"):
        assert lin[width] == CATALOG["linear_attn_config"][width]
    for width in ("hidden_size", "intermediate_size", "kv_lora_rank",
                  "moe_intermediate_size", "num_attention_heads",
                  "num_experts_per_token", "num_shared_experts",
                  "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"):
        assert width not in c["reduced"] and c[width] == CATALOG[width]
    assert set(c["assumed"]) >= {
        "sources", "kda_equations", "low_rank_width", "decay_parameters",
        "no_biases", "l2_norm", "gated_norm", "latent_attention", "norms",
        "router", "bias_update", "shared_expert", "auxiliary_loss",
        "initialisation", "dtype", "optimizer", "tokens", "routing"}
    assert "2510.26692" in c["assumed"]["sources"]
    assert "DEPARTURE" in c["assumed"]["bias_update"]
    assert "NO rotary" in c["assumed"]["latent_attention"]
    assert "one rank of sixteen" in c["deployment"]
    assert c["optimizer"] == {"name": "adamw_bf16_router_bias",
                              "learning_rate": 1e-05, "bias_rate": 0.001}


def test_parameter_counts(builder):
    """The issue's table."""
    cell = cells.load_cell(CELL, ROOT)
    d, P, D = 2304, 4096, 128
    kda = 3 * d * P + 3 * 4 * P + (d * D + D * P) + (P + 32) + d * 32 \
        + (d * D + D * P) + D + P * d
    mla = d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + 32 * 128 * d
    dense = 3 * d * 9216
    experts = 16 * 3 * d * 1024 + 3 * d * 1024 + d * 256 + 256
    assert (kda, mla, dense, experts) == (39_514_272, 29_114_880,
                                          63_700_992, 120_914_176)
    layers = (kda + dense + 2 * d) + 3 * (kda + experts + 2 * d) \
        + (mla + experts + 2 * d)
    assert layers == 734_552_704
    assert builder.num_params(cell.config) == 828_926_848 \
        == layers + 2 * 20480 * d + d
    assert builder.runs(cell.config) == [
        (("kda", True), 1), (("kda", False), 2), (("mla", False), 1),
        (("kda", False), 1)]
    facts = builder.train_facts(cell.config)
    assert facts["resident_params"] == 828_926_848
    # What a token meets in a matmul: half a routed expert, and the head.
    kda_mm = kda - 3 * 4 * P - (P + 32) - D
    assert facts["n_params"] == 4 * kda_mm + (mla - 512) + dense + 4 * (
        d * 256 + 3 * d * 1024 + 3 * d * 1024 // 2) + 20480 * d \
        == 342_671_360
    tiny = tiny_config()
    params = builder.make_params(tiny, 7)
    assert builder.num_params(tiny) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    again = builder.make_params(tiny, 7)
    other = builder.make_params(tiny, 2_500_000_008)
    assert jnp.array_equal(params["tok_emb"], again["tok_emb"])
    assert not jnp.array_equal(params["tok_emb"], other["tok_emb"])
    assert set(params) == {"tok_emb", "norm_f", "lm_head", "run0", "run1",
                           "run2", "run3"}
    run = params["run1"]
    assert run["wq"].shape == (2, 64, 128) \
        and run["conv_q"].shape == (2, 4, 128) \
        and run["w_gate"].shape == (2, 16, 64, 32) \
        and run["router"].shape == (2, 64, 32)
    assert run["A_log"].dtype == jnp.float32 \
        and run["dt_bias"].dtype == jnp.float32
    # A = log U(1, 16); dt the inverse softplus of U_log(0.001, 0.1).
    assert 0.0 <= float(run["A_log"].min()) \
        and float(run["A_log"].max()) <= np.log(16.0)
    step = jax.nn.softplus(run["dt_bias"])
    assert 0.0009 < float(step.min()) and float(step.max()) < 0.11
    assert "wkva" in params["run2"] and "conv_q" not in params["run2"]
    assert int(builder.make_tokens(tiny, 3, 2, 4, 16).max()) < 512
    cfg = builder.program_config(cell.config)
    assert (cfg.kda_num_heads, cfg.kda_head_dim, cfg.short_conv_kernel_size,
            cfg.kda_chunk) == (32, 128, 4, cell.config["program"][
                "kda_chunk"])
    assert cfg.mixers == ("kda", "kda", "kda", "mla", "kda")
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.route_scale, cfg.moe_tile_m) == (256, (0, 16), 8, 2.446, 128)
    assert cfg.num_hidden_layers == 5 and cfg.remat \
        and cfg.rope_table is None
    hp = builder.reference_hyper(cell.config)
    assert (hp.kda_heads, hp.kv_lora_rank, hp.held, hp.route_scale, hp.eps) \
        == (32, 512, (0, 16), 2.446, 1e-5)
    bad = dict(cell.config, linear_attn_config=dict(
        cell.config["linear_attn_config"], full_attn_layers=[3, 4]))
    with pytest.raises(cells.BenchError, match="once each"):
        builder.mixers(bad)


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


def test_kda_cost_by_hand_at_the_cells_shape():
    """``[1, 8192]`` tokens of 32 heads of 128 + 128 channels in bf16: a
    token and head costs the forward the recurrence's three products with
    the state, ``6 x 128 x 128`` operations, and the backward fourteen
    halves of that; the operands and results cross HBM once, ``g`` and
    ``beta`` in float32; both bound by HBM."""
    T, H, K = 8192, 32, 128
    fwd = kda_cost.forward(T, H, K, K)
    bwd = kda_cost.backward(T, H, K, K)
    assert fwd["ops"] == 6 * 16384 * T * H == 25_769_803_776
    assert bwd["ops"] == 14 * 16384 * T * H
    wide = T * H * K                       # elements of a [T, H x 128]
    assert fwd["bytes"] == 4 * wide * 2 + wide * 4 + T * H * 4 \
        == 403_701_760
    assert bwd["bytes"] == 8 * wide * 2 + 2 * wide * 4 + 2 * T * H * 4
    least = {k: kda_cost.roofline_seconds(c, PEAKS)
             for k, c in (("fwd", fwd), ("bwd", bwd))}
    assert least["fwd"]["bound"] == least["bwd"]["bound"] == "memory"
    assert least["fwd"]["seconds"] == pytest.approx(fwd["bytes"] / 819e9)
    assert 490e-6 < least["fwd"]["seconds"] < 495e-6
    assert 980e-6 < least["bwd"]["seconds"] < 990e-6
    # The operations alone would take 0.13 ms forward.
    assert 130e-6 < fwd["ops"] / 197e12 < 132e-6
    # float32 activations: more bytes, the same operations.
    assert kda_cost.forward(T, H, K, K, 4)["ops"] == fwd["ops"]
    assert kda_cost.forward(T, H, K, K, 4)["bytes"] > 1.6 * fwd["bytes"]


def test_both_readers_on_an_excerpt_of_the_cells_trace(capsys):
    """``testdata/kimi_linear.ops.json``: operations of one traced step of
    the cell on a v5e (PR 52's chip run), the kernels and a few of their
    neighbours, with the window they came from."""
    from benchmark.layer_metrics import _kda
    cell = cells.load_cell(CELL, ROOT)
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    trace = SavedTrace(os.path.join(BENCH, "testdata",
                                    "kimi_linear.ops.json"))
    host = {"peaks": PEAKS}
    got = {name: readers[name].read(trace, host, cell)
           for name in (*NEW_READERS, "gmm_time_share.train",
                        "mla_time_share.train", "mla_roofline_share.train")}
    for name, value in got.items():
        assert isinstance(value, float) and 0.0 < value < 100.0, (name, got)
    printed = capsys.readouterr().out
    assert "delta rule roofline" in printed \
        and "bytes, bound by memory" in printed
    by_kind = {}
    for text, s, calls in trace.ops(_kda.is_kda):
        parsed = _kda.parse(text)
        assert parsed[1:] == (8192, 32, 128, 2), parsed
        by_kind.setdefault(parsed[0], []).append(
            (s, calls, _kda.call_cost(parsed)))
    # The differentiated forward writes the states: no sweep makes them
    # again in the step.
    assert set(by_kind) == {"forward", "backward"}
    # 8 micro batches x 4 KDA layers: the forward twice (a walked block
    # makes its mixer again), the backward once.
    assert sum(calls for _, calls, _ in by_kind["forward"]) == 64
    assert sum(calls for _, calls, _ in by_kind["backward"]) == 32
    for _, _, cost in by_kind["forward"]:
        assert cost == kda_cost.forward(8192, 32, 128, 128)
    for _, _, cost in by_kind["backward"]:
        assert cost == kda_cost.backward(8192, 32, 128, 128)
    # Where a backward is asked for without them, that sweep is the
    # implementation's and costs nothing at the roofline.
    again = ("%tepdist_kda_bwd_states.1 = f32[1,64,32,128,128]{4,3,2,1,0} "
             "custom-call(bf16[1,8192,4096]{2,1,0} %q), custom_call_target="
             "\"tpu_custom_call\", operand_layout_constraints={"
             "bf16[1,8192,4096]{2,1,0}, bf16[1,8192,4096]{2,1,0}, "
             "bf16[1,8192,4096]{2,1,0}, f32[1,8192,4096]{2,1,0}, "
             "f32[1,8192,32]{2,1,0}}")
    parsed = _kda.parse(again)
    assert parsed == ("states_again", 8192, 32, 128, 2)
    assert _kda.call_cost(parsed) == {"ops": 0.0, "bytes": 0.0}
    taken = sum(s for s, _, _ in sum(by_kind.values(), []))
    assert got["kda_time_share.train"] == pytest.approx(
        100 * taken / trace.window_s)
    least = sum(
        calls * kda_cost.roofline_seconds(cost, PEAKS)["seconds"]
        for _, calls, cost in sum(by_kind.values(), []))
    assert got["kda_roofline_share.train"] == pytest.approx(
        100 * least / taken)
    # The latent kernels at all 32 heads: one layer a micro batch.
    from benchmark.layer_metrics import _mla
    names = {text.split(" ", 1)[0] for text, _, _ in trace.ops(_mla.is_mla)}
    assert names and all("__h32" in n for n in names), names


def test_the_new_readers_return_nothing_without_the_kernels():
    """The parent's trace, or any other model's: nothing is returned and
    nothing raises; an event whose operands are not the kernels' is not
    costed."""
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    dense = ("%fusion.9 = bf16[8192,2304]{1,0:T(8,128)(2,1)} fusion("
             "bf16[8192,2304]{1,0:T(8,128)(2,1)} %h)")
    lightning = ("%tepdist_lightning_fwd.1 = bf16[1,32768,4096]{2,1,0} "
                 "custom-call(f32[32]{0} %d, bf16[1,32768,4096]{2,1,0} %a), "
                 "custom_call_target=\"tpu_custom_call\", "
                 "operand_layout_constraints={f32[32]{0}, "
                 "bf16[1,32768,4096]{2,1,0}}")
    odd = ("%jvp_tepdist_kda_fwd_.1 = bf16[1,128,256]{2,1,0} "
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
    for name in (CELL, "gpt2-1.5b.train.b48", "minicpm-sala.train.s32768"):
        cell = cells.load_cell(name, ROOT)
        for reader in NEW_READERS:
            assert readers[reader].read(
                Trace((dense, 0.5, 9), (lightning, 0.1, 3)), host,
                cell) is None
    cell = cells.load_cell(CELL, ROOT)
    assert readers["kda_roofline_share.train"].read(
        Trace((odd, 0.1, 3)), host, cell) is None
    assert readers["kda_time_share.train"].read(
        Trace((odd, 0.1, 3)), host, cell) == pytest.approx(5.0)
