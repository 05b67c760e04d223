"""The qwen3-next-80b-a3b cell's own files: the cell loads with its readers
and the published widths, every number of the catalog's row is in the
configuration but the three cut ones, the builder draws what the reference
and the program both read and counts 1,028,320,320 parameters, the planned
step passes where the fp8 control fails, ``gdn_cost.py`` by hand at the
cell's shape, and both new readers on an excerpt of a trace of the cell from
the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_jamba_cell import SavedTrace

from benchmark.kernels import gdn_cost
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "qwen3-next-80b-a3b.train.s8192"
NEW_READERS = ("gdn_time_share.train", "gdn_roofline_share.train")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
# The catalog's row (model-configs guide, ``architectures.jsonl``:
# Qwen3-Next-80B-A3B-Instruct, its ``config``), every key.
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def builder():
    return cells.load_module(
        os.path.join(BENCH, "builders", "qwen3_next.py"),
        "bench_builder_qwen3_next")


def tiny_config(dtype="float32"):
    """The published structure small: four layers, three Gated-DeltaNet to
    one attention, two value heads a key head, 16 of 32 experts held from
    the ninth on, 10 a token."""
    with open(os.path.join(BENCH, "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        config = json.load(f)
    config.update(
        vocab_size=512, hidden_size=64, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=32,
        linear_value_head_dim=32, num_attention_heads=8,
        num_key_value_heads=1, head_dim=16, rope_theta=100.0,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_experts=16, router_num_experts=32, experts_held_first=8,
        dtype=dtype,
        program={"stacked": True, "remat": True, "loss_chunk": 16,
                 "moe_tile_m": 8, "gdn_chunk": 16})
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
                 if e["name"] == "qwen3-next-80b-a3b")
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/qwen3-next-80b-a3b.json"
    assert entry["reduced"] == c["reduced"] == REDUCED
    for m in (m for m in bench["per_layer"] if m["name"] in NEW_READERS):
        assert m["workloads"] == [CELL] and m["layer"] == "kernels" \
            and m["moves"] == "train_tokens_per_s_chip" \
            and m["source"] == "device_trace" and m["unit"] == "%"
    listed = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (listed["chips"], listed["traffic"]) == (1, "train-b8-s8192-ga8")
    assert len(listed["why"]) <= 200 and len(entry["why"]) <= 200
    limit = cell.spec["correct"]["limits"]["step_state_rel_err"]
    assert 0.0 < limit < 1.0 and cell.spec["correct"]["unique_sequences"] == 4


def test_every_number_of_the_catalog_row_but_the_three_cut_ones():
    c = cells.load_cell(CELL, ROOT).config
    kept = {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: c[k] for k in kept} == kept
    assert c["reduced_from"] == {k: CATALOG[k] for k in REDUCED}
    # Published layers 0-3 (one whole period), 64 of the 512 scored
    # experts, an eighth of the table.
    assert (c["num_hidden_layers"], c["num_experts"],
            c["router_num_experts"], c["experts_held_first"],
            c["vocab_size"]) == (4, 64, 512, 0, 151936 // 8)
    for width in ("hidden_size", "head_dim", "linear_key_head_dim",
                  "linear_value_head_dim", "linear_num_key_heads",
                  "linear_num_value_heads", "linear_conv_kernel_dim",
                  "moe_intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "num_experts_per_tok",
                  "shared_expert_intermediate_size",
                  "partial_rotary_factor"):
        assert width not in c["reduced"] and c[width] == CATALOG[width]
    assert set(c["assumed"]) >= {
        "sources", "layer_pattern", "norms", "gdn_equations",
        "column_order", "decay_parameters", "attention", "experts",
        "auxiliary_loss", "mtp", "initialisation", "dtype", "optimizer",
        "tokens", "routing"}
    assert "2412.06464" in c["assumed"]["sources"] \
        and "modeling_qwen3_next.py" in c["assumed"]["sources"]
    assert "DEPARTURE" in c["assumed"]["decay_parameters"]
    assert "LEFT OUT" in c["assumed"]["mtp"]
    assert "one rank of eight" in c["deployment"]
    assert c["optimizer"] == {"name": "adamw_bf16", "learning_rate": 1e-05}


def test_parameter_counts(builder):
    """The issue's table."""
    cell = cells.load_cell(CELL, ROOT)
    d = 2048
    gdn = d * 12288 + d * 64 + 4 * 8192 + 64 + 128 + 4096 * d
    attn = d * 8192 + 2 * d * 512 + 512 + 4096 * d
    experts = 64 * 3 * d * 512 + 3 * d * 512 + d + d * 512
    assert (gdn, attn, experts) == (33_718_464, 27_263_488, 205_522_944)
    layers = 3 * (gdn + experts + 2 * d) + (attn + experts + 2 * d)
    assert layers == 950_527_040
    assert builder.num_params(cell.config) == 1_028_320_320 \
        == layers + 2 * 18992 * d + d
    assert builder.runs(cell.config) == [("linear_attention", 3),
                                         ("full_attention", 1)]
    facts = builder.train_facts(cell.config)
    assert facts["resident_params"] == 1_028_320_320
    # What a token meets in a matmul: 1.25 routed experts, and the head.
    gdn_mm, attn_mm = gdn - 4 * 8192 - 64 - 128, attn - 512
    assert facts["n_params"] == 3 * gdn_mm + attn_mm + 4 * (
        d * 512 + d + 3 * d * 512 + 3 * d * 512 * 10 // 8) + 18992 * d \
        == 199_729_152
    tiny = tiny_config()
    params = builder.make_params(tiny, 7)
    assert builder.num_params(tiny) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    again = builder.make_params(tiny, 7)
    other = builder.make_params(tiny, 2_500_000_008)
    assert jnp.array_equal(params["tok_emb"], again["tok_emb"])
    assert not jnp.array_equal(params["tok_emb"], other["tok_emb"])
    assert set(params) == {"tok_emb", "norm_f", "lm_head", "run0", "run1"}
    run = params["run0"]
    assert run["wqkv"].shape == (3, 64, 256) \
        and run["conv"].shape == (3, 4, 256) \
        and run["wba"].shape == (3, 64, 8) \
        and run["w_gate"].shape == (3, 16, 64, 32) \
        and run["router"].shape == (3, 64, 32) \
        and run["shared_expert_gate"].shape == (3, 64, 1)
    assert run["A_log"].dtype == jnp.float32 \
        and run["dt_bias"].shape == (3, 4)
    # Zero-centred norm leaves start at 0, the gated norm's gain at 1.
    assert not np.asarray(run["input_ln"]).any() \
        and not np.asarray(params["norm_f"]).any() \
        and not np.asarray(params["run1"]["q_norm"]).any() \
        and np.asarray(run["o_norm"]).min() == 1.0
    # A = log U(1, 16); dt the inverse softplus of U_log(0.001, 0.1).
    assert 0.0 <= float(run["A_log"].min()) \
        and float(run["A_log"].max()) <= np.log(16.0)
    step = jax.nn.softplus(run["dt_bias"])
    assert 0.0009 < float(step.min()) and float(step.max()) < 0.11
    assert "wa" in params["run1"] and "conv" not in params["run1"]
    assert int(builder.make_tokens(tiny, 3, 2, 4, 16).max()) < 512
    cfg = builder.program_config(cell.config)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_conv_kernel_dim,
            cfg.gdn_chunk) == (16, 32, 128, 4, cell.config["program"][
                "gdn_chunk"])
    assert cfg.kinds == ("linear_attention",) * 3 + ("full_attention",)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.rotary_dim, cfg.rope_theta) == (16, 2, 256, 64, 1e7)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.moe_tile_m) == (512, (0, 64), 10,
                                cell.config["program"]["moe_tile_m"])
    assert cfg.num_hidden_layers == 4 and cfg.remat
    hp = builder.reference_hyper(cell.config)
    assert (hp.key_heads, hp.value_heads, hp.n_head, hp.n_kv_head,
            hp.rotary_dim, hp.top_k, hp.held, hp.eps) \
        == (16, 32, 16, 2, 64, 10, (0, 64), 1e-6)
    bad = dict(cell.config, mlp_only_layers=[0])
    with pytest.raises(cells.BenchError, match="mlp_only_layers"):
        builder.model_sizes(bad)


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
    batches, the kernels interpreted, ``adamw_bf16``) against the float32
    reference, and the fp8 control in its place: read as
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


def test_gdn_cost_by_hand_at_the_cells_shape():
    """``[1, 8192]`` tokens of 16 key heads under 32 value heads of 128 +
    128 channels in bf16: a token and value head costs the forward the
    recurrence's three products with the state, ``6 x 128 x 128``
    operations, and the backward fourteen halves of that; the operands and
    results cross HBM once, ``g`` and ``beta`` ``[T, 32]`` in float32; both
    bound by HBM."""
    T, Hk, Hv, K = 8192, 16, 32, 128
    fwd = gdn_cost.forward(T, Hk, Hv, K, K)
    bwd = gdn_cost.backward(T, Hk, Hv, K, K)
    assert fwd["ops"] == 6 * 16384 * T * Hv == 25_769_803_776
    assert bwd["ops"] == 14 * 16384 * T * Hv
    narrow, wide, head = T * Hk * K, T * Hv * K, T * Hv
    assert fwd["bytes"] == (2 * narrow + 2 * wide) * 2 + 2 * head * 4 \
        == 203_423_744
    assert bwd["bytes"] == (4 * narrow + 4 * wide) * 2 + 4 * head * 4
    least = {k: gdn_cost.roofline_seconds(c, PEAKS)
             for k, c in (("fwd", fwd), ("bwd", bwd))}
    assert least["fwd"]["bound"] == least["bwd"]["bound"] == "memory"
    assert least["fwd"]["seconds"] == pytest.approx(fwd["bytes"] / 819e9)
    assert 245e-6 < least["fwd"]["seconds"] < 250e-6
    assert 490e-6 < least["bwd"]["seconds"] < 500e-6
    # The operations alone would take 0.13 ms forward.
    assert 130e-6 < fwd["ops"] / 197e12 < 132e-6
    # As many key heads as value heads and a decay a head: what the
    # broadcast call moves less the per-channel decays.
    assert gdn_cost.forward(T, Hv, Hv, K, K)["bytes"] \
        == 4 * wide * 2 + 2 * head * 4
    # float32 activations: more bytes, the same operations.
    assert gdn_cost.forward(T, Hk, Hv, K, K, 4)["ops"] == fwd["ops"]
    assert gdn_cost.forward(T, Hk, Hv, K, K, 4)["bytes"] > 1.9 * fwd["bytes"]


def test_both_readers_on_an_excerpt_of_the_cells_trace(capsys):
    """``testdata/qwen3_next.ops.json``: operations of one traced step of
    the cell on a v5e (PR 54's chip run), the kernels and a few of their
    neighbours, with the window they came from."""
    from benchmark.layer_metrics import _gdn
    cell = cells.load_cell(CELL, ROOT)
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    trace = SavedTrace(os.path.join(BENCH, "testdata",
                                    "qwen3_next.ops.json"))
    host = {"peaks": PEAKS}
    got = {name: readers[name].read(trace, host, cell)
           for name in (*NEW_READERS, "gmm_time_share.train",
                        "attn_time_share.train",
                        "attn_mixed_roofline_share.train")}
    for name, value in got.items():
        assert isinstance(value, float) and 0.0 < value < 100.0, (name, got)
    printed = capsys.readouterr().out
    assert "gated delta rule roofline" in printed \
        and "bytes, bound by memory" in printed
    by_kind = {}
    for text, s, calls in trace.ops(_gdn.is_gdn):
        parsed = _gdn.parse(text)
        assert parsed[1:] == (8192, 16, 32, 128, 2), parsed
        by_kind.setdefault(parsed[0], []).append(
            (s, calls, _gdn.call_cost(parsed)))
    assert set(by_kind) == {"forward", "backward"}
    # 8 micro batches x 3 Gated-DeltaNet layers: the forward once (the walk
    # keeps it), the backward once.
    assert sum(calls for _, calls, _ in by_kind["forward"]) == 24
    assert sum(calls for _, calls, _ in by_kind["backward"]) == 24
    for _, _, cost in by_kind["forward"]:
        assert cost == gdn_cost.forward(8192, 16, 32, 128, 128)
    for _, _, cost in by_kind["backward"]:
        assert cost == gdn_cost.backward(8192, 16, 32, 128, 128)
    taken = sum(s for s, _, _ in sum(by_kind.values(), []))
    assert got["gdn_time_share.train"] == pytest.approx(
        100 * taken / trace.window_s)
    least = sum(
        calls * gdn_cost.roofline_seconds(cost, PEAKS)["seconds"]
        for _, calls, cost in sum(by_kind.values(), []))
    assert got["gdn_roofline_share.train"] == pytest.approx(
        100 * least / taken)
    # The flash kernels at 16 query heads over 2 key/value heads.
    names = {text.split(" ", 1)[0] for text, _, _ in trace.ops(
        lambda t: "tepdist_flash_" in t.split(" ", 1)[0])}
    assert names and all("__h16__kv2" in n for n in names), names


def test_the_new_readers_return_nothing_without_the_kernels():
    """The parent's trace, or any other model's: nothing is returned and
    nothing raises; an event whose operands are not the kernels' is not
    costed."""
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    dense = ("%fusion.9 = bf16[8192,2048]{1,0:T(8,128)(2,1)} fusion("
             "bf16[8192,2048]{1,0:T(8,128)(2,1)} %h)")
    kda = ("%tepdist_kda_fwd.1 = bf16[1,8192,4096]{2,1,0} custom-call("
           "bf16[1,8192,4096]{2,1,0} %q), custom_call_target="
           "\"tpu_custom_call\", operand_layout_constraints={"
           "bf16[1,8192,4096]{2,1,0}, bf16[1,8192,4096]{2,1,0}, "
           "bf16[1,8192,4096]{2,1,0}, f32[1,8192,4096]{2,1,0}, "
           "f32[1,8192,32]{2,1,0}}")
    odd = ("%jvp_tepdist_gdn_fwd_.1 = bf16[1,128,256]{2,1,0} "
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
                 "kimi-linear-48b-a3b.train.s8192"):
        cell = cells.load_cell(name, ROOT)
        for reader in NEW_READERS:
            assert readers[reader].read(
                Trace((dense, 0.5, 9), (kda, 0.1, 3)), host, cell) is None
    cell = cells.load_cell(CELL, ROOT)
    assert readers["gdn_roofline_share.train"].read(
        Trace((odd, 0.1, 3)), host, cell) is None
    assert readers["gdn_time_share.train"].read(
        Trace((odd, 0.1, 3)), host, cell) == pytest.approx(5.0)
