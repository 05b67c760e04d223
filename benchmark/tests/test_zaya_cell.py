"""The zaya1-8b cell's own files: the cell loads with its readers and the
published widths, every number of the catalog's row is in the configuration
but the three cut ones, the builder draws what the reference and the program
both read and counts 1,104,975,450 parameters, the planned step passes where
the fp8 control fails, ``cca_mix_cost.py`` by hand at the cell's shape, and
both new readers on an excerpt of a trace of the cell from the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_jamba_cell import SavedTrace

from benchmark.kernels import cca_mix_cost
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "zaya1-8b.train.s8192"
NEW_READERS = ("cca_mix_time_share.train", "cca_mix_roofline_share.train")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
# The catalog's row (model-configs guide, ``architectures.jsonl``: ZAYA1-8B,
# its ``config``), every key.
CATALOG = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}


@pytest.fixture(scope="module")
def builder():
    return cells.load_module(os.path.join(BENCH, "builders", "zaya.py"),
                             "bench_builder_zaya")


def tiny_config(dtype="float32"):
    """The published ratios small: latents hidden / 2 and hidden / 8."""
    with open(os.path.join(BENCH, "configs", "zaya1-8b.json")) as f:
        config = json.load(f)
    config.update(
        vocab_size=512, hidden_size=128, head_dim=8, num_hidden_layers=3,
        layer_types=["hybrid"] * 3, router_hidden_size=16,
        moe_intermediate_size=64, dtype=dtype,
        program={"stacked": True, "remat": True, "loss_chunk": 16,
                 "moe_tile_m": 8})
    config["rope_parameters"] = dict(
        config["rope_parameters"],
        hybrid=dict(config["rope_parameters"]["hybrid"], rope_theta=100))
    return config


def test_the_cell_loads_with_its_readers_and_published_widths():
    cell = cells.load_cell(CELL, ROOT)
    names = {m["name"] for m in cell.per_layer}
    assert {*NEW_READERS, "device_idle_share.train", "gmm_time_share.train",
            "attn_time_share.train", "attn_mixed_roofline_share.train",
            "step_device_ms.train", "step_host_ms.train",
            "idle_attributed_share.train", "plan_s", "plan_trace_s",
            "plan_search_s", "plan_place_s", "first_step_s",
            "setup_compile_s"} == names
    found = {m.NAME for m in cells.layer_metric_modules(cell.bench_dir)}
    assert set(NEW_READERS) <= found
    t, c = cell.traffic, cell.config
    assert (t["batch"], t["seq"], t["num_micro_batches"], t["explore"],
            t["trace_steps"]) == (8, 8192, 8, False, 1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"] if e["name"] == "zaya1-8b")
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/zaya1-8b.json"
    assert entry["reduced"] == c["reduced"] == REDUCED
    for m in (m for m in bench["per_layer"] if m["name"] in NEW_READERS):
        assert m["workloads"] == [CELL] and m["layer"] == "kernels" \
            and m["moves"] == "train_tokens_per_s_chip" \
            and m["source"] == "device_trace"
    listed = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (listed["chips"], listed["traffic"]) == (1, "train-b8-s8192-ga8")
    assert bench["workloads"][-1] == listed       # appended, nothing moved
    limit = cell.spec["correct"]["limits"]["step_state_rel_err"]
    assert 0.0 < limit < 1.0 and cell.spec["correct"]["unique_sequences"] == 4


def test_every_number_of_the_catalog_row_but_the_three_cut_ones():
    c = cells.load_cell(CELL, ROOT).config
    kept = {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: c[k] for k in kept} == kept
    assert c["reduced_from"] == {k: CATALOG[k] for k in REDUCED}
    # One pipeline stage of eight: an eighth of the layers and of the table.
    assert (c["num_hidden_layers"], c["layer_types"], c["vocab_size"]) == (
        5, ["hybrid"] * 5, 262272 // 8)
    # No width is cut: the latents', a head's, the router's, the experts',
    # experts a token; and all 16 experts are held.
    for width in ("hidden_size", "head_dim", "num_attention_heads",
                  "num_key_value_heads", "router_hidden_size",
                  "moe_intermediate_size", "num_experts_per_tok",
                  "num_experts", "cca_time0", "cca_time1",
                  "partial_rotary_factor"):
        assert width not in c["reduced"] and c[width] == CATALOG[width]
    assert set(c["assumed"]) >= {
        "sources", "latents", "value_shift", "convolutions", "qk_mean",
        "norm_and_temperature", "rotary", "router", "gate", "bias_update",
        "left_out", "norms", "auxiliary_loss", "initialisation", "dtype",
        "optimizer", "tokens", "routing"}
    for paper in ("2510.04476", "2511.17127"):
        assert paper in c["assumed"]["sources"]
    assert "DEPARTURE" in c["assumed"]["bias_update"] \
        and "DEPARTURES" in c["assumed"]["left_out"]
    assert "one pipeline stage of eight" in c["deployment"]
    assert c["optimizer"] == {"name": "adamw_bf16_router_bias",
                              "learning_rate": 1e-05, "bias_rate": 0.001}


def test_parameter_counts(builder):
    """The issue's table."""
    cell = cells.load_cell(CELL, ROOT)
    d, D, N, R, E = 2048, 128, 10, 256, 16
    attention = d * 1024 + d * 256 + 2 * d * 128 + 1024 * d \
        + 2 * N * D + N * D + 2 * N * D * D + N * D + 2 + 2 * d
    router = d * R + R + R + 2 * R * R + R * E + E
    experts = E * 3 * d * 2048
    assert (attention, router, experts) == (5_579_778, 659_984, 201_326_592)
    assert builder.layer_params(cell.config) == 207_566_354 \
        == attention + router + experts
    assert builder.num_params(cell.config) == 1_104_975_450 \
        == 5 * 207_566_354 + 32784 * d + d
    facts = builder.train_facts(cell.config)
    assert facts["resident_params"] == 1_104_975_450
    # What a token meets in a matmul: one expert of the 16, and the head.
    assert facts["n_params"] == 5 * (
        5_242_880 + 2 * N * D * D + d * R + 2 * R * R + R * E
        + 3 * d * 2048) + 32784 * d == 161_206_272
    tiny = tiny_config()
    params = builder.make_params(tiny, 7)
    assert builder.num_params(tiny) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    again = builder.make_params(tiny, 7)
    other = builder.make_params(tiny, 2_500_000_008)
    assert jnp.array_equal(params["tok_emb"], again["tok_emb"])
    assert not jnp.array_equal(params["tok_emb"], other["tok_emb"])
    blocks = params["blocks"]
    assert blocks["router_w1"].dtype == jnp.float32 \
        and blocks["conv_w1"].dtype == jnp.float32
    # Taps of unit order (a gain a channel), matrices normal(0.02).
    assert 0.4 < float(jnp.std(blocks["conv_w1"])) < 0.6
    assert 0.015 < float(jnp.std(blocks["conv_w2"])) < 0.025
    assert float(blocks["router_gamma"][0, 0]) == 0.5
    assert int(builder.make_tokens(tiny, 3, 2, 4, 16).max()) < 512
    cfg = builder.program_config(cell.config)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.rotary_dim, cfg.rope_theta) == (8, 2, 128, 64, 5e6)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_tile_m,
            cfg.router_hidden_size) == (16, 1, 128, 256)
    assert cfg.num_hidden_layers == 5 and cfg.remat
    hp = builder.reference_hyper(cell.config)
    assert (hp.head_dim, hp.rotary_dim, hp.rope_theta, hp.eps) \
        == (128, 64, 5e6, 1e-5)


def test_reference_step_agrees_with_the_program(builder):
    """A batch that repeats sequences, from the distinct ones and their
    shares; float32 against float32: rounding only."""
    config = tiny_config()
    params = builder.make_params(config, 2_500_000_001)
    unique = builder.make_tokens(config, 5, 2, 4, 16)
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
               "seq": 16, "num_micro_batches": 4, "explore": False,
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


def test_cca_mix_cost_by_hand_at_the_cells_shape():
    """``[1, 8192]`` rows of 10 heads of 128 in bf16: a head and row costs
    the forward ``2 x 2 x 128 x 128`` operations and the backward twice
    that; the latents and the result cross HBM once, the weights and their
    float32 sums once a head; both bound by HBM."""
    rows, N, D = 8192, 10, 128
    fwd = cca_mix_cost.forward(rows, N, D)
    bwd = cca_mix_cost.backward(rows, N, D)
    assert fwd["ops"] == 65536 * rows * N == 5_368_709_120
    assert bwd["ops"] == 2 * fwd["ops"]
    array = rows * N * D * 2
    weights = N * (2 * D * D * 2 + 4 * D * 4)
    assert array == 20_971_520 and weights == 675_840
    assert fwd["bytes"] == 2 * array + weights
    assert bwd["bytes"] == 3 * array + weights + N * (2 * D * D + 4 * D) * 4
    least = {k: cca_mix_cost.roofline_seconds(c, PEAKS)
             for k, c in (("fwd", fwd), ("bwd", bwd))}
    assert least["fwd"]["bound"] == least["bwd"]["bound"] == "memory"
    assert least["fwd"]["seconds"] == pytest.approx(fwd["bytes"] / 819e9)
    assert 50e-6 < least["fwd"]["seconds"] < 53e-6
    assert 77e-6 < least["bwd"]["seconds"] < 81e-6
    # float32 latents: twice the arrays' bytes, the same operations.
    assert cca_mix_cost.forward(rows, N, D, 4)["ops"] == fwd["ops"]
    assert cca_mix_cost.forward(rows, N, D, 4)["bytes"] > 1.9 * fwd["bytes"]


def test_both_readers_on_an_excerpt_of_the_cells_trace(capsys):
    """``testdata/zaya.ops.json``: operations of one traced step of the cell
    on a v5e (PR 48's chip run), the kernels and a few of their neighbours,
    with the window they came from."""
    from benchmark.layer_metrics import _cca
    cell = cells.load_cell(CELL, ROOT)
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    trace = SavedTrace(os.path.join(BENCH, "testdata", "zaya.ops.json"))
    host = {"peaks": PEAKS}
    got = {name: readers[name].read(trace, host, cell)
           for name in (*NEW_READERS, "gmm_time_share.train",
                        "attn_time_share.train",
                        "attn_mixed_roofline_share.train")}
    for name, value in got.items():
        assert isinstance(value, float) and 0.0 < value < 100.0, (name, got)
    printed = capsys.readouterr().out
    assert "cca mixing roofline" in printed \
        and "bytes, bound by memory" in printed
    by_kind = {}
    for text, s, calls in trace.ops(_cca.is_cca_mix):
        kind, cost = _cca.call_cost(text)
        by_kind.setdefault(kind, []).append((s, calls, cost))
    assert set(by_kind) == {"forward", "backward"}
    # 8 micro batches x 5 layers: the forward twice (a walked block
    # recomputes its mixing), the backward once.
    assert sum(calls for _, calls, _ in by_kind["forward"]) == 80
    assert sum(calls for _, calls, _ in by_kind["backward"]) == 40
    for kind, found in by_kind.items():
        for _, _, cost in found:
            assert cost == getattr(cca_mix_cost, kind)(8192, 10, 128)
    taken = sum(s for s, _, _ in sum(by_kind.values(), []))
    assert got["cca_mix_time_share.train"] == pytest.approx(
        100 * taken / trace.window_s)
    least = sum(
        calls * cca_mix_cost.roofline_seconds(cost, PEAKS)["seconds"]
        for _, calls, cost in sum(by_kind.values(), []))
    assert got["cca_mix_roofline_share.train"] == pytest.approx(
        100 * least / taken)
    # The flash kernels at 8 query heads over 2 key/value heads.
    from benchmark.layer_metrics import _window_flash
    names = {text.split(" ", 1)[0] for text, _, _ in
             trace.ops(_window_flash.is_attention)}
    assert names and all("__h8" in n and "__kv2" in n for n in names), names


def test_the_new_readers_return_nothing_without_the_kernels():
    """The parent's trace, or any other model's: nothing is returned and
    nothing raises; an event whose operands are not the kernels' is not
    costed."""
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    dense = ("%fusion.9 = bf16[8192,2048]{1,0:T(8,128)(2,1)} fusion("
             "bf16[8192,2048]{1,0:T(8,128)(2,1)} %h)")
    conv = ("%tepdist_conv_fwd.1 = bf16[1,8192,5120]{2,1,0} custom-call("
            "bf16[1,8192,5120]{2,1,0} %a), custom_call_target="
            "\"tpu_custom_call\", operand_layout_constraints={bf16[1,8192,"
            "5120]{2,1,0}, f32[4,5120]{1,0}, f32[1,5120]{1,0}}")
    odd = ("%jvp_tepdist_cca_mix_fwd_.1 = bf16[1,8,128,128]{3,2,1,0} "
           "custom-call(bf16[1,128,1024]{2,1,0} %a), custom_call_target="
           "\"tpu_custom_call\", operand_layout_constraints={bf16[1,128,1024]"
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
    for name in (CELL, "gpt2-1.5b.train.b48", "jamba2-3b.train.s8192"):
        cell = cells.load_cell(name, ROOT)
        for reader in NEW_READERS:
            assert readers[reader].read(
                Trace((dense, 0.5, 9), (conv, 0.1, 3)), host, cell) is None
    cell = cells.load_cell(CELL, ROOT)
    assert readers["cca_mix_roofline_share.train"].read(
        Trace((odd, 0.1, 3)), host, cell) is None
    assert readers["cca_mix_time_share.train"].read(
        Trace((odd, 0.1, 3)), host, cell) == pytest.approx(5.0)
