"""The Mellum2 cell's own files: the cell loads with its readers and the
published widths, the builder draws what the reference and the program both
read and counts 538.5e6 parameters, the planned step passes where the fp8
control fails, ``window_flash_cost.py`` at the cell's window and length, and
the new reader, beside the accepted ones the cell lists, on an excerpt of a
trace of the cell from the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.kernels import window_flash_cost
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "mellum2-12b-a2.5b.train.s16384"
NEW_READER = "attn_global_time_share.train"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
W, G = "sliding_attention", "full_attention"


@pytest.fixture(scope="module")
def builder():
    return cells.load_module(os.path.join(BENCH, "builders", "mellum.py"),
                             "bench_builder_mellum")


def tiny_config(dtype="float32"):
    with open(os.path.join(BENCH, "configs", "mellum2-12b-a2.5b.json")) as f:
        config = json.load(f)
    config.update(
        vocab_size=512, hidden_size=64, moe_intermediate_size=32,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window=8, router_num_experts=16, num_experts=4,
        experts_held_first=4, num_experts_per_tok=2, dtype=dtype,
        program={"stacked": True, "remat": True, "loss_chunk": 16,
                 "moe_tile_m": 8})
    # The tiny sequences are 16 long: an original context of 4 puts them
    # four times past it, as 16384 is twice past 8192.
    config["rope_parameters"] = {
        "full_attention": {"rope_type": "yarn", "rope_theta": 100,
                           "factor": 4, "original_max_position_embeddings": 4,
                           "beta_fast": 2, "beta_slow": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 100}}
    return config


def test_the_cell_loads_with_its_readers_and_published_widths():
    cell = cells.load_cell(CELL, ROOT)
    names = {m["name"] for m in cell.per_layer}
    assert {NEW_READER, "device_idle_share.train", "gmm_time_share.train",
            "attn_time_share.train", "attn_mixed_roofline_share.train",
            "step_device_ms.train", "step_host_ms.train", "plan_s",
            "first_step_s", "setup_compile_s"} <= names
    # Readers that would cost a window kernel as full causal, or reckon
    # S x k rows where the held experts see a part of them, stay out.
    assert not {"flash_time_share.train", "flash_roofline_share.train",
                "attn_roofline_share.train", "gmm_roofline_share.train",
                "moe_time_share.train"} & names
    assert NEW_READER in {m.NAME for m in
                          cells.layer_metric_modules(cell.bench_dir)}
    t, c = cell.traffic, cell.config
    assert (t["batch"], t["seq"], t["num_micro_batches"], t["explore"],
            t["trace_steps"]) == (4, 16384, 4, False, 1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"]
                 if e["name"] == "mellum2-12b-a2.5b")
    assert entry["file"] == "benchmark/configs/mellum2-12b-a2.5b.json"
    assert entry["source"] == c["source"]
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_experts", "vocab_size"]
    new = next(m for m in bench["per_layer"] if m["name"] == NEW_READER)
    assert new["workloads"] == [CELL] and bench["per_layer"][-1] is new
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    # Every key of the published config.json but the five cut ones.
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "sliding_window": 1024,
        "tie_word_embeddings": False, "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}}}
    assert {k: c[k] for k in published} == published
    assert c["reduced_from"]["num_experts"] == c["router_num_experts"] == 64
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        4, 16, 98304 // 8)
    assert c["layer_types"] == [W, W, W, G]        # published layers 0-3
    assert c["mlp_layer_types"] == ["sparse"] * 4
    assert set(c["assumed"]) >= {
        "qk_norm", "norms", "auxiliary_loss", "mtp_head", "optimizer",
        "tokens", "routing", "initialisation", "dtype", "positions"}
    assert c["optimizer"] == {"name": "adamw_bf16", "learning_rate": 1e-05}


def test_parameter_counts(builder):
    cell = cells.load_cell(CELL, ROOT)
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512            # 21.23e6
    expert = 3 * 2304 * 896                                 # 6.193e6
    layer = attention + 2304 * 64 + 16 * expert + 2 * 2304 + 2 * 128
    assert builder.num_params(cell.config) == 538_531_072 \
        == 4 * layer + 2 * 12288 * 2304 + 2304
    facts = builder.train_facts(cell.config)
    assert facts["resident_params"] == 538_531_072
    # Routed experts at the expected 2 of a token's 8 choices (16 of 64).
    assert facts["n_params"] == 4 * (attention + 2304 * 64 + 2 * expert) \
        + 12288 * 2304 == 163_381_248
    tiny = tiny_config()
    params = builder.make_params(tiny, 7)
    assert builder.num_params(tiny) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    again = builder.make_params(tiny, 7)
    other = builder.make_params(tiny, 2_500_000_008)
    assert jnp.array_equal(params["lm_head"], again["lm_head"])
    assert not jnp.array_equal(params["lm_head"], other["lm_head"])
    assert int(builder.make_tokens(tiny, 3, 2, 4, 16).max()) < 512
    cfg = builder.program_config(cell.config)
    assert cfg.experts_held == (0, 16) and cfg.num_experts == 64
    assert cfg.layer_types == (W, W, W, G) and cfg.sliding_window == 1024
    assert cfg.global_rope.scale == 1.2772588722239782
    hp = builder.reference_hyper(cell.config)
    assert hp.held == (0, 16) and hp.yarn.factor == 16.0 \
        and hp.yarn.original_max_position == 8192 and hp.eps == 1e-6


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
    batches, the kernels interpreted, ``adamw_bf16``) against the float32
    reference, and the fp8 control in its place: read as
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


def test_window_flash_cost_at_the_cells_window_and_length():
    W_, T = 1024, 16384
    assert window_flash_cost.pairs(1, 1, T, True, W_) == W_ * T - W_ * W_ / 2
    # A window layer does 12.1% of a global layer's pairs here (44% in the
    # Trinity cell): a global layer is some eight window layers of pairs.
    share = window_flash_cost.pairs(1, 32, T, True, W_) \
        / window_flash_cost.pairs(1, 32, T, True, None)
    assert share == pytest.approx(0.12109375)
    cost = window_flash_cost.forward((1, 32, T, 128), 2, True, W_, 4)
    q_io, kv_io = 32 * T * 128 * 2, 4 * T * 128 * 2
    assert cost["bytes"] == 2 * q_io + 2 * kv_io + 32 * T * 4
    assert cost["ops"] == 4 * 128 * 32 * (W_ * T - W_ * W_ / 2)


class SavedTrace:
    """``TraceSummary``'s ``ops``/``op_seconds``/``window_s`` over a saved
    list of ``(HLO text, seconds, calls)``."""

    def __init__(self, path):
        with open(path) as f:
            saved = json.load(f)
        self.window_s = saved["window_s"]
        self._ops = [tuple(op) for op in saved["ops"]]

    def ops(self, match):
        return [op for op in self._ops if match(op[0])]

    def op_seconds(self, match):
        return sum(s for _, s, _ in self.ops(match))


def test_the_new_reader_on_an_excerpt_of_the_cells_trace():
    """``testdata/mellum.ops.json``: operations of one traced step of the
    cell on a v5e (PR 33's chip run), the kernels and a few of their
    neighbours, with the window they came from."""
    cell = cells.load_cell(CELL, ROOT)
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    trace = SavedTrace(os.path.join(BENCH, "testdata", "mellum.ops.json"))
    host = {"peaks": PEAKS}
    listed = [m["name"] for m in cell.per_layer
              if m["source"] == "device_trace"
              and m["name"].split(".")[0] in (
                  "attn_global_time_share", "attn_time_share",
                  "attn_mixed_roofline_share", "gmm_time_share",
                  "moe_held_time_share")]
    got = {name: readers[name].read(trace, host, cell) for name in listed}
    assert NEW_READER in got and "attn_time_share.train" in got
    for name, value in got.items():
        assert isinstance(value, float) and 0.0 < value < 100.0, (name, got)
    from benchmark.layer_metrics import _window_flash
    flash = trace.ops(_window_flash.is_attention)
    by_label = {}
    for text, s, _ in flash:
        label = _window_flash.call_cost(text)[0]
        by_label[label] = by_label.get(label, 0.0) + s
    assert set(by_label) == {"forward", "backward_dq", "backward_dkv",
                             "forward_w1024", "backward_dq_w1024",
                             "backward_dkv_w1024"}
    unwindowed = sum(s for k, s in by_label.items() if "_w" not in k)
    assert got[NEW_READER] == pytest.approx(100 * unwindowed
                                            / trace.window_s)
    assert got[NEW_READER] < got["attn_time_share.train"] == pytest.approx(
        100 * sum(by_label.values()) / trace.window_s)
    # One global layer's kernels take longer than three window layers'.
    assert unwindowed > sum(by_label.values()) - unwindowed


def test_the_new_reader_returns_nothing_where_kinds_do_not_mix():
    """The parent's trace, a dense model's, or kernels all of one kind: no
    such split; nothing is returned and nothing raises."""
    reader = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}[
        NEW_READER]
    dense = ("%fusion.9 = bf16[8192,2048]{1,0:T(8,128)(2,1)} fusion("
             "bf16[8192,2048]{1,0:T(8,128)(2,1)} %h)")
    plain = ("%tepdist_flash_fwd__c1__s0.088__h16.1 = (bf16[32,4096,128]{2,1,"
             "0}, f32[32,8,1,512]{3,2,1,0}) custom-call(bf16[32,4096,128]"
             "{2,1,0} %a, bf16[32,4096,128]{2,1,0} %b, bf16[32,4096,128]"
             "{2,1,0} %c), custom_call_target=\"tpu_custom_call\"")

    class Trace:
        window_s = 2.0

        def __init__(self, *ops):
            self._ops = ops

        def ops(self, match):
            return [op for op in self._ops if match(op[0])]

        def op_seconds(self, match):
            return sum(s for _, s, _ in self.ops(match))

    host = {"peaks": PEAKS}
    for name in (CELL, "gpt2-1.5b.train.b48", "olmoe-1b-7b.train.s4096"):
        cell = cells.load_cell(name, ROOT)
        assert reader.read(Trace((dense, 0.5, 9)), host, cell) is None
        assert reader.read(Trace((dense, 0.5, 9), (plain, 0.1, 3)), host,
                           cell) is None
