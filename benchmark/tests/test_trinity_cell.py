"""The Trinity-Mini cell's own files: the cell loads with its readers and
the published widths, the builder draws what the reference and the program
both read, the planned step passes where the fp8 control fails,
``window_flash_cost.py`` against ``flash_cost.py`` and hand counts, and the
two new readers, beside the accepted ones the cell lists, on an excerpt of
a trace of the cell from the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.kernels import flash_cost, window_flash_cost
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "trinity-mini.train.s8192"
NEW_READERS = ("attn_mixed_roofline_share.train",
               "moe_held_time_share.train")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def builder():
    return cells.load_module(os.path.join(BENCH, "builders", "afmoe.py"),
                             "bench_builder_afmoe")


def tiny_config(dtype="float32"):
    with open(os.path.join(BENCH, "configs", "trinity-mini.json")) as f:
        config = json.load(f)
    config.update(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, sliding_window=8,
        router_num_experts=16, num_experts=4, experts_held_first=4,
        num_experts_per_tok=2, dtype=dtype,
        program={"stacked": True, "remat": True, "loss_chunk": 16,
                 "moe_tile_m": 8})
    return config


def test_the_cell_loads_with_its_readers_and_published_widths():
    cell = cells.load_cell(CELL, ROOT)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= names
    # The kernels' time goes by their names alone: the accepted readers.
    assert {"device_idle_share.train", "gmm_time_share.train",
            "attn_time_share.train", "step_device_ms.train"} <= names
    # Readers that would cost a window kernel as full causal, or reckon
    # S x k rows where the held experts see a part of them, stay out.
    assert not {"flash_time_share.train", "flash_roofline_share.train",
                "attn_roofline_share.train", "gmm_roofline_share.train",
                "moe_time_share.train"} & names
    readers = {m.NAME for m in cells.layer_metric_modules(cell.bench_dir)}
    assert set(NEW_READERS) <= readers
    t, c = cell.traffic, cell.config
    assert (t["batch"], t["seq"], t["num_micro_batches"], t["explore"]) == (
        8, 8192, 8, False)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == "trinity-mini")
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
    # Every number of the published config.json but the five cut keys.
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "moe_intermediate_size": 1024, "n_group": 1,
        "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_scale": 2.826,
        "sliding_window": 2048, "topk_group": 1, "mup_enabled": True,
        "route_norm": True, "score_func": "sigmoid",
        "tie_word_embeddings": False, "use_grouped_mm": True,
        "model_type": "afmoe", "hidden_act": "silu", "rope_scaling": None}
    assert {k: c[k] for k in published} == published
    assert c["reduced_from"]["num_experts"] == c["router_num_experts"] == 128
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"]) == (5, 1, 32, 200192 // 8)
    assert c["layer_types"] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]   # published layers 1-5
    assert set(c["assumed"]) >= {
        "mup_embedding_scale", "sandwich_norms", "qk_norm", "positions",
        "gated_attention", "bias_update", "initialisation", "dtype",
        "tokens", "routing"}
    assert c["optimizer"]["bias_rate"] == c["load_balance_coeff"]


def test_parameter_counts(builder):
    cell = cells.load_cell(CELL, ROOT)
    assert builder.num_params(cell.config) == 1_108_127_488
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    facts = builder.train_facts(cell.config)
    assert facts["resident_params"] == 1_108_127_488
    # Routed experts at the expected 2 of a token's 8 choices (32 of 128).
    assert facts["n_params"] == (
        attention + 3 * 2048 * 6144
        + 4 * (attention + 2048 * 128 + 3 * 2048 * 1024 * (1 + 2))
        + 25024 * 2048)
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
    assert cfg.experts_held == (0, 32) and cfg.num_experts == 128
    assert builder.reference_hyper(cell.config).held == (0, 32)


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


@pytest.mark.parametrize("shape,dtype_bytes,causal", [
    ((3, 25, 1024, 64), 2, True), ((2, 16, 4096, 128), 2, True),
    ((1, 1, 4, 2), 4, True), ((2, 4, 128, 64), 2, False)])
def test_window_flash_cost_is_flash_cost_where_they_overlap(shape,
                                                            dtype_bytes,
                                                            causal):
    H, T = shape[1], shape[2]
    for kind in ("forward", "backward_dq", "backward_dkv"):
        want = getattr(flash_cost, kind)(shape, dtype_bytes, causal)
        for kw in ({}, {"kv_heads": H}, {"window": None, "kv_heads": None}):
            assert getattr(window_flash_cost, kind)(
                shape, dtype_bytes, causal, **kw) == want
        if causal:                # a window that reaches every earlier key
            for window in (T, T + 1, 10 * T):
                assert getattr(window_flash_cost, kind)(
                    shape, dtype_bytes, causal, window, H) == want


def test_window_flash_cost_by_hand():
    # T = 8, window 3: query i sees min(i + 1, 3) keys: 1+2+3+3+3+3+3+3 = 21
    # = W*T - W*W/2 + W/2 by exact count; the formula keeps to the
    # continuous triangle as flash_cost's T*T/2 does: 3*8 - 4.5 = 19.5.
    assert window_flash_cost.pairs(1, 1, 8, True, 3) == 19.5
    assert window_flash_cost.pairs(1, 1, 8, True, None) == 32.0
    # The cell's window layer does 44% of its global layer's pairs.
    share = window_flash_cost.pairs(1, 32, 8192, True, 2048) \
        / window_flash_cost.pairs(1, 32, 8192, True, None)
    assert share == pytest.approx(0.4375)
    # 32 query heads over 4: k and v (and dk, dv) cross at their own count.
    cost = window_flash_cost.forward((1, 32, 8192, 128), 2, True, 2048, 4)
    q_io, kv_io = 32 * 8192 * 128 * 2, 4 * 8192 * 128 * 2
    assert cost["bytes"] == 2 * q_io + 2 * kv_io + 32 * 8192 * 4
    assert cost["ops"] == 4 * 128 * 32 * (2048 * 8192 - 2048 * 2048 / 2)
    dkv = window_flash_cost.backward_dkv((1, 32, 8192, 128), 2, True, 2048,
                                         4)
    assert dkv["bytes"] == 2 * q_io + 4 * kv_io + 2 * 32 * 8192 * 4
    dq = window_flash_cost.backward_dq((1, 32, 8192, 128), 2, True, 2048, 4)
    assert dq["ops"] + dkv["ops"] == pytest.approx(2.5 * cost["ops"])


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


def test_the_new_readers_on_an_excerpt_of_the_cells_trace():
    """``testdata/trinity.ops.json``: operations of one traced step of the
    cell on a v5e (PR 31's chip run), the kernels and a few of their
    neighbours, with the window they came from."""
    cell = cells.load_cell(CELL, ROOT)
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    trace = SavedTrace(os.path.join(BENCH, "testdata", "trinity.ops.json"))
    host = {"peaks": PEAKS}
    got = {name: readers[name].read(trace, host, cell)
           for name in NEW_READERS + ("gmm_time_share.train",
                                      "attn_time_share.train")}
    for name, value in got.items():
        assert isinstance(value, float) and 0.0 < value < 100.0, (name, got)
    from benchmark.layer_metrics import _window_flash
    flash = trace.ops(_window_flash.is_attention)
    labels = {_window_flash.call_cost(text)[0] for text, _, _ in flash}
    assert labels == {"forward", "backward_dq", "backward_dkv",
                      "forward_w2048", "backward_dq_w2048",
                      "backward_dkv_w2048"}
    # The accepted time reader finds the same events by its shorter name.
    assert got["attn_time_share.train"] == pytest.approx(
        100 * sum(s for _, s, _ in flash) / trace.window_s)
    # A window kernel costed as full causal would claim 2.3 times the work.
    full = sum(calls * flash_cost.roofline_seconds(
        flash_cost.forward((1, 32, 8192, 128)), PEAKS)["seconds"]
        for text, _, calls in flash if "fwd" in text and "__w2048" in text)
    own = sum(calls * window_flash_cost.roofline_seconds(
        _window_flash.call_cost(text)[1], PEAKS)["seconds"]
        for text, _, calls in flash if "fwd" in text and "__w2048" in text)
    assert full / own == pytest.approx(1 / 0.4375, rel=0.02)
    rows = readers["moe_held_time_share.train"].layout_rows(trace)
    assert rows == {73984}           # (65536 / 256 + 32 + 1) tiles of 256
    assert got["moe_held_time_share.train"] \
        > got["gmm_time_share.train"]


def test_the_new_readers_return_nothing_for_a_program_without_the_layer():
    """The parent's trace, or a dense model's: no such kernel, no such
    array; nothing is returned and nothing raises."""
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    dense = ("%fusion.9 = bf16[8192,2048]{1,0:T(8,128)(2,1)} fusion("
             "bf16[8192,2048]{1,0:T(8,128)(2,1)} %h)")

    class Empty:
        window_s = 2.0

        def ops(self, match):
            return [op for op in [(dense, 0.5, 9)] if match(op[0])]

        def op_seconds(self, match):
            return sum(s for _, s, _ in self.ops(match))

    host = {"peaks": PEAKS}
    for name in (CELL, "gpt2-1.5b.train.b48", "olmoe-1b-7b.train.s4096"):
        cell = cells.load_cell(name, ROOT)
        for reader in NEW_READERS:
            assert readers[reader].read(Empty(), host, cell) is None
