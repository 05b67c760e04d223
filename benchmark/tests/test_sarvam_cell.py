"""The sarvam-105b cell's own files: the cell loads with its readers and the
published widths, every number of the catalog's row is in the configuration
but the four cut ones, the builder draws what the reference and the program
both read and counts 1,505,016,832 parameters, the planned step passes where
the fp8 control fails, ``mla_cost.py`` against ``flash_cost.py`` and at the
cell's shapes, and both new readers on an excerpt of a trace of the cell from
the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.kernels import flash_cost, mla_cost
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "sarvam-105b.train.s16384"
NEW_READERS = ("mla_time_share.train", "mla_roofline_share.train")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ["num_hidden_layers", "num_experts", "num_attention_heads",
           "vocab_size"]
# The catalog's row (model-configs guide, ``architectures.jsonl``:
# sarvam-105b, its ``config``), every key.
CATALOG = {
    "attn_implementation": None, "default_theta": 10000,
    "first_k_dense_replace": 1, "head_dim": 576, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "sarvam_mla",
    "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_shared_experts": 1, "q_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "deepseek_yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "tie_word_embeddings": False, "use_qk_norm": True, "v_head_dim": 128,
    "vocab_size": 262144}


@pytest.fixture(scope="module")
def builder():
    return cells.load_module(
        os.path.join(BENCH, "builders", "sarvam_mla.py"),
        "bench_builder_sarvam_mla")


def tiny_config(dtype="float32"):
    with open(os.path.join(BENCH, "configs", "sarvam-105b.json")) as f:
        config = json.load(f)
    config.update(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=2, heads_held_first=2,
        kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, num_hidden_layers=3, router_num_experts=16,
        num_experts=4, experts_held_first=4, num_experts_per_tok=2,
        dtype=dtype, program={"stacked": True, "remat": True,
                              "loss_chunk": 16, "moe_tile_m": 8})
    config["reduced_from"] = dict(config["reduced_from"],
                                  num_attention_heads=4)
    # The tiny sequences are 16 long: an original context of 4 puts them
    # four times past it, as 16384 is four times past 4096.
    config["rope_scaling"] = dict(
        config["rope_scaling"], factor=4, beta_fast=2, beta_slow=0.5,
        original_max_position_embeddings=4)
    return config


def test_the_cell_loads_with_its_readers_and_published_widths():
    cell = cells.load_cell(CELL, ROOT)
    names = {m["name"] for m in cell.per_layer}
    assert {*NEW_READERS, "device_idle_share.train", "gmm_time_share.train",
            "step_device_ms.train", "step_host_ms.train",
            "idle_attributed_share.train", "plan_s", "plan_trace_s",
            "plan_search_s", "plan_place_s", "first_step_s",
            "setup_compile_s"} == names
    found = {m.NAME for m in cells.layer_metric_modules(cell.bench_dir)}
    assert set(NEW_READERS) <= found
    t, c = cell.traffic, cell.config
    assert (t["batch"], t["seq"], t["num_micro_batches"], t["explore"],
            t["trace_steps"]) == (4, 16384, 4, False, 1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"] if e["name"] == "sarvam-105b")
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/sarvam-105b.json"
    assert entry["reduced"] == c["reduced"] == REDUCED
    for m in (m for m in bench["per_layer"] if m["name"] in NEW_READERS):
        assert CELL in m["workloads"] and m["layer"] == "kernels" \
            and m["moves"] == "train_tokens_per_s_chip"
    listed = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (listed["chips"], listed["traffic"]) == (1, "train-b4-s16384-ga4")
    limit = cell.spec["correct"]["limits"]["step_state_rel_err"]
    assert 0.0 < limit < 1.0 and cell.spec["correct"]["unique_sequences"] == 2


def test_every_number_of_the_catalog_row_but_the_four_cut_ones():
    c = cells.load_cell(CELL, ROOT).config
    kept = {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: c[k] for k in kept} == kept
    assert c["reduced_from"] == {k: CATALOG[k] for k in REDUCED}
    assert c["router_num_experts"] == CATALOG["num_experts"]
    # The floors: a dense layer and four after it, 8 experts, 1/8 vocabulary;
    # a quarter of the heads; first of each share.
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        5, 8, 262144 // 8)
    assert c["num_attention_heads"] == 16
    assert (c["experts_held_first"], c["heads_held_first"]) == (0, 0)
    # No width is cut: a head's, the latent's, the MLPs', experts a token.
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                  "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim", "head_dim", "q_head_dim",
                  "num_experts_per_tok"):
        assert width not in c["reduced"] and c[width] == CATALOG[width]
    assert set(c["assumed"]) >= {
        "qk_norm", "router", "bias_update", "rotary_pairs", "auxiliary_loss",
        "optimizer", "norms", "tokens", "routing", "initialisation",
        "dtype"}
    assert "sixteen" in c["deployment"]
    assert c["optimizer"] == {"name": "adamw_bf16_router_bias",
                              "learning_rate": 1e-05, "bias_rate": 0.001}


def test_parameter_counts(builder):
    cell = cells.load_cell(CELL, ROOT)
    d, R, Hh = 4096, 512, 16
    attention = d * Hh * 192 + d * (R + 64) + R * Hh * 256 + Hh * 128 * d
    norms = 2 * d + R
    dense = attention + norms + 3 * d * 16384
    expert = attention + norms + d * 128 + 128 + 3 * d * 2048 * (1 + 8)
    assert builder.num_params(cell.config) == 1_505_016_832 \
        == dense + 4 * expert + 2 * 32768 * d + d
    # The issue's table, 16 of 64 heads: 25,428,480 a layer of attention
    # (with the latent's norm), 226,763,264 the dense layer, 252,453,504 an
    # expert layer, 268,439,552 the eighth of the vocabulary.
    assert attention + R == 25_428_480
    assert dense == 226_763_264 and expert == 252_453_504
    assert 2 * 32768 * d + d == 268_439_552
    facts = builder.train_facts(cell.config)
    assert facts["resident_params"] == 1_505_016_832
    # Routed experts at the expected half of a choice of a token's 8.
    assert facts["n_params"] == 5 * attention + 3 * d * 16384 + 4 * (
        d * 128 + 3 * d * 2048 * 1.5) + 32768 * d == 615_776_256
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
    assert cfg.heads_held == (0, 16) and cfg.num_attention_heads == 64
    assert cfg.experts_held == (0, 8) and cfg.num_experts == 128
    assert cfg.num_hidden_layers == 5 and cfg.first_k_dense_replace == 1
    assert cfg.rope_table.scale == 1.0 and cfg.route_scale == 2.5
    assert cfg.softmax_scale == pytest.approx(0.13523377886088)
    hp = builder.reference_hyper(cell.config)
    assert hp.held == (0, 8) and hp.yarn.factor == 40.0 \
        and hp.yarn.original_max_position == 4096 and hp.eps == 1e-6 \
        and hp.route_scale == 2.5 and hp.kv_lora_rank == 512


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


@pytest.mark.parametrize("kind", ["forward", "backward_dq", "backward_dkv"])
@pytest.mark.parametrize("shape", [(1, 16, 16384, 128), (3, 25, 1024, 64)])
def test_mla_cost_is_flash_cost_at_equal_widths_and_no_shared_part(kind,
                                                                   shape):
    B, H, T, D = shape
    for causal in (True, False):
        assert getattr(mla_cost, kind)((B, H, T), (D, 0, D), 2, causal) \
            == getattr(flash_cost, kind)(shape, 2, causal)


def test_mla_cost_at_the_cells_shapes():
    """A live pair costs the forward ``2 (192 + 128)`` operations and the
    backward ``2 (3 x 192 + 2 x 128)``; ``k_rope`` and its gradient cross
    HBM once a batch row, every other operand once a head."""
    heads, widths = (1, 16, 16384), (128, 64, 128)
    live = 16 * 16384 * 16384 / 2
    fwd = mla_cost.forward(heads, widths)
    dq, dkv = (getattr(mla_cost, k)(heads, widths)
               for k in ("backward_dq", "backward_dkv"))
    assert fwd["ops"] == 640 * live
    assert dq["ops"] + dkv["ops"] == pytest.approx(1664 * live)
    assert dq["ops"] / dkv["ops"] == pytest.approx(3 / 4)
    a_head, rows = 16 * 16384 * 2, 16 * 16384 * 4
    k_rope = 16384 * 64 * 2
    assert fwd["bytes"] == a_head * (192 + 128 + 128 + 128) + k_rope + rows
    assert dq["bytes"] == a_head * (192 + 128 + 128 + 128 + 192) + k_rope \
        + 2 * rows
    assert dkv["bytes"] == a_head * (192 + 128 + 128 + 128 + 128 + 128) \
        + 2 * k_rope + 2 * rows
    # A broadcast k_rope and a v padded to 192 through the one-D kernels:
    # 1.2 times the forward's operations, 1.3 times its bytes.
    one_d = flash_cost.forward((1, 16, 16384, 192))
    assert one_d["ops"] / fwd["ops"] == pytest.approx(1.2)
    assert one_d["bytes"] / fwd["bytes"] > 1.3
    for cost in (fwd, dq, dkv):
        assert mla_cost.roofline_seconds(cost, PEAKS)["bound"] == "compute"


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


def test_both_readers_on_an_excerpt_of_the_cells_trace(capsys):
    """``testdata/sarvam_mla.ops.json``: operations of one traced step of
    the cell on a v5e (PR 45's chip run), the kernels and a few of their
    neighbours, with the window they came from."""
    from benchmark.layer_metrics import _mla
    cell = cells.load_cell(CELL, ROOT)
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    trace = SavedTrace(os.path.join(BENCH, "testdata",
                                    "sarvam_mla.ops.json"))
    host = {"peaks": PEAKS}
    got = {name: readers[name].read(trace, host, cell)
           for name in (*NEW_READERS, "gmm_time_share.train")}
    for name, value in got.items():
        assert isinstance(value, float) and 0.0 < value < 100.0, (name, got)
    printed = capsys.readouterr().out
    assert "operations" in printed and "bytes, bound by compute" in printed
    events = trace.ops(_mla.is_mla)
    by_kind = {}
    for text, s, calls in events:
        kind, cost = _mla.call_cost(text)
        by_kind.setdefault(kind, []).append((s, calls, cost))
    assert set(by_kind) == {"forward", "backward_dq", "backward_dkv"}
    # One forward a layer and micro batch: 5 x 4 calls of each kernel.
    for kind, found in by_kind.items():
        assert sum(calls for _, calls, _ in found) == 20, kind
        for _, _, cost in found:
            assert cost == getattr(mla_cost, kind)((1, 16, 16384),
                                                   (128, 64, 128))
    taken = sum(s for s, _, _ in sum(by_kind.values(), []))
    assert got["mla_time_share.train"] == pytest.approx(
        100 * taken / trace.window_s)
    least = sum(calls * mla_cost.roofline_seconds(cost, PEAKS)["seconds"]
                for _, calls, cost in sum(by_kind.values(), []))
    assert got["mla_roofline_share.train"] == pytest.approx(
        100 * least / taken)
    # No flash reader finds anything of this cell's attention.
    from benchmark.layer_metrics import _moe, _window_flash
    assert not trace.ops(_moe.is_attention)
    assert not trace.ops(_window_flash.is_attention)


def test_the_new_readers_return_nothing_without_the_kernels():
    """The parent's trace, or any other model's: nothing is returned and
    nothing raises; an event whose operands are not the kernels' five is
    not costed."""
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    dense = ("%fusion.9 = bf16[8192,2048]{1,0:T(8,128)(2,1)} fusion("
             "bf16[8192,2048]{1,0:T(8,128)(2,1)} %h)")
    flash = ("%tepdist_flash_fwd__c1__s0.088__h16.1 = (bf16[32,4096,128]{2,1,"
             "0}, f32[32,8,1,512]{3,2,1,0}) custom-call(bf16[32,4096,128]"
             "{2,1,0} %a, bf16[32,4096,128]{2,1,0} %b, bf16[32,4096,128]"
             "{2,1,0} %c), custom_call_target=\"tpu_custom_call\"")
    odd = ("%tepdist_mla_fwd__c1__s0.135__h16.1 = bf16[16,128,128]{2,1,0} "
           "custom-call(bf16[16,128,128]{2,1,0} %a), custom_call_target="
           "\"tpu_custom_call\", operand_layout_constraints={bf16[16,128,128]"
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
    for name in (CELL, "gpt2-1.5b.train.b48", "trinity-mini.train.s8192"):
        cell = cells.load_cell(name, ROOT)
        for reader in NEW_READERS:
            assert readers[reader].read(
                Trace((dense, 0.5, 9), (flash, 0.1, 3)), host, cell) is None
    cell = cells.load_cell(CELL, ROOT)
    assert readers["mla_roofline_share.train"].read(
        Trace((odd, 0.1, 3)), host, cell) is None
    assert readers["mla_time_share.train"].read(
        Trace((odd, 0.1, 3)), host, cell) == pytest.approx(5.0)
