"""The OLMoE cell's own files: the cell loads with its readers, the builder
draws what the reference and the program both read, the planned step passes
where the fp8 control fails, ``gmm_cost.py`` against hand-counted cases, and
the expert layer's readers on a made-up trace."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.kernels import gmm_cost
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "olmoe-1b-7b.train.s4096"
NEW_READERS = ("moe_time_share.train", "gmm_time_share.train",
               "gmm_roofline_share.train", "attn_time_share.train",
               "attn_roofline_share.train")


@pytest.fixture(scope="module")
def builder():
    return cells.load_module(os.path.join(ROOT, "benchmark", "builders",
                                          "olmoe.py"), "bench_builder_olmoe")


def tiny_config(dtype="float32"):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmoe-1b-7b.json")) as f:
        config = json.load(f)
    config.update(
        vocab_size=512, hidden_size=64, intermediate_size=32,
        max_position_embeddings=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, num_experts=8,
        num_experts_per_tok=2, dtype=dtype,
        program={"stacked": True, "remat": True, "loss_chunk": 16,
                 "moe_tile_m": 8})
    return config


def test_the_cell_loads_with_its_readers_and_published_widths():
    cell = cells.load_cell(CELL, ROOT)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= names
    assert "device_idle_share.train" in names
    assert not {"flash_time_share.train",
                "flash_roofline_share.train"} & names   # the is_flash trap
    readers = {m.NAME for m in cells.layer_metric_modules(cell.bench_dir)}
    assert set(NEW_READERS) <= readers
    t, c = cell.traffic, cell.config
    assert (t["batch"], t["seq"], t["num_micro_batches"], t["explore"]) == (
        16, 4096, 8, False)
    catalog = {"hidden_size": 2048, "intermediate_size": 1024,
               "num_attention_heads": 16, "num_key_value_heads": 16,
               "num_experts": 64, "num_experts_per_tok": 8,
               "vocab_size": 50304, "max_position_embeddings": 4096,
               "rope_theta": 10000, "rms_norm_eps": 1e-05,
               "norm_topk_prob": False, "tie_word_embeddings": False}
    assert {k: c[k] for k in catalog} == catalog
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers"] == 3


def test_parameter_counts(builder):
    cell = cells.load_cell(CELL, ROOT)
    assert builder.num_params(cell.config) == 1_464_756_224
    facts = builder.train_facts(cell.config)
    # 3 x (4 d^2 + d E + 8 x 3 d f) + V d: active, in a matmul
    assert facts["n_params"] == 3 * (4 * 2048 ** 2 + 2048 * 64
                                     + 24 * 2048 * 1024) + 50304 * 2048
    tiny = tiny_config()
    params = builder.make_params(tiny, 7)
    assert builder.num_params(tiny) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    again = builder.make_params(tiny, 7)
    other = builder.make_params(tiny, 2_500_000_008)
    assert jnp.array_equal(params["lm_head"], again["lm_head"])
    assert not jnp.array_equal(params["lm_head"], other["lm_head"])


def test_reference_step_agrees_with_the_program(builder):
    """A batch that repeats sequences, from the distinct ones and their
    shares; float32 against float32: rounding only."""
    config = tiny_config()
    params = builder.make_params(config, 2_500_000_001)
    unique = builder.make_tokens(config, 5, 2, 4, 16)
    index = np.array([0, 1, 1, 2, 3, 3, 3, 0])
    shares = np.bincount(index) / len(index)
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
                      per_layer=[], root=ROOT,
                      bench_dir=os.path.join(ROOT, "benchmark"))
    rows = list(cells.driver_for(cell).readings(
        cell, builder, jax.devices()[:1], [1, 2], [1, 2], HostLog()))
    sound = [r["step_state_rel_err"] for r in rows if r["side"] == "program"]
    control = [r["step_state_rel_err"] for r in rows
               if r["side"] == "control"]
    assert len(sound) == len(control) == 2
    assert min(control) > 2 * max(sound), rows


@pytest.mark.parametrize("rows,K,N,groups,ops,nbytes", [
    # one row through one 2 x 3 expert: 2*2*3 flops; (2 + 3 + 6) bf16
    (1, 2, 3, 1, 12.0, 22.0),
    # the cell's gate projection: 65536 rows, 64 experts of 2048 x 1024
    (65536, 2048, 1024, 64, 2.0 * 65536 * 2048 * 1024,
     2.0 * (65536 * 2048 + 65536 * 1024 + 64 * 2048 * 1024)),
    # the down projection is the same count with K and N exchanged
    (65536, 1024, 2048, 64, 274877906944.0, 671088640.0),
])
def test_gmm_cost_against_hand_counts(rows, K, N, groups, ops, nbytes):
    cost = gmm_cost.grouped_matmul(rows, K, N, groups)
    assert cost == {"ops": ops, "bytes": nbytes}


def test_gmm_roofline_is_compute_bound_at_the_cell_and_not_for_one_row():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    at_cell = gmm_cost.roofline_seconds(
        gmm_cost.grouped_matmul(65536, 2048, 1024, 64), peaks)
    assert at_cell["bound"] == "compute"
    assert at_cell["seconds"] == pytest.approx(274877906944 / 197e12)
    one_row = gmm_cost.roofline_seconds(
        gmm_cost.grouped_matmul(64, 2048, 1024, 64), peaks)
    assert one_row["bound"] == "memory"


class FakeTrace:
    """``TraceSummary``'s ``ops``/``op_seconds`` over a list of
    ``(HLO text, seconds, calls)``."""
    window_s = 2.0

    def __init__(self, ops):
        self._ops = ops

    def ops(self, match):
        return [op for op in self._ops if match(op[0])]

    def op_seconds(self, match):
        return sum(s for _, s, _ in self.ops(match))


L = "{1,0:T(8,128)(2,1)}"
GMM = (f"%tepdist_gmm_fwd.3 = bf16[81920,1024]{L} custom-call(s32[320] %a, "
       f"s32[1] %b, bf16[81920,2048]{L} %x, bf16[64,2048,1024]{L} %w), "
       'custom_call_target="tpu_custom_call"')
GMM_DW = (f"%tepdist_gmm_dw.1 = bf16[64,1024,2048]{L} custom-call(s32[320] "
          f"%a, s32[1] %b, bf16[81920,1024]{L} %x, bf16[81920,2048]{L} %dy),"
          ' custom_call_target="tpu_custom_call"')
FLASH = (f"%jvp_tepdist_flash_fwd__c1__s0.08838834764831845__h16_.2 = "
         f"(bf16[32,4096,128]{L}, f32[32,8,1,512]) custom-call("
         f"bf16[32,4096,128]{L} %q, bf16[32,4096,128]{L} %k, "
         f"bf16[32,4096,128]{L} %v), "
         'custom_call_target="tpu_custom_call"')
GATHER = f"%fusion.7 = bf16[81920,2048]{L} fusion(bf16[8192,2048]{L} %h)"
ROUTER = f"%convolution.2 = f32[8192,64] fusion(bf16[8192,2048]{L} %h)"
DENSE = f"%fusion.9 = bf16[8192,2048]{L} fusion(bf16[8192,2048]{L} %h)"


def test_the_new_readers_on_a_made_up_trace():
    cell = cells.load_cell(CELL, ROOT)
    readers = {m.NAME: m for m in cells.layer_metric_modules(cell.bench_dir)}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    host = {"peaks": peaks}
    least = 274877906944 / 197e12                  # one call, compute bound
    trace = FakeTrace([(GMM, 4 * least, 2), (GMM_DW, 2 * least, 1),
                       (FLASH, 0.1, 4), (GATHER, 0.05, 3),
                       (ROUTER, 0.01, 1), (DENSE, 0.5, 9)])

    def read(name):
        return readers[name].read(trace, host, cell)

    assert read("gmm_time_share.train") == pytest.approx(
        100 * 6 * least / 2.0)
    assert read("gmm_roofline_share.train") == pytest.approx(50.0)
    assert read("moe_time_share.train") == pytest.approx(
        100 * (6 * least + 0.06) / 2.0)
    assert read("attn_time_share.train") == pytest.approx(5.0)
    from benchmark.kernels import flash_cost
    want = flash_cost.roofline_seconds(
        flash_cost.forward((1, 32, 4096, 128), 2, True), peaks)["seconds"]
    assert read("attn_roofline_share.train") == pytest.approx(
        100 * 4 * want / 0.1)


def test_the_new_readers_return_nothing_for_a_program_without_the_layer():
    """The parent's trace, or a dense model's: no such kernel, no such
    array; nothing is returned and nothing raises."""
    readers = {m.NAME: m for m in cells.layer_metric_modules(
        os.path.join(ROOT, "benchmark"))}
    trace = FakeTrace([(DENSE, 0.5, 9)])
    host = {"peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    for cell in (cells.load_cell(CELL, ROOT),
                 cells.load_cell("gpt2-1.5b.train.b48", ROOT)):
        for name in NEW_READERS:
            assert readers[name].read(trace, host, cell) is None
