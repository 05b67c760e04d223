"""The xing4.0-29b-a4b cell's own files: the cell loads with its readers and
the published widths, every number of the catalog's row is in the
configuration but the four cut ones, the builder draws what the reference and
the program both read and counts 913,473,668 parameters (29.5e9 whole beside
the prediction module), the planned step passes where the fp8 control fails,
``mhc_cost.py`` by hand at the cell's shape, and both new readers on an
excerpt of a trace of the cell from the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_jamba_cell import SavedTrace

from benchmark.kernels import mhc_cost
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "xing4.0-29b-a4b.train.s4096"
NEW_READERS = ("mhc_time_share.train", "mtp_time_share.train")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"]
# The catalog's row (model-configs guide, ``architectures.jsonl``:
# Xing4.0-29B-A4B, its ``config``), every key.
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


class Recorded(SavedTrace):
    """A saved list of operations as the one device of a ``TraceSummary``:
    what the scope readers walk (``devices[*].op_self_s`` by an operation's
    short name)."""

    def __init__(self, path):
        from benchmark.trace_reduce import short_name
        super().__init__(path)

        class Device:
            op_self_s = {short_name(t): s for t, s, _ in self._ops}
            op_text = {short_name(t): t for t, _, _ in self._ops}

        self.devices = [Device]


@pytest.fixture(scope="module")
def builder():
    return cells.load_module(os.path.join(BENCH, "builders", "xing.py"),
                             "bench_builder_xing")


def tiny_config(dtype="float32"):
    """The published structure small: a dense layer and two expert layers
    of 2 held of 8 experts, 4 heads behind a query latent, four lanes, 5
    Sinkhorn rounds, one prediction module."""
    with open(os.path.join(BENCH, "configs", "xing4.0-29b-a4b.json")) as f:
        config = json.load(f)
    config.update(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=20,
        kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, num_hidden_layers=3, n_routed_experts=2,
        router_num_experts=8, experts_held_first=2, num_experts_per_tok=2,
        hc_sinkhorn_iters=5, dtype=dtype,
        rope_scaling=dict(config["rope_scaling"], factor=4,
                          original_max_position_embeddings=8, beta_fast=2,
                          beta_slow=0.5),
        program={"stacked": True, "remat": True, "loss_chunk": 16,
                 "moe_tile_m": 8})
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
            t["trace_steps"]) == (16, 4096, 8, False, 1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"] if e["name"] == c["name"])
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/xing4.0-29b-a4b.json"
    assert entry["reduced"] == c["reduced"] == REDUCED
    for m in (m for m in bench["per_layer"] if m["name"] in NEW_READERS):
        assert m["workloads"] == [CELL] and m["layer"] == "models" \
            and m["moves"] == "train_tokens_per_s_chip" \
            and m["source"] == "device_trace" and m["unit"] == "%" \
            and m["better"] == "lower"
    listed = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (listed["chips"], listed["traffic"]) == (1, "train-b16-s4096-ga8")
    # The traffic file is OLMoE's cell's, as it is.
    assert listed["traffic"] == next(
        w for w in bench["workloads"]
        if w["name"] == "olmoe-1b-7b.train.s4096")["traffic"]
    assert len(listed["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(bench["workloads"]) >= 12 \
        and not [w for w in bench["workloads"] if w["chips"] != 1]
    limit = cell.spec["correct"]["limits"]["step_state_rel_err"]
    assert 0.0 < limit < 1.0 and cell.spec["correct"]["unique_sequences"] == 2


def test_every_number_of_the_catalog_row_but_the_four_cut_ones():
    c = cells.load_cell(CELL, ROOT).config
    kept = {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: c[k] for k in kept} == kept
    assert {k: c["reduced_from"][k] for k in REDUCED} \
        == {k: CATALOG[k] for k in REDUCED}
    assert "8-way expert parallel, one rank" in c["reduced_from"]["deployment"]
    # Published layers 1-5 (the leading dense layers once, then four expert
    # layers), 8 of the 64 scored experts, an eighth of the table.
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["router_num_experts"],
            c["experts_held_first"], c["vocab_size"]) \
        == (5, 1, 8, 64, 0, 131072 // 8)
    for width in ("hidden_size", "intermediate_size",
                  "moe_intermediate_size", "q_lora_rank", "kv_lora_rank",
                  "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                  "num_attention_heads", "num_experts_per_tok", "hc_mult",
                  "hc_sinkhorn_iters", "num_nextn_predict_layers",
                  "routed_scaling_factor"):
        assert width not in c["reduced"] and c[width] == CATALOG[width]
    assert set(c["assumed"]) >= {
        "sources", "mhc", "mhc_start", "mtp", "attention", "routing",
        "optimizer", "program", "norms", "initialisation", "dtype", "tokens",
        "unread_keys"}
    for paper in ("2412.19437", "2512.24880", "2409.19606"):
        assert paper in c["assumed"]["sources"]
    assert "DEPARTURE" in c["assumed"]["mhc_start"]
    assert c["mtp_loss_weight"] == 0.1 and "0.1" in c["assumed"]["mtp"]
    assert "8 chips share each expert layer" in c["deployment"] \
        and "rank 0" in c["deployment"] and "913,473,668" in c["deployment"]
    assert c["optimizer"] == {"name": "adamw_bf16_router_bias",
                              "learning_rate": 1e-05, "bias_rate": 0.001}
    assert c["program"] == {"stacked": True, "flash_block_q": 512,
                            "flash_block_k": 512, "remat": True,
                            "loss_chunk": 512, "moe_tile_m": 128}


def test_parameter_counts(builder):
    """The issue's table."""
    cell = cells.load_cell(CELL, ROOT)
    d = 3584
    attention = d * 768 + 768 + 768 * 32 * 192 + d * 576 + 512 \
        + 512 * 32 * 256 + 32 * 128 * d
    maps = 2 * (4 * d * 24 + 24 + 3)
    expert = 3 * d * 1024
    dense = attention + 2 * d + maps + 3 * d * 9216
    outside = attention + 2 * d + maps + d * 64 + 64 + expert
    assert (attention, maps, expert, dense, outside + 8 * expert) == (
        28_411_136, 688_182, 11_010_048, 128_196_918, 128_426_358)
    module = 2 * d * d + 3 * d + outside + 8 * expert
    assert module == 154_127_222
    assert builder.num_params(cell.config) == 913_473_668 \
        == dense + 4 * (outside + 8 * expert) + module + 2 * 16384 * d + d
    whole = dict(cell.config, **{k: cell.config["reduced_from"][k]
                                 for k in REDUCED}, router_num_experts=64)
    assert outside + 64 * expert == 744_989_046
    assert builder.num_params(whole) - (module + 56 * expert) \
        == 2 * dense + 38 * 744_989_046 + 2 * 131072 * d + d \
        == 29_505_505_264
    facts = builder.train_facts(cell.config)
    assert facts["resident_params"] == 913_473_668
    # What a token meets in a matmul here: half a routed expert of its 4.
    mm = attention - 768 - 512 + 2 * 4 * d * 24
    assert facts["n_params"] == mm + 3 * d * 9216 + 5 * (
        mm + d * 64 + expert + expert // 2) + 2 * d * d + 2 * 16384 * d
    tiny = tiny_config()
    params = builder.make_params(tiny, 7)
    assert builder.num_params(tiny) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    again = builder.make_params(tiny, 7)
    other = builder.make_params(tiny, 2_500_000_008)
    assert jnp.array_equal(params["tok_emb"], again["tok_emb"])
    assert not jnp.array_equal(params["tok_emb"], other["tok_emb"])
    assert set(params) == {
        "tok_emb", "norm_f", "lm_head", "mtp_eh", "mtp_hnorm", "mtp_enorm",
        "mtp_norm", "dense", "blocks", "mtp", "hcdense", "hcblocks", "hcmtp"}
    assert params["blocks"]["w_up"].shape == (2, 2, 64, 32) \
        and params["mtp"]["router"].shape == (1, 64, 8) \
        and params["hcblocks"]["phi_attn"].shape == (2, 256, 24) \
        and params["dense"]["wqb"].shape == (1, 20, 96)
    # The maps start where they do something (assumed.mhc_start).
    hc = params["hcblocks"]
    assert float(jnp.std(hc["phi_attn"])) == pytest.approx(0.01, rel=0.05)
    assert bool((hc["alpha_mlp"] == 1).all()) \
        and 0.5 < float(jnp.std(hc["b_attn"])) < 1.6
    res = hc["b_mlp"][:, 8:].reshape(2, 4, 4)
    assert float(jnp.trace(res, axis1=1, axis2=2).mean()) > 4.0
    assert bool((params["blocks"]["router_bias"] == 0).all())


def test_the_program_config_and_the_reference_read_the_same_numbers(builder):
    cell = cells.load_cell(CELL, ROOT)
    cfg = builder.program_config(cell.config)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (
                3584, 9216, 1024, 768, 512, 128, 64, 128)
    assert (cfg.num_attention_heads, cfg.heads_held, cfg.num_experts,
            cfg.experts_held, cfg.num_experts_per_tok, cfg.route_scale) == (
                32, (0, 32), 64, (0, 8), 4, 2.0)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.mhc_h_res_clamp, cfg.num_nextn_predict_layers,
            cfg.mtp_loss_weight) == (4, 20, 1e-6, (-30.0, 30.0), 1, 0.1)
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace, cfg.remat,
            cfg.loss_chunk, cfg.moe_tile_m) == (5, 1, True, 512, 128)
    # m = 0.1 ln 64 + 1: the softmax scale carries its square.
    assert cfg.softmax_scale == pytest.approx(0.14469, rel=1e-4)
    assert cfg.rope_table.scale == 1.0
    hp = builder.reference_hyper(cell.config)
    assert (hp.lanes, hp.sinkhorn_iters, hp.clamp, hp.mtp_weight, hp.top_k,
            hp.held, hp.route_scale, hp.yarn.factor) == (
                4, 20, (-30.0, 30.0), 0.1, 4, (0, 8), 2.0, 64.0)
    for bad, word in ((dict(cell.config, n_group=2), "one group"),
                      (dict(cell.config, rope_scaling={"type": "linear"}),
                       "yarn")):
        with pytest.raises(cells.BenchError, match=word):
            builder.program_config(bad)
    assert builder.PROBE == ("tok_emb", "lm_head", "norm_f", "mtp_eh",
                             "hcblocks")


def test_reference_step_agrees_with_the_program(builder):
    """A batch that repeats sequences, from the distinct ones and their
    shares; float32 against float32: rounding only. Every compared leaf,
    the prediction module's ``mtp_eh`` and the expert layers' maps among
    them."""
    config = tiny_config()
    params = builder.make_params(config, 2_500_000_001)
    unique = builder.make_tokens(config, 5, 2, 2, 32)
    index = np.array([0, 1, 1, 0, 1, 1, 1, 0])
    shares = np.bincount(index) / len(index)
    with jax.default_matmul_precision("highest"):
        loss, grads = builder.reference_step_fn(config, 1)(params, unique,
                                                           shares)
        p_loss, p_grads = jax.jit(jax.value_and_grad(
            builder.program_loss_fn(config)))(
            builder.to_program(params, config), unique[index])
    assert abs(float(loss) - float(p_loss)) < 1e-5 * float(p_loss)
    want = dict(jax.tree_util.tree_flatten_with_path(
        {k: grads[k] for k in builder.PROBE})[0])
    got = dict(jax.tree_util.tree_flatten_with_path(
        {k: p_grads[k] for k in builder.PROBE})[0])
    assert len(want) == 4 + 6
    for path, w in want.items():
        np.testing.assert_allclose(
            got[path], w, rtol=0, atol=2e-5 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


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
    spec = {"correct": {"unique_sequences": 2, "reference_chunk": 1,
                        "limits": {"step_state_rel_err": 0.0}}}
    cell = cells.Cell(name="tiny", chips=1, why="", config=config,
                      traffic=traffic, spec=spec, end_to_end=[],
                      per_layer=[], root=ROOT, bench_dir=BENCH)
    rows = list(cells.driver_for(cell).readings(
        cell, builder, jax.devices()[:1], [1], [1], HostLog()))
    sound = [r["step_state_rel_err"] for r in rows if r["side"] == "program"]
    control = [r["step_state_rel_err"] for r in rows
               if r["side"] == "control"]
    assert len(sound) == len(control) == 1
    assert min(control) > 2 * max(sound), rows
    # The maps' leaves are a slot of their own beside the outside leaves'.
    assert any("hcblocks" in k for k in rows[0]), rows[0]


def test_mhc_cost_by_hand_at_the_cells_shape():
    """8,192 tokens of four lanes of 3,584 in bf16: the read moves the
    stream in and the sub-layer's input out, 5 d values a token, the write
    the stream in and out and the output in, 9 d; 14 d a sub-layer's
    forward, twice that backward; bound by HBM."""
    tokens, n, d = 8192, 4, 3584
    read, write = mhc_cost.read(tokens, n, d), mhc_cost.write(tokens, n, d)
    assert read["bytes"] == tokens * 5 * d * 2 == 293_601_280
    assert write["bytes"] == tokens * 9 * d * 2 == 528_482_304
    assert (read["bytes"] + write["bytes"]) / tokens == 14 * d * 2 == 100_352
    assert mhc_cost.backward(read)["bytes"] == 2 * read["bytes"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for cost in (read, write, mhc_cost.backward(write)):
        least = mhc_cost.roofline_seconds(cost, peaks)
        assert least["bound"] == "memory"
        assert least["seconds"] == pytest.approx(cost["bytes"] / 819e9)
    # 48 applications a micro batch, 8 micro batches a step: forward,
    # recomputed, and the backward at twice the bytes.
    step = 8 * 12 * 4 * (read["bytes"] + write["bytes"]) / 819e9
    assert 0.38 < step < 0.39


def test_both_readers_on_an_excerpt_of_the_cells_trace():
    """``testdata/xing.ops.json``: operations of one traced step of the cell
    on a v5e (PR 61's chip run) with the window they came from, and
    ``testdata/xing.scopes.json``: their ``tf_op`` paths."""
    from benchmark.layer_metrics import _mhc, _mtp, _scopes
    cell = cells.load_cell(CELL, ROOT)
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    trace = Recorded(os.path.join(BENCH, "testdata", "xing.ops.json"))
    with open(os.path.join(BENCH, "testdata", "xing.scopes.json")) as f:
        scopes = json.load(f)
    cell.facts["scopes"] = _scopes.by_part(trace, scopes)
    cell.facts["trace_path"] = "recorded"
    original = _scopes.operation_scopes
    _scopes.operation_scopes = lambda path: scopes
    find = _mtp.trace_reduce.find_xplane
    _mtp.trace_reduce.find_xplane = lambda path: path
    try:
        got = {name: readers[name].read(trace, {}, cell)
               for name in (*NEW_READERS, "mla_time_share.train",
                            "gmm_time_share.train")}
    finally:
        _scopes.operation_scopes = original
        _mtp.trace_reduce.find_xplane = find
    for name, value in got.items():
        assert isinstance(value, float) and 0.0 < value < 100.0, (name, got)
    by_sub = cell.facts["scopes"]["by_sub"]
    assert {sub for _, sub in by_sub} >= set(_mhc.SCOPES)
    assert got["mhc_time_share.train"] == pytest.approx(
        100 * sum(s for (_, sub), s in by_sub.items() if sub in _mhc.SCOPES)
        / trace.window_s)
    # The module is one layer of six and a second head pass.
    assert got["mtp_time_share.train"] > got["mhc_time_share.train"] / 6


def test_the_new_readers_return_nothing_without_the_scopes():
    """The parent's trace, or any other model's: nothing is returned and
    nothing raises."""
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}

    class Device:
        op_self_s = {"fusion.9": 0.5, "custom-call.3": 0.1}
        op_text = {"fusion.9": "%fusion.9 = ...", "custom-call.3": "..."}

    class Trace:
        window_s = 2.0
        devices = [Device()]

    from benchmark.layer_metrics import _scopes
    paths = {"fusion.9": "jit(step)/while/body/part_mixer/mla_q/dot_general",
             "custom-call.3": "jit(step)/part_head_loss/while/body/exp"}
    for name in (CELL, "sarvam-105b.train.s16384"):
        cell = cells.load_cell(name, ROOT)
        for reader in NEW_READERS:     # not traced: no trace path
            assert readers[reader].read(Trace(), {}, cell) is None
        cell.facts["scopes"] = _scopes.by_part(Trace(), paths)
        assert readers["mhc_time_share.train"].read(Trace(), {}, cell) is None
    # A path that only resembles the scope's word names no module.
    assert _scopes._whole_word(["mtp"]).search("jit(s)/mtp_in/add") is None
    assert _scopes.place("a/part_moe/mhc_write/mul")[2] == "mhc_write"
