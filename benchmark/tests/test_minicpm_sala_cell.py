"""The MiniCPM-SALA cell's own files: the cell loads with its readers and the
published widths, the builder draws what the reference and the program both
read and counts the parameters its ``deployment`` states, the check batch
reaches the probe leaves inside both kinds of walk, the planned step passes
where the fp8 control fails, the builder holds the linear-attention kernels
and counts the differing sets, the two cost files at the cell's shapes, and
the four new readers on an excerpt of a trace of the cell from the chip."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.kernels import lightning_cost, topk_attn_cost
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "minicpm-sala.train.s32768"
NEW_READERS = ("lin_attn_time_share.train", "lin_attn_roofline_share.train",
               "topk_attn_time_share.train", "topk_attn_roofline_share.train")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


@pytest.fixture(scope="module")
def builder():
    return cells.load_module(
        os.path.join(BENCH, "builders", "minicpm_sala.py"),
        "bench_builder_minicpm_sala")


def cell_config():
    with open(os.path.join(BENCH, "configs", "minicpm-sala.json")) as f:
        return json.load(f)


def tiny_config(dtype="float32"):
    """The published structure small: 2 key/value groups, an uneven
    ``mixer_types``, a geometry under which 128 positions are past
    ``dense_len``."""
    config = cell_config()
    config.update(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
        mixer_types=[SPARSE, LIGHTNING, LIGHTNING, SPARSE, LIGHTNING],
        dim_model_base=32, dtype=dtype,
        sparse_config={"block_size": 8, "kernel_size": 4, "kernel_stride": 2,
                       "init_blocks": 1, "window_size": 16, "topk": 4,
                       "dense_len": 32},
        held={"first_layer": 0, "published_layers": 8},
        program={"stacked": True, "remat": True, "loss_chunk": 48})
    return config


def test_the_cell_loads_with_its_readers_and_published_widths():
    cell = cells.load_cell(CELL, ROOT)
    names = {m["name"] for m in cell.per_layer}
    assert {*NEW_READERS, "device_idle_share.train", "step_device_ms.train",
            "step_host_ms.train", "plan_s", "first_step_s", "setup_compile_s",
            "plan_trace_s", "plan_search_s", "plan_place_s",
            "idle_attributed_share.train"} == names
    found = {m.NAME for m in cells.layer_metric_modules(cell.bench_dir)}
    assert set(NEW_READERS) <= found
    t, c = cell.traffic, cell.config
    assert (t["batch"], t["seq"], t["num_micro_batches"], t["explore"],
            t["trace_steps"]) == (2, 32768, 2, False, 1)
    assert cell.spec["end_to_end"] == ["train_tokens_per_s_chip", "setup_s"]
    assert cell.spec["correct"]["unique_sequences"] == 2
    assert list(cell.spec["correct"]["limits"]) == ["step_state_rel_err"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # Found by name, not by place: a later PR appends to these lists.
    entry = next(e for e in bench["configs"] if e["name"] == "minicpm-sala")
    assert entry["file"] == "benchmark/configs/minicpm-sala.json"
    assert entry["source"] == c["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "mixer_types", "vocab_size"]
    assert set(c["reduced_from"]) == set(c["reduced"])
    new = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == list(NEW_READERS)
    for m in new:
        assert m["workloads"] == [CELL] and m["layer"] == "kernels"
        assert m["moves"] == "train_tokens_per_s_chip" and m["unit"] == "%"
        assert m["better"] == ("lower" if "time_share" in m["name"]
                               else "higher")
    listed = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        "minicpm-sala", "train-b2-s32768-ga2", 1)
    assert len(listed["why"]) <= 200 and len(entry["why"]) <= 200
    # Not on the readers that take every custom call, nor on the flash ones.
    for m in bench["per_layer"]:
        if m["name"].startswith(("flash_", "attn_", "ssm_", "gmm_", "moe_")):
            assert CELL not in m["workloads"], m["name"]
    # Every key of the published config.json but the cut ones.
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "num_attention_heads": 32, "num_key_value_heads": 2, "qk_norm": True,
        "rand_init": False, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
        "dim_model_base": 256, "tie_word_embeddings": False,
        "use_output_gate": True, "use_output_norm": True,
        "attn_use_output_gate": True}
    assert {k: c[k] for k in published} == published
    assert (c["num_hidden_layers"], c["vocab_size"]) == (4, 18432)
    assert c["mixer_types"] == [SPARSE] + [LIGHTNING] * 3
    assert c["vocab_size"] % 128 == 0 and c["vocab_size"] >= 73448 / 4
    assert c["reduced_from"]["num_hidden_layers"].startswith("32;")
    assert c["reduced_from"]["vocab_size"].startswith("73448;")
    assert c["sparse_config"] == {
        "block_size": 64, "kernel_size": 32, "kernel_stride": 16,
        "init_blocks": 1, "window_size": 2048, "topk": 64, "dense_len": 8192}
    assert c["held"] == {"first_layer": 0, "published_layers": 32}
    assert set(c["assumed"]) >= {
        "scalings", "norms", "lightning_layer", "decay_slopes",
        "sparse_layer", "initialisation", "dtype", "optimizer", "tokens"}
    assert c["deployment"] and c["what_the_cut_distorts"]
    assert c["optimizer"] == {"name": "adamw_bf16", "learning_rate": 1e-04}
    assert 0 < c["lightning_check"]["rel_err"] < 1e-3
    assert t["seq"] > c["sparse_config"]["dense_len"]


def test_parameter_counts(builder):
    cell = cells.load_cell(CELL, ROOT)
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384 \
        + 2 * 4096 + 2 * 128
    lightning = 5 * 4096 * 4096 + 3 * 4096 * 16384 + 2 * 4096 + 2 * 128 \
        + 4096
    assert (sparse, lightning) == (253_763_840, 285_225_216)
    # The numbers the configuration's ``deployment`` states, leaf by leaf.
    count = builder.num_params(cell.config)
    assert count == 1_260_438_528 \
        == sparse + 3 * lightning + 2 * 18432 * 4096 + 4096
    said = [int(n.replace(",", "")) for n in re.findall(
        r"\d{1,3}(?:,\d{3})+", cell.config["deployment"])]
    for n in (count, sparse, lightning, 9_477_206_016, 75_497_472,
              16_777_216, 1_048_576, 201_326_592):
        assert n in said, n
    assert builder.block_params(cell.config) == sparse + 3 * lightning
    whole = {**cell.config, "vocab_size": 73448, "num_hidden_layers": 32,
             "mixer_types": list(builder.program.MiniCPMSALAConfig()
                                 .mixer_types)}
    assert builder.num_params(whole) == 9_477_206_016 \
        == 8 * sparse + 24 * lightning + 2 * 73448 * 4096 + 4096
    facts = builder.train_facts(cell.config)
    assert facts["resident_params"] == count
    # Five projections a mixer (the sparse layer's k and v narrow), the MLPs
    # and the head; norms and the mixing itself are no parameters.
    assert facts["n_params"] == 1_184_890_880 \
        == 52_428_800 + 3 * 83_886_080 + 4 * 201_326_592 + 18432 * 4096
    assert builder.runs(cell.config) == [(SPARSE, 1), (LIGHTNING, 3)]
    assert [(k, n) for k, n in builder.runs(whole)][:3] == [
        (SPARSE, 1), (LIGHTNING, 8), (SPARSE, 1)]
    with pytest.raises(cells.BenchError, match="mixer_types"):
        builder.runs({**cell.config, "num_hidden_layers": 5})
    tiny = tiny_config()
    params = builder.make_params(tiny, 7)
    assert builder.num_params(tiny) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    assert sorted(params) == ["lm_head", "norm_f", "run0", "run1", "run2",
                              "run3", "tok_emb", "vec0", "vec1", "vec2",
                              "vec3"]
    # The program's own layout, leaf for leaf.
    cfg = builder.program_config(tiny)
    ours = jax.eval_shape(lambda: params)
    theirs = jax.eval_shape(lambda: builder.program.stacked_init_params(
        cfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(ours) \
        == jax.tree_util.tree_structure(theirs)
    assert jax.tree_util.tree_leaves(ours) == jax.tree_util.tree_leaves(theirs)
    again = builder.make_params(tiny, 7)
    other = builder.make_params(tiny, 2_500_000_008)
    assert jnp.array_equal(params["tok_emb"], again["tok_emb"])
    assert not jnp.array_equal(params["tok_emb"], other["tok_emb"])
    assert not jnp.array_equal(params["tok_emb"], params["lm_head"])
    assert not jnp.array_equal(params["run1"]["wq"][0],
                               params["run1"]["wq"][1])
    assert params["vec1"]["o_norm"].dtype == jnp.float32
    assert int(builder.make_tokens(tiny, 3, 2, 4, 16).max()) < 512
    cfg = builder.program_config(cell.config)
    assert [(k, n) for k, _, n in cfg.runs] == [(SPARSE, 1), (LIGHTNING, 3)]
    assert (cfg.head_dim, cfg.lightning_nh, cfg.loss_chunk, cfg.remat,
            cfg.published_layers, cfg.first_layer) == (128, 32, 512, True,
                                                       32, 0)
    assert tuple(cfg.sparse) == (64, 32, 16, 1, 2048, 64, 8192)
    hp = builder.reference_hyper(cell.config)
    assert (hp.n_head, hp.n_kv_head, hp.lightning_heads, hp.scale_emb,
            hp.scale_depth, hp.dim_model_base, hp.published_layers,
            hp.topk, hp.dense_len, hp.eps) == (
                32, 2, 32, 12.0, 1.4, 256, 32, 64, 8192, 1e-6)


def test_reference_step_agrees_with_the_program(builder, capsys):
    """A batch that repeats sequences, from the distinct ones and their
    shares; float32 against float32: rounding only. The probe leaves' own
    gradients are not zero: the check batch reaches them, in the sparse
    walk and in the lightning walk."""
    config = tiny_config()
    params = builder.make_params(config, 2_500_000_001)
    unique = builder.make_tokens(config, 5, 2, 3, 128)
    index = np.array([0, 1, 1, 2, 2, 2])
    shares = np.bincount(index) / len(index)
    with jax.default_matmul_precision("highest"):
        loss, grads = builder.reference_step_fn(config, 1)(params, unique,
                                                           shares)
        p_loss, p_grads = jax.jit(jax.value_and_grad(
            builder.program_loss_fn(config)))(
            builder.to_program(params, config), unique[index])
    said = capsys.readouterr().out
    assert "lightning check" in said and "sets check: 0.000000" in said
    assert abs(float(loss) - float(p_loss)) < 1e-5 * float(p_loss)
    assert builder.PROBE == ("tok_emb", "lm_head", "norm_f", "vec0", "vec1")
    assert sorted(grads) == sorted(builder.PROBE)
    want = dict(jax.tree_util.tree_flatten_with_path(
        {k: p_grads[k] for k in builder.PROBE})[0])
    assert len(want) == 3 + 4 + 5
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        g, w = (np.asarray(x, np.float64) for x in (g, want[path]))
        assert np.linalg.norm(w) > 0, path
        assert np.linalg.norm(g - w) < 1e-4 * np.linalg.norm(w), path


def tiny_cell(config, limit=0.0):
    traffic = {"kind": "train", "driver": "train_steps", "batch": 4,
               "seq": 128, "num_micro_batches": 2, "explore": False,
               "trace_steps": 1}
    spec = {"correct": {"unique_sequences": 2, "reference_chunk": 1,
                        "limits": {"step_state_rel_err": limit}}}
    return cells.Cell(name="tiny", chips=1, why="", config=config,
                      traffic=traffic, spec=spec, end_to_end=[],
                      per_layer=[], root=ROOT, bench_dir=BENCH)


def test_the_control_fails_where_the_planned_step_passes(builder):
    """The plan's own step in bf16 (gradient accumulation over 2 micro
    batches, four walks, the kernels interpreted, ``adamw_bf16``) against
    the float32 reference, and the fp8 control in its place: read as
    ``check_control.py`` reads them on the chip."""
    from benchmark.lib.host import HostLog
    cell = tiny_cell(tiny_config("bfloat16"))
    rows = list(cells.driver_for(cell).readings(
        cell, builder, jax.devices()[:1], [1, 2], [1, 2], HostLog()))
    sound = [r["step_state_rel_err"] for r in rows if r["side"] == "program"]
    control = [r["step_state_rel_err"] for r in rows
               if r["side"] == "control"]
    assert len(sound) == len(control) == 2
    assert min(control) > 2 * max(sound), rows
    slots = {k for k in rows[0] if k.startswith("state")}
    assert len(slots) == 2 * 3          # two moments: outside, vec0, vec1


def test_the_builder_refuses_kernels_whose_state_is_not_float32(
        builder, monkeypatch, capsys):
    """``hold_the_kernels``, which the reference's step function calls where
    the cell's check runs: the program's kernels pass, far under the limit;
    the same kernels with the carried state through bf16 are refused."""
    config = tiny_config("bfloat16")
    tokens = builder.make_tokens(config, 5, 2, 2, 512)
    read = builder.hold_the_kernels(config, tokens)
    limit = config["lightning_check"]["rel_err"]
    assert max(read.values()) < limit / 4
    assert "lightning check" in capsys.readouterr().out
    la = builder.lightning_attention
    sound = (la.forward, la.backward)
    monkeypatch.setattr(la, "forward", lambda *a, **k: sound[0](
        *a, **k, state_dtype=jnp.bfloat16))
    monkeypatch.setattr(la, "backward", lambda *a, **k: sound[1](
        *a, **k, state_dtype=jnp.bfloat16))
    with pytest.raises(cells.BenchError, match="float32"):
        builder.hold_the_kernels(config, tokens)


def test_the_sets_check_counts_differing_sets(builder, capsys):
    config = tiny_config("bfloat16")
    params = builder.make_params(config, 3)
    tokens = builder.make_tokens(config, 3, 2, 2, 128)
    share = builder.sets_differing(config, params, tokens)
    assert 0.0 <= share < 0.1 and "sets check" in capsys.readouterr().out
    # At or under dense_len nothing is chosen, so nothing is counted.
    assert builder.sets_differing(config, params, tokens[:, :33]) is None


def test_the_cost_files_at_the_cells_shapes():
    T, H, G, D = 32768, 32, 2, 128
    fwd, bwd = lightning_cost.forward(T, H, D), lightning_cost.backward(
        T, H, D)
    # q, k, v in and o out in bf16; the backward reads those three and d o
    # and writes three gradients. 4 D^2 and 8 D^2 operations a token, head.
    assert fwd["bytes"] == 4 * T * H * D * 2
    assert bwd["bytes"] == 7 * T * H * D * 2
    assert (fwd["ops"], bwd["ops"]) == (4 * T * H * D * D, 8 * T * H * D * D)
    for cost in (fwd, bwd):
        assert lightning_cost.roofline_seconds(cost, PEAKS)["bound"] \
            == "memory"
    assert lightning_cost.roofline_seconds(fwd, PEAKS)["seconds"] \
        == pytest.approx(1311.04e-6, rel=1e-4)
    # Hand-worked: 2 blocks of 4, top 1: query 0 sees 1 key, 1: 2, ...,
    # 3: 4; queries 4-7 have one block, their own: 1..4 again.
    assert topk_attn_cost.keys_visited(8, 4, 1) == 2 * (1 + 2 + 3 + 4)
    # top 2: queries 4-7 see block 0 whole and their own up to themselves.
    assert topk_attn_cost.keys_visited(8, 4, 2) == 10 + (5 + 6 + 7 + 8)
    visited = topk_attn_cost.keys_visited(T, 64, 64)
    assert visited == 124_928_000 and visited / T == 3812.5
    t = np.arange(T)
    assert visited == int(((np.minimum(64, t // 64 + 1) - 1) * 64
                           + t % 64 + 1).sum())
    fwd = topk_attn_cost.forward(T, H, G, D, 64, 64)
    bwd = topk_attn_cost.backward(T, H, G, D, 64, 64)
    assert fwd["ops"] == 4 * D * H * visited
    assert bwd["ops"] == 10 * D * H * visited
    narrow = 2 * T * G * D * 2 + 4 * T * G * 64 + 4 * T * H
    assert fwd["bytes"] == 2 * T * H * D * 2 + narrow
    assert bwd["bytes"] == 4 * T * H * D * 2 + narrow + 2 * T * G * D * 2
    # A chosen block is counted once, not once a query that chose it.
    assert fwd["bytes"] < 1e9 < 2 * visited * G * D * 2
    for cost, ms in ((fwd, 10.39), (bwd, 25.97)):
        least = topk_attn_cost.roofline_seconds(cost, PEAKS)
        assert least["bound"] == "compute"
        assert least["seconds"] == pytest.approx(ms * 1e-3, rel=1e-3)


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


def test_the_new_readers_on_an_excerpt_of_the_cells_trace(capsys):
    """``testdata/minicpm_sala.ops.json``: operations of one traced step of
    the cell on a v5e (PR 40's chip run), the five kernels, the choice's
    operations and a few of their neighbours, with the window they came
    from."""
    from benchmark.layer_metrics import _sala
    cell = cells.load_cell(CELL, ROOT)
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    trace = SavedTrace(os.path.join(BENCH, "testdata",
                                    "minicpm_sala.ops.json"))
    host = {"peaks": PEAKS}
    got = {name: readers[name].read(trace, host, cell)
           for name in NEW_READERS}
    for name, value in got.items():
        assert isinstance(value, float) and 0.0 < value < 100.0, (name, got)
    said = capsys.readouterr().out
    assert "bound by memory" in said and "bound by compute" in said
    lightning = trace.ops(_sala.is_lightning)
    parsed = [_sala.parse_lightning(text) for text, _, _ in lightning]
    assert {p for p in parsed} == {("forward", 32768, 32, 128, 2),
                                   ("backward", 32768, 32, 128, 2)}
    calls = {}
    for text, _, n in lightning:
        kind = next(k for k in ("fwd", "bwd_dq", "bwd_dkv")
                    if f"lightning_{k}" in text.split(" = ")[0])
        calls[kind] = calls.get(kind, 0) + n
    # 3 lightning layers x 2 micro batches: the forward in the walk and in
    # its recomputation (the gauge lin_attn_calls: 6 a micro batch), each
    # backward kernel once.
    assert calls == {"fwd": 12, "bwd_dq": 6, "bwd_dkv": 6}
    kernels = trace.ops(_sala.is_topk_kernel)
    assert {_sala.parse_topk(text) for text, _, _ in kernels} == {
        ("forward", 1, 32768, 32, 2, 128, 64, 2),
        ("backward", 1, 32768, 32, 2, 128, 64, 2)}
    by_kind = {}
    for text, _, n in kernels:
        kind = _sala.parse_topk(text)[0]
        by_kind[kind] = by_kind.get(kind, 0) + n
    assert by_kind == {"forward": 4, "backward": 2}
    seconds = sum(s for _, s, _ in lightning)
    assert got["lin_attn_time_share.train"] == pytest.approx(
        100 * seconds / trace.window_s)
    least = 12 * lightning_cost.roofline_seconds(
        lightning_cost.forward(32768, 32, 128), PEAKS)["seconds"] \
        + 6 * lightning_cost.roofline_seconds(
            lightning_cost.backward(32768, 32, 128), PEAKS)["seconds"]
    assert got["lin_attn_roofline_share.train"] == pytest.approx(
        100 * least / seconds)
    seconds = sum(s for _, s, _ in kernels)
    least = 4 * 10.38995e-3 + 2 * 25.97488e-3
    assert got["topk_attn_roofline_share.train"] == pytest.approx(
        100 * least / seconds, rel=1e-4)
    # The time share also holds the choice, which is no kernel.
    choice = trace.ops(_sala.choice_matcher(cell))
    assert choice and not any(_sala.is_topk_kernel(t) for t, _, _ in choice)
    assert got["topk_attn_time_share.train"] == pytest.approx(
        100 * (seconds + sum(s for _, s, _ in choice)) / trace.window_s)


def test_the_new_readers_return_nothing_where_no_such_kernel_runs():
    """The parent's trace, or any other cell's: no such kernel; nothing is
    returned and nothing raises."""
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    dense = ("%fusion.9 = bf16[8192,2048]{1,0:T(8,128)(2,1)} fusion("
             "bf16[8192,2048]{1,0:T(8,128)(2,1)} %h)")
    flash = ("%tepdist_flash_fwd__c1__s0.088__h16.1 = (bf16[32,4096,128]{2,1,"
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
    for name in (CELL, "gpt2-1.5b.train.b48", "olmoe-1b-7b.train.s4096",
                 "trinity-mini.train.s8192", "jamba2-3b.train.s8192",
                 "mellum2-12b-a2.5b.train.s16384"):
        cell = cells.load_cell(name, ROOT)
        for reader in NEW_READERS:
            assert readers[reader].read(
                Trace((dense, 0.5, 9), (flash, 0.1, 3)), host, cell) is None
    # Calls the readers cannot size: no roofline share, and no exception.
    odd = ("%tepdist_lightning_fwd.3 = bf16[1,64,128]{2,1,0} custom-call(%a)"
           ", custom_call_target=\"tpu_custom_call\"",
           "%tepdist_topk_attn_fwd.3 = bf16[1,64,128]{2,1,0} custom-call(%a)"
           ", custom_call_target=\"tpu_custom_call\"")
    cell = cells.load_cell(CELL, ROOT)
    trace = Trace(*((text, 0.1, 2) for text in odd))
    assert readers["lin_attn_roofline_share.train"].read(
        trace, host, cell) is None
    assert readers["topk_attn_roofline_share.train"].read(
        trace, host, cell) is None
    assert readers["lin_attn_time_share.train"].read(
        trace, host, cell) == pytest.approx(5.0)
