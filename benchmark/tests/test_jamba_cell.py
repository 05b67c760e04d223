"""The Jamba cell's own files: the cell loads with its readers and the
published widths, the builder draws what the reference and the program both
read and counts the parameters ISSUE 38 counted, the check batch reaches the
probe leaves, the planned step passes where the fp8 control fails,
``ssm_cost.py`` at the cell's shapes, and the two new readers, beside the
accepted ones the cell lists, on an excerpt of a trace of the cell from the
chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.kernels import ssm_cost
from benchmark.lib import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "jamba2-3b.train.s8192"
NEW_READERS = ("ssm_time_share.train", "ssm_roofline_share.train")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def builder():
    return cells.load_module(os.path.join(BENCH, "builders", "jamba.py"),
                             "bench_builder_jamba")


def tiny_config(dtype="float32"):
    with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
        config = json.load(f)
    config.update(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=1,
        attn_layer_period=4, attn_layer_offset=2, mamba_d_state=8,
        mamba_dt_rank=8, dtype=dtype,
        program={"stacked": True, "remat": True, "loss_chunk": 16,
                 "ssm_chunk": 8, "ssm_block_d": 128})
    return config


def test_the_cell_loads_with_its_readers_and_published_widths():
    cell = cells.load_cell(CELL, ROOT)
    names = {m["name"] for m in cell.per_layer}
    assert {*NEW_READERS, "device_idle_share.train", "attn_time_share.train",
            "attn_mixed_roofline_share.train", "step_device_ms.train", "step_host_ms.train", "plan_s",
            "first_step_s", "setup_compile_s", "plan_trace_s",
            "plan_search_s", "plan_place_s",
            "idle_attributed_share.train"} == names
    found = {m.NAME for m in cells.layer_metric_modules(cell.bench_dir)}
    assert set(NEW_READERS) <= found
    t, c = cell.traffic, cell.config
    assert (t["batch"], t["seq"], t["num_micro_batches"], t["explore"],
            t["trace_steps"]) == (4, 8192, 4, False, 1)
    assert cell.spec["end_to_end"] == ["train_tokens_per_s_chip", "setup_s"]
    assert cell.spec["correct"]["unique_sequences"] == 2
    assert list(cell.spec["correct"]["limits"]) == ["step_state_rel_err"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # Found by name, not by place: a later PR appends to these lists.
    entry = next(e for e in bench["configs"] if e["name"] == "jamba2-3b")
    assert entry["file"] == "benchmark/configs/jamba2-3b.json"
    assert entry["source"] == c["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/"
        "config.json")
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers",
                                                "vocab_size"]
    assert set(c["reduced_from"]) == set(c["reduced"])
    new = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == list(NEW_READERS)
    for m in new:
        assert CELL in m["workloads"] and m["layer"] == "kernels"
        assert m["moves"] == "train_tokens_per_s_chip" and m["unit"] == "%"
    listed = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        "jamba2-3b", "train-b4-s8192-ga4", 1)
    assert len(listed["why"]) <= 200 and len(entry["why"]) <= 200
    # Every key of the published config.json but the two cut ones.
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_key_value_heads": 1,
        "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
        "sliding_window": None, "tie_word_embeddings": True,
        "use_mamba_kernels": True}
    assert {k: c[k] for k in published} == published
    assert (c["num_hidden_layers"], c["vocab_size"]) == (14, 65536 // 4)
    assert c["reduced_from"]["num_hidden_layers"].startswith("28;")
    assert c["reduced_from"]["vocab_size"].startswith("65536;")
    assert set(c["assumed"]) >= {
        "layer_order", "inner_norms", "initialisation", "dtype",
        "weight_decay", "optimizer", "tokens", "positions"}
    assert c["deployment"] and c["what_the_cut_distorts"]
    assert c["optimizer"] == {"name": "adamw_bf16", "learning_rate": 1e-04}


def test_parameter_counts(builder):
    cell = cells.load_cell(CELL, ROOT)
    whole = {**cell.config, "vocab_size": 65536}
    mixer = 2560 * 10240 + (5120 * 4 + 5120) + 5120 * 192 \
        + (160 * 5120 + 5120) + 5120 * 16 + 5120 + 192 + 5120 * 2560
    assert mixer == 41_241_792
    mamba = mixer + 3 * 2560 * 8192 + 2 * 2560
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128 + 3 * 2560 * 8192 + 2 * 2560
    assert (mamba, attention) == (104_161_472, 76_682_240)
    # ISSUE 38's numbers: one period with the whole vocabulary, the blocks'
    # share of it (what a GA step adds inside the layer loop), and the cell.
    assert builder.num_params(whole) == 1_598_556_096 \
        == 13 * mamba + attention + 65536 * 2560 + 2560
    assert builder.block_params(whole) == 1_430_781_376 \
        == builder.block_params(cell.config)
    assert builder.num_params(cell.config) == 1_472_726_976
    assert builder.block_params(whole) / builder.num_params(whole) \
        == pytest.approx(0.895, abs=5e-4)
    assert builder.block_params(cell.config) \
        / builder.num_params(cell.config) == pytest.approx(0.9715, abs=5e-4)
    assert builder.num_params({**whole, "num_hidden_layers": 28}) \
        == 3_029_337_472
    facts = builder.train_facts(cell.config)
    assert facts["resident_params"] == 1_472_726_976
    # Four projections a mixer, the MLPs, the attention layer and the head;
    # conv, scan and norms are no matmuls.
    assert facts["n_params"] == 13 * (41_123_840 + 62_914_560) \
        + (13_762_560 + 62_914_560) + 16384 * 2560 == 1_471_119_360
    assert builder.runs(cell.config) == [(False, 7), (True, 1), (False, 6)]
    assert builder.runs({**cell.config, "num_hidden_layers": 28}) == [
        (False, 7), (True, 1), (False, 13), (True, 1), (False, 6)]
    tiny = tiny_config()
    params = builder.make_params(tiny, 7)
    assert builder.num_params(tiny) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    assert sorted(params) == ["decay0", "decay2", "norm_f", "run0", "run1",
                              "run2", "tok_emb", "vec0", "vec1", "vec2"]
    # The program's own layout, leaf for leaf.
    cfg = builder.program_config(tiny)
    ours = jax.eval_shape(lambda: params)
    theirs = jax.eval_shape(
        lambda: builder.program.stacked_init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(ours) \
        == jax.tree_util.tree_structure(theirs)
    assert jax.tree_util.tree_leaves(ours) == jax.tree_util.tree_leaves(theirs)
    again = builder.make_params(tiny, 7)
    other = builder.make_params(tiny, 2_500_000_008)
    assert jnp.array_equal(params["tok_emb"], again["tok_emb"])
    assert not jnp.array_equal(params["tok_emb"], other["tok_emb"])
    assert not jnp.array_equal(params["run0"]["in_proj"],
                               params["run2"]["in_proj"])
    dt = np.asarray(jax.nn.softplus(params["vec0"]["dt_bias"]))
    assert 0.999e-3 <= dt.min() and dt.max() <= 0.1001
    assert params["decay0"]["A_log"].dtype == params["vec0"]["D"].dtype \
        == jnp.float32
    assert int(builder.make_tokens(tiny, 3, 2, 4, 16).max()) < 512
    cfg = builder.program_config(cell.config)
    assert [(k, n) for k, _, n in cfg.runs] == [
        ("mamba", 7), ("attention", 1), ("mamba", 6)]
    assert (cfg.d_inner, cfg.head_dim, cfg.ssm_chunk, cfg.ssm_block_d,
            cfg.loss_chunk, cfg.remat) == (5120, 128, 64, 1024, 512, True)
    hp = builder.reference_hyper(cell.config)
    assert (hp.n_head, hp.n_kv_head, hp.attn_layer_period,
            hp.attn_layer_offset, hp.d_state, hp.dt_rank, hp.eps) == (
                20, 1, 14, 7, 16, 160, 1e-6)


def test_reference_step_agrees_with_the_program(builder):
    """A batch that repeats sequences, from the distinct ones and their
    shares; float32 against float32: rounding only. The probe leaves' own
    gradients are not zero: the check batch reaches them."""
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
    # Outside the layers, and the small groups inside each of the three
    # walks (a Mamba run's per-channel leaves and its A_log, the attention
    # layer's norm gains).
    assert builder.PROBE == ("tok_emb", "norm_f", "vec0", "decay0", "vec1",
                             "vec2", "decay2")
    assert sorted(grads) == sorted(builder.PROBE)
    want = dict(jax.tree_util.tree_flatten_with_path(
        {k: p_grads[k] for k in builder.PROBE})[0])
    assert len(want) == 2 + 9 + 1 + 2 + 9 + 1
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        g, w = (np.asarray(x, np.float64) for x in (g, want[path]))
        assert np.linalg.norm(w) > 0, path
        assert np.linalg.norm(g - w) < 1e-4 * np.linalg.norm(w), path


def tiny_cell(config, limit=0.0):
    traffic = {"kind": "train", "driver": "train_steps", "batch": 8,
               "seq": 16, "num_micro_batches": 4, "explore": False,
               "trace_steps": 1}
    spec = {"correct": {"unique_sequences": 4, "reference_chunk": 2,
                        "limits": {"step_state_rel_err": limit}}}
    return cells.Cell(name="tiny", chips=1, why="", config=config,
                      traffic=traffic, spec=spec, end_to_end=[],
                      per_layer=[], root=ROOT, bench_dir=BENCH)


def test_the_control_fails_where_the_planned_step_passes(builder):
    """The plan's own step in bf16 (gradient accumulation over 4 micro
    batches, three walks, the kernels interpreted, ``adamw_bf16``) against
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


@pytest.mark.parametrize("where", ["everywhere", "vec1", "decay2"])
@pytest.mark.parametrize("fault", ["dropped", "doubled"])
def test_a_dropped_or_doubled_micro_batch_shows(builder, fault, where):
    """The check's own comparison (``state_errors`` of the optimizer state
    the reference's gradients leave) on a batch whose last micro batch is
    left out or counted twice: the error is far over what rounding gives.
    Also where the fault is in one walk's accumulator alone (the attention
    layer's, or the second Mamba run's): that walk's own slot shows it."""
    driver = cells.driver_for(tiny_cell(tiny_config()))
    config = tiny_config()
    cell = tiny_cell(config)
    seed = 11
    params = builder.make_params(config, seed)
    unique, shares, batch = driver.check_batch(cell, builder, seed)
    optimizer = builder.program_optimizer(config)
    probe = {k: params[k] for k in builder.PROBE}

    def grads_of(tokens):
        with jax.default_matmul_precision("highest"):
            grads = jax.grad(builder.program_loss_fn(config))(
                builder.to_program(params, config), tokens)
        return {k: grads[k] for k in builder.PROBE}

    def errors(grads):
        _, state = optimizer.update(grads, optimizer.init(probe), probe)
        return driver.state_errors(driver._array_leaves(state), want)

    with jax.default_matmul_precision("highest"):
        _, want = driver.reference_state(
            cell, builder, seed, driver._step_fn(cell, builder))
    right = grads_of(batch)
    wrong = grads_of(batch[:6] if fault == "dropped"
                     else jnp.concatenate([batch, batch[6:]]))
    if where != "everywhere":
        wrong = {**right, where: wrong[where]}
    sound, faulty = errors(right), errors(wrong)
    slots = {k for k in sound if k.startswith("state")}
    assert len(slots) == 2 * (1 + 5)            # two moments a group
    assert sound["step_state_rel_err"] < 0.01
    assert faulty["step_state_rel_err"] > 10 * sound["step_state_rel_err"]
    assert faulty["step_state_rel_err"] > 0.05
    for slot in slots:
        hit = where == "everywhere" or f"['{where}']" in slot
        assert (faulty[slot] > 0.05) == hit, (slot, faulty[slot])


def test_the_builder_refuses_a_scan_whose_state_is_not_float32(
        builder, monkeypatch, capsys):
    """``hold_the_scan``, which the reference's step function calls where
    the cell's check runs: the program's kernel passes, far under the limit;
    the sequential scan with its state rounded to bf16 after every step, in
    the kernel's place, is refused."""
    config = tiny_config("bfloat16")
    tokens = builder.make_tokens(config, 5, 2, 2, 64)
    read = builder.hold_the_scan(config, tokens)
    limit = config["scan_check"]["dA_rel_err"]
    assert read["dA"] < limit / 10
    assert "scan check" in capsys.readouterr().out

    def low(c, delta, A, B, C, D, z, **_):
        f32 = jnp.float32

        def step(h, x):
            c_t, d_t, B_t, C_t = x
            h = jnp.exp(d_t[None] * A.T) * h + B_t[:, None] * (d_t * c_t)[None]
            h = h.astype(jnp.bfloat16).astype(f32)
            return h, jnp.sum(h * C_t[:, None], axis=0)

        c32 = c[0].astype(f32)
        y = jax.lax.scan(step, jnp.zeros(A.T.shape, f32), (
            c32, delta[0], B[0].astype(f32), C[0].astype(f32)))[1]
        return ((y + D * c32) * jax.nn.silu(z[0].astype(f32)))[None] \
            .astype(c.dtype)

    monkeypatch.setattr(builder.program, "selective_scan", low)
    with pytest.raises(cells.BenchError, match="float32"):
        builder.hold_the_scan(config, tokens)
    assert "scan check" in capsys.readouterr().out


def test_ssm_cost_at_the_cells_shapes():
    T, Di, N = 8192, 5120, 16
    fwd, bwd = ssm_cost.forward(T, Di, N), ssm_cost.backward(T, Di, N)
    narrow = 2 * T * N * 2 + 4 * (Di * N + Di)
    # c, z in and out out in bf16, delta in float32: 10 bytes a channel and
    # token; the backward reads those four and d out, writes three gradients.
    assert fwd["bytes"] == T * Di * 10 + narrow
    assert bwd["bytes"] == T * Di * 18 + 2 * narrow
    assert fwd["ops"] == 7 * T * Di * N and bwd["ops"] == 17 * T * Di * N
    for cost in (fwd, bwd):
        least = ssm_cost.roofline_seconds(cost, PEAKS)
        assert least["bound"] == "memory"
    assert ssm_cost.roofline_seconds(fwd, PEAKS)["seconds"] \
        == pytest.approx(513.19e-6, rel=1e-4)
    # Not the implementation's: neither boundary states nor a wider B, C.
    assert fwd["bytes"] < T * Di * 10 + 2 * T * N * 128 * 2


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
    """``testdata/jamba.ops.json``: operations of one traced step of the
    cell on a v5e (PR 38's chip run), the kernels and a few of their
    neighbours, with the window they came from."""
    from benchmark.layer_metrics import _ssm
    cell = cells.load_cell(CELL, ROOT)
    readers = {m.NAME: m for m in cells.layer_metric_modules(BENCH)}
    trace = SavedTrace(os.path.join(BENCH, "testdata", "jamba.ops.json"))
    host = {"peaks": PEAKS}
    got = {name: readers[name].read(trace, host, cell)
           for name in (*NEW_READERS, "attn_time_share.train",
                        "attn_mixed_roofline_share.train")}
    for name, value in got.items():
        assert isinstance(value, float) and 0.0 < value < 100.0, (name, got)
    assert "bound by memory" in capsys.readouterr().out
    scans = trace.ops(_ssm.is_ssm)
    parsed = [_ssm.parse(text) for text, _, _ in scans]
    assert {p[:1] + p[2:] for p in parsed} == {
        ("forward", 8192, 5120, 16, 2, 4), ("backward", 8192, 5120, 16, 2, 4)}
    calls = {}
    for (kind, *_), (_, _, n) in zip(parsed, scans):
        calls[kind] = calls.get(kind, 0) + n
    # 13 Mamba layers x 4 micro batches: the forward in the walk and in its
    # recomputation (the gauge ssm_scan_calls: 26 a micro batch), the
    # backward once.
    assert calls == {"forward": 104, "backward": 52}
    seconds = sum(s for _, s, _ in scans)
    assert got["ssm_time_share.train"] == pytest.approx(
        100 * seconds / trace.window_s)
    least = 104 * ssm_cost.roofline_seconds(
        ssm_cost.forward(8192, 5120, 16), PEAKS)["seconds"] \
        + 52 * ssm_cost.roofline_seconds(
            ssm_cost.backward(8192, 5120, 16), PEAKS)["seconds"]
    assert got["ssm_roofline_share.train"] == pytest.approx(
        100 * least / seconds)
    assert got["attn_time_share.train"] < got["ssm_time_share.train"]


def test_the_new_readers_return_nothing_where_no_scan_runs():
    """The parent's trace, or any other cell's: no scan kernel; nothing is
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
    for name in (CELL, "gpt2-1.5b.train.b48", "olmoe-1b-7b.train.s4096"):
        cell = cells.load_cell(name, ROOT)
        for reader in NEW_READERS:
            assert readers[reader].read(
                Trace((dense, 0.5, 9), (flash, 0.1, 3)), host, cell) is None
    # A scan call the reader cannot size: no share, and no exception.
    odd = ("%tepdist_ssm_fwd.3 = bf16[1,64,128]{2,1,0} custom-call(%a), "
           "custom_call_target=\"tpu_custom_call\"")
    cell = cells.load_cell(CELL, ROOT)
    assert readers["ssm_roofline_share.train"].read(
        Trace((odd, 0.1, 2)), host, cell) is None
    assert readers["ssm_time_share.train"].read(
        Trace((odd, 0.1, 2)), host, cell) == pytest.approx(5.0)
