"""The step's device time by the program's own parts: the vocabulary of
``models/layers.py`` (six ``part_*`` scopes, three ``walk_*`` phases) on
every model of the benchmark, through ``plan_training``'s re-emission of the
graph, and the benchmark's readers of it (``benchmark/layer_metrics/
_scopes.py`` and the eight ``scope_*_share.train``) on two traces recorded on
the v5e: ``spans.xplane.pb`` from before the scopes (every operation
``unscoped``) and ``scopes.xplane.pb`` with them (``benchmark/testdata/
record_scopes.py``; the numbers beside it are what the readers read on the
chip when it was recorded)."""

import dataclasses
import importlib
import json
import os
import re
import shutil
import sys
import types

import jax
import jax.numpy as jnp
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DATA = os.path.join(ROOT, "benchmark", "testdata")

from benchmark.layer_metrics import _scopes  # noqa: E402
from benchmark.testdata import record_scopes  # noqa: E402
from tepdist_tpu.models import layers  # noqa: E402
from tepdist_tpu.parallel.sync_free import build_ga_step  # noqa: E402

PARTITION = record_scopes.READERS[:-1]      # all but the recomputation's


def test_the_benchmark_repeats_the_programs_vocabulary():
    assert _scopes.PARTS == layers.PARTS
    assert _scopes.PHASES == layers.PHASES
    with pytest.raises(ValueError):
        layers.part("attention")


# -- an operation's place, from its name stack --------------------------------

@pytest.mark.parametrize("path, want", [
    ("jit(tepdist_train_step)/while/body/closed_call/jvp()/dot_general:",
     ("unscoped", "-", "-")),
    ("jit(step)/part_optimizer/jit(_where)/select_n:",
     ("optimizer", "-", "-")),
    # JAX wraps a scope in its transforms' names.
    ("a/walk_bwd/transpose(jvp(part_mixer))/mla_in/mla_q/dot_general:",
     ("mixer", "walk_bwd", "mla_q")),
    # A backward rule's operations: the phase they run in comes first.
    ("walk_bwd/transpose(walk_recompute)/jvp(part_mixer)/cos:",
     ("mixer", "walk_bwd", "-")),
    ("walk_recompute/jvp(part_mixer)/mla_in/mla_rope/rope_yarn/mul:",
     ("mixer", "walk_recompute", "rope_yarn")),
    # The innermost part wins: the halves of one chunked function.
    ("walk_fwd/mixer_out_mlp/checkpoint/part_mlp/dot_general:",
     ("mlp", "walk_fwd", "-")),
    ("walk_bwd/transpose(jvp(mla_out_mlp))/walk_recompute/jvp(mla_out_mlp)/"
     "checkpoint/rematted_computation/part_moe/moe_dispatch/gather:",
     ("moe", "rematted", "moe_dispatch")),
    ("walk_bwd/transpose(jvp(mla_out_mlp))/walk_recompute/jvp(mla_out_mlp)/"
     "checkpoint/part_moe/cond/branch_1_fun/transpose(jvp(moe_experts))/"
     "tepdist_gmm_dw/while/body/cond/branch_1_fun/mul:",
     ("moe", "walk_bwd", "tepdist_gmm_dw")),
    ("jvp()/while/body/closed_call/walk_fwd/part_mixer/"
     "tepdist_mla_fwd__c1__s0.1352337788608801__h16/pallas_call:",
     ("mixer", "walk_fwd", "tepdist_mla_fwd")),
    # A residual stream of lanes: the maps under the part they serve, their
    # Sinkhorn loop's words JAX's own; the query
    # latent beside the key/value path; the prediction module's scope stands
    # outside the parts and names no sub-scope.
    ("walk_fwd/mla_out_mlp/checkpoint/part_moe/mhc_maps/while/body/mul:",
     ("moe", "walk_fwd", "mhc_maps")),
    ("walk_bwd/transpose(jvp(mla_out_mlp))/checkpoint/rematted_computation/"
     "part_mixer/mla_out/mhc_write/slice:",
     ("mixer", "rematted", "mhc_write")),
    ("jit(s)/mtp/while/body/closed_call/walk_bwd/transpose(jvp(part_mixer))/"
     "mla_in/checkpoint/mla_q_up/dot_general:",
     ("mixer", "walk_bwd", "mla_q_up")),
    ("jit(s)/mtp/part_embed/mtp_in/checkpoint/concatenate:",
     ("embed", "-", "mtp_in")),
    ("jit(s)/mtp/part_head_loss/while/body/exp:", ("head_loss", "-", "-")),
    # Whole words only: an einsum's string, a scope that begins alike.
    ("jvp(bhqk,bhkd->bhqd)/dot_general:", ("unscoped", "-", "-")),
    ("part_mixer_in/part_mlpx/mlp/moe/walk_fwds/dot:",
     ("unscoped", "-", "-")),
    ("", ("unscoped", "-", "-")),
])
def test_place(path, want):
    assert _scopes.place(path) == want


# -- the scopes in every model's step -----------------------------------------

def _name_stacks(jaxpr, outer=""):
    """The name stack of every equation, the enclosing equations' before
    it, as the lowering joins them."""
    for eqn in jaxpr.eqns:
        path = "/".join(filter(None, (outer, str(eqn.source_info.name_stack))))
        yield f"{path}/{eqn.primitive.name}:"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _name_stacks(sub, path)


def _ga_step(module: str):
    """(a two-micro-batch step of the model's ``test`` configuration, layers
    stacked and rematerialised, its arguments' shapes)."""
    mod = importlib.import_module("tepdist_tpu.models." + module)
    cfg = dataclasses.replace(mod.CONFIGS["test"], remat=True)
    if hasattr(cfg, "loss_chunk"):
        cfg = dataclasses.replace(cfg, loss_chunk=16)
    loss_of = getattr(mod, "loss_fn_stacked", mod.loss_fn)

    def loss(p, t):
        return loss_of(p, t, cfg)

    tx = optax.sgd(1e-3)

    def apply_fn(p, s, g):
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    params = jax.eval_shape(
        lambda: mod.stacked_init_params(cfg, jax.random.PRNGKey(0)))
    step = build_ga_step(lambda p, b: jax.value_and_grad(loss)(p, b),
                         apply_fn, 2, loss_fn=loss)
    return step, (params, jax.eval_shape(tx.init, params),
                  jax.ShapeDtypeStruct((2, 33), jnp.int32))


@pytest.mark.parametrize("module, parts", [
    ("gpt2", ("mixer", "mlp")),
    ("olmoe", ("mixer", "moe")),
    ("afmoe", ("mixer", "mlp", "moe")),
    ("mellum", ("mixer", "moe")),
    ("jamba", ("mixer", "mlp")),
    ("minicpm_sala", ("mixer", "mlp")),
    ("sarvam_mla", ("mixer", "mlp", "moe")),
    ("zaya", ("mixer", "moe")),
    ("xing", ("mixer", "mlp", "moe")),
])
def test_a_models_step_holds_its_parts_and_the_walks_phases(module, parts):
    step, args = _ga_step(module)
    paths = set(_name_stacks(jax.jit(step).trace(*args).jaxpr.jaxpr))
    placed = {_scopes.place(p)[:2] for p in paths}
    inside = {"walk_fwd", "walk_recompute", "walk_bwd"}
    for part in parts:              # a block's parts, in every phase but
        got = {phase for p, phase in placed if p == part}
        # ... the recomputation where ``over_sequence`` makes it (rematted).
        assert got >= inside - {"walk_recompute"} and got & {
            "walk_recompute", "rematted"}, (part, got)
    for part in ("embed", "head_loss", "optimizer"):
        assert (part, "-") in placed, part
    # The accumulation inside a walk is the optimizer's too.
    assert ("optimizer", "walk_bwd") in placed
    assert {p for p, _ in placed} == {*parts, "embed", "head_loss",
                                      "optimizer", "unscoped"}
    # Autodiff wraps the scope; the word is found inside the wrapping.
    wrapped = [p for p in paths if "transpose(jvp(part_mixer" in p]
    assert wrapped and all(_scopes.place(p)[0] == "mixer" for p in wrapped)
    if module == "xing":    # the maps' sub-scopes, in each part they serve
        subs = {(_scopes.place(p)[0], _scopes.place(p)[2]) for p in paths}
        assert subs >= {(part, sub) for part in parts for sub in (
            "mhc_maps", "mhc_read", "mhc_write")} | {
            ("mixer", "mla_q_down"), ("mixer", "mla_q_up"),
            ("embed", "mtp_in")}
        from benchmark.layer_metrics import _mtp
        inside_mtp = {_scopes.place(p)[0] for p in paths
                      if _mtp._MTP.search(p)}
        assert inside_mtp >= {"embed", "mixer", "moe", "head_loss"}


def test_the_lowered_text_carries_the_scopes():
    """What the jaxpr's name stacks say is what the lowering writes out
    (``tests/test_jamba.py`` reads its scopes there)."""
    step, args = _ga_step("gpt2")
    text = jax.jit(step).lower(*args).as_text(debug_info=True)
    placed = {_scopes.place(p)[:2]
              for p in re.findall(r'loc\("([^"]*)"', text)}
    assert placed >= {("embed", "-"), ("mixer", "walk_fwd"),
                      ("mixer", "walk_recompute"), ("mlp", "walk_bwd"),
                      ("head_loss", "-"), ("optimizer", "-")}


def test_a_planned_step_keeps_the_scopes_of_its_top_level():
    """``plan_training`` binds the graph's equations anew
    (``parallel/spmd_transform.py``): the optimizer's update lies at the
    graph's top level, where only the equation's own record says which scope
    it was traced under."""
    from tepdist_tpu.models import gpt2
    from tepdist_tpu.train import plan_training
    cfg = dataclasses.replace(gpt2.CONFIGS["test"], remat=True)
    params = gpt2.stacked_init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, 4, 32)
    plan = plan_training(lambda p, t: gpt2.loss_fn_stacked(p, t, cfg),
                         optax.adam(1e-3), params, tokens,
                         devices=jax.devices()[:1], num_micro_batches=2)
    names = re.findall(r'op_name="([^"]*)"', plan.compiled_step_text())
    placed = [_scopes.place(n)[:2] for n in names]
    # Adam's update is some hundred operations; the accumulators' zeros and
    # the 1/n scale alone are a dozen.
    assert placed.count(("optimizer", "-")) > 100
    assert {("head_loss", "-"), ("embed", "-"), ("mixer", "walk_recompute"),
            ("optimizer", "walk_bwd")} <= set(placed)
    assert all(n.startswith("jit(tepdist_train_step)") for n in names
               if "part_optimizer" in n)


def test_an_inlined_call_keeps_the_scopes_round_it():
    """The planner's graph inlines ``jit`` and ``custom_jvp`` / ``custom_vjp``
    calls (``graph/jaxpr_graph.py:inline_calls``); what a call held is named
    relative to the call, whose own name stack has to go with it."""
    from tepdist_tpu.graph.jaxpr_graph import inline_calls

    @jax.custom_vjp
    def kept(x):
        return jnp.sin(x) * 2.0

    kept.defvjp(lambda x: (kept(x), x), lambda x, g: (g * jnp.cos(x),))

    def f(x):
        with layers.part("mlp"):
            return jax.jit(lambda y: jnp.cos(y) + 1.0)(kept(x))

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4, 8))).jaxpr
    assert len(jaxpr.eqns) == 2         # the two calls
    inlined = inline_calls(jaxpr)
    assert len(inlined.eqns) == 4
    for eqn in inlined.eqns:
        assert _scopes.place(str(eqn.source_info.name_stack) + "/x")[0] \
            == "mlp", eqn


# -- the readers on recorded traces -------------------------------------------

@pytest.fixture(scope="module")
def read(tmp_path_factory):
    """trace file -> what the readers find in it (each in a directory of
    its own: ``find_xplane`` takes the newest trace under the one given)."""
    found = {}

    def of(name):
        if name not in found:
            trace_dir = tmp_path_factory.mktemp(name.split(".")[0])
            shutil.copy(os.path.join(DATA, name), trace_dir)
            found[name] = record_scopes.read_all(str(trace_dir))
        return found[name]
    return of


def test_operation_scopes_reads_the_event_metadata():
    scopes = _scopes.operation_scopes(os.path.join(DATA, "spans.xplane.pb"))
    # 506 entries of the plane's event_metadata: 402 instruction names (the
    # programs that ran in the window number their fusions alike) and five
    # names of steps, of the module and of a host region.
    assert len(scopes) == 407
    assert sum(k.startswith("%") for k in scopes) == 402
    assert sum(map(bool, scopes.values())) == 163
    assert "jit(tepdist_train_step)/while/body/closed_call/jvp()/" \
        "dot_general:" in scopes.values()


@pytest.mark.parametrize("name", record_scopes.READERS)
def test_reader_on_a_trace_from_before_the_scopes(read, name):
    got = read("spans.xplane.pb")["readers"]
    assert got[name] == (100.0 if name == "scope_unscoped_share.train"
                         else 0.0)
    assert sum(got[n] for n in PARTITION) == pytest.approx(100.0)


def test_a_run_that_was_not_traced_reads_nothing():
    cell = types.SimpleNamespace(facts={})
    assert _scopes.part_share(None, cell, "mixer") is None
    assert _scopes.phase_share(None, cell, _scopes.RECOMPUTED) is None


@pytest.fixture(scope="module")
def want():
    with open(os.path.join(DATA, "scopes.expected.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", record_scopes.READERS)
def test_reader_on_the_recorded_trace(read, want, name):
    got = read("scopes.xplane.pb")
    assert got["readers"][name] == pytest.approx(want["readers"][name],
                                                 rel=1e-9)
    if name == "scope_moe_share.train":
        assert got["readers"][name] == 0.0        # GPT-2 has no expert layer
    elif name != "scope_unscoped_share.train":
        assert got["readers"][name] > 0.0


def test_the_join_on_the_recorded_trace(read, want, capsys):
    got = read("scopes.xplane.pb")
    assert (got["operations"], got["with_tf_op"]) == (
        want["operations"], want["with_tf_op"])
    assert sum(got["readers"][n] for n in PARTITION) == pytest.approx(100.0)
    for part, phases in want["by_phase"].items():
        assert got["by_phase"][part] == pytest.approx(phases, rel=1e-9), part
    # The parts of a block lie in the walk's three phases and nowhere else;
    # the step's ends lie outside them.
    for part in ("mixer", "mlp"):
        assert set(got["by_phase"][part]) == {
            "walk_fwd", "walk_recompute", "walk_bwd"}
    for part in ("embed", "head_loss"):
        assert set(got["by_phase"][part]) == {"-"}
    assert set(got["by_phase"]["optimizer"]) == {"-", "walk_bwd"}
    assert got["total_s"] == pytest.approx(
        sum(s for phases in got["by_phase"].values()
            for s in phases.values()))


def test_the_table_a_run_prints(tmp_path, capsys):
    from benchmark import trace_reduce
    shutil.copy(os.path.join(DATA, "scopes.xplane.pb"), tmp_path)
    cell = types.SimpleNamespace(facts={"trace_path": str(tmp_path)})
    trace = trace_reduce.reduce_file(trace_reduce.find_xplane(str(tmp_path)))
    _scopes.traced(trace, cell)
    _scopes.traced(trace, cell)                     # read and printed once
    out = capsys.readouterr().out
    assert out.count("scopes: device self seconds") == 1
    assert re.search(r"^  mixer +0\.0+ +0\.\d+ +0\.\d+ +0\.0+ +0\.\d+",
                     out, re.M)
    assert "longest unscoped operations" in out


# -- the benchmark's entries ------------------------------------------------

def test_the_entries_list_the_cells_that_have_the_part():
    from benchmark.lib import cells
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]
               if m["name"].startswith("scope_")}
    assert set(entries) == set(record_scopes.READERS)
    every = [w["name"] for w in bench["workloads"]]
    for name, m in entries.items():
        assert (m["layer"], m["moves"], m["better"]) == (
            "models", "train_tokens_per_s_chip", "lower")
        if name not in ("scope_moe_share.train", "scope_mlp_share.train"):
            assert m["workloads"] == every
    # An expert layer, a dense MLP: by what the cell's own model builds.
    for w in every:
        cell = cells.load_cell(w, ROOT)
        model = cell.config.get("model", cell.config)
        # (The published key: ``n_routed_experts`` in a ``nemotron_h`` file,
        # ``num_local_experts`` in a ``granitemoehybrid`` one.)
        experts = next((model[k] for k in (
            "num_experts", "n_routed_experts", "num_local_experts")
            if k in model), 0)
        assert (w in entries["scope_moe_share.train"]["workloads"]) == (
            experts > 1), w
