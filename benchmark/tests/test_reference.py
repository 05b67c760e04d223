"""The plain reference against ``models/gpt2.py`` at the tiny test size on
the CPU, the step check through a planned step, and the control: the
reference one precision step lower, put in the program's place, has to come
out as not correct."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import cells
from benchmark.reference import gpt2 as ref

import os
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {"builder": "gpt2", "dtype": "float32",
        "model": {"n_vocab": 512, "n_ctx": 64, "n_embd": 64, "n_head": 4,
                  "n_layer": 2},
        "optimizer": {"name": "adamw", "learning_rate": 1e-4}}


@pytest.fixture(scope="module")
def builder():
    return cells.load_module(os.path.join(ROOT, "benchmark", "builders",
                                          "gpt2.py"), "bench_builder_gpt2")


@pytest.mark.parametrize("stacked", [False, True])
def test_reference_agrees_with_the_program(builder, stacked):
    from tepdist_tpu.models import gpt2
    config = dict(TINY, program={"stacked": stacked, "attn": "einsum",
                                 "remat": stacked, "loss_chunk": 0})
    params = builder.make_params(config, 2_500_000_001)
    unique = builder.make_tokens(config, 5, 2, 4, 32)
    prog = builder.to_program(params, config)
    cfg = builder.program_config(config)
    fwd = gpt2.forward_stacked if stacked else gpt2.forward
    want = ref.logits(params, unique[:, :-1], 4)
    got = fwd(prog, unique[:, :-1], cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # A batch that repeats sequences, from the distinct ones and their
    # shares, two at a time; float32 against float32: rounding only.
    index = np.array([0, 1, 1, 2, 3, 3, 3, 0])
    shares = np.bincount(index) / len(index)
    loss, grads = builder.reference_step_fn(config, 2)(params, unique,
                                                       shares)
    p_loss, p_grads = jax.value_and_grad(builder.program_loss_fn(config))(
        prog, unique[index])
    assert abs(float(loss) - float(p_loss)) < 1e-5 * float(p_loss)
    for k in builder.PROBE:
        np.testing.assert_allclose(grads[k], p_grads[k], rtol=2e-3,
                                   atol=1e-7)


def test_same_seed_same_weights_other_seed_other_weights(builder):
    config = dict(TINY, program={"stacked": False})
    a = builder.make_params(config, 9)
    b = builder.make_params(config, 9)
    c = builder.make_params(config, 10)
    assert jnp.array_equal(a["wte"], b["wte"])
    assert not jnp.array_equal(a["wte"], c["wte"])
    assert builder.num_params(config) == sum(
        x.size for x in jax.tree_util.tree_leaves(a))


def _tiny_cell(stacked: bool):
    """A bf16 cell at the test size: stacked with 4 micro batches and
    ``adamw_bf16``, or unstacked with one and ``optax.adamw``."""
    bench_dir = os.path.join(ROOT, "benchmark")
    config = dict(
        TINY, dtype="bfloat16",
        program={"stacked": stacked, "attn": "einsum", "remat": stacked,
                 "loss_chunk": 16 if stacked else 0},
        optimizer={"name": "adamw_bf16" if stacked else "adamw",
                   "learning_rate": 1e-4})
    traffic = {"kind": "train", "driver": "train_steps", "batch": 8,
               "seq": 32, "num_micro_batches": 4 if stacked else 1,
               "explore": False, "trace_steps": 1}
    spec = {"correct": {"unique_sequences": 4, "reference_chunk": 2,
                        "limits": {"step_state_rel_err": 0.0}}}
    return cells.Cell(name="tiny", chips=1, why="", config=config,
                      traffic=traffic, spec=spec, end_to_end=[],
                      per_layer=[], root=ROOT, bench_dir=bench_dir)


@pytest.mark.parametrize("stacked", [False, True])
def test_the_control_fails_where_the_planned_step_passes(builder, stacked):
    """The plan's own step (gradient accumulation, the optimizer, the
    lowering) in bf16 against the float32 reference passes a limit that the
    fp8 control, the step below bf16, fails: at this size on the CPU the
    two are several times apart (the cells' own limits are set from chip
    readings at full size, PERF.md section 2)."""
    from benchmark.lib.host import HostLog
    cell = _tiny_cell(stacked)
    driver = cells.driver_for(cell)
    rows = list(driver.readings(cell, builder, jax.devices()[:1],
                                [1, 2, 3], [1, 2, 3], HostLog()))
    sound = [r["step_state_rel_err"] for r in rows if r["side"] == "program"]
    control = [r["step_state_rel_err"] for r in rows
               if r["side"] == "control"]
    assert len(sound) == len(control) == 3
    assert min(control) > 3 * max(sound), rows
    limit = (max(sound) * min(control)) ** 0.5
    cell.spec["correct"]["limits"]["step_state_rel_err"] = limit
    # The same comparison as a run makes it, on a step that went wrong:
    # one sequence of the batch replaced by its neighbour.
    batch = driver.check_batch(cell, builder, 4)[2]
    verdicts = {}
    for name, tokens in (("sound", batch),
                         ("wrong", batch.at[0].set(batch[1]))):
        params = builder.to_program(builder.make_params(cell.config, 4),
                                    cell.config)     # the plan takes them
        paths = driver.probe_paths(cell, builder, params)
        plan = driver._plan(cell, builder, jax.devices()[:1], params, batch)
        step_loss = plan.step(tokens)
        got = driver.step_state(plan, paths)
        driver.release(plan)
        verdicts[name] = driver.check_step(cell, builder, 4, step_loss, got,
                                           HostLog())["ok"]
    assert verdicts == {"sound": True, "wrong": False}


def test_a_cell_without_a_limit_is_refused(builder):
    cell = _tiny_cell(False)
    cell.spec["correct"]["limits"] = {}
    with pytest.raises(cells.BenchError):
        cells.driver_for(cell).check_step(cell, builder, 1, 1.0, {}, None)
