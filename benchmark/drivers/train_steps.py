"""Driver loop for ``kind: train`` traffic: whole optimizer steps through
``plan_training``, a new seeded batch each step.

Set-up (all of it before the window opens, all of it in ``setup_s``): weights
from the seed, ``plan_training`` (planner and, on an explored plan, the
winner's compile), the first two steps (compile or cache read, then the
steady signature). The window then counts whole steps: tokens of the steps
completed, over the time from the window's opening to the end of the last
step, which ``plan.step`` marks by returning the loss as a host float.

``correct`` holds the plan's own step to the reference. The first step runs
on the check batch, and what it leaves in the optimizer's state for the probe
leaves is copied to the host. After the window, with the plan's state freed,
the float32 reference computes the same batch's gradients from the seed's
weights and pushes them through the cell's own optimizer from a fresh state;
the two states are compared. So gradient accumulation, the kernels, remat,
the loss, the optimizer, the lowering and, on several chips, the sharding and
the collectives are all on the path, and none of the reference's time is in
``setup_s``.

Trace modes. 0: no profiler. 1: the traffic's ``trace_steps`` steps run
traced in the measured window's place. 2: mode 0 to the end of the measured
window, whose numbers are taken; then, before the plan's state is released,
``trace_steps`` more steps of the same traffic run traced (seeds continuing
from the window's) and count in neither ``attempted`` nor the rate. In every
mode the program's span recorder, on since process start, is switched off as
the window opens (``lib/recorder.py``); a traced window switches it on
through the program's control (``lib/tracing.py``).
"""

from __future__ import annotations

import math
import time

import jax
import numpy as np

from benchmark.lib import device, recorder
from benchmark.lib.cells import BenchError

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def check_batch(cell, builder, seed: int):
    """The check step's batch: ``unique_sequences`` seeded sequences, each
    at least once, repeated in a seeded order to the cell's batch size. The
    reference needs the distinct ones and their shares only, while every
    micro batch and every chip of the plan gets a different mix, so a step
    that drops, repeats or mis-weights a part of the batch comes out wrong.
    Returns (distinct tokens [U, T+1], shares [U], batch [B, T+1])."""
    n = int(cell.spec["correct"]["unique_sequences"])
    batch, seq = int(cell.traffic["batch"]), int(cell.traffic["seq"])
    unique = builder.make_tokens(cell.config, seed, 2, n, seq)
    s = int(seed)
    rng = np.random.default_rng([s % 2 ** 31, (s // 2 ** 31) % 2 ** 31, 3])
    index = rng.permutation(np.concatenate(
        [np.arange(n), rng.integers(0, n, batch - n)]))
    shares = np.bincount(index, minlength=n).astype(np.float32) / batch
    return unique, shares, unique[index]


def _array_leaves(tree, only=None) -> dict:
    """``{path: float32 host copy}`` of the tree's non-scalar leaves."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = jax.tree_util.keystr(path)
        if np.ndim(leaf) > 0 and (only is None or key in only):
            out[key] = np.asarray(leaf).astype(np.float32)
    return out


def probe_paths(cell, builder, params_program) -> set:
    """Where the optimizer keeps its state for the probe leaves: a state
    built for those leaves alone has the same paths as the whole one."""
    shapes = jax.eval_shape(builder.program_optimizer(cell.config).init,
                            {k: params_program[k] for k in builder.PROBE})
    return {jax.tree_util.keystr(path) for path, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0] if leaf.ndim}


def step_state(plan, paths) -> dict:
    """Host copies of the plan's optimizer state at ``paths`` (the next
    step donates the buffers)."""
    _, opt_state = jax.tree_util.tree_unflatten(plan._state_tree,
                                                plan._device_state())
    return _array_leaves(opt_state, paths)


def release(plan) -> None:
    """Free the plan's state now, whoever else still refers to it."""
    for leaf in plan._device_state():
        leaf.delete()


def reference_state(cell, builder, seed: int, step_fn) -> tuple:
    """(loss, optimizer state of the probe leaves) after one step on the
    check batch by the reference: its gradients (``step_fn`` from the
    builder's ``reference_step_fn``) through the cell's own optimizer."""
    params = builder.make_params(cell.config, seed)
    unique, shares, _ = check_batch(cell, builder, seed)
    loss, grads = step_fn(params, unique, shares)
    probe = {k: params[k] for k in builder.PROBE}
    del params
    optimizer = builder.program_optimizer(cell.config)
    _, state = optimizer.update(grads, optimizer.init(probe), probe)
    return float(loss), _array_leaves(state)


def state_errors(got: dict, want: dict) -> dict:
    """Relative L2 error of each slot of the optimizer state (one moment:
    the leaves whose paths differ in the last key only, pooled) and the
    largest of them, ``step_state_rel_err``."""
    sums = {}
    for path, w in want.items():
        slot = path.rsplit("[", 1)[0]
        num, den = sums.get(slot, (0.0, 0.0))
        sums[slot] = (
            num + float(np.sum(np.square(got[path] - w), dtype=np.float64)),
            den + float(np.sum(np.square(w), dtype=np.float64)))
    slots = {slot: math.sqrt(num / den) for slot, (num, den) in sums.items()}
    return {"step_state_rel_err": max(slots.values()),
            **{"state" + slot: v for slot, v in slots.items()}}


def _step_fn(cell, builder, cast=None):
    return builder.reference_step_fn(
        cell.config, int(cell.spec["correct"]["reference_chunk"]), cast)


def check_step(cell, builder, seed: int, step_loss: float, got: dict,
               host) -> dict:
    """The check step's loss and optimizer state against the reference's.
    Runs after the window, when the plan's state is freed."""
    limits = cell.spec["correct"]["limits"]
    if not limits:
        raise BenchError("the cell's file sets no limit to hold its step to")
    with host.span("check"):
        want_loss, want = reference_state(cell, builder, seed,
                                          _step_fn(cell, builder))
        errors = state_errors(got, want)
        errors["loss_rel_err"] = abs(step_loss - want_loss) / abs(want_loss)
    return {"compared": {k: {"value": errors[k], "limit": limits[k]}
                         for k in limits},
            "also_read": {k: v for k, v in errors.items()
                          if k not in limits},
            "ok": all(errors[k] <= limits[k] for k in limits)}


def _plan(cell, builder, devices, params, example):
    from tepdist_tpu.train import plan_training
    t = cell.traffic
    return plan_training(
        builder.program_loss_fn(cell.config),
        builder.program_optimizer(cell.config), params, example,
        devices=devices, explore=bool(t.get("explore")),
        num_micro_batches=t.get("num_micro_batches"))


def run(cell, builder, devices, seed: int, seconds: float, trace: int,
        host, compiles) -> dict:
    from tepdist_tpu import telemetry
    t = cell.traffic
    batch, seq = int(t["batch"]), int(t["seq"])

    with host.span("weights"):
        params = builder.to_program(
            builder.make_params(cell.config, seed), cell.config)
        first = check_batch(cell, builder, seed)[2]
        paths = probe_paths(cell, builder, params)
    with host.span("plan"):
        plan = _plan(cell, builder, devices, params, first)
    del params
    with host.span("first_step"):
        losses = [plan.step(first)]
    with host.span("check_copy"):
        got = step_state(plan, paths)
    with host.span("settle"):
        losses.append(plan.step(
            builder.make_tokens(cell.config, seed, 101, batch, seq)))

    # The step as compiled (a cache read by now): its memory by the
    # compiler's account, and on several chips what crosses them.
    with host.span("inspect"):
        pp = plan.parallel_plan
        compiled = pp.executable(
            devices=devices, donate_invars=pp.state_donation()).lower(
            *[jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
              for v in pp.graph.invars]).compile()
        program_peak = device.program_peak_bytes(compiled)
    structure_ok, structure = True, {}
    if len(devices) > 1:
        text = compiled.as_text()
        found = [c for c in COLLECTIVES if c in text]
        holders = {s.device.id for leaf in plan._device_state()
                   for s in leaf.addressable_shards}
        structure = {"collectives": found,
                     "devices_holding_shards": sorted(holders)}
        structure_ok = bool(found) and holders == {d.id for d in devices}
        print("structure: " + str(structure), flush=True)
    del compiled

    def one_step(i: int) -> float:
        tokens = builder.make_tokens(cell.config, seed, 1000 + i, batch, seq)
        with host.span("step"):
            return plan.step(tokens)

    def traced_steps(first: int) -> tuple:
        from benchmark.lib import tracing
        with tracing.traced_window(cell.root, cell.name, host) as path:
            t0 = time.perf_counter()
            losses = [one_step(first + i)
                      for i in range(int(t["trace_steps"]))]
            t1 = time.perf_counter()
        cell.facts["trace_path"] = path
        return losses, t0, t1

    # What the program compiled during set-up, by its own counter; then its
    # recorder goes off: the window runs with spans and profiler off.
    program_compiles = recorder.off_for_window()
    mark = compiles.n
    if trace == 1:
        window_losses, t_open, t_last = traced_steps(0)
    else:
        window_losses = []
        t_open = time.perf_counter()
        host.counters["setup_s"] = t_open - host.t0
        i = 0
        while time.perf_counter() - t_open < seconds:
            window_losses.append(one_step(i))
            i += 1
        t_last = time.perf_counter()
    host.counters.setdefault("setup_s", t_open - host.t0)
    compiled_inside = compiles.since(mark)

    steps = len(window_losses)
    bad = sum(1 for x in window_losses if not math.isfinite(x))
    elapsed = t_last - t_open
    rate = steps * batch * seq / elapsed / len(devices)
    cell.facts.update(builder.train_facts(cell.config))
    print(f"window: {steps} steps in {elapsed:.4f} s, losses "
          f"{window_losses[0]:.4f} .. {window_losses[-1]:.4f}, "
          f"compiles inside the window: {compiled_inside} (limit 0)",
          flush=True)

    if trace == 2:
        traced_losses, t0, t1 = traced_steps(steps)
        traced_rate = len(traced_losses) * batch * seq / (t1 - t0) \
            / len(devices)
        print(f"traced after the window: {len(traced_losses)} steps in "
              f"{t1 - t0:.4f} s, {traced_rate:.1f} tokens/s/chip, "
              f"{100 * (1 - traced_rate / rate):.4f}% under the window's "
              f"rate (what tracing costs when on), losses "
              f"{traced_losses[0]:.4f} .. {traced_losses[-1]:.4f}",
              flush=True)
    program_spans = telemetry.tracer().snapshot()

    release(plan)
    del plan
    check = check_step(cell, builder, seed, losses[0], got, host)
    print("check: " + str(check), flush=True)
    return {
        "correct": bool(check["ok"] and bad == 0 and structure_ok
                        and compiled_inside == 0
                        and all(math.isfinite(x) for x in losses)),
        "attempted": steps, "failed": bad,
        "end_to_end": {"train_tokens_per_s_chip": rate},
        "program_peak_bytes": program_peak,
        "host": {"steps": steps, "elapsed_s": elapsed,
                 "compiles_in_window": compiled_inside,
                 "program_spans": program_spans,
                 "program_compiles": program_compiles},
    }


def readings(cell, builder, devices, seeds, control_seeds, host):
    """For ``check_control.py``: per seed, the error of the plan's check
    step against the reference, and on ``control_seeds`` that of the
    control: the reference computed one precision step lower and put in
    the program's place. One plan for all seeds; its state is loaded anew
    from each seed and freed while the reference runs."""
    from benchmark.reference import gpt2 as ref
    want_fn = _step_fn(cell, builder)
    control_fn = _step_fn(cell, builder, ref.fp8_cast)
    optimizer = builder.program_optimizer(cell.config)
    plan = None
    for seed in seeds:
        params = builder.to_program(
            builder.make_params(cell.config, seed), cell.config)
        first = check_batch(cell, builder, seed)[2]
        if plan is None:
            paths = probe_paths(cell, builder, params)
            plan = _plan(cell, builder, devices, params, first)
        else:
            plan._load((params, optimizer.init(params)))
        del params
        step_loss = plan.step(first)
        got = step_state(plan, paths)
        release(plan)
        want_loss, want = reference_state(cell, builder, seed, want_fn)
        yield {"seed": seed, "side": "program", **state_errors(got, want),
               "loss_rel_err": abs(step_loss - want_loss) / abs(want_loss)}
        if seed in control_seeds:
            loss, state = reference_state(cell, builder, seed, control_fn)
            yield {"seed": seed, "side": "control",
                   **state_errors(state, want),
                   "loss_rel_err": abs(loss - want_loss) / abs(want_loss)}
