"""The router's selection bias after an AFMoE cell's own first step, on the
chip, against the reference's update.

``run.py``'s step check compares the optimizer's state of the leaves outside
the blocks; the bias is a parameter inside them, so no run holds its update
to anything. This does, at the cell's sizes: the plan's step on the check
batch (every micro batch's counts summed through the accumulation, then
``optim.sign_and_centre``), then the bias it leaves against
``reference/afmoe.py:bias_update`` on (a) the float32 reference's counts of
the same batch and (b) the counts of the program's own forward pass
(``expert_choices``, compiled apart from the step). Routing is discrete, so
an expert whose count lies at the mean can fall on either side of it: the
readings are shares of entries whose sign agrees, and carry no limit.

Run: chiprun -- python tools/afmoe_bias.py [--workload trinity-mini.train.s8192]
     [--seeds 1,2]     (about 2 minutes a seed, 2 more when the step compiles)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="trinity-mini.train.s8192")
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import cells, device
    from benchmark.reference import afmoe as ref
    cell = cells.load_cell(args.workload, ROOT)
    devices = device.own_chips(1)
    device.configure_cache(ROOT)
    builder, driver = cells.builder_for(cell), cells.driver_for(cell)
    cfg, hp = builder.program_config(cell.config), \
        builder.reference_hyper(cell.config)
    rate = float(cell.config["optimizer"]["bias_rate"])
    optimizer = builder.program_optimizer(cell.config)
    choose = jax.jit(lambda p, t: builder.program.expert_choices(p, t, cfg))
    count = jax.jit(lambda p, t: ref.expert_counts(p, t, hp))

    def agree(got, counts):
        want = np.asarray(ref.bias_update(jnp.zeros_like(got),
                                          jnp.asarray(counts), rate))
        return float((np.sign(got) == np.sign(want)).mean())

    plan = None
    for seed in (int(s) for s in args.seeds.split(",")):
        params = builder.to_program(
            builder.make_params(cell.config, seed), cell.config)
        unique, shares, first = driver.check_batch(cell, builder, seed)
        times = np.rint(np.asarray(shares) * first.shape[0])
        if plan is None:
            plan = driver._plan(cell, builder, devices, params, first)
        else:
            plan._load((params, optimizer.init(params)))
        del params
        plan.step(first)
        stepped, _ = jax.tree_util.tree_unflatten(plan._state_tree,
                                                  plan._device_state())
        got = np.asarray(stepped["blocks"]["router_bias"], np.float32)
        driver.release(plan)
        del stepped

        params = builder.make_params(cell.config, seed)
        program_counts = sum(
            n * np.stack([np.bincount(np.asarray(layer).ravel(),
                                      minlength=cfg.num_experts)
                          for layer in choose(
                              builder.to_program(params, cell.config),
                              t[None, :-1])])
            for n, t in zip(times, unique)).astype(np.float32)
        reference_counts = sum(n * np.asarray(count(params, t[None]))
                               for n, t in zip(times, unique))
        del params
        print(json.dumps({
            "workload": cell.name, "seed": seed, "bias": list(got.shape),
            "bias_rate": rate,
            "values_by_layer": [len(np.unique(np.round(r, 7))) for r in got],
            "largest_sum_of_a_layer": float(np.abs(got.sum(-1)).max()),
            "sign_agrees_with_reference_counts": agree(got,
                                                       reference_counts),
            "sign_agrees_with_program_forward_counts": agree(
                got, program_counts),
            "experts_within_1pct_of_the_mean_by_layer": [
                int((np.abs(r - r.mean()) < 0.01 * r.mean()).sum())
                for r in reference_counts],
            "device": devices[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
