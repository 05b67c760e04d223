"""Share of the router's top-k choices on which the program (bf16) and the
float32 reference disagree, on a cell's check batch, on the chip.

Routing is discrete: where two experts' probabilities lie closer than bf16
resolves, the program can pick the one the reference does not, and from
that layer on the two compute different functions. This reads how often:
for every layer and token, the part of the reference's k experts the
program did not choose. It also fills the program's routing counters
(the model's ``routing_stats``; ``moe_tokens_dropped`` must read 0; for a
chip that holds a share of the experts ``moe_layout_rows_share`` and
``moe_layout_worst_case`` say which size each layer's layout took). The
model and its reference are the cell's builder's (``builder.program``,
``benchmark/reference/<builder>.py``, whose ``hidden`` returns the expert
ids last): OLMoE's and, with the share of the experts a chip holds
(``held_share_by_layer``: 1 for a chip that holds them all), AFMoE's and
Mellum2's.
``--sizes-out FILE`` writes the rows each expert got in each layer from the
first micro batch of the last seed's check batch, for
``tools/gmm_bench.py --sizes FILE``: the groups the cell's kernels meet.

Weights and sequences are the cell's own (``benchmark/builders/``,
``drivers/train_steps.py:check_batch``), one sequence at a time.

Run: chiprun -- python tools/olmoe_flips.py [--workload olmoe-1b-7b.train.s4096 |
     trinity-mini.train.s8192 | mellum2-12b-a2.5b.train.s16384]
     [--seeds 1,2] [--sizes-out chiprun_out/olmoe_group_sizes.json]
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="olmoe-1b-7b.train.s4096")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--sizes-out", default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmark.lib import cells, device
    cell = cells.load_cell(args.workload, ROOT)
    devices = device.own_chips(1)
    device.configure_cache(ROOT)
    builder, driver = cells.builder_for(cell), cells.driver_for(cell)
    program = builder.program
    ref = importlib.import_module(
        "benchmark.reference." + cell.config["builder"])
    cfg, hp = builder.program_config(cell.config), \
        builder.reference_hyper(cell.config)
    want_fn = jax.jit(lambda p, t: ref.hidden(p, t, hp)[-1])
    k = cfg.num_experts_per_tok
    first, count = getattr(cfg, "experts_held", (0, cfg.num_experts))
    micro = int(cell.traffic["batch"]) // int(
        cell.traffic.get("num_micro_batches") or 1)
    for seed in (int(s) for s in args.seeds.split(",")):
        params = builder.make_params(cell.config, seed)
        unique = driver.check_batch(cell, builder, seed)[0]
        kept, total, stats, busiest, rows = None, 0, {}, [], []
        for tokens in unique:
            got = program.routing_stats(
                builder.to_program(params, cell.config), tokens[None], cfg)
            want = np.asarray(want_fn(params, tokens[:-1]))     # [L, T, k]
            have = np.asarray(got.pop("experts"))
            got.pop("held_rows", None)
            # Share of a layer's assignments its k busiest experts took:
            # k / E under a balanced router, 1 where every token agrees.
            rows.append([np.bincount(layer.ravel(),
                                     minlength=cfg.num_experts)
                         for layer in have])                 # [L, E]
            busiest.append([np.sort(r)[-k:].sum() / r.sum()
                            for r in rows[-1]])
            same = (want[..., :, None] == have[..., None, :]).any(-1)
            kept = same.sum(axis=(1, 2)) + (0 if kept is None else kept)
            total += same[0].size
            for name, v in got.items():
                stats[name] = max(stats.get(name, 0), v) \
                    if name.endswith("_max") else stats.get(name, 0) + v
        for name in stats:
            if name.endswith(("_mean", "_share")):
                stats[name] /= len(unique)
        flipped = 1.0 - kept / total
        # What the experts held here got of each sequence's choices.
        held = np.asarray(rows)[:, :, first:first + count]  # [U, L, count]
        totals = np.asarray(rows).sum(-1)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "sequences": len(unique),
            "choices_a_layer": int(total), "k": k,
            "flipped_share_by_layer": [float(x) for x in flipped],
            "flipped_share": float(flipped.mean()),
            "busiest_k_experts_share_by_layer":
                [float(x) for x in np.mean(busiest, axis=0)],
            "held_share_by_layer": [float(x) for x in np.mean(
                held.sum(-1) / totals, axis=0)],
            "held_rows_max_by_layer": held.max(axis=(0, 2)).tolist(),
            "held_empty_by_layer": [float(x) for x in np.mean(
                (held == 0).sum(-1), axis=0)], **stats,
            "device": devices[0].device_kind}), flush=True)
    if args.sizes_out:
        os.makedirs(os.path.dirname(os.path.abspath(args.sizes_out)),
                    exist_ok=True)
        with open(args.sizes_out, "w") as f:
            json.dump({"workload": cell.name, "seed": seed,
                       "sequences": micro,
                       "layers": np.sum(rows[:micro], axis=0).tolist()}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
