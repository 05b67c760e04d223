"""Time and check the rotary embedding alone, on the chip:
``models/layers.py:rope``, its value and its vjp, at the shapes the cells
call it with.

A case is ``B,H,T,D[/rotary_dim]:dtype:table`` (``table``: ``plain``, the
plain table at theta 10,000, or ``yarn``, Mellum2's YaRN table with its scale
on cos and sin). Device microseconds a call out, from one ``jax.profiler``
trace a form and direction, with the longest operations of each, beside what
HBM needs to read the heads once and write them once
(``benchmark/peaks.json``); and, from the optimised HLO of each compiled
form, the ``concatenate``s and the results ``half = rotary_dim / 2`` wide that
are left in it (a head's lanes split or joined: a relayout on the chip).

``--impl parent=<another layers.py, or a directory that holds one>`` times a
second copy of ``rope`` in the same process and says whether its value and
its vjp are the first copy's bit for bit (exit 1 where one is not, but for
the sign of a zero):
``git archive <parent> tepdist_tpu/models/layers.py | tar -x -C
.chip_scratch/p``, then ``--impl
parent=.chip_scratch/p/tepdist_tpu/models/``.

No benchmark cell runs this; it is for work on the function. No CPU
fallback.

Run: chiprun -- python tools/rope_bench.py [--impl parent=DIR]
     [--case 1,32,16384,128:bf16:plain ...] [--iters 10] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools.flash_bench import load_impl as load_file  # noqa: E402
from tools.gmm_bench import traced_us  # noqa: E402

# Mellum2's q and k under both its tables, Trinity's q, ZAYA's float32 heads
# (half a head rotated), Qwen3-Next's q (64 of 256), sarvam's rotary part of
# a chunk (float32, a head of 64).
CASES = ("1,32,16384,128:bf16:plain", "1,32,16384,128:bf16:yarn",
         "1,4,16384,128:bf16:plain", "1,4,16384,128:bf16:yarn",
         "1,32,8192,128:bf16:plain", "1,8,8192,128/64:f32:plain",
         "1,16,8192,256/64:bf16:plain", "1,16,2048,64:f32:plain")


def load_impl(label: str, path: str):
    """A copy of ``layers.py`` as a module of its own: ``path`` the file or a
    directory that holds it."""
    if os.path.isdir(path):
        path = os.path.join(path, "layers.py")
    return load_file(label + "_layers", path)


def split_lanes(hlo: str, half: int) -> dict:
    """What an optimised HLO text still holds of a head cut at lane
    ``half``: its ``concatenate`` instructions, and the instructions whose
    result's minor dimension is ``half``."""
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = \(?(\w+\[[\d,]*\])", hlo,
                         re.M)
    return {"concatenates": len(re.findall(r"\bconcatenate\(", hlo)),
            "half_wide_results": sum(1 for r in results
                                     if re.search(rf"[\[,]{half}\]$", r))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--case", action="append", default=[],
                    help="B,H,T,D[/rotary_dim]:bf16|f32:plain|yarn; "
                    "repeatable (the default: the cells' calls)")
    ap.add_argument("--impl", action="append", default=[],
                    help="label=path to another layers.py or its directory")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--start", type=int, default=0,
                    help="the first position (an int: a traced scalar where "
                    "it is not 0, as sarvam's chunks hand it)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import device
    from tepdist_tpu.models import mellum

    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    impls = [("tree", os.path.join(ROOT, "tepdist_tpu", "models"))]
    impls += [tuple(item.partition("=")[::2]) for item in args.impl]
    impls = [(label, load_impl(label, path)) for label, path in impls]
    yarn = mellum.MellumConfig().global_rope
    trace_root = os.path.join(ROOT, ".bench_trace", "rope_bench")
    records, sound = [], True

    for case in args.case or CASES:
        shape, dtype_name, table_name = case.split(":")
        shape, _, rotary_dim = shape.partition("/")
        B, H, T, D = (int(v) for v in shape.split(","))
        rotary_dim = int(rotary_dim) if rotary_dim else None
        dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype_name]
        bits = {"bf16": np.uint16, "f32": np.uint32}[dtype_name]
        rng = np.random.default_rng(args.seed)
        x, g = (jnp.asarray(rng.standard_normal((B, H, T, D), np.float32)
                            ).astype(dtype) for _ in range(2))
        start = jnp.int32(args.start)
        moved = 2 * x.size * x.dtype.itemsize
        rec = {"case": case, "start": args.start, "iters": args.iters,
               "bytes_in_and_out": moved,
               "hbm_us": 1e6 * moved / peaks["hbm_bytes_per_s"],
               "device": devices[0].device_kind}
        first = None
        for label, module in impls:
            table = 10000.0 if table_name == "plain" \
                else module.RopeTable(*yarn)

            def rotated(x, start, rope=module.rope, table=table):
                # Behind a barrier: alone, with its result the program's
                # own, the sliced form's ``concatenate`` at lane 64 aborts
                # the TPU compiler (``IsFusibleUnalignedDUS``).
                return jax.lax.optimization_barrier(rope(
                    x, table, start if args.start else 0, rotary_dim))

            forms = {
                "value": (jax.jit(rotated), (x, start)),
                "vjp": (jax.jit(lambda x, g, start, rotated=rotated: jax.vjp(
                    lambda x: rotated(x, start), x)[1](g)[0]),
                    (x, g, start))}
            got = {}
            for name, (fn, operands) in forms.items():
                hlo = fn.lower(*operands).compile().as_text()
                us, ops = traced_us(fn, operands, args.iters,
                                    os.path.join(trace_root, label, name))
                got[name] = np.asarray(fn(*operands))
                rec[f"{label}.{name}"] = {
                    "us_per_call": us, "hbm_share": rec["hbm_us"] / us,
                    "top_ops": ops,
                    **split_lanes(hlo, (rotary_dim or D) // 2)}
            if first is None:
                first = got
            else:
                differ = {name: got[name].view(bits) != first[name].view(bits)
                          for name in got}
                rec[f"{label}.bit_for_bit"] = {
                    name: not d.any() for name, d in differ.items()}
                # Where a copy is off: how many elements, in which channels,
                # by how much, and how many of them only by the sign of a
                # zero (the product form hands a ``-0.0`` past the rotary
                # width on as ``+0.0``: the one difference that is sound).
                rec[f"{label}.differs"] = {
                    name: {"elements": int(d.sum()),
                           "zero_signs": int((d & (got[name] == 0)
                                              & (first[name] == 0)).sum()),
                           "channels": np.unique(np.nonzero(d)[3]).tolist(),
                           "largest": float(np.abs(
                               got[name].astype(np.float32)
                               - first[name].astype(np.float32)).max())}
                    for name, d in differ.items() if d.any()}
                sound = sound and all(
                    off["elements"] == off["zero_signs"]
                    for off in rec[f"{label}.differs"].values())
        line = json.dumps(rec)
        print(line, flush=True)
        records.append(line)
        del x, g, first, got
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(records) + "\n")
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
