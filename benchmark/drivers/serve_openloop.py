"""Driver loop for ``kind: serve`` traffic: the paged ``ServingEngine`` in
this process under open-loop arrivals at the rate the traffic file fixes.

Set-up, before the window opens: weights from the seed, the engine and its
page pool, every prefill, insert, decode and pick program the mix can reach
(run once each on the trash page, so nothing compiles inside the window),
then the output check: a seeded sample of requests served through the engine
together, their logits caught at ``prefill_chunk`` and ``decode_batch`` and
compared with the reference's full forward pass over the same tokens.

The benchmark wraps three methods of the engine's model object from here
(``prefill_chunk``, ``decode_batch``, ``pick``): host spans in a traced run,
and the count of decode calls and rows always. Nothing in the program is
edited, and the clock that times requests is the benchmark's own.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark.lib import openloop, recorder
from benchmark.lib.cells import BenchError


def _pow2_at_least(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _pow2_range(lo: int, hi: int) -> list:
    out, b = [], _pow2_at_least(lo)
    while b <= _pow2_at_least(hi):
        out.append(b)
        b *= 2
    return out


class _Wrapped:
    """The engine behind ``openloop.System``, with the model's three entry
    points wrapped for spans, counts and (during the check) logits."""

    def __init__(self, engine, host, vocab: int, seed: int):
        self.engine, self.host, self.model = engine, host, engine.model
        self.rng = np.random.default_rng(int(seed))
        self.vocab = vocab
        self.decode_calls = 0
        self.decode_rows = 0
        self.caught = None                # list while the check runs
        model = self.model
        inner_chunk, inner_decode, inner_pick = (
            model.prefill_chunk, model.decode_batch, model.pick)

        def prefill_chunk(pages, prompt, start, end):
            if host.annotate:
                with host.span("prefill_chunk"):
                    out = inner_chunk(pages, prompt, start, end)
            else:
                out = inner_chunk(pages, prompt, start, end)
            if self.caught is not None and end >= len(prompt):
                self.caught.append(("prefill", len(prompt), out))
            return out

        def decode_batch(rows):
            self.decode_calls += 1
            self.decode_rows += len(rows)
            if host.annotate:
                with host.span("decode_batch"):
                    out = inner_decode(rows)
                    out.block_until_ready()
            else:
                out = inner_decode(rows)
            if self.caught is not None:
                self.caught.append(("decode", [r[2] for r in rows], out))
            return out

        def pick(*args, **kwargs):
            if host.annotate:
                with host.span("pick"):
                    return inner_pick(*args, **kwargs)
            return inner_pick(*args, **kwargs)

        model.prefill_chunk, model.decode_batch, model.pick = (
            prefill_chunk, decode_batch, pick)

    # -- openloop.System ------------------------------------------------
    def submit(self, req) -> bool:
        prompt = self.rng.integers(0, self.vocab, req.prompt_len,
                                   dtype=np.int32)
        return self.submit_tokens(req.rid, prompt, req.max_new_tokens)

    def submit_tokens(self, rid, prompt, max_new_tokens) -> bool:
        reply = self.engine.submit(rid, prompt,
                                   max_new_tokens=max_new_tokens,
                                   greedy=True)
        return reply["status"] == "queued"

    def step(self) -> bool:
        with self.host.span("engine_step") if self.host.annotate \
                else contextlib.nullcontext():
            return self.engine.step()

    def progress(self, rids):
        from tepdist_tpu.serving.engine import TERMINAL
        return {r["request_id"]: (r["n_tokens"], r["status"],
                                  r["status"] in TERMINAL)
                for r in self.engine.poll(rids)}

    def tokens_of(self, rid):
        return self.engine.poll([rid])[0]["tokens"]


def warm_up(system: _Wrapped, schedule, traffic: dict) -> dict:
    """Run every program shape the mix can reach once, on the trash page
    (physical page 0, which the engine itself uses for padded rows)."""
    model = system.model
    ps, chunk = model.page_size, model.chunk_tokens
    # Chunks start at multiples of the chunk size; a prompt's last chunk
    # can have any length, so every chunk bucket is warmed at every history
    # bucket the longest prompt reaches.
    longest = max(r.prompt_len for r in schedule)
    seen = set()
    for start in range(0, longest, chunk):
        for c in (b for b in model.buckets if b <= chunk):
            key = (c, _pow2_at_least(max(start // ps, 1)))
            if key in seen or start + c > model.max_len:
                continue
            seen.add(key)
            end = start + c
            logits = model.prefill_chunk([0] * (-(-end // ps)),
                                         np.zeros(end, np.int32), start, end)
            model.pick(logits, None, 1.0, 0, True)
    lo = min(-(-(r.prompt_len + 1) // ps) for r in schedule)
    hi = max(-(-(r.prompt_len + r.max_new_tokens - 1) // ps)
             for r in schedule)
    page_buckets = _pow2_range(lo, hi)
    row_buckets = _pow2_range(1, int(traffic["engine"]["warm_rows"]))
    for rows in row_buckets:
        for pages in page_buckets:
            logits = model.decode_batch([([0] * pages, 0, 0)] * rows)
            model.pick(logits[0], None, 1.0, 0, True)
    logits.block_until_ready()
    return {"chunk_programs": len(seen),
            "decode_programs": len(row_buckets) * len(page_buckets),
            "row_buckets": row_buckets, "page_buckets": page_buckets}


def catch_logits(system: _Wrapped, cell, seed: int):
    """Serve the check's sample requests together through the engine and
    return, per request, its tokens (prompt then generated) and the logits
    the engine computed at each position it predicted from."""
    spec = cell.spec["correct"]
    lens, new = list(spec["sample_prompt_tokens"]), int(spec["sample_new_tokens"])
    rng = np.random.default_rng([int(seed), 7])
    prompts = [rng.integers(0, system.vocab, n, dtype=np.int32)
               for n in lens]
    system.caught = []
    for i, p in enumerate(prompts):
        if not system.submit_tokens(f"check{seed}-{i}", p, new):
            raise RuntimeError("the engine refused a check request")
    for _ in range(100000):
        if not system.engine.step():
            break
    caught, system.caught = system.caught, None
    samples = []
    for i, p in enumerate(prompts):
        toks = system.tokens_of(f"check{seed}-{i}")
        if len(toks) != new:
            raise RuntimeError(f"check request {i} produced {len(toks)} "
                               f"tokens of {new}")
        rows = {}
        for kind, where, out in caught:
            if kind == "prefill" and where == len(p):
                rows[len(p) - 1] = np.asarray(out)
            elif kind == "decode":
                for j, pos in enumerate(where):
                    if len(p) <= pos < len(p) + new - 1:
                        rows[pos] = np.asarray(out[j])
        samples.append({"tokens": np.concatenate([p, np.asarray(
            toks[:-1], np.int32)]), "logits": rows})
    return samples


def logits_error(samples, reference_logits) -> float:
    """Largest relative L2 error of a caught logits row against the
    reference's row at the same position of the same sequence."""
    worst = 0.0
    for s, ref in zip(samples, reference_logits):
        for pos, row in s["logits"].items():
            want = np.asarray(ref[pos], np.float64)
            err = np.linalg.norm(row.astype(np.float64) - want) \
                / np.linalg.norm(want)
            worst = max(worst, float(err))
    return worst


def reference_logits(cell, builder, params, samples, cast=None):
    """The reference's full forward pass over each sample's tokens, the
    samples padded to one length (causal: padding changes nothing before
    it) so that one program serves all."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import gpt2 as ref
    n_head = builder.model_sizes(cell.config)["H"]
    longest = max(len(s["tokens"]) for s in samples)
    batch = np.zeros((len(samples), longest), np.int32)
    for i, s in enumerate(samples):
        batch[i, :len(s["tokens"])] = s["tokens"]
    fn = jax.jit(lambda p, t: ref.logits(p, t, n_head,
                                         cast or ref.identity))
    return np.asarray(fn(params, jnp.asarray(batch)))


def check_outputs(system, cell, builder, params, seed: int) -> dict:
    limits = cell.spec["correct"]["limits"]
    samples = catch_logits(system, cell, seed)
    n_rows = sum(len(s["logits"]) for s in samples)
    err = logits_error(samples, reference_logits(cell, builder, params,
                                                 samples))
    errors = {"logits_rel_err": err}
    return {"compared": {k: {"value": errors[k], "limit": limits[k]}
                         for k in limits},
            "rows_compared": n_rows,
            "ok": n_rows > 0 and all(errors[k] <= limits[k]
                                     for k in limits)}


def build(cell, builder, seed: int, host):
    with host.span("weights"):
        params = builder.make_params(cell.config, seed)
    with host.span("engine"):
        engine = builder.serving_engine(
            cell.config, cell.traffic,
            builder.to_program(params, cell.config))
        system = _Wrapped(engine, host,
                          builder.model_sizes(cell.config)["V"], seed)
    return params, system


class _TracedPart:
    """Starts the profiler once the system has ramped up and stops it
    ``span_s`` later; called after every scheduler iteration."""

    def __init__(self, cell, host, system, ramp_s: float, span_s: float):
        from benchmark.lib import tracing
        self.trace = tracing.WindowTrace(cell.root, cell.name, host)
        self.system = system
        self.t0 = time.perf_counter()
        self.ramp_s, self.end_s = ramp_s, ramp_s + span_s
        self.state = "ramp"

    def tick(self) -> None:
        now = time.perf_counter() - self.t0
        if self.state == "ramp" and now >= self.ramp_s:
            self.system.decode_calls = self.system.decode_rows = 0
            self.trace.start()
            self.state = "tracing"
        elif self.state == "tracing" and now >= self.end_s:
            self.close()

    def close(self) -> None:
        if self.state == "tracing":
            self.calls = self.system.decode_calls
            self.rows = self.system.decode_rows
            self.trace.stop()
        self.state = "closed"


def run(cell, builder, devices, seed: int, seconds: float, trace: int,
        host, compiles) -> dict:
    if trace == 2:
        raise BenchError("the serving driver has no --trace 2 yet: it "
                         "returns with its cell")
    t = cell.traffic
    drain_s = float(t["drain_seconds"])
    params, system = build(cell, builder, seed, host)
    with host.span("warm_up"):
        warmed = warm_up(system, openloop.make_schedule(
            t["mix"], seconds), t)
    print("warm-up: " + str(warmed), flush=True)
    with host.span("check"):
        check = check_outputs(system, cell, builder, params, seed)
    print("check: " + str(check), flush=True)
    del params
    system.decode_calls = system.decode_rows = 0

    # The window runs with the program's spans and the profiler off; the
    # traced part switches both on through the program's control.
    program_compiles = recorder.off_for_window()
    mark = compiles.n
    if trace:
        ramp_s, span_s = (float(t["trace_ramp_seconds"]),
                          float(t["trace_seconds"]))
        seconds = ramp_s + span_s
        schedule = openloop.make_schedule(t["mix"], seconds)
        part = _TracedPart(cell, host, system, ramp_s, span_s)
        t_open = openloop.run_open_loop(system, schedule, seconds, drain_s,
                                        on_iteration=part.tick)
        part.close()
        cell.facts["trace_path"] = part.trace.path
        calls, rows = part.calls, part.rows
    else:
        schedule = openloop.make_schedule(t["mix"], seconds)
        host.counters["setup_s"] = time.perf_counter() - host.t0
        t_open = openloop.run_open_loop(system, schedule, seconds, drain_s)
        calls, rows = system.decode_calls, system.decode_rows
    host.counters.setdefault("setup_s", t_open - host.t0)
    compiled_inside = compiles.since(mark)
    s = openloop.summarise(schedule, seconds, drain_s)
    print("window: " + str(s) + f", compiles inside the window: "
          f"{compiled_inside} (limit 0), decode calls {calls}, rows {rows}",
          flush=True)
    return {
        "correct": bool(check["ok"] and compiled_inside == 0),
        "attempted": s["attempted"], "failed": s["failed"],
        "end_to_end": {k: s[k] for k in
                       ("serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms")},
        "host": {"decode_calls": calls, "decode_rows": rows,
                 "compiles_in_window": compiled_inside, "summary": s,
                 "program_compiles": program_compiles},
    }


def readings(cell, builder, devices, seeds, control_seeds, host):
    """For ``check_control.py``: one engine, new weights and new sample
    requests per seed; the control is the reference's forward pass one
    precision step lower, compared as the engine's logits are."""
    from benchmark.reference import gpt2 as ref
    params, system = build(cell, builder, seeds[0], host)
    for seed in seeds:
        params = builder.make_params(cell.config, seed)
        system.model.params = builder.to_program(params, cell.config)
        samples = catch_logits(system, cell, seed)
        want = reference_logits(cell, builder, params, samples)
        yield {"seed": seed, "side": "program",
               "logits_rel_err": logits_error(samples, want)}
        if seed in control_seeds:
            ctl = reference_logits(cell, builder, params, samples,
                                   ref.fp8_cast)
            for s, rows in zip(samples, ctl):
                s["logits"] = {pos: rows[pos] for pos in s["logits"]}
            yield {"seed": seed, "side": "control",
                   "logits_rel_err": logits_error(samples, want)}
