"""Open-loop load: requests are due on a schedule fixed by the traffic file
and the seed, whatever the system does.

``make_schedule`` turns a mix's parameters into requests: the quantiles of
the mix's distributions of gaps, prompt lengths and output lengths (so that
no draw is lucky or unlucky), in the one order the mix's ``order_seed``
fixes. Every run of a cell replays that trace of arrivals and sizes; the
run's seed changes the token ids and the weights only. (With the order drawn
from the run's seed, who queues behind whom moved the tails of six seeds by
17%, my chip runs, PR 23.)

``run_open_loop`` submits each request when it is due and times everything
from then: a stall of the system delays the submission of later requests,
and that wait is in their time to first token. It drives the system in this
thread, one scheduler iteration at a time, so there is no second thread to
fight for the interpreter; how late each request was handed over is
reported.
"""

from __future__ import annotations

import math
import random
import statistics
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Protocol, Sequence


@dataclass
class Request:
    rid: str
    due_s: float                 # seconds after the window opens
    prompt_len: int
    max_new_tokens: int
    # filled by the run
    submitted_s: Optional[float] = None
    accepted: bool = False
    first_s: Optional[float] = None
    done_s: Optional[float] = None
    n_tokens: int = 0
    tokens_in_window: int = 0    # of n_tokens, those out by the window's end
    status: str = "due"


class System(Protocol):
    def submit(self, req: Request) -> bool: ...
    def step(self) -> bool: ...
    def progress(self, rids: Sequence[str]) -> Dict[str, tuple]: ...


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def _lognormal_lengths(n: int, spec: dict) -> List[int]:
    nd = statistics.NormalDist()
    mu = math.log(spec["median"])
    return [int(min(spec["max"], max(spec["min"], round(
        math.exp(mu + spec["sigma"] * nd.inv_cdf(q))))))
        for q in _quantiles(n)]


def make_schedule(mix: dict, seconds: float) -> List[Request]:
    """``round(rate * seconds)`` requests with exponential gaps (Poisson
    arrivals), all due inside the window."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    gaps = [-math.log(1.0 - q) for q in _quantiles(n)]
    scale = seconds / sum(gaps) * (n - 0.5) / n
    gaps = [g * scale for g in gaps]
    prompts = _lognormal_lengths(n, mix["prompt_tokens"])
    outputs = _lognormal_lengths(n, mix["output_tokens"])
    rng = random.Random(int(mix["order_seed"]))
    for seq in (gaps, prompts, outputs):
        rng.shuffle(seq)
    limit = int(mix["max_total_tokens"])
    out, t = [], 0.0
    for i in range(n):
        t += gaps[i]
        out.append(Request(rid=f"r{i}", due_s=t, prompt_len=prompts[i],
                           max_new_tokens=min(outputs[i],
                                              limit - prompts[i])))
    return out


def run_open_loop(system: System, schedule: List[Request], seconds: float,
                  drain_s: float,
                  clock: Callable[[], float] = time.perf_counter,
                  sleep: Callable[[float], None] = time.sleep,
                  on_iteration: Optional[Callable[[], None]] = None
                  ) -> float:
    """Drive ``system`` through ``schedule``; returns the clock value at
    which the window opened. After ``seconds`` nothing new is due; the
    requests in flight get ``drain_s`` more to finish, outside the window."""
    t_open = clock()
    todo = sorted(schedule, key=lambda r: r.due_s)
    nxt = 0
    live: Dict[str, Request] = {}
    while True:
        now = clock() - t_open
        while nxt < len(todo) and todo[nxt].due_s <= now:
            req = todo[nxt]
            nxt += 1
            req.submitted_s = now
            req.accepted = system.submit(req)
            if req.accepted:
                live[req.rid] = req
                req.status = "submitted"
            else:
                req.status = "refused"
        if not live and nxt >= len(todo):
            break
        if now > seconds + drain_s:
            break
        worked = system.step() if live else False
        now = clock() - t_open
        if live:
            for rid, (n_tokens, status, terminal) in system.progress(
                    list(live)).items():
                req = live[rid]
                if n_tokens and req.first_s is None:
                    req.first_s = now
                req.n_tokens = n_tokens
                if now <= seconds:
                    req.tokens_in_window = n_tokens
                req.status = status
                if terminal:
                    req.done_s = now
                    del live[rid]
        if on_iteration is not None:
            on_iteration()
        if not worked and nxt < len(todo):
            wait = todo[nxt].due_s - (clock() - t_open)
            if wait > 0:
                sleep(min(wait, 0.05))
    return t_open


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` of
    the sample at or below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def summarise(schedule: List[Request], seconds: float, drain_s: float
              ) -> dict:
    """End-to-end numbers of one window, from the client's side. Tokens per
    second counts every output token that was out by the window's end, over
    the window. A request with no first token by the end of the drain
    counts with the time it had waited by then (a floor on its true value)
    and as failed."""
    end = seconds + drain_s
    ttft, itl, failed = [], [], 0
    tokens_in_window = 0
    for r in schedule:
        ok = r.status == "done" and r.n_tokens == r.max_new_tokens
        failed += 0 if ok else 1
        ttft.append(((r.first_s if r.first_s is not None else end)
                     - r.due_s) * 1e3)
        if r.n_tokens >= 2 and r.done_s is not None:
            itl.append((r.done_s - r.first_s) / (r.n_tokens - 1) * 1e3)
        elif not ok:
            itl.append((end - r.due_s) * 1e3)
        tokens_in_window += r.tokens_in_window
    late = [(r.submitted_s - r.due_s) * 1e3 for r in schedule
            if r.submitted_s is not None]
    return {
        "attempted": len(schedule), "failed": failed,
        "serve_tokens_per_s": tokens_in_window / seconds,
        "ttft_p95_ms": percentile(ttft, 0.95),
        "itl_p95_ms": percentile(itl, 0.95),
        "ttft_p50_ms": percentile(ttft, 0.50),
        "itl_p50_ms": percentile(itl, 0.50),
        "generator_late_p50_ms": percentile(late, 0.50) if late else 0.0,
        "generator_late_max_ms": max(late) if late else 0.0,
        "completed_in_window": sum(1 for r in schedule if r.done_s is not None
                                   and r.done_s <= seconds),
        "unfinished_after_drain": sum(1 for r in schedule
                                      if r.done_s is None),
    }
