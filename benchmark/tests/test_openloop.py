"""The open-loop generator: seeded, timed from due time, reports its own
lateness."""

from collections import Counter

import pytest

from benchmark.lib import openloop

MIX = {"rate_per_s": 10.0, "order_seed": 3_000_000_019,
       "prompt_tokens": {"median": 192, "sigma": 0.8, "min": 32, "max": 768},
       "output_tokens": {"median": 96, "sigma": 0.7, "min": 16, "max": 256},
       "max_total_tokens": 1024}


def _sizes(schedule):
    return (Counter(r.prompt_len for r in schedule),
            Counter(r.max_new_tokens for r in schedule))


def _trace(schedule):
    return [(r.due_s, r.prompt_len, r.max_new_tokens) for r in schedule]


def test_one_order_seed_one_trace_another_the_same_work_reordered():
    a = openloop.make_schedule(MIX, 30.0)
    b = openloop.make_schedule(dict(MIX), 30.0)
    c = openloop.make_schedule(dict(MIX, order_seed=7), 30.0)
    assert _trace(a) == _trace(b)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in c]
    assert _sizes(a) == _sizes(c)           # the order changes, no more
    gaps = lambda s: sorted(round(y.due_s - x.due_s, 9)
                            for x, y in zip(s, s[1:]))
    assert len(a) == 300 and a[-1].due_s <= 30.0
    assert abs(sum(gaps(a)) - sum(gaps(c))) < 0.5


def test_a_mix_without_an_order_seed_is_refused():
    mix = {k: v for k, v in MIX.items() if k != "order_seed"}
    with pytest.raises(KeyError):
        openloop.make_schedule(mix, 30.0)


def test_lengths_follow_the_mix():
    s = openloop.make_schedule(MIX, 60.0)
    lens = sorted(r.prompt_len for r in s)
    assert lens[0] >= 32 and lens[-1] <= 768
    assert 170 <= lens[len(lens) // 2] <= 215          # median 192
    assert all(r.prompt_len + r.max_new_tokens <= 1024 for r in s)


class _FakeSystem:
    """Takes 1.0 s of fake time per step and finishes a request's tokens
    one per step; the clock only moves when it works or sleeps."""

    def __init__(self):
        self.now = 0.0
        self.reqs = {}

    def clock(self):
        return self.now

    def sleep(self, s):
        self.now += s

    def submit(self, req):
        self.reqs[req.rid] = [0, req.max_new_tokens]
        return True

    def step(self):
        self.now += 1.0
        for st in self.reqs.values():
            st[0] = min(st[0] + 1, st[1])
        return True

    def progress(self, rids):
        return {r: (self.reqs[r][0],
                    "done" if self.reqs[r][0] == self.reqs[r][1]
                    else "active", self.reqs[r][0] == self.reqs[r][1])
                for r in rids}


def test_times_run_from_due_time_and_lateness_is_reported():
    sys_ = _FakeSystem()
    sched = [openloop.Request("a", 0.0, 8, 3),
             openloop.Request("b", 0.25, 8, 2)]
    openloop.run_open_loop(sys_, sched, seconds=10.0, drain_s=5.0,
                           clock=sys_.clock, sleep=sys_.sleep)
    a, b = sched
    # a: submitted at 0, first token after one step.
    assert a.submitted_s == 0.0 and a.first_s == 1.0 and a.done_s == 3.0
    # b fell due at 0.25 while the system was inside a step: handed over
    # at 1.0, first token at 2.0 -> 1.75 s from when it was DUE.
    assert b.submitted_s == 1.0 and b.first_s == 2.0
    out = openloop.summarise(sched, 10.0, 5.0)
    assert out["failed"] == 0 and out["attempted"] == 2
    assert out["ttft_p95_ms"] == 1750.0
    assert out["generator_late_max_ms"] == 750.0
    assert out["serve_tokens_per_s"] == 5 / 10.0
    # With the window closed at 2.5 s only the tokens out by then count:
    # a has 2 of its 3, b (first token at 2.0) has 1 of its 2.
    again = [openloop.Request("a", 0.0, 8, 3),
             openloop.Request("b", 0.25, 8, 2)]
    sys2 = _FakeSystem()
    openloop.run_open_loop(sys2, again, seconds=2.5, drain_s=5.0,
                           clock=sys2.clock, sleep=sys2.sleep)
    assert openloop.summarise(again, 2.5, 5.0)["serve_tokens_per_s"] == \
        3 / 2.5
    # a: (3.0 - 1.0) / (3 - 1) = 1 s a token
    assert out["itl_p95_ms"] == 1000.0


def test_an_unserved_request_counts_as_failed_and_as_waiting():
    class Dead(_FakeSystem):
        def step(self):
            self.now += 1.0
            return True
    sys_ = Dead()
    sched = [openloop.Request("a", 0.0, 8, 3)]
    openloop.run_open_loop(sys_, sched, seconds=2.0, drain_s=2.0,
                           clock=sys_.clock, sleep=sys_.sleep)
    out = openloop.summarise(sched, 2.0, 2.0)
    assert out["failed"] == 1
    assert out["ttft_p95_ms"] == 4000.0


def test_percentile_is_nearest_rank():
    assert openloop.percentile(list(range(1, 101)), 0.95) == 95
    assert openloop.percentile([5.0], 0.95) == 5.0
