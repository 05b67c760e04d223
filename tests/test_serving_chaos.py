"""Serving-plane fault-tolerance tests (supervisor, drain, overload).

The serving counterpart of tests/test_faults.py, all on the inproc RPC
transport (socketless, tier-1 fast). Covers the ISSUE acceptance gates:

  * CHAOS: a two-worker fleet under a seeded spec with one
    ``engine_crash`` and one ``serve_fault`` mid-decode — every request
    reaches exactly ONE terminal state ("done"), nothing is delivered
    twice, and every greedy output is BIT-IDENTICAL to the sequential
    ``sample()`` reference (a double-generation or misjoined replay
    prefix would diverge).
  * DRAIN: draining a replica mid-flight hands its un-started queued
    requests back for resubmission on the survivors — zero failed
    in-flight requests, even while a survivor goes through a supervised
    engine restart under the handed-off load.
  * OVERLOAD: the shed watermark (hysteresis), the client circuit
    breaker state machine, failover past drained replicas, and the typed
    ``ServeOverloadError`` when the whole fleet refuses.
  * SUPERVISOR: restart-budget exhaustion falls to ``_fail_all_locked``
    without leaking SlotPool capacity; finished-but-unpolled results are
    carried across a restart (exactly-once delivery); the replayed Drain
    RPC answers with the ORIGINAL handoff list.
"""

import itertools
import time

import jax
import numpy as np
import pytest

from tepdist_tpu.models import gpt2
from tepdist_tpu.models.sampling import sample
from tepdist_tpu.rpc.client import TepdistClient
from tepdist_tpu.rpc.inproc import (close_inproc_cluster,
                                    make_inproc_cluster)
from tepdist_tpu.runtime import faults
from tepdist_tpu.serving import (ServeClient, ServeOverloadError,
                                 ServingSupervisor)
from tepdist_tpu.serving.client import _Breaker
from tepdist_tpu.telemetry import metrics

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

CFG = gpt2.CONFIGS["test"]


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    faults.configure(None)
    yield
    faults.reset()


def _counters():
    return dict(metrics().snapshot()["counters"])


def _mix(n, seed=7, lo=3, hi=12, max_new=5):
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, CFG.vocab_size,
                           size=int(rng.randint(lo, hi))).astype(np.int32)
               for _ in range(n)]
    return prompts, [max_new] * n


def _deal_out(sc, prompts, mnts):
    """Submit request i with replica ``i % n`` first in its order; the ids.

    ``ServeClient.submit`` draws n numbers of its round-robin counter a
    request, so every request's order begins at replica 0 and another
    replica sees a request only when replica 0 sheds one under load
    (ROADMAP D19). A gate that needs every worker busy hands ``submit`` the
    order itself: one draw or n, the order begins where the counter stands."""
    n = len(sc.clients)
    rids = []
    for i, (p, m) in enumerate(zip(prompts, mnts)):
        sc._rr = itertools.count(i % n)
        rids.append(sc.submit(p, max_new_tokens=m)["request_id"])
    return rids


def _assert_matches_sample(params, prompts, mnts, results, rids):
    for p, m, rid in zip(prompts, mnts, rids):
        ref = np.asarray(sample(params, p[None], CFG, max_new_tokens=m,
                                greedy=True))[0, len(p):]
        np.testing.assert_array_equal(
            np.asarray(results[rid]["tokens"], np.int32), ref)


# ---------------------------------------------------------------------------
# Acceptance: engine_crash + serve_fault mid-decode over RPC
# ---------------------------------------------------------------------------

def test_serving_chaos_exactly_once_bit_identical(params):
    """THE serving chaos gate: worker 0's engine is killed at its 3rd
    scheduler step, worker 1 takes a serve_fault on its 3rd decode; the
    supervisors rebuild + replay, and every request still ends in exactly
    one "done" with tokens bit-identical to sequential sample()."""
    prompts, mnts = _mix(8, seed=7)
    cluster, servicers = make_inproc_cluster(2, jax.devices()[:2])
    sc = ServeClient(clients=[TepdistClient(w.address)
                              for w in cluster.workers])
    before = _counters()
    try:
        sc.load(params, CFG, slots=2, max_len=32, name="chaos")
        # Both faults fire whatever the hour: each worker is dealt four
        # requests of five tokens, and one such request alone takes a
        # prefill and four decodes, so worker 0 reaches a third scheduler
        # step and worker 1 a third decode before either can drain.
        faults.configure(
            "engine_crash:step=3,ti=0;"
            "serve_fault:op=decode,step=3,ti=1,seed=11")
        rids = _deal_out(sc, prompts, mnts)
        results = sc.wait(rids, timeout_s=300)
    finally:
        faults.configure(None)
        for s in servicers:
            s.close_servables()
        close_inproc_cluster(cluster)
    # Exactly one terminal state per request, and it is "done".
    assert sorted(results) == sorted(rids)
    assert all(r["status"] == "done" for r in results.values()), (
        {k: v["status"] for k, v in results.items()})
    # Bit-identity is the no-double-delivery/no-regeneration evidence:
    # a replay that re-emitted (or dropped) prefix tokens would diverge.
    _assert_matches_sample(params, prompts, mnts, results, rids)
    d = lambda k: _counters().get(k, 0) - before.get(k, 0)  # noqa: E731
    assert d("fault_injected:engine_crash") >= 1
    assert d("fault_injected:serve_fault") >= 1
    assert d("engine_restarts") >= 2
    assert d("requests_replayed") >= 1


def test_lockstep_supervisor_replays_greedy_and_sampled(params):
    """Lockstep (no threads): a supervisor surviving two engine
    generations reproduces the fault-free run for BOTH replay modes —
    greedy prefix-resume and seeded-sampling replay-from-scratch."""
    prompts, mnts = _mix(4, seed=3, max_new=4)
    greedy = [True, False, True, False]

    def run(spec):
        faults.configure(spec)
        try:
            sup = ServingSupervisor(params, CFG, slots=2, max_len=32)
            for i, (p, m) in enumerate(zip(prompts, mnts)):
                out = sup.submit(f"r{i}", p, max_new_tokens=m,
                                 greedy=greedy[i], seed=100 + i,
                                 temperature=0.9)
                assert out["status"] == "queued"
            sup.run_until_idle()
            res = {r["request_id"]: r for r in sup.poll()}
            return sup, res
        finally:
            faults.configure(None)

    _, clean = run(None)
    sup, chaotic = run("engine_crash:step=2;serve_fault:op=decode,step=4")
    assert sup.restarts == 2
    for rid in clean:
        assert chaotic[rid]["status"] == clean[rid]["status"] == "done"
        assert chaotic[rid]["tokens"] == clean[rid]["tokens"], rid


# ---------------------------------------------------------------------------
# Acceptance: graceful drain — zero failed in-flight requests
# ---------------------------------------------------------------------------

def test_drain_hands_off_without_failing_requests(params):
    """Drain replica 0 while its queue is still full; its un-started
    requests are resubmitted (same ids) on replica 1 — which itself goes
    through a supervised restart under the extra load. No request may
    end anywhere but "done"."""
    prompts, mnts = _mix(10, seed=5, max_new=6)
    cluster, servicers = make_inproc_cluster(2, jax.devices()[:2])
    sc = ServeClient(clients=[TepdistClient(w.address)
                              for w in cluster.workers])
    before = _counters()
    try:
        sc.load(params, CFG, slots=1, max_len=32, name="drainable")
        faults.configure("engine_crash:step=4,ti=1")
        rids = [sc.submit(p, max_new_tokens=m)["request_id"]
                for p, m in zip(prompts, mnts)]
        moved = sc.drain(0, wait_ms=30000)
        assert moved["failed"] == []
        results = sc.wait(rids, timeout_s=300)
    finally:
        faults.configure(None)
        for s in servicers:
            s.close_servables()
        close_inproc_cluster(cluster)
    assert all(r["status"] == "done" for r in results.values()), (
        {k: v["status"] for k, v in results.items()})
    _assert_matches_sample(params, prompts, mnts, results, rids)
    d = lambda k: _counters().get(k, 0) - before.get(k, 0)  # noqa: E731
    assert d("drain_handoffs") == moved["handed_off"]
    assert d("serve_requests_failed") == 0
    # Post-drain, replica 0 refuses new work and submit fails over.
    assert 0 in sc._drained


def test_drain_rpc_is_idempotent_with_original_handoffs(params):
    """A replayed Drain (same idem token) must answer with the ORIGINAL
    handoff list: the re-run would find an already-empty queue and a
    lost response would lose the handed-off requests."""
    from tepdist_tpu.rpc import protocol

    cluster, servicers = make_inproc_cluster(1)
    c = TepdistClient(cluster.workers[0].address)
    sc = ServeClient(clients=[c])
    before = _counters()
    try:
        sc.load(params, CFG, slots=1, max_len=32, name="idem-drain")
        sid = sc._placements[0][1]
        # Freeze the scheduler so the queue deterministically holds both
        # requests when the drain arrives.
        servicers[0].servables[sid].stop(timeout=0.0, drain=False)
        p = np.arange(1, 6, dtype=np.int32)
        for rid in ("d1", "d2"):
            assert c.submit_request(sid, rid, p, max_new_tokens=3)[
                "status"] == "queued"
        hdr = {"servable_id": sid, "wait_ms": 0.0,
               "idem": "test:Drain:1"}
        r1 = c.call("Drain", dict(hdr))
        r2 = c.call("Drain", dict(hdr))
        assert r1 == r2                      # byte-identical replay answer
        handed, _ = protocol.unpack(r1)
        assert sorted(h["request_id"] for h in handed["handed_off"]) \
            == ["d1", "d2"]
        # A FRESH drain finds the queue already empty.
        fresh = c.drain_servable(sid, wait_ms=0.0)
        assert fresh == []
    finally:
        for s in servicers:
            s.close_servables()
        close_inproc_cluster(cluster)
    d = lambda k: _counters().get(k, 0) - before.get(k, 0)  # noqa: E731
    assert d("dedup_hits") >= 1
    assert d("drain_handoffs") == 2          # counted once, not per replay


# ---------------------------------------------------------------------------
# Overload protection: watermark shedding + circuit breaker + failover
# ---------------------------------------------------------------------------

def test_shed_watermark_hysteresis(params):
    sup = ServingSupervisor(params, CFG, slots=1, max_len=32,
                            shed_high=2, shed_low=1)
    p = np.arange(1, 5, dtype=np.int32)
    before = _counters()
    assert sup.submit("a", p, max_new_tokens=2)["status"] == "queued"
    assert sup.submit("b", p, max_new_tokens=2)["status"] == "queued"
    # Depth hit shed_high: refusals start, and STAY on (hysteresis)
    # until the queue falls back to shed_low.
    out = sup.submit("c", p, max_new_tokens=2)
    assert out["status"] == "shed" and "watermark" in out["error"]
    assert sup.submit("d", p, max_new_tokens=2)["status"] == "shed"
    assert sup.stats()["shedding"]
    # Shed requests leave no record: the same id is admissible later.
    sup.run_until_idle()                     # queue drains to 0 <= low
    assert sup.submit("c", p, max_new_tokens=2)["status"] == "queued"
    assert not sup.stats()["shedding"]
    sup.run_until_idle()
    res = {r["request_id"]: r for r in sup.poll()}
    assert sorted(res) == ["a", "b", "c"]    # d was shed, never recorded
    assert all(r["status"] == "done" for r in res.values())
    d = lambda k: _counters().get(k, 0) - before.get(k, 0)  # noqa: E731
    assert d("serve_shed") == 2


def test_breaker_state_machine():
    before = _counters()
    br = _Breaker(threshold=2, cooldown_s=0.05)
    assert br.allow() and br.state == "closed"
    br.record_failure()
    assert br.allow()                        # below threshold: still closed
    br.record_failure()
    assert br.state == "open" and not br.allow()
    time.sleep(0.06)
    assert br.allow() and br.state == "half-open"   # one probe through
    br.record_failure()                      # probe failed: re-open
    assert br.state == "open" and not br.allow()
    time.sleep(0.06)
    assert br.allow()
    br.record_success()                      # probe succeeded: closed
    assert br.state == "closed" and br.failures == 0
    d = lambda k: _counters().get(k, 0) - before.get(k, 0)  # noqa: E731
    assert d("serve_breaker_trips") == 2     # two closed/half-open -> open


def test_submit_fails_over_and_raises_typed_overload(params):
    cluster, servicers = make_inproc_cluster(2, jax.devices()[:2])
    sc = ServeClient(clients=[TepdistClient(w.address)
                              for w in cluster.workers])
    try:
        sc.load(params, CFG, slots=1, max_len=32, name="failover")
        sc.drain(0, wait_ms=5000)
        p = np.arange(1, 6, dtype=np.int32)
        # Every post-drain submit fails over to replica 1.
        rids = [sc.submit(p, max_new_tokens=2)["request_id"]
                for _ in range(3)]
        assert all(sc._where[r][0] is sc.clients[1] for r in rids)
        results = sc.wait(rids, timeout_s=120)
        assert all(r["status"] == "done" for r in results.values())
        # With the whole fleet out, the refusal is typed — not a retry
        # storm, not a transport error.
        sc.drain(1, wait_ms=5000)
        with pytest.raises(ServeOverloadError, match="2 replicas"):
            sc.submit(p, max_new_tokens=2)
    finally:
        for s in servicers:
            s.close_servables()
        close_inproc_cluster(cluster)


# ---------------------------------------------------------------------------
# Supervisor internals: budget exhaustion, carried results
# ---------------------------------------------------------------------------

def test_restart_budget_exhaustion_fails_all_without_slot_leak(params):
    """Two crashes against max_restarts=1: the first restarts, the
    second falls to the ladder's last rung — every in-flight request
    fails, the SlotPool is whole, and the dead engine refuses new work
    without claiming the rid."""
    sup = ServingSupervisor(params, CFG, slots=2, max_len=32,
                            max_restarts=1)
    p = np.arange(1, 7, dtype=np.int32)
    for i in range(3):
        assert sup.submit(f"r{i}", p, max_new_tokens=6)["status"] \
            == "queued"
    faults.configure("engine_crash:step=2;engine_crash:step=3")
    for _ in range(12):
        sup.step()
        if sup.stats()["dead"]:
            break
    faults.configure(None)
    assert sup.restarts == 1
    assert sup.engine.model.pool.n_used == 0
    res = {r["request_id"]: r for r in sup.poll()}
    assert all(r["status"] == "failed" for r in res.values())
    assert all("1 restarts" in r["error"] for r in res.values())
    out = sup.submit("late", p, max_new_tokens=2)
    assert out["status"] == "rejected" and "engine dead" in out["error"]
    assert "late" not in sup.engine._reqs    # replacement could own it


def test_finished_results_carried_across_restart(params):
    """Exactly-once delivery: a request that FINISHED in the dead
    generation but was never polled must be answered by the supervisor
    (once) after the restart — neither lost nor re-generated."""
    sup = ServingSupervisor(params, CFG, slots=1, max_len=32)
    p = np.arange(1, 5, dtype=np.int32)
    ref = np.asarray(sample(params, p[None], CFG, max_new_tokens=1,
                            greedy=True))[0, len(p):]
    sup.submit("fin", p, max_new_tokens=1)   # done at prefill (1 token)
    sup.submit("run", p, max_new_tokens=6)
    sup.step()                               # "fin" done, NOT polled
    faults.configure("engine_crash:step=2")
    sup.run_until_idle()
    faults.configure(None)
    assert sup.restarts == 1
    res = {r["request_id"]: r for r in sup.poll()}
    assert res["fin"]["status"] == res["run"]["status"] == "done"
    np.testing.assert_array_equal(
        np.asarray(res["fin"]["tokens"], np.int32), ref)
    assert sup.stats()["carried_results"] == 1
    # A replayed submit of the carried rid answers from the supervisor.
    before = _counters()
    out = sup.submit("fin", p, max_new_tokens=1)
    assert out == {"status": "duplicate", "state": "done"}
    assert _counters().get("serve_requests_deduped", 0) \
        - before.get("serve_requests_deduped", 0) == 1


# ---------------------------------------------------------------------------
# Disaggregated handoff chaos (ISSUE 19)
# ---------------------------------------------------------------------------

def test_decode_replica_death_mid_handoff_replays_exactly_once(params):
    """A decode replica dies between submit and handoff: the router's
    AdoptPages attempt fails over to the surviving decode replica,
    the replay adopts exactly once (the failed attempt's rid record is
    dropped, so the survivor is not dedup-blocked), outputs stay
    bit-identical to sample(), and no pages leak on either live pool."""
    from tepdist_tpu.rpc.inproc import unregister_servicer
    from tepdist_tpu.serving import FleetRouter, pages_for

    prompts, mnts = _mix(4, seed=13, lo=5, hi=20)
    cluster, servicers = make_inproc_cluster(3, jax.devices()[:3])
    clients = [TepdistClient(w.address) for w in cluster.workers]
    router = FleetRouter(clients, prefill=1, decode=2)
    before = _counters()
    try:
        router.load(params, CFG, max_len=64, name="ddeath")
        rids = [router.submit(p, max_new_tokens=m)["request_id"]
                for p, m in zip(prompts, mnts)]
        # Kill decode replica d0 (worker 1) before any handoff: every
        # AdoptPages aimed at it burns the retry budget, surfaces as a
        # transport error, and fails over to d1.
        unregister_servicer(cluster.workers[1].address)
        for rid in rids:
            out = router.handoff(rid, timeout_s=120)
            assert out["status"] in ("adopted", "duplicate")
        results = router.wait(rids, timeout_s=300)
        # Every request landed on the survivor, exactly once.
        assert all(results[r]["status"] == "done" for r in rids)
        for p, m, rid in zip(prompts, mnts, rids):
            ref = np.asarray(sample(params, p[None], CFG,
                                    max_new_tokens=m,
                                    greedy=True))[0, len(p):]
            np.testing.assert_array_equal(
                np.asarray(results[rid]["tokens"], np.int32), ref)
        router.drain_all(wait_ms=5000.0)
        leaked = sum(int(e.stats().get("pages_used", 0))
                     for s in (servicers[0], servicers[2])
                     for e in s.servables.values())
        assert leaked == 0
    finally:
        faults.configure(None)
        for s in servicers:
            s.close_servables()
        close_inproc_cluster(cluster)
    d = lambda k: _counters().get(k, 0) - before.get(k, 0)  # noqa: E731
    # Exactly-once: the survivor adopted each request's live pages once.
    live = sum(pages_for(len(p), router.page_size) for p in prompts)
    assert d("kv_pages_adopted") == live
    assert d("pool_handoffs") == len(prompts)


# ---------------------------------------------------------------------------
# ISSUE 20: bounded retention (the _completed/_journal/_delivered leak)
# ---------------------------------------------------------------------------

def test_retention_is_bounded_by_ttl_and_cap(params):
    """Delivered bookkeeping expires ``completed_ttl_s`` after first
    delivery and carried results are LRU-capped: a long-lived supervisor
    no longer accumulates one journal entry per request ever served."""
    sup = ServingSupervisor(params, CFG, slots=2, max_len=32,
                            completed_cap=4, completed_ttl_s=0.05)
    prompts, mnts = _mix(6, seed=11, max_new=2)
    for i, (p, m) in enumerate(zip(prompts, mnts)):
        assert sup.submit(f"r{i}", p, max_new_tokens=m,
                          greedy=True)["status"] == "queued"
    sup.run_until_idle()
    res = {r["request_id"]: r for r in sup.poll()}   # delivers all 6
    assert all(r["status"] == "done" for r in res.values())
    assert len(sup._journal) == 6 and len(sup._delivered) == 6
    time.sleep(0.06)
    sup.stats()                                      # prune tick
    assert not sup._journal and not sup._delivered and not sup._completed
    assert _counters().get("serve_retention_expired", 0) >= 6

    # Carried (finished-but-unpolled) results respect the LRU cap even
    # before any delivery: fill _completed past the cap via a restart.
    sup2 = ServingSupervisor(params, CFG, slots=2, max_len=32,
                             completed_cap=2, completed_ttl_s=900.0)
    prompts2, mnts2 = _mix(5, seed=12, max_new=2)
    for i, (p, m) in enumerate(zip(prompts2, mnts2)):
        sup2.submit(f"c{i}", p, max_new_tokens=m, greedy=True)
    sup2.run_until_idle()                 # all finished, none polled
    sup2._recover(RuntimeError("injected"))   # terminal results carried
    sup2.stats()
    assert len(sup2._completed) <= 2


# ---------------------------------------------------------------------------
# ISSUE 20: serving journal in the control-plane WAL + master rebuild
# ---------------------------------------------------------------------------

def test_supervisor_rebuild_from_wal_exactly_once(params, tmp_path):
    """Master crash with a WAL-journaled supervisor: non-terminal
    requests replay under their original rids on the rebuilt supervisor
    (greedy outputs bit-identical to the uninterrupted run); delivered
    rids are NOT replayed."""
    from tepdist_tpu.runtime import controlplane

    wal_dir = str(tmp_path / "wal")
    prompts, mnts = _mix(4, seed=13, max_new=3)

    # Fault-free reference outputs.
    ref = {}
    for i, (p, m) in enumerate(zip(prompts, mnts)):
        ref[f"r{i}"] = list(np.asarray(sample(
            params, p[None], CFG, max_new_tokens=m,
            greedy=True))[0, len(p):])

    wal = controlplane.ControlPlaneWAL(wal_dir)
    sup = ServingSupervisor(params, CFG, slots=2, max_len=32, wal=wal)
    for i, (p, m) in enumerate(zip(prompts, mnts)):
        assert sup.submit(f"r{i}", p, max_new_tokens=m,
                          greedy=True)["status"] == "queued"
    sup.run_until_idle()
    # Deliver ONLY r0: the other three are finished but undelivered
    # (or would still be decoding in a bigger run) at crash time.
    (r0,) = sup.poll(["r0"])
    assert r0["status"] == "done"
    wal.flush()
    wal.close()          # master process dies; supervisor state is gone

    state = controlplane.replay(wal_dir)
    pending = dict(state.pending_serving())
    assert "r0" not in pending           # delivered: terminal in the WAL
    assert set(pending) == {"r1", "r2", "r3"}

    wal2 = controlplane.ControlPlaneWAL(wal_dir)
    sup2 = ServingSupervisor.rebuild_from_wal(
        params, CFG, state, wal=wal2, slots=2, max_len=32)
    sup2.run_until_idle()
    res = {r["request_id"]: r for r in sup2.poll()}
    assert set(res) == {"r1", "r2", "r3"}     # r0 NOT re-run
    for rid in ("r1", "r2", "r3"):
        assert res[rid]["status"] == "done"
        assert list(res[rid]["tokens"]) == ref[rid], rid
    wal2.close()
